"""chip_smoke.py off the chip: its phases at a tiny geometry on the CPU
with the kernels in interpret mode, its refusal to run without a TPU,
and the compile-cache policy it reports."""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# the tiny encoder/decoder shapes other test modules build too, so their
# op-by-op flax inits hit this process's compile cache
TINY = chip_smoke.Geometry(
    name="tiny", n_docs=32, doc_words=(3, 3, 6, 12),
    encoder=dict(
        vocab_size=512, hidden_dim=32, num_layers=1, num_heads=4, mlp_dim=64,
        max_len=64,
    ),
    decoder=dict(
        vocab_size=211, hidden_dim=64, num_layers=2, num_heads=4, mlp_dim=128,
        max_len=128,
    ),
    max_new_tokens=4, streams=2, kernel_rows=64, kernel_tokens=128,
    kernel_pool_blocks=32,
)


def test_phases_at_tiny_geometry_with_interpreted_kernels(monkeypatch):
    # force every kernel choice the chip makes by itself, so the served
    # path runs the Pallas bodies (interpreted) end to end
    monkeypatch.setenv("PATHWAY_SERVING_KERNEL", "pallas")
    monkeypatch.setenv("PATHWAY_DECODE_KERNEL", "pallas")
    # the smoke reads process-wide counters and expects a process of its own;
    # under xdist this worker may have run the fault-containment tests first
    from pathway_tpu.generation import engine

    monkeypatch.setattr(engine, "_COUNTERS", dict.fromkeys(engine._COUNTERS, 0))
    smoke = chip_smoke.Smoke(TINY, require_tpu=False)
    summary = smoke.run()
    assert summary["ok"] is True and summary["claim"] is None
    assert summary["device"] == {
        "platform": "cpu",
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
    # the last stdout line: exactly what the driver parses, nothing more
    assert json.loads(chip_smoke.verdict_line(summary)) == {
        "ok": True, "device": summary["device"],
    }
    assert set(summary["phases"]) == {
        "device", "ingest_retrieve", "streamed_answers", "nothing_stood_in",
        "kernels", "profiler",
    }
    assert all(p["ok"] for p in summary["phases"].values())
    assert summary["impl"]["serving_topk"] == "pallas"
    assert summary["impl"]["decode_step"] == "pallas"
    kernels = summary["phases"]["kernels"]
    assert len([k for k in kernels if isinstance(kernels[k], dict)]) == 11
    assert summary["phases"]["nothing_stood_in"]["launch_totals"]["fused"] > 0
    # JAX_PLATFORMS=cpu persists no compiles (see test_compile_cache_policy)
    assert summary["compile_cache"]["dir"] is None


def test_script_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "JAX found platform 'cpu'" in proc.stderr
    # no result line on stdout
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_compile_cache_policy(monkeypatch, tmp_path):
    from pathway_tpu.utils import compile_cache as cc

    assert cc.CHECKOUT_CACHE_DIR == os.path.join(REPO, ".jax_compile_cache")
    before = jax.config.jax_compilation_cache_dir
    # placed from outside: the code sets no directory at all
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cc.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # not placed, pinned to the CPU (this test run): nothing persists
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    # not placed, an accelerator may be found: the fixed in-checkout path
    monkeypatch.setattr(cc, "CHECKOUT_CACHE_DIR", str(tmp_path / "fixed"))
    platforms = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", None)
        assert cc.enable_compile_cache() == str(tmp_path / "fixed")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
        assert os.path.isdir(tmp_path / "fixed")
    finally:
        jax.config.update("jax_platforms", platforms)
        jax.config.update("jax_compilation_cache_dir", before)
    assert "PATHWAY_JAX_CACHE_DIR" not in open(cc.__file__).read()
