"""The routed experts' grouped product (``pathway_tpu/ops/grouped_matmul.py``):
the Pallas kernel ``pw_grouped_matmul`` in interpret mode against
:func:`jax.lax.ragged_dot`, on the routed rows only (rows past the groups'
total are not defined); the fused epilogue against the float32 product's
``silu(gate) * up`` rounded as ``routed_experts`` rounds it; ``routed_experts``
through the kernel against its XLA twin on a packed batch with padding; and
which implementation runs where, counted a launch.

Widths are tiny, in the three embedders' ratios of expert width to hidden
(1,536, 768 and 512 to 2,048).  Tolerances: float32 operands sum the same
products in another order (1e-5 of the largest output); a bfloat16 output
may differ by one rounding (2^-8 of the value, plus 2^-8 of the largest
output for values that round near zero).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.internals import flight_recorder
from pathway_tpu.ops import grouped_matmul as GM
from pathway_tpu.ops import routed_experts as rx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")

HIDDEN = 64
#: the three configurations' expert widths over a hidden of 2,048, scaled to 64
WIDTHS = {"lfm2": 48, "joyai": 24, "laguna": 16}
F32_TOL = 1e-5
BF16_ROUNDING = 2.0 ** -8

#: name -> (group sizes, rows); the row tile is 128 (``GM.ROW_TILE``)
CASES = {
    "empty_groups": ([0, 37, 0, 90, 0, 0, 1, 0], 128),
    "a_group_spans_row_tiles": ([20, 200, 30, 6], 256),
    "every_row_in_one_group": ([0, 0, 320, 0], 320),
    "padding_pairs_past_the_total": ([50, 0, 101, 40], 384),
    "lfm2": ([70, 12, 0, 91, 55, 3], 256),
    "joyai": ([9, 0, 44, 33, 120, 7, 0, 30], 256),
    "laguna": ([0, 18, 26, 5, 60, 0, 77, 11], 256),
}


def _operands(sizes, m: int, k: int, n: int, dtype, seed: int = 0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)) / np.sqrt(k), dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _interpret(lhs, rhs, group_sizes, gated=False):
    return GM.grouped_matmul_pallas(lhs, rhs, group_sizes, gated=gated, interpret=True)


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_agrees_with_ragged_dot_on_the_routed_rows(case, gated):
    sizes, m = CASES[case]
    width = WIDTHS.get(case, 32)
    # the first product reads hidden and writes twice the expert width; the
    # second reads the expert width and writes hidden
    k, n = (HIDDEN, 2 * width) if gated else (width, HIDDEN)
    lhs, rhs, gs = _operands(sizes, m, k, n, jnp.float32, seed=len(case))
    got = np.asarray(_interpret(lhs, rhs, gs, gated))
    want = np.asarray(GM.grouped_matmul_xla(lhs, rhs, gs, gated=gated))
    routed = sum(sizes)
    assert got.shape == want.shape == (m, n // 2 if gated else n)
    assert got.dtype == want.dtype == np.float32
    np.testing.assert_allclose(got[:routed], want[:routed], rtol=0,
                               atol=F32_TOL * np.abs(want[:routed]).max())


def test_the_fused_epilogue_rounds_once_as_routed_experts_did():
    """bfloat16 operands: the kernel's ``silu(gate) * up`` against that of the
    float32 product :func:`jax.lax.ragged_dot` gives, rounded to bfloat16."""
    sizes, m = CASES["lfm2"]
    f = WIDTHS["lfm2"]
    lhs, rhs, gs = _operands(sizes, m, HIDDEN, 2 * f, jnp.bfloat16, seed=3)
    h = jax.lax.ragged_dot(lhs, rhs, gs, preferred_element_type=jnp.float32)
    want = np.asarray((jax.nn.silu(h[:, :f]) * h[:, f:]).astype(jnp.bfloat16), np.float32)
    got = _interpret(lhs, rhs, gs, gated=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (m, f)
    routed = sum(sizes)
    got, want = np.asarray(got, np.float32)[:routed], want[:routed]
    np.testing.assert_allclose(
        got, want, rtol=BF16_ROUNDING, atol=BF16_ROUNDING * np.abs(want).max())


def test_the_tiling_reads_each_expert_whole_where_it_fits():
    # the three cells' products: the whole width, so the rows are read once
    # a product
    for f in (1536, 768, 512):
        assert GM.tiling(2048, 2 * f, gated=True) == f
        assert GM.tiling(f, 2048, gated=False) == 2048
    # a matrix too wide for fast memory: the largest divisor in lanes that fits
    tn = GM.tiling(8192, 2 * 4096, gated=True)
    assert tn == 512 and 2 * 2 * 8192 * tn * 2 <= 40 << 20
    assert GM.tiling(8192, 2 * 4224, gated=True) == 384  # 4,224 = 33 lanes


# -- routed_experts through the kernel --------------------------------------------


def _through_the_kernel(monkeypatch):
    monkeypatch.setattr(GM, "grouped_matmul_impl", lambda: "pallas")
    monkeypatch.setattr(GM, "grouped_matmul_pallas",
                        functools.partial(GM.grouped_matmul_pallas, interpret=True))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_routed_experts_through_the_kernel_match_the_xla_twin(monkeypatch, dtype):
    """A packed batch of 96 token slots, 70 of them text: eight experts of
    48 over a hidden of 64, top 3; the padding pairs lie past the total."""
    t, real, experts, f, top_k = 96, 70, 8, WIDTHS["lfm2"], 3
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(t, HIDDEN)), dtype)
    valid = jnp.arange(t) < real
    router = jnp.asarray(rng.normal(size=(HIDDEN, experts)), jnp.float32)
    w_gate_up = jnp.asarray(rng.normal(size=(experts, HIDDEN, 2 * f)) / 8, dtype)
    w_down = jnp.asarray(rng.normal(size=(experts, f, HIDDEN)) / 7, dtype)
    run = lambda: rx.routed_experts(x, valid, router, w_gate_up, w_down, top_k=top_k,
                                    scaling=1.0)
    want, want_sizes = run()
    _through_the_kernel(monkeypatch)
    got, got_sizes = run()
    np.testing.assert_array_equal(np.asarray(got_sizes), np.asarray(want_sizes))
    assert int(np.asarray(got_sizes).sum()) == real * top_k
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(got[real:] == 0) and np.all(want[real:] == 0)
    tol = F32_TOL if dtype == jnp.float32 else 2 * BF16_ROUNDING
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * np.abs(want).max())


def test_the_kernel_runs_on_a_tpu_and_the_twin_elsewhere(monkeypatch):
    calls = []
    monkeypatch.setattr(GM, "grouped_matmul_pallas",
                        lambda *a, **kw: calls.append("pallas") or GM.grouped_matmul_xla(*a, **kw))
    monkeypatch.setattr(GM, "grouped_matmul_xla",
                        functools.partial(lambda f, *a, **kw: calls.append("xla") or f(*a, **kw),
                                          GM.grouped_matmul_xla))
    lhs, rhs, gs = _operands([3, 5], 8, 16, 32, jnp.float32)
    for backend, impl in (("tpu", "pallas"), ("cpu", "xla"), ("gpu", "xla")):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert GM.grouped_matmul_impl() == impl
        calls.clear()
        GM.grouped_matmul(lhs, rhs, gs, gated=True)
        assert calls[0] == impl, backend


def _tiny_laguna():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from encoders import laguna as builder

    with open(os.path.join(BENCH, "configs", "vs-laguna-xs2-bf16-marcodoc.json")) as f:
        published = json.load(f)
    tiny = dict(published, **published["rehearse"])
    return tiny, builder


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_grouped_launches_are_counted_a_launch_by_the_traced_implementation(monkeypatch, impl):
    """Three launches of one program (one trace): three counts under the
    implementation the program was traced with, none under the other."""
    from pathway_tpu.models import causal_moe_embedder as cme
    from pathway_tpu.models.encoder import SentenceEncoder

    if impl == "pallas":
        _through_the_kernel(monkeypatch)
        monkeypatch.setattr(cme, "grouped_matmul_impl", GM.grouped_matmul_impl)
    tiny, builder = _tiny_laguna()
    cfg = dataclasses.replace(builder.model_config(tiny), token_buckets=(32,),
                              dtype=jnp.float32)
    enc = SentenceEncoder(cfg=cfg, max_length=tiny["max_seq_length"],
                          params=builder.params(tiny, 2147483659))
    texts = [" ".join(f"w{i}" for i in range(n)) for n in (5, 9)]
    enc.encode(texts)  # the first: warms the bucket on padding, then the texts
    before = flight_recorder.moe_grouped_stats()
    compiles = flight_recorder.compile_stats().get("encoder.forward_ragged", 0)
    for _ in range(3):
        enc.encode(texts)
    after = flight_recorder.moe_grouped_stats()
    assert flight_recorder.compile_stats().get("encoder.forward_ragged", 0) == compiles
    other = "xla" if impl == "pallas" else "pallas"
    assert after.get(impl, 0) - before.get(impl, 0) == 3
    assert after.get(other, 0) == before.get(other, 0)
    assert any(line == f'pathway_moe_grouped_launches_total{{impl="{impl}"}} {after[impl]}'
               for line in flight_recorder.observability_metrics_lines())
