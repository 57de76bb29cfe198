"""Continuous-batching paged decode vs sequential dense-scan decode,
plus the ISSUE 16 shared-template / speculative phase.

The serving A/B for ISSUE 14: the pre-PR decode shape is ONE request at
a time through ``CausalLM.generate_ids`` (a private dense KV cache per
launch, no cross-request batching) — the paged path admits the whole
request set into one :class:`DecodeSession` and advances EVERY live
sequence one token per launch.  Measures:

* aggregate tokens/s for both paths over the same request set
  (acceptance: paged ≥ 2x sequential at batch ≥ 4 on this box's CPU
  reference path);
* inter-token latency p50/p99 of the paged stream (per-token callbacks)
  vs the dense path's effective per-token time (a client staring at a
  sequential queue waits for every request ahead of it).

The SPECULATIVE phase (ISSUE 16) replays the RAG serving shape — every
request carries the same template preamble with a short unique tail —
through three sessions over identical requests: the PR 14 baseline
(sharing off, spec off), prefix sharing on, and sharing + ``--spec-k``
drafting.  Banked as ``metric=decode_speculative`` with aggregate
tokens/s, inter-token p50/p99, prefix-hit rate and draft acceptance
rate (acceptance: ≥2x tokens/s at batch 8 over the PR 14 baseline).

Prints one JSON line per batch size and consolidated
``decode_continuous_batching`` + ``decode_speculative`` records; all
append to ``benchmarks/bench_results.jsonl``.

Run: ``JAX_PLATFORMS=cpu python benchmarks/decode_bench.py [geometry]
[--spec-k K]`` (geometry: "tiny" | "small" (default off-TPU) | "gpt2"
(default on TPU)).  ``DECODE_BENCH_PHASE`` = ``all`` (default) | ``cb``
| ``spec`` selects the phases.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

RESULTS = os.path.join(HERE, "bench_results.jsonl")


def _pctl(xs: list[float], q: float) -> float:
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def run(geometry: str | None = None) -> dict:
    import jax
    import numpy as np
    import jax.numpy as jnp

    from pathway_tpu.generation import DecodeSession
    from pathway_tpu.models.decoder import CausalLM, DecoderConfig

    platform = jax.devices()[0].platform
    if geometry is None:
        geometry = "gpt2" if platform == "tpu" else "small"
    if geometry == "tiny":
        cfg = DecoderConfig(
            vocab_size=512, hidden_dim=128, num_layers=4, num_heads=4,
            mlp_dim=512, max_len=512,
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16,
        )
    elif geometry == "small":
        # compute-dominated on CPU: per-step matmul work outweighs the
        # per-launch dispatch overhead, so the A/B measures the batching
        # lever (the thing paged decode exists for), not Python overhead.
        # max_len sized to the workload: off-TPU the functional KV pool
        # update pays a copy per tick (donation is TPU-only), so block
        # tables/pool are kept at the served horizon like an operator
        # would (PATHWAY_DECODE_POOL_TOKENS)
        cfg = DecoderConfig(
            vocab_size=4096, hidden_dim=512, num_layers=8, num_heads=8,
            mlp_dim=2048, max_len=128,
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16,
        )
    else:
        cfg = DecoderConfig(
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16
        )
    lm = CausalLM(cfg=cfg)
    rng = np.random.default_rng(0)
    max_new = int(os.environ.get("DECODE_BENCH_MAX_NEW", "32"))
    budget = float(os.environ.get("DECODE_BENCH_BUDGET_S", "420"))
    deadline = time.monotonic() + budget
    # mixed prompt lengths — the serving-shaped workload
    lens = (12, 24, 40, 18, 32, 48, 20, 28)

    def prompts_for(batch: int) -> list[list[int]]:
        return [
            rng.integers(1, cfg.vocab_size, size=lens[i % len(lens)]).tolist()
            for i in range(batch)
        ]

    rows = []
    consolidated: dict = {
        "metric": "decode_continuous_batching",
        "geometry": geometry,
        "platform": platform,
        "max_new_tokens": max_new,
    }
    for batch in (1, 4, 8):
        reqs = prompts_for(batch)

        # -- sequential dense-scan baseline: one request per launch --
        for p in reqs:
            lm.generate_ids([p], max_new_tokens=max_new)  # warm EVERY bucket
        t0 = time.perf_counter()
        for p in reqs:
            lm.generate_ids([p], max_new_tokens=max_new)
        dense_s = time.perf_counter() - t0
        dense_tps = batch * max_new / dense_s

        # -- paged continuous batching: one session, shared ticks --
        def paged_run(measure: bool) -> tuple[float, list[float]]:
            # pool sized to the admitted set (+25% slack): off-TPU every
            # tick copies the pool arrays, so an oversized pool taxes the
            # CPU A/B with memcpy the TPU path never pays
            need = sum(
                -(-(len(p) + max_new) // 16) for p in reqs
            )
            sess = DecodeSession(
                cfg, lm.params, auto=False,
                pool_tokens=16 * (need + max(2, need // 4)),
                block_size=16,
            )
            stamps: dict[int, list[float]] = {i: [] for i in range(batch)}
            handles = []
            t0 = time.perf_counter()
            for i, p in enumerate(reqs):
                handles.append(
                    sess.submit(
                        p, max_new_tokens=max_new,
                        stream_cb=(
                            (lambda tok, i=i: stamps[i].append(
                                time.perf_counter()
                            )) if measure else None
                        ),
                    )
                )
            sess.drain(timeout=600)
            elapsed = time.perf_counter() - t0
            for h in handles:
                assert len(h.result()) == max_new
            sess.close()
            gaps = []
            for ts in stamps.values():
                gaps.extend(b - a for a, b in zip(ts, ts[1:]))
            return elapsed, gaps

        paged_run(measure=False)  # warm every launch shape
        paged_s, gaps = paged_run(measure=True)
        paged_tps = batch * max_new / paged_s
        row = {
            "metric": "decode_cb_point",
            "platform": platform,
            "geometry": geometry,
            "batch": batch,
            "max_new_tokens": max_new,
            "dense_sequential_tokens_per_sec": round(dense_tps, 1),
            "paged_cb_tokens_per_sec": round(paged_tps, 1),
            "paged_vs_dense": round(paged_tps / dense_tps, 3),
            "inter_token_p50_ms": round(_pctl(gaps, 0.50) * 1e3, 2),
            "inter_token_p99_ms": round(_pctl(gaps, 0.99) * 1e3, 2),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
        consolidated[f"paged_vs_dense_b{batch}"] = row["paged_vs_dense"]
        consolidated[f"paged_tokens_per_sec_b{batch}"] = row[
            "paged_cb_tokens_per_sec"
        ]
        consolidated[f"inter_token_p99_ms_b{batch}"] = row[
            "inter_token_p99_ms"
        ]
        if time.monotonic() > deadline:
            break
    # acceptance: ≥2x aggregate tokens/s at batch ≥ 4
    ratios = [
        consolidated.get(f"paged_vs_dense_b{b}")
        for b in (4, 8)
        if consolidated.get(f"paged_vs_dense_b{b}") is not None
    ]
    consolidated["meets_acceptance"] = bool(ratios) and max(ratios) >= 2.0
    consolidated["rows"] = rows
    return consolidated


def run_speculative(
    geometry: str | None = None, spec_k: int = 4, batch: int = 8
) -> dict:
    """Shared-template phase + --spec-k A/B (ISSUE 16)."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    from pathway_tpu.generation import DecodeSession
    from pathway_tpu.generation.engine import generation_status
    from pathway_tpu.models.decoder import CausalLM, DecoderConfig

    platform = jax.devices()[0].platform
    if geometry is None:
        geometry = "gpt2" if platform == "tpu" else "small"
    if geometry == "tiny":
        cfg = DecoderConfig(
            vocab_size=512, hidden_dim=128, num_layers=4, num_heads=4,
            mlp_dim=512, max_len=512,
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16,
        )
    elif geometry == "small":
        cfg = DecoderConfig(
            vocab_size=4096, hidden_dim=512, num_layers=8, num_heads=8,
            mlp_dim=2048, max_len=128,
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16,
        )
    else:
        cfg = DecoderConfig(
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16
        )
    lm = CausalLM(cfg=cfg)
    rng = np.random.default_rng(7)
    max_new = min(16, int(os.environ.get("DECODE_BENCH_MAX_NEW", "16")))
    # the RAG serving shape: one long template preamble shared verbatim
    # by every request, plus a short unique per-request tail.  384
    # tokens ≈ a system prompt + one retrieved passage; geometries with
    # a short max_len (small: 128) get what fits
    tail_len = 8
    template_len = min(
        cfg.max_len - max_new - tail_len - 1, 24 * 16
    )
    template = rng.integers(1, cfg.vocab_size, size=template_len).tolist()
    reqs = [
        template + rng.integers(1, cfg.vocab_size, size=tail_len).tolist()
        for _ in range(batch)
    ]
    need = sum(-(-(len(p) + max_new) // 16) for p in reqs)
    pool_tokens = 16 * (need + max(2, need // 4))

    def one_run(share: bool, k: int, measure: bool):
        sess = DecodeSession(
            cfg, lm.params, auto=False,
            pool_tokens=pool_tokens, block_size=16,
            prefix_share=share, spec_k=k,
        )
        stamps: dict[int, list[float]] = {i: [] for i in range(batch)}
        t0 = time.perf_counter()
        handles = [
            sess.submit(
                reqs[0], max_new_tokens=max_new,
                stream_cb=(
                    (lambda tok: stamps[0].append(time.perf_counter()))
                    if measure else None
                ),
            )
        ]
        # template carrier prefills (and content-registers) first; the
        # followers then admit against a warm prefix index — the
        # sequential-then-concurrent shape real template traffic has
        sess.tick()
        for i in range(1, batch):
            handles.append(
                sess.submit(
                    reqs[i], max_new_tokens=max_new,
                    stream_cb=(
                        (lambda tok, i=i: stamps[i].append(
                            time.perf_counter()
                        )) if measure else None
                    ),
                )
            )
        sess.drain(timeout=600)
        elapsed = time.perf_counter() - t0
        for h in handles:
            assert len(h.result()) == max_new
        sess.close()
        gaps = []
        for ts in stamps.values():
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        return elapsed, gaps

    out = {
        "metric": "decode_speculative",
        "geometry": geometry,
        "platform": platform,
        "batch": batch,
        "max_new_tokens": max_new,
        "template_tokens": template_len,
        "tail_tokens": tail_len,
        "spec_k": spec_k,
    }
    variants = {
        "baseline": (False, 0),       # PR 14 semantics: no share, no spec
        "shared": (True, 0),          # prefix sharing only
        "shared_spec": (True, spec_k),  # sharing + drafting
    }
    for name, (share, k) in variants.items():
        one_run(share, k, measure=False)  # warm every launch shape
        before = dict(generation_status())
        elapsed, gaps = one_run(share, k, measure=True)
        after = dict(generation_status())
        tps = batch * max_new / elapsed
        out[f"{name}_tokens_per_sec"] = round(tps, 1)
        out[f"{name}_inter_token_p50_ms"] = round(
            _pctl(gaps, 0.50) * 1e3, 2
        )
        out[f"{name}_inter_token_p99_ms"] = round(
            _pctl(gaps, 0.99) * 1e3, 2
        )
        if share:
            hit = after["prefix_hit_blocks_total"] - before[
                "prefix_hit_blocks_total"
            ]
            cand = after["prefix_candidate_blocks_total"] - before[
                "prefix_candidate_blocks_total"
            ]
            out[f"{name}_prefix_hit_rate"] = round(
                hit / cand if cand else 0.0, 3
            )
        if k > 0:
            prop = after["draft_proposed_total"] - before[
                "draft_proposed_total"
            ]
            acc = after["draft_accepted_total"] - before[
                "draft_accepted_total"
            ]
            out["draft_acceptance_rate"] = round(
                acc / prop if prop else 0.0, 3
            )
    base = out["baseline_tokens_per_sec"]
    out["speedup_shared"] = round(out["shared_tokens_per_sec"] / base, 3)
    out["speedup_shared_spec"] = round(
        out["shared_spec_tokens_per_sec"] / base, 3
    )
    out["meets_acceptance"] = (
        max(out["speedup_shared"], out["speedup_shared_spec"]) >= 2.0
    )
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    argv = [a for a in sys.argv[1:]]
    spec_k = 4
    if "--spec-k" in argv:
        i = argv.index("--spec-k")
        spec_k = int(argv[i + 1])
        del argv[i:i + 2]
    geometry = argv[0] if argv else None
    phase = os.environ.get("DECODE_BENCH_PHASE", "all")
    outs = []
    if phase in ("all", "cb"):
        outs.append(run(geometry))
    if phase in ("all", "spec"):
        outs.append(run_speculative(geometry, spec_k=spec_k))
    with open(RESULTS, "a") as f:
        for out in outs:
            out["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
            line = json.dumps(out)
            print(line)
            f.write(line + "\n")
