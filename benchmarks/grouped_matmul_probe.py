"""The routed experts' two grouped products of one layer, alone on the chip:
the Pallas kernel ``pw_grouped_matmul`` (gated, then plain) against XLA's
``ragged_dot`` with the epilogue between them, at the three embedders' layer
shapes (hidden 2,048; experts of 1,536 / 768 / 512, 64 / 256 / 256 of them,
top 4 / 8 / 8) on a packed launch of ``bucket`` tokens of which ``real`` are
text (the rest are padding pairs, routed nowhere).

Routing is drawn from the seed: each token's top-k of Gumbel noise plus a
per-expert popularity of deviation ``--skew`` (the cells' fullest expert is
2.7 to 13 times the mean).  Inputs are resident on the device; a time is the
median over five rounds of ``--iters`` calls launched back to back, waited
for at the end.  The two sides' outputs are compared on the routed rows.

    python benchmarks/grouped_matmul_probe.py [--shape lfm2]

One JSON line per shape; with no TPU the kernel runs in interpret mode at
tiny widths (a check of the script, not a time).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from pathway_tpu.ops import grouped_matmul as GM  # noqa: E402

#: name -> (hidden, expert width, experts, top k, bucket tokens, real tokens)
SHAPES = {
    "lfm2": (2048, 1536, 64, 4, 4096, 3200),
    "joyai": (2048, 768, 256, 8, 4096, 3200),
    "laguna": (2048, 512, 256, 8, 4096, 3200),
}
TINY = {"tiny": (64, 128, 8, 2, 256, 200)}


def _inputs(key, d, f, e, k, bucket, real, skew):
    kx, kg, kd, kr, kp = jax.random.split(key, 5)
    popular = skew * jax.random.normal(kp, (e,))
    noise = jax.random.gumbel(kr, (real, e))
    _, experts = jax.lax.top_k(noise + popular, k)
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    x = jax.random.normal(kx, (bucket, d), jnp.float32).astype(jnp.bfloat16)
    rows = jnp.concatenate([x[order // k], jnp.zeros(((bucket - real) * k, d), x.dtype)])
    w_gu = (jax.random.normal(kg, (e, d, 2 * f), jnp.float32) / d ** 0.5).astype(jnp.bfloat16)
    w_down = (jax.random.normal(kd, (e, f, d), jnp.float32) / f ** 0.5).astype(jnp.bfloat16)
    return rows, w_gu, w_down, sizes


def _layer(gated, plain):
    def run(rows, w_gu, w_down, sizes):
        act = gated(rows, w_gu, sizes)
        return plain(act, w_down, sizes)
    return jax.jit(run)


def _time(fn, args, iters):
    jax.block_until_ready(fn(*args))
    rounds = []
    for _ in range(5):
        t = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t) / iters * 1e3)
    return statistics.median(rounds)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--seed", type=int, default=2147483001)
    ap.add_argument("--skew", type=float, default=0.5)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    shapes = SHAPES if on_tpu else TINY
    names = args.shape or list(shapes)
    pallas = lambda a, b, g, gated=False: GM.grouped_matmul_pallas(
        a, b, g, gated=gated, interpret=not on_tpu)
    xla = _layer(lambda a, b, g: GM.grouped_matmul_xla(a, b, g, gated=True),
                 GM.grouped_matmul_xla)
    for name in names:
        d, f, e, k, bucket, real = shapes[name]
        ins = _inputs(jax.random.PRNGKey(args.seed), d, f, e, k, bucket, real, args.skew)
        sizes = ins[3]
        routed = real * k
        rec = {"shape": name, "device": jax.devices()[0].device_kind, "hidden": d,
               "expert_width": f, "experts": e, "top_k": k, "bucket": bucket, "real": real,
               "touched": int(jnp.sum(sizes > 0)), "fullest": int(jnp.max(sizes)),
               "row_tile": GM.ROW_TILE,
               "tn": [GM.tiling(d, 2 * f, gated=True), GM.tiling(f, d, gated=False)]}
        ref = xla(*ins)[:routed]
        rec["xla_ms"] = _time(xla, ins, args.iters)
        fn = _layer(lambda a, b, g: pallas(a, b, g, gated=True), pallas)
        got = fn(*ins)[:routed]
        rec["pallas_ms"] = _time(fn, ins, args.iters)
        rec["pallas_max_rel_err"] = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
        rec["speedup"] = rec["xla_ms"] / rec["pallas_ms"]
        # the least time of the two products: the touched experts once and
        # each routed row in and out, at the chip's 819 GB/s
        least_bytes = 2 * (rec["touched"] * 3 * d * f + routed * (2 * d + 3 * f))
        rec["least_ms_at_819GBps"] = least_bytes / 819e9 * 1e3
        print(json.dumps(rec), flush=True)
        del ins, ref


if __name__ == "__main__":
    main()
