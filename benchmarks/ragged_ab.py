"""Four-way attention-impl A/B on the chip: flax / fused / pallas / ragged.

ISSUE 9's MFU headline needs ONE apples-to-apples number set per healthy
TPU window: the same MiniLM-L6 geometry, the same mixed-length corpus
(two short / one medium / one long per 4 docs — bench.py's distribution),
measured compute-only (inputs device-resident, no per-dispatch host
transfer) across all four attention implementations.  The bucketed impls
(flax/fused/pallas) dispatch the packed per-bucket launch set; "ragged"
dispatches the packed-token layout (ops/ragged_attention.py) — one
launch per token-budget window with near-zero padding.

MFU is computed from USEFUL FLOPs (each doc's real length, not its
padded bucket), so a padding win shows up as MFU instead of being
normalized away.

Each variant prints + appends its own JSON line to
``benchmarks/ragged_ab_results.jsonl``, followed by a consolidated
``{"metric": "ragged_ab"}`` record with all four docs/s + MFU; every
record names the platform it ran on.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

RESULTS = os.path.join(HERE, "ragged_ab_results.jsonl")

_L, _H, _I = 6, 384, 1536
_MIXED_WORDS = (24, 24, 56, 120)  # bench.py's mixed-length distribution

_PEAK_BF16 = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def _bank(rec: dict, path: str = RESULTS) -> None:
    rec = dict(rec)
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    print(json.dumps(rec), flush=True)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")


def _useful_flops_per_doc(lengths) -> float:
    """Mean forward FLOPs per doc at each doc's REAL length."""
    ln = np.asarray(lengths, dtype=np.float64)
    per_doc = _L * (8 * ln * _H * _H + 4 * ln * ln * _H + 4 * ln * _H * _I)
    return float(per_doc.mean())


def _mfu(docs_per_sec: float, flops_per_doc: float, kind: str) -> float | None:
    for key, peak in _PEAK_BF16.items():
        if key in kind.lower():
            return round(docs_per_sec * flops_per_doc / peak, 4)
    return None


def main() -> int:
    deadline = time.monotonic() + float(
        os.environ.get("RAGGED_AB_BUDGET_S", "540")
    )
    seconds = float(os.environ.get("RAGGED_AB_WINDOW_S", "6"))
    dev = jax.devices()[0]
    platform = dev.platform
    kind = getattr(dev, "device_kind", str(dev))
    print(json.dumps({"device": platform, "kind": kind}), flush=True)

    from pathway_tpu.models.encoder import (
        EncoderConfig,
        SentenceEncoder,
        packed_prepare,
    )

    rng = np.random.default_rng(0)
    words = [f"w{i:04d}" for i in range(2000)]
    n_docs = int(os.environ.get("RAGGED_AB_DOCS", "1024"))
    docs = [
        " ".join(rng.choice(words, size=_MIXED_WORDS[i % len(_MIXED_WORDS)]))
        for i in range(n_docs)
    ]
    # bf16 on chip, f32 on the CPU smoke (bf16 is emulated there)
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32

    base = SentenceEncoder(
        max_length=128, cfg=EncoderConfig(dtype=dtype, attention_impl="flax")
    )
    ids, mask = base.tokenizer.encode_batch(docs, max_length=128)
    lengths = mask.sum(axis=1)
    flops_per_doc = _useful_flops_per_doc(lengths)
    vocab = base.cfg.vocab_size

    summary: dict = {
        "metric": "ragged_ab",
        "platform": platform,
        "device_kind": kind,
        "corpus": "mixed_seq32/64/128",
        "n_docs": n_docs,
        "useful_flops_per_doc": round(flops_per_doc),
    }

    for impl in ("flax", "fused", "pallas", "ragged"):
        if time.monotonic() > deadline - 3 * seconds:
            break
        try:
            enc = SentenceEncoder(
                max_length=128,
                cfg=EncoderConfig(dtype=dtype, attention_impl=impl),
            )
            enc.params = base.params  # pure kernel A/B: shared weights
            if impl == "ragged":
                prepared, _stats = enc.prepare_chunks(ids, mask)
                launches = [
                    (
                        [jax.device_put(a) for a in p.device_args()],
                        p.dense_s,
                    )
                    for p, _rows, _tokens in prepared
                ]

                def one_pass():
                    out = None
                    for args, dense_s in launches:
                        out = enc._apply_ragged(
                            enc.params, *args, dense_s=dense_s
                        )
                    return out
            else:
                prepared, _stats = packed_prepare(
                    ids, mask, 128, vocab_size=vocab
                )
                chunks = [
                    (
                        jax.device_put(jnp.asarray(i)),
                        jax.device_put(jnp.asarray(m)),
                    )
                    for i, m, _t, _r in prepared
                ]

                def one_pass():
                    out = None
                    for di, dm in chunks:
                        out = enc._apply(enc.params, di, dm)
                    return out

            one_pass().block_until_ready()  # compile + warm
            t0 = time.perf_counter()
            passes = 0
            out = None
            while time.perf_counter() - t0 < seconds:
                out = one_pass()
                passes += 1
                out.block_until_ready()  # bound the async queue per pass
            dt = time.perf_counter() - t0
            dps = passes * n_docs / dt
            rec = {
                "metric": "ragged_ab_variant",
                "platform": platform,
                "device_kind": kind,
                "attn_impl": impl,
                "docs_per_sec": round(dps, 1),
                "mfu": _mfu(dps, flops_per_doc, kind),
                "launches_per_pass": len(
                    launches if impl == "ragged" else chunks
                ),
            }
            _bank(rec)
            summary[f"{impl}_docs_per_sec"] = rec["docs_per_sec"]
            summary[f"{impl}_mfu"] = rec["mfu"]
        except Exception as exc:  # noqa: BLE001 — bank the failure, keep going
            _bank(
                {
                    "metric": "ragged_ab_variant",
                    "platform": platform,
                    "attn_impl": impl,
                    "error": repr(exc)[:300],
                }
            )
            summary[f"{impl}_error"] = repr(exc)[:200]

    if summary.get("fused_docs_per_sec") and summary.get("ragged_docs_per_sec"):
        summary["ragged_vs_fused"] = round(
            summary["ragged_docs_per_sec"] / summary["fused_docs_per_sec"], 3
        )
    _bank(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
