"""Compute-only encoder throughput + attention-impl A/B on the chip.

`bench.py` meters the realistic dispatch path: host tokenization done,
u16 ids shipped per batch, so host-to-device transfer is inside its
number.  This probe answers two different questions with device-resident
inputs (no per-dispatch transfer):

  1. what does the chip itself sustain on the MiniLM-L6 geometry
     (the honest "A100-parity" comparison — published A100 figures are
     likewise measured with data resident); and
  2. where does the pallas flash-attention kernel overtake XLA's fused
     ``jax.nn.dot_product_attention`` as sequence length grows.

Each result prints as its own JSON line and is
appended to ``benchmarks/attn_probe_results.jsonl``.

Reference counterpart: `xpacks/llm/embedders.py:270` (torch
SentenceTransformer, the compute path the north star replaces).
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

RESULTS = os.path.join(HERE, "attn_probe_results.jsonl")


def _bank(rec: dict) -> None:
    rec = dict(rec)
    rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    print(json.dumps(rec), flush=True)
    with open(RESULTS, "a") as f:
        f.write(json.dumps(rec) + "\n")


def main() -> int:
    deadline = time.monotonic() + float(
        os.environ.get("ATTN_PROBE_BUDGET_S", "540")
    )
    dev = jax.devices()[0]
    platform = dev.platform
    kind = getattr(dev, "device_kind", str(dev))
    print(json.dumps({"device": platform, "kind": kind}), flush=True)

    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder

    rng = np.random.default_rng(0)

    def compute_only(enc, batch, seq, label, seconds=6.0):
        ids = rng.integers(1, 1000, size=(batch, seq)).astype(np.int32)
        mask = np.ones((batch, seq), dtype=np.int32)
        di, dm = jax.device_put(ids), jax.device_put(mask)
        enc._apply(enc.params, di, dm).block_until_ready()  # compile
        t0 = time.perf_counter()
        n = 0
        out = None
        while time.perf_counter() - t0 < seconds:
            # sync every 32 dispatches: async dispatch would otherwise
            # enqueue unbounded device work the trailing drain pays for
            for _ in range(32):
                out = enc._apply(enc.params, di, dm)
                n += batch
            out.block_until_ready()
        dt = time.perf_counter() - t0
        _bank(
            {
                "metric": "encoder_compute_only",
                "platform": platform,
                "device_kind": kind,
                "label": label,
                "batch": batch,
                "seq": seq,
                "docs_per_sec": round(n / dt, 1),
                "tokens_per_sec": round(n * seq / dt, 1),
            }
        )

    enc128 = SentenceEncoder(
        max_length=128, cfg=EncoderConfig(attention_impl="fused")
    )
    for b in (256, 1024, 2048):
        if time.monotonic() > deadline - 30:
            return 0
        compute_only(enc128, b, 128, "fused_seq128")

    if time.monotonic() > deadline - 60:
        return 0
    enc512f = SentenceEncoder(
        max_length=512, cfg=EncoderConfig(attention_impl="fused")
    )
    compute_only(enc512f, 256, 512, "fused_seq512")
    if time.monotonic() > deadline - 60:
        return 0
    try:
        enc512p = SentenceEncoder(
            max_length=512, cfg=EncoderConfig(attention_impl="pallas")
        )
        enc512p.params = enc512f.params  # same weights: pure kernel A/B
        compute_only(enc512p, 256, 512, "pallas_seq512")
    except Exception as exc:  # noqa: BLE001 - bank the failure, don't die
        _bank({"metric": "encoder_compute_only", "label": "pallas_seq512",
               "platform": platform, "error": repr(exc)[:300]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
