"""Ingest-plane benchmark: packed pipelined embed→upsert.

Measures the live-RAG product loop the serving benches don't: how fast a
mixed-length document stream becomes QUERYABLE.  Two numbers:

* ``docs_per_sec`` — tokenize → pack → encode → device-staged upsert
  through :class:`~pathway_tpu.xpacks.llm._ingest.IngestPipeline`
  (two-stage overlap, per-seq-bucket packing, device-resident
  embed→upsert);
* ``ingest_to_queryable_s`` — wall time from the LAST batch's submission
  to its documents answering a search, observed through the same
  :class:`FreshnessTracker` that feeds
  ``pathway_index_freshness_seconds``.

``--mock`` shrinks the model to a test-size config for CI smoke runs
(finishes in seconds on CPU).  One JSON line on stdout; every run also
appends to ``benchmarks/ingest_results.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _corpus(n_docs: int) -> list[str]:
    import numpy as np

    rng = np.random.default_rng(0)
    words = [f"w{i:04d}" for i in range(2000)]
    pattern = (24, 24, 56, 120)  # two short, one medium, one long
    return [
        " ".join(rng.choice(words, size=pattern[i % len(pattern)]))
        for i in range(n_docs)
    ]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mock", action="store_true", help="tiny config, CI smoke")
    ap.add_argument("--docs", type=int, default=None)
    ap.add_argument("--batch", type=int, default=64, help="docs per submit")
    ap.add_argument("--no-ledger", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.internals.flight_recorder import ingest_stats
    from pathway_tpu.internals.monitoring import get_freshness
    from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder
    from pathway_tpu.stdlib.indexing.retrievers import BruteForceKnnIndex
    from pathway_tpu.xpacks.llm._ingest import IngestPipeline

    if args.mock:
        cfg = EncoderConfig(
            vocab_size=2048, hidden_dim=32, num_layers=2, num_heads=4,
            mlp_dim=64, max_len=128, dtype=jnp.float32,
        )
        n_docs = args.docs or 256
    else:
        cfg = None  # MiniLM geometry (EncoderConfig defaults)
        n_docs = args.docs or 1024
    enc = SentenceEncoder(cfg=cfg, max_length=128)
    docs = _corpus(n_docs)
    keys = [f"doc{i}" for i in range(n_docs)]
    batch = max(args.batch, 1)
    import jax

    platform = jax.devices()[0].platform

    # ---- packed pipelined path: device-resident embed→upsert
    index = BruteForceKnnIndex(dim=enc.dim, capacity=2 * n_docs)
    stats_before = ingest_stats()
    fresh = get_freshness()
    scope = id(index)
    with IngestPipeline(enc, index) as pipe:
        # warmup packed shapes + the upsert scatter (re-upserted in the
        # timed loop below — upsert overwrites, so the index stays exact)
        pipe.submit(docs[:batch], keys=keys[:batch]).result()
        t0 = time.perf_counter()
        futs = []
        n_batches = 0
        for start in range(0, n_docs, batch):
            fresh.note_ingest(n_batches, scope=scope)
            futs.append(
                (
                    n_batches,
                    pipe.submit(
                        docs[start : start + batch],
                        keys=keys[start : start + batch],
                    ),
                )
            )
            n_batches += 1
        for _, f in futs:
            f.result()
        # queryable: the search forces the staged device scatter (and the
        # async encodes feeding it) to apply — timing stops only once the
        # documents actually ANSWER, not when the launches were queued
        q = enc.encode([docs[-1]])
        hit = index.search([(q[0], 1, None)])[0]
        elapsed = time.perf_counter() - t0
    packed_dps = n_docs / elapsed
    assert hit and hit[0][0] == keys[-1], "last ingested doc must be queryable"
    lag = fresh.note_indexed("ingest_bench", n_batches - 1, scope=scope)
    stats_after = ingest_stats()
    d_real = stats_after["real_tokens"] - stats_before["real_tokens"]
    d_padded = stats_after["padded_tokens"] - stats_before["padded_tokens"]

    out = {
        "metric": "ingest_throughput",
        "unit": "docs/sec",
        "platform": platform,
        "mock": bool(args.mock),
        "n_docs": n_docs,
        "batch": batch,
        "value": round(packed_dps, 1),
        "padding_efficiency": round(d_real / d_padded, 4) if d_padded else None,
        "ingest_to_queryable_s": round(lag, 4) if lag is not None else None,
        "pipeline_depth": pipe.depth,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(out), flush=True)
    if not args.no_ledger:
        ledger = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "ingest_results.jsonl"
        )
        with open(ledger, "a") as f:
            f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
