"""Exact brute-force vs LSH-bucketed KNN: latency + recall crossover.

VERDICT r1 weak #5: the "one MXU matmul beats a graph walk" stance
(STATUS.md §2.5) was asserted, not measured.  This script measures it:
for corpus sizes N, query the same corpus through

  * DeviceKnnIndex       — exact fused matmul + top-k (the design bet)
  * LshKnnIndex          — banded LSH candidate buckets + exact rescoring
                           of candidates (the reference's _knn_lsh.py shape)

and report p50 query-batch latency plus recall@10 of LSH against the exact
result.  Run on TPU for the real numbers; on CPU it still produces the
relative shape (recorded in benchmarks/KNN_CROSSOVER.md with platform).

Usage: python benchmarks/knn_crossover.py [N ...]   (default 10k 100k)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(
    n: int,
    dim: int = 384,
    n_queries: int = 64,
    k: int = 10,
    deadline: float | None = None,
) -> dict:
    """Measure exact and LSH search at corpus size ``n``.

    Emits the exact-index measurement as its own JSON line BEFORE starting
    the LSH side, so a run cut short still leaves every completed stage
    on its output.
    """
    import jax

    from pathway_tpu.ops.knn import DeviceKnnIndex
    from pathway_tpu.stdlib.indexing.retrievers import LshKnnIndex

    rng = np.random.default_rng(0)
    # clustered corpus (mixture of gaussians) — embedding-like structure;
    # i.i.d. gaussian vectors would starve LSH of any bucket locality and
    # overstate the exact index's quality advantage
    n_centers = max(n // 100, 10)
    centers = rng.standard_normal((n_centers, dim)).astype(np.float32)
    assign = rng.integers(0, n_centers, size=n)
    corpus = (centers[assign] + 0.3 * rng.standard_normal((n, dim))).astype(
        np.float32
    )
    q_assign = rng.integers(0, n_centers, size=n_queries)
    queries = (
        centers[q_assign] + 0.3 * rng.standard_normal((n_queries, dim))
    ).astype(np.float32)

    exact = DeviceKnnIndex(dim=dim, metric="cos", capacity=n)
    for i in range(n):
        exact.upsert(i, corpus[i])
    exact._apply_staged()

    def timed(fn, reps=3):
        fn()  # warmup/compile
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            times.append(time.perf_counter() - t0)
        return out, sorted(times)[len(times) // 2]

    exact_res, exact_t = timed(lambda: exact.search(queries, k))
    result = {
        "n": n,
        "platform": jax.devices()[0].platform,
        "exact_ms_per_query": round(exact_t / n_queries * 1000, 3),
    }
    print(json.dumps(result), flush=True)  # salvage point: exact banked

    # KNN_STAGES (comma list of int8,tiered,lsh; exact always runs — it
    # is every stage's oracle) selects the stages to run
    stages = {
        s.strip()
        for s in os.environ.get("KNN_STAGES", "int8,tiered,lsh").split(",")
        if s.strip()
    }

    if deadline is not None and time.monotonic() > deadline - 30:
        result["lsh_skipped"] = "child budget exhausted after exact stage"
        return result

    if "int8" in stages:
        # quantized exact index (ISSUE 11): same brute-force scan over
        # int8 codes + asymmetric-distance scoring + top-c rescore.  On
        # TPU the Pallas kernel streams 4x fewer HBM bytes; off-TPU the
        # XLA reference measures the relative shape only.
        quant = DeviceKnnIndex(
            dim=dim, metric="cos", capacity=n, index_dtype="int8"
        )
        quant.upsert_batch(list(range(n)), corpus)
        quant_res, quant_t = timed(lambda: quant.search(queries, k))
        hits = total = 0
        for qi in range(n_queries):
            truth = {key for key, _ in exact_res[qi]}
            hits += len(truth & {key for key, _ in quant_res[qi][:k]})
            total += len(truth)
        result["int8_ms_per_query"] = round(quant_t / n_queries * 1000, 3)
        result["int8_recall_at_10"] = round(hits / max(total, 1), 4)
        result["int8_vs_f32"] = (
            round(exact_t / quant_t, 3) if quant_t else None
        )
        result["int8_hbm_bytes_per_vector"] = round(quant.hbm_bytes() / n, 2)
        result["f32_hbm_bytes_per_vector"] = round(exact.hbm_bytes() / n, 2)
        print(json.dumps(result), flush=True)  # salvage point: int8 banked

        if deadline is not None and time.monotonic() > deadline - 30:
            result["lsh_skipped"] = "child budget exhausted after int8 stage"
            return result

    if "tiered" in stages:
        # tiered index (ISSUE 12): hot tier capped at 1/10 of the corpus
        # in HBM, the rest in routed host-RAM partitions — the
        # 10x-over-HBM acceptance shape.  Recall is measured vs the
        # full-HBM f32 oracle across hot-fraction sweeps; the headline
        # (1/10) row is banked to bench_results.jsonl (metric
        # knn_tiered).
        from pathway_tpu.tiering import TieredKnnIndex, tier_probe_default

        tiered_sweep = {}
        for frac in (0.05, 0.1, 0.25):
            hot_rows = max(int(n * frac), 1)
            t = TieredKnnIndex(
                dim=dim, hot_rows=hot_rows, metric="cos", capacity=n,
                n_partitions=64, migrate_batch=0,
            )
            t.upsert_batch(list(range(n)), corpus)
            t_res, t_t = timed(lambda t=t: t.search(queries, k))
            hits = total = 0
            for qi in range(n_queries):
                truth = {key for key, _ in exact_res[qi]}
                hits += len(truth & {key for key, _ in t_res[qi][:k]})
                total += len(truth)
            tiered_sweep[str(frac)] = {
                "hot_rows": hot_rows,
                "ms_per_query": round(t_t / n_queries * 1000, 3),
                "recall_at_10": round(hits / max(total, 1), 4),
                "probe_rows_per_query": round(
                    t.probe_rows_total / t.searches, 1
                ),
                "hbm_bytes": int(t.hbm_bytes()),
                "host_bytes": int(t.host_bytes()),
            }
        head = tiered_sweep["0.1"]
        result["tiered_ms_per_query"] = head["ms_per_query"]
        result["tiered_recall_at_10"] = head["recall_at_10"]
        result["tiered_hot_fraction_sweep"] = tiered_sweep
        result["tiered_vs_f32"] = (
            round(exact_t / (head["ms_per_query"] * n_queries / 1000), 3)
            if head["ms_per_query"]
            else None
        )
        print(json.dumps(result), flush=True)  # salvage point: tiered banked
        bank = {
            "metric": "knn_tiered",
            "platform": result["platform"],
            "n": n,
            "dim": dim,
            "hot_fraction": 0.1,
            **head,
            "probe_partitions": tier_probe_default(),
            "exact_ms_per_query": result["exact_ms_per_query"],
            "sweep": tiered_sweep,
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        with open(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "bench_results.jsonl"),
            "a",
        ) as fh:
            fh.write(json.dumps(bank) + "\n")

        if deadline is not None and time.monotonic() > deadline - 30:
            result["lsh_skipped"] = "child budget exhausted after tiered stage"
            return result

    if "lsh" in stages:
        lsh = LshKnnIndex(dim=dim, metric="cos", capacity=n)
        for i in range(n):
            lsh.add(i, corpus[i], None)
        lsh_res, lsh_t = timed(
            lambda: lsh.search([(q, k, None) for q in queries])
        )

        hits = total = 0
        for qi in range(n_queries):
            truth = {key for key, _ in exact_res[qi]}
            got = {key for key, _ in lsh_res[qi][:k]}  # noqa: E501
            hits += len(truth & got)
            total += len(truth)
        result["lsh_ms_per_query"] = round(lsh_t / n_queries * 1000, 3)
        result["lsh_recall_at_10"] = round(hits / max(total, 1), 4)
    return result


if __name__ == "__main__":
    sizes = [int(x) for x in sys.argv[1:]] or [10_000, 100_000]
    deadline = None
    if os.environ.get("KNN_BUDGET_S"):
        deadline = time.monotonic() + float(os.environ["KNN_BUDGET_S"])
    rows = []
    for n in sizes:
        if deadline is not None and time.monotonic() > deadline - 30:
            # don't start a size whose exact stage (corpus build + upload)
            # would run entirely past the parent's child timeout
            print(json.dumps({"n": n, "skipped": "budget exhausted"}), flush=True)
            continue
        row = run(n, deadline=deadline)
        rows.append(row)
        print(json.dumps(row), flush=True)
    # quantized crossover summary: the first corpus size at which the
    # int8 scan beats the f32 scan (None = not reached on this backend —
    # expected off-TPU, where the reference dequantizes through a
    # conversion XLA-CPU cannot vectorize)
    measured = [r for r in rows if r.get("int8_vs_f32") is not None]
    if measured:
        crossover = next(
            (r["n"] for r in measured if r["int8_vs_f32"] > 1.0), None
        )
        print(
            json.dumps(
                {
                    "metric": "knn_quant_crossover",
                    "platform": measured[-1]["platform"],
                    "crossover_n": crossover,
                    "int8_vs_f32_by_n": {
                        str(r["n"]): r["int8_vs_f32"] for r in measured
                    },
                    "int8_recall_by_n": {
                        str(r["n"]): r["int8_recall_at_10"] for r in measured
                    },
                }
            ),
            flush=True,
        )
