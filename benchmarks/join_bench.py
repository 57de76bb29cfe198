"""Streaming hash-join throughput harness.

Companion to wordcount.py for the stateful-operator hot path: build one
side, stream the other through an inner equi-join, verify row counts.
reference: the differential ``join_core`` probe loop is the hot path in
src/engine/dataflow.rs; the reference commits no target number, so the
contract here is the same as wordcount — measure rows/sec, verify, print
one JSON line.

Run: ``JAX_PLATFORMS=cpu python benchmarks/join_bench.py [n_rows]``
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pathway_tpu as pw  # noqa: E402


def run(n_rows: int = 200_000, n_keys: int = 10_000) -> dict:
    right_rows = "\n".join(
        ["    rk | label | __time__"]
        + [f"    key{i} | lab{i} | 2" for i in range(n_keys)]
    )
    left_rows = "\n".join(
        ["    lk | v | __time__"]
        + [f"    key{i % n_keys} | {i} | 4" for i in range(n_rows)]
    )
    right = pw.debug.table_from_markdown(right_rows)
    left = pw.debug.table_from_markdown(left_rows)
    joined = left.join(right, left.lk == right.rk).select(
        left.v, right.label
    )
    t0 = time.perf_counter()
    (out,) = pw.debug.materialize(joined)
    elapsed = time.perf_counter() - t0
    assert len(out.current) == n_rows, len(out.current)
    return {
        "metric": "join_probe_rows_per_sec",
        "value": round(n_rows / elapsed, 1),
        "unit": "rows/sec",
        "n_rows": n_rows,
        "n_keys": n_keys,
    }


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    print(json.dumps(run(n)))
