"""Streaming wordcount throughput harness.

reference: integration_tests/wordcount/base.py:205-240 — the reference's
only in-tree performance harness measures wordcount runtime over
n_threads × n_processes and verifies correctness; it commits no target
number.  Same contract here: measure rows/sec through the host engine
(select → groupby → count), verify the counts, print one JSON line.

Run: ``JAX_PLATFORMS=cpu python benchmarks/wordcount.py [n_rows]``
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pathway_tpu as pw  # noqa: E402


def run(n_rows: int = 200_000, n_words: int = 997) -> dict:
    rows = "\n".join(
        ["    data | __time__"]
        + [f"    word{i % n_words} | 2" for i in range(n_rows)]
    )
    t = pw.debug.table_from_markdown(rows)
    parts = t.select(w=t.data)
    counts = parts.groupby(parts.w).reduce(parts.w, c=pw.reducers.count())
    t0 = time.perf_counter()
    (out,) = pw.debug.materialize(counts)
    elapsed = time.perf_counter() - t0
    got = {row[0]: row[1] for row in out.current.values()}
    assert len(got) == n_words
    base, extra = divmod(n_rows, n_words)
    assert all(
        got[f"word{i}"] == base + (1 if i < extra else 0)
        for i in range(n_words)
    ), "wordcount incorrect"
    return {
        "metric": "wordcount_rows_per_sec",
        "value": round(n_rows / elapsed, 1),
        "unit": "rows/sec",
        "n_rows": n_rows,
        "threads": pw.internals.config.get_pathway_config().threads,
    }


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 200_000
    print(json.dumps(run(n)))
