"""Streaming ingest + query benchmark (BASELINE config #2).

reference: ``VectorStoreServer`` streaming ingest with incremental index
upsert/delete (BASELINE.md configs:2).  Measures, on one process:

- ingest throughput: docs/sec from file drop → parse → embed (mock) →
  index visibility (the full dataflow, not just the index op);
- query latency percentiles served WHILE ingest churns.

Run: ``JAX_PLATFORMS=cpu python benchmarks/streaming_ingest.py [n_docs]``
"""

from __future__ import annotations

import json
import os
import pathlib
import socket
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pathway_tpu as pw  # noqa: E402
from pathway_tpu.xpacks.llm import mocks  # noqa: E402
from pathway_tpu.xpacks.llm.vector_store import (  # noqa: E402
    VectorStoreClient,
    VectorStoreServer,
)


def run(n_docs: int = 400) -> dict:
    tmp = tempfile.mkdtemp(prefix="ingest_bench_")
    docs_dir = pathlib.Path(tmp)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    docs = pw.io.fs.read(
        str(docs_dir), format="binary", mode="streaming",
        with_metadata=True, refresh_interval=0.05,
    )
    server = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=64))
    server.run_server(host="127.0.0.1", port=port, threaded=True)
    client = VectorStoreClient(host="127.0.0.1", port=port)

    # seed one doc and wait for serving to come up
    (docs_dir / "seed.txt").write_text("seed document zero")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        try:
            if client.query("seed", k=1):
                break
        except Exception:
            pass
        time.sleep(0.2)

    # drop the corpus while a query thread hammers the serving path
    # (FakeEmbedder hashes exact text, so queries use exact doc texts —
    # the point here is serving-path latency under churn, not recall)
    latencies: list[float] = []
    served_nonempty = [0]
    stop = threading.Event()

    def doc_text(i: int) -> str:
        return f"document number {i} about topic {i % 17}"

    def query_loop():
        i = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                res = client.query(doc_text(i % n_docs), k=3)
                latencies.append(time.perf_counter() - t0)
                served_nonempty[0] += bool(res)
            except Exception:
                pass
            i += 1
            time.sleep(0.005)

    qt = threading.Thread(target=query_loop, daemon=True)
    qt.start()

    # sustained churn: docs arrive in waves so the serving path is
    # measured against continuous incremental upserts, not one burst
    t0 = time.perf_counter()
    wave = max(n_docs // 20, 1)
    for start in range(0, n_docs, wave):
        for i in range(start, min(start + wave, n_docs)):
            (docs_dir / f"doc{i:05d}.txt").write_text(doc_text(i))
        time.sleep(0.15)
    # ingest complete when every file is visible in the index stats
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            stats = client.get_vectorstore_statistics()
            if stats.get("file_count", 0) >= n_docs + 1:
                break
        except Exception:
            pass
        time.sleep(0.05)
    ingest_s = time.perf_counter() - t0
    stop.set()
    qt.join(timeout=5)

    latencies.sort()
    p50 = latencies[len(latencies) // 2] if latencies else None
    p95 = latencies[int(len(latencies) * 0.95)] if latencies else None
    return {
        "metric": "streaming_ingest_docs_per_sec",
        "value": round(n_docs / ingest_s, 1),
        "unit": "docs/sec",
        "n_docs": n_docs,
        "query_p50_ms": round(p50 * 1000, 2) if p50 else None,
        "query_p95_ms": round(p95 * 1000, 2) if p95 else None,
        "queries_served_during_ingest": len(latencies),
    }


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 400
    print(json.dumps(run(n)))
    os._exit(0)
