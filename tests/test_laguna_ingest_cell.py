"""The language-model embedder on the served path: a small
``VectorStoreServer`` that finds each document first by its own text, and the
benchmark's cell ``ingest-docs-laguna`` rehearsed end to end on the CPU at the
configuration file's tiny preset (``perfbench/run.py --rehearse``)."""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

import pytest

import pathway_tpu as pw

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

SEED = 2147483659


def _rehearse(trace: int) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "ingest-docs-laguna",
         "--seed", str(SEED), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_to_a_correct_result_with_its_freshness():
    line = _rehearse(trace=0)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 12
    assert line["device"]["platform"] == "cpu"  # a CPU run cannot pass for a chip run
    assert set(line["metrics"]) == {"fresh_p95_ms", "setup_s"}
    assert line["metrics"]["fresh_p95_ms"]["value"] > 0
    compared = line["compared"]
    for name in ("files_not_counted", "files_lost", "files_doubled", "own_text_not_first_once"):
        assert compared[name] == {"value": 0, "limit": 0}
    assert compared["answers_compared"]["value"] == 6
    assert compared["score_gap"]["value"] < compared["score_gap"]["limit"] < 1e30
    # what tells the stated precision from the one below it: the program's layers fed
    # the stated-precision reference's own input differ by the order of float32 sums
    assert compared["layer_gap"]["value"] < 1e-5 < compared["layer_gap"]["limit"]


def test_a_traced_rehearsal_reads_the_routing_counters_and_no_device_metric():
    line = _rehearse(trace=1)
    assert line["correct"] is True
    metrics = line["metrics"]
    # counters are counts on any platform; times, shares of a peak and
    # rooflines come from a chip alone, and their readers return nothing here
    assert 1.0 <= metrics["moe.tokens_per_expert"]["value"] <= 64 * 4 / 4
    assert metrics["moe.load_max_over_mean"]["value"] >= 1.0
    assert metrics["ingest.rows_per_tick"]["value"] > 0
    # documents that arrive together share a packed launch: never under one a launch
    assert metrics["embed.docs_per_launch"]["value"] >= 1.0
    for name in ("embed_moe.device_ms_per_launch", "moe.grouped_matmul_roofline",
                 "ingest_moe_step.mfu"):
        assert name not in metrics


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_a_small_vector_store_finds_each_document_first_by_its_own_text(tmp_path):
    from encoders import laguna as builder
    from generators import file_drop_docs

    from pathway_tpu.models.encoder import SentenceEncoder
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient, VectorStoreServer

    with open(os.path.join(BENCH, "configs", "vs-laguna-xs2-bf16-marcodoc.json")) as f:
        config = json.load(f)
    config.update({k: v for k, v in config["rehearse"].items() if not isinstance(v, dict)})
    encoder = SentenceEncoder(cfg=builder.model_config(config),
                              max_length=config["max_seq_length"],
                              params=builder.params(config, SEED))
    texts = [file_drop_docs.document(i, SEED, config["document_words"]) for i in range(6)]
    for i, text in enumerate(texts):
        (tmp_path / f"doc{i}.txt").write_text(text)
    docs = pw.io.fs.read(tmp_path, format="binary", mode="streaming", with_metadata=True,
                         refresh_interval=0.2)
    vs = VectorStoreServer(docs, embedder=SentenceTransformerEmbedder(encoder=encoder))
    port = _free_port()
    vs.run_server(host="127.0.0.1", port=port, threaded=True, with_cache=False)
    client = VectorStoreClient(host="127.0.0.1", port=port)
    deadline = time.monotonic() + 120
    while True:
        try:
            if len(client.query(texts[0], k=6)) == 6:
                break
        except Exception:  # noqa: BLE001 - the server is still starting
            pass
        assert time.monotonic() < deadline, "the documents never became queryable"
        time.sleep(0.2)
    for text in texts:  # across the window's edge: 5, 8, 9, 16, 32 and 64 tokens
        rows = client.query(text, k=3)
        assert rows[0]["text"] == text and rows[0]["dist"] < rows[1]["dist"]
        assert abs(rows[0]["dist"] + 1.0) < 1e-3  # cosine 1 with itself
    # a seventh document dropped while the server runs becomes queryable
    late = file_drop_docs.document(6, SEED, config["document_words"])
    (tmp_path / "doc6.txt").write_text(late)
    while client.query(late, k=1)[0]["text"] != late:
        assert time.monotonic() < deadline + 60, "the late document never became queryable"
        time.sleep(0.2)
