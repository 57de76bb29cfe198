"""The document store with a language-model embedder: ``vector_store.py``'s
deployment with the encoder of the configuration's ``embedder`` group
(``encoders/<builder>.py``: the program's config object and the weights,
made on the device from the seed layer by layer) and documents of the
configuration's ``document_words`` cycle in the watched directory.

Everything after the encoder is the path MiniLM takes:
``SentenceTransformerEmbedder(encoder=...)`` -> ``VectorStoreServer``, the
tick runtime, ``bucketed_dispatch``, ``ExternalIndexNode.flush``, the staged
upsert.  No ``PATHWAY_*`` variable is set: sequence buckets, the weights'
dtype and the absent host twin follow from the encoder's own config.

Repeated from ``vector_store.Deployment`` because it cannot be inherited:
``__init__`` (it builds the BERT encoder in its first lines) and
``after_window`` (it asks with ``textgen.passage``).
"""

from __future__ import annotations

import importlib
import os
import time

from generators import file_drop_docs
from servers import vector_store


class Deployment(vector_store.Deployment):
    def __init__(self, config: dict, seed: int, workdir: str, log):
        import pathway_tpu as pw
        from pathway_tpu.models.encoder import SentenceEncoder
        from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
        from pathway_tpu.stdlib.indexing.lowering import live_index_node
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
        from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

        self.config, self.seed, self.log = config, seed, log
        t0 = time.monotonic()
        self.builder = importlib.import_module("encoders." + config["embedder"]["builder"])
        # the encoder is handed the harness's weights: a model of gigabytes
        # is never drawn twice, and the reference makes each layer again
        self.encoder = SentenceEncoder(
            cfg=self.builder.model_config(config),
            max_length=int(config["max_seq_length"]),
            params=self.builder.params(config, seed),
        )
        log(f"encoder built in {time.monotonic() - t0:.1f}s")

        self.corpus_dir = os.path.join(workdir, "corpus")
        os.makedirs(self.corpus_dir)
        self.n_passages = int(config["ingested_passages"])
        for i in range(self.n_passages):
            self.write_passage(i)
        embedder = SentenceTransformerEmbedder(encoder=self.encoder)
        self.factory = BruteForceKnnFactory(
            embedder=embedder, reserved_space=int(config["index"]["capacity"]),
        )
        table = pw.io.fs.read(
            self.corpus_dir, format="binary", mode="streaming",
            with_metadata=True, refresh_interval=0.2,
        )
        self.vs = VectorStoreServer(table, embedder=embedder, index_factory=self.factory)
        self.port = vector_store.free_port()
        self.urls = {"retrieve": f"http://127.0.0.1:{self.port}/v1/retrieve",
                     "corpus_dir": "file://" + self.corpus_dir}
        self.build_and_run()
        t1 = time.monotonic()
        self.node = vector_store.poll(
            "the live index", lambda: live_index_node(self.factory), 300.0)
        vector_store.poll(f"{self.n_passages} ingested documents",
                          lambda: len(self.node.doc_payload) >= self.n_passages, 900.0)
        log(f"{self.n_passages} documents served-ingested in {time.monotonic() - t1:.1f}s")
        self.inner = self.node.index.index  # DeviceKnnIndex under the retriever
        t2 = time.monotonic()
        self.prefill()
        log(f"{config['rows']} rows prefilled in {time.monotonic() - t2:.1f}s; "
            f"capacity {self.inner.capacity}, index dtype {self.inner.index_dtype}")
        if self.inner.capacity != int(config["index"]["capacity"]):
            raise RuntimeError(f"index capacity {self.inner.capacity} is not the "
                               f"configuration's {config['index']['capacity']}")

    def document(self, i: int) -> str:
        return file_drop_docs.document(i, self.seed, self.config["document_words"])

    def write_passage(self, i: int) -> str:
        """Document ``i`` as a file of the watched directory, renamed in."""
        path = os.path.join(self.corpus_dir, f"passage_{i:07d}.txt")
        tmp = os.path.join(os.path.dirname(self.corpus_dir), f".tmp_{i}")
        with open(tmp, "w") as f:
            f.write(self.document(i))
        os.rename(tmp, path)
        return path

    def warm_up(self, traffic: dict) -> None:
        """Served ingest during set-up has driven every shape a dropped file
        drives: one document a launch, each sequence bucket of the cycle.
        The own-text queries that follow the window take the same programs
        (one query a tick)."""
        if "min_words" in traffic:
            raise ValueError("this deployment serves ingest mixes only")

    def counters(self) -> dict:
        from pathway_tpu.internals.flight_recorder import moe_stats

        out = super().counters()
        for name, value in moe_stats().items():
            out[f"moe.{name}"] = value
        return out

    def after_window(self, traffic: dict, records: list[dict], seed: int) -> None:
        """``vector_store.Deployment.after_window`` with the document's own
        text as the query: a sample drawn from the seed, the longest
        document that was dropped always among them."""
        if not traffic.get("watch_index"):
            return
        import http.client
        import json
        import random
        import zlib

        words = self.config["document_words"]
        picks = sorted((r for r in records if not r["failed"]), key=lambda r: r["i"])
        random.Random(f"{seed}:check").shuffle(picks)
        if picks:  # the longest document first (the first of them as shuffled)
            longest = max(picks, key=lambda r: int(words[r["answer"]["passage"] % len(words)]))
            picks = [longest] + [r for r in picks if r is not longest]
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=300)
        for rec in picks[: int(traffic["check_sample"])]:
            conn.request("POST", "/v1/retrieve",
                         json.dumps({"query": self.document(rec["answer"]["passage"]),
                                     "k": int(traffic["k"])}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            rec["answer"]["own_query"] = (
                [[(r["metadata"] or {}).get("path"), r["dist"],
                  zlib.crc32(r["text"].encode("utf-8"))] for r in body]
                if resp.status == 200 and isinstance(body, list) else None)
        conn.close()

    def facts(self) -> dict:
        out = super().facts()
        out["document_words"] = [int(w) for w in self.config["document_words"]]
        out["encoder"] = self.builder.sizes(self.config)
        out["encoder_programs"] = list(self.builder.PROGRAMS)
        out["grouped_matmul_ops"] = list(self.builder.GROUPED_MATMUL_OPS)
        return out


def start(config: dict, seed: int, workdir: str, log) -> Deployment:
    return Deployment(config, seed, workdir, log)
