"""The retrieval deployment: ``VectorStoreServer`` over a watched directory,
a brute-force float32 index in HBM, the sentence encoder in front of it.

Copied from ``chip_smoke.py`` (``Smoke.phase_ingest_retrieve``): the server
start-up and the wait for the ingested documents.  New here: the index is
brought up at a deployment's size the way a warm restart does it (PR 6's
``restore_snapshot`` path: the index's own batched upsert), not through
served ingest, which takes 16 ms a document today.

A configuration reaches the program through constructor arguments only; no
``PATHWAY_*`` variable is set.  The encoder's weights are the harness's own
(``seeded.minilm_params``), assigned over the ones the constructor draws,
because the plain reference may take nothing the program has made.
"""

from __future__ import annotations

import os
import socket
import time

import numpy as np

import seeded
import textgen

ENCODER_KEYS = {  # configuration key (HF name) -> EncoderConfig argument
    "vocab_size": "vocab_size", "hidden_size": "hidden_dim",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "intermediate_size": "mlp_dim", "max_position_embeddings": "max_len",
    "type_vocab_size": "type_vocab_size", "layer_norm_eps": "ln_eps",
}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def poll(what: str, fn, timeout_s: float, every_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        try:
            last = fn()
            if last:
                return last
        except OSError as exc:
            last = exc
        time.sleep(every_s)
    raise RuntimeError(f"timed out after {timeout_s:.0f}s waiting for {what}: {last!r}")


class Deployment:
    """A running ``VectorStoreServer`` and what the harness knows of it."""

    def __init__(self, config: dict, seed: int, workdir: str, log):
        import jax

        import pathway_tpu as pw
        from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder
        from pathway_tpu.stdlib.indexing import BruteForceKnnFactory
        from pathway_tpu.stdlib.indexing.lowering import live_index_node
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
        from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

        self.config, self.seed, self.log = config, seed, log
        t0 = time.monotonic()
        e = config["encoder"]
        self.encoder = SentenceEncoder(
            cfg=EncoderConfig(**{ENCODER_KEYS[k]: e[k] for k in ENCODER_KEYS}),
            max_length=e["max_seq_length"],
        )
        params = seeded.encoder_params(config, seed)
        drawn = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), self.encoder.params)
        made = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
        if drawn != made:
            raise RuntimeError("the encoder's parameter tree is not the one "
                               "perfbench/seeded.py makes")
        self.encoder.params = params
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
        self.port = free_port()
        self.urls = {"retrieve": f"http://127.0.0.1:{self.port}/v1/retrieve",
                     "corpus_dir": "file://" + self.corpus_dir}
        self.build_and_run()
        t1 = time.monotonic()
        self.node = poll("the live index", lambda: live_index_node(self.factory), 300.0)
        poll(f"{self.n_passages} ingested passages",
             lambda: len(self.node.doc_payload) >= self.n_passages, 900.0)
        log(f"{self.n_passages} passages served-ingested in {time.monotonic() - t1:.1f}s")
        self.inner = self.node.index.index  # DeviceKnnIndex under the retriever
        t2 = time.monotonic()
        self.prefill()
        log(f"{config['rows']} rows prefilled in {time.monotonic() - t2:.1f}s; "
            f"capacity {self.inner.capacity}, index dtype {self.inner.index_dtype}")
        if self.inner.capacity != int(config["index"]["capacity"]):
            raise RuntimeError(f"index capacity {self.inner.capacity} is not the "
                               f"configuration's {config['index']['capacity']}")

    # -- construction ----------------------------------------------------
    def build_and_run(self) -> None:
        self.vs.run_server(host="127.0.0.1", port=self.port, threaded=True,
                           with_cache=False)

    def write_passage(self, i: int) -> str:
        """Passage ``i`` as a file of the watched directory (written beside
        it and renamed in, so the reader never sees half a file)."""
        path = os.path.join(self.corpus_dir, f"passage_{i:07d}.txt")
        tmp = os.path.join(os.path.dirname(self.corpus_dir), f".tmp_{i}")
        with open(tmp, "w") as f:
            f.write(textgen.passage(i, self.seed))
        os.rename(tmp, path)
        return path

    def prefill(self) -> None:
        """Load ``rows`` seeded vectors through the index's own batched
        upsert, block by block.  A one-row search makes the index apply what
        it has staged, so that few blocks are held twice; the first comes
        only once over a quarter of the capacity is staged, because an index
        that a search finds under a quarter full compacts itself to twice
        its live rows and then regrows by doubling (the first chip run ended
        at 4,014,080 slots, not the 3,145,728 reserved)."""
        import jax

        cfg = self.config
        rows, dim = int(cfg["rows"]), int(cfg["index"]["dim"])
        block = int(cfg["index"]["prefill_block_rows"])
        if rows % block:
            raise ValueError("rows must be a whole number of prefill blocks")
        plane = self.vs._retrieve_plane
        width = 1 + max(plane._text_i, plane._meta_i)
        texts = [textgen.passage(i, self.seed) for i in range(int(cfg["payload_texts"]))]
        key = seeded.key_of(self.seed, 1)
        probe = np.zeros((1, dim), np.float32)
        probe[0, 0] = 1.0
        in_use: list = []
        spent = {"make rows": 0.0, "add_batch": 0.0, "payloads": 0.0, "apply": 0.0}
        for b in range(rows // block):
            t0 = time.monotonic()
            vecs = seeded.row_block(key, b, rows=block, dim=dim)
            # keys are the row numbers (the engine's own keys are pointers,
            # so the two never meet); checks read them back from the path
            keys = list(range(b * block, (b + 1) * block))
            metas = [{"path": f"prefill/{k}"} for k in keys]
            t1 = time.monotonic()
            self.node.index.add_batch(keys, vecs, metas)
            t2 = time.monotonic()
            payload = self.node.doc_payload
            for k, meta in zip(keys, metas):
                row = [None] * width
                row[plane._text_i] = texts[k % len(texts)]
                row[plane._meta_i] = meta
                payload[k] = tuple(row)
            t3 = time.monotonic()
            if (b + 1) * block > 0.3 * self.inner.capacity:
                self.inner.search(probe, 1)
                # the scatters copy the matrix and donate nothing, and they
                # are dispatched ahead of the device: wait for them, or the
                # next block finds the chip's memory full of old copies
                jax.block_until_ready(self.inner.vectors)
            del vecs
            in_use.append(round(jax.devices()[0].memory_stats().get("bytes_in_use", 0) / 1e9, 2)
                          if jax.devices()[0].memory_stats() else None)
            for name, dt in zip(spent, (t1 - t0, t2 - t1, t3 - t2, time.monotonic() - t3)):
                spent[name] += dt
        self.node.bump_commit_seq()
        self.log("prefill: " + ", ".join(f"{k} {v:.1f}s" for k, v in spent.items())
                 + f"; GB in use after each block {in_use}")

    # -- warm-up -----------------------------------------------------------
    def warm_up(self, traffic: dict) -> None:
        """Every shape the traffic can drive: the encoder and the search at
        each batch bucket (ticks carry 1 to ``max_batch`` queries), the
        host twin of the encoder that takes short queries when the queue
        is deep, then a burst over HTTP."""
        from pathway_tpu.models.encoder import BATCH_BUCKETS

        if "min_words" not in traffic:
            # a mix without queries: served ingest during set-up has driven
            # every shape a dropped file drives (one document a tick, the
            # 32/64/128-token buckets)
            return
        plane = self.vs._retrieve_plane
        k = int(traffic["k"])
        top = plane.group.max_batch
        texts = textgen.query_texts(top, self.seed ^ 0x5EED, int(traffic["min_words"]),
                                    int(traffic["max_words"]))
        buckets = [b for b in BATCH_BUCKETS if b <= top]
        for b in buckets:
            t0 = time.monotonic()
            out = plane._batch([(f"{t} warm{b}", k, None) for t in texts[:b]])
            if any(r["degraded"] for r in out):
                raise RuntimeError("a warm-up batch answered degraded")
            self.log(f"warm-up bucket {b}: {time.monotonic() - t0:.2f}s")
        stack = plane._cache_stack()
        collab = getattr(stack, "collab", None) if stack is not None else None
        if collab is not None:
            t0 = time.monotonic()
            ids, mask = self.encoder.tokenizer.encode_batch(
                texts, max_length=self.encoder.max_length)
            if collab.parity_ok is None:
                device_row = np.asarray(self.encoder.encode(texts[:1]), np.float32)
                collab.check_parity(device_row, ids[:1], mask[:1])
            for b in buckets:
                collab.encode_rows(ids[:b], mask[:b])
            self.log(f"warm-up host twin (parity_ok={collab.parity_ok}): "
                     f"{time.monotonic() - t0:.2f}s")
        self.http_burst(texts, k)

    def http_burst(self, texts: list[str], k: int) -> None:
        import concurrent.futures
        import http.client
        import json

        def one(text: str) -> int:
            conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
            try:
                conn.request("POST", "/v1/retrieve",
                             json.dumps({"query": text + " burst", "k": k}),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                return resp.status
            finally:
                conn.close()

        t0 = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(32) as pool:
            statuses = list(pool.map(one, texts))
        bad = [s for s in statuses if s != 200]
        if bad:
            raise RuntimeError(f"warm-up burst: {len(bad)} responses not 200")
        self.log(f"warm-up HTTP burst of {len(texts)}: {time.monotonic() - t0:.2f}s")

    # -- what the metrics read ----------------------------------------------
    def counters(self) -> dict:
        """The program's cumulative counters, flat; metrics take the
        difference between the window's start and its end."""
        from pathway_tpu.internals.flight_recorder import (
            compile_stats, ingest_stats, observability_metrics_lines)
        from pathway_tpu.ops.fused_serving import launch_totals
        from pathway_tpu.runtime import get_runtime
        from pathway_tpu.xpacks.llm._query_cache import query_cache_stats

        out: dict = {}
        rt = get_runtime().stats()
        out["ticks_total"] = rt["ticks_total"]
        for label, c in rt["classes"].items():
            for name in ("submitted_total", "completed_total", "failed_total",
                         "shed_deadline_total", "admission_rejected_total",
                         "wait_ms_sum", "wait_ms_count"):
                out[f"runtime.{label}.{name}"] = c.get(name, 0)
        for line in observability_metrics_lines():
            for kind in ("sum", "count"):
                head = f"pathway_request_stage_ms_{kind}{{stage=\""
                if line.startswith(head):
                    stage, value = line[len(head):].split("\"} ")
                    out[f"stage.{stage}.{kind}"] = float(value.split()[0])
            for head in ("pathway_decode_launch_ms_sum{kind=\"",
                         "pathway_decode_launch_ms_count{kind=\""):
                if line.startswith(head):
                    kind, value = line[len(head):].split("\"} ")
                    which = "sum" if "_sum" in head else "count"
                    out[f"decode_launch.{kind}.{which}"] = float(value.split()[0])
        for stage, n in launch_totals().items():
            out[f"launches.{stage}"] = n
        for site, n in compile_stats().items():
            out[f"compiles.{site}"] = n
        cache = query_cache_stats()
        for layer in ("embed", "result"):
            for name in ("hits", "misses"):
                out[f"cache.{layer}.{name}"] = cache[layer][name]
        out["collab.embeds_total"] = cache["collab"]["embeds_total"]
        ing = ingest_stats()
        out["ingest.docs_total"] = ing["docs_total"]
        out["index.rebuilds"] = int(self.inner.rebuilds)
        out["index.live_rows"] = len(self.inner.slot_of_key)
        breaker = self.vs._retrieve_plane.breaker.stats()
        out["breaker.retrieve.trips_total"] = breaker["trips_total"]
        out["breaker.retrieve.failures_total"] = breaker["failures_total"]
        return out

    # -- a window that watches the index (ingest cells) --------------------
    def window_opens(self, traffic: dict) -> None:
        """Start polling the live index's count every ``poll_ms``."""
        if not traffic.get("watch_index"):
            return
        import threading

        self._watch = {"stop": threading.Event(), "base": len(self.node.doc_payload),
                       "seen": []}  # (monotonic time, count) whenever the count rose

        def poll_count() -> None:
            w, last = self._watch, self._watch["base"]
            while not w["stop"].is_set():
                n = len(self.node.doc_payload)
                if n > last:
                    w["seen"].append((time.monotonic(), n))
                    last = n
                time.sleep(float(traffic["poll_ms"]) / 1e3)

        self._watch["thread"] = threading.Thread(target=poll_count, daemon=True)
        self._watch["thread"].start()

    def window_closed(self, traffic: dict, records: list[dict], seed: int) -> None:
        """Wait until the index has counted every dropped file (``drain_s``
        at most) and fill ``fresh_ms`` into the records in rename order."""
        if not traffic.get("watch_index"):
            return
        w = self._watch
        dropped = sorted((r for r in records if not r["failed"]), key=lambda r: r["renamed_at"])
        want = w["base"] + len(dropped)
        deadline = time.monotonic() + float(traffic["drain_s"])
        while len(self.node.doc_payload) < want and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(2 * float(traffic["poll_ms"]) / 1e3)
        w["stop"].set()
        w["thread"].join()
        seen, j = w["seen"], 0
        for n, rec in enumerate(dropped):
            while j < len(seen) and seen[j][1] < w["base"] + n + 1:
                j += 1
            rec["fresh_ms"] = ((seen[j][0] - rec["renamed_at"]) * 1e3 if j < len(seen) else None)
            if rec["fresh_ms"] is None:
                rec["failed"] = True
        self.n_passages += len(dropped)

    def after_window(self, traffic: dict, records: list[dict], seed: int) -> None:
        """Once the counters are read: ask for a sample of the dropped files
        by their own text, one query at a time, and keep the answers for
        the check."""
        if not traffic.get("watch_index"):
            return
        import http.client
        import json
        import random
        import zlib

        picks = sorted((r for r in records if not r["failed"]), key=lambda r: r["i"])
        random.Random(f"{seed}:check").shuffle(picks)
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        for rec in picks[: int(traffic["check_sample"])]:
            text = textgen.passage(rec["answer"]["passage"], self.seed)
            conn.request("POST", "/v1/retrieve",
                         json.dumps({"query": text, "k": int(traffic["k"])}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            rec["answer"]["own_query"] = (
                [[(r["metadata"] or {}).get("path"), r["dist"],
                  zlib.crc32(r["text"].encode("utf-8"))] for r in body]
                if resp.status == 200 and isinstance(body, list) else None)
        conn.close()

    def facts(self) -> dict:
        """Shapes the cost functions need, as the program holds them."""
        return {
            "next_passage": self.n_passages, "corpus_seed": self.seed,
            "capacity": int(self.inner.capacity), "dim": int(self.inner.dim),
            "itemsize": int(np.dtype(self.inner.vectors.dtype).itemsize),
            "live_rows": len(self.inner.slot_of_key),
            "search_programs": ("_pallas_fused_dense", "_xla_fused_dense"),
        }

    def free(self) -> None:
        """Drop the program's device state so that the reference has the
        chip's memory (the engine thread is a daemon and ends with the
        process)."""
        import gc

        with self.inner._lock:
            self.inner.vectors = None
            self.inner.valid = None
            self.inner._staged_device.clear()
        self.encoder.params = None
        gc.collect()


def start(config: dict, seed: int, workdir: str, log) -> Deployment:
    return Deployment(config, seed, workdir, log)
