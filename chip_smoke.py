#!/usr/bin/env python3
"""chip_smoke.py — the live RAG path, end to end, on one TPU chip.

``python chip_smoke.py`` (no arguments, one process, from a clean
checkout) drives the two normal entry points through their public
surface — ``VectorStoreServer`` + ``VectorStoreClient`` for ingest and
``/v1/retrieve``, ``BaseRAGQuestionAnswerer`` + ``build_server`` +
``RAGClient`` for streamed answers — at the full width of the two models
the repo supports (MiniLM-L6 encoder, GPT-2 124M decoder; random weights
from a seed, hash tokenizer), checks every result against a reference,
and asserts that nothing stood in for the device.  It exits 0 only if
every phase passed on a TPU.  It then prints two JSON lines: the full
summary (also written to ``chiprun_out/chip_smoke.json``) and, as the
last line of its standard output, the verdict the driver reads:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with exactly those keys.

``--mesh N`` runs the device and ingest+retrieve phases over an N-device
serving mesh instead (fails with fewer than N devices) and checks that
the corpus is spread over N shards and that the sharded search equals the
one-device search.

The phases are importable functions; ``tests/test_chip_smoke.py`` runs
them at a tiny geometry on the CPU with the kernels in interpret mode.
A phase that fails raises :class:`SmokeFailure`; nothing catches it on
the way to a zero exit.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import io
import json
import os
import socket
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request
import zipfile
from typing import Any, Callable

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: the 24/24/56/120-word document-length cycle of the ingest workload
#: (short titles/queries, medium passages, long chunks)
MIXED_WORDS = (24, 24, 56, 120)

#: cosine scores lie in [-1, 1].  The served query embedding crosses the
#: encoder->search wire in bf16 (the reference applies the same rounding)
#: and the MXU's default single pass rounds both dot operands to bf16's 8
#: significant bits, so a 384-term dot of unit vectors may move by about
#: one bf16 ulp of 1.0
SCORE_TOL = 2.0 ** -7

#: attention kernels read and write bf16 and accumulate in f32; their XLA
#: references round the softmax weights to bf16 before the value matmul.
#: Outputs are convex combinations of N(0,1) values, so a few bf16
#: roundings (2^-8 relative each) bound the difference well under this
ATTN_TOL = 3e-2

#: random-weight GPT-2 logits are ~N(0,1) over 50,257 tokens: the top two
#: sit ~0.2 apart, which bf16 activations (8 significant bits through 12
#: layers, ~0.02 logits of noise) can flip, while a wrong kernel lands ~4
#: logits below the maximum.  A greedy token is accepted when its logit
#: in the float32 teacher-forced reference is within this of the maximum
LOGIT_TOL = 0.25


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond: bool, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """What one run is sized to.  ``encoder``/``decoder`` are overrides of
    ``EncoderConfig()`` / ``DecoderConfig()``: empty means full width."""

    name: str
    n_docs: int
    doc_words: tuple[int, ...]  # document lengths, cycled over the corpus
    encoder: dict
    decoder: dict
    max_new_tokens: int
    streams: int
    kernel_rows: int      # corpus rows of the scoring kernels
    kernel_tokens: int    # packed tokens of the attention kernels
    kernel_pool_blocks: int


FULL = Geometry(
    name="full", n_docs=4096, doc_words=MIXED_WORDS, encoder={}, decoder={},
    max_new_tokens=32,
    streams=4, kernel_rows=4096, kernel_tokens=1024, kernel_pool_blocks=256,
)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _poll(what: str, fn: Callable[[], Any], timeout_s: float) -> Any:
    """Call ``fn`` until it returns something truthy; fail after
    ``timeout_s``.  Connection errors while a server starts count as
    "not yet"."""
    deadline = time.monotonic() + timeout_s
    last: Any = None
    while time.monotonic() < deadline:
        try:
            last = fn()
            if last:
                return last
        except (OSError, urllib.error.URLError) as exc:
            last = exc
        time.sleep(0.25)
    raise SmokeFailure(f"timed out after {timeout_s:.0f}s waiting for {what}: {last!r}")


def corpus(n_docs: int, doc_words: tuple[int, ...]) -> list[str]:
    rng = np.random.default_rng(0)
    words = np.array([f"w{i:04d}" for i in range(2000)])
    return [
        " ".join(rng.choice(words, size=doc_words[i % len(doc_words)]))
        for i in range(n_docs)
    ]


class Smoke:
    """State shared by the phases of one run."""

    def __init__(self, geometry: Geometry = FULL, mesh_devices: int = 0,
                 require_tpu: bool = True):
        self.g = geometry
        self.mesh_devices = mesh_devices
        self.require_tpu = require_tpu
        self.summary: dict[str, Any] = {"phases": {}, "impl": {}}
        self.tmp = tempfile.TemporaryDirectory(
            prefix="chip_smoke_", ignore_cleanup_errors=True
        )
        self.docs = corpus(geometry.n_docs, geometry.doc_words)
        self.on_tpu = False
        self.mesh = None
        self.responses = 0  # HTTP answers checked for `degraded`

    # -- phase 1 ---------------------------------------------------------
    def phase_device(self) -> dict:
        """A TPU, the native tokenizer, the compile cache."""
        import jax
        import jaxlib

        from pathway_tpu.utils.compile_cache import (
            cache_entry_count,
            enable_compile_cache,
        )

        self.cache_dir = enable_compile_cache()
        dev, count = jax.devices()[0], len(jax.devices())
        self.on_tpu = dev.platform == "tpu"
        if self.require_tpu and not self.on_tpu:
            raise SmokeFailure(
                f"chip_smoke needs a TPU; JAX found platform "
                f"{dev.platform!r} ({dev.device_kind!r}, {count} device(s))"
            )
        if self.mesh_devices:
            check(
                count >= self.mesh_devices,
                f"--mesh {self.mesh_devices} needs {self.mesh_devices} "
                f"devices; JAX found {count}",
            )
            from pathway_tpu.parallel import make_mesh

            self.mesh = make_mesh(self.mesh_devices)
        from pathway_tpu import _native  # a failed g++ build raises here

        from importlib import metadata

        try:
            libtpu = metadata.version("libtpu")
        except metadata.PackageNotFoundError:
            libtpu = None
        check(
            not self.on_tpu or self.cache_dir is not None,
            "no persistent compile cache on the chip",
        )
        self.cache_before = cache_entry_count(self.cache_dir)
        self.summary.update(
            device={
                "platform": dev.platform,
                "kind": dev.device_kind,
                "count": count,
            },
            platform=dev.platform,
            device_kind=dev.device_kind,
            device_count=count,
            versions={
                "python": sys.version.split()[0],
                "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "libtpu": libtpu,
            },
            native_tokenizer=os.path.basename(_native.lib._name),
            compile_cache={
                "dir": self.cache_dir,
                "placed_by": (
                    "JAX_COMPILATION_CACHE_DIR"
                    if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                    else "checkout" if self.cache_dir else None
                ),
                "entries_before": self.cache_before,
            },
        )
        return {"device_kind": dev.device_kind}

    # -- phase 2 ---------------------------------------------------------
    def phase_ingest_retrieve(self) -> dict:
        """Ingest the corpus from a watched directory, then retrieve."""
        import pathway_tpu as pw
        from pathway_tpu.models.encoder import EncoderConfig, SentenceEncoder
        from pathway_tpu.ops.fused_serving import (
            pick_serving_impl,
            serving_kernel_mode,
        )
        from pathway_tpu.stdlib.indexing.lowering import live_index_node
        from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
        from pathway_tpu.xpacks.llm.vector_store import (
            VectorStoreClient,
            VectorStoreServer,
        )

        g = self.g
        self.corpus_dir = os.path.join(self.tmp.name, "corpus")
        os.makedirs(self.corpus_dir)
        for i, text in enumerate(self.docs):
            with open(os.path.join(self.corpus_dir, f"doc_{i:05d}.txt"), "w") as f:
                f.write(text)
        with concurrent.futures.ThreadPoolExecutor(1) as pool:
            # both models initialise op by op; overlap their compiles
            lm = None if self.mesh_devices else pool.submit(self._build_lm)
            self.encoder = SentenceEncoder(
                cfg=EncoderConfig(**g.encoder), max_length=256, mesh=self.mesh
            )
            self.lm = lm.result() if lm is not None else None
        embedder = SentenceTransformerEmbedder(encoder=self.encoder)
        table = pw.io.fs.read(
            self.corpus_dir, format="binary", mode="streaming",
            with_metadata=True, refresh_interval=0.2,
        )
        self.vs = VectorStoreServer(table, embedder=embedder, mesh=self.mesh)
        self.vs_port = _free_port()
        t0 = time.monotonic()
        if self.mesh_devices:
            self.vs.run_server(
                host="127.0.0.1", port=self.vs_port, threaded=True,
                with_cache=False,
            )
        else:
            # one dataflow graph, two web servers: retrieval here, the
            # question answerer of phase 3 beside it
            self.vs.build_server(host="127.0.0.1", port=self.vs_port)
            self._build_qa()
            self.qa.server.run(threaded=True, with_cache=False)
        self.vs_client = VectorStoreClient(
            host="127.0.0.1", port=self.vs_port, timeout=600.0
        )

        def indexed() -> bool:
            node = live_index_node(self.vs.index_factory)
            return node is not None and len(node.doc_payload) >= g.n_docs

        _poll(f"{g.n_docs} documents in the index", indexed, 900.0)
        stats = self.vs_client.get_vectorstore_statistics()
        check(
            int(stats.get("file_count", -1)) == g.n_docs,
            f"/v1/statistics reports {stats!r}, expected {g.n_docs} files",
        )
        ingest_s = time.monotonic() - t0
        # how the ingest was dispatched: rows through the encoder, device
        # ticks that carried them, programs compiled
        from pathway_tpu.internals.flight_recorder import (
            compile_stats,
            ingest_stats,
        )
        from pathway_tpu.runtime import get_runtime

        ingested, ticks = ingest_stats(), get_runtime().stats()
        dispatch = {
            "encoder_rows": int(ingested["docs_total"]),
            "padding_efficiency": round(ingested["padding_efficiency"], 3),
            "device_ticks": int(ticks["ticks_total"]),
            "tick_occupancy_mean": round(ticks["tick_occupancy_mean"], 2),
            "embed_queue_depth_max": int(
                ticks["classes"]["llm_rerank"]["queue_depth_max"]
            ),
            "compiles_by_site": compile_stats(),
        }
        node = live_index_node(self.vs.index_factory)
        self.index = node.index.index  # DeviceKnnIndex under the retriever
        impl = pick_serving_impl(
            serving_kernel_mode(), self.index.capacity, self.index.metric
        )
        if self.mesh is not None:
            # the mesh-sharded dense search is XLA inside shard_map
            impl = "xla(shard_map)"
        self.summary["impl"]["serving_topk"] = impl
        if self.on_tpu and self.mesh is None:
            check(impl == "pallas", f"serving kernel resolved to {impl!r} on the chip")

        self.host_index = None
        # 8 sequential (the first compiles) + 8 concurrent, k=10: each
        # query is a document's exact text, one per length class in turn
        picks = [(i * (g.n_docs // 16)) for i in range(16)]
        t1 = time.monotonic()
        checked = [self._retrieve_checked(picks[0])]
        compile_s = time.monotonic() - t1
        t2 = time.monotonic()
        checked += [self._retrieve_checked(i) for i in picks[1:8]]
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            checked += pool.map(self._retrieve_checked, picks[8:])
        steady_s = time.monotonic() - t2

        # live ingest: one new file, retrievable without a restart
        fresh = "zz_live_ingest " + " ".join(f"q{i:03d}" for i in range(40))
        with open(os.path.join(self.corpus_dir, "doc_live.txt"), "w") as f:
            f.write(fresh)
        t3 = time.monotonic()

        def fresh_first() -> bool:
            rows = self._query(fresh, 10)
            return bool(rows) and rows[0]["text"] == fresh

        _poll("the live-ingested document to rank first", fresh_first, 300.0)
        out = {
            "docs": g.n_docs,
            "index_capacity": int(self.index.capacity),
            "ingest_s": round(ingest_s, 2),
            "ingest_dispatch": dispatch,
            "first_query_s": round(compile_s, 2),
            "steady_15_checked_queries_s": round(steady_s, 3),
            "live_ingest_to_queryable_s": round(time.monotonic() - t3, 2),
            "max_abs_score_diff": max(diff for diff, _ in checked),
            "score_tolerance": SCORE_TOL,
            # how far the document's own score led the runner-up: random
            # weights embed every document close to every other
            "min_lead_over_second": min(lead for _, lead in checked),
        }
        if self.mesh is not None:
            out.update(self._check_mesh())
        return out

    def _query(self, text: str, k: int) -> list[dict]:
        rows = self.vs_client.query(text, k=k)
        self.responses += 1
        check(
            not self.vs_client.last_degraded,
            f"/v1/retrieve answered degraded for {text[:40]!r}",
        )
        return rows

    def _reference_topk(self, text: str, k: int) -> np.ndarray:
        """float32 numpy scores on the host from the same embeddings: the
        index matrix as it sits on the device, and the query embedded by
        the server's own encoder and rounded to the bf16 wire dtype."""
        import jax.numpy as jnp

        emb, _n = self.encoder.encode_padded([text])
        q = np.asarray(emb.astype(jnp.bfloat16).astype(jnp.float32))[0]
        q = q / max(float(np.linalg.norm(q)), 1e-30)
        if self.host_index is None:
            # one copy, taken after the first served query has applied
            # every staged upsert; the corpus is static until live ingest
            self.host_index = (
                np.asarray(self.index.vectors, dtype=np.float32),
                np.asarray(self.index.valid),
            )
        vectors, valid = self.host_index
        scores = np.where(valid, vectors @ q, -np.inf)
        return np.sort(scores)[::-1][:k]

    def _retrieve_checked(self, doc_i: int, k: int = 10) -> tuple[float, float]:
        """Query a document's own text; returns (largest difference from
        the host reference, lead of the best score over the second)."""
        text = self.docs[doc_i]
        rows = self._query(text, k)
        check(len(rows) == k, f"doc {doc_i}: {len(rows)} results, expected {k}")
        check(
            rows[0]["text"] == text,
            f"doc {doc_i} did not rank first for its own text "
            f"(dists {[round(r['dist'], 5) for r in rows[:3]]})",
        )
        served = -np.asarray([r["dist"] for r in rows], dtype=np.float32)
        check(bool(np.all(np.isfinite(served))), f"doc {doc_i}: non-finite score")
        ref = self._reference_topk(text, k)
        diff = float(np.max(np.abs(served - ref)))
        check(
            diff <= SCORE_TOL,
            f"doc {doc_i}: served top-{k} scores differ from the float32 "
            f"host reference by {diff:.3g} > {SCORE_TOL:.3g}",
        )
        return diff, float(served[0] - served[1])

    def _check_mesh(self) -> dict:
        """Sharded phase 2: rows spread over every shard (the HBM ledger's
        per-device bytes, the arrays' own placement), and the sharded
        search equal to a one-device search over the same rows."""
        from pathway_tpu.observability.hbm_ledger import get_ledger
        from pathway_tpu.ops.knn import DeviceKnnIndex
        from pathway_tpu.parallel.index import ShardedKnnIndex, mesh_status

        n = self.mesh_devices
        check(isinstance(self.index, ShardedKnnIndex), "index is not sharded")
        per_shard = {
            shard: b for comp, shard, b in get_ledger().entries()
            if comp.startswith("knn:") and shard is not None
        }
        check(
            len(per_shard) == n and all(b > 0 for b in per_shard.values()),
            f"HBM ledger per-shard bytes {per_shard!r}: expected {n} "
            "non-empty shards",
        )
        placed = {s.device.id for s in self.index.vectors.addressable_shards}
        check(len(placed) == n, f"index rows live on devices {sorted(placed)}")
        # slots fill in order, so a half-full index leaves its last shards
        # empty: what must hold is that no one device holds every document
        rows = next(iter(mesh_status().values()))["rows_per_shard"]
        check(
            len(rows) == n and max(rows) < sum(rows),
            f"rows per shard {rows}: every document sits on one device",
        )
        vectors = np.asarray(self.index.vectors, dtype=np.float32)
        keys = list(self.index.slot_of_key)
        live = vectors[[self.index.slot_of_key[key] for key in keys]]
        queries = self.encoder.encode([self.docs[0], self.docs[-1]])

        def agreement(sharded, single, what: str) -> float:
            worst = 0.0
            for a, b in zip(single.search(queries, 10), sharded.search(queries, 10)):
                check(
                    a[0][0] == b[0][0],
                    f"{what}: sharded and one-device search disagree on "
                    "the best hit",
                )
                worst = max(
                    worst, max(abs(sa - sb) for (_, sa), (_, sb) in zip(a, b))
                )
            check(
                worst <= SCORE_TOL,
                f"{what}: sharded vs one-device scores differ by {worst:.3g}",
            )
            return worst

        def filled(index):
            index.upsert_batch(keys, live)
            return index

        cap, dim, metric = self.index.capacity, self.index.dim, self.index.metric
        f32 = agreement(
            self.index, filled(DeviceKnnIndex(dim, metric=metric, capacity=cap)),
            "f32 index",
        )
        # the int8 index scores through the Pallas kernel inside shard_map
        int8 = agreement(
            filled(ShardedKnnIndex(
                dim, self.mesh, metric=metric, capacity=cap, index_dtype="int8")),
            filled(DeviceKnnIndex(
                dim, metric=metric, capacity=cap, index_dtype="int8")),
            "int8 index",
        )
        return {
            "mesh_devices": n,
            "ledger_bytes_per_shard": per_shard,
            "rows_per_shard": rows,
            "sharded_vs_single_max_abs_diff": f32,
            "int8_sharded_vs_single_max_abs_diff": int8,
        }

    # -- phase 3 ---------------------------------------------------------
    def _build_lm(self):
        from pathway_tpu.models.decoder import CausalLM, DecoderConfig

        return CausalLM(None, cfg=DecoderConfig(**self.g.decoder))

    def _build_qa(self) -> None:
        from pathway_tpu.xpacks.llm.llms import JaxPipelineChat
        from pathway_tpu.xpacks.llm.question_answering import (
            BaseRAGQuestionAnswerer,
        )

        chat = JaxPipelineChat(
            model=None, causal_lm=self.lm,
            max_new_tokens=self.g.max_new_tokens,
        )
        self.qa = BaseRAGQuestionAnswerer(llm=chat, indexer=self.vs)
        self.qa_port = _free_port()
        self.qa.build_server(host="127.0.0.1", port=self.qa_port)

    def _stream(self, question: str) -> dict:
        """One streamed answer; returns its prompt ids and token ids."""
        from pathway_tpu.xpacks.llm import prompts
        from pathway_tpu.xpacks.llm.question_answering import _NO_INFO, RAGClient

        client = RAGClient(host="127.0.0.1", port=self.qa_port, timeout=900.0)
        t0 = time.monotonic()
        first_token_s = None
        events = []
        for ev in client.pw_ai_answer_stream(
            question, max_new_tokens=self.g.max_new_tokens,
            return_context_docs=True,
        ):
            if ev.get("event") == "token" and first_token_s is None:
                first_token_s = time.monotonic() - t0
            events.append(ev)
        self.responses += 1
        check(bool(events), "empty answer stream")
        done = events[-1]
        check(done.get("event") == "done", f"stream ended with {done!r}")
        check(
            not done.get("degraded") and done.get("response") is not None,
            f"streamed answer degraded: {done!r}",
        )
        context = [e for e in events if e.get("event") == "context"]
        check(
            len(context) == 1 and not context[0].get("retrieval_degraded"),
            f"context line missing or degraded: {context!r}",
        )
        pieces = [e["text"] for e in events if e.get("event") == "token"]
        check(bool(pieces), "no token lines before done")
        check(
            "".join(pieces).strip() == done["response"],
            "done.response is not the joined token pieces",
        )
        docs = context[0]["context_docs"]
        check(question in docs, "the question's own document was not retrieved")
        # the hash tokenizer decodes ids as "<id>" pieces
        tokens = [int(p.strip("<>")) for p in done["response"].split()]
        check(
            len(tokens) == self.g.max_new_tokens,
            f"{len(tokens)} tokens streamed, expected {self.g.max_new_tokens}",
        )
        prompt = prompts.prompt_qa_geometric_rag(
            question, docs, information_not_found_response=_NO_INFO
        )
        cap = max(1, self.lm.cfg.max_len - self.g.max_new_tokens)
        return {
            "prompt_ids": self.lm.encode_prompt(prompt)[-cap:],
            "tokens": tokens,
            "first_token_s": first_token_s,
            "total_s": time.monotonic() - t0,
        }

    def _check_tokens(self, answer: dict) -> dict:
        """Greedy tokens against the dense reference (``generate_ids``)
        and against a float32 teacher-forced forward of the same weights.
        How many leading tokens agree exactly is reported, not fixed:
        see LOGIT_TOL.  What must hold: every streamed token is within
        LOGIT_TOL of the reference maximum at its position, and the two
        greedy runs part, if at all, at a reference near-tie."""
        import jax
        import jax.numpy as jnp

        from pathway_tpu.models.decoder import Decoder

        ids, toks = answer["prompt_ids"], answer["tokens"]
        dense = self.lm.generate_ids([ids], max_new_tokens=len(toks))[0].tolist()
        agree = next(
            (i for i, (a, b) in enumerate(zip(toks, dense)) if a != b), len(toks)
        )
        ref_model = Decoder(dataclasses.replace(self.lm.cfg, dtype=jnp.float32))
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(
                ref_model.apply(
                    {"params": self.lm.params},
                    jnp.asarray([ids + toks[:-1]], jnp.int32),
                )[0, len(ids) - 1:],
                dtype=np.float32,
            )
        check(bool(np.all(np.isfinite(logits))), "non-finite reference logits")
        margin = logits.max(axis=1) - logits[np.arange(len(toks)), toks]
        worst = float(margin.max())
        check(
            worst <= LOGIT_TOL,
            f"streamed token {int(margin.argmax())} sits {worst:.3g} logits "
            f"below the float32 reference maximum (> {LOGIT_TOL})",
        )
        if agree < len(toks):
            split = abs(
                float(logits[agree, toks[agree]] - logits[agree, dense[agree]])
            )
            check(
                split <= LOGIT_TOL,
                f"paged and dense greedy runs part at token {agree} where "
                f"the reference separates them by {split:.3g} logits",
            )
        return {
            "prompt_tokens": len(ids),
            "leading_tokens_equal_to_dense": agree,
            "max_logit_margin": worst,
        }

    def phase_streamed_answers(self) -> dict:
        """Streamed answers over the corpus of phase 2."""
        from pathway_tpu.generation.decode_kernel import resolve_decode_mode
        from pathway_tpu.ops import ragged_attention as ra

        g = self.g
        decode_mode = resolve_decode_mode()
        ragged_mode = ra.kernel_mode()
        if ragged_mode == "auto":
            ragged_mode = "pallas" if self.on_tpu else "reference"
        self.summary["impl"].update(
            decode_step=decode_mode, decode_verify=decode_mode,
            causal_prefill=decode_mode,  # the session passes its mode down
            ragged_auto=ragged_mode,
        )
        if self.on_tpu:
            check(
                decode_mode == "pallas" and ragged_mode == "pallas",
                f"decode={decode_mode!r} ragged={ragged_mode!r} on the chip",
            )
        n_q = 1 + 2 * g.streams
        questions = [self.docs[i * (g.n_docs // n_q) + 2] for i in range(n_q)]
        # the first answer compiles prefill and decode step and registers
        # the template's KV blocks; the concurrent ones then adopt that
        # prefix and ingest their tails through the verify kernel (which
        # compiles in the first round: the second is the steady one)
        t0 = time.monotonic()
        first = self._stream(questions[0])
        first_s = time.monotonic() - t0
        rounds = []
        with concurrent.futures.ThreadPoolExecutor(g.streams) as pool:
            for lo in (1, 1 + g.streams):
                t1 = time.monotonic()
                rest = list(pool.map(self._stream, questions[lo:lo + g.streams]))
                rounds.append(round(time.monotonic() - t1, 2))
        t2 = time.monotonic()
        parity = {
            "prefilled": self._check_tokens(first),
            "prefix_adopted": self._check_tokens(rest[-1]),
        }
        return {
            "streams": n_q,
            "first_answer_s": round(first_s, 2),
            "concurrent_round_s": rounds,
            "steady_first_token_s": [round(a["first_token_s"], 3) for a in rest],
            "new_tokens": g.max_new_tokens,
            "reference_check_s": round(time.monotonic() - t2, 2),
            "logit_tolerance": LOGIT_TOL,
            "parity": parity,
        }

    # -- phase 4 ---------------------------------------------------------
    def phase_nothing_stood_in(self) -> dict:
        """No degraded answer, no tripped breaker, no rebuilt index, no
        contained or retried generation fault, decode on the paged
        session, one fused launch per search."""
        from pathway_tpu.generation.engine import generation_status
        from pathway_tpu.ops.fused_serving import launch_totals
        from pathway_tpu.runtime import get_runtime
        from pathway_tpu.xpacks.llm._query_cache import query_cache_stats

        session = self.lm.paged_session()
        breakers = {
            "retrieve": self.vs._retrieve_plane.breaker,
            "stream_retrieve": self.qa._stream_retrieve_plane().breaker,
            "llm": self.qa.llm_breaker,
            "generation": session.breaker,
        }
        states = {}
        for name, breaker in breakers.items():
            s = breaker.stats()
            states[name] = s["state"]
            check(
                s["state"] == "closed" and s["trips_total"] == 0
                and s["failures_total"] == 0,
                f"breaker {name}: {s!r}",
            )
        check(self.index.rebuilds == 0, f"index rebuilt {self.index.rebuilds}x")
        gen = generation_status()
        for key in ("fault_contained_total", "fault_retries_total",
                    "fault_replays_total", "kv_pool_rebuilds_total"):
            check(gen[key] == 0, f"generation {key} = {gen[key]}")
        check(
            gen["tokens_generated_total"]
            >= (2 * self.g.streams + 1) * self.g.max_new_tokens,
            f"paged session generated {gen['tokens_generated_total']} tokens",
        )
        generate = get_runtime().stats()["classes"]["generate"]
        check(
            generate["completed_total"] > 0,
            "no GENERATE-class tick completed: decode did not ride the "
            "paged session",
        )
        launches = launch_totals()
        check(launches.get("fused", 0) > 0, f"launch totals {launches!r}")
        staged = {s: launches[s] for s in ("prep", "score", "topk") if s in launches}
        check(not staged, f"staged (unfused) launches on the search path: {staged!r}")
        cache = query_cache_stats()
        return {
            "responses_checked": self.responses,
            "degraded_responses": 0,
            "breakers": states,
            "index_rebuilds": int(self.index.rebuilds),
            "generation_faults": {
                k: gen[k] for k in ("fault_contained_total", "fault_retries_total")
            },
            "generate_ticks_completed": int(generate["completed_total"]),
            "prefix_hit_blocks": int(gen["prefix_hit_blocks_total"]),
            "launch_totals": launches,
            # the CPU twin of _query_cache.py embeds on the host by design
            "pathway_collab_embeds_total": int(cache["collab"]["embeds_total"]),
        }

    # -- phase 5 ---------------------------------------------------------
    def phase_kernels(self) -> dict:
        """Every Pallas kernel in the tree once, at a deployment shape,
        against its XLA reference."""
        results = {}
        for name, build in kernel_checks(self.g).items():
            t0 = time.monotonic()
            row = run_kernel_check(name, build, self.on_tpu)
            row["seconds"] = round(time.monotonic() - t0, 2)
            results[name] = row
            log(
                f"kernel {name}: compiled_by_mosaic={row['mosaic']} "
                f"max_abs_diff={row['max_abs_diff']:.3g} tol={row['tolerance']:.3g}"
            )
        return results

    # -- phase 6 ---------------------------------------------------------
    def phase_profiler(self) -> dict:
        """One /v1/debug/profile window while queries flow."""
        stop = threading.Event()

        def traffic() -> None:
            i = 0
            while not stop.is_set():
                self._query(self.docs[(7 * i + 5) % self.g.n_docs], 10)
                i += 1

        worker = threading.Thread(target=traffic, daemon=True)
        worker.start()
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{self.vs_port}/v1/debug/profile?ms=500",
                timeout=300,
            ) as resp:
                kind = resp.headers.get("x-pathway-profile-kind")
                body = resp.read()
        finally:
            stop.set()
            worker.join(timeout=60)
        check(not worker.is_alive(), "query traffic thread did not stop")
        out: dict[str, Any] = {"kind": kind, "bytes": len(body)}
        if self.on_tpu:
            check(kind == "jax", f"profile kind {kind!r} on the chip")
        if kind == "jax":
            names = zipfile.ZipFile(io.BytesIO(body)).namelist()
            xplanes = [n for n in names if n.endswith(".xplane.pb")]
            check(bool(xplanes), f"no .xplane.pb in the profile zip: {names!r}")
            out["xplane"] = xplanes[0]
        else:
            check(kind == "flight_recorder", f"profile kind {kind!r}")
            check("traceEvents" in json.loads(body), "profile has no traceEvents")
        return out

    # -- driver ----------------------------------------------------------
    def run(self) -> dict:
        """Every phase in order; the summary once all of them held."""
        for name, fn in self.phases():
            t0 = time.monotonic()
            log(f"phase {name} ...")
            detail = fn()
            seconds = round(time.monotonic() - t0, 2)
            self.summary["phases"][name] = {"ok": True, "seconds": seconds, **detail}
            log(f"phase {name} ok in {seconds}s")
        from pathway_tpu.utils.compile_cache import cache_entry_count

        after = cache_entry_count(self.cache_dir)
        self.summary["compile_cache"].update(
            entries_after=after, entries_added=after - self.cache_before
        )
        self.summary["ok"] = True
        self.summary["claim"] = None
        return self.summary

    def phases(self) -> list[tuple[str, Callable[[], dict]]]:
        phases = [
            ("device", self.phase_device),
            ("ingest_retrieve", self.phase_ingest_retrieve),
        ]
        if not self.mesh_devices:
            phases += [
                ("streamed_answers", self.phase_streamed_answers),
                ("nothing_stood_in", self.phase_nothing_stood_in),
                ("kernels", self.phase_kernels),
                ("profiler", self.phase_profiler),
            ]
        return phases


# ---------------------------------------------------------------------------
# phase 5: kernel checks
# ---------------------------------------------------------------------------


def run_kernel_check(name: str, build: Callable[[], tuple], on_tpu: bool) -> dict:
    """``build()`` -> (kernel_fn, reference_fn, args, tolerance).  Runs
    both, compares, and reads from the lowered program whether the kernel
    went to Mosaic (a ``tpu_custom_call``) or was interpreted."""
    import jax

    kernel_fn, reference_fn, args, tol = build()
    jitted = jax.jit(kernel_fn)
    mosaic = "tpu_custom_call" in jitted.lower(*args).as_text()
    if on_tpu:
        check(mosaic, f"kernel {name} did not lower to a Mosaic custom call")
    got = jax.tree_util.tree_leaves(jitted(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.tree_util.tree_leaves(jax.jit(reference_fn)(*args))
    worst = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, dtype=np.float32)
        w = np.asarray(w, dtype=np.float32)
        check(g.shape == w.shape, f"kernel {name}: shape {g.shape} vs {w.shape}")
        check(
            bool(np.all(np.isfinite(g) == np.isfinite(w))),
            f"kernel {name}: -inf/nan pattern differs from the reference",
        )
        finite = np.isfinite(w)
        if finite.any():
            worst = max(worst, float(np.max(np.abs(g[finite] - w[finite]))))
    check(
        worst <= tol,
        f"kernel {name}: max abs difference {worst:.3g} > tolerance {tol:.3g}",
    )
    return {"mosaic": mosaic, "max_abs_diff": worst, "tolerance": tol}


def kernel_checks(g: Geometry) -> dict[str, Callable[[], tuple]]:
    import jax
    import jax.numpy as jnp

    from pathway_tpu.generation import decode_kernel as dk
    from pathway_tpu.models.decoder import DecoderConfig
    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.ops import flash_attention as fa
    from pathway_tpu.ops import fused_serving as fs
    from pathway_tpu.ops import quantized_scoring as qs
    from pathway_tpu.ops import ragged_attention as ra
    from pathway_tpu.ops import topk as tk

    enc = EncoderConfig(**g.encoder)
    dec = DecoderConfig(**g.decoder)
    interpret = jax.default_backend() != "tpu"
    n, d = g.kernel_rows, enc.hidden_dim
    block_n = qs.pick_block_n(n)
    rng = np.random.default_rng(21)

    def unit(rows: int) -> np.ndarray:
        x = rng.standard_normal((rows, d)).astype(np.float32)
        return x / np.linalg.norm(x, axis=1, keepdims=True)

    vectors = unit(n)
    queries = rng.standard_normal((8, d)).astype(np.float32)
    valid = rng.random(n) > 0.05  # a few tombstones
    codes, scales = qs.quantize_rows_np(vectors)

    def topk_scores_only(fn):
        # ranks of near-tied scores may swap between formulations; the
        # scores themselves are the comparable quantity
        return lambda *a: fn(*a)[0]

    def dense(qdt: str, dtype):
        def build():
            v = jnp.asarray(vectors, dtype)
            kern = topk_scores_only(lambda q, v, m: fs._pallas_fused_dense(
                q, v, m, k=16, q_b=8, metric="cos", normalize=True, qdt=qdt,
                block_n=block_n, interpret=interpret))
            ref = topk_scores_only(lambda q, v, m: fs._xla_fused_dense(
                q, v, m, k=16, q_b=8, metric="cos", normalize=True, qdt=qdt))
            return kern, ref, (jnp.asarray(queries), v, jnp.asarray(valid)), SCORE_TOL
        return build

    def quant_fused():
        def kern(q, c, s, m):
            _qn, cand_s, _cand_i = fs._pallas_fused_quant(
                q, c, s, m, c=32, q_b=8, normalize=True, block_n=block_n,
                interpret=interpret)
            return cand_s
        def ref(q, c, s, m):
            empty = jnp.zeros((0, d), jnp.float32)
            return fs._xla_fused_quant(
                q, c, s, m, empty, jnp.zeros((n,), jnp.int32), c=32, k=32,
                q_b=8, metric="cos", normalize=True, use_cache=False)[0]
        args = (jnp.asarray(queries), jnp.asarray(codes), jnp.asarray(scales),
                jnp.asarray(valid))
        return kern, ref, args, SCORE_TOL

    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)

    def quant_scores():
        kern = lambda q, c, s, m: qs.pallas_quantized_scores(  # noqa: E731
            q, c, s, m.astype(jnp.float32), interpret=interpret)
        ref = lambda q, c, s, m: qs._reference_scores(q, c, s, m, "cos")  # noqa: E731
        args = (jnp.asarray(qn), jnp.asarray(codes), jnp.asarray(scales),
                jnp.asarray(valid))
        return kern, ref, args, SCORE_TOL

    def masked_scores():
        kern = lambda q, v, m: tk.pallas_masked_scores(  # noqa: E731
            q, v, m.astype(jnp.float32), block_n=block_n, interpret=interpret)
        ref = lambda q, v, m: tk.masked_topk_scores(q, v, m, "cos")  # noqa: E731
        return kern, ref, (jnp.asarray(qn), jnp.asarray(vectors),
                           jnp.asarray(valid)), SCORE_TOL

    def ragged(causal: bool, heads: int, dh: int, total: int):
        def build():
            # mixed row lengths filling `total` but for a pad tail
            lens, left = [], total - 5
            for ln in (MIXED_WORDS * (total // 16 + 1)):
                ln = min(ln + 2, left)
                if ln <= 0:
                    break
                lens.append(ln)
                left -= ln
            cu = np.concatenate([[0], np.cumsum(lens)])
            rows = 1 << (len(lens) - 1).bit_length()
            seg = np.full(total, rows, np.int32)
            pos = np.zeros(total, np.int32)
            starts = np.zeros(rows, np.int32)
            for j, ln in enumerate(lens):
                seg[cu[j]:cu[j + 1]] = j
                pos[cu[j]:cu[j + 1]] = np.arange(ln)
                starts[j] = cu[j]
            block = ra.ragged_block(total)
            bounds = ra.ragged_bounds(cu, total, block)
            dense_s = 1 << (max(lens) - 1).bit_length()
            real = seg < rows  # pad-tail outputs are unspecified
            q, k, v = (
                jnp.asarray(rng.standard_normal((total, heads, dh)), jnp.bfloat16)
                for _ in range(3)
            )
            def run(mode):
                def fn(q, k, v):
                    out = ra.ragged_attention(
                        q, k, v, jnp.asarray(seg), pos=jnp.asarray(pos),
                        starts=jnp.asarray(starts), bounds=jnp.asarray(bounds),
                        num_rows=rows, dense_s=dense_s, causal=causal, mode=mode)
                    return jnp.where(jnp.asarray(real)[:, None, None], out, 0)
                return fn
            return run("pallas"), run("reference"), (q, k, v), ATTN_TOL
        return build

    def flash():
        b, s, h, dh = 8, min(128, g.kernel_tokens), enc.num_heads, enc.hidden_dim // enc.num_heads
        q, k, v = (
            jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.bfloat16)
            for _ in range(3)
        )
        mask = jnp.asarray(np.arange(s)[None, :] < rng.integers(s // 2, s + 1, (b, 1)))
        kern = lambda q, k, v, m: fa.flash_attention(q, k, v, kv_mask=m)  # noqa: E731
        ref = lambda q, k, v, m: jax.nn.dot_product_attention(  # noqa: E731
            q, k, v, mask=m[:, None, None, :])
        return kern, ref, (q, k, v, mask), ATTN_TOL

    heads, dh = dec.num_heads, dec.hidden_dim // dec.num_heads
    bs, nb, layers = 16, g.kernel_pool_blocks, 2
    width = -(-dec.max_len // bs)

    def pools():
        shape = (layers, nb, bs, heads, dh)
        return (
            jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
            jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
        )

    def tables(rows: int, lengths: np.ndarray) -> np.ndarray:
        bt = np.zeros((rows, width), np.int32)
        perm = rng.permutation(nb)
        at = 0
        for r in range(rows):
            need = -(-int(lengths[r]) // bs)
            bt[r, :need] = perm[at:at + need]
            at += need
        return bt

    def paged_decode():
        rows = 8
        cap = min(dec.max_len, nb * bs // rows)
        lengths = np.array([1, bs, bs + 1, cap, cap // 2, 0, 37 % cap + 1, cap - 1][:rows], np.int32)
        kp, vp = pools()
        q = jnp.asarray(rng.standard_normal((rows, heads, dh)), jnp.bfloat16)
        args = (q, kp, vp, jnp.asarray(tables(rows, lengths)), jnp.asarray(lengths))
        def run(mode):
            def fn(q, kp, vp, bt, ln):
                out = dk.paged_decode_attention(
                    q, kp, vp, bt, ln, 1, block_size=bs, mode=mode)
                # a length-0 row is inactive: the kernel writes zeros, the
                # reference an average nobody reads
                return jnp.where((ln > 0)[:, None, None], out, 0)
            return fn
        return run("pallas"), run("reference"), args, ATTN_TOL

    def paged_verify():
        rows, K = 4, 16
        cap = min(dec.max_len, nb * bs // rows) - K
        base = np.array([0, bs - 3, cap, cap // 3][:rows], np.int32)
        n_new = np.array([K, K, K // 2, 1][:rows], np.int32)
        kp, vp = pools()
        q = jnp.asarray(rng.standard_normal((rows, K, heads, dh)), jnp.bfloat16)
        live = np.arange(K)[None, :] < n_new[:, None]  # dead lanes are garbage
        args = (q, kp, vp, jnp.asarray(tables(rows, base + K)),
                jnp.asarray(base), jnp.asarray(n_new))
        def run(mode):
            def fn(q, kp, vp, bt, b, nn):
                out = dk.paged_verify_attention(
                    q, kp, vp, bt, b, nn, 1, block_size=bs, mode=mode)
                return jnp.where(jnp.asarray(live)[:, :, None, None], out, 0)
            return fn
        return run("pallas"), run("reference"), args, ATTN_TOL

    eh, edh = enc.num_heads, enc.hidden_dim // enc.num_heads
    return {
        "fused_serving_dense_f32": dense("f32", jnp.float32),
        "fused_serving_dense_bf16": dense("bf16", jnp.bfloat16),
        "fused_serving_int8": quant_fused,
        "quantized_scores": quant_scores,
        "masked_scores": masked_scores,
        "ragged_attention_causal": ragged(True, heads, dh, g.kernel_tokens),
        "ragged_attention_causal_one_block": ragged(True, heads, dh, 64),
        "ragged_attention_noncausal": ragged(False, eh, edh, g.kernel_tokens),
        "flash_attention": flash,
        "paged_decode": paged_decode,
        "paged_verify": paged_verify,
    }


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def verdict_line(summary: dict) -> str:
    """The last line of standard output: ``ok`` and the device as JAX
    reports it, and no other key (the driver parses it strictly; the
    full summary is the line before it)."""
    device = summary["device"]
    return json.dumps({
        "ok": bool(summary["ok"]),
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--mesh", type=int, default=0, metavar="N",
        help="run ingest+retrieve over an N-device serving mesh",
    )
    args = ap.parse_args(argv)
    smoke = Smoke(FULL, mesh_devices=args.mesh)
    try:
        summary = smoke.run()
    except BaseException:
        # a failed phase: say which on stderr, print no result, exit non-zero
        traceback.print_exc()
        done = ", ".join(smoke.summary["phases"]) or "none"
        print(f"chip_smoke FAILED (phases passed: {done})", file=sys.stderr, flush=True)
        sys.stderr.flush()
        os._exit(1)
    line = json.dumps(summary, sort_keys=True)
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    name = f"chip_smoke_mesh{args.mesh}.json" if args.mesh else "chip_smoke.json"
    with open(os.path.join(out_dir, name), "w") as f:
        f.write(line + "\n")
    print(line, flush=True)
    smoke.tmp.cleanup()
    print(verdict_line(summary), flush=True)
    # the servers' threads are daemons of this process; leave without
    # waiting on their event loops
    os._exit(0)


if __name__ == "__main__":
    main()
