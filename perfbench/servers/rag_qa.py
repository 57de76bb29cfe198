"""The question-answering deployment: the retrieval service of
``vector_store.py`` with ``BaseRAGQuestionAnswerer``'s streamed answers over a
decoder on the same dataflow graph and the same web server.
``POST /v1/pw_ai_answer_stream`` beside ``/v1/retrieve``, ``/v1/statistics``
and ``/v1/inputs``.

One index: the answerer's streamed route retrieves through the live index the
``VectorStoreServer`` maintains.  The answerer's dataflow routes
(``/v1/pw_ai_answer``, its own ``/v1/retrieve``, ``/v1/pw_ai_summary``) are NOT
served: each lowers a ``DeviceKnnIndex`` of its own at ``reserved_space``, and
three of 4.83 GB do not fit the chip (PERF.md, Open questions).  The cell's
traffic never asks them.

The query-cache stack of the retrieve planes is off (``QUERY_CACHES_OFF``, the
same Open questions).  Both departures from ``qa.build_server`` with the
program's defaults go once the program faults behind them are repaired, and
the cell's rate and limits are then read again: until then they are
provisional (``staged.json``).

The decoder is the one the configuration names (``decoder.builder``, a file of
``decoders/``); it is made after the index is full, because the prefill's
scatters already take the chip to 98%.  Its weights are the harness's; the KV
pool, the answerer's ``search_topk`` and its prompt are the program's defaults.
"""

from __future__ import annotations

import importlib
import os
import time

from servers import vector_store


#: with the caches on, a retrieve tick of exactly three cache-missing
#: questions answers its first from a pad row's embedding on the TPU
#: (``_query_cache.py`` ``_embed_pending``; PERF.md 7 #1).  Read when the
#: planes are built; these lines go with that repair.
QUERY_CACHES_OFF = {"PATHWAY_EMBED_CACHE": "0", "PATHWAY_RESULT_CACHE": "0",
                    "PATHWAY_COLLAB_DEPTH": "0"}


class Deployment(vector_store.Deployment):
    def __init__(self, config: dict, seed: int, workdir: str, log):
        os.environ.update(QUERY_CACHES_OFF)
        super().__init__(config, seed, workdir, log)  # index up and full
        import jax

        t0 = time.monotonic()
        self.lm = self.chat._ensure_lm()
        params = self.decoder.params(config, seed)
        drawn = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), self.lm.params)
        made = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
        if drawn != made:
            raise RuntimeError("the decoder's parameter tree is not the one "
                               f"perfbench/decoders/{config['decoder']['builder']}.py makes")
        self.lm.params = params
        log(f"decoder built in {time.monotonic() - t0:.1f}s")

    def build_and_run(self) -> None:
        from pathway_tpu.xpacks.llm._utils import run_with_cache
        from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer

        self.decoder = importlib.import_module("decoders." + self.config["decoder"]["builder"])
        self.chat = self.decoder.chat(self.config)
        self.vs.build_server(host="127.0.0.1", port=self.port)
        self.qa = BaseRAGQuestionAnswerer(llm=self.chat, indexer=self.vs)
        self.vs._webserver.add_raw_route("/v1/pw_ai_answer_stream", ("GET", "POST"),
                                         self.qa.answer_stream_handler())
        run_with_cache(threaded=True, with_cache=False)

    def warm_up(self, traffic: dict) -> None:
        """The answerer's own retrieve plane at every tick size it can carry
        (a stall of some seconds queues that many questions, and a tick that
        compiled then would turn the stall into a collapse), then the decode
        session's programs; ``warm_s`` seconds of the mix itself follow
        (run.py sends them)."""
        self.urls["answer_stream"] = f"http://127.0.0.1:{self.port}/v1/pw_ai_answer_stream"
        import textgen
        from pathway_tpu.models.encoder import BATCH_BUCKETS

        plane = self.qa._stream_retrieve_plane()
        k = int(traffic["k"])
        top = plane.group.max_batch
        texts = textgen.query_texts(top, self.seed ^ 0x5EED, int(traffic["min_words"]),
                                    int(traffic["max_words"]))
        t0 = time.monotonic()
        for b in (b for b in BATCH_BUCKETS if b <= top):
            out = plane._batch([(f"{t} warm{b}", k, None) for t in texts[:b]])
            if any(r["degraded"] for r in out):
                raise RuntimeError("a warm-up batch answered degraded")
        self.log(f"warm-up stream-retrieve ticks up to {top}: {time.monotonic() - t0:.2f}s")
        self.warm_decode(traffic)

    def warm_decode(self, traffic: dict) -> None:
        """Every program the decode session launches under this mix, through
        its own ``submit``.  A first whole prompt is prefilled and leaves the
        template's first block resident, as in service; from then on every
        prompt adopts that block and its tail rides the multi-token launches,
        16 tokens a tick, beside the rows that decode.  Those programs are
        shaped by the rows of a tick (a power of two, at most what the pool
        holds of the shortest prompts, or ``max_live``) and by the longest
        bundle in it (2, 4, 8 or 16), so each
        pair is driven once: ``rows`` prompts of the block and a tail of 23,
        19 or 18 tokens are ingested in ticks of 16 and then 7, 3 or 2, and
        decode two tokens with the single-token step of that many rows."""
        import random

        import textgen
        from pathway_tpu.xpacks.llm import prompts

        session = self.lm.paged_session()
        docs = [textgen.passage(i, self.seed) for i in range(int(traffic["k"]))]
        whole = self.lm.encode_prompt(prompts.prompt_qa_geometric_rag("warm up", docs))
        t0 = time.monotonic()
        session.submit(whole, max_new_tokens=2).result(timeout=600.0)
        block = session.pool.block_size
        shortest = session.pool.blocks_for(200 + int(traffic["max_new_tokens"]))
        most = min(session.max_live, session.pool.num_blocks // shortest)
        rng = random.Random(self.seed ^ 0x5EED)
        vocab = int(self.config["decoder"]["vocab_size"])
        rows = 1
        while rows < 2 * most:
            for tail in (23, 19, 18):
                handles = [session.submit(
                    whole[:block] + [rng.randrange(vocab) for _ in range(tail)],
                    max_new_tokens=2) for _ in range(rows)]
                for h in handles:
                    h.result(timeout=600.0)
            rows *= 2
        self.log(f"warm-up decode session, up to {rows // 2} rows: {time.monotonic() - t0:.2f}s")

    def counters(self) -> dict:
        from pathway_tpu.internals.monitoring import StatsMonitor
        from pathway_tpu.runtime import get_runtime

        out = super().counters()
        for line in StatsMonitor().openmetrics().splitlines():
            if line.startswith(("pathway_decode_", "pathway_kv_pool_")) and " " in line:
                name, value = line.rsplit(" ", 1)
                if "_bucket{" in name:
                    continue
                try:
                    out["om." + name] = float(value)
                except ValueError:
                    pass
        for breaker, obj in (("llm", self.qa.llm_breaker),
                             ("generation", self.lm.paged_session().breaker),
                             ("retrieve", self.qa._stream_retrieve_plane().breaker)):
            stats = obj.stats()
            out[f"breaker.{breaker}.trips_total"] = stats["trips_total"]
            out[f"breaker.{breaker}.failures_total"] = stats["failures_total"]
        out["runtime.generate.ticks"] = get_runtime().stats()["classes"]["generate"][
            "completed_total"]
        # retrieve ticks by how many questions they carried: one with exactly
        # three cache misses answers its first wrong (PERF.md, Open questions)
        sched = self.qa._stream_retrieve_plane().scheduler.stats()
        out["sched.batches_total"] = sched["batches_total"]
        out["sched.multi_item_batches_total"] = sched["multi_item_batches_total"]
        return out

    def facts(self) -> dict:
        out = super().facts()
        session = self.lm.paged_session()
        out["kv_pool_tokens"] = int(session.pool.num_blocks * session.pool.block_size)
        out["decoder"] = self.decoder.sizes(self.config)
        out["decoder_programs"] = self.decoder.PROGRAMS
        return out

    def free(self) -> None:
        super().free()
        self.lm.params = None
        session = self.lm.paged_session()
        session.params = None
        session.pool.k_pool = session.pool.v_pool = None


def start(config: dict, seed: int, workdir: str, log) -> Deployment:
    return Deployment(config, seed, workdir, log)
