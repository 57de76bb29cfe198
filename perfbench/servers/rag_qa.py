"""The question-answering deployment: the retrieval service of
``vector_store.py`` with ``BaseRAGQuestionAnswerer`` over a GPT-2 decoder on
the same dataflow graph, a second web server beside the first, as
``chip_smoke.py`` ``_build_qa`` starts it.  ``POST /v1/pw_ai_answer_stream``.

The decoder's weights are the harness's (``seeded.gpt2_params``); the KV pool,
the answerer's ``search_topk`` and its prompt are the program's defaults.
"""

from __future__ import annotations

import time

import seeded
from servers import vector_store

DECODER_KEYS = {  # configuration key (HF name) -> DecoderConfig argument
    "vocab_size": "vocab_size", "n_embd": "hidden_dim", "n_layer": "num_layers",
    "n_head": "num_heads", "n_inner": "mlp_dim", "n_positions": "max_len",
    "layer_norm_epsilon": "ln_eps",
}


class Deployment(vector_store.Deployment):
    def build_and_run(self) -> None:
        import jax

        from pathway_tpu.models.decoder import CausalLM, DecoderConfig
        from pathway_tpu.xpacks.llm.llms import JaxPipelineChat
        from pathway_tpu.xpacks.llm.question_answering import BaseRAGQuestionAnswerer

        t0 = time.monotonic()
        d = self.config["decoder"]
        self.lm = CausalLM(None, cfg=DecoderConfig(**{DECODER_KEYS[k]: d[k] for k in DECODER_KEYS}))
        params = seeded.decoder_params(self.config, self.seed)
        drawn = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), self.lm.params)
        made = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)
        if drawn != made:
            raise RuntimeError("the decoder's parameter tree is not the one "
                               "perfbench/seeded.py makes")
        self.lm.params = params
        self.log(f"decoder built in {time.monotonic() - t0:.1f}s")
        # one dataflow graph, two web servers
        self.vs.build_server(host="127.0.0.1", port=self.port)
        chat = JaxPipelineChat(model=None, causal_lm=self.lm)
        self.qa = BaseRAGQuestionAnswerer(llm=chat, indexer=self.vs)
        self.qa_port = vector_store.free_port()
        self.qa.build_server(host="127.0.0.1", port=self.qa_port)
        self.qa.server.run(threaded=True, with_cache=False)

    def warm_up(self, traffic: dict) -> None:
        """The answerer's own retrieve plane at the small ticks a few
        answers a second make; prefill, verify and decode shapes are warmed
        by ``warm_s`` seconds of the mix itself (run.py sends them)."""
        self.urls["answer_stream"] = f"http://127.0.0.1:{self.qa_port}/v1/pw_ai_answer_stream"
        import textgen

        plane = self.qa._stream_retrieve_plane()
        k = int(traffic["k"])
        texts = textgen.query_texts(8, self.seed ^ 0x5EED, int(traffic["min_words"]),
                                    int(traffic["max_words"]))
        for b in (1, 2, 4, 8):
            t0 = time.monotonic()
            out = plane._batch([(f"{t} warm{b}", k, None) for t in texts[:b]])
            if any(r["degraded"] for r in out):
                raise RuntimeError("a warm-up batch answered degraded")
            self.log(f"warm-up stream-retrieve bucket {b}: {time.monotonic() - t0:.2f}s")

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
                             ("generation", self.lm.paged_session().breaker)):
            stats = obj.stats()
            out[f"breaker.{breaker}.trips_total"] = stats["trips_total"]
            out[f"breaker.{breaker}.failures_total"] = stats["failures_total"]
        out["runtime.generate.ticks"] = get_runtime().stats()["classes"]["generate"][
            "completed_total"]
        return out

    def facts(self) -> dict:
        out = super().facts()
        session = self.lm.paged_session()
        out["kv_pool_tokens"] = int(session.pool.num_blocks * session.pool.block_size)
        return out

    def free(self) -> None:
        super().free()
        self.lm.params = None
        session = self.lm.paged_session()
        session.params = None
        session.pool.k_pool = session.pool.v_pool = None


def start(config: dict, seed: int, workdir: str, log) -> Deployment:
    return Deployment(config, seed, workdir, log)
