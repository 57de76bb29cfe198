"""The document store with the latent-attention embedder:
``vector_store_laguna.py``'s deployment, whose constructor, documents,
warm-up, own-text queries, ``moe.*`` counters and facts serve any builder of
``encoders/`` whose forward has routed experts, with the counters of what a
launch held (``mla.*``: documents, real tokens, bucket tokens, attention's
pairs) beside them.
"""

from __future__ import annotations

from servers import vector_store_laguna


class Deployment(vector_store_laguna.Deployment):
    def counters(self) -> dict:
        from pathway_tpu.internals.flight_recorder import mla_stats

        out = super().counters()
        for name, value in mla_stats().items():
            out[f"mla.{name}"] = value
        return out


def start(config: dict, seed: int, workdir: str, log) -> Deployment:
    return Deployment(config, seed, workdir, log)
