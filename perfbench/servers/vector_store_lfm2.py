"""The document store with the LFM2 embedder (gated short convolutions and
grouped-query attention, routed experts): ``vector_store_laguna.py``'s
deployment, whose constructor, documents, warm-up, own-text queries, ``moe.*``
counters and facts serve any builder of ``encoders/`` whose forward has routed
experts, with the counters of what a launch held (``conv.*``: documents, real
tokens, bucket tokens) beside them.
"""

from __future__ import annotations

from servers import vector_store_laguna


class Deployment(vector_store_laguna.Deployment):
    def counters(self) -> dict:
        from pathway_tpu.internals.flight_recorder import conv_stats

        out = super().counters()
        for name, value in conv_stats().items():
            out[f"conv.{name}"] = value
        return out


def start(config: dict, seed: int, workdir: str, log) -> Deployment:
    return Deployment(config, seed, workdir, log)
