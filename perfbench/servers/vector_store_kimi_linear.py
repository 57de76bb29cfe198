"""The document store with the Kimi-Linear embedder (Kimi Delta Attention and
latent attention, a held share of the routed experts): ``vector_store_joyai.py``'s
deployment, whose ``moe.*`` counters and the latent family's ``mla.*`` beside
them (documents, real tokens, bucket tokens, attention's pairs: the forward
lays them out because it has a latent layer) serve this forward too, with the
scan's kernel name among the facts (``kda_scan_ops``).  The delta rule's scan
walks a launch's whole bucket, so the ``mla.*`` tokens and bucket tokens are
its own as well.
"""

from __future__ import annotations

from servers import vector_store_joyai


class Deployment(vector_store_joyai.Deployment):
    def facts(self) -> dict:
        out = super().facts()
        out["kda_scan_ops"] = list(self.builder.KDA_SCAN_OPS)
        return out


def start(config: dict, seed: int, workdir: str, log) -> Deployment:
    return Deployment(config, seed, workdir, log)
