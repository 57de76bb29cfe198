"""The document store with the hybrid (attention + state-space) embedder:
``vector_store_laguna.py``'s deployment, whose constructor, documents,
warm-up and own-text queries serve any builder of ``encoders/``, with the
counters (``ssm.*``) and the facts (``ssm_scan_ops``) of a model without
experts.
"""

from __future__ import annotations

from servers import vector_store, vector_store_laguna


class Deployment(vector_store_laguna.Deployment):
    def counters(self) -> dict:
        from pathway_tpu.internals.flight_recorder import ssm_stats

        out = super().counters()
        for name, value in ssm_stats().items():
            out[f"ssm.{name}"] = value
        return out

    def facts(self) -> dict:
        # not ``vector_store_laguna``'s: it reads the builder's grouped product
        out = vector_store.Deployment.facts(self)
        out["document_words"] = [int(w) for w in self.config["document_words"]]
        out["encoder"] = self.builder.sizes(self.config)
        out["encoder_programs"] = list(self.builder.PROGRAMS)
        out["ssm_scan_ops"] = list(self.builder.SSM_SCAN_OPS)
        return out


def start(config: dict, seed: int, workdir: str, log) -> Deployment:
    return Deployment(config, seed, workdir, log)
