"""Cross-encoder reranker (query, doc) -> relevance score.

TPU replacement for the reference's sentence-transformers CrossEncoder
(xpacks/llm/rerankers.py:186 ``CrossEncoderReranker``): the pair is packed
as ``[CLS] q [SEP] d [SEP]`` through the shared transformer encoder and a
scalar head scores the CLS position; batches are padded to shape buckets and
jit-compiled once per bucket.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from .encoder import (
    EncoderConfig,
    PackedTransformerEncoder,
    TransformerEncoder,
    bucketed_dispatch,
    default_attention_impl,
    named_jit,
)
from .tokenizer import load_tokenizer

__all__ = ["CrossEncoder"]


class _ScoredEncoder(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, ids, mask, type_ids=None):
        hidden = TransformerEncoder(self.cfg, name="encoder")(
            ids, mask, type_ids=type_ids, pool=False
        )
        cls = hidden[:, 0, :].astype(jnp.float32)
        # BERT pooler (tanh dense on CLS) then the classifier head — the
        # exact stack BertForSequenceClassification scores with, so
        # converted HF cross-encoder checkpoints are weight-compatible
        pooled = jnp.tanh(nn.Dense(self.cfg.hidden_dim, name="pooler")(cls))
        return nn.Dense(1, name="score_head")(pooled)[:, 0]


class _PackedScoredEncoder(nn.Module):
    """Ragged-layout twin of :class:`_ScoredEncoder` (identical param
    tree): pairs concatenated along one token axis, ONE launch per
    batch, each row's CLS gathered at its ``starts`` offset."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(self, ids, pos, seg, type_ids, starts, bounds, *, dense_s):
        hidden = PackedTransformerEncoder(self.cfg, name="encoder")(
            ids, pos, seg, starts, bounds, type_ids=type_ids,
            dense_s=dense_s, pool=False,
        )  # [1, T, H]
        cls = hidden[0, starts.astype(jnp.int32), :].astype(jnp.float32)
        pooled = jnp.tanh(nn.Dense(self.cfg.hidden_dim, name="pooler")(cls))
        return nn.Dense(1, name="score_head")(pooled)[:, 0]


class CrossEncoder:
    def __init__(
        self,
        model_name: str | None = None,
        cfg: EncoderConfig | None = None,
        seed: int = 0,
        max_length: int = 256,
        mesh=None,
        max_tokens: int | None = None,
    ):
        import dataclasses

        from .encoder import embed_max_tokens

        # rerank pairs are even more length-skewed than documents (query
        # + doc concatenated): the packed dispatch + token budget apply
        # exactly as in SentenceEncoder
        self.max_tokens = max_tokens if max_tokens is not None else embed_max_tokens()

        self.pretrained = False
        params = None
        impl = (
            cfg.attention_impl if cfg is not None else default_attention_impl()
        )
        if model_name is not None:
            from . import checkpoint

            loaded = checkpoint.load_cross_encoder(model_name)
            if loaded is not None:
                loaded_cfg, params = loaded
                cfg = dataclasses.replace(
                    loaded_cfg,
                    dtype=(cfg or EncoderConfig()).dtype,
                    attention_impl=impl,
                )
                self.pretrained = True
        self.cfg = cfg or EncoderConfig(attention_impl=impl)
        self.max_length = min(max_length, self.cfg.max_len)
        self.tokenizer = load_tokenizer(model_name, vocab_size=self.cfg.vocab_size)
        self.model = _ScoredEncoder(self.cfg)
        if params is not None:
            self.params = jax.tree_util.tree_map(jnp.asarray, params)
        else:
            ids = jnp.zeros((1, 8), jnp.int32)
            self.params = self.model.init(
                jax.random.PRNGKey(seed), ids, jnp.ones_like(ids)
            )["params"]
        # multi-chip reranking: same tp/dp recipe as SentenceEncoder —
        # the sharding rules match the encoder subtree by path name, the
        # pooler column-splits, and XLA inserts the collectives
        self.mesh = mesh
        self._batch_multiple = 1
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.sharding import mesh_setup

            self.params, self._data_sharding, self._batch_multiple = (
                mesh_setup(self.params, mesh)
            )
            self._replicated_sharding = NamedSharding(mesh, PartitionSpec())
        from ..internals.flight_recorder import (
            instrument_jit,
            record_attention_impl,
        )

        record_attention_impl(self.cfg.attention_impl)
        self._apply = instrument_jit(
            named_jit(
                lambda params, ids, mask, tids: self.model.apply(
                    {"params": params}, ids, mask, tids
                ),
                "pw_cross_encoder_forward",
            ),
            "cross_encoder.forward",
        )
        self._packed_model = _PackedScoredEncoder(self.cfg)
        self._apply_ragged = instrument_jit(
            named_jit(
                self._forward_ragged, "pw_cross_encoder_forward_ragged",
                static_argnames=("dense_s",),
            ),
            "cross_encoder.forward_ragged",
        )

    def _forward_ragged(
        self, params, ids, pos, seg, tids, starts, bounds, *, dense_s
    ):
        return self._packed_model.apply(
            {"params": params}, ids, pos, seg, tids, starts, bounds,
            dense_s=dense_s,
        )

    def _predict_ragged(self, ids_all, mask_all, type_ids_all) -> np.ndarray:
        """Ragged rerank dispatch: (query, doc) pairs concatenated along
        one token axis, one launch per token-budget group, scores
        collected in submission order."""
        from ..internals.flight_recorder import record_padding
        from .encoder import ragged_prepare

        prepared, stats = ragged_prepare(
            ids_all, mask_all, self.max_length,
            type_ids_all=type_ids_all,
            vocab_size=self.cfg.vocab_size,
            max_tokens=self.max_tokens,
            cfg=self.cfg,
        )
        record_padding(
            stats["real_tokens"], stats["padded_tokens"], stats["row_tokens"]
        )
        pending = []
        for payload, rows, _tokens in prepared:
            args = payload.device_args(include_type_ids=True)
            if self.mesh is not None:
                args = [
                    jax.device_put(a, self._replicated_sharding) for a in args
                ]
            pending.append(
                (
                    self._apply_ragged(
                        self.params, *args, dense_s=payload.dense_s
                    ),
                    rows,
                )
            )
        out = np.empty((ids_all.shape[0],), dtype=np.float32)
        for res, rows in pending:
            out[rows] = np.asarray(res, dtype=np.float32)[: len(rows)]
        return out

    def predict(self, pairs: Sequence[tuple[str, str]]) -> np.ndarray:
        """Scores for (query, doc) pairs, higher = more relevant."""
        if not pairs:
            return np.zeros((0,), dtype=np.float32)
        queries = [q for q, _ in pairs]
        docs = [d for _, d in pairs]
        ids_all, mask_all, type_ids_all = self.tokenizer.encode_batch(
            queries, max_length=self.max_length, pair=docs, return_type_ids=True
        )
        if self.cfg.attention_impl == "ragged":
            return self._predict_ragged(ids_all, mask_all, type_ids_all)

        def dispatch(ids, mask, tids):
            if self.mesh is not None:
                # the one shard-vs-replicate rule shared with
                # SentenceEncoder (encoder.pick_input_sharding)
                from .encoder import pick_input_sharding

                sharding = pick_input_sharding(
                    ids.shape[0], self._batch_multiple,
                    self._data_sharding, self._replicated_sharding,
                )
                ids = jax.device_put(ids, sharding)
                mask = jax.device_put(mask, sharding)
                tids = jax.device_put(tids, sharding)
            return self._apply(self.params, ids, mask, tids)

        return bucketed_dispatch(
            dispatch,
            ids_all,
            mask_all,
            self.max_length,
            type_ids_all=type_ids_all,
            vocab_size=self.cfg.vocab_size,
            batch_multiple=self._batch_multiple,
            max_tokens=self.max_tokens,
        )

    def __call__(self, query: str, doc: str) -> float:
        return float(self.predict([(query, doc)])[0])
