"""Flax transformer sentence encoder (MiniLM-class).

TPU-native replacement for the reference's in-UDF torch model
(xpacks/llm/embedders.py:270 ``SentenceTransformerEmbedder`` running
sentence-transformers/all-MiniLM-L6-v2 on CPU/GPU).

Design for the MXU/HBM:
* bf16 activations + f32 layernorm/softmax accumulation;
* static shapes only — sequence lengths bucketed to powers of two and
  batches padded, so each (batch_bucket, seq_bucket) pair compiles once;
* masked mean pooling + L2 norm fused into the jitted forward;
* parameters shardable over a mesh (see parallel/sharding.py for the
  tp/dp partition specs used by the multi-chip path).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, ClassVar, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from .tokenizer import HashTokenizer, load_tokenizer

__all__ = [
    "EncoderConfig",
    "TransformerEncoder",
    "PackedTransformerEncoder",
    "SentenceEncoder",
    "packed_plan",
    "packed_prepare",
    "embed_max_tokens",
    "default_attention_impl",
    "ragged_plan",
    "ragged_prepare",
    "RaggedChunk",
    "TOKEN_BUCKETS",
]

SEQ_BUCKETS = (32, 64, 128, 256, 512)
# large top buckets matter: fewer, bigger launches amortize per-dispatch
# latency and fill the MXU.  Small buckets matter too: serving-scheduler ticks carry 1-8 queries, and
# padding a 2-query tick to batch 8 is free on the MXU but real compute on
# the CPU backend (measured 74 ms vs 25 ms for MiniLM at seq 128) — the
# 2/4 steps keep low-occupancy ticks pay-for-what-you-use at the cost of
# two extra compiles per sequence bucket
BATCH_BUCKETS = (1, 2, 4, 8, 32, 128, 256, 512, 1024)
#: a group of rows goes out in exact-fill launches while this many remain
#: (``_chunk_sizes``); what is left, or a group that never had as many, is
#: its tail
TAIL_ROWS = 32
#: launch sizes for the packed token axis: fine 128-token steps (the
#: ragged kernel's block) up to 4096, then 512 steps to the VMEM cap —
#: a FINITE shape set (the compile-flatness pin), with only tail-block
#: alignment as padding (<=3% at any size, <1% amortized on full
#: launches).  The 32/64 sub-block buckets keep a 1-row tick from
#: padding to a full 128-token block.
TOKEN_BUCKETS: tuple[int, ...] = (
    (32, 64)
    + tuple(range(128, 4096 + 1, 128))
    + tuple(range(4608, 8192 + 1, 512))
)


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """all-MiniLM-L6-v2 geometry by default."""

    vocab_size: int = 30522
    hidden_dim: int = 384
    num_layers: int = 6
    num_heads: int = 12
    mlp_dim: int = 1536
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    emb_dim: int | None = None  # pooled output dim; defaults to hidden_dim
    #: BERT checkpoint conventions (exact values matter for weight parity
    #: with converted HF checkpoints, models/checkpoint.py)
    ln_eps: float = 1e-12
    type_vocab_size: int = 2
    #: attention kernel: "flax" (flax's unfused einsum chain — the
    #: golden-parity reference), "fused" (jax.nn.dot_product_attention,
    #: one XLA custom-call the compiler fuses QK^T→softmax→AV through —
    #: no S² intermediate round-trips to HBM), "pallas" (our explicit
    #: flash-style TPU kernel, ops/flash_attention.py), or "ragged"
    #: (packed ragged-batch dispatch: rows concatenated along one token
    #: axis with segment ids, ONE Pallas launch per tick through
    #: ops/ragged_attention.py, near-zero padding).  Process default via
    #: PATHWAY_ATTENTION_IMPL (see :func:`default_attention_impl`).
    attention_impl: str = "flax"
    #: sequence buckets of the dispatch grid and the dtype the parameters
    #: are held in: the model's own, because a language-model embedder
    #: takes documents of thousands of tokens and cannot hold float32
    #: weights (models/causal_moe_embedder.py)
    seq_buckets: tuple[int, ...] = SEQ_BUCKETS
    param_dtype: Any = jnp.float32
    #: row counts a launch may take, the model's own like the sequence
    #: buckets: every (rows, sequence) pair is one compiled program, which a
    #: bulk load amortises for a BERT and nothing does for a model of
    #: gigabytes (models/causal_moe_embedder.py)
    batch_buckets: tuple[int, ...] = BATCH_BUCKETS
    #: token counts a packed (``attention_impl="ragged"``) launch is padded
    #: to; the last is the most one launch holds.  The model's own too: the
    #: BERT's follow its kernel's 128-token block, a language-model
    #: embedder's are few because each is a program of seconds
    token_buckets: tuple[int, ...] = TOKEN_BUCKETS

    #: what :class:`SentenceEncoder` asks of any encoder config: the name
    #: of its jitted programs in a device trace, its two forwards, and how
    #: its packed launches are laid out
    program_name: ClassVar[str] = "pw_encoder_forward"
    #: off the TPU the packed attention unpacks a launch's rows to a dense
    #: [rows, sequence bucket] shape: the launch carries that bucket
    #: (``dense_s``, part of the program's key), and whether rows of
    #: different buckets may share it is :func:`ragged_mixes_buckets`'s call
    packed_unpacks_rows: ClassVar[bool] = True
    #: whether the first packed dispatch launches every token bucket once
    #: on padding (42 token buckets by nine row counts are loaded on demand)
    warm_packed: ClassVar[bool] = False

    @property
    def packed_row_buckets(self) -> tuple[int, ...]:
        """Row counts a packed launch's ``starts`` is padded to; the last
        is the most rows one launch takes."""
        return self.batch_buckets

    def build_models(self):
        """(dense [batch, seq] forward, packed ragged forward) over one
        parameter tree."""
        return TransformerEncoder(self), PackedTransformerEncoder(self)


def _fused_attention_fn(query, key, value, bias=None, mask=None, **_kw):
    """flax ``attention_fn`` adapter over :func:`jax.nn.dot_product_attention`
    (VERDICT r3 #2: MFU — keep the S×S attention intermediates out of HBM).
    flax does not pre-scale the query when a custom fn is supplied;
    dot_product_attention applies 1/sqrt(head_dim) itself."""
    return jax.nn.dot_product_attention(query, key, value, bias=bias, mask=mask)


def _pallas_attention_fn(query, key, value, bias=None, mask=None, **_kw):
    """flax ``attention_fn`` adapter over our Pallas flash kernel
    (ops/flash_attention.py).  The encoder's mask is padding-only
    ([batch, 1, 1, kv] broadcast), so it reduces to a per-key bool."""
    from ..ops.flash_attention import flash_attention

    if bias is not None:
        # the kernel has no bias term; computing without it would be
        # silently wrong — refuse loudly like the mask-shape check below
        raise ValueError(
            "attention_impl='pallas' does not support an attention bias"
        )
    kv_mask = None
    if mask is not None:
        if mask.ndim != 4 or mask.shape[-2] != 1:
            # a causal/pairwise mask varies along q; collapsing it to one
            # key row would be silently wrong — refuse loudly
            raise ValueError(
                "attention_impl='pallas' supports padding-only masks "
                f"([batch, 1, 1, kv]); got shape {mask.shape}"
            )
        # [batch, 1, 1, kv] (or broadcastable) → [batch, kv]
        kv_mask = jnp.broadcast_to(
            mask, (query.shape[0], 1, 1, key.shape[1])
        )[:, 0, 0, :]
    return flash_attention(query, key, value, kv_mask=kv_mask)


_ATTENTION_FNS = {
    "flax": None,
    "fused": _fused_attention_fn,
    "pallas": _pallas_attention_fn,
    # "ragged" selects the packed-layout forward (PackedTransformerEncoder
    # + ops/ragged_attention.py); when the DENSE model is applied anyway
    # (the sequence-parallel ring path for over-cap documents, direct
    # bench probes of `_apply`) it degrades to the fused XLA kernel —
    # same numerics, no packed layout required
    "ragged": _fused_attention_fn,
}


def default_attention_impl() -> str:
    """Process-default attention implementation
    (``PATHWAY_ATTENTION_IMPL``: flax | fused | pallas | ragged).
    Applied when an encoder is built without an explicit config; a
    garbage value warns and falls back to the flax golden path."""
    raw = os.environ.get("PATHWAY_ATTENTION_IMPL", "").strip().lower()
    if not raw:
        return "flax"
    if raw in _ATTENTION_FNS:
        return raw
    import warnings

    warnings.warn(
        f"PATHWAY_ATTENTION_IMPL={raw!r} is not one of "
        f"{sorted(_ATTENTION_FNS)} — using 'flax'",
        stacklevel=2,
    )
    return "flax"


class Block(nn.Module):
    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x, mask):
        cfg = self.cfg
        attn_kwargs = {}
        fn = _ATTENTION_FNS[cfg.attention_impl]
        if fn is not None:
            attn_kwargs["attention_fn"] = fn
        h = nn.MultiHeadDotProductAttention(
            num_heads=cfg.num_heads,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="attention",
            **attn_kwargs,
        )(x, x, mask=mask)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.ln_eps, name="ln1")(x + h)
        h = nn.Dense(cfg.mlp_dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp_in")(x)
        h = nn.gelu(h, approximate=False)  # BERT's erf gelu (HF ACT2FN["gelu"])
        h = nn.Dense(cfg.hidden_dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp_out")(h)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.ln_eps, name="ln2")(x + h)
        return x


class TransformerEncoder(nn.Module):
    """BERT-style encoder with masked mean pooling."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(self, ids, mask, type_ids=None, pool: bool = True):
        cfg = self.cfg
        # callers transfer narrow dtypes (u16 ids / u8 masks) to cut
        # host↔device bytes; widen on device where it is free
        ids = ids.astype(jnp.int32)
        mask = mask.astype(jnp.int32)
        if type_ids is not None:
            type_ids = type_ids.astype(jnp.int32)
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, param_dtype=cfg.param_dtype, name="tok_emb"
        )(ids).astype(cfg.dtype)
        pos = nn.Embed(
            cfg.max_len, cfg.hidden_dim, param_dtype=cfg.param_dtype, name="pos_emb"
        )(jnp.arange(ids.shape[1])[None, :]).astype(cfg.dtype)
        x = x + pos
        if cfg.type_vocab_size:
            if type_ids is None:
                type_ids = jnp.zeros_like(ids)
            x = x + nn.Embed(
                cfg.type_vocab_size, cfg.hidden_dim, param_dtype=cfg.param_dtype,
                name="type_emb",
            )(type_ids).astype(cfg.dtype)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.ln_eps, name="ln_emb")(x)
        attn_mask = mask[:, None, None, :].astype(bool)
        for i in range(cfg.num_layers):
            x = Block(cfg, name=f"layer_{i}")(x, attn_mask)
        if not pool:
            return x
        m = mask[:, :, None].astype(jnp.float32)
        pooled = jnp.sum(x.astype(jnp.float32) * m, axis=1) / jnp.maximum(
            jnp.sum(m, axis=1), 1.0
        )
        if cfg.emb_dim is not None and cfg.emb_dim != cfg.hidden_dim:
            pooled = nn.Dense(cfg.emb_dim, dtype=jnp.float32, name="proj")(pooled)
        # L2 normalize (sentence-transformers convention)
        norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / jnp.maximum(norm, 1e-12)


def _ragged_attention_fn(
    query, key, value, bias=None, mask=None, *,
    seg, pos, starts, bounds, num_rows, dense_s, **_kw,
):
    """flax ``attention_fn`` adapter over the packed ragged kernel
    (ops/ragged_attention.py).  ``query`` is ``[1, T, heads, dh]`` —
    the packed token axis has no batch dim; segment ids carry the row
    structure, so a padding mask is meaningless here."""
    from ..ops.ragged_attention import ragged_attention

    if bias is not None or mask is not None:
        raise ValueError(
            "attention_impl='ragged' encodes row boundaries in segment "
            "ids; bias/mask terms are not supported"
        )
    out = ragged_attention(
        query[0], key[0], value[0], seg,
        pos=pos, starts=starts, bounds=bounds,
        num_rows=num_rows, dense_s=dense_s,
    )
    return out[None]


class PackedBlock(nn.Module):
    """One transformer layer over the packed ragged layout — the exact
    parameter tree of :class:`Block` (attention/ln1/mlp_in/mlp_out/ln2),
    so the two forwards share one checkpoint."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(self, x, seg, pos, starts, bounds, num_rows, dense_s):
        cfg = self.cfg
        fn = functools.partial(
            _ragged_attention_fn, seg=seg, pos=pos, starts=starts,
            bounds=bounds, num_rows=num_rows, dense_s=dense_s,
        )
        h = nn.MultiHeadDotProductAttention(
            num_heads=cfg.num_heads,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="attention",
            attention_fn=fn,
        )(x, x)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.ln_eps, name="ln1")(x + h)
        h = nn.Dense(cfg.mlp_dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp_in")(x)
        h = nn.gelu(h, approximate=False)
        h = nn.Dense(cfg.hidden_dim, dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="mlp_out")(h)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.ln_eps, name="ln2")(x + h)
        return x


class PackedTransformerEncoder(nn.Module):
    """BERT-style encoder over a PACKED RAGGED batch: rows concatenated
    along one token axis (segment ids mark boundaries), ONE launch per
    batch, per-token compute with zero intra-row padding, and masked
    mean pooling done SEGMENT-WISE on device (``jax.ops.segment_sum``
    over the row bucket — pad-tail tokens carry an out-of-bounds segment
    id and drop structurally).

    Parameter tree is IDENTICAL to :class:`TransformerEncoder` (tok_emb,
    pos_emb, type_emb, ln_emb, layer_i.*, proj), so the same params /
    checkpoints serve both layouts."""

    cfg: EncoderConfig

    @nn.compact
    def __call__(
        self, ids, pos, seg, starts, bounds, type_ids=None, *,
        dense_s: int, pool: bool = True,
    ):
        cfg = self.cfg
        # callers transfer narrow dtypes (u16 ids/pos/seg) to cut
        # host↔device bytes; widen on device where it is free
        ids = ids.astype(jnp.int32)
        pos = pos.astype(jnp.int32)
        seg = seg.astype(jnp.int32)
        num_rows = starts.shape[0]
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_dim, param_dtype=cfg.param_dtype, name="tok_emb"
        )(ids[None, :]).astype(cfg.dtype)
        x = x + nn.Embed(
            cfg.max_len, cfg.hidden_dim, param_dtype=cfg.param_dtype, name="pos_emb"
        )(pos[None, :]).astype(cfg.dtype)
        if cfg.type_vocab_size:
            tids = (
                jnp.zeros_like(ids) if type_ids is None
                else type_ids.astype(jnp.int32)
            )
            x = x + nn.Embed(
                cfg.type_vocab_size, cfg.hidden_dim, param_dtype=cfg.param_dtype,
                name="type_emb",
            )(tids[None, :]).astype(cfg.dtype)
        x = nn.LayerNorm(dtype=jnp.float32, epsilon=cfg.ln_eps, name="ln_emb")(x)
        for i in range(cfg.num_layers):
            x = PackedBlock(cfg, name=f"layer_{i}")(
                x, seg, pos, starts, bounds, num_rows, dense_s
            )
        if not pool:
            return x  # [1, T, H] packed hidden states
        # segment-wise masked mean pooling: pad tokens (seg == num_rows)
        # are out of bounds for the scatter-add and drop silently — no
        # mask multiply, no 0/0 (pad ROWS pool to the zero vector)
        xf = x[0].astype(jnp.float32)
        sums = jax.ops.segment_sum(xf, seg, num_segments=num_rows)
        counts = jax.ops.segment_sum(
            jnp.ones((xf.shape[0],), jnp.float32), seg, num_segments=num_rows
        )
        pooled = sums / jnp.maximum(counts[:, None], 1.0)
        if cfg.emb_dim is not None and cfg.emb_dim != cfg.hidden_dim:
            pooled = nn.Dense(cfg.emb_dim, dtype=jnp.float32, name="proj")(pooled)
        norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / jnp.maximum(norm, 1e-12)


def _bucket(value: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if value <= b:
            return b
    return buckets[-1]


def pick_input_sharding(batch: int, multiple: int, data_sharding, replicated_sharding):
    """Placement half of :func:`round_batch_to_multiple`'s policy: a
    batch that divides the data axis shards over it, anything else
    dispatches replicated.  Shared by SentenceEncoder and CrossEncoder
    so the two dispatch paths cannot drift."""
    if multiple > 1 and batch % multiple == 0:
        return data_sharding
    return replicated_sharding


def round_batch_to_multiple(bb: int, multiple: int) -> int:
    """THE shard-vs-replicate batch policy, in one place: a launch
    at/above the mesh's data-axis width rounds up to a dividing multiple
    (its batch dim shards over the axis); a smaller launch keeps its
    natural bucket and dispatches replicated instead — padding a 1-query
    serving tick to an 8-row launch is free on one MXU but 8x real
    compute when each pad row occupies a different chip for nothing.
    ``_input_sharding`` is the placement half of the same rule."""
    if multiple > 1 and bb >= multiple:
        return bb + (multiple - bb % multiple) % multiple
    return bb


def pad_chunk(
    ids,
    mask,
    bb: int,
    seq: int,
    type_ids=None,
    ids_dtype=np.int32,
):
    """Pad one (chunk, seq') slice to the (bb, seq) bucket shape with the
    dispatch dtypes.  This is THE padding protocol compiled executables are
    keyed on — external callers (bench.py's compute-only probe) reuse it so
    they hit the same cached executable instead of re-deriving the rules."""
    chunk = ids.shape[0]
    out_ids = np.zeros((bb, seq), ids_dtype)
    out_mask = np.zeros((bb, seq), np.uint8)
    out_ids[:chunk] = ids[:, :seq]
    out_mask[:chunk] = mask[:, :seq]
    out_mask[chunk:, 0] = 1  # avoid 0/0 in pooling for pad rows
    out_tids = None
    if type_ids is not None:
        out_tids = np.zeros((bb, seq), np.uint8)
        out_tids[:chunk] = type_ids[:, :seq]
    return out_ids, out_mask, out_tids


def dispatch_dtype(vocab_size: int):
    """ids dtype rule shared by the dispatch path and external probes:
    u16 halves wire bytes whenever the vocab fits, else i32 (large-vocab
    checkpoints, e.g. multilingual with 250k ids: a u16 buffer would
    silently wrap their ids).  The choice keys on the model's vocab, not
    batch content, so the compiled shape/dtype is stable across batches."""
    return np.uint16 if vocab_size <= 1 << 16 else np.int32


def embed_max_tokens() -> int | None:
    """Process-default token budget per device dispatch
    (``PATHWAY_EMBED_MAX_TOKENS``, unset = batch-bucket sizing only)."""
    raw = os.environ.get("PATHWAY_EMBED_MAX_TOKENS", "").strip()
    if not raw:
        return None
    try:
        n = int(raw)
    except ValueError:
        return None
    return n if n > 0 else None


def _chunk_sizes(
    n: int, seq: int, batch_multiple: int, max_tokens: int | None,
    batch_buckets: Sequence[int] = BATCH_BUCKETS,
    lone_tail: bool = False,
) -> list[int]:
    """Batch-bucket decomposition of an ``n``-row group at seq bucket
    ``seq``: exact-fill with the largest admissible bucket while at least
    32 rows remain (a 300-row group becomes 256+32+pad instead of one
    512-padded launch), then one padded launch for the small tail (the
    1/2/4/8 buckets exist precisely to keep tiny groups cheap).  A token
    budget caps the bucket at ``max_tokens // seq`` so batch size adapts
    to document length.  With ``lone_tail`` the tail goes out in launches
    of the smallest bucket instead, one row each: the programs a lone row
    has compiled, whatever else arrived with it."""
    allowed = list(batch_buckets)
    if max_tokens is not None:
        cap = max(max_tokens // max(seq, 1), 1)
        capped = [b for b in allowed if b <= cap]
        allowed = capped or allowed[:1]
    out: list[int] = []
    remaining = n
    while remaining >= TAIL_ROWS and allowed[-1] >= TAIL_ROWS:
        bb = max(b for b in allowed if b <= remaining) if remaining >= allowed[0] else allowed[0]
        if bb < TAIL_ROWS:
            break
        out.append(bb)
        remaining -= bb
    if lone_tail and remaining < TAIL_ROWS:
        allowed = allowed[:1]
    while remaining > 0:
        bb = _bucket(remaining, allowed)
        out.append(bb)
        remaining -= min(bb, remaining)
    if batch_multiple > 1:
        out = [round_batch_to_multiple(bb, batch_multiple) for bb in out]
    return out


def packed_plan(
    lengths,
    max_length: int,
    batch_multiple: int = 1,
    max_tokens: int | None = None,
    seq_buckets: Sequence[int] = SEQ_BUCKETS,
    batch_buckets: Sequence[int] = BATCH_BUCKETS,
    lone_tail: bool = False,
) -> list[tuple[int, int, np.ndarray]]:
    """Packing plan for per-row token counts: rows grouped by their OWN
    seq bucket (not the batch max), each group chunked to batch buckets.
    Returns ``(seq, bb, row_indices)`` triples; row order inside a group
    preserves submission order so results re-zip deterministically."""
    lengths = np.asarray(lengths)
    groups: dict[int, list[int]] = {}
    for i, ln in enumerate(lengths):
        seq = min(_bucket(max(int(ln), 1), seq_buckets), max_length)
        groups.setdefault(seq, []).append(i)
    plan: list[tuple[int, int, np.ndarray]] = []
    for seq in sorted(groups):
        rows = np.asarray(groups[seq], dtype=np.int64)
        start = 0
        for bb in _chunk_sizes(
            len(rows), seq, batch_multiple, max_tokens, batch_buckets,
            lone_tail,
        ):
            take = min(bb, len(rows) - start)
            plan.append((seq, bb, rows[start : start + take]))
            start += take
            if start >= len(rows):
                break
    return plan


def packed_prepare(
    ids_all,
    mask_all,
    max_length: int,
    type_ids_all=None,
    vocab_size: int = 1 << 31,
    batch_multiple: int = 1,
    max_tokens: int | None = None,
    seq_buckets: Sequence[int] = SEQ_BUCKETS,
    batch_buckets: Sequence[int] = BATCH_BUCKETS,
    lone_tail: bool = False,
) -> tuple[list[tuple], dict]:
    """Host half of the packed dispatch: tokenized rows → padded
    ``(ids, mask, tids, rows)`` chunks ready for device transfer, plus
    padding-efficiency stats.  Split out so a pipeline worker can run it
    one batch ahead of the device (tokenize/pack(N+1) overlaps encode(N))."""
    lengths = np.asarray(mask_all.sum(axis=1), dtype=np.int64)
    ids_dtype = dispatch_dtype(vocab_size)
    prepared: list[tuple] = []
    padded_tokens = 0
    row_tokens = 0
    for seq, bb, rows in packed_plan(
        lengths, max_length, batch_multiple, max_tokens, seq_buckets,
        batch_buckets, lone_tail,
    ):
        ids, mask, tids = pad_chunk(
            ids_all[rows][:, :seq],
            mask_all[rows][:, :seq],
            bb,
            seq,
            type_ids=None if type_ids_all is None else type_ids_all[rows][:, :seq],
            ids_dtype=ids_dtype,
        )
        prepared.append((ids, mask, tids, rows))
        padded_tokens += bb * seq
        row_tokens += len(rows) * seq
    stats = {
        "rows": int(len(lengths)),
        "real_tokens": int(lengths.sum()),
        "padded_tokens": int(padded_tokens),
        # real rows × their seq bucket: the intra-bucket share of the
        # padding accounting (real/row = token padding inside buckets,
        # row/padded = pad-row + tail waste) — see flight_recorder
        "row_tokens": int(row_tokens),
    }
    return prepared, stats


# ---------------------------------------------------------------------------
# packed RAGGED dispatch (attention_impl="ragged"): rows concatenated along
# one token axis, ONE launch per tick, near-zero padding
# ---------------------------------------------------------------------------

class RaggedChunk:
    """One prepared ragged launch: rows concatenated along the token
    axis.  ``ids``/``pos``/``seg`` are per-token (pad tail carries
    ``seg == num_rows``); ``starts`` is the per-row token offset (the
    CLS position — cross-encoder scoring gathers it); ``bounds`` is the
    per-q-block kv block range for the Pallas kernel
    (ops/ragged_attention.ragged_bounds); ``dense_s`` is the seq bucket
    the XLA reference unpacks to off-TPU (None for a model whose packed
    attention never unpacks)."""

    __slots__ = ("ids", "pos", "seg", "type_ids", "starts", "bounds", "dense_s")

    def __init__(self, ids, pos, seg, type_ids, starts, bounds, dense_s):
        self.ids = ids
        self.pos = pos
        self.seg = seg
        self.type_ids = type_ids
        self.starts = starts
        self.bounds = bounds
        self.dense_s = dense_s

    def device_args(self, include_type_ids: bool = False) -> list:
        """THE launch argument marshalling, in one place (the forward's
        positional order) — SentenceEncoder, CrossEncoder and the bench
        probes all launch through this so a new field can't be threaded
        through one site and missed at another."""
        args = [jnp.asarray(self.ids), jnp.asarray(self.pos),
                jnp.asarray(self.seg)]
        if include_type_ids:
            args.append(jnp.asarray(self.type_ids))
        args += [jnp.asarray(self.starts), jnp.asarray(self.bounds)]
        return args


def ragged_mixes_buckets() -> bool:
    """Whether one ragged launch may mix rows from different seq buckets.

    On TPU — or when the Pallas kernel is forced — yes: the kernel's
    block-skipping makes mixed-length launches cheap, and ONE launch per
    tick is the whole point.  Under the XLA reference (off-TPU), a mixed
    launch would unpack EVERY row to the longest row's seq bucket for
    the attention stage, paying 2-6x the packed path's attention pairs
    on short rows — so the plan groups rows by their own seq bucket
    first (attention cost then matches the packed path exactly, and the
    per-token 96% of the FLOPs still runs unpadded on the ragged axis).
    Numerics are identical either way; this is purely launch geometry."""
    from ..ops.ragged_attention import kernel_mode

    mode = kernel_mode()
    if mode == "auto":
        return jax.default_backend() == "tpu"
    return mode == "pallas"


def ragged_plan(
    lengths,
    max_length: int,
    max_tokens: int | None = None,
    mix_buckets: bool | None = None,
    cfg: Any = None,
) -> list[np.ndarray]:
    """Launch plan for the ragged layout: rows greedily packed until the
    token budget (``max_tokens``, capped by the largest token bucket) or
    the row bucket ceiling.  With ``mix_buckets`` rows pack in submission
    order into ONE launch per budget window; without it rows group by
    their own seq bucket first (the XLA reference's attention-cost guard,
    :func:`ragged_mixes_buckets`).  Row order inside a group preserves
    submission order so results re-zip deterministically.  The buckets,
    the row ceiling and (where ``mix_buckets`` is None) whether lengths
    mix are the encoder config's (``cfg``; None: an ``EncoderConfig``'s)."""
    cfg = cfg or EncoderConfig()
    seq_buckets, row_buckets = cfg.seq_buckets, cfg.packed_row_buckets
    if mix_buckets is None:
        mix_buckets = not cfg.packed_unpacks_rows or ragged_mixes_buckets()
    # same row cap as the bucketed dispatch: sequences truncate at the
    # largest seq bucket (over-cap documents go sequence-parallel via
    # the ring path, never through a single-device launch)
    lengths = np.minimum(
        np.maximum(np.asarray(lengths, dtype=np.int64), 1),
        min(max_length, seq_buckets[-1]),
    )
    cap = cfg.token_buckets[-1]
    if max_tokens is not None:
        cap = min(int(max_tokens), cap)
    # a single row must always fit (its length is bounded by the seq cap)
    cap = max(cap, int(lengths.max()) if len(lengths) else 1)
    groups: list[np.ndarray] = []
    if mix_buckets:
        # one launch per token-budget window, submission order preserved
        rows = np.arange(len(lengths), dtype=np.int64)
        start = 0
        total = 0
        for j, r in enumerate(rows):
            if j > start and (
                total + int(lengths[r]) > cap
                or j - start >= row_buckets[-1]
            ):
                groups.append(rows[start:j])
                start, total = j, 0
            total += int(lengths[r])
        if start < len(rows):
            groups.append(rows[start:])
        return groups
    # reference-mode plan: group by seq bucket, then chunk each group on
    # the row-bucket grid exactly like the packed path (_chunk_sizes)
    # — so the attention unpack's [row_bucket, seq_bucket] shape carries
    # no pad rows (a 64-row group must not round to a 128-row unpack)
    by_bucket: dict[int, list[int]] = {}
    for i, ln in enumerate(lengths):
        seq = min(_bucket(int(ln), seq_buckets), max_length)
        by_bucket.setdefault(seq, []).append(i)
    for seq in sorted(by_bucket):
        rows = np.asarray(by_bucket[seq], dtype=np.int64)
        # bb*seq bounds the chunk's real tokens, so the VMEM/budget cap
        # holds a fortiori on the ragged axis
        start = 0
        for bb in _chunk_sizes(len(rows), seq, 1, cap, row_buckets):
            take = min(bb, len(rows) - start)
            groups.append(rows[start : start + take])
            start += take
            if start >= len(rows):
                break
    return groups


def ragged_chunk(rows, lengths, ids_all, type_ids_all, max_length: int,
                 ids_dtype, cfg: Any, tokens: int = 0) -> "RaggedChunk":
    """One launch of ``rows`` (indices into ``ids_all``) laid out along the
    token axis, padded to ``cfg``'s token and row buckets; at least to the
    bucket of ``tokens`` (no rows at all: a launch of padding alone, which
    warms that bucket's program)."""
    from ..ops.ragged_attention import ragged_block, ragged_bounds

    t_bucket = _bucket(max(int(lengths[rows].sum()), tokens), cfg.token_buckets)
    n_rows = _bucket(len(rows), cfg.packed_row_buckets)
    dense_s = None
    if cfg.packed_unpacks_rows:
        longest = int(lengths[rows].max()) if len(rows) else 1
        dense_s = min(_bucket(longest, cfg.seq_buckets), max_length)
    ids = np.zeros(t_bucket, ids_dtype)
    pos = np.zeros(t_bucket, np.uint16)
    seg = np.full(t_bucket, n_rows, np.uint16)  # pad tail: OOB segment
    tids = None if type_ids_all is None else np.zeros(t_bucket, np.uint8)
    starts = np.zeros(n_rows, np.int32)
    cu = np.zeros(len(rows) + 1, np.int64)
    off = 0
    for j, r in enumerate(rows):
        ln = int(lengths[r])
        ids[off : off + ln] = ids_all[r, :ln]
        pos[off : off + ln] = np.arange(ln, dtype=np.uint16)
        seg[off : off + ln] = j
        if tids is not None:
            tids[off : off + ln] = type_ids_all[r, :ln]
        starts[j] = off
        off += ln
        cu[j + 1] = off
    bounds = ragged_bounds(cu, t_bucket, ragged_block(t_bucket))
    return RaggedChunk(ids, pos, seg, tids, starts, bounds, dense_s)


def ragged_prepare(
    ids_all,
    mask_all,
    max_length: int,
    type_ids_all=None,
    vocab_size: int = 1 << 31,
    max_tokens: int | None = None,
    mix_buckets: bool | None = None,
    cfg: Any = None,
) -> tuple[list[tuple], dict]:
    """Host half of the ragged dispatch: tokenized rows → packed
    ``(RaggedChunk, rows, tokens)`` launches plus padding stats.  Every
    row occupies exactly its own length on the token axis (intra-bucket
    token padding is structurally zero — ``row_tokens == real_tokens``);
    only the tail block's bucket alignment pads."""
    cfg = cfg or EncoderConfig()
    lengths = np.minimum(
        np.maximum(np.asarray(mask_all.sum(axis=1), dtype=np.int64), 1),
        min(max_length, cfg.seq_buckets[-1]),
    )
    ids_dtype = dispatch_dtype(vocab_size)
    prepared: list[tuple] = []
    padded_tokens = 0
    for rows in ragged_plan(lengths, max_length, max_tokens, mix_buckets, cfg):
        chunk = ragged_chunk(rows, lengths, ids_all, type_ids_all, max_length,
                             ids_dtype, cfg)
        prepared.append((chunk, rows, chunk.ids.shape[0]))
        padded_tokens += chunk.ids.shape[0]
    real = int(lengths.sum())
    stats = {
        "rows": int(len(lengths)),
        "real_tokens": real,
        "padded_tokens": int(padded_tokens),
        "row_tokens": real,  # rows occupy exactly their length
    }
    return prepared, stats


def _dispatch_prepared(apply_fn, prepared) -> list[tuple[Any, np.ndarray]]:
    """Device half: launch every prepared chunk (JAX async dispatch queues
    them back-to-back) and return ``(device_result, rows)`` pairs WITHOUT
    syncing — the caller decides host collection vs device-resident use."""
    pending = []
    for ids, mask, tids, rows in prepared:
        args = [jnp.asarray(ids), jnp.asarray(mask)]
        if tids is not None:
            args.append(jnp.asarray(tids))
        pending.append((apply_fn(*args), rows))
    return pending


def named_jit(fn, name: str, **jit_kwargs):
    """``jax.jit(fn)`` as a program called ``jit_<name>``: the device
    trace names a program after its function, and ``jit__forward`` could
    be any model's."""

    @functools.wraps(fn)
    def call(*args, **kwargs):
        return fn(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call, **jit_kwargs)


def _peel_launch_counters(apply_fn, record):
    """``apply_fn`` of a model whose forward returns ``(embeddings,
    counters)``: hand the counters (a device array, not waited for) to
    ``record`` and return the embeddings alone.  No ``record``: as it is."""
    if record is None:
        return apply_fn

    @functools.wraps(apply_fn)
    def call(*args, **kwargs):
        out, counters = apply_fn(*args, **kwargs)
        record(counters)
        return out

    return call


def _collect_rows(pending, n: int) -> np.ndarray:
    """Host half after the launches: wait for each device result and put
    its real rows in submission order (the host's wait for the device)."""
    from ..internals.flight_recorder import span

    with span("embed.d2h_wait", "encoder", stage="embed.d2h_wait", rows=n):
        # ask for every copy before waiting for the first: the results of
        # many small launches then come back together, not a trip each
        for res, _rows in pending:
            start_copy = getattr(res, "copy_to_host_async", None)
            if start_copy is not None:
                start_copy()
        out: np.ndarray | None = None
        for res, rows in pending:
            res = np.asarray(res, dtype=np.float32)
            if out is None:
                out = np.empty((n,) + res.shape[1:], dtype=np.float32)
            out[rows] = res[: len(rows)]
    assert out is not None
    return out


def bucketed_dispatch(
    apply_fn, ids_all, mask_all, max_length: int, type_ids_all=None,
    vocab_size: int = 1 << 31, batch_multiple: int = 1,
    max_tokens: int | None = None,
    seq_buckets: Sequence[int] = SEQ_BUCKETS,
    batch_buckets: Sequence[int] = BATCH_BUCKETS,
    lone_tail: bool = False,
) -> np.ndarray:
    """Pad (batch, seq) to buckets and dispatch chunks through a jitted
    ``apply_fn(ids, mask[, type_ids])`` — one compilation per
    (batch_bucket, seq_bucket).  Shared by SentenceEncoder and CrossEncoder.
    ``batch_multiple`` rounds the batch bucket up so the batch dimension
    divides evenly over a data-parallel mesh axis.

    Seq bucketing is per row: rows are grouped by their OWN seq bucket
    and each group dispatched at its bucket shape, so one 256-token chunk
    does not inflate a batch of 64-token chunks ~4x in FLOPs.  Every
    per-bucket shape comes from the one (BATCH_BUCKETS x SEQ_BUCKETS)
    grid, so the compiled-executable set — and
    ``pathway_xla_compile_total`` — stays flat across mixed-length
    corpora.  ``max_tokens`` caps ``batch_bucket * seq_bucket`` per
    launch (token-budget batching, ``PATHWAY_EMBED_MAX_TOKENS``)."""
    from ..internals.flight_recorder import record_padding, span

    prepared, stats = packed_prepare(
        ids_all, mask_all, max_length,
        type_ids_all=type_ids_all, vocab_size=vocab_size,
        batch_multiple=batch_multiple, max_tokens=max_tokens,
        seq_buckets=seq_buckets, batch_buckets=batch_buckets,
        lone_tail=lone_tail,
    )
    record_padding(
        stats["real_tokens"], stats["padded_tokens"], stats["row_tokens"]
    )
    # H2D and dispatch, until the last launch call returns
    with span(
        "embed.launch", "encoder", stage="embed.launch",
        chunks=len(prepared),
    ):
        pending = _dispatch_prepared(apply_fn, prepared)
    return _collect_rows(pending, ids_all.shape[0])


def _encoder_params_nbytes(enc: "SentenceEncoder") -> int:
    """HBM ledger ``bytes_fn`` (module-level: the weak owner ref must
    stay the only reference to the encoder)."""
    from ..observability.hbm_ledger import tree_nbytes

    return tree_nbytes(enc.params)


class SentenceEncoder:
    """Host-facing embedder: tokenization + bucketed jit dispatch.

    Where the reference embeds one string per UDF call and gets concurrency
    only from the async executor (embedders.py: async UDF w/ capacity), here
    batches are padded to (batch, seq) buckets so every shape compiles once
    and lands on the MXU full-width."""

    def __init__(
        self,
        model_name: str | None = None,
        cfg: EncoderConfig | None = None,
        seed: int = 0,
        max_length: int = 256,
        mesh=None,
        extend_positions: int | None = None,
        max_tokens: int | None = None,
        params: Any = None,
    ):
        #: token budget per device launch (None = PATHWAY_EMBED_MAX_TOKENS)
        self.max_tokens = max_tokens if max_tokens is not None else embed_max_tokens()
        self.pretrained = False
        # ``params``: a ready parameter tree of ``cfg``'s model, so that a
        # model of gigabytes is never drawn twice
        # attention impl: explicit cfg wins; otherwise the process-wide
        # PATHWAY_ATTENTION_IMPL knob (checkpoints pin geometry, never
        # the kernel choice)
        impl = (
            cfg.attention_impl if cfg is not None else default_attention_impl()
        )
        if model_name is not None:
            from . import checkpoint

            loaded = checkpoint.load_encoder(model_name)
            if loaded is not None:
                loaded_cfg, params = loaded
                # keep the caller's compute dtype (bf16 default) — the
                # checkpoint only pins geometry + norm conventions
                loaded_cfg = dataclasses.replace(
                    loaded_cfg,
                    dtype=(cfg or EncoderConfig()).dtype,
                    emb_dim=(cfg.emb_dim if cfg is not None else None),
                    attention_impl=impl,
                )
                cfg = loaded_cfg
                self.pretrained = True
        self.cfg = cfg or EncoderConfig(attention_impl=impl)
        if (
            extend_positions is not None
            and extend_positions > self.cfg.seq_buckets[-1]
            and mesh is None
        ):
            import warnings

            warnings.warn(
                f"extend_positions={extend_positions} without a mesh: the "
                f"single-device dispatch caps sequences at "
                f"{self.cfg.seq_buckets[-1]} tokens, so longer documents will be "
                "truncated — pass mesh= to embed them sequence-parallel",
                stacklevel=2,
            )
        if extend_positions is not None and extend_positions > self.cfg.max_len:
            # stretch the learned position table by linear interpolation
            # (the standard BERT-family length extension) so a 512-pos
            # checkpoint can serve multi-thousand-token documents — the
            # sequence-parallel ring path then spans them across the mesh
            if params is not None:
                params = dict(params)
                pos = jnp.asarray(params["pos_emb"]["embedding"])
                params["pos_emb"] = {
                    "embedding": jax.image.resize(
                        pos.astype(jnp.float32),
                        (extend_positions, pos.shape[1]),
                        method="linear",
                    ).astype(pos.dtype)
                }
            self.cfg = dataclasses.replace(self.cfg, max_len=extend_positions)
        self.max_length = min(max_length, self.cfg.max_len)
        self.tokenizer = load_tokenizer(model_name, vocab_size=self.cfg.vocab_size)
        self.model, self._packed_model = self.cfg.build_models()
        if params is not None:
            self.params = jax.tree_util.tree_map(jnp.asarray, params)
        else:
            ids = jnp.zeros((1, 8), jnp.int32)
            self.params = self.model.init(
                jax.random.PRNGKey(seed), ids, jnp.ones_like(ids)
            )["params"]
        # multi-chip serving (SURVEY §2.7): weights tensor-parallel over the
        # mesh's model axis, batches data-parallel over its data axis — XLA
        # inserts the psums/all-gathers from the committed placements
        self.mesh = mesh
        self._batch_multiple = 1
        self._sp_mesh = None
        self._packed_warm = False
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..parallel.sharding import mesh_setup

            self.params, self._data_sharding, self._batch_multiple = (
                mesh_setup(self.params, mesh)
            )
            # sub-multiple launches (small serving ticks, packed tails)
            # replicate their inputs over the data axis instead of
            # rounding the batch up to it — see _chunk_sizes
            self._replicated_sharding = NamedSharding(mesh, PartitionSpec())
        from ..internals.flight_recorder import (
            instrument_jit,
            record_attention_impl,
        )

        record_attention_impl(self.cfg.attention_impl)
        # unified HBM ledger: the parameter tree is device-resident from
        # first apply — register it next to the index/KV allocations so
        # the process total is honest (sharded params report their
        # GLOBAL logical bytes; the ledger documents that convention)
        from ..observability.hbm_ledger import get_ledger

        get_ledger().register_unique(
            f"encoder_params:{model_name or 'custom'}",
            self,
            _encoder_params_nbytes,
        )
        name = self.cfg.program_name
        # a model whose forward also returns its launch's counters says
        # where they go (``record_launch``, of the model that program
        # applies); they stay on the device until somebody reads the counters
        self._apply = _peel_launch_counters(
            instrument_jit(named_jit(self._forward, name), "encoder.forward"),
            getattr(self.model, "record_launch", None),
        )
        # packed ragged forward: same params, concatenated-token layout —
        # built unconditionally (construction is free until first trace)
        # so probes can A/B both layouts on one encoder
        self._apply_ragged = _peel_launch_counters(
            instrument_jit(
                named_jit(
                    self._forward_ragged, name + "_ragged",
                    static_argnames=("dense_s",),
                ),
                "encoder.forward_ragged",
            ),
            getattr(self._packed_model, "record_launch", None),
        )

    def _forward(self, params, ids, mask):
        return self.model.apply({"params": params}, ids, mask)

    def _forward_ragged(
        self, params, ids, pos, seg, starts, bounds, *, dense_s
    ):
        return self._packed_model.apply(
            {"params": params}, ids, pos, seg, starts, bounds,
            dense_s=dense_s,
        )

    @property
    def dim(self) -> int:
        return self.cfg.emb_dim or self.cfg.hidden_dim

    def get_embedding_dimension(self) -> int:
        return self.dim

    def encode(self, texts: Sequence[str]) -> np.ndarray:
        """Embed a batch of strings -> [B, dim] float32 (L2-normalized).

        With a mesh and ``max_length`` beyond the single-dispatch bucket
        cap (512), documents longer than the cap run sequence-parallel:
        token positions sharded over all mesh devices with ring attention
        rotating kv blocks over ICI (parallel/long_encoder.py) — the
        reference can only chunk such documents (splitters.py:34)."""
        if not texts:
            return np.zeros((0, self.dim), dtype=np.float32)
        ids_all, mask_all = self._tokenize(texts)

        if self.mesh is not None and self.max_length > self.cfg.seq_buckets[-1]:
            lengths = mask_all.sum(axis=1)
            long_rows = lengths > self.cfg.seq_buckets[-1]
            if long_rows.any():
                out = np.zeros((len(texts), self.dim), dtype=np.float32)
                short = np.where(~long_rows)[0]
                if short.size:
                    out[short] = self._encode_bucketed(
                        ids_all[short], mask_all[short]
                    )
                longi = np.where(long_rows)[0]
                out[longi] = self._encode_ring(ids_all[longi], mask_all[longi])
                return out

        return self._encode_bucketed(ids_all, mask_all)

    def _tokenize(self, texts: Sequence[str]):
        from ..internals.flight_recorder import span

        with span(
            "embed.tokenize", "encoder", stage="embed.tokenize",
            rows=len(texts),
        ):
            return self.tokenizer.encode_batch(
                list(texts), max_length=self.max_length
            )

    def _input_sharding(self, batch: int):
        """Data-parallel placement rule for one launch: shard the batch
        dim over the mesh's ``data`` axis when it divides, replicate
        otherwise (small ticks / packed tails — see _chunk_sizes)."""
        return pick_input_sharding(
            batch, self._batch_multiple,
            self._data_sharding, self._replicated_sharding,
        )

    def _encode_bucketed(self, ids_all, mask_all) -> np.ndarray:
        if self.cfg.attention_impl == "ragged":
            return self._encode_ragged(ids_all, mask_all)

        def dispatch(ids, mask):
            if self.mesh is not None:
                sharding = self._input_sharding(ids.shape[0])
                ids = jax.device_put(ids, sharding)
                mask = jax.device_put(mask, sharding)
            return self._apply(self.params, ids, mask)

        # under TAIL_ROWS rows of a sequence bucket (the files of one scan,
        # the queries of one engine step, what a bulk load leaves over) go
        # out one row a launch, back to back: which small multi-row bucket
        # they would hit depends on what arrived together, and each
        # (rows, sequence) program takes half a second to load from the
        # compile cache and seconds to compile, in set-up or, worse, under
        # live traffic.  Thirty-two rows and more take the row buckets
        return bucketed_dispatch(
            dispatch,
            ids_all,
            mask_all,
            self.max_length,
            vocab_size=self.cfg.vocab_size,
            batch_multiple=self._batch_multiple,
            max_tokens=self.max_tokens,
            seq_buckets=self.cfg.seq_buckets,
            batch_buckets=self.cfg.batch_buckets,
            lone_tail=True,
        )

    def encode_tokenized(self, ids_all, mask_all) -> np.ndarray:
        """Encode already-tokenized rows through this encoder's dispatch
        path (bucketed or ragged, per ``cfg.attention_impl``) — the bench
        harness entry, so A/B runs meter dispatch without re-tokenizing."""
        return self._encode_bucketed(ids_all, mask_all)

    # -- prepared-chunk protocol (shared by the ingest pipeline and the
    #    runtime's BULK_INGEST plane: host half / device half split) -----
    def prepare_chunks(
        self, ids_all, mask_all, max_tokens: int | None = None
    ) -> tuple[list[tuple], dict]:
        """Host half of dispatch for THIS encoder's impl: returns
        ``([(payload, rows, tokens)], stats)`` where ``payload`` feeds
        :meth:`encode_prepared` (one device launch), ``rows`` are the
        submission-order indices the launch covers, and ``tokens`` is its
        padded token mass (the runtime's budget estimate).
        ``max_tokens`` overrides the encoder's own budget (the ingest
        pipeline's knob wins over the encoder default)."""
        if max_tokens is None:
            max_tokens = self.max_tokens
        if self.cfg.attention_impl == "ragged":
            return ragged_prepare(
                ids_all, mask_all, self.max_length,
                vocab_size=self.cfg.vocab_size, max_tokens=max_tokens,
                cfg=self.cfg,
            )
        prepared, stats = packed_prepare(
            ids_all, mask_all, self.max_length,
            vocab_size=self.cfg.vocab_size,
            batch_multiple=self._batch_multiple,
            max_tokens=max_tokens,
            seq_buckets=self.cfg.seq_buckets,
            batch_buckets=self.cfg.batch_buckets,
        )
        return (
            [
                ((ids, mask, tids), rows, int(ids.size))
                for ids, mask, tids, rows in prepared
            ],
            stats,
        )

    def encode_prepared(self, payload) -> Any:
        """Device half for ONE prepared chunk: H2D + forward, the DEVICE
        output returned as-is (rows past ``len(rows)`` are pads).  Packed
        payloads are ``(ids, mask, tids)``; ragged payloads are
        :class:`RaggedChunk` (one concatenated-token launch)."""
        if isinstance(payload, RaggedChunk):
            self._warm_packed()
            args = payload.device_args()
            if self.mesh is not None:
                # the packed token axis has no batch dim to shard —
                # ragged launches dispatch replicated over the mesh
                args = [
                    jax.device_put(a, self._replicated_sharding) for a in args
                ]
            return self._apply_ragged(
                self.params, *args, dense_s=payload.dense_s
            )
        ids, mask, tids = payload
        args = [jnp.asarray(ids), jnp.asarray(mask)]
        if tids is not None:
            args.append(jnp.asarray(tids))
        if self.mesh is not None:
            sharding = self._input_sharding(args[0].shape[0])
            args = [jax.device_put(a, sharding) for a in args]
        return self._apply(self.params, *args)

    def _warm_packed(self) -> None:
        """Before the first packed launch of an encoder whose config asks
        for it (``warm_packed``): every token bucket launched once on
        padding, so that each program is compiled or loaded here and not
        under the first call that happens to need it."""
        if self._packed_warm or not self.cfg.warm_packed:
            return
        self._packed_warm = True
        none = np.zeros(0, np.int64)
        jax.block_until_ready([
            self.encode_prepared(ragged_chunk(
                none, none, None, None, self.max_length,
                dispatch_dtype(self.cfg.vocab_size), self.cfg, tokens=tokens,
            ))
            for tokens in self.cfg.token_buckets
        ])

    def _encode_ragged(self, ids_all, mask_all) -> np.ndarray:
        """Ragged dispatch: one launch per token-budget group (ONE for a
        whole serving tick), order-preserving collection."""
        from ..internals.flight_recorder import record_padding, span

        prepared, stats = ragged_prepare(
            ids_all, mask_all, self.max_length,
            vocab_size=self.cfg.vocab_size, max_tokens=self.max_tokens,
            cfg=self.cfg,
        )
        record_padding(
            stats["real_tokens"], stats["padded_tokens"], stats["row_tokens"]
        )
        with span(
            "embed.launch", "encoder", stage="embed.launch",
            chunks=len(prepared),
        ):
            pending = [
                (self.encode_prepared(payload), rows)
                for payload, rows, _tokens in prepared
            ]
        return _collect_rows(pending, ids_all.shape[0])

    def encode_padded(self, texts: Sequence[str]) -> tuple[Any, int]:
        """Fused-serving embed half: ONE whole-batch launch whose DEVICE
        output is returned as-is, ``(embeddings [bb, dim], n_real)`` —
        rows at/after ``n_real`` are dispatch pads.

        The serving tick hands this array straight to the index search
        (``DeviceKnnIndex.search`` accepts device queries), so the
        per-tick D2H(embeddings) + H2D(same bytes) round trip disappears;
        with a mesh the batch shards over the ``data`` axis when it
        divides and replicates otherwise, and the search side consumes it
        under its own specs (replicated queries for the sharded index).
        ``bb`` is a power-of-two batch bucket, i.e. already the shape
        ``bucket_q`` would pad to — the search compiles no extra shapes.

        Raises ``ValueError`` when the batch exceeds the largest dispatch
        bucket (callers fall back to :meth:`encode`)."""
        n = len(texts)
        if n == 0 or n > self.cfg.batch_buckets[-1]:
            raise ValueError(f"batch of {n} outside the dispatch buckets")
        ids_all, mask_all = self._tokenize(texts)
        if self.cfg.attention_impl == "ragged":
            return self._encode_padded_ragged(ids_all, mask_all, n)
        longest = int(mask_all.sum(axis=1).max())
        if self.mesh is not None and longest > self.cfg.seq_buckets[-1]:
            raise ValueError("batch needs the sequence-parallel ring path")
        seq = min(
            _bucket(max(longest, 1), self.cfg.seq_buckets), self.max_length
        )
        bb = round_batch_to_multiple(
            _bucket(n, self.cfg.batch_buckets), self._batch_multiple
        )
        if self.max_tokens is not None and bb * seq > self.max_tokens:
            # the token budget bounds EVERY launch's padded mass
            # (PATHWAY_EMBED_MAX_TOKENS exists to cap launch memory) —
            # a tick too big for one budgeted launch falls back to the
            # packed host path, which splits it under the same cap
            raise ValueError(
                f"padded tick {bb}x{seq} exceeds max_tokens={self.max_tokens}"
            )
        ids, mask, _ = pad_chunk(
            ids_all[:, :seq],
            mask_all[:, :seq],
            bb,
            seq,
            ids_dtype=dispatch_dtype(self.cfg.vocab_size),
        )
        from ..internals.flight_recorder import record_padding

        record_padding(int(mask_all.sum()), bb * seq, n * seq)
        args = [jnp.asarray(ids), jnp.asarray(mask)]
        if self.mesh is not None:
            sharding = self._input_sharding(bb)
            args = [jax.device_put(a, sharding) for a in args]
        return self._apply(self.params, *args), n

    def _encode_padded_ragged(self, ids_all, mask_all, n: int):
        """Fused-serving embed half, ragged layout: the whole tick is ONE
        concatenated-token launch (vs one per (batch, seq) bucket), and
        the ``[row_bucket, dim]`` device output keeps the
        :meth:`encode_padded` contract — rows at/after ``n`` are pads the
        search discards, and the row bucket is the same power-of-two grid
        ``bucket_q`` pads to."""
        from ..internals.flight_recorder import record_padding

        longest = int(mask_all.sum(axis=1).max())
        if self.mesh is not None and longest > self.cfg.seq_buckets[-1]:
            # same refusal as the bucketed tick: over-cap documents go
            # sequence-parallel, not silently truncated
            raise ValueError("batch needs the sequence-parallel ring path")
        prepared, stats = ragged_prepare(
            ids_all, mask_all, self.max_length,
            vocab_size=self.cfg.vocab_size, max_tokens=self.max_tokens,
            # the fused tick IS the one-launch case — never split it by
            # seq bucket (the whole-tick launch is the contract)
            mix_buckets=True,
            cfg=self.cfg,
        )
        if len(prepared) != 1:
            # a tick too big for one launch (token budget / VMEM cap)
            # falls back to the multi-launch host path, same as the
            # bucketed impl's max_tokens refusal
            raise ValueError(
                f"padded tick of {stats['real_tokens']} tokens needs "
                f"{len(prepared)} ragged launches; fused tick wants one"
            )
        payload, _rows, _tokens = prepared[0]
        record_padding(
            stats["real_tokens"], stats["padded_tokens"], stats["row_tokens"]
        )
        out = self.encode_prepared(payload)
        # where the launch's rows are padded past the dense dispatch's row
        # bucket (one fixed row count), hand the search that bucket's rows
        bb = _bucket(n, self.cfg.batch_buckets)
        return (out[:bb] if out.shape[0] > bb else out), n

    def _encode_ring(self, ids_all, mask_all) -> np.ndarray:
        """Sequence-parallel path for documents beyond the bucket cap."""
        from jax.sharding import Mesh

        from ..parallel.long_encoder import ring_encode

        if self._sp_mesh is None:
            devices = np.asarray(self.mesh.devices).reshape(-1)
            self._sp_mesh = Mesh(devices, ("sp",))
        n = self._sp_mesh.shape["sp"]
        # pad the sequence to a coarse multiple so shapes (and compiles)
        # stay few; the mask keeps the padding out of attention + pooling.
        # cap = max_length rounded DOWN to the shard count, so the padded
        # length never exceeds the position table (docs at the very cap
        # lose < n tail tokens on a non-dividing mesh)
        step = max(n * 64, 128)
        cap = self.max_length - self.max_length % n
        longest = int(mask_all.sum(axis=1).max())
        seq = min(-(-longest // step) * step, cap)
        if seq % n:  # step itself may not divide when n*64 < 128
            seq += n - seq % n
            seq = min(seq, cap)
        ids = np.zeros((ids_all.shape[0], seq), np.int32)
        mask = np.zeros((ids_all.shape[0], seq), np.int32)
        width = min(seq, ids_all.shape[1])
        ids[:, :width] = ids_all[:, :width]
        mask[:, :width] = mask_all[:, :width]
        out = ring_encode(
            self.params, ids, mask, self._sp_mesh, "sp",
            num_layers=self.cfg.num_layers, ln_eps=self.cfg.ln_eps,
        )
        return np.asarray(out, dtype=np.float32)

    def __call__(self, text: str) -> np.ndarray:
        return self.encode([text])[0]
