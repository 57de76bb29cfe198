"""Pallas flash attention over a PACKED RAGGED batch (one launch per tick).

The bucketed dispatch (models/encoder.py) pads every row to a
(batch_bucket, seq_bucket) shape and pays one kernel launch per bucket —
0.906 padding efficiency on the mixed ingest corpus, and a launch count
that grows with length heterogeneity.  This module is the TPU-native fix
from "Ragged Paged Attention" (PAPERS.md): rows are CONCATENATED along
one token axis (``cu_seqlens``/segment ids mark the boundaries), the
whole tick is ONE kernel launch, and only the tail block's alignment is
padding (~1.0 efficiency).

Kernel design (see /opt/skills/guides/pallas_guide.md):

* grid = (heads, q_blocks) — the ragged layout has no batch axis left to
  tile, so programs flatten over head x token-block; each program owns a
  ``[block_q, head_dim]`` query tile and streams kv blocks through the
  MXU with an f32 online softmax (bf16 in / f32 accumulate).
* **block-aligned ragged masks**: rows never attend across segment
  boundaries (``seg_q == seg_k`` elementwise inside a block), and blocks
  wholly outside the q tile's row span are SKIPPED, not masked — the per
  q-block kv range rides in as a scalar-prefetch ``[q_blocks, 2]`` array
  (``ragged_bounds``, host-computed from cu_seqlens) so the fori_loop
  trip count is data-dependent.  Cross-row attention is structurally
  impossible; the wasted compute is only the partial blocks at segment
  boundaries.
* K/V live whole in VMEM per head (encoder geometry: T<=8192, head_dim
  <=128 -> <=4 MB), so no manual DMA pipeline is needed; the MXU sees
  back-to-back [block_q, dh] x [dh, block_k] and [block_q, block_k] x
  [block_k, dh] matmuls.

Off-TPU the DEFAULT is an XLA reference (``mode="reference"``): scatter
the packed tokens to a dense ``[rows, seq_bucket]`` layout, run the
exact masked softmax there, gather back — same numerics as the flax
golden path, and the per-token 96% of the network still runs unpadded on
the ragged axis.  ``PATHWAY_RAGGED_KERNEL=pallas`` forces the Pallas
kernel (interpret mode off-TPU) so tier-1 tests exercise the real kernel
on the CPU mesh.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "ragged_attention",
    "ragged_block",
    "ragged_bounds",
    "validate_attention_geometry",
]

_NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

#: kernel tile along the packed token axis (q and kv); token buckets are
#: multiples of this (or one of the small sub-block buckets below it)
TOKEN_BLOCK = 128

#: VMEM guard: whole-K/V-per-head residency is the kernel's design point
#: (encoder sequences are short); past this the kernel would need an HBM
#: streaming loop it does not have
MAX_PACKED_TOKENS = 8192


def ragged_block(total_tokens: int) -> int:
    """Kernel block size for a packed launch: TOKEN_BLOCK, except a
    launch smaller than one block runs at its own (bucketed) size — a
    1-row tick of 5 tokens must not pad to a 128-token block."""
    return TOKEN_BLOCK if total_tokens >= TOKEN_BLOCK else total_tokens


def validate_attention_geometry(head_dim: int, sm_scale, *, knob: str) -> None:
    """Up-front geometry check shared by the dense and ragged Pallas
    kernels.  Mosaic tiles the minor dimension in 128-wide lanes; a
    head_dim that neither divides nor is a multiple of the lane tile
    fails deep inside lowering with an opaque error — refuse here and
    name the knob that selects a working implementation instead."""
    if head_dim <= 0 or (128 % head_dim != 0 and head_dim % 128 != 0):
        raise ValueError(
            f"{knob} requires head_dim to divide (or be a multiple of) the "
            f"128-lane MXU tile; got head_dim={head_dim}.  Use "
            "attention_impl='fused' (PATHWAY_ATTENTION_IMPL=fused) for "
            "this geometry."
        )
    if sm_scale is not None and (
        not math.isfinite(sm_scale) or sm_scale <= 0.0
    ):
        raise ValueError(
            f"{knob}: sm_scale must be a positive finite float, got "
            f"{sm_scale!r}.  Callers that already applied the softmax "
            "scale to the query must pass pre_scaled=True instead of a "
            "second scale."
        )


def kernel_mode() -> str:
    """``PATHWAY_RAGGED_KERNEL``: ``auto`` (Pallas compiled on TPU, XLA
    reference elsewhere), ``pallas`` (force the kernel; interpret mode
    off-TPU — slow but exact, how tier-1 exercises it on CPU), or
    ``reference`` (force the XLA path everywhere)."""
    raw = os.environ.get("PATHWAY_RAGGED_KERNEL", "auto").strip().lower()
    if raw in ("auto", "pallas", "reference"):
        return raw
    import warnings

    warnings.warn(
        f"PATHWAY_RAGGED_KERNEL={raw!r} is not one of auto/pallas/reference"
        " — using auto",
        stacklevel=2,
    )
    return "auto"


def ragged_bounds(cu_seqlens, total_tokens: int, block: int) -> np.ndarray:
    """Per-q-block kv BLOCK range ``[lo, hi)`` for the packed layout —
    the host half of the block-aligned ragged mask.

    ``cu_seqlens``: int array ``[rows+1]`` of cumulative row lengths
    (``cu[0] == 0``, ``cu[-1] == real tokens``).  ``total_tokens`` is the
    bucket-padded launch length (a multiple of ``block``).  Blocks whose
    q tokens are all padding get ``lo == hi == 0`` (the kernel skips them
    entirely)."""
    cu = np.asarray(cu_seqlens, dtype=np.int64)
    if total_tokens % block:
        raise ValueError(
            f"total_tokens={total_tokens} is not a multiple of block={block}"
        )
    n_blocks = total_tokens // block
    t_real = int(cu[-1])
    bounds = np.zeros((n_blocks, 2), np.int32)
    for i in range(n_blocks):
        q0 = i * block
        if q0 >= t_real:
            continue  # pure pad tail: zero-trip loop
        q1 = min((i + 1) * block, t_real)
        first = int(np.searchsorted(cu, q0, side="right")) - 1
        last = int(np.searchsorted(cu, q1 - 1, side="right")) - 1
        bounds[i, 0] = cu[first] // block
        bounds[i, 1] = -(-int(cu[last + 1]) // block)
    return bounds


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _ragged_kernel(
    bounds_ref,  # scalar-prefetch [q_blocks, 2] (SMEM)
    q_ref,  # [1, block_q, dh]
    k_ref,  # [1, T, dh] (whole kv for this head)
    v_ref,  # [1, T, dh]
    seg_ref,  # [1, T] int32 segment ids (pads = num_rows)
    pos_ref,  # [1, T] int32 position-within-row (causal masking)
    o_ref,  # [1, block_q, dh]
    *,
    block_q: int,
    block_k: int,
    sm_scale: float,
    causal: bool,
):
    # a launch smaller than TOKEN_BLOCK is ONE block (ragged_block), so
    # its offsets are the static 0: Mosaic refuses a dynamic lane-axis
    # slice it cannot prove 128-aligned ("cannot statically prove that
    # index in dimension 1 is a multiple of 128" at block 32/64)
    single_block = seg_ref.shape[1] == block_q
    i = 0 if single_block else pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [bq, dh]
    seg_q = seg_ref[0, pl.ds(i * block_q, block_q)]  # [bq]
    pos_q = pos_ref[0, pl.ds(i * block_q, block_q)]  # [bq]

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        seg_k = seg_ref[0, pl.ds(j * block_k, block_k)]
        valid = seg_q[:, None] == seg_k[None, :]
        if causal:
            # decoder prefill: a token attends only to its own row's
            # PREFIX (pos_q >= pos_k); the block-skip bounds stay the
            # bidirectional row bounds — future blocks mask, not skip
            pos_k = pos_ref[0, pl.ds(j * block_k, block_k)]
            valid &= pos_q[:, None] >= pos_k[None, :]
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # masked entries must contribute 0 even when a row has seen no
        # valid key yet (m_new still _NEG_INF -> exp(s - m_new) == 1)
        p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot(
            p, vb, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    a0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    if single_block:
        # never a pure-pad launch (>= 1 real token), so bounds are [0, 1)
        m, l, acc = body(0, (m0, l0, a0))
    else:
        m, l, acc = jax.lax.fori_loop(
            bounds_ref[i, 0], bounds_ref[i, 1], body, (m0, l0, a0)
        )
    # pad-tail blocks (zero-trip) and all-pad rows divide 0/eps -> 0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block", "sm_scale", "interpret", "causal")
)
def _ragged_pallas(q, k, v, seg, pos, bounds, block, sm_scale, interpret,
                   causal=False):
    # layout: [T, h, dh] -> [h, T, dh]; one program per (head, q block)
    total, heads, dh = q.shape
    qh = jnp.transpose(q, (1, 0, 2))
    kh = jnp.transpose(k, (1, 0, 2))
    vh = jnp.transpose(v, (1, 0, 2))
    seg2 = seg.astype(jnp.int32)[None, :]  # [1, T]
    if pos is None:
        pos = jnp.zeros((total,), jnp.int32)
    pos2 = pos.astype(jnp.int32)[None, :]  # [1, T]
    n_blocks = total // block
    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(heads, n_blocks),
        in_specs=[
            pl.BlockSpec((1, block, dh), lambda h, i, b: (h, i, 0)),
            pl.BlockSpec((1, total, dh), lambda h, i, b: (h, 0, 0)),
            pl.BlockSpec((1, total, dh), lambda h, i, b: (h, 0, 0)),
            pl.BlockSpec((1, total), lambda h, i, b: (0, 0)),
            pl.BlockSpec((1, total), lambda h, i, b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, dh), lambda h, i, b: (h, i, 0)),
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel,
            block_q=block,
            block_k=block,
            sm_scale=sm_scale,
            causal=causal,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((heads, total, dh), q.dtype),
        cost_estimate=pl.CostEstimate(
            # upper bound: a fully dense launch; the ragged bounds make
            # the realized cost ~(mean row len / T) of this
            flops=4 * heads * total * total * dh,
            bytes_accessed=3 * heads * total * dh * q.dtype.itemsize
            + heads * total * dh * q.dtype.itemsize,
            transcendentals=heads * total * total,
        ),
        interpret=interpret,
    )(bounds, qh, kh, vh, seg2, pos2)
    return jnp.transpose(out, (1, 0, 2))


# ---------------------------------------------------------------------------
# XLA reference (off-TPU default): dense-unpack -> exact softmax -> repack
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("num_rows", "dense_s", "sm_scale", "causal")
)
def _ragged_reference(q, k, v, seg, pos, starts, num_rows, dense_s, sm_scale,
                      causal=False):
    """Gather the packed tokens into the bucketed dense layout
    ``[rows, seq_bucket]`` the legacy dispatch uses, run the flax-exact
    masked softmax there, gather back to the packed axis.  GATHERS, not
    scatters: XLA-CPU lowers scatter row-serially, which erased the
    ragged path's win in the first cut; the dense view is
    ``packed[starts[r] + s]`` with junk lanes (positions past a row's
    end alias the next row) masked out of the SCORES instead of zeroed
    in the operands.  Attention is the only stage that pays the dense
    shape; every other FLOP in the encoder runs on the unpadded token
    axis — the ragged path's whole win off-TPU, where Mosaic is
    unavailable."""
    total, heads, dh = q.shape
    seg = seg.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    # [R, S] token index of each dense lane into the packed axis
    idx = jnp.clip(
        starts.astype(jnp.int32)[:, None]
        + jax.lax.broadcasted_iota(jnp.int32, (num_rows, dense_s), 1),
        0,
        total - 1,
    )
    # a lane is real iff the token it aliases belongs to row r AND sits
    # at that lane's position — the position check catches the clipped
    # tail of the LAST row, whose out-of-range lanes alias back into the
    # row itself when the launch has no pad tail (seg alone would call
    # them valid and double-count the final token).  Layer-invariant, so
    # XLA CSE shares it across the 6 layers' attention calls.
    valid = (
        seg[idx]
        == jax.lax.broadcasted_iota(jnp.int32, (num_rows, dense_s), 0)
    ) & (
        pos[idx]
        == jax.lax.broadcasted_iota(jnp.int32, (num_rows, dense_s), 1)
    )
    qd = q[idx]  # [R, S, h, d] — junk lanes ride along, masked below
    kd = k[idx]
    vd = v[idx]
    s = jnp.einsum(
        "rqhd,rkhd->rhqk", qd, kd, preferred_element_type=jnp.float32
    ) * sm_scale
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    if causal:
        # in the dense unpack the lane index IS the within-row position,
        # so causal masking is a plain lower-triangular mask
        tri = jnp.tril(jnp.ones((dense_s, dense_s), bool))
        s = jnp.where(tri[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    od = jnp.einsum("rhqk,rkhd->rqhd", p, vd.astype(p.dtype))
    # gather back (pads clamp to the last row — their output is
    # unspecified by contract and dropped at pooling)
    gather_seg = jnp.minimum(seg, num_rows - 1)
    return od[gather_seg, pos].astype(q.dtype)


def ragged_attention(
    q,
    k,
    v,
    seg,
    *,
    pos=None,
    starts=None,
    bounds=None,
    num_rows: int | None = None,
    dense_s: int | None = None,
    sm_scale: float | None = None,
    pre_scaled: bool = False,
    causal: bool = False,
    mode: str | None = None,
):
    """Attention over a packed ragged batch.

    ``causal=True`` additionally masks each token to its own row's
    prefix (``pos_q >= pos_k``) — the decoder-prefill contract (the
    paged-KV generation subsystem rides this for its one-launch
    mixed-length prefill).  Requires ``pos`` in BOTH modes.

    ``q``/``k``/``v``: ``[T, heads, head_dim]`` — rows concatenated along
    the token axis, ``T`` padded to a token bucket.  ``seg``: ``[T]``
    int segment ids (row index per token; pad-tail tokens carry
    ``num_rows``).  Tokens attend only within their own segment; pad
    tokens' outputs are unspecified (callers drop them at pooling).

    ``bounds``: ``[T // block, 2]`` kv block ranges from
    :func:`ragged_bounds` (required for the Pallas kernel).  ``pos`` +
    ``num_rows`` + ``dense_s`` parameterize the XLA reference's dense
    unpack (position-within-row, row bucket, seq bucket).

    ``pre_scaled=True`` means the caller already multiplied the softmax
    scale into ``q`` — passing a second ``sm_scale`` alongside it raises
    instead of silently double-scaling.
    """
    if pre_scaled:
        if sm_scale is not None:
            raise ValueError(
                "ragged_attention: pre_scaled=True with an explicit "
                "sm_scale would double-scale the logits — pass one or "
                "the other"
            )
        scale = 1.0
    else:
        scale = (
            1.0 / math.sqrt(q.shape[-1]) if sm_scale is None else float(sm_scale)
        )
    validate_attention_geometry(
        int(q.shape[-1]), scale, knob="attention_impl='ragged'"
    )
    total = int(q.shape[0])
    if total > MAX_PACKED_TOKENS:
        raise ValueError(
            f"packed launch of {total} tokens exceeds MAX_PACKED_TOKENS="
            f"{MAX_PACKED_TOKENS} (whole-K/V VMEM residency); split the "
            "batch (PATHWAY_EMBED_MAX_TOKENS) or use attention_impl='fused'"
        )
    if mode is None:
        mode = kernel_mode()
    if mode == "auto":
        mode = "pallas" if jax.default_backend() == "tpu" else "reference"
    if mode == "reference":
        if pos is None or starts is None or num_rows is None or dense_s is None:
            raise ValueError(
                "ragged_attention reference mode needs pos, starts, "
                "num_rows and dense_s for the dense unpack"
            )
        return _ragged_reference(
            q, k, v, seg, pos, starts, int(num_rows), int(dense_s),
            float(scale), causal=causal,
        )
    block = ragged_block(total)
    if total % block:
        raise ValueError(
            f"packed length {total} is not a multiple of the {block}-token "
            "block — pad to a token bucket (models/encoder.ragged_prepare)"
        )
    if bounds is None:
        raise ValueError(
            "ragged_attention pallas mode needs the per-q-block kv bounds "
            "(ragged_bounds)"
        )
    if causal and pos is None:
        raise ValueError(
            "ragged_attention causal=True needs pos (position within row) "
            "for the prefix mask"
        )
    interpret = jax.default_backend() != "tpu"
    return _ragged_pallas(
        q, k, v, seg, pos, bounds, block, float(scale), interpret,
        causal=causal,
    )
