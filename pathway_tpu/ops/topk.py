"""Masked distance + top-k search kernels.

reference semantics: src/external_integration/brute_force_knn_integration.rs
(``fill_cos_distances``:69, ``fill_l2sq_distances``:91, blocked matmul with
``auxiliary_space`` bound, top-k via OrderedFloat sort).

TPU design: one fused XLA computation — score matrix on the MXU
(``queries @ vectors.T`` in bf16/f32), tombstone masking fused into the
matmul epilogue, ``lax.top_k`` on device.  A Pallas variant tiles the score
computation through VMEM for the case where the index matrix is too large
for XLA's fusion to stay in VMEM; both produce identical results and the
index picks per-backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "masked_topk_scores",
    "topk_search",
    "pallas_masked_scores",
    "bucket_k",
    "bucket_q",
]

NEG_INF = -jnp.inf


def bucket_k(k: int, cap: int) -> int:
    """Round ``k`` up to the next power of two, clamped to ``cap``.

    ``k`` is a static argument of the jitted top-k searches, so every
    distinct serving ``k`` would otherwise trigger a fresh XLA compile;
    bucketing it the same way the query/candidate dims are bucketed keeps
    compiled shapes stable — callers slice the returned (sorted) rows
    back down to the requested ``k``."""
    k = max(1, k)
    return min(cap, 1 << (k - 1).bit_length())


def bucket_q(n: int, lo: int = 8) -> int:
    """Round a query-batch size up to the next power of two (≥ ``lo``).

    Serving traffic arrives in ragged batches (whatever the scheduler
    tick collected); padding the Q dim to buckets keeps the compiled
    top-k variants to O(log) — callers slice the padded rows back off."""
    return max(lo, 1 << (max(1, n) - 1).bit_length())


def _scores(queries: jax.Array, vectors: jax.Array, metric: str) -> jax.Array:
    """Similarity scores, higher = better.  cos assumes rows pre-normalized."""
    if metric in ("cos", "dot"):
        return jnp.dot(
            queries, vectors.T, preferred_element_type=jnp.float32
        )
    if metric == "l2sq":
        # -||q - v||^2 = 2 q·v - ||q||^2 - ||v||^2 (negated: higher better)
        dots = jnp.dot(queries, vectors.T, preferred_element_type=jnp.float32)
        qn = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        vn = jnp.sum(vectors.astype(jnp.float32) ** 2, axis=-1)
        return 2.0 * dots - qn - vn[None, :]
    raise ValueError(f"unknown metric {metric!r}")


@functools.partial(jax.jit, static_argnames=("metric",))
def masked_topk_scores(
    queries: jax.Array,  # [Q, D]
    vectors: jax.Array,  # [N, D]
    valid: jax.Array,  # [N] bool — tombstone mask (False = deleted/free slot)
    metric: str = "cos",
) -> jax.Array:
    s = _scores(queries, vectors, metric)
    return jnp.where(valid[None, :], s, NEG_INF)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def topk_search(
    queries: jax.Array,
    vectors: jax.Array,
    valid: jax.Array,
    k: int,
    metric: str = "cos",
) -> tuple[jax.Array, jax.Array]:
    """Returns (scores[Q,k], indices[Q,k]); deleted slots never surface
    (their score is -inf — callers drop -inf results host-side)."""
    s = masked_topk_scores(queries, vectors, valid, metric)
    return lax.top_k(s, k)


# compile counting (pathway_xla_compile_total{site=...}): the serving
# guarantee that bucket_q/bucket_k keep compiled-program counts flat under
# heterogeneous (Q, k) traffic becomes an observable series instead of a
# test-only _cache_size() probe
from ..internals.flight_recorder import instrument_jit as _instrument_jit

topk_search = _instrument_jit(topk_search, "knn.topk_search")


# ---------------------------------------------------------------------------
# Pallas tiled variant (HBM-resident index streamed through VMEM)
# ---------------------------------------------------------------------------


def pallas_masked_scores(
    queries: jax.Array,  # [Q, D] — Q, D multiples of tile sizes
    vectors: jax.Array,  # [N, D]
    valid: jax.Array,  # [N] float32 {0,1}
    *,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Tiled score kernel: for each (query-block, vector-block) grid cell,
    compute q·vᵀ on the MXU and apply the tombstone mask in the epilogue.

    Used when the index matrix exceeds what XLA keeps fused in VMEM; grid
    iterates vector blocks in the minor dimension so each query tile stays
    resident while index tiles stream from HBM.
    """
    from jax.experimental import pallas as pl

    if interpret is None:
        # the Mosaic backend exists on TPU only; elsewhere (CPU mesh in
        # tests) the interpreter executes the same kernel
        interpret = jax.default_backend() != "tpu"

    q, d = queries.shape
    n = vectors.shape[0]
    block_q = min(q, 256)
    assert n % block_n == 0 and q % block_q == 0, "pad inputs to block multiples"

    def kernel(q_ref, v_ref, m_ref, o_ref):
        scores = jnp.dot(
            q_ref[:], v_ref[:].T, preferred_element_type=jnp.float32
        )
        masked = jnp.where(m_ref[:][None, :] > 0, scores, NEG_INF)
        o_ref[:] = masked

    grid = (q // block_q, n // block_n)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        interpret=interpret,
    )(queries, vectors, valid.astype(jnp.float32))


#: index sizes from which the tiled Pallas path pays for itself (smaller
#: matrices stay fused in VMEM by XLA on their own)
PALLAS_MIN_ROWS = 4096


def pallas_topk_search(
    queries: jax.Array,
    vectors: jax.Array,
    valid: jax.Array,
    k: int,
    metric: str = "cos",
) -> tuple[jax.Array, jax.Array]:
    """Tiled-score variant of :func:`topk_search` (cos/dot only — l2sq
    falls back).  Queries are padded to the query-block multiple."""
    q = queries.shape[0]
    block_q = 256
    if q > block_q and q % block_q:
        pad = block_q - q % block_q
        queries = jnp.concatenate(
            [queries, jnp.zeros((pad, queries.shape[1]), queries.dtype)]
        )
    scores = pallas_masked_scores(queries, vectors, valid)[:q]
    return lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def among_topk_search(
    queries: jax.Array,  # [Q, D]
    vectors: jax.Array,  # [N, D] full index matrix
    valid: jax.Array,  # [N] tombstone mask
    idx: jax.Array,  # [Q, C] per-query candidate slot indices
    pad_valid: jax.Array,  # [Q, C] False on padding entries
    k: int,
    metric: str = "cos",
):
    """Per-query candidate-subset top-k in ONE device call.

    The LSH rescoring path (reference: _knn_lsh.py:219-256 rescores each
    query's bucket union) previously dispatched one gather+top-k per
    query.  Here all Q candidate sets ride one gather ([Q, C, D]) and
    one batched matvec.
    """
    sub = vectors[idx]  # [Q, C, D]
    v = valid[idx] & pad_valid
    dots = jnp.einsum(
        "qd,qcd->qc", queries, sub, preferred_element_type=jnp.float32
    )
    if metric in ("cos", "dot"):
        s = dots
    elif metric == "l2sq":
        qn = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        vn = jnp.sum(sub.astype(jnp.float32) ** 2, axis=-1)
        s = 2.0 * dots - qn - vn
    else:
        raise ValueError(f"unknown metric {metric!r}")
    s = jnp.where(v, s, NEG_INF)
    return lax.top_k(s, k)


among_topk_search = _instrument_jit(among_topk_search, "knn.among_topk_search")
