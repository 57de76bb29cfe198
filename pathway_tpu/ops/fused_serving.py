"""Fused serving-tick megakernel: normalized query → top-k in ONE launch.

The serving hot path used to execute as a chain of separate device
dispatches — query L2-normalize (``_prep_queries``), masked scoring over
the resident corpus, ``lax.top_k``, and (int8) the rescore-ring pass —
each paying dispatch latency plus a round trip through HBM for the full
``[Q, N]`` score intermediate.  This module collapses the chain:

* **Pallas megakernel** — one ``pallas_call`` whose grid streams corpus
  blocks through VMEM while the query tile stays resident: the queries
  L2-normalize in VMEM at the first block, every block's scores are
  computed on the MXU (asymmetric int8 dequant-in-register on the
  quantized path, the ``ops/quantized_scoring.py`` math), and a running
  per-query top-k merges across the block grid (the online-accumulator
  idiom from ``ops/ragged_attention.py`` / ``decode_kernel.py``) — the
  full score matrix never exists in HBM;
* **fused XLA formulation** — the same normalize→score→top-k
  composition under ONE jit (one dispatch, XLA fuses the mask into the
  matmul epilogue).  Off-TPU this is the fused lowering (Pallas
  interpret mode is a per-element evaluator, ~40x slower) and
  everywhere it is the bit-compatibility oracle the megakernel is
  pinned against;
* **staged reference formulation** — the legacy separate-launch chain
  (normalize / score matrix / top-k / rescore as individual dispatches,
  the ``[Q, N]`` intermediate materialized) kept for A/B benches and
  parity tests.

Mode knob (``PATHWAY_QUANT_KERNEL`` idiom): ``PATHWAY_SERVING_KERNEL=``
``auto`` (megakernel on TPU when the geometry tiles, fused XLA
elsewhere), ``fused`` (same lowering, stated intent), ``reference``
(the staged legacy chain), ``pallas`` (force the megakernel body —
interpret mode off-TPU, how tier-1 exercises the real kernel on CPU).
``validate_serving_geometry`` names the knob when a forced kernel
cannot tile.

Bit-compatibility contract: every score element is the same length-D
dot in every formulation (per-element reductions are insensitive to the
output tiling — the property the sharded-parity tests already pin), the
megakernel's online merge breaks score ties toward the lower slot index
exactly like ``lax.top_k``'s stable order, and rows with fewer than k
valid slots surface the same ``-inf``/index tail.  Fused-vs-reference
top-k is therefore bit-exact at f32, pinned by ``tests/test_fused_serving.py``.

Launch accounting: every serving-path dispatch calls
:func:`record_launch`; :func:`serving_tick` aggregates per tick and
emits a ``pathway_serving_launches_total{stage=}`` counter family plus
a flight-recorder ``serving.tick`` span carrying per-stage launch
counts — the fused win is provable without a chip
(``PATHWAY_LAUNCH_ACCOUNTING=0`` disables, for overhead A/Bs).

Wire dtype: ``PATHWAY_SERVING_WIRE_DTYPE`` (default ``bf16``) is the
encoder→search handoff dtype — half the bytes on the device-resident
wire, widened back to f32 in-register before normalization (exact), so
query-cache hit/miss bit-exactness is preserved.  ``f32`` opts out
(see MIGRATION).
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import warnings

import jax
import jax.numpy as jnp
from jax import lax

from .quantized_scoring import (
    _reference_scores,
    compute_dtype,
    pick_block_n,
    rescore_topk,
)
from .topk import _scores as _dense_scores

__all__ = [
    "SERVING_KERNEL_MODES",
    "SERVING_WIRE_DTYPES",
    "serving_kernel_mode",
    "serving_wire_dtype",
    "launch_accounting_enabled",
    "validate_serving_geometry",
    "record_launch",
    "serving_tick",
    "launch_totals",
    "reset_launch_metrics",
    "dense_fused_search",
    "quant_fused_search",
    "dense_reference_search",
    "quant_reference_search",
    "pallas_fused_topk",
    "pallas_fused_quant_topk",
]

#: every literal the mode parser accepts — the kernel-registry lint pins
#: this tuple against the README knob table, both directions
SERVING_KERNEL_MODES = ("auto", "fused", "reference", "pallas")

SERVING_WIRE_DTYPES = ("bf16", "f32")

#: tombstoned-slot sentinel INSIDE the megakernel (the ragged_attention
#: idiom: finite, so the taken-entry marker below it still exists in
#: f32).  Converted back to -inf at the final grid step so the output is
#: bit-identical to the reference's ``where(valid, s, -inf)`` masking.
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
#: unfilled top-k lane sentinel: strictly below every maskable score so
#: real (even tombstoned) candidates always displace it — rows with
#: >= k corpus slots can never surface an unfilled lane
_UNFILLED = -0.8 * float(jnp.finfo(jnp.float32).max)

_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------


def serving_kernel_mode() -> str:
    """``PATHWAY_SERVING_KERNEL``: ``auto`` (megakernel on TPU when the
    geometry tiles, fused XLA elsewhere — the serving default),
    ``fused`` (explicit fused lowering, same dispatch), ``reference``
    (staged legacy chain: separate normalize/score/top-k/rescore
    launches), or ``pallas`` (force the megakernel; interpret mode
    off-TPU — slow but exact, tier-1's kernel coverage)."""
    raw = os.environ.get("PATHWAY_SERVING_KERNEL", "auto").strip().lower()
    if raw in SERVING_KERNEL_MODES:
        return raw
    warnings.warn(
        f"PATHWAY_SERVING_KERNEL={raw!r} is not one of "
        f"{'/'.join(SERVING_KERNEL_MODES)} — using auto",
        stacklevel=2,
    )
    return "auto"


def serving_wire_dtype() -> str:
    """``PATHWAY_SERVING_WIRE_DTYPE`` (default ``bf16``): dtype of the
    encoder→search device handoff.  bf16 halves the on-wire bytes (the
    banked ``wire_bf16`` A/B win) and widens back to f32 exactly before
    normalization, so scores and cache hit/miss bit-exactness are
    unchanged; ``f32`` opts out (MIGRATION documents the flip)."""
    raw = os.environ.get("PATHWAY_SERVING_WIRE_DTYPE", "bf16").strip().lower()
    if raw in SERVING_WIRE_DTYPES:
        return raw
    warnings.warn(
        f"PATHWAY_SERVING_WIRE_DTYPE={raw!r} is not one of "
        f"{'/'.join(SERVING_WIRE_DTYPES)} — using bf16",
        stacklevel=2,
    )
    return "bf16"


def launch_accounting_enabled() -> bool:
    """``PATHWAY_LAUNCH_ACCOUNTING`` (default on): per-dispatch launch
    counting + the per-tick ``serving.tick`` flight-recorder span.  The
    off switch exists for the ``obs_overhead.py --fused`` budget A/B."""
    return os.environ.get("PATHWAY_LAUNCH_ACCOUNTING", "1").strip().lower() not in (
        "0", "false", "off", "no",
    )


def validate_serving_geometry(n_rows: int, metric: str) -> int:
    """Block size for the megakernel's corpus grid, or raise naming the
    knob when the forced kernel cannot tile this index.  ``auto``/
    ``fused`` callers never raise — they fall back to the fused XLA
    formulation instead (same launch count, no tiling constraint)."""
    problems = []
    if metric not in ("cos", "dot"):
        problems.append(
            f"metric {metric!r} has no megakernel body (cos/dot only)"
        )
    block_n = pick_block_n(n_rows)
    if block_n is None:
        problems.append(
            f"corpus capacity {n_rows} has no power-of-two block tile "
            "(needs a divisor >= 32, the int8 sublane tile)"
        )
    if problems:
        raise ValueError(
            "PATHWAY_SERVING_KERNEL=pallas forces the fused serving "
            "megakernel, but " + "; ".join(problems) + " — set "
            "PATHWAY_SERVING_KERNEL=auto (or fused) to use the fused "
            "XLA formulation on this geometry"
        )
    return int(block_n)


def pick_serving_impl(mode: str, n_rows: int, metric: str) -> str:
    """``"pallas"`` or ``"xla"`` for the fused lowering.  ``pallas``
    mode validates (and raises on) geometry; ``auto``/``fused`` take the
    megakernel only where it is compiled Mosaic on a real TPU and the
    corpus tiles — everywhere else the single-jit XLA formulation is
    the same launch count without interpret-mode cost."""
    if mode == "pallas":
        validate_serving_geometry(n_rows, metric)
        return "pallas"
    if (
        metric in ("cos", "dot")
        and pick_block_n(n_rows) is not None
        and jax.default_backend() == "tpu"
    ):
        return "pallas"
    return "xla"


# ---------------------------------------------------------------------------
# launch accounting
# ---------------------------------------------------------------------------

_tls = threading.local()
_totals_lock = threading.Lock()
_LAUNCH_TOTALS: dict[str, int] = {}
_provider_registered = False


class _Tick:
    """Per-serving-tick launch ledger (thread-local; nested ticks fold
    into the outermost one)."""

    __slots__ = ("counts",)

    def __init__(self) -> None:
        self.counts: dict[str, int] = {}

    @property
    def total(self) -> int:
        return sum(self.counts.values())


class _ServingLaunchMetricsProvider:
    """``pathway_serving_launches_total{stage=}`` counter family: one
    series per dispatch stage on the serving search path (``fused`` /
    ``prep`` / ``score`` / ``topk`` / ``rescore`` / ``wire``)."""

    def stats(self) -> dict:
        return {"serving_launches": launch_totals()}

    def openmetrics_lines(self) -> list[str]:
        from ..internals.metrics_names import escape_label_value

        with _totals_lock:
            items = sorted(_LAUNCH_TOTALS.items())
        if not items:
            return []
        lines = ["# TYPE pathway_serving_launches_total counter"]
        for stage, n in items:
            lines.append(
                f'pathway_serving_launches_total{{stage="'
                f'{escape_label_value(stage)}"}} {n}'
            )
        return lines


def _ensure_provider() -> None:
    global _provider_registered
    if _provider_registered:
        return
    from ..internals.monitoring import register_metrics_provider_once

    register_metrics_provider_once(
        "serving_launches", _ServingLaunchMetricsProvider
    )
    _provider_registered = True


def record_launch(stage: str, n: int = 1) -> None:
    """Count one serving-path device dispatch.  Rides the current
    :func:`serving_tick` (if one is open) AND the process-lifetime
    ``pathway_serving_launches_total{stage=}`` counters."""
    if not launch_accounting_enabled():
        return
    _ensure_provider()
    with _totals_lock:
        _LAUNCH_TOTALS[stage] = _LAUNCH_TOTALS.get(stage, 0) + n
    tick = getattr(_tls, "tick", None)
    if tick is not None:
        tick.counts[stage] = tick.counts.get(stage, 0) + n


@contextlib.contextmanager
def serving_tick():
    """Scope one serving tick's launch ledger: yields the :class:`_Tick`
    (``.counts`` maps stage → dispatches, ``.total`` sums them) and, on
    exit, records a ``serving.tick`` flight-recorder span whose attrs
    carry the per-tick launch counts — the ≤2-launches-per-tick pin is
    readable straight off the trace.  Reentrant: a nested tick folds
    into the outermost one (one span per logical tick)."""
    outer = getattr(_tls, "tick", None)
    if outer is not None:
        yield outer
        return
    from ..internals.flight_recorder import span

    tick = _Tick()
    _tls.tick = tick
    with span("serving.tick", "serve") as timed:
        try:
            yield tick
        finally:
            _tls.tick = None
            if tick.counts and launch_accounting_enabled():
                timed.set(
                    launches=tick.total,
                    **{
                        f"launches.{stage}": n
                        for stage, n in sorted(tick.counts.items())
                    },
                )
            else:  # a tick that launched nothing is no news for the ring
                timed.record = False


def launch_totals() -> dict[str, int]:
    """Process-lifetime launch counters (stage → count), a snapshot."""
    with _totals_lock:
        return dict(_LAUNCH_TOTALS)


def reset_launch_metrics() -> None:
    """Test hook: zero the process-lifetime launch counters."""
    with _totals_lock:
        _LAUNCH_TOTALS.clear()


# ---------------------------------------------------------------------------
# shared stage bodies (one arithmetic, three formulations)
# ---------------------------------------------------------------------------


def _l2_normalize(q: jax.Array) -> jax.Array:
    """Row L2 normalize, f32.  ``x*x`` is bitwise ``abs(x)**2`` for f32,
    so this matches ``jnp.linalg.norm``-based callers exactly — one
    arithmetic shared by the megakernel (in VMEM) and the XLA bodies."""
    norm = jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True))
    return q / jnp.maximum(norm, 1e-30)


def _prep_body(q: jax.Array, q_b: int, normalize: bool) -> jax.Array:
    """f32 widen → optional L2 normalize → pad to the Q bucket (the
    ``knn._prep_queries`` math, here inlined into the fused jits so
    query prep stops being its own dispatch)."""
    q = q.astype(jnp.float32)
    if normalize:
        q = _l2_normalize(q)
    if q_b > q.shape[0]:
        q = jnp.pad(q, ((0, q_b - q.shape[0]), (0, 0)))
    return q


def _merge_topk(cand_s, cand_i, k: int):
    """Online top-k merge: select the k best of ``cand_s`` (ties toward
    the lower candidate POSITION — running buffer first, then ascending
    slot — which reproduces ``lax.top_k``'s stable lowest-index-first
    order over the full row).  Vectorized compare/select/reduce only, so
    the body lowers on Mosaic (no sort, no gather)."""
    bq, w = cand_s.shape
    pos = lax.broadcasted_iota(jnp.int32, (bq, w), 1)
    lane = lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    best_s0 = jnp.full((bq, k), _UNFILLED, jnp.float32)
    best_i0 = jnp.zeros((bq, k), jnp.int32)

    def body(t, carry):
        cs, bs, bi = carry
        m = jnp.max(cs, axis=1)
        # first-occurrence argmax via masked position-min (ties resolve
        # toward the earlier candidate, the stable-top_k tie rule)
        first = jnp.min(jnp.where(cs == m[:, None], pos, w), axis=1)
        hit = pos == first[:, None]
        sel = jnp.sum(jnp.where(hit, cand_i, 0), axis=1)
        bs = jnp.where(lane == t, m[:, None], bs)
        bi = jnp.where(lane == t, sel[:, None], bi)
        # taken entries drop strictly below every live sentinel
        cs = jnp.where(hit, -jnp.inf, cs)
        return cs, bs, bi

    _, best_s, best_i = lax.fori_loop(0, k, body, (cand_s, best_s0, best_i0))
    return best_s, best_i


# ---------------------------------------------------------------------------
# Pallas megakernel (dense f32/bf16 rows + int8 codes variants)
# ---------------------------------------------------------------------------


#: the largest corpus block the dense megakernel runs under the compiler's
#: default VMEM limit (16 MiB): beyond it the call asks for its own
_DEFAULT_VMEM_BLOCK_BYTES = 2 << 20


def pallas_fused_topk(
    q: jax.Array,  # [q_b, D] f32 (widened+padded by the jit wrapper)
    vectors: jax.Array,  # [N, D] f32/bf16
    valid: jax.Array,  # [N] f32 {0,1}
    *,
    k: int,
    metric: str,
    normalize: bool,
    qdt: str,
    block_n: int,
    interpret: bool,
):
    """Dense serving megakernel: ONE launch from raw query block to
    ``(top-k scores, top-k slots)``.  Grid streams corpus blocks minor;
    the query tile normalizes into the (revisited) ``qn`` output at the
    first block and stays VMEM-resident; the running top-k lives in the
    revisited output blocks, merged online per block — the ``[Q, N]``
    score matrix never exists."""
    from jax.experimental import pallas as pl

    q_b, d = q.shape
    n = vectors.shape[0]
    block_q = min(q_b, 256)
    cdt = _DTYPES[qdt]

    def kernel(q_ref, v_ref, m_ref, qn_ref, s_ref, i_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            qf = q_ref[:].astype(jnp.float32)
            if normalize:
                qf = _l2_normalize(qf)
            qn_ref[:] = qf
            s_ref[:] = jnp.full((block_q, k), _UNFILLED, jnp.float32)
            i_ref[:] = lax.broadcasted_iota(jnp.int32, (block_q, k), 1)

        qc = qn_ref[:].astype(cdt)
        scores = jnp.dot(
            qc, v_ref[:].astype(cdt).T, preferred_element_type=jnp.float32
        )
        masked = jnp.where(m_ref[:][None, :] > 0, scores, _MASKED)
        gidx = j * block_n + lax.broadcasted_iota(
            jnp.int32, (block_q, block_n), 1
        )
        cand_s = jnp.concatenate([s_ref[:], masked], axis=1)
        cand_i = jnp.concatenate([i_ref[:], gidx], axis=1)
        best_s, best_i = _merge_topk(cand_s, cand_i, k)
        i_ref[:] = best_i

        @pl.when(j == pl.num_programs(1) - 1)
        def _final():
            # sentinel → -inf: bit-identical to the reference's
            # where(valid, s, -inf) masking at the output surface
            s_ref[:] = jnp.where(best_s <= _MASKED, -jnp.inf, best_s)

        @pl.when(j < pl.num_programs(1) - 1)
        def _carry():
            s_ref[:] = best_s

    grid = (q_b // block_q, n // block_n)
    # a corpus block is held twice by the pipeline and once more,
    # transposed, by the product: 1,024 rows of 384 values fit the
    # compiler's own VMEM limit, rows of 2,048 values (8 MiB a block) do
    # not.  The block cannot shrink instead: the one-dimensional mask is
    # tiled by 1,024.  Narrow rows keep the default, and their program.
    block_bytes = block_n * d * vectors.dtype.itemsize
    extra = {}
    if block_bytes > _DEFAULT_VMEM_BLOCK_BYTES and not interpret:
        from jax.experimental.pallas import tpu as pltpu

        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=4 * block_bytes + (16 << 20)
        )
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((q_b, d), jnp.float32),
            jax.ShapeDtypeStruct((q_b, k), jnp.float32),
            jax.ShapeDtypeStruct((q_b, k), jnp.int32),
        ),
        grid=grid,
        **extra,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n,), lambda i, j: (j,)),
        ],
        out_specs=(
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i, j: (i, 0)),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * q_b * n * d,
            bytes_accessed=(
                n * d * vectors.dtype.itemsize + n * 4 + q_b * d * 4
                + q_b * k * 8
            ),
            transcendentals=0,
        ),
        interpret=interpret,
    )(q, vectors, valid)


def pallas_fused_quant_topk(
    q: jax.Array,  # [q_b, D] f32
    codes: jax.Array,  # [N, D] int8
    scales: jax.Array,  # [N] f32
    valid: jax.Array,  # [N] f32 {0,1}
    *,
    c: int,
    normalize: bool,
    block_n: int,
    interpret: bool,
):
    """Quantized serving megakernel: normalize in VMEM, asymmetric
    int8 dequant-in-register scoring (``scale_v * (q · codes_v)``, the
    ``quantized_scoring`` math — HBM only ever moves 1 byte/element),
    online top-c merge across the code-block grid.  Returns
    ``(cand scores, cand slots, normalized queries)`` — the third
    output feeds the rescore-ring pass without re-normalizing."""
    from jax.experimental import pallas as pl

    q_b, d = q.shape
    n = codes.shape[0]
    block_q = min(q_b, 256)
    ct = compute_dtype()

    def kernel(q_ref, c_ref, sc_ref, m_ref, qn_ref, s_ref, i_ref):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            qf = q_ref[:].astype(jnp.float32)
            if normalize:
                qf = _l2_normalize(qf)
            qn_ref[:] = qf
            s_ref[:] = jnp.full((block_q, c), _UNFILLED, jnp.float32)
            i_ref[:] = lax.broadcasted_iota(jnp.int32, (block_q, c), 1)

        dots = jnp.dot(
            qn_ref[:].astype(ct), c_ref[:].astype(ct).T,
            preferred_element_type=jnp.float32,
        )
        scored = dots * sc_ref[:][None, :]
        masked = jnp.where(m_ref[:][None, :] > 0, scored, _MASKED)
        gidx = j * block_n + lax.broadcasted_iota(
            jnp.int32, (block_q, block_n), 1
        )
        cand_s = jnp.concatenate([s_ref[:], masked], axis=1)
        cand_i = jnp.concatenate([i_ref[:], gidx], axis=1)
        best_s, best_i = _merge_topk(cand_s, cand_i, c)
        i_ref[:] = best_i

        @pl.when(j == pl.num_programs(1) - 1)
        def _final():
            s_ref[:] = jnp.where(best_s <= _MASKED, -jnp.inf, best_s)

        @pl.when(j < pl.num_programs(1) - 1)
        def _carry():
            s_ref[:] = best_s

    grid = (q_b // block_q, n // block_n)
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((q_b, d), jnp.float32),
            jax.ShapeDtypeStruct((q_b, c), jnp.float32),
            jax.ShapeDtypeStruct((q_b, c), jnp.int32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n,), lambda i, j: (j,)),
            pl.BlockSpec((block_n,), lambda i, j: (j,)),
        ],
        out_specs=(
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, c), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, c), lambda i, j: (i, 0)),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * q_b * n * d,
            bytes_accessed=n * d + n * 8 + q_b * d * 4 + q_b * c * 8,
            transcendentals=0,
        ),
        interpret=interpret,
    )(q, codes, scales, valid)


# ---------------------------------------------------------------------------
# fused jits (ONE dispatch each; the Pallas wrappers fold widen+pad into
# the same launch as the kernel)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("k", "q_b", "metric", "normalize", "qdt"),
)
def _xla_fused_dense(q, vectors, valid, *, k, q_b, metric, normalize, qdt):
    qn = _prep_body(q, q_b, normalize)
    s = _dense_scores(qn.astype(_DTYPES[qdt]), vectors, metric)
    s = jnp.where(valid[None, :], s, -jnp.inf)
    return lax.top_k(s, k)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "q_b", "metric", "normalize", "qdt", "block_n", "interpret",
    ),
)
def _pallas_fused_dense(
    q, vectors, valid, *, k, q_b, metric, normalize, qdt, block_n, interpret
):
    del metric  # cos/dot share the dot body; validate gated l2sq out
    qp = q.astype(jnp.float32)
    if q_b > qp.shape[0]:
        qp = jnp.pad(qp, ((0, q_b - qp.shape[0]), (0, 0)))
    _qn, scores, idx = pallas_fused_topk(
        qp,
        vectors,
        valid.astype(jnp.float32),
        k=k,
        metric="dot",
        normalize=normalize,
        qdt=qdt,
        block_n=block_n,
        interpret=interpret,
    )
    return scores, idx


@functools.partial(
    jax.jit,
    static_argnames=("c", "k", "q_b", "metric", "normalize", "use_cache"),
)
def _xla_fused_quant(
    q, codes, scales, valid, cache_vecs, cache_map,
    *, c, k, q_b, metric, normalize, use_cache,
):
    from .quantized_scoring import _rescore_body

    qn = _prep_body(q, q_b, normalize)
    s = _reference_scores(qn, codes, scales, valid, metric)
    cand_s, cand_i = lax.top_k(s, c)
    if not use_cache:
        return cand_s[:, :k], cand_i[:, :k]
    return _rescore_body(qn, cand_s, cand_i, cache_vecs, cache_map, k, metric)


@functools.partial(
    jax.jit,
    static_argnames=("c", "q_b", "normalize", "block_n", "interpret"),
)
def _pallas_fused_quant(
    q, codes, scales, valid, *, c, q_b, normalize, block_n, interpret
):
    qp = q.astype(jnp.float32)
    if q_b > qp.shape[0]:
        qp = jnp.pad(qp, ((0, q_b - qp.shape[0]), (0, 0)))
    return pallas_fused_quant_topk(
        qp,
        codes,
        scales,
        valid.astype(jnp.float32),
        c=c,
        normalize=normalize,
        block_n=block_n,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# staged reference formulation (the legacy separate-launch chain)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k",))
def _staged_topk(s, *, k):
    return lax.top_k(s, k)


@functools.partial(jax.jit, static_argnames=("metric",))
def _staged_dense_scores(q, vectors, valid, *, metric):
    s = _dense_scores(q, vectors, metric)
    return jnp.where(valid[None, :], s, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("metric",))
def _staged_quant_scores(q, codes, scales, valid, *, metric):
    return _reference_scores(q, codes, scales, valid, metric)


def dense_reference_search(q, vectors, valid, *, k, metric, qdt):
    """Separate-launch legacy chain (the A/B baseline): the full
    ``[Q, N]`` masked score matrix materializes in HBM between two
    dispatches.  ``q`` arrives prepped (normalized + padded)."""
    s = _staged_dense_scores(
        jnp.asarray(q, dtype=_DTYPES[qdt]), vectors, valid, metric=metric
    )
    record_launch("score")
    out = _staged_topk(s, k=k)
    record_launch("topk")
    return out


def quant_reference_search(
    q, codes, scales, valid, cache_vecs, cache_map,
    *, c, k, metric, use_cache,
):
    """Quantized legacy chain: asymmetric scores / top-c / rescore as
    three separate dispatches (+1 for prep upstream = the ≥4-launch
    baseline the megakernel collapses)."""
    qf = jnp.asarray(q, dtype=jnp.float32)
    s = _staged_quant_scores(qf, codes, scales, valid, metric=metric)
    record_launch("score")
    cand_s, cand_i = _staged_topk(s, k=c)
    record_launch("topk")
    if not use_cache:
        return cand_s[:, :k], cand_i[:, :k]
    out = rescore_topk(
        qf, cand_s, cand_i, cache_vecs, cache_map, k=k, metric=metric
    )
    record_launch("rescore")
    return out


# ---------------------------------------------------------------------------
# fused dispatchers (what the index search path calls)
# ---------------------------------------------------------------------------


def dense_fused_search(
    q, vectors, valid, *, k, q_b, metric, normalize, qdt, mode
):
    """One-launch dense search: raw (device or host) queries in,
    ``(scores[q_b,k], slots[q_b,k])`` out — normalize, pad, score and
    top-k all inside a single dispatch (megakernel or fused XLA per
    :func:`pick_serving_impl`)."""
    impl = pick_serving_impl(mode, vectors.shape[0], metric)
    record_launch("fused")
    if impl == "pallas":
        block_n = validate_serving_geometry(vectors.shape[0], metric)
        return _pallas_fused_dense(
            q, vectors, valid,
            k=k, q_b=q_b, metric=metric, normalize=normalize, qdt=qdt,
            block_n=block_n,
            interpret=jax.default_backend() != "tpu",
        )
    return _xla_fused_dense(
        q, vectors, valid,
        k=k, q_b=q_b, metric=metric, normalize=normalize, qdt=qdt,
    )


def quant_fused_search(
    q, codes, scales, valid, cache_vecs, cache_map,
    *, c, k, q_b, metric, normalize, use_cache, mode,
):
    """Fused quantized search: megakernel stage-1 (top-c) + the
    rescore-ring handoff as the only second launch, or — on the XLA
    lowering — the whole funnel (normalize → asymmetric scores → top-c
    → rescore) under ONE jit.  Either way ≤2 launches per tick."""
    impl = pick_serving_impl(mode, codes.shape[0], metric)
    record_launch("fused")
    if impl == "pallas":
        block_n = validate_serving_geometry(codes.shape[0], metric)
        qn, cand_s, cand_i = _pallas_fused_quant(
            q, codes, scales, valid,
            c=c, q_b=q_b, normalize=normalize, block_n=block_n,
            interpret=jax.default_backend() != "tpu",
        )
        if not use_cache:
            return cand_s[:, :k], cand_i[:, :k]
        out = rescore_topk(
            qn, cand_s, cand_i, cache_vecs, cache_map, k=k, metric=metric
        )
        record_launch("rescore")
        return out
    return _xla_fused_quant(
        q, codes, scales, valid, cache_vecs, cache_map,
        c=c, k=k, q_b=q_b, metric=metric, normalize=normalize,
        use_cache=use_cache,
    )


# observable compile counts: the fused serving sites share the
# bucket_q/bucket_k flatness contract (heterogeneous (Q, k) serving
# traffic lands on the bounded static grid, pinned by test)
from ..internals.flight_recorder import instrument_jit as _instrument_jit

_xla_fused_dense = _instrument_jit(_xla_fused_dense, "serving.fused_topk")
_pallas_fused_dense = _instrument_jit(
    _pallas_fused_dense, "serving.fused_topk_pallas"
)
_xla_fused_quant = _instrument_jit(_xla_fused_quant, "serving.fused_quant")
_pallas_fused_quant = _instrument_jit(
    _pallas_fused_quant, "serving.fused_quant_pallas"
)
_staged_topk = _instrument_jit(_staged_topk, "serving.reference_topk")
_staged_dense_scores = _instrument_jit(
    _staged_dense_scores, "serving.reference_scores"
)
_staged_quant_scores = _instrument_jit(
    _staged_quant_scores, "serving.reference_quant_scores"
)
