"""Mesh-sharded KNN index: per-device shards + ICI top-k merge.

reference: src/engine/dataflow/operators/external_index.rs:95-98 keeps a
FULL index replica on every timely worker (index stream ``.broadcast()``)
and shards only the queries.  That replication cannot fit TPU HBM at scale,
so the TPU design inverts it: the vector matrix is sharded row-wise over
the mesh's ``data`` axis (NamedSharding ``P("data", None)``), queries are
replicated, and one ``shard_map``-compiled program computes each shard's
local scores on its MXU, takes a local top-k, then merges across chips
with ``lax.all_gather`` over ICI followed by a final top-k — the classic
distributed-top-k recipe.  Per query the wire cost is ``S·k`` floats+ints
instead of shipping any index rows.
"""

from __future__ import annotations

import functools
import itertools
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.knn import (
    DeviceKnnIndex,
    _coded_scatter_body,
    _quant_scatter_body,
    _scatter_rows_dropping_body,
)
from .mesh import data_axis

__all__ = ["ShardedKnnIndex", "mesh_status"]

NEG_INF = -jnp.inf


@functools.lru_cache(maxsize=None)
def _sharded_search_fn(mesh: Mesh, k: int, metric: str, n_local: int):
    """Compile the per-shard search + ICI merge for one (mesh, k, metric)."""

    def local_search(q, vecs, valid):
        # q: [Q, D] replicated; vecs: [n_local, D]; valid: [n_local]
        if metric in ("cos", "dot"):
            s = jnp.dot(q, vecs.T, preferred_element_type=jnp.float32)
        else:  # l2sq, negated so higher = better
            dots = jnp.dot(q, vecs.T, preferred_element_type=jnp.float32)
            qn = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
            vn = jnp.sum(vecs.astype(jnp.float32) ** 2, axis=-1)
            s = 2.0 * dots - qn - vn[None, :]
        s = jnp.where(valid[None, :], s, NEG_INF)
        k_local = min(k, n_local)
        scores, idx = lax.top_k(s, k_local)
        # local slot -> global slot
        shard = lax.axis_index(data_axis)
        gidx = idx + shard * n_local
        # merge over ICI: all-gather per-shard candidates, final top-k
        all_s = lax.all_gather(scores, data_axis)  # [S, Q, k_local]
        all_i = lax.all_gather(gidx, data_axis)
        n_shards = all_s.shape[0]
        all_s = jnp.transpose(all_s, (1, 0, 2)).reshape(q.shape[0], n_shards * k_local)
        all_i = jnp.transpose(all_i, (1, 0, 2)).reshape(q.shape[0], n_shards * k_local)
        k_out = min(k, n_shards * k_local)
        ms, pos = lax.top_k(all_s, k_out)
        mi = jnp.take_along_axis(all_i, pos, axis=1)
        return ms, mi

    specs = dict(
        mesh=mesh,
        in_specs=(P(), P(data_axis, None), P(data_axis)),
        out_specs=(P(), P()),
    )
    mapped = jax.shard_map(local_search, check_vma=False, **specs)
    return jax.jit(mapped)


@functools.lru_cache(maxsize=None)
def _sharded_quant_search_fn(
    mesh: Mesh, c: int, metric: str, n_local: int, mode: str
):
    """Per-shard asymmetric int8 scoring + ICI top-c merge for one
    (mesh, c, metric, kernel mode).  Each shard scores its slice through
    the SAME dispatcher the single-device path uses
    (``quantized_scoring.quantized_scores``) — on a real TPU the Pallas
    kernel streams the shard's int8 code tiles from HBM, off-TPU the XLA
    reference runs (interpret mode never executes inside shard_map), so
    single-vs-sharded scores come from one scoring body per platform and
    the merged candidate list is bit-identical to the single-device
    stage 1 — the property the quantized parity tests pin.  The rescore
    stage runs OUTSIDE the shard_map against the replicated f32 ring
    (``ops/quantized_scoring.rescore_topk``), exactly as on one
    device."""
    from ..ops.quantized_scoring import _reference_scores, quantized_scores

    on_tpu = jax.default_backend() == "tpu"

    def local_search(q, codes, scales, valid):
        # q: [Q, D] replicated; codes: [n_local, D]; scales/valid:
        # [n_local] — the shard slice through the shared dispatcher
        if on_tpu:
            s = quantized_scores(q, codes, scales, valid, metric, mode)
        else:
            s = _reference_scores(q, codes, scales, valid, metric)
        c_local = min(c, n_local)
        cand, idx = lax.top_k(s, c_local)
        shard = lax.axis_index(data_axis)
        gidx = idx + shard * n_local
        all_s = lax.all_gather(cand, data_axis)
        all_i = lax.all_gather(gidx, data_axis)
        n_shards = all_s.shape[0]
        all_s = jnp.transpose(all_s, (1, 0, 2)).reshape(
            q.shape[0], n_shards * c_local
        )
        all_i = jnp.transpose(all_i, (1, 0, 2)).reshape(
            q.shape[0], n_shards * c_local
        )
        c_out = min(c, n_shards * c_local)
        ms, pos = lax.top_k(all_s, c_out)
        mi = jnp.take_along_axis(all_i, pos, axis=1)
        return ms, mi

    specs = dict(
        mesh=mesh,
        in_specs=(P(), P(data_axis, None), P(data_axis), P(data_axis)),
        out_specs=(P(), P()),
    )
    mapped = jax.shard_map(local_search, check_vma=False, **specs)
    return jax.jit(mapped)


@functools.lru_cache(maxsize=None)
def _sharded_fused_search_fn(
    mesh: Mesh, k: int, metric: str, n_local: int, normalize: bool,
    q_b: int, qdt: str,
):
    """Fused sharded serving: query widen/L2-normalize/pad folded into
    the SAME dispatch as the per-shard search + ICI merge — one launch
    per tick instead of prep + search.  The body reuses the staged
    ``_sharded_search_fn`` computation verbatim (traced inline), so the
    sharded fused-vs-reference parity is by construction."""
    from ..ops.fused_serving import _DTYPES, _prep_body

    base = _sharded_search_fn(mesh, k, metric, n_local)

    def fused(q, vecs, valid):
        qn = _prep_body(q, q_b, normalize)
        return base(qn.astype(_DTYPES[qdt]), vecs, valid)

    return jax.jit(fused)


@functools.lru_cache(maxsize=None)
def _sharded_fused_quant_fn(
    mesh: Mesh, c: int, metric: str, n_local: int, mode: str,
    normalize: bool, q_b: int,
):
    """Quantized twin: prep + per-shard int8 scoring + ICI top-c merge
    in one dispatch, returning the normalized queries alongside the
    candidates so the rescore-ring pass (the only second launch) never
    re-normalizes."""
    from ..ops.fused_serving import _prep_body

    base = _sharded_quant_search_fn(mesh, c, metric, n_local, mode)

    def fused(q, codes, scales, valid):
        qn = _prep_body(q, q_b, normalize)
        cand_s, cand_i = base(qn, codes, scales, valid)
        return cand_s, cand_i, qn

    return jax.jit(fused)


#: live sharded indexes, for /status + /v1/health mesh surfacing (weak:
#: a finished run's indexes drop out with it)
_LIVE_SHARDED: "weakref.WeakSet[ShardedKnnIndex]" = weakref.WeakSet()
_label_seq = itertools.count()


class ShardedKnnIndex(DeviceKnnIndex):
    """KNN index whose vector matrix is sharded over a device mesh.

    Drop-in for :class:`DeviceKnnIndex` — host-side bookkeeping (slots,
    tombstones, staging) is inherited; only array placement and the search
    path change.  Works on any mesh with a ``data`` axis; arrays are
    replicated over other mesh axes.

    Device-batch staging (the ingest plane's embed→upsert fast path) is
    supported since PR 8: the dropping scatter is jitted with
    ``out_shardings`` pinned to the mesh, so staged rows land in their
    owning shard — the PR 5 ``_device_stage_ok=False`` restriction is
    lifted (see MIGRATION).
    """

    def __init__(
        self,
        dim: int,
        mesh: Mesh,
        metric: str = "cos",
        capacity: int = 1024,
        dtype=None,
        index_dtype: str | None = None,
        rescore_depth: int | None = None,
        rescore_cache_rows: int | None = None,
    ):
        self.mesh = mesh
        self.n_shards = mesh.shape[data_axis]
        super().__init__(
            dim,
            metric=metric,
            capacity=int(capacity),
            dtype=dtype,
            index_dtype=index_dtype,
            rescore_depth=rescore_depth,
            rescore_cache_rows=rescore_cache_rows,
        )
        self._vec_sharding = NamedSharding(mesh, P(data_axis, None))
        self._mask_sharding = NamedSharding(mesh, P(data_axis))
        #: the f32 rescore ring and the slot→ring table replicate (they
        #: are small by construction, and the post-merge rescore gathers
        #: arbitrary global slots — a replicated read beats an
        #: all-to-all per search)
        self._repl_sharding = NamedSharding(mesh, P())
        self._place()
        self._scatter_rows_fn = jax.jit(
            lambda m, i, v: m.at[i].set(v), out_shardings=self._vec_sharding
        )
        self._scatter_mask_fn = jax.jit(
            lambda m, i, v: m.at[i].set(v), out_shardings=self._mask_sharding
        )
        # device-staged rows scatter through the SAME body as the
        # single-device path (no numeric divergence) but with the output
        # pinned to the mesh — GSPMD routes each row to its owning shard
        self._scatter_dropping_fn = functools.partial(
            jax.jit,
            static_argnames=("normalize",),
            out_shardings=self._vec_sharding,
        )(_scatter_rows_dropping_body)
        # quantized twins: codes shard row-wise like the f32 matrix,
        # scales like the tombstone mask, ring + map replicated
        self._quant_scatter_fn = functools.partial(
            jax.jit,
            static_argnames=("normalize",),
            out_shardings=(
                self._vec_sharding,
                self._mask_sharding,
                self._repl_sharding,
                self._repl_sharding,
            ),
        )(_quant_scatter_body)
        self._coded_scatter_fn = jax.jit(
            _coded_scatter_body,
            out_shardings=(self._vec_sharding, self._mask_sharding),
        )
        #: fused embed→search ticks answered by this sharded index
        self.sharded_ticks = 0
        self.mesh_label = f"sharded{next(_label_seq)}"
        _LIVE_SHARDED.add(self)
        _ensure_mesh_provider()

    def _round_capacity(self, capacity: int) -> int:
        """Also keep capacity divisible by the shard count through every
        doubling/compaction so row-sharding stays balanced."""
        capacity = super()._round_capacity(max(capacity, 8 * self.n_shards))
        rem = capacity % self.n_shards
        if rem:
            capacity += self.n_shards - rem
        return capacity

    def _place(self) -> None:
        # __init__ ordering: the base constructor builds the arrays before
        # the shardings exist; the explicit _place() call after they do
        # pins both arrays to the mesh
        if hasattr(self, "_vec_sharding"):
            if self.quantized:
                self.codes = jax.device_put(self.codes, self._vec_sharding)
                self.scales = jax.device_put(self.scales, self._mask_sharding)
                self.rescore_vecs = jax.device_put(
                    self.rescore_vecs, self._repl_sharding
                )
                self.cache_map = jax.device_put(
                    self.cache_map, self._repl_sharding
                )
            else:
                self.vectors = jax.device_put(self.vectors, self._vec_sharding)
            self.valid = jax.device_put(self.valid, self._mask_sharding)

    def _device_search(self, q, k: int):
        from ..ops.fused_serving import record_launch

        n_local = self.capacity // self.n_shards
        self.sharded_ticks += 1
        if self.quantized:
            from ..ops.quantized_scoring import kernel_mode, rescore_topk

            self.quant_searches += 1
            k_eff = min(int(k), self.capacity)
            c = self.quant_depth(k_eff)
            fn = _sharded_quant_search_fn(
                self.mesh, c, self.metric, n_local, kernel_mode()
            )
            record_launch("score")
            cand_scores, cand_idx = fn(
                self._quant_device_search(q), self.codes, self.scales, self.valid
            )
            if self.rescore_cache_rows > 0:
                record_launch("rescore")
                return rescore_topk(
                    jnp.asarray(q, dtype=jnp.float32),
                    cand_scores,
                    cand_idx,
                    self.rescore_vecs,
                    self.cache_map,
                    k=k_eff,
                    metric=self.metric,
                )
            return cand_scores[:, :k_eff], cand_idx[:, :k_eff]
        fn = _sharded_search_fn(self.mesh, int(k), self.metric, n_local)
        record_launch("score")
        return fn(jnp.asarray(q, dtype=self.dtype), self.vectors, self.valid)

    def _fused_device_search(self, q, k: int, q_b: int, normalize: bool, mode: str):
        """Fused sharded serving tick: ≤2 launches (1 dense, 2 with the
        int8 rescore-ring pass) — prep rides inside the shard_map jit.
        The ``mode`` knob's pallas/auto distinction is a per-shard
        concern handled by the quantized scoring dispatcher; the merge
        topology is the same either way."""
        from ..ops.fused_serving import record_launch

        n_local = self.capacity // self.n_shards
        self.sharded_ticks += 1
        if self.quantized:
            from ..ops.quantized_scoring import kernel_mode, rescore_topk

            self.quant_searches += 1
            k_eff = min(int(k), self.capacity)
            c = self.quant_depth(k_eff)
            fn = _sharded_fused_quant_fn(
                self.mesh, c, self.metric, n_local, kernel_mode(),
                normalize, q_b,
            )
            record_launch("fused")
            cand_scores, cand_idx, qn = fn(
                q if isinstance(q, jax.Array)
                else jnp.asarray(q, dtype=jnp.float32),
                self.codes,
                self.scales,
                self.valid,
            )
            if self.rescore_cache_rows > 0:
                record_launch("rescore")
                return rescore_topk(
                    qn,
                    cand_scores,
                    cand_idx,
                    self.rescore_vecs,
                    self.cache_map,
                    k=k_eff,
                    metric=self.metric,
                )
            return cand_scores[:, :k_eff], cand_idx[:, :k_eff]
        fn = _sharded_fused_search_fn(
            self.mesh, int(k), self.metric, n_local, normalize, q_b,
            "bf16" if self.dtype == jnp.bfloat16 else "f32",
        )
        record_launch("fused")
        return fn(
            q if isinstance(q, jax.Array) else jnp.asarray(q),
            self.vectors,
            self.valid,
        )

    # -- mesh observability ---------------------------------------------
    def hbm_ledger_entries(self) -> dict[str, int]:
        """Per-shard breakdown for the unified HBM ledger
        (``pathway_hbm_bytes{component="knn:<label>",shard=}``).  The
        shard rows sum to EXACTLY :meth:`hbm_bytes` — the replicated
        rescore ring/cache-map copies are already counted per shard
        there, so an even split (remainder on shard 0) attributes every
        byte exactly once."""
        total = int(self.hbm_bytes())
        n = max(int(self.n_shards), 1)
        base = total // n
        out = {str(i): base for i in range(n)}
        out["0"] = base + (total - base * n)
        return out

    def shard_row_counts(self) -> list[int]:
        """Live rows per shard (row-sharding balance observable — slots
        are allocated LIFO off one free list, so a heavily skewed profile
        here means deletes concentrated in one shard's slot range).

        LOCK-FREE on purpose: health probes and metric scrapes call this,
        and taking ``self._lock`` would block them behind an in-flight
        search or a long staged apply — exactly the "probe stalls during
        heavy ingest" failure /v1/health must not have.  ``list(dict
        .values())`` is one C-level snapshot under the GIL; a concurrent
        resize raises RuntimeError, so retry a few times and report the
        last good approximation (it is a gauge, not an invariant)."""
        n_local = max(self.capacity // self.n_shards, 1)
        slots: list = []
        for _attempt in range(4):
            try:
                slots = list(self.slot_of_key.values())
                break
            except RuntimeError:  # dict resized mid-snapshot
                continue
        counts = [0] * self.n_shards
        for slot in slots:
            counts[min(slot // n_local, self.n_shards - 1)] += 1
        return counts


# ---------------------------------------------------------------------------
# mesh observability: pathway_mesh_* series on /status, mesh block on
# /v1/health (internals/health.py reads mesh_status() only when this
# module is already imported — a health probe never imports jax state)
# ---------------------------------------------------------------------------


class _MeshMetricsProvider:
    """``pathway_mesh_*`` OpenMetrics series over every live sharded
    index: mesh width, per-shard live rows, fused sharded-tick count."""

    def stats(self) -> dict:
        return mesh_status() or {}

    def openmetrics_lines(self) -> list[str]:
        from ..internals.metrics_names import escape_label_value

        indexes = sorted(_LIVE_SHARDED, key=lambda i: i.mesh_label)
        if not indexes:
            return []
        lines = [
            "# TYPE pathway_mesh_devices gauge",
        ]
        for idx in indexes:
            lbl = f'index="{escape_label_value(idx.mesh_label)}"'
            lines.append(f"pathway_mesh_devices{{{lbl}}} {idx.n_shards}")
        lines.append("# TYPE pathway_mesh_shard_rows gauge")
        for idx in indexes:
            lbl = f'index="{escape_label_value(idx.mesh_label)}"'
            for shard, rows in enumerate(idx.shard_row_counts()):
                lines.append(
                    f'pathway_mesh_shard_rows{{{lbl},shard="{shard}"}} {rows}'
                )
        lines.append("# TYPE pathway_mesh_sharded_ticks_total counter")
        for idx in indexes:
            lbl = f'index="{escape_label_value(idx.mesh_label)}"'
            lines.append(
                f"pathway_mesh_sharded_ticks_total{{{lbl}}} {idx.sharded_ticks}"
            )
        return lines


def _ensure_mesh_provider() -> None:
    # once-registration with a strong ref held by monitoring (the
    # provider table itself is weak-valued)
    from ..internals.monitoring import register_metrics_provider_once

    register_metrics_provider_once("mesh", _MeshMetricsProvider)


def mesh_status() -> dict | None:
    """Mesh shape + per-shard row counts for ``/v1/health`` (None when no
    sharded index is live)."""
    indexes = sorted(_LIVE_SHARDED, key=lambda i: i.mesh_label)
    if not indexes:
        return None
    return {
        idx.mesh_label: {
            "devices": int(idx.n_shards),
            "capacity_rows": int(idx.capacity),
            "rows_per_shard": idx.shard_row_counts(),
            "sharded_ticks": int(idx.sharded_ticks),
            "metric": idx.metric,
            "dim": int(idx.dim),
            "index_dtype": idx.index_dtype,
            # "hot" when this mesh-sharded index is a tiered index's
            # per-shard HBM hot tier (pathway_tpu/tiering)
            "role": getattr(idx, "tier_role", "primary"),
        }
        for idx in indexes
    }
