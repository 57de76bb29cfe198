"""HBM-resident brute-force KNN index with incremental upsert/delete.

reference semantics: src/external_integration/brute_force_knn_integration.rs
(dense matrix, grow-by-doubling at :113-120, cos + l2sq, top-k) — redesigned
for TPU:

* the vector matrix lives on device (HBM) as a padded ``[capacity, dim]``
  array; rows are recycled through a tombstone ``valid`` mask instead of
  compaction, so deletes are O(1) mask flips and search stays one fused
  matmul+top-k on the MXU (``ops/topk.py``);
* upserts/deletes arriving from the dataflow are staged host-side and
  applied in one scatter per micro-batch (donated buffers — no reallocation
  until the capacity doubles);
* cosine vectors are L2-normalized once at insert, making query scoring a
  plain dot product.

The multi-device sharded variant lives in ``pathway_tpu/parallel/index.py``.
"""

from __future__ import annotations

import functools
import itertools
import os
import threading
import weakref
from typing import Any, Hashable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .topk import topk_search
from .quantized_scoring import (
    dequantize_record,
    is_quant_record,
    quantize_jnp,
    rescore_cache_rows_default,
    rescore_depth_default,
    resolve_index_dtype,
)

__all__ = [
    "DeviceKnnIndex",
    "upsert_slice_rows",
    "upsert_coalesce_rows",
    "quantization_status",
]


def upsert_slice_rows() -> int:
    """Row cap per staged device scatter (``PATHWAY_UPSERT_SLICE_ROWS``,
    default 1024 — the largest dispatch batch bucket).  Device batches
    bigger than this are staged as multiple bounded slices, so (a) the
    scatter compile set stays on the bounded grid a jumbo bulk load
    would otherwise blow past, and (b) every individual scatter dispatch
    is tick-sized: under the unified runtime a bulk backfill becomes a
    sequence of bounded device steps instead of one monopolizing launch."""
    try:
        n = int(os.environ.get("PATHWAY_UPSERT_SLICE_ROWS", "1024"))
    except ValueError:
        n = 1024
    return max(n, 1)


def upsert_coalesce_rows() -> int:
    """Row cap per COALESCED apply-time scatter
    (``PATHWAY_UPSERT_COALESCE_ROWS``, default 8192; 0 disables).

    Staging slices batches to tick-sized chunks (``upsert_slice_rows``)
    so the runtime can preempt between them — but once a search (or a
    budget drain) decides to APPLY, issuing one scatter per chunk just
    multiplies dispatch latency: a 100-chunk bulk backlog pays 100
    launches where ~12 suffice.  The apply path therefore re-coalesces
    consecutive staged chunks up to this many rows per scatter (padded
    to a power of two so the compiled scatter shapes stay bounded)."""
    try:
        n = int(os.environ.get("PATHWAY_UPSERT_COALESCE_ROWS", "8192"))
    except ValueError:
        n = 8192
    return max(n, 0)


class DeviceKnnIndex:
    """Single-device incremental KNN index."""

    #: dead-slot fraction beyond which the matrix is rebuilt smaller —
    #: a churny corpus (steady upsert+delete) keeps matmul cost bounded at
    #: O(live) instead of paying for every slot it ever touched (the
    #: reference's HNSW actually removes points, usearch_integration.rs:60-90;
    #: brute-force here compacts instead)
    COMPACT_DEAD_FRACTION = 0.75
    MIN_CAPACITY = 8

    def __init__(
        self,
        dim: int,
        metric: str = "cos",
        capacity: int = 1024,
        dtype=None,
        index_dtype: str | None = None,
        rescore_depth: int | None = None,
        rescore_cache_rows: int | None = None,
    ):
        if metric not in ("cos", "l2sq", "dot"):
            raise ValueError(f"unknown metric {metric!r}")
        self.dim = dim
        self.metric = metric
        #: storage-dtype knob value ("f32" / "bf16" / "int8"); explicit
        #: arg > explicit jnp dtype > PATHWAY_INDEX_DTYPE process default
        self.index_dtype = resolve_index_dtype(index_dtype, dtype)
        self.quantized = self.index_dtype == "int8"
        if self.quantized:
            # compute dtype for queries/rescoring; codes live in int8
            self.dtype = jnp.float32
        else:
            self.dtype = jnp.bfloat16 if self.index_dtype == "bf16" else jnp.float32
        self.capacity = self._round_capacity(int(capacity))
        if self.quantized:
            self.vectors = None  # stale f32 paths must fail loudly
            self.codes = jnp.zeros((self.capacity, dim), dtype=jnp.int8)
            self.scales = jnp.zeros((self.capacity,), dtype=jnp.float32)
            #: stage-1 candidate funnel depth (effective per-search depth
            #: is bucket_k(max(k, rescore_depth)))
            self.rescore_depth = (
                int(rescore_depth)
                if rescore_depth is not None
                else rescore_depth_default()
            )
            #: f32 rescore ring: recently written rows keep an exact
            #: full-precision copy (the latency-critical tier)
            self.rescore_cache_rows = (
                int(rescore_cache_rows)
                if rescore_cache_rows is not None
                else rescore_cache_rows_default()
            )
            r = self.rescore_cache_rows
            self.rescore_vecs = jnp.zeros((r, dim), dtype=jnp.float32)
            self.cache_map = jnp.full((self.capacity,), -1, dtype=jnp.int32)
            # host mirrors of the ring (truth for rebuilds/compaction):
            # slot -> ring row, ring row -> slot (-1 empty), next ring pos
            self._cache_row_of_slot: dict[int, int] = {}
            self._cache_slot_of_row = np.full((r,), -1, dtype=np.int64)
            self._cache_next = 0
            # snapshot-restored rows staged as ready-made codes (zero
            # re-quantization): slot -> (codes int8 [dim], scale f32)
            self._staged_coded: dict[int, tuple[np.ndarray, np.float32]] = {}
        else:
            self.vectors = jnp.zeros((self.capacity, dim), dtype=self.dtype)
            self.rescore_depth = 0
            self.rescore_cache_rows = 0
            self._staged_coded = {}
        self.valid = jnp.zeros((self.capacity,), dtype=bool)
        self.key_of_slot: list[Hashable | None] = [None] * self.capacity
        self.slot_of_key: dict[Hashable, int] = {}
        self.free: list[int] = list(range(self.capacity - 1, -1, -1))
        # staged updates applied lazily before the next search
        self._staged_set: dict[int, np.ndarray] = {}
        self._staged_valid: dict[int, bool] = {}
        # device-resident staged batches: (slots[-1 = pad row], device
        # array [bb, dim]) applied FIFO before the host dict — keeps
        # last-write-wins semantics when the same slot is touched by both
        self._staged_device: list[tuple[np.ndarray, Any]] = []
        # the engine serializes index ops, but REST/serving threads may
        # query while another thread ingests — a coarse reentrant lock
        # keeps every public op a coherent snapshot (cost is ~100ns,
        # noise next to a device dispatch)
        self._lock = threading.RLock()
        # scatter fns — subclasses swap in sharding-preserving variants
        self._scatter_rows_fn = _scatter_rows
        self._scatter_mask_fn = _scatter_mask
        self._scatter_dropping_fn = _scatter_rows_dropping
        self._quant_scatter_fn = _quant_scatter
        self._coded_scatter_fn = _coded_scatter
        #: fatal-device-fault recoveries performed (rebuild_device_arrays)
        self.rebuilds = 0
        #: staged-device scatters actually dispatched (after coalescing) —
        #: the observable the coalescing satellite pins by test
        self.scatter_dispatches = 0
        #: quantized searches answered (quantization-block observable)
        self.quant_searches = 0
        self.quant_label = f"knn{next(_quant_label_seq)}"
        _LIVE_INDEXES.add(self)
        _ensure_index_provider()
        _register_hbm_ledger(self)

    def _round_capacity(self, capacity: int) -> int:
        """Capacities at/above the Pallas threshold are kept at multiples
        of its 1024-row tile so every large index takes the tiled path
        (doubling preserves the invariant)."""
        from .topk import PALLAS_MIN_ROWS

        capacity = max(capacity, self.MIN_CAPACITY)
        if capacity >= PALLAS_MIN_ROWS and capacity % 1024:
            capacity += 1024 - capacity % 1024
        return capacity

    def _place(self) -> None:
        """Re-establish array placement after a rebuild (sharded subclasses
        re-pin to the mesh)."""

    def __len__(self) -> int:
        return len(self.slot_of_key)

    def hbm_bytes(self) -> int:
        """Resident device bytes of this index (matrix + tombstones +,
        when quantized, scales, rescore ring and slot→ring table) — the
        ``pathway_index_hbm_bytes`` observable."""
        cap = self.capacity
        if self.quantized:
            # the ring and the slot→ring table REPLICATE on a mesh (see
            # ShardedKnnIndex) — count every copy, or an operator sizing
            # corpus-per-chip from this gauge overcommits HBM
            repl = getattr(self, "n_shards", 1)
            return (
                cap * self.dim  # int8 codes
                + cap * 4  # f32 scales
                + repl * cap * 4  # int32 cache map (replicated)
                + repl * self.rescore_cache_rows * self.dim * 4  # f32 ring
                + cap  # bool tombstones
            )
        itemsize = jnp.dtype(self.dtype).itemsize
        return cap * self.dim * itemsize + cap

    def hbm_ledger_entries(self):
        """This index's entry in the unified HBM ledger
        (``pathway_hbm_bytes{component="knn:<label>"}``) — an ``int``
        here; :class:`~pathway_tpu.parallel.index.ShardedKnnIndex`
        overrides with a per-shard dict that sums to EXACTLY the same
        total, so the ledger and the legacy ``pathway_index_hbm_bytes``
        gauge can never disagree (one source of truth: this method
        family)."""
        return self.hbm_bytes()

    def staged_hbm_bytes(self) -> int:
        """Device-staged scatter debt: embed→upsert batches that landed
        on device but have not been applied into the matrix yet hold
        their OWN device arrays until the next search drains them —
        invisible to :meth:`hbm_bytes`, real to the allocator."""
        return int(
            sum(
                int(getattr(arr, "nbytes", 0))
                for _slots, arr in list(self._staged_device)
            )
        )

    # -- mutation --
    def upsert(self, key: Hashable, vector: Any) -> None:
        with self._lock:
            self._upsert_locked(key, vector)

    def _upsert_locked(self, key: Hashable, vector: Any) -> None:
        vec = np.asarray(vector, dtype=np.float32).reshape(-1)
        if vec.shape[0] != self.dim:
            raise ValueError(
                f"vector dim {vec.shape[0]} != index dim {self.dim}"
            )
        if self.metric == "cos" and not self.quantized:
            # quantized rows stage RAW and normalize inside the fused
            # device quantize scatter instead — host- and device-staged
            # rows then share ONE normalization arithmetic, so their
            # codes and scales are bit-identical (the invariant the
            # snapshot plane's verbatim code export rests on)
            norm = float(np.linalg.norm(vec))
            if norm > 0:
                vec = vec / norm
        slot = self.slot_of_key.get(key)
        if slot is None:
            if not self.free:
                self._grow()
            slot = self.free.pop()
            self.slot_of_key[key] = slot
            self.key_of_slot[slot] = key
        self._staged_set[slot] = vec
        self._staged_coded.pop(slot, None)
        self._staged_valid[slot] = True

    def upsert_coded(self, key: Hashable, record: dict) -> None:
        """Stage one snapshot record (``quantize_record_np`` output) —
        the zero-re-quantization restore path: a quantized index scatters
        the codes straight back into HBM; any other dtype dequantizes
        once and takes the normal upsert path."""
        with self._lock:
            if not self.quantized:
                self._upsert_locked(key, dequantize_record(record))
                return
            codes = np.asarray(record["codes"], dtype=np.int8).reshape(-1)
            if codes.shape[0] != self.dim:
                raise ValueError(
                    f"record dim {codes.shape[0]} != index dim {self.dim}"
                )
            slot = self.slot_of_key.get(key)
            if slot is None:
                if not self.free:
                    self._grow()
                slot = self.free.pop()
                self.slot_of_key[key] = slot
                self.key_of_slot[slot] = key
            self._staged_coded[slot] = (codes, np.float32(record["scale"]))
            self._staged_set.pop(slot, None)
            self._staged_valid[slot] = True
            # a coded write supersedes any cached f32 copy of the slot's
            # previous value — drop the host mapping and force a device
            # cache_map rebuild at apply time.  The rebuild is marked
            # UNCONDITIONALLY: a slot recycled from the free list may
            # still carry a stale DEVICE mapping from a deleted key
            # (harmless while tombstoned, but a coded revive would score
            # the new key against the old key's ring vector), and the
            # host mirror cannot see that entry.
            pos = self._cache_row_of_slot.pop(slot, None)
            if pos is not None and self._cache_slot_of_row[pos] == slot:
                self._cache_slot_of_row[pos] = -1
            self._cache_map_dirty = True

    #: opt-out hook for subclasses that cannot take device-array staging;
    #: the mesh-sharded index (parallel/index.py) used to set this False —
    #: since PR 8 its dropping scatter pins ``out_shardings`` to the mesh,
    #: so device batches stage everywhere
    _device_stage_ok = True

    def upsert_batch(self, keys: Sequence[Hashable], vectors) -> None:
        """Stage a whole batch of vectors under one lock acquisition.

        ``vectors`` is ``[n, dim]`` — a host array (staged row-by-row like
        :meth:`upsert`), or a DEVICE array straight off the encoder
        (``n >= len(keys)``; rows past ``len(keys)`` are dispatch pad rows).
        Device batches never round-trip to host: they are kept as-is and
        scattered into the HBM matrix by ``_apply_staged`` in one fused
        normalize+scatter, with pad rows dropped via an out-of-bounds
        index (XLA scatter ``mode="drop"``).  This is the ingest-plane
        embed→upsert fast path — the D2H copy of the embedding and the
        H2D re-stage of the same bytes both disappear."""
        with self._lock:
            if isinstance(vectors, np.ndarray) or not self._device_stage_ok:
                vecs = np.asarray(vectors, dtype=np.float32)
                for j, key in enumerate(keys):
                    self._upsert_locked(key, vecs[j])
                return
            if vectors.ndim != 2 or vectors.shape[1] != self.dim:
                raise ValueError(
                    f"vector batch shape {vectors.shape} != [n, {self.dim}]"
                )
            if vectors.shape[0] < len(keys):
                raise ValueError(
                    f"{len(keys)} keys for {vectors.shape[0]} vector rows"
                )
            slots = np.full((vectors.shape[0],), -1, dtype=np.int64)
            row_of_slot: dict[int, int] = {}
            for j, key in enumerate(keys):
                slot = self.slot_of_key.get(key)
                if slot is None:
                    if not self.free:
                        self._grow()
                    slot = self.free.pop()
                    self.slot_of_key[key] = slot
                    self.key_of_slot[slot] = key
                # this device value supersedes any host value staged
                # earlier for the slot (FIFO batches apply before the dict)
                self._staged_set.pop(slot, None)
                self._staged_coded.pop(slot, None)
                self._staged_valid[slot] = True
                # a repeated key within ONE batch would put the same index
                # into the scatter twice — XLA applies duplicate updates in
                # undefined order, so drop the earlier row (last wins, like
                # the host path)
                prev = row_of_slot.get(slot)
                if prev is not None:
                    slots[prev] = -1
                row_of_slot[slot] = j
                slots[j] = slot
            # tick-granularity staging: bound each staged scatter at
            # upsert_slice_rows() rows (slicing a device array is lazy —
            # no host round trip); FIFO order within the batch preserves
            # last-write-wins exactly
            step = upsert_slice_rows()
            n = vectors.shape[0]
            if n <= step:
                self._staged_device.append((slots, vectors))
            else:
                for s in range(0, n, step):
                    self._staged_device.append(
                        (slots[s : s + step], vectors[s : s + step])
                    )

    def remove(self, key: Hashable) -> None:
        with self._lock:
            self._remove_locked(key)

    def _remove_locked(self, key: Hashable) -> None:
        slot = self.slot_of_key.pop(key, None)
        if slot is None:
            return
        self.key_of_slot[slot] = None
        self.free.append(slot)
        self._staged_valid[slot] = False
        self._staged_set.pop(slot, None)
        self._staged_coded.pop(slot, None)
        if self.quantized:
            # ring hygiene only: the device cache_map entry may stay —
            # a tombstoned slot scores -inf in stage 1 and the rescore
            # keeps -inf for invalid candidates, so a stale mapping can
            # never resurrect the row
            pos = self._cache_row_of_slot.pop(slot, None)
            if pos is not None and self._cache_slot_of_row[pos] == slot:
                self._cache_slot_of_row[pos] = -1

    def _grow(self) -> None:
        """Double capacity (reference: brute_force add :113-120)."""
        old = self.capacity
        self.capacity = self._round_capacity(old * 2)
        extra = self.capacity - old
        if self.quantized:
            self.codes = jnp.concatenate(
                [self.codes, jnp.zeros((extra, self.dim), dtype=jnp.int8)]
            )
            self.scales = jnp.concatenate(
                [self.scales, jnp.zeros((extra,), dtype=jnp.float32)]
            )
            self.cache_map = jnp.concatenate(
                [self.cache_map, jnp.full((extra,), -1, dtype=jnp.int32)]
            )
        else:
            self.vectors = jnp.concatenate(
                [self.vectors, jnp.zeros((extra, self.dim), dtype=self.dtype)]
            )
        self.valid = jnp.concatenate([self.valid, jnp.zeros((extra,), dtype=bool)])
        self.key_of_slot.extend([None] * extra)
        self.free.extend(range(self.capacity - 1, old - 1, -1))
        self._place()

    def _maybe_compact(self) -> None:
        """Shrink the matrix once dead slots dominate (amortized: a rebuild
        moves O(live) rows and at least halves capacity, so its cost is
        charged to the deletes that created the slack)."""
        live = len(self.slot_of_key)
        if self.capacity <= self.MIN_CAPACITY:
            return
        if live > self.capacity * (1.0 - self.COMPACT_DEAD_FRACTION):
            return
        new_capacity = self._round_capacity(max(2 * live, self.MIN_CAPACITY))
        if new_capacity >= self.capacity:
            return
        live_slots = sorted(self.slot_of_key.values())
        idx = jnp.asarray(np.asarray(live_slots, dtype=np.int32))
        pad = new_capacity - len(live_slots)
        if self.quantized:
            gathered_c = self.codes[idx] if live_slots else jnp.zeros(
                (0, self.dim), dtype=jnp.int8
            )
            gathered_s = self.scales[idx] if live_slots else jnp.zeros(
                (0,), dtype=jnp.float32
            )
            self.codes = jnp.concatenate(
                [gathered_c, jnp.zeros((pad, self.dim), dtype=jnp.int8)]
            )
            self.scales = jnp.concatenate(
                [gathered_s, jnp.zeros((pad,), dtype=jnp.float32)]
            )
        else:
            gathered = self.vectors[idx] if live_slots else jnp.zeros(
                (0, self.dim), dtype=self.dtype
            )
            self.vectors = jnp.concatenate(
                [gathered, jnp.zeros((pad, self.dim), dtype=self.dtype)]
            )
        self.valid = jnp.concatenate(
            [
                jnp.ones((len(live_slots),), dtype=bool),
                jnp.zeros((pad,), dtype=bool),
            ]
        )
        remap = {old: new for new, old in enumerate(live_slots)}
        if self.quantized:
            # remap the rescore ring's slot side; the ring rows (and the
            # f32 vectors they hold) are untouched — only slot indices
            # moved
            new_row_of_slot: dict[int, int] = {}
            slot_of_row = np.full_like(self._cache_slot_of_row, -1)
            for slot, row in self._cache_row_of_slot.items():
                ns = remap.get(slot)
                if ns is not None:
                    new_row_of_slot[ns] = row
                    slot_of_row[row] = ns
            self._cache_row_of_slot = new_row_of_slot
            self._cache_slot_of_row = slot_of_row
            self._staged_coded = {
                remap[s]: v
                for s, v in self._staged_coded.items()
                if s in remap
            }
            self._rebuild_cache_map(new_capacity)
        self.slot_of_key = {k: remap[s] for k, s in self.slot_of_key.items()}
        self.key_of_slot = [None] * new_capacity
        for key, slot in self.slot_of_key.items():
            self.key_of_slot[slot] = key
        self.capacity = new_capacity
        self.free = list(range(new_capacity - 1, len(live_slots) - 1, -1))
        self._place()

    def apply_staged_budget(self, max_entries: int = 8) -> int:
        """Apply up to ``max_entries`` staged device batches NOW (oldest
        first) and return how many were applied.

        Incremental, tick-sized flushing for bulk backfills: a search
        still applies everything pending (as-of-now semantics are
        untouched — staged rows stay invisible either way until the
        valid-mask scatter in :meth:`_apply_staged` runs), but a bulk
        ingest driver can drain its scatter debt in bounded doses
        between searches instead of handing the next query one
        100-dispatch apply burst.  FIFO order is preserved, so
        last-write-wins semantics against later host writes hold."""
        with self._lock:
            from ..testing import faults

            if faults.enabled and self._staged_device:
                faults.perturb("device.upsert")
            n = 0
            while self._staged_device and n < max_entries:
                self._apply_device_entry(*self._staged_device.pop(0))
                n += 1
            return n

    def _rebuild_cache_map(self, capacity: int) -> None:
        """Re-materialize the device slot→ring-row table from the host
        mirror (capacity changes and rebuilds rewrite slot indices
        wholesale — one H2D of ``[capacity]`` int32 beats scatter
        surgery)."""
        m = np.full((capacity,), -1, dtype=np.int32)
        for slot, row in self._cache_row_of_slot.items():
            if 0 <= slot < capacity:
                m[slot] = row
        self.cache_map = jnp.asarray(m)

    def _assign_cache_rows(
        self, slots: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Ring-assign rescore-cache rows for one apply batch (host
        bookkeeping under the index lock).  Returns ``(rows, map_idx,
        evict_idx)`` aligned with ``slots``: ``rows[j]`` is the cache row
        receiving row j's f32 vector (``R`` = none, dropped by the OOB
        scatter), ``map_idx[j]`` the slot whose mapping is set (capacity
        = none), ``evict_idx[j]`` a slot whose mapping must clear first
        (capacity = none).  A slot already resident reuses its row; a
        batch larger than the ring keeps only its newest R rows."""
        r = self.rescore_cache_rows
        n = int(slots.shape[0])
        rows = np.full((n,), r, dtype=np.int32)
        map_idx = np.full((n,), self.capacity, dtype=np.int32)
        evict_idx = np.full((n,), self.capacity, dtype=np.int32)
        if r <= 0:
            return rows, map_idx, evict_idx
        last_j_of_row: dict[int, int] = {}
        for j in range(n):
            slot = int(slots[j])
            if slot < 0:
                continue
            pos = self._cache_row_of_slot.get(slot)
            if pos is None:
                pos = self._cache_next
                self._cache_next = (pos + 1) % r
                old = int(self._cache_slot_of_row[pos])
                if old >= 0 and self._cache_row_of_slot.get(old) == pos:
                    del self._cache_row_of_slot[old]
                    evict_idx[j] = old
            prev_j = last_j_of_row.get(pos)
            if prev_j is not None:
                # the ring wrapped within this one batch: the earlier
                # row's write must be blanked — duplicate scatter rows
                # apply in undefined order, and its mapping would
                # otherwise resurrect after the evict pass
                rows[prev_j] = r
                map_idx[prev_j] = self.capacity
            last_j_of_row[pos] = j
            self._cache_slot_of_row[pos] = slot
            self._cache_row_of_slot[slot] = pos
            rows[j] = pos
            map_idx[j] = slot
        return rows, map_idx, evict_idx

    def _apply_device_entry(self, slots: np.ndarray, vals: Any) -> None:
        """Scatter ONE staged device batch into the matrix.  Pad rows
        (slot -1) scatter out of bounds and are dropped on device; the
        OOB index is resolved at apply time — capacity may have grown
        since staging.  Shared by the search-time full apply and the
        incremental budget apply so their numerics can never diverge.
        Subclasses with sharded matrices point ``_scatter_dropping_fn``
        at a mesh-pinning variant (``out_shardings``), so device-staged
        rows land in their owning shard instead of collapsing the
        placement onto one device.

        A quantized index routes through the fused quantize+scatter
        instead: rows normalize (cos) and quantize ON DEVICE, codes and
        scales scatter into their matrices, and the f32 rows land in the
        rescore ring — still one launch, no host round trip."""
        idx = np.where(slots >= 0, slots, self.capacity).astype(np.int32)
        self.scatter_dispatches += 1
        if self.quantized:
            self._apply_quantized_rows(
                idx, slots, vals, normalize=(self.metric == "cos")
            )
            return
        self.vectors = self._scatter_dropping_fn(
            self.vectors,
            jnp.asarray(idx),
            vals,
            normalize=(self.metric == "cos"),
        )

    def _apply_quantized_rows(
        self, idx: np.ndarray, slots: np.ndarray, vals: Any, normalize: bool
    ) -> None:
        """One fused quantize+scatter of ``vals`` rows into (codes,
        scales, rescore ring, cache map).  ``idx`` is the drop-resolved
        scatter index (pad rows already at capacity)."""
        rows, map_idx, evict_idx = self._assign_cache_rows(slots)
        (
            self.codes,
            self.scales,
            self.rescore_vecs,
            self.cache_map,
        ) = self._quant_scatter_fn(
            self.codes,
            self.scales,
            self.rescore_vecs,
            self.cache_map,
            jnp.asarray(idx),
            jnp.asarray(rows),
            jnp.asarray(map_idx),
            jnp.asarray(evict_idx),
            vals,
            normalize=normalize,
        )

    def _coalesce_staged_device(
        self,
    ) -> list[tuple[np.ndarray, Any]]:
        """Re-group the staged device chunks into few large scatters
        (≤ :func:`upsert_coalesce_rows` rows each, padded to a power of
        two so compiled scatter shapes stay bounded).

        Only CONSECUTIVE chunks merge, so FIFO order is preserved; a slot
        written by two coalesced chunks keeps only its LAST row (XLA
        applies duplicate scatter indices in undefined order), which is
        exactly the last-write-wins outcome the sequential applies had."""
        entries = self._staged_device
        cap = upsert_coalesce_rows()
        if cap <= 0 or len(entries) <= 1:
            return list(entries)
        groups: list[list[tuple[np.ndarray, Any]]] = []
        cur: list[tuple[np.ndarray, Any]] = []
        rows = 0
        for slots, vals in entries:
            n = int(slots.shape[0])
            if cur and rows + n > cap:
                groups.append(cur)
                cur, rows = [], 0
            cur.append((slots, vals))
            rows += n
        if cur:
            groups.append(cur)
        out: list[tuple[np.ndarray, Any]] = []
        for group in groups:
            if len(group) == 1:
                out.append(group[0])
                continue
            slots = np.concatenate([s for s, _ in group])
            # later occurrences win: blank earlier duplicates (walk from
            # the end; np.concatenate copied, so staged arrays are safe)
            seen: set[int] = set()
            for i in range(len(slots) - 1, -1, -1):
                s = int(slots[i])
                if s < 0:
                    continue
                if s in seen:
                    slots[i] = -1
                else:
                    seen.add(s)
            total = int(slots.shape[0])
            padded = 1 << (total - 1).bit_length()
            parts = [v for _, v in group]
            if padded > total:
                slots = np.concatenate(
                    [slots, np.full((padded - total,), -1, dtype=slots.dtype)]
                )
                parts.append(
                    jnp.zeros(
                        (padded - total, parts[0].shape[1]),
                        dtype=parts[0].dtype,
                    )
                )
            out.append((slots, jnp.concatenate(parts)))
        return out

    def _apply_staged(self) -> None:
        if (
            not self._staged_set
            and not self._staged_valid
            and not self._staged_device
            and not self._staged_coded
        ):
            self._maybe_compact()
            return
        from ..internals.flight_recorder import span

        with span(
            "index.scatter", "index", stage="index.scatter",
            rows=len(self._staged_set) + len(self._staged_coded),
            device_batches=len(self._staged_device),
        ):
            self._scatter_staged()
        self._maybe_compact()

    def _scatter_staged(self) -> None:
        from ..testing import faults

        if faults.enabled:
            # chaos site "device.upsert": the staged scatter is where a
            # flaky dispatch / HBM allocator failure lands in production —
            # a "fail" here surfaces through whichever caller (search or
            # ingest flush) triggered the apply, exercising both
            # containment paths
            faults.perturb("device.upsert")
        # device batches FIRST (FIFO), host dict after: a host upsert that
        # landed later than a device batch for the same slot wins, and
        # upsert_batch already evicts older host entries for its slots.
        # A long backlog coalesces into few large scatters here — the
        # tick-sized chunks existed for preemptibility while QUEUED, not
        # to be paid one launch each once the apply is committed.
        for slots, vals in self._coalesce_staged_device():
            self._apply_device_entry(slots, vals)
        self._staged_device.clear()
        if self._staged_set:
            idx = np.fromiter(self._staged_set.keys(), dtype=np.int32)
            if self.quantized:
                # host rows staged RAW: the fused scatter normalizes
                # (cos) and quantizes on device — the same arithmetic
                # the device-batch path runs, so host- and device-staged
                # rows can never diverge in their codes or scales
                vals = np.stack(list(self._staged_set.values())).astype(
                    np.float32
                )
                self._apply_quantized_rows(
                    idx, idx.astype(np.int64), jnp.asarray(vals),
                    normalize=(self.metric == "cos"),
                )
            else:
                vals = np.stack(list(self._staged_set.values())).astype(self.dtype)
                self.vectors = self._scatter_rows_fn(
                    self.vectors, jnp.asarray(idx), jnp.asarray(vals)
                )
        if self._staged_coded:
            cidx = np.fromiter(self._staged_coded.keys(), dtype=np.int32)
            ccodes = np.stack([c for c, _ in self._staged_coded.values()])
            cscales = np.asarray(
                [s for _, s in self._staged_coded.values()], dtype=np.float32
            )
            self.codes, self.scales = self._coded_scatter_fn(
                self.codes,
                self.scales,
                jnp.asarray(cidx),
                jnp.asarray(ccodes),
                jnp.asarray(cscales),
            )
            self._staged_coded.clear()
            if getattr(self, "_cache_map_dirty", False):
                self._rebuild_cache_map(self.capacity)
                self._cache_map_dirty = False
                self._place()
        if self._staged_valid:
            vidx = np.fromiter(self._staged_valid.keys(), dtype=np.int32)
            vvals = np.fromiter(self._staged_valid.values(), dtype=bool)
            self.valid = self._scatter_mask_fn(
                self.valid, jnp.asarray(vidx), jnp.asarray(vvals)
            )
        self._staged_set.clear()
        self._staged_valid.clear()

    def export_records(self, keys: Sequence[Hashable]) -> dict:
        """Snapshot records for ``keys`` holding the EXACT resident
        bytes (codes + scale) the index serves — one batched gather +
        D2H for the whole delta.  Applying staged first is deliberate:
        a snapshot must describe committed rows, and the apply was due
        at the next search anyway.  Restore scatters these bytes back
        verbatim (``upsert_coded``): bit-identical, zero re-embeds,
        zero re-quantization.  Empty for unquantized indexes."""
        with self._lock:
            if not self.quantized:
                return {}
            self._apply_staged()
            present = [
                (k, self.slot_of_key[k]) for k in keys if k in self.slot_of_key
            ]
            if not present:
                return {}
            slots = jnp.asarray(
                np.asarray([s for _, s in present], dtype=np.int32)
            )
            codes = np.asarray(self.codes[slots])
            scales = np.asarray(self.scales[slots])
            from .quantized_scoring import QUANT_RECORD_KEY

            return {
                k: {
                    QUANT_RECORD_KEY: 1,
                    "codes": codes[i],
                    "scale": np.float32(scales[i]),
                }
                for i, (k, _slot) in enumerate(present)
            }

    # -- fatal-device-fault recovery ------------------------------------
    def rebuild_device_arrays(self, vectors_by_key=None) -> bool:
        """Recreate the device-resident arrays after a fatal device fault
        (HBM OOM, XLA runtime error, failed transfer) without losing the
        host-side bookkeeping.

        Two recovery sources, tried in order:

        1. **host mirror** — pull the (possibly still readable) matrix
           back to host and re-place fresh arrays from the copy; the
           usual path when the fault hit a scatter/launch but the
           resident buffers survived;
        2. **snapshot provider** — ``vectors_by_key`` (key → raw vector,
           e.g. replayed from the operator-snapshot plane by
           ``ExternalIndexNode``): slots are reassigned and every vector
           re-staged, the path when the arrays themselves are gone.

        Staged device batches are salvaged to host where their buffers
        still read; rows that cannot be copied are dropped loudly (the
        error log) rather than poisoning the rebuild.  ``_place()`` runs
        at the end so sharded subclasses re-pin to the mesh instead of
        landing on the default device.  Returns True on success.
        """
        with self._lock:
            return self._rebuild_locked(vectors_by_key)

    def _rebuild_locked(self, vectors_by_key) -> bool:
        from ..internals.errors import register_error

        salvaged: list[tuple[np.ndarray, np.ndarray]] = []
        dropped_slots: list[int] = []
        for slots, vals in self._staged_device:
            try:
                salvaged.append((slots, np.asarray(vals, dtype=np.float32)))
            except Exception:  # noqa: BLE001 — buffer on the dead device
                dropped_slots.extend(int(s) for s in slots if s >= 0)
        self._staged_device.clear()
        if dropped_slots:
            register_error(
                f"index rebuild dropped {len(dropped_slots)} staged device "
                "rows (buffers unreadable after device fault)",
                kind="index",
                operator="knn.rebuild",
            )
        host = valid = None
        try:
            if self.quantized:
                # the quantized resident state is codes+scales (+ the f32
                # rescore ring): pull ALL of it back — a rebuild that
                # resurrected only an f32 matrix would silently lose the
                # codes the searches actually scan (the PR 6 device-fault
                # path predating quantization did exactly that)
                host_codes = np.asarray(self.codes, dtype=np.int8)
                host_scales = np.asarray(self.scales, dtype=np.float32)
                host_cache = np.asarray(self.rescore_vecs, dtype=np.float32)
                host = True
            else:
                host = np.asarray(self.vectors, dtype=np.float32)
            valid = np.asarray(self.valid, dtype=bool)
        except Exception:  # noqa: BLE001 — resident arrays are gone too
            host = None
        slots_reassigned = False
        if host is not None:
            if self.quantized:
                self.codes = jnp.asarray(host_codes)
                self.scales = jnp.asarray(host_scales)
                self.rescore_vecs = jnp.asarray(host_cache)
                self._rebuild_cache_map(self.capacity)
            else:
                self.vectors = jnp.asarray(host.astype(np.float32), dtype=self.dtype)
            self.valid = jnp.asarray(valid)
        elif vectors_by_key is not None:
            # arrays unreadable: rebuild bookkeeping + staging from the
            # snapshot.  Keys absent from the provider (an uncommitted
            # tail) are lost here and re-enter via replay/re-ingest.
            lost = len(self.slot_of_key) - sum(
                1 for k in self.slot_of_key if k in vectors_by_key
            )
            if lost:
                register_error(
                    f"index rebuild from snapshot lost {lost} uncommitted "
                    "rows (will re-enter via replay/re-ingest)",
                    kind="index",
                    operator="knn.rebuild",
                )
            self.slot_of_key = {}
            self.key_of_slot = [None] * self.capacity
            self.free = list(range(self.capacity - 1, -1, -1))
            self._staged_set.clear()
            self._staged_valid.clear()
            self._staged_coded.clear()
            if self.quantized:
                self.codes = jnp.zeros((self.capacity, self.dim), dtype=jnp.int8)
                self.scales = jnp.zeros((self.capacity,), dtype=jnp.float32)
                self.rescore_vecs = jnp.zeros(
                    (self.rescore_cache_rows, self.dim), dtype=jnp.float32
                )
                self._cache_row_of_slot = {}
                self._cache_slot_of_row = np.full(
                    (self.rescore_cache_rows,), -1, dtype=np.int64
                )
                self._cache_next = 0
                self._rebuild_cache_map(self.capacity)
            else:
                self.vectors = jnp.zeros((self.capacity, self.dim), dtype=self.dtype)
            self.valid = jnp.zeros((self.capacity,), dtype=bool)
            for key, vec in vectors_by_key.items():
                # snapshot records restore their codes verbatim (zero
                # re-quantization); raw f32 vectors re-code through the
                # normal staged path
                if is_quant_record(vec):
                    self.upsert_coded(key, vec)
                else:
                    self._upsert_locked(key, vec)
            slots_reassigned = True
        else:
            return False
        if slots_reassigned:
            # the snapshot path reassigned every slot: salvaged batches
            # carry only PRE-rebuild slot indices, so re-staging them
            # would write stale vectors into slots now owned by other
            # keys (or resurrect freed slots).  Drop them loudly — they
            # belong to an uncommitted tail that re-enters via replay.
            n = sum(int((slots >= 0).sum()) for slots, _ in salvaged)
            if n:
                register_error(
                    f"index rebuild from snapshot dropped {n} salvaged "
                    "staged rows (slot layout was reassigned; rows "
                    "re-enter via replay/re-ingest)",
                    kind="index",
                    operator="knn.rebuild",
                )
        else:
            # re-stage salvaged device rows host-side; pre-existing host
            # staging wins (it was staged AFTER the device batches)
            host_staged = set(self._staged_set) | set(self._staged_coded)
            for slots, vals in salvaged:
                for j, slot in enumerate(slots):
                    slot = int(slot)
                    if slot < 0 or slot in host_staged:
                        continue
                    vec = vals[j]
                    if self.metric == "cos" and not self.quantized:
                        # quantized rows stay RAW — the fused scatter
                        # normalizes on device (see _upsert_locked)
                        norm = float(np.linalg.norm(vec))
                        if norm > 0:
                            vec = vec / norm
                    self._staged_set[slot] = vec.astype(np.float32)
                    self._staged_valid[slot] = True
            # dropped rows whose slot holds NO materialized vector (a new
            # key whose only write was the unreadable batch) must not stay
            # pending-valid: the scatter would mark a never-written matrix
            # row live and searches would rank its zeros.  Keys with an
            # old materialized vector keep it.
            for slot in dropped_slots:
                if (
                    slot in self._staged_set
                    or slot in self._staged_coded
                    or bool(valid[slot])
                ):
                    continue
                self._staged_valid.pop(slot, None)
                key = self.key_of_slot[slot]
                if key is not None:
                    del self.slot_of_key[key]
                    self.key_of_slot[slot] = None
                    self.free.append(slot)
        self._place()
        self.rebuilds += 1
        return True

    # -- search --
    def search_among(
        self, query: Any, keys: list[Hashable], k: int
    ) -> list[tuple[Hashable, float]]:
        """Exact rescoring restricted to ``keys`` (LSH candidate sets).
        Gathers candidate rows on device and runs the same fused top-k."""
        with self._lock:
            return self._search_among_locked(query, keys, k)

    def _search_among_locked(self, query, keys, k):
        self._apply_staged()
        slots = [self.slot_of_key[key] for key in keys if key in self.slot_of_key]
        if not slots:
            return []
        q = np.asarray(query, dtype=np.float32).reshape(1, -1)
        if self.metric == "cos":
            norm = np.linalg.norm(q)
            if norm > 0:
                q = q / norm
        idx = jnp.asarray(np.asarray(slots, dtype=np.int32))
        if self.quantized:
            from .quantized_scoring import dequant_gather

            sub_vectors = dequant_gather(self.codes, self.scales, idx)
        else:
            sub_vectors = self.vectors[idx]
        sub_valid = self.valid[idx]
        k_eff = min(k, len(slots))
        scores, sub_idx = topk_search(
            jnp.asarray(q, dtype=self.dtype), sub_vectors, sub_valid, k_eff, self.metric
        )
        out: list[tuple[Hashable, float]] = []
        for s, i in zip(np.asarray(scores)[0], np.asarray(sub_idx)[0]):
            if not np.isfinite(s):
                continue
            key = self.key_of_slot[slots[int(i)]]
            if key is not None:
                out.append((key, float(s)))
        return out

    def search_among_batched(
        self,
        queries: Any,  # [Q, D]
        keys_lists: list[list[Hashable]],
        k: int,
    ) -> list[list[tuple[Hashable, float]]]:
        """Batched :meth:`search_among`: one device call rescoring every
        query against its own candidate set (padded to shared buckets so
        compiled shapes stay stable).  The per-query form pays one
        dispatch per query; this is the LSH serving path."""
        with self._lock:
            return self._search_among_batched_locked(queries, keys_lists, k)

    #: elements budget for the [Q, C, D] candidate gather — bounds peak
    #: HBM next to the resident index (32M f32 elems ≈ 128 MB); larger
    #: batches process in query chunks
    _AMONG_GATHER_ELEMS = 32 * 1024 * 1024

    def _search_among_batched_locked(self, queries, keys_lists, k):
        from .topk import among_topk_search, bucket_k, bucket_q

        self._apply_staged()
        slot_lists = [
            [self.slot_of_key[key] for key in keys if key in self.slot_of_key]
            for keys in keys_lists
        ]
        cmax = max((len(s) for s in slot_lists), default=0)
        if cmax == 0:
            return [[] for _ in keys_lists]
        # bucket the candidate dim: stable compiled shapes
        c_b = max(16, 1 << (cmax - 1).bit_length())
        n_q = len(slot_lists)
        # chunk queries so the [Q, C, D] gather stays within budget (one
        # huge bucket union must not OOM HBM; a chunk of 1 degrades to the
        # per-query cost, never worse)
        max_chunk = max(1, self._AMONG_GATHER_ELEMS // (c_b * self.dim))
        q_all = np.asarray(queries, dtype=np.float32).reshape(n_q, -1)
        results: list[list[tuple[Hashable, float]]] = []
        for start in range(0, n_q, max_chunk):
            chunk = slot_lists[start : start + max_chunk]
            q_b = bucket_q(len(chunk))
            idx = np.zeros((q_b, c_b), np.int32)
            pad_valid = np.zeros((q_b, c_b), bool)
            for i, s in enumerate(chunk):
                idx[i, : len(s)] = s
                pad_valid[i, : len(s)] = True
            q = np.zeros((q_b, self.dim), np.float32)
            q[: len(chunk)] = q_all[start : start + len(chunk)]
            if self.metric == "cos":
                norms = np.linalg.norm(q, axis=1, keepdims=True)
                np.divide(q, norms, out=q, where=norms > 0)
            # bucket k like q/c: heterogeneous serving k values must not
            # each compile a fresh kernel — top_k rows come back sorted,
            # so slicing recovers the exact k-result (ADVICE #2)
            k_eff = min(k, c_b)
            if self.quantized:
                from .quantized_scoring import quant_among_topk_search

                scores, sub_idx = quant_among_topk_search(
                    jnp.asarray(q, dtype=jnp.float32),
                    self.codes,
                    self.scales,
                    self.valid,
                    jnp.asarray(idx),
                    jnp.asarray(pad_valid),
                    bucket_k(k_eff, c_b),
                    self.metric,
                )
            else:
                scores, sub_idx = among_topk_search(
                    jnp.asarray(q, dtype=self.dtype),
                    self.vectors,
                    self.valid,
                    jnp.asarray(idx),
                    jnp.asarray(pad_valid),
                    bucket_k(k_eff, c_b),
                    self.metric,
                )
            scores = np.asarray(scores)[:, :k_eff]
            sub_idx = np.asarray(sub_idx)[:, :k_eff]
            for i in range(len(chunk)):
                row: list[tuple[Hashable, float]] = []
                for s, j in zip(scores[i], sub_idx[i]):
                    if not np.isfinite(s):
                        continue
                    key = self.key_of_slot[int(idx[i, int(j)])]
                    if key is not None:
                        row.append((key, float(s)))
                results.append(row)
        return results

    def quant_depth(self, k: int) -> int:
        """Stage-1 candidate count for a quantized search: the rescore
        funnel never narrows below ``k`` and rides the same power-of-two
        bucket grid as ``k`` itself."""
        from .topk import bucket_k

        return bucket_k(max(k, self.rescore_depth), self.capacity)

    def _quant_device_search(self, q) -> Any:
        """Shared quantized stage-1 inputs: queries as a device f32
        array (kernel/reference cast per mode inside the jit)."""
        return jnp.asarray(q, dtype=jnp.float32)

    def _device_search(self, q: np.ndarray, k: int) -> tuple[jax.Array, jax.Array]:
        """(scores, slot indices) for PREPPED (normalized + padded)
        queries — the staged REFERENCE chain: scoring and top-k as
        separate dispatches with the full ``[Q, N]`` score intermediate
        materialized between them.  Serving reaches this only under
        ``PATHWAY_SERVING_KERNEL=reference`` (the A/B baseline the fused
        path is benched and parity-pinned against); subclasses override
        with the mesh-sharded formulation."""
        from .fused_serving import (
            dense_reference_search,
            quant_reference_search,
            record_launch,
        )
        from .topk import PALLAS_MIN_ROWS, pallas_topk_search

        if self.quantized:
            self.quant_searches += 1
            return quant_reference_search(
                self._quant_device_search(q),
                self.codes,
                self.scales,
                self.valid,
                self.rescore_vecs,
                self.cache_map,
                c=self.quant_depth(k),
                k=min(k, self.capacity),
                metric=self.metric,
                use_cache=self.rescore_cache_rows > 0,
            )
        if (
            self.metric in ("cos", "dot")
            and self.capacity >= PALLAS_MIN_ROWS
            and self.capacity % 1024 == 0
            # compiled Mosaic only: off-TPU the "kernel" would run in
            # interpret mode — a per-element Python-level evaluator meant
            # for test coverage, ~40x slower than the fused XLA path at
            # this size (it silently dominated the CPU exact-search
            # numbers in knn_crossover before the quantized A/B caught it)
            and jax.default_backend() == "tpu"
        ):
            record_launch("topk")
            return pallas_topk_search(
                jnp.asarray(q, dtype=self.dtype),
                self.vectors,
                self.valid,
                min(k, self.capacity),
                self.metric,
            )
        return dense_reference_search(
            q,
            self.vectors,
            self.valid,
            k=min(k, self.capacity),
            metric=self.metric,
            qdt="bf16" if self.dtype == jnp.bfloat16 else "f32",
        )

    def _fused_device_search(
        self, q, k: int, q_b: int, normalize: bool, mode: str
    ) -> tuple[jax.Array, jax.Array]:
        """(scores, slot indices) for RAW queries — the fused serving
        path (megakernel or single-jit XLA per
        ``fused_serving.pick_serving_impl``): widen/normalize/pad, score
        and top-k inside one dispatch, plus at most the rescore-ring
        pass.  Subclasses override with the mesh-sharded fused path."""
        from .fused_serving import dense_fused_search, quant_fused_search

        if self.quantized:
            self.quant_searches += 1
            # raw queries straight in — the fused jit widens/normalizes
            # in-register (no eager pre-cast dispatch like the staged
            # reference's `_quant_device_search`)
            return quant_fused_search(
                q if isinstance(q, jax.Array)
                else jnp.asarray(q, dtype=jnp.float32),
                self.codes,
                self.scales,
                self.valid,
                self.rescore_vecs,
                self.cache_map,
                c=self.quant_depth(k),
                k=min(k, self.capacity),
                q_b=q_b,
                metric=self.metric,
                normalize=normalize,
                use_cache=self.rescore_cache_rows > 0,
                mode=mode,
            )
        return dense_fused_search(
            q if isinstance(q, jax.Array) else jnp.asarray(q),
            self.vectors,
            self.valid,
            k=min(k, self.capacity),
            q_b=q_b,
            metric=self.metric,
            normalize=normalize,
            qdt="bf16" if self.dtype == jnp.bfloat16 else "f32",
            mode=mode,
        )

    def search(
        self,
        queries: Any,
        k: int,
        n_valid: int | None = None,
        *,
        pre_normalized: bool = False,
    ) -> list[list[tuple[Hashable, float]]]:
        """Top-k per query as (key, score) lists, higher scores better.

        ``queries`` may be a host ``[Q, D]`` array, or a DEVICE array
        straight off the encoder (the fused serving tick): device
        queries are normalized and bucket-padded on device — the
        embed→search handoff never round-trips through host memory.
        By default the whole chain runs as the fused serving path —
        normalize, scoring and top-k in ONE launch (megakernel on TPU,
        single-jit XLA elsewhere; ``PATHWAY_SERVING_KERNEL`` selects,
        ``reference`` restores the staged legacy chain).
        ``n_valid`` caps how many leading rows get host-side result
        assembly (the fused tick's trailing dispatch-pad rows searched
        on device anyway, but building and filtering (key, score) lists
        for them is pure waste).  ``pre_normalized`` tells a cos index
        the caller already L2-normalized the queries (the tiered hot
        tier does) so they are not normalized twice."""
        with self._lock:
            return self._search_locked(
                queries, k, n_valid, pre_normalized=pre_normalized
            )

    def _search_locked(self, queries, k, n_valid=None, *, pre_normalized=False):
        from .fused_serving import (
            record_launch,
            serving_kernel_mode,
            serving_tick,
        )
        from .topk import bucket_k, bucket_q

        self._apply_staged()
        on_device = isinstance(queries, jax.Array) and not isinstance(
            queries, np.ndarray
        )
        if on_device and queries.ndim == 1:
            queries = queries[None, :]  # lazy device reshape
        if len(self.slot_of_key) == 0 or k <= 0:
            n = (
                queries.shape[0]
                if on_device
                else np.atleast_2d(np.asarray(queries)).shape[0]
            )
            if n_valid is not None:
                n = min(n, n_valid)
            return [[] for _ in range(n)]
        # normalize cosine queries exactly ONCE: host queries normalize
        # on host (below), device queries inside the fused jit / the
        # reference `_prep_queries` dispatch — never both, and never
        # again when the caller (tiered hot tier) already did
        normalize = self.metric == "cos" and not pre_normalized
        mode = serving_kernel_mode()
        if on_device:
            n_q = queries.shape[0]
            q_b = bucket_q(n_q)
            q = queries
        else:
            q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
            if normalize:
                norms = np.linalg.norm(q, axis=1, keepdims=True)
                norms[norms == 0] = 1.0
                q = q / norms
            normalize = False  # already done, host-side
            n_q = q.shape[0]
            # bucket BOTH dims that vary under serving traffic: the ragged
            # scheduler-tick batch size (pad Q to a power of two, slice
            # back) and the heterogeneous per-request k (bucket_k; top_k
            # rows come back sorted so slicing recovers the exact result)
            # — without this every distinct (Q, k) pair compiles a fresh
            # XLA program
            q_b = bucket_q(n_q)
            if q_b != n_q:
                q = np.concatenate(
                    [q, np.zeros((q_b - n_q, q.shape[1]), dtype=q.dtype)]
                )
        k_req = min(k, self.capacity)
        k_b = bucket_k(k_req, self.capacity)
        with serving_tick():
            if mode == "reference":
                if on_device:
                    q = _prep_queries(q, q_b=q_b, normalize=normalize)
                    record_launch("prep")
                scores, idx = self._device_search(q, k_b)
            else:
                scores, idx = self._fused_device_search(
                    q, k_b, q_b=q_b, normalize=normalize, mode=mode
                )
        if n_valid is not None:
            n_q = min(n_q, n_valid)
        scores = np.asarray(scores)[:n_q]
        idx = np.asarray(idx)[:n_q]
        out: list[list[tuple[Hashable, float]]] = []
        for qi in range(n_q):
            row: list[tuple[Hashable, float]] = []
            for s, i in zip(scores[qi], idx[qi]):
                if not np.isfinite(s):
                    continue
                key = self.key_of_slot[int(i)]
                if key is None:
                    continue
                row.append((key, float(s)))
                if len(row) == k_req:
                    break
            out.append(row)
        return out


@jax.jit
def _scatter_rows(matrix: jax.Array, idx: jax.Array, vals: jax.Array) -> jax.Array:
    return matrix.at[idx].set(vals)


def _scatter_rows_dropping_body(
    matrix: jax.Array, idx: jax.Array, vals: jax.Array, normalize: bool
) -> jax.Array:
    """Device-resident embed→upsert scatter: rows whose index is out of
    bounds (dispatch pad rows) are dropped by XLA, cos rows are
    L2-normalized on device (f32 accumulation) — one fused kernel instead
    of a D2H copy, host normalize, and H2D re-stage.  The un-jitted body
    is shared with the sharded index's mesh-pinning jit
    (``out_shardings``) so the two paths can never numerically diverge."""
    v = vals.astype(jnp.float32)
    if normalize:
        norm = jnp.linalg.norm(v, axis=1, keepdims=True)
        v = v / jnp.maximum(norm, 1e-30)
    return matrix.at[idx].set(v.astype(matrix.dtype), mode="drop")


_scatter_rows_dropping = functools.partial(jax.jit, static_argnames=("normalize",))(
    _scatter_rows_dropping_body
)


def _quant_scatter_body(
    codes: jax.Array,  # [cap, D] int8
    scales: jax.Array,  # [cap] f32
    cache_vecs: jax.Array,  # [R, D] f32
    cache_map: jax.Array,  # [cap] int32
    idx: jax.Array,  # [n] scatter slots (cap = dropped pad row)
    rows: jax.Array,  # [n] ring rows (R = no cache row)
    map_idx: jax.Array,  # [n] slots whose mapping is set (cap = none)
    evict_idx: jax.Array,  # [n] slots whose mapping clears first (cap = none)
    vals: jax.Array,  # [n, D] raw rows (device or host-staged)
    normalize: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Quantized twin of the dropping scatter: normalize (cos) and
    symmetric-scale quantize the rows ON DEVICE, scatter codes+scales
    into the resident matrices, and land the exact f32 rows in the
    rescore ring — one fused launch, the embed→upsert fast path never
    round-trips to host.  All out-of-bounds indices drop, so pad rows
    and no-cache rows cost nothing.  The un-jitted body is shared with
    the sharded index's mesh-pinning jit (``out_shardings``) so the two
    paths can never numerically diverge."""
    v = vals.astype(jnp.float32)
    if normalize:
        norm = jnp.linalg.norm(v, axis=1, keepdims=True)
        v = v / jnp.maximum(norm, 1e-30)
    c, s = quantize_jnp(v)
    codes = codes.at[idx].set(c, mode="drop")
    scales = scales.at[idx].set(s, mode="drop")
    cache_vecs = cache_vecs.at[rows].set(v, mode="drop")
    cache_map = cache_map.at[evict_idx].set(-1, mode="drop")
    cache_map = cache_map.at[map_idx].set(rows.astype(jnp.int32), mode="drop")
    return codes, scales, cache_vecs, cache_map


_quant_scatter = functools.partial(jax.jit, static_argnames=("normalize",))(
    _quant_scatter_body
)


def _coded_scatter_body(
    codes: jax.Array, scales: jax.Array, idx: jax.Array,
    new_codes: jax.Array, new_scales: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Snapshot-restore scatter: ready-made codes land verbatim (zero
    re-quantization — the bytes that were durable are the bytes that
    serve)."""
    return (
        codes.at[idx].set(new_codes, mode="drop"),
        scales.at[idx].set(new_scales, mode="drop"),
    )


_coded_scatter = jax.jit(_coded_scatter_body)


@functools.partial(jax.jit, static_argnames=("q_b", "normalize"))
def _prep_queries(q: jax.Array, q_b: int, normalize: bool) -> jax.Array:
    """Fused-serving query prep, on device: f32 widen, optional L2
    normalize, pad the ragged tick batch up to its Q bucket.  Shapes come
    from the same power-of-two grid as the host path, so the compile set
    stays bounded."""
    q = q.astype(jnp.float32)
    if normalize:
        norm = jnp.linalg.norm(q, axis=1, keepdims=True)
        q = q / jnp.maximum(norm, 1e-30)
    if q_b > q.shape[0]:
        q = jnp.pad(q, ((0, q_b - q.shape[0]), (0, 0)))
    return q


@jax.jit
def _scatter_mask(mask: jax.Array, idx: jax.Array, vals: jax.Array) -> jax.Array:
    return mask.at[idx].set(vals)


# ---------------------------------------------------------------------------
# quantization observability: pathway_index_* series on /status, the
# "quantization" block on /v1/health (internals/health.py reads
# quantization_status() only when this module is already imported — a
# health probe never pulls in jax state)
# ---------------------------------------------------------------------------

#: live device indexes, for /status + /v1/health quantization surfacing
#: (weak: a finished run's indexes drop out with it)
_LIVE_INDEXES: "weakref.WeakSet[DeviceKnnIndex]" = weakref.WeakSet()
_quant_label_seq = itertools.count()


def _live_indexes() -> list["DeviceKnnIndex"]:
    return sorted(_LIVE_INDEXES, key=lambda i: i.quant_label)


class _IndexMetricsProvider:
    """``pathway_index_dtype`` / ``pathway_index_hbm_bytes`` /
    ``pathway_index_rescore_depth`` OpenMetrics series over every live
    device index."""

    def stats(self) -> dict:
        return quantization_status() or {}

    def openmetrics_lines(self) -> list[str]:
        from ..internals.metrics_names import escape_label_value

        indexes = _live_indexes()
        if not indexes:
            return []
        lines = ["# TYPE pathway_index_dtype gauge"]
        for idx in indexes:
            lines.append(
                f'pathway_index_dtype{{index="'
                f'{escape_label_value(idx.quant_label)}",dtype="'
                f'{escape_label_value(idx.index_dtype)}"}} 1'
            )
        lines.append("# TYPE pathway_index_hbm_bytes gauge")
        for idx in indexes:
            lines.append(
                f'pathway_index_hbm_bytes{{index="'
                f'{escape_label_value(idx.quant_label)}"}} {idx.hbm_bytes()}'
            )
        lines.append("# TYPE pathway_index_rescore_depth gauge")
        for idx in indexes:
            lines.append(
                f'pathway_index_rescore_depth{{index="'
                f'{escape_label_value(idx.quant_label)}"}} '
                f"{idx.rescore_depth}"
            )
        return lines


def _ledger_index_bytes(idx: "DeviceKnnIndex"):
    return idx.hbm_ledger_entries()


def _ledger_staged_bytes(idx: "DeviceKnnIndex") -> int:
    return idx.staged_hbm_bytes()


def _register_hbm_ledger(idx: "DeviceKnnIndex") -> None:
    """Every device index is a unified-HBM-ledger client: the resident
    matrix/codes/ring under ``knn:<label>`` and the transient
    staged-scatter debt under ``knn_staged:<label>`` (module-level
    ``bytes_fn``s so the ledger's weak owner ref stays the only
    reference — a bound method would pin the index alive)."""
    from ..observability.hbm_ledger import get_ledger

    led = get_ledger()
    led.register(f"knn:{idx.quant_label}", idx, _ledger_index_bytes)
    led.register(f"knn_staged:{idx.quant_label}", idx, _ledger_staged_bytes)


def _ensure_index_provider() -> None:
    # once-registration with a strong ref held by monitoring (the
    # provider table itself is weak-valued)
    from ..internals.monitoring import register_metrics_provider_once

    register_metrics_provider_once("index_quant", _IndexMetricsProvider)


def quantization_status() -> dict | None:
    """Per-index storage dtype + byte footprint + rescore configuration
    for ``/v1/health`` (None when no device index is live)."""
    indexes = _live_indexes()
    if not indexes:
        return None
    out = {}
    for idx in indexes:
        cap = max(int(idx.capacity), 1)
        info = {
            "dtype": idx.index_dtype,
            # "hot" when the index serves as a tiered index's HBM tier
            # (pathway_tpu/tiering), "primary" when it IS the corpus
            "role": getattr(idx, "tier_role", "primary"),
            "metric": idx.metric,
            "dim": int(idx.dim),
            "capacity_rows": int(idx.capacity),
            "live_rows": len(idx),
            "hbm_bytes": int(idx.hbm_bytes()),
            "bytes_per_vector": round(idx.hbm_bytes() / cap, 2),
        }
        if idx.quantized:
            info["rescore_depth"] = int(idx.rescore_depth)
            info["rescore_cache_rows"] = int(idx.rescore_cache_rows)
            info["cache_rows_live"] = len(idx._cache_row_of_slot)
            info["quant_searches"] = int(idx.quant_searches)
        out[idx.quant_label] = info
    return out


# observable compile counts (pathway_xla_compile_total): upsert scatters
# recompile only on capacity growth/compaction — a climbing counter here
# under steady traffic means the doubling/rounding invariants broke
from ..internals.flight_recorder import instrument_jit as _instrument_jit

_scatter_rows = _instrument_jit(_scatter_rows, "knn.scatter_rows")
_scatter_mask = _instrument_jit(_scatter_mask, "knn.scatter_mask")
# device-batch shapes come from the dispatch bucket grid (plus the
# power-of-two coalesce pads), so this site is bounded by
# (#batch_buckets x capacity growths), like the others
_scatter_rows_dropping = _instrument_jit(
    _scatter_rows_dropping, "knn.scatter_rows_padded"
)
# quantized twins: same bounded shape grids as their f32 counterparts
_quant_scatter = _instrument_jit(_quant_scatter, "knn.quant_scatter")
_coded_scatter = _instrument_jit(_coded_scatter, "knn.coded_scatter")
# fused-serving query prep: shapes are (bucket_q, dim) — same grid the
# search itself compiles over
_prep_queries = _instrument_jit(_prep_queries, "knn.query_prep")
