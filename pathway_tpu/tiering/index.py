"""Two-tier KNN index: HBM hot tier + routed host-RAM cold tier.

Design (ROADMAP item 1; EdgeRAG's prune-then-selectively-fetch and
VectorLiteRAG's partition-by-access-pattern, PAPERS.md):

* the **full corpus** lives in one host-RAM f32 matrix (the cold store —
  normalized rows, numpy); a seeded :class:`~pathway_tpu.ops.lsh
  .PartitionRouter` assigns every row to a partition at insert time;
* a bounded **hot tier** (``hot_rows`` rows) is additionally resident in
  HBM behind an ordinary :class:`~pathway_tpu.ops.knn.DeviceKnnIndex`
  (or a mesh-sharded :class:`~pathway_tpu.parallel.index.ShardedKnnIndex`
  — per-shard hot tiers) in any PR 11 ``index_dtype``, so the
  latency-critical slice keeps the one-matmul brute-force tick;
* a **search** is: one HBM brute-force tick over the hot tier, plus a
  device-side routing matmul picking ``probe_partitions`` cold
  partitions, plus a bounded host-side probe of those partitions; both
  candidate streams take their FINAL score from the host f32 mirror
  through one function (``ops/quantized_scoring.host_exact_scores``) and
  merge into one top-k — a key's score can never depend on which tier
  holds it, which is what makes online migration safe to interleave
  with serving;
* **access counts** accumulate per served key; once enough drift builds
  up, a promotion/demotion batch is scheduled as a ``BULK_INGEST``
  work item on the PR 7 :class:`DeviceTickRuntime` (no new loops) —
  promotions stage through the ordinary upsert scatters (landing via
  the PR 8 coalesced dropping-scatter path), demotions are tombstone
  flips, and every move happens under the index lock so a search never
  observes a half-migrated key;
* **snapshots**: the tier assignment (hot key set + router spec) rides
  the PR 6 snapshot plane as a reserved placement row plus the
  delta-chunk header, so a warm restart rebuilds the exact same
  placement with zero re-embeds (stdlib/indexing/lowering.py).
"""

from __future__ import annotations

import heapq
import itertools
import os
import threading
import time
import weakref
from typing import Any, Hashable, Sequence

import numpy as np

from ..ops.lsh import PartitionRouter
from ..ops.quantized_scoring import (
    dequantize_record,
    host_exact_scores,
    is_quant_record,
)

__all__ = [
    "TIER_PLACEMENT_KEY",
    "TieredKnnIndex",
    "tier_hot_rows_default",
    "tier_probe_default",
    "tier_migrate_batch_default",
    "tiering_status",
]

#: reserved snapshot-state key carrying the tier placement blob (hot key
#: set + router spec).  Rides the ordinary upsert delta stream — a plain
#: dict key the PR 6 framing needs no format bump for; readers that
#: predate tiering never see one because only tiered indexes write it.
#: stdlib/indexing/lowering.py pops it before feeding docs to the index.
TIER_PLACEMENT_KEY = "__pw_tier_placement__"


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------


def tier_hot_rows_default() -> int:
    """``PATHWAY_TIER_HOT_ROWS`` (default 0 = tiering off): HBM-resident
    row budget of the hot tier.  Any index factory built without an
    explicit ``hot_rows`` reads this — the process default reaches every
    server with zero plumbing, like ``PATHWAY_INDEX_DTYPE``."""
    try:
        n = int(os.environ.get("PATHWAY_TIER_HOT_ROWS", "0"))
    except ValueError:
        n = 0
    return max(n, 0)


def tier_probe_default() -> int:
    """``PATHWAY_TIER_PROBE_PARTITIONS`` (default 8): cold partitions
    probed per query.  Higher = better recall, more host bytes scanned;
    ``>= n_partitions`` makes the cold probe exhaustive (exact)."""
    try:
        n = int(os.environ.get("PATHWAY_TIER_PROBE_PARTITIONS", "8"))
    except ValueError:
        n = 8
    return max(n, 1)


def tier_migrate_batch_default() -> int:
    """``PATHWAY_TIER_MIGRATE_BATCH`` (default 256; 0 disables online
    migration): max rows moved per scheduled promotion/demotion item."""
    try:
        n = int(os.environ.get("PATHWAY_TIER_MIGRATE_BATCH", "256"))
    except ValueError:
        n = 256
    return max(n, 0)


class TieredKnnIndex:
    """Drop-in two-tier KNN index (module docstring).

    API-compatible with :class:`~pathway_tpu.ops.knn.DeviceKnnIndex` for
    everything the serving/ingest/recovery planes call: ``upsert`` /
    ``upsert_batch`` / ``upsert_coded`` / ``remove`` / ``search`` (host
    or device query batches, ``n_valid``) / ``rebuild_device_arrays`` /
    ``hbm_bytes`` / ``__len__``.
    """

    MIN_CAPACITY = 8

    def __init__(
        self,
        dim: int,
        hot_rows: int,
        metric: str = "cos",
        capacity: int = 1024,
        mesh: Any = None,
        index_dtype: str | None = None,
        n_partitions: int = 64,
        probe_partitions: int | None = None,
        migrate_batch: int | None = None,
        seed: int = 0,
    ):
        if metric not in ("cos", "l2sq", "dot"):
            raise ValueError(f"unknown metric {metric!r}")
        if hot_rows < 1:
            raise ValueError("TieredKnnIndex needs hot_rows >= 1 (0 = use "
                             "an untiered DeviceKnnIndex instead)")
        self.dim = int(dim)
        self.metric = metric
        self.hot_rows = int(hot_rows)
        self.probe_partitions = (
            int(probe_partitions)
            if probe_partitions is not None
            else tier_probe_default()
        )
        self.migrate_batch = (
            int(migrate_batch)
            if migrate_batch is not None
            else tier_migrate_batch_default()
        )
        self.router = PartitionRouter(dim, n_partitions=n_partitions, seed=seed)
        # hot tier: an ordinary device index (per-shard hot tiers when a
        # mesh is given) — its capacity is the hot budget, and the budget
        # is enforced HERE so the device index never grows past it
        if mesh is not None:
            from ..parallel.index import ShardedKnnIndex

            self.hot = ShardedKnnIndex(
                dim=dim, mesh=mesh, metric=metric, capacity=self.hot_rows,
                index_dtype=index_dtype,
            )
        else:
            from ..ops.knn import DeviceKnnIndex

            self.hot = DeviceKnnIndex(
                dim=dim, metric=metric, capacity=self.hot_rows,
                index_dtype=index_dtype,
            )
        self.hot.tier_role = "hot"
        self.index_dtype = self.hot.index_dtype
        # host-RAM cold store: every key's normalized f32 row (the hot
        # tier's rows included — host mirror of the whole corpus; the hot
        # fraction's duplication is bounded by hot_rows)
        self.capacity = max(int(capacity), self.MIN_CAPACITY)
        self._mat = np.zeros((self.capacity, self.dim), dtype=np.float32)
        self.slot_of_key: dict[Hashable, int] = {}
        self.key_of_slot: list[Hashable | None] = [None] * self.capacity
        self.free: list[int] = list(range(self.capacity - 1, -1, -1))
        # partition membership: live slots only (deletes remove the slot)
        self._parts: list[set[int]] = [
            set() for _ in range(self.router.n_partitions)
        ]
        self._part_cache: list[np.ndarray | None] = [None] * self.router.n_partitions
        self._part_of_slot = np.full((self.capacity,), -1, dtype=np.int32)
        # tier placement + access accounting
        self._hot_keys: set[Hashable] = set()
        self._hits: dict[Hashable, int] = {}
        self._hits_dirty = 0
        #: restore override: while set, upserts place per this key set
        #: instead of the fill rule (warm restart rebuilds placement
        #: bit-for-bit; cleared by finish_restore)
        self._forced_hot: set | None = None
        self._placement_rev = 0
        self._placement_dirty = False
        self._migration_pending = False
        self._lock = threading.RLock()
        # observability
        self.searches = 0
        self.probe_rows_total = 0
        self.migrations = {"promote": 0, "demote": 0}
        self.migrate_errors = 0
        self.rebuilds = 0
        self.tier_label = f"tiered{next(_tier_label_seq)}"
        self._migrate_group = None  # built lazily (runtime import)
        #: (trace_id, span_id) of the search that scheduled the pending
        #: migration — the migrate span links back to it
        self._migrate_trace_link: tuple[str, str] | None = None
        _LIVE_TIERED.add(self)
        _ensure_tier_provider()
        # unified HBM ledger: the hot tier registers itself through the
        # DeviceKnnIndex/ShardedKnnIndex constructor, so the ONLY
        # device-resident bytes still unaccounted here are the router's
        # centroid matrix (the [C, D] routing matmul operand)
        from ..observability.hbm_ledger import get_ledger

        get_ledger().register(
            f"tier_router:{self.tier_label}", self, _router_hbm_bytes
        )

    # -- sizing ----------------------------------------------------------
    def __len__(self) -> int:
        return len(self.slot_of_key)

    def hbm_bytes(self) -> int:
        """Device-resident bytes: the hot tier only — the whole point."""
        return self.hot.hbm_bytes()

    def host_bytes(self) -> int:
        """Host-RAM bytes of the cold store (the full-corpus mirror)."""
        return int(self._mat.nbytes + self._part_of_slot.nbytes)

    # NOTE: deliberately NO shard_row_counts passthrough — the restore
    # health path keys mesh fields off that attribute, and the hot
    # tier's per-shard counts would masquerade as the whole (restored)
    # corpus next to rows_restored.  Mesh shape rides the "tiering"
    # health block instead; the sharded hot tier reports its own rows
    # in the "mesh" block under role="hot".
    @property
    def n_shards(self) -> int:
        return getattr(self.hot, "n_shards", 1)

    # -- mutation --------------------------------------------------------
    def _grow_host(self) -> None:
        old = self.capacity
        self.capacity = old * 2
        self._mat = np.concatenate(
            [self._mat, np.zeros((old, self.dim), dtype=np.float32)]
        )
        self.key_of_slot.extend([None] * old)
        self.free.extend(range(self.capacity - 1, old - 1, -1))
        self._part_of_slot = np.concatenate(
            [self._part_of_slot, np.full((old,), -1, dtype=np.int32)]
        )

    def _normalize(self, vecs: np.ndarray) -> np.ndarray:
        v = np.asarray(vecs, dtype=np.float32)
        if self.metric != "cos":
            return v
        norms = np.linalg.norm(v, axis=1, keepdims=True)
        norms[norms == 0] = 1.0
        return v / norms

    def _want_hot_locked(self, key: Hashable) -> bool:
        if key in self._hot_keys:
            return True
        if self._forced_hot is not None:
            return key in self._forced_hot and len(self._hot_keys) < self.hot_rows
        return len(self._hot_keys) < self.hot_rows

    def _set_partition_locked(self, slot: int, part: int) -> None:
        old = int(self._part_of_slot[slot])
        if old == part:
            return
        if old >= 0:
            self._parts[old].discard(slot)
            self._part_cache[old] = None
        self._parts[part].add(slot)
        self._part_cache[part] = None
        self._part_of_slot[slot] = part

    def upsert(self, key: Hashable, vector: Any) -> None:
        vec = np.asarray(vector, dtype=np.float32).reshape(1, -1)
        if vec.shape[1] != self.dim:
            raise ValueError(
                f"vector dim {vec.shape[1]} != index dim {self.dim}"
            )
        self.upsert_batch([key], vec)

    def upsert_coded(self, key: Hashable, record: dict) -> None:
        """Quantized snapshot records (a dtype transition from an int8
        untiered index) dequantize once into the host store."""
        self.upsert(key, dequantize_record(record))

    def upsert_batch(self, keys: Sequence[Hashable], vectors) -> None:
        """Batch upsert.  ``vectors`` is ``[n, dim]`` host OR device
        array (``n >= len(keys)``; trailing rows are dispatch pads).
        The cold store is host RAM, so device batches pay one D2H here —
        the price of a corpus that does not fit HBM; hot-tier rows are
        re-staged to the device index from the host copy."""
        # np.asarray on a jax array is the D2H; pad rows sliced off first
        vecs = np.asarray(vectors, dtype=np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None, :]
        if vecs.shape[1] != self.dim:
            raise ValueError(
                f"vector batch shape {vecs.shape} != [n, {self.dim}]"
            )
        if vecs.shape[0] < len(keys):
            raise ValueError(
                f"{len(keys)} keys for {vecs.shape[0]} vector rows"
            )
        vecs = self._normalize(vecs[: len(keys)])
        parts = self.router.assign(vecs) if len(keys) else np.zeros((0,), np.int32)
        with self._lock:
            hot_keys: list[Hashable] = []
            hot_rows: list[int] = []
            for j, key in enumerate(keys):
                slot = self.slot_of_key.get(key)
                if slot is None:
                    if not self.free:
                        self._grow_host()
                    slot = self.free.pop()
                    self.slot_of_key[key] = slot
                    self.key_of_slot[slot] = key
                self._mat[slot] = vecs[j]
                self._set_partition_locked(slot, int(parts[j]))
                self._hits.setdefault(key, 0)
                if self._want_hot_locked(key):
                    if key not in self._hot_keys:
                        self._hot_keys.add(key)
                        self._placement_dirty = True
                        self._placement_rev += 1
                    hot_keys.append(key)
                    hot_rows.append(slot)
            if hot_keys:
                # last occurrence wins within the batch (the host matrix
                # already holds the final row per slot)
                self.hot.upsert_batch(hot_keys, self._mat[np.asarray(hot_rows)])

    def remove(self, key: Hashable) -> None:
        with self._lock:
            slot = self.slot_of_key.pop(key, None)
            if slot is None:
                return
            self.key_of_slot[slot] = None
            self.free.append(slot)
            part = int(self._part_of_slot[slot])
            if part >= 0:
                self._parts[part].discard(slot)
                self._part_cache[part] = None
                self._part_of_slot[slot] = -1
            self._hits.pop(key, None)
            if key in self._hot_keys:
                self._hot_keys.discard(key)
                self.hot.remove(key)
                self._placement_dirty = True
                self._placement_rev += 1

    # -- search ----------------------------------------------------------
    def _part_slots(self, part: int) -> np.ndarray:
        arr = self._part_cache[part]
        if arr is None:
            arr = np.fromiter(self._parts[part], dtype=np.int64, count=len(self._parts[part]))
            arr.sort()
            self._part_cache[part] = arr
        return arr

    def search(
        self, queries: Any, k: int, n_valid: int | None = None
    ) -> list[list[tuple[Hashable, float]]]:
        """Top-k per query as (key, score) lists, higher scores better.

        One hot-tier device tick (candidates), one device routing matmul,
        one bounded host probe of the routed partitions, one merged exact
        top-k from the host f32 mirror.  Deterministic: equal scores
        break ties by slot, so two processes with the same state answer
        bit-identically regardless of tier placement."""
        q = np.asarray(queries, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        if n_valid is not None:
            q = q[: max(n_valid, 0)]
        n_q = q.shape[0]
        if n_q == 0:
            return []
        with self._lock:
            if not self.slot_of_key or k <= 0:
                return [[] for _ in range(n_q)]
            q = self._normalize(q)
            k_req = min(int(k), len(self.slot_of_key))
            # 1. hot tick: the HBM brute-force candidates.  The queries
            # are already L2-normalized above — `pre_normalized` keeps
            # the fused hot-tier kernel from normalizing a second time
            # (idempotent, but wasted FLOPs and a bf16 rounding
            # divergence risk; pinned by the normalize-once parity test)
            hot_res = (
                self.hot.search(q, k_req, pre_normalized=True)
                if len(self.hot)
                else [[] for _ in range(n_q)]
            )
            # 2. routing: device-side centroid scoring picks the cold
            # partitions each query probes
            routed = self.router.route(q, self.probe_partitions)
            out: list[list[tuple[Hashable, float]]] = []
            for qi in range(n_q):
                slot_arrs = [self._part_slots(int(p)) for p in routed[qi]]
                hot_slots = [
                    self.slot_of_key[key]
                    for key, _ in hot_res[qi]
                    if key in self.slot_of_key
                ]
                if hot_slots:
                    slot_arrs.append(np.asarray(hot_slots, dtype=np.int64))
                cand = (
                    np.unique(np.concatenate(slot_arrs))
                    if slot_arrs
                    else np.zeros((0,), np.int64)
                )
                if cand.size == 0:
                    out.append([])
                    continue
                self.probe_rows_total += int(cand.size)
                # 3. merge: ONE exact scoring of the union against the
                # host f32 mirror — tier-independent final scores
                scores = host_exact_scores(q[qi], self._mat[cand], self.metric)
                k_eff = min(k_req, cand.size)
                order = np.lexsort((cand, -scores))[:k_eff]
                row = []
                for i in order:
                    key = self.key_of_slot[int(cand[i])]
                    if key is None:
                        continue
                    row.append((key, float(scores[i])))
                    self._hits[key] = self._hits.get(key, 0) + 1
                out.append(row)
            self.searches += n_q
            self._hits_dirty += n_q
        self.maybe_schedule_migrations()
        return out

    # -- online tier migration ------------------------------------------
    def plan_migrations(
        self, limit: int | None = None
    ) -> tuple[list[Hashable], list[Hashable]]:
        """(promotions, demotions) by access count: top-hit cold keys
        fill free hot budget, then swap in over the least-hit hot keys
        they strictly out-hit.  Deterministic (ties break by slot)."""
        with self._lock:
            return self._plan_locked(limit)

    def _plan_locked(self, limit):
        limit = int(limit) if limit is not None else self.migrate_batch
        if limit <= 0:
            return [], []
        hits = self._hits
        slot = self.slot_of_key
        # at most ``limit`` cold keys are ever consumed (fill + swap), so
        # a bounded heap selection replaces a full O(n log n) sort of the
        # whole cold set — this runs under the index lock every
        # MIGRATE_CHECK_EVERY searches, and searches block on that lock
        cold = heapq.nsmallest(
            limit,
            (k for k in slot if k not in self._hot_keys),
            key=lambda k: (-hits.get(k, 0), slot[k]),
        )
        free = max(self.hot_rows - len(self._hot_keys), 0)
        promos = cold[: min(free, limit)]
        demos: list[Hashable] = []
        rest = cold[len(promos):]
        if rest and len(promos) < limit:
            hot_asc = heapq.nsmallest(
                limit, self._hot_keys, key=lambda k: (hits.get(k, 0), slot[k])
            )
            for ck, hk in zip(rest, hot_asc):
                if len(promos) >= limit:
                    break
                if hits.get(ck, 0) > hits.get(hk, 0):
                    promos.append(ck)
                    demos.append(hk)
                else:
                    break
        return promos, demos

    def migrate(
        self,
        plan: tuple[list[Hashable], list[Hashable]] | None = None,
        limit: int | None = None,
    ) -> dict:
        """Apply one promotion/demotion batch NOW (planning it first if
        ``plan`` is None).  Keys deleted since the plan was drawn are
        skipped — an in-flight migration of a removed key is a no-op,
        never a resurrection.  Runs under the index lock, so interleaved
        searches see either the old or the new placement, never half."""
        t0 = time.monotonic()
        wall = time.time()
        from ..testing import faults as _faults

        if _faults.enabled:
            try:
                _faults.perturb("tier.migrate")
            except _faults.FaultInjected:
                # chaos containment: a failed migration pass is absorbed
                # right here — placements stay exactly as they were (the
                # batch is all-or-nothing under the lock anyway), serving
                # never notices, and the next search window re-arms the
                # check via the cleared pending flag
                with self._lock:
                    self._migration_pending = False
                    self.migrate_errors += 1
                return {"promoted": 0, "demoted": 0}
        with self._lock:
            self._migration_pending = False
            self._hits_dirty = 0
            trace_link, self._migrate_trace_link = self._migrate_trace_link, None
            promos, demos = plan if plan is not None else self._plan_locked(limit)
            n_promoted = n_demoted = 0
            for key in demos:
                # re-validate: the key must still exist and still be hot
                if key in self.slot_of_key and key in self._hot_keys:
                    self.hot.remove(key)
                    self._hot_keys.discard(key)
                    n_demoted += 1
            up_keys: list[Hashable] = []
            up_slots: list[int] = []
            for key in promos:
                s = self.slot_of_key.get(key)
                if s is None or key in self._hot_keys:
                    continue
                if len(self._hot_keys) + len(up_keys) >= self.hot_rows:
                    break
                up_keys.append(key)
                up_slots.append(s)
            if up_keys:
                # promotions ride the ordinary staged scatter path (and
                # its apply-time coalescing) — bit-for-bit the same
                # arithmetic as a fresh ingest of these rows
                self.hot.upsert_batch(up_keys, self._mat[np.asarray(up_slots)])
                self._hot_keys.update(up_keys)
                n_promoted = len(up_keys)
            if n_promoted or n_demoted:
                self.migrations["promote"] += n_promoted
                self.migrations["demote"] += n_demoted
                self._placement_dirty = True
                self._placement_rev += 1
        try:
            from ..internals.flight_recorder import new_span_id, record_span

            lineage = {}
            if trace_link is not None:
                # link the background migration to the search that
                # triggered it — it shows up in that request's trace
                lineage = {
                    "trace_id": trace_link[0],
                    "span_id": new_span_id(),
                    "parent_id": trace_link[1],
                }
            record_span(
                f"tier:migrate:{self.tier_label}", "runtime", wall,
                (time.monotonic() - t0) * 1000.0,
                attrs={
                    "promoted": n_promoted,
                    "demoted": n_demoted,
                    "hot_rows": len(self._hot_keys),
                },
                **lineage,
            )
        except Exception:  # noqa: BLE001 — observability must never raise
            pass
        return {"promoted": n_promoted, "demoted": n_demoted}

    #: schedule a migration check once this many served queries have
    #: accumulated new hit counts
    MIGRATE_CHECK_EVERY = 16

    def maybe_schedule_migrations(self) -> bool:
        """Submit one promotion/demotion batch as a ``BULK_INGEST`` work
        item on the unified runtime (at most one in flight) — no new
        loop exists anywhere."""
        if self.migrate_batch <= 0:
            return False
        with self._lock:
            if self._migration_pending:
                return False
            if self._hits_dirty < self.MIGRATE_CHECK_EVERY:
                return False
            self._migration_pending = True
        try:
            from ..runtime import QoS, WorkGroup, get_runtime

            if self._migrate_group is None:
                self._migrate_group = WorkGroup(
                    f"tier-migrate:{self.tier_label}",
                    self._migrate_deferred,
                    max_batch=1,
                )
            from ..internals.flight_recorder import current_trace_link

            self._migrate_trace_link = current_trace_link()
            # defer=True: a search executing INSIDE a runtime tick must
            # enqueue the migration for a LATER BULK_INGEST tick, never
            # run it inline on the interactive tick's latency budget
            get_runtime().submit(
                self._migrate_group,
                None,
                qos=QoS.BULK_INGEST,
                tokens=max(self.migrate_batch, 1),
                coalesce_s=0.0,
                defer=True,
            )
            return True
        except Exception:  # noqa: BLE001 — tier maintenance is
            # best-effort: the triggering query's results are already
            # computed, and a transient fault in the runtime submit
            # must not ride its error path.  The check counter re-arms
            # on the next search window.
            self._migration_pending = False
            self.migrate_errors += 1
            return False

    def _migrate_deferred(self, payloads: list) -> list:
        """The migration item's batch handler (runtime tick thread).
        Nobody reads a deferred item's future, so a fault in migrate()
        is absorbed here: counted, and the pending flag cleared so the
        next search window re-arms the check."""
        out = []
        for _ in payloads:
            try:
                out.append(self.migrate())
            except Exception:  # noqa: BLE001 — best-effort, see above
                with self._lock:
                    self._migration_pending = False
                    self.migrate_errors += 1
                out.append(None)
        return out

    # -- snapshot / restore ---------------------------------------------
    def snapshot_header(self) -> dict:
        """Delta-chunk header: the routing state a restored process must
        rebuild verbatim (the router is a pure function of its spec)."""
        return {"router": self.router.spec()}

    def apply_snapshot_header(self, header: dict) -> None:
        spec = (header or {}).get("router")
        if spec:
            self._apply_router_spec(spec)

    def _apply_router_spec(self, spec: dict) -> None:
        with self._lock:
            if self.router.spec() == spec:
                return
            self.router = PartitionRouter.from_spec(spec)
            self._parts = [set() for _ in range(self.router.n_partitions)]
            self._part_cache = [None] * self.router.n_partitions
            self._part_of_slot.fill(-1)
            live = sorted(self.slot_of_key.values())
            if live:
                slots = np.asarray(live, dtype=np.int64)
                parts = self.router.assign(self._mat[slots])
                for s, p in zip(live, parts):
                    self._set_partition_locked(int(s), int(p))

    @property
    def placement_dirty(self) -> bool:
        """Non-consuming probe: tier assignment changed since the last
        staged placement blob.  The streaming driver polls this while
        sources are idle — an online migration driven purely by query
        traffic must still reach the snapshot plane, so the driver steps
        the engine once to let ``end_of_step`` stage and persist it."""
        return self._placement_dirty

    def placement_blob_if_dirty(self) -> dict | None:
        """The placement delta the snapshot plane stages when the tier
        assignment changed since the last one (lowering.end_of_step)."""
        with self._lock:
            if not self._placement_dirty:
                return None
            self._placement_dirty = False
            return self._placement_blob_locked()

    def placement_blob(self) -> dict:
        with self._lock:
            return self._placement_blob_locked()

    def _placement_blob_locked(self) -> dict:
        return {
            "rev": self._placement_rev,
            "router": self.router.spec(),
            # repr-sorted: deterministic bytes regardless of set order
            "hot_keys": sorted(self._hot_keys, key=repr),
        }

    def restore_placement(self, blob: dict) -> None:
        """Pin placement for a warm restart: called BEFORE the restored
        rows stream back in, so each arriving key lands straight in the
        tier it held when the snapshot was cut."""
        if not blob:
            return
        with self._lock:
            spec = blob.get("router")
            if spec:
                self._apply_router_spec(spec)
            forced = list(blob.get("hot_keys", ()))
            if len(forced) > self.hot_rows:
                # the budget shrank since the snapshot (operator lowered
                # PATHWAY_TIER_HOT_ROWS): truncate DETERMINISTICALLY —
                # set-iteration/arrival order would make two restores of
                # the same snapshot place different keys hot
                forced = sorted(forced, key=repr)[: self.hot_rows]
            self._forced_hot = set(forced)
            self._reconcile_placement_locked()

    def finish_restore(self) -> None:
        """End of the restore stream: stop pinning placement (new keys
        follow the ordinary fill rule) and mark the restored placement
        clean — it IS the durable one."""
        with self._lock:
            self._forced_hot = None
            self._placement_dirty = False

    def _reconcile_placement_locked(self) -> None:
        """Align already-present keys with the forced placement (restore
        over a non-empty index, e.g. replayed rows that arrived before
        the placement blob)."""
        if self._forced_hot is None:
            return
        for key in [k for k in self._hot_keys if k not in self._forced_hot]:
            self._hot_keys.discard(key)
            self.hot.remove(key)
        for key in sorted(self._forced_hot, key=repr):
            s = self.slot_of_key.get(key)
            if s is None or key in self._hot_keys:
                continue
            if len(self._hot_keys) >= self.hot_rows:
                break
            self.hot.upsert(key, self._mat[s])
            self._hot_keys.add(key)

    def placement_digest(self) -> str:
        """Stable digest of (router spec, hot key set) — the observable
        the soak harness compares across a SIGKILL restore."""
        import hashlib

        blob = self.placement_blob()
        h = hashlib.blake2b(digest_size=16)
        h.update(repr(blob["router"]).encode())
        for k in blob["hot_keys"]:
            h.update(repr(k).encode())
        return h.hexdigest()

    # -- fatal-device-fault recovery ------------------------------------
    def rebuild_device_arrays(self, vectors_by_key=None) -> bool:
        """Recreate the HOT tier's device arrays after a fatal device
        fault.  The cold store is host RAM and survives by construction;
        if the hot index's own rebuild fails, the tier is rebuilt from
        the host mirror (fresh arrays, same keys) — the tiered index
        never needs the snapshot-provider fallback."""
        with self._lock:
            ok = False
            try:
                ok = self.hot.rebuild_device_arrays()
            except Exception:  # noqa: BLE001 — fall through to host rebuild
                ok = False
            if not ok:
                self._rebuild_hot_from_host_locked()
            self.rebuilds += 1
            return True

    def _rebuild_hot_from_host_locked(self) -> None:
        # fresh inner index with the same configuration, refilled from
        # the host mirror (placement unchanged)
        cls = type(self.hot)
        kwargs = dict(
            dim=self.dim, metric=self.metric, capacity=self.hot_rows,
            index_dtype=self.index_dtype,
        )
        if hasattr(self.hot, "mesh"):
            kwargs["mesh"] = self.hot.mesh
        self.hot = cls(**kwargs)
        self.hot.tier_role = "hot"
        keys = [k for k in self._hot_keys if k in self.slot_of_key]
        if keys:
            slots = np.asarray([self.slot_of_key[k] for k in keys])
            self.hot.upsert_batch(keys, self._mat[slots])
        self._hot_keys = set(keys)


# ---------------------------------------------------------------------------
# tiering observability: pathway_tier_* series on /status, "tiering" block
# on /v1/health (internals/health.py reads tiering_status() only when this
# module is already imported — a health probe never pulls jax)
# ---------------------------------------------------------------------------

_LIVE_TIERED: "weakref.WeakSet[TieredKnnIndex]" = weakref.WeakSet()
_tier_label_seq = itertools.count()


def _router_hbm_bytes(idx: "TieredKnnIndex") -> int:
    """HBM ledger ``bytes_fn`` (module-level so the ledger's weak owner
    ref stays the only reference): the router's centroid matrix.  Reads
    ``idx.router`` at call time — a restore that swaps the router spec
    is tracked automatically."""
    return int(getattr(idx.router.centroids, "nbytes", 0))


def _live_tiered() -> list[TieredKnnIndex]:
    return sorted(_LIVE_TIERED, key=lambda i: i.tier_label)


class _TierMetricsProvider:
    """``pathway_tier_*`` OpenMetrics series over every live tiered
    index: per-tier row counts, migration counters, probe width."""

    def stats(self) -> dict:
        return tiering_status() or {}

    def openmetrics_lines(self) -> list[str]:
        from ..internals.metrics_names import escape_label_value

        indexes = _live_tiered()
        if not indexes:
            return []
        lines = ["# TYPE pathway_tier_rows gauge"]
        for idx in indexes:
            lbl = f'index="{escape_label_value(idx.tier_label)}"'
            hot = len(idx._hot_keys)
            lines.append(f'pathway_tier_rows{{{lbl},tier="hot"}} {hot}')
            lines.append(
                f'pathway_tier_rows{{{lbl},tier="cold"}} {len(idx) - hot}'
            )
        lines.append("# TYPE pathway_tier_migrations_total counter")
        for idx in indexes:
            lbl = f'index="{escape_label_value(idx.tier_label)}"'
            for direction in ("promote", "demote"):
                lines.append(
                    f'pathway_tier_migrations_total{{{lbl},direction="'
                    f'{direction}"}} {idx.migrations[direction]}'
                )
        lines.append("# TYPE pathway_tier_probe_partitions gauge")
        for idx in indexes:
            lbl = f'index="{escape_label_value(idx.tier_label)}"'
            lines.append(
                f"pathway_tier_probe_partitions{{{lbl}}} "
                f"{idx.probe_partitions}"
            )
        return lines


def _ensure_tier_provider() -> None:
    # once-registration with a strong ref held by monitoring (the
    # provider table itself is weak-valued)
    from ..internals.monitoring import register_metrics_provider_once

    register_metrics_provider_once("tiering", _TierMetricsProvider)


def tiering_status() -> dict | None:
    """Per-index tier state for ``/v1/health`` (None when no tiered
    index is live)."""
    indexes = _live_tiered()
    if not indexes:
        return None
    out = {}
    for idx in indexes:
        hot = len(idx._hot_keys)
        out[idx.tier_label] = {
            "metric": idx.metric,
            "dim": int(idx.dim),
            "hot_dtype": idx.index_dtype,
            "hot_rows_budget": int(idx.hot_rows),
            "hot_rows": hot,
            "cold_rows": len(idx) - hot,
            "n_partitions": int(idx.router.n_partitions),
            "probe_partitions": int(idx.probe_partitions),
            "migrate_batch": int(idx.migrate_batch),
            "migrations": dict(idx.migrations),
            "migrate_errors": int(idx.migrate_errors),
            "searches": int(idx.searches),
            "probe_rows_total": int(idx.probe_rows_total),
            "hbm_bytes": int(idx.hbm_bytes()),
            "host_bytes": int(idx.host_bytes()),
            "placement_rev": int(idx._placement_rev),
            "mesh_devices": int(idx.n_shards) if idx.n_shards > 1 else None,
        }
    return out
