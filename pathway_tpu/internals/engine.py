"""Micro-batch incremental diff engine — the host-side dataflow runtime.

TPU-native re-design of the reference's Rust engine
(src/engine/dataflow.rs:757 ``DataflowGraphInner`` over vendored
timely/differential).  The *semantics* are kept — tables are streams of
``(key, values, time, diff)`` updates, operators maintain state and emit
retraction/insertion deltas, consistency is per-timestamp — but the
implementation is a lean single-pass topological micro-batch scheduler
instead of a general progress-tracking dataflow:

* every logical timestamp ``t`` forms one micro-batch;
* nodes are flushed in topological order, so all inputs for ``t`` are
  delivered before a node runs (the reference gets this from timely
  frontiers; a total order over a DAG gives it for free — the reference's
  outer scope is also totally ordered, src/engine/dataflow.rs MaybeTotalScope);
* stateful operators (groupby/join/...) recompute only dirty keys and emit
  diffs, mirroring differential's ``reduce``/``join_core``;
* numeric batch work (embedding, KNN search) is *not* done per-row here — it
  escapes to JAX/Pallas device ops at dedicated nodes (see
  ``pathway_tpu/stdlib/indexing`` and ``pathway_tpu/ops``).

Within one timestamp the engine preserves the updates-before-queries
invariant needed by as-of-now index serving
(reference: src/engine/dataflow/operators/external_index.rs:129-160) by
flushing a node's input ports in ascending port order.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
from collections import Counter, defaultdict
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from .keys import derive_subkey, ref_scalar
from .value import ERROR, Json, Pointer

__all__ = [
    "Entry",
    "consolidate",
    "freeze_value",
    "Node",
    "SourceNode",
    "RowwiseNode",
    "GroupByNode",
    "JoinNode",
    "ConcatNode",
    "UpdateRowsNode",
    "UpdateCellsNode",
    "SemiJoinNode",
    "DeduplicateNode",
    "OutputNode",
    "AsyncMapNode",
    "BufferNode",
    "Engine",
]

# An entry is (key, values_tuple, diff)
Entry = tuple[Pointer, tuple, int]

#: hashable stand-in for a None cell on the join fast path (None itself is
#: the slow path's "key function returned no key" sentinel)
_NULL_CELL = ("__pw_null_cell__",)


def freeze_value(v: Any) -> Any:
    """Hashable representative of a value (ndarrays/Json are unhashable)."""
    if isinstance(v, np.ndarray):
        return (b"__nd__", v.dtype.str, v.shape, v.tobytes())
    if isinstance(v, Json):
        return (b"__json__", v.to_string())
    if isinstance(v, tuple):
        return tuple(freeze_value(x) for x in v)
    if isinstance(v, dict):
        return (b"__dict__", tuple(sorted((k, freeze_value(x)) for k, x in v.items())))
    if isinstance(v, list):
        return (b"__list__", tuple(freeze_value(x) for x in v))
    return v


def freeze_row(row: tuple) -> tuple:
    # fast path: rows are overwhelmingly tuples of hashable scalars —
    # hashing probes that in C instead of a Python isinstance walk
    try:
        hash(row)
        return row
    except TypeError:
        return tuple(freeze_value(v) for v in row)


_gc_mode_depth = 0


@contextlib.contextmanager
def gc_batch_mode():
    """Tame the cyclic GC during engine flush loops.

    The engine's state (group dicts, pending rows, parsed tuples) is
    large, long-lived and acyclic; default gen-2 collections re-traverse
    all of it every few thousand allocations and were measured at ~60%
    of wordcount flush wall time (300k → 730k rows/s with gc off).
    Freezing existing objects into the permanent generation and raising
    the thresholds keeps those scans off the hot loop while still
    collecting genuinely-cyclic garbage (user UDFs may create cycles),
    unlike a blanket ``gc.disable``.  reference analogue: the Rust
    engine has no tracing GC to fight — this recovers the same property
    for the Python host plane."""
    # reentrant: pw.iterate runs an inner engine.run_all() inside the
    # outer engine's step — only the OUTERMOST enter/exit may touch gc
    # state, or the inner exit would unfreeze the outer run's heap
    global _gc_mode_depth
    _gc_mode_depth += 1
    if _gc_mode_depth > 1:
        try:
            yield
        finally:
            _gc_mode_depth -= 1
        return
    old = gc.get_threshold()
    # freeze WITHOUT a preceding collect: a full collection here would
    # re-traverse the just-built graph (often inside a caller's timed
    # window); freezing a handful of pending garbage objects permanently
    # is the cheaper trade
    gc.freeze()
    gc.set_threshold(100_000, 50, 25)
    try:
        yield
    finally:
        _gc_mode_depth -= 1
        gc.set_threshold(*old)
        gc.unfreeze()


def net_row_changes(entries: Iterable[Entry]) -> dict:
    """Fold one port's batch into the net per-key row change,
    order-independently: ``{key: new_row | None}`` where a row means the
    key's single net-inserted row and ``None`` means net-removed; keys
    whose diffs cancel exactly are absent (no change).

    Slot-per-key nodes (Zip/UpdateRows/UpdateCells) must NOT apply
    entries last-wins: upstream nodes don't promise retract-before-insert
    within a batch (e.g. JoinNode emits new matches in ``_process`` but
    outer-padding retractions later in ``_reconcile_padding``), so an
    (insert new, retract old) arrival order would otherwise null the slot
    and silently drop the key until its next touch."""
    changes: dict = {}
    # consolidate is the canonical fold (freeze_row keying, diff summing,
    # zero-dropping); a surviving positive diff is the key's net-live row
    # — universe invariant says at most one, keep the last on anomalies —
    # and surviving negatives alone mean net-removed
    for key, row, diff in consolidate(entries):
        if diff > 0:
            changes[key] = row
        else:
            changes.setdefault(key, None)
    return changes


def consolidate(entries: Iterable[Entry]) -> list[Entry]:
    """Merge entries with equal (key, values), summing diffs, dropping zeros
    (differential's ``consolidate``)."""
    acc: dict[tuple, list] = {}
    get = acc.get
    for key, row, diff in entries:
        try:
            k = (key, row)
            slot = get(k)
        except TypeError:  # unhashable cell (ndarray/Json/list/dict)
            k = (key, freeze_row(row))
            slot = get(k)
        if slot is None:
            acc[k] = [key, row, diff]
        else:
            slot[2] += diff
    return [(k, r, d) for k, r, d in acc.values() if d != 0]


class Node:
    """Runtime dataflow node."""

    # late nodes flush only after the rest of the graph is quiescent for the
    # timestamp — the global updates-before-queries barrier that the
    # reference gets from batch_by_time (external_index.rs:129)
    late: bool = False
    # local error-log subjects active when this node's operator was built
    # (errors.local_error_log); () for the common case
    error_logs: tuple = ()

    def __init__(self, n_inputs: int = 1, name: str = ""):
        self.n_inputs = n_inputs
        self.name = name or type(self).__name__
        self.pending: dict[int, list[Entry]] = defaultdict(list)
        self.downstream: list[tuple["Node", int]] = []
        self.id: int = -1

    def subscribe_to(self, node: "Node", port: int = 0) -> None:
        node.downstream.append((self, port))

    def receive(self, port: int, entries: list[Entry]) -> None:
        if entries:
            self.pending[port].extend(entries)

    def flush(self, time: int) -> list[Entry]:
        """Consume pending inputs for this timestamp, return output entries."""
        raise NotImplementedError

    def has_pending(self, time: int) -> bool:
        return any(self.pending.values())

    def end_of_step(self, time: int) -> None:
        """Called once per timestamp after the whole graph is quiescent."""

    def on_end(self) -> list[Entry]:
        """Called once when all sources are exhausted; may emit final entries."""
        return []

    def on_stream_close(self) -> None:
        """Called after all final emissions have propagated."""

    def take(self, port: int = 0) -> list[Entry]:
        entries = self.pending.pop(port, [])
        return entries


class SourceNode(Node):
    """Input: a queue of (time, entries) fed by connectors or static data."""

    def __init__(self, name: str = "source"):
        super().__init__(n_inputs=0, name=name)
        self.queue: dict[int, list[Entry]] = defaultdict(list)

    def push(self, time: int, entries: list[Entry]) -> None:
        self.queue[time].extend(entries)

    def flush(self, time: int) -> list[Entry]:
        # raw entries, no consolidation: every stateful consumer absorbs
        # diff streams (multiset counts), DeduplicateNode and OutputNode
        # consolidate their own input, and push order is preserved — the
        # same reasoning that dropped consolidation from row-wise maps
        return self.queue.pop(time, [])

    def has_pending(self, time: int) -> bool:
        return time in self.queue

    def pending_times(self) -> list[int]:
        return sorted(self.queue.keys())


class RowwiseNode(Node):
    """Stateless per-entry map (select/filter/flatten/reindex).

    ``fn(key, row, diff) -> iterable[(key', row', diff')]`` must be a
    deterministic function of (key, row); non-deterministic mappers set
    ``memoize=True`` so retractions replay the memoized result
    (reference: deterministic flag on UDFs, internals/udfs/__init__.py)."""

    def __init__(self, fn: Callable, memoize: bool = False, name: str = "rowwise"):
        super().__init__(n_inputs=1, name=name)
        self.fn = fn
        self.memoize = memoize
        self._memo: dict[tuple, list] = {}
        #: columnar fast path (set by the lowering when the select/filter
        #: vectorizes): big batches evaluate as numpy columns and fall
        #: back to the row path when a batch holds non-numeric values
        self.vector_fn = None  # rows -> list[out_row] | None
        self.vector_mask = None  # rows -> list[bool] | None
        self.vector_entries_fn = None  # entries -> list[Entry] (projections)
        self.filter_width = 0

    #: below this batch size the pool's dispatch overhead beats the win
    PARALLEL_MIN_ROWS = 64
    #: below this batch size numpy conversion overhead beats the win
    VECTOR_MIN_ROWS = 256

    def flush(self, time: int) -> list[Entry]:
        entries = self.take(0)
        if self.vector_entries_fn is not None and entries:
            # pure projection: always total (no numpy involved, so no
            # dtype fallback needed) and cheaper than per-row dispatch at
            # every batch size
            return self.vector_entries_fn(entries)
        if len(entries) >= self.VECTOR_MIN_ROWS:
            if self.vector_fn is not None:
                rows = [e[1] for e in entries]
                out_rows = self.vector_fn(rows)
                if out_rows is not None:
                    return [
                        (e[0], row, e[2])
                        for e, row in zip(entries, out_rows)
                    ]
            elif self.vector_mask is not None:
                rows = [e[1] for e in entries]
                mask = self.vector_mask(rows)
                if mask is not None:
                    w = self.filter_width
                    return [
                        (k, r[:w], d)
                        for (k, r, d), keep in zip(entries, mask)
                        if keep
                    ]
        pool = getattr(getattr(self, "engine", None), "host_pool", None)
        # no consolidation here: row-wise maps are the hottest nodes and
        # every stateful consumer (groupby/join multisets, output,
        # exchange) absorbs raw diff streams; DeduplicateNode — the one
        # consumer whose semantics need per-timestamp consolidation —
        # consolidates its own input
        if (
            pool is not None
            and not self.memoize
            and len(entries) >= self.PARALLEL_MIN_ROWS
        ):
            return self._flush_parallel(pool, entries)
        out: list[Entry] = []
        for key, row, diff in entries:
            if self.memoize:
                mk = (key, freeze_row(row))
                if mk in self._memo:
                    results = self._memo[mk]
                else:
                    results = list(self.fn(key, row, 1))
                    self._memo[mk] = results
                out.extend((k, r, d * diff) for k, r, d in results)
            else:
                out.extend(
                    (k, r, d * diff) for k, r, d in self.fn(key, row, 1)
                )
        return out

    def _flush_parallel(self, pool, entries: list[Entry]) -> list[Entry]:
        """Split the batch across the host worker pool; chunk order is
        preserved so output is identical to the serial path (timely's
        worker shards, but within one operator's batch)."""
        n = self.engine.threads
        chunk_size = (len(entries) + n - 1) // n
        chunks = [
            entries[i : i + chunk_size]
            for i in range(0, len(entries), chunk_size)
        ]

        def run_chunk(chunk):
            part: list[Entry] = []
            for key, row, diff in chunk:
                part.extend(
                    (k, r, d * diff) for k, r, d in self.fn(key, row, 1)
                )
            return part

        out: list[Entry] = []
        for part in pool.map(run_chunk, chunks):
            out.extend(part)
        return out


class ZipNode(Node):
    """N-ary key-aligned combine: rows from same-universe tables are merged
    and mapped through ``fn(key, rows_per_port) -> row``.

    Covers the reference's same-universe cross-table column references in
    ``select`` (internals/column.py RowwiseContext over multiple tables).
    Emits once all ports have the key; updates retract the previous output."""

    def __init__(self, n_inputs: int, fn: Callable, name: str = "zip"):
        super().__init__(n_inputs=n_inputs, name=name)
        self.fn = fn
        self.state: dict[Pointer, list] = {}
        self.last_out: dict[Pointer, tuple] = {}
        # chunked operator-snapshot plane (OPERATOR_PERSISTING): the
        # per-key port slots are cross-step state — restarting them empty
        # would swallow one side's post-restart retractions.  The lowering
        # assigns a deterministic persistent_id; the streaming driver
        # attaches the snapshot and restores before data flows.
        self.persistent_id: str | None = None
        self._op_snapshot = None
        self._snap_dirty: set = set()

    def flush(self, time: int) -> list[Entry]:
        touched: set[Pointer] = set()
        for port in range(self.n_inputs):
            # order-independent fold: see net_row_changes — last-wins
            # application would drop keys on (insert, retract) arrival
            # order from upstreams like JoinNode's padding reconciler
            for key, new_row in net_row_changes(self.take(port)).items():
                slot = self.state.setdefault(key, [None] * self.n_inputs)
                slot[port] = new_row
                touched.add(key)
        out: list[Entry] = []
        for key in touched:
            slot = self.state.get(key)
            prev = self.last_out.pop(key, None)
            if prev is not None:
                out.append((key, prev, -1))
            if slot is not None and all(r is not None for r in slot):
                row = self.fn(key, slot)
                self.last_out[key] = row
                out.append((key, row, 1))
            elif slot is not None and all(r is None for r in slot):
                del self.state[key]
        if self.persistent_id and self._op_snapshot is not None:
            self._snap_dirty |= touched
        return consolidate(out)

    def end_of_step(self, time: int) -> None:
        if not (
            self._snap_dirty
            and self._op_snapshot is not None
            and self.persistent_id
        ):
            self._snap_dirty.clear()
            return
        upserts = {}
        deletes = []
        for key in self._snap_dirty:
            if key in self.state:
                upserts[key] = (list(self.state[key]), self.last_out.get(key))
            else:
                deletes.append(key)
        self._op_snapshot.save_delta(
            self.persistent_id,
            time,
            upserts,
            deletes,
            live_entries=len(self.state),
        )
        self._snap_dirty.clear()

    def restore_snapshot(self, snapshot: dict) -> None:
        for key, (slot, last) in snapshot.items():
            self.state[key] = list(slot)
            if last is not None:
                self.last_out[key] = last


class GroupByNode(Node):
    """Incremental grouped reduction (reference: differential ``reduce``;
    src/engine/dataflow.rs group/reduce operators + src/engine/reduce.rs).

    State per group: multiset of per-row reducer argument tuples; dirty
    groups are recomputed wholesale and output deltas emitted."""

    def __init__(
        self,
        group_fn: Callable[[Pointer, tuple], tuple],
        instance_fn: Callable[[Pointer, tuple], Any] | None,
        args_fn: Callable[[Pointer, tuple], tuple],
        out_fn: Callable[[tuple, list], tuple],
        key_fn: Callable[[tuple, Any], Pointer] | None = None,
        reducers: Sequence[Any] = (),
        sort_by_fn: Callable[[Pointer, tuple], Any] | None = None,
        name: str = "groupby",
        persistent_id: str | None = None,
    ):
        super().__init__(n_inputs=1, name=name)
        self.group_fn = group_fn
        self.instance_fn = instance_fn
        self.args_fn = args_fn
        self.out_fn = out_fn
        self.key_fn = key_fn
        self.reducers = list(reducers)
        self.sort_by_fn = sort_by_fn
        # group_frozen -> {frozen_args: [count, raw_args, key, sort_key, seq]}
        self.state: dict[tuple, dict] = defaultdict(dict)
        # C-level counter: slot creation happens from pool threads in the
        # sharded columnar ingest, and `self._seq += 1` would race
        self._seq = itertools.count(1)
        self.group_raw: dict[tuple, tuple] = {}
        self.group_instance: dict[tuple, Any] = {}
        self.last_out: dict[tuple, Entry] = {}
        #: O(1) running aggregates per group for decomposable reducers
        #: (count/sum/avg) — a touched group emits from these instead of
        #: recomputing over its whole multiset; a state whose exactness
        #: flag (last element) dropped falls back to recompute
        self._inc_idx = [
            i for i, r in enumerate(self.reducers) if r.incremental
        ]
        self.red_state: dict[tuple, dict[int, list]] = {}
        #: columnar ingest (set by the lowering when grouping columns and
        #: reducer args are plain slot projections and every reducer is
        #: vector-safe): ``(group_slots, arg_slots_per_reducer)``
        self.vector_spec = None
        #: chunked operator-snapshot plane (streaming driver attaches it in
        #: OPERATOR_PERSISTING mode when a persistent_id is set): dirty
        #: groups accumulate per finalized time and emit as delta chunks
        self.persistent_id = persistent_id
        self._op_snapshot = None
        self._snap_dirty: set = set()

    #: below this batch size numpy conversion overhead beats the win
    VECTOR_MIN_ROWS = 512
    #: below this batch size per-thread partitioning overhead beats the
    #: win (PATHWAY_THREADS stateful scaling)
    PARALLEL_MIN_ROWS = 16_384

    def flush(self, time: int) -> list[Entry]:
        entries = self.take(0)
        dirty = None
        if self.vector_spec is not None and len(entries) >= self.VECTOR_MIN_ROWS:
            engine = getattr(self, "engine", None)
            pool = getattr(engine, "host_pool", None)
            if (
                pool is not None
                and getattr(engine, "shard_stateful", False)
                and len(entries) >= self.PARALLEL_MIN_ROWS
            ):
                dirty = self._ingest_vector_parallel(entries, pool)
            if dirty is None:
                dirty = self._ingest_vector(entries)
        if dirty is None:
            dirty = self._ingest_rows(entries)
        if self.persistent_id and self._op_snapshot is not None:
            self._snap_dirty |= dirty
        return self._emit(dirty)

    def _ingest_vector_parallel(self, entries: list[Entry], pool) -> set | None:
        """PATHWAY_THREADS scaling for the stateful hot path (reference:
        timely worker threads, src/engine/dataflow/config.rs:63-70):
        shard the batch by a hash of its FIRST grouping column so each
        thread owns a disjoint set of groups — disjoint ``state``/
        ``red_state``/``group_raw`` keys, so no locks — and run the
        columnar ingest per shard.  The np.unique/argsort inside release
        the GIL, so shards overlap on multi-core hosts.  Seq numbers are
        allocated per shard (seq-order-sensitive reducers are excluded
        from the vector gate).  Returns None to fall back when the batch
        cannot be sharded at all (object dtype / ndarray cells)."""
        group_slots, _arg_slots = self.vector_spec
        if not group_slots:
            return None  # global reduce: one group — nothing to shard
        import pandas as pd

        threads = self.engine.threads
        s0 = group_slots[0]
        vals0 = [e[1][s0] for e in entries]
        col0 = np.asarray(vals0)
        if col0.dtype == object or col0.ndim != 1:
            return None
        if col0.dtype.kind == "f":
            from .evaluator import _float_col_exact

            if not _float_col_exact(col0, vals0):
                # same guard as _ingest_vector: huge int-sourced values
                # collapse to identical floats under coercion; don't even
                # shard on a lossy identity
                return None
            # bitwise hashing must not split -0.0 / 0.0 (equal dict keys)
            # across shards — same normalization as _ingest_vector
            col0 = col0 + 0.0
        owners = pd.util.hash_array(col0) % threads
        shards: list[list[Entry]] = [[] for _ in range(threads)]
        for e, o in zip(entries, owners.tolist()):
            shards[o].append(e)
        results = list(pool.map(self._ingest_vector, shards))
        dirty: set = set()
        for i, r in enumerate(results):
            if r is None:
                # this shard's batch was columnar-unsafe (NaN/mixed):
                # none of its rows were ingested — replay it on the row
                # path (state keys stay disjoint per shard)
                r = self._ingest_rows(shards[i])
            dirty |= r
        return dirty

    def _ingest_rows(self, entries: list[Entry]) -> set:
        dirty: set[tuple] = set()
        for key, row, diff in entries:
            gvals = self.group_fn(key, row)
            args = self.args_fn(key, row)
            sort_key = self.sort_by_fn(key, row) if self.sort_by_fn else None
            # ERROR-row guard (reference: src/engine/error.rs — rows whose
            # grouping, reducer or sort inputs are ERROR go to the error
            # log and never poison the aggregate: an ERROR sort key would
            # blow up the sorted() at emission).  Symmetric across diff
            # signs: the retraction of a skipped addition skips identically.
            if (
                any(v is ERROR for v in gvals)
                or any(v is ERROR for t in args for v in t)
                or sort_key is ERROR
            ):
                if diff > 0:
                    from .errors import register_error

                    register_error(
                        "row with ERROR excluded from aggregation",
                        kind="groupby",
                        operator=self.name,
                    )
                continue
            gfrozen = freeze_row(gvals)
            self.group_raw[gfrozen] = gvals
            if self.instance_fn is not None:
                self.group_instance[gfrozen] = self.instance_fn(key, row)
            afrozen = (freeze_row(args), key if self._needs_key() else None)
            slot = self.state[gfrozen].get(afrozen)
            if slot is None:
                slot = self.state[gfrozen][afrozen] = [
                    0, args, key, sort_key, next(self._seq)
                ]
            slot[0] += diff
            if slot[0] == 0:
                del self.state[gfrozen][afrozen]
            if self._inc_idx:
                states = self.red_state.get(gfrozen)
                if states is None:
                    states = self.red_state[gfrozen] = {
                        i: self.reducers[i].init_state() for i in self._inc_idx
                    }
                for i in self._inc_idx:
                    self.reducers[i].update(states[i], args[i], diff)
            dirty.add(gfrozen)
        return dirty

    def _ingest_vector(self, entries: list[Entry]) -> set | None:
        """Columnar ingest: group the batch by its (grouping, reducer-args)
        identity with one ``np.unique`` pass, then apply ONE state update
        per distinct slot instead of one per row.  State layout and seq
        assignment match `_ingest_rows` exactly (slots are read back from
        the original Python rows, not numpy casts), so vector and row
        batches interleave freely on the same node.  Returns None to fall
        back when the batch isn't columnar-safe (object dtype, NaN)."""
        group_slots, arg_slots = self.vector_spec
        rows = [e[1] for e in entries]
        # an arg is either an int slot or a ("const", value) placeholder
        # (count()'s Const(0)); constants are identical across rows, so
        # they join the args tuples but not the identity columns
        needed = sorted(
            {*group_slots}
            | {s for sl in arg_slots for s in sl if not isinstance(s, tuple)}
        )
        cols = []
        for s in needed:
            vals = [r[s] for r in rows]
            arr = np.asarray(vals)
            if arr.dtype == object:
                return None  # None/ERROR/mixed types — row path handles
            if arr.ndim != 1:
                return None  # ndarray-valued column — row path handles
            if arr.dtype.kind in "US":
                # numpy silently coerces mixed batches (int+str, bytes+str)
                # to one string dtype, merging values Python dict identity
                # keeps distinct; numeric mixes (int/float/bool) are safe
                # because Python == agrees with the coercion
                t0 = type(vals[0])
                if t0 not in (str, bytes) or any(
                    t is not t0 for t in map(type, vals)
                ):
                    return None
            if arr.dtype.kind == "f":
                if np.isnan(arr).any():
                    # dict identity for NaN is per-object; np.unique would
                    # merge them — keep row-path semantics
                    return None
                from .evaluator import _float_col_exact

                if not _float_col_exact(arr, vals):
                    # float64 coerced from huge Python ints (e.g. an INT
                    # column mixing 2**63 with smaller numerics): distinct
                    # ints beyond 2**53 become byte-identical floats, so
                    # np.unique would merge groups the row path keeps
                    # distinct — silent wrong aggregates.  The "numeric
                    # mixes are safe" reasoning only holds within float53
                    return None
                # byte-wise rec-array identity must not split -0.0 / 0.0
                # (Python dict keys treat them equal)
                arr = arr + 0.0
            cols.append(arr)
        diffs = np.fromiter(
            (e[2] for e in entries), np.int64, count=len(entries)
        )
        if not cols:
            # global reduce with const-only args: every row shares one
            # identity — one slot, net = sum of diffs
            first_idx = np.zeros(1, np.int64)
            net = np.asarray([diffs.sum()])
        else:
            if len(cols) == 1:
                ident = cols[0]
            else:
                ident = np.rec.fromarrays(cols)
            _, first_idx, sinv = np.unique(
                ident, return_index=True, return_inverse=True
            )
            net = np.bincount(sinv, weights=diffs, minlength=len(first_idx))
        # first-occurrence order keeps slot seq numbers identical to the
        # row path (earliest/latest-style reducers are excluded from the
        # vector gate, but state must stay bit-compatible regardless)
        order = np.argsort(first_idx, kind="stable")
        dirty: set[tuple] = set()
        state = self.state
        for u in order.tolist():
            d = int(net[u])
            if d == 0:
                # add+retract cancelling within the batch: the row path's
                # create-then-delete leaves the same state, and its
                # retract+re-add emission cancels in consolidate()
                continue
            i = int(first_idx[u])
            row = rows[i]
            gvals = tuple(row[s] for s in group_slots)
            gfrozen = gvals  # scalars from non-object columns — hashable
            self.group_raw[gfrozen] = gvals
            args = tuple(
                tuple(
                    s[1] if isinstance(s, tuple) else row[s] for s in sl
                )
                for sl in arg_slots
            )
            afrozen = (args, None)
            bucket = state[gfrozen]
            slot = bucket.get(afrozen)
            if slot is None:
                slot = bucket[afrozen] = [
                    0, args, entries[i][0], None, next(self._seq)
                ]
            slot[0] += d
            if slot[0] == 0:
                del bucket[afrozen]
            if self._inc_idx:
                states = self.red_state.get(gfrozen)
                if states is None:
                    states = self.red_state[gfrozen] = {
                        j: self.reducers[j].init_state() for j in self._inc_idx
                    }
                for j in self._inc_idx:
                    self.reducers[j].update(states[j], args[j], d)
            dirty.add(gfrozen)
        return dirty

    def _emit(self, dirty: set) -> list[Entry]:
        out: list[Entry] = []
        for gfrozen in dirty:
            group_state = self.state.get(gfrozen)
            prev = self.last_out.pop(gfrozen, None)
            if prev is not None:
                out.append((prev[0], prev[1], -1))
            if not group_state:
                self.state.pop(gfrozen, None)
                self.red_state.pop(gfrozen, None)
                continue
            gvals = self.group_raw[gfrozen]
            instance = self.group_instance.get(gfrozen)
            rows = None
            inc_states = self.red_state.get(gfrozen, {})
            values = []
            for i, red in enumerate(self.reducers):
                st = inc_states.get(i)
                if st is not None and st[-1]:
                    values.append(red.current(st))
                    continue
                if rows is None:
                    rows = list(group_state.values())  # [count,args,key,sk,seq]
                    if self.sort_by_fn is not None:
                        # None sort keys (outer-join padding) order last
                        rows.sort(key=lambda s: (s[3] is None, s[3]))
                values.append(
                    red.compute([(s[1][i], s[0], s[2], s[4]) for s in rows])
                )
            if self.key_fn is not None:
                out_key = self.key_fn(gvals, instance)
            else:
                out_key = ref_scalar(*gvals)
            row = self.out_fn(gvals, values)
            entry = (out_key, row, 1)
            self.last_out[gfrozen] = entry
            out.append(entry)
        return consolidate(out)

    def _needs_key(self) -> bool:
        return any(getattr(r, "distinguish_by_key", False) for r in self.reducers)

    # -- operator snapshots (reference: operator_snapshot.rs) --
    def end_of_step(self, time: int) -> None:
        if not (
            self._snap_dirty
            and self._op_snapshot is not None
            and self.persistent_id
        ):
            self._snap_dirty.clear()
            return
        upserts: dict = {}
        deletes: list = []
        for g in self._snap_dirty:
            if g in self.state:
                upserts[g] = (
                    dict(self.state[g]),
                    self.red_state.get(g),
                    self.group_raw.get(g),
                    self.group_instance.get(g),
                    self.last_out.get(g),
                )
            else:
                deletes.append(g)
        self._op_snapshot.save_delta(
            self.persistent_id,
            time,
            upserts,
            deletes,
            live_entries=len(self.state),
        )
        self._snap_dirty.clear()

    def restore_snapshot(self, snapshot: dict) -> None:
        """Adopt restored per-group records (state, incremental reducer
        states, raw group values, instance, last emitted entry); the slot
        seq counter resumes past every restored slot so seq-sensitive
        reducers keep a total order across the restart."""
        max_seq = 0
        for g, (slots, red, graw, ginst, last) in snapshot.items():
            self.state[g] = dict(slots)
            if red is not None:
                self.red_state[g] = red
            self.group_raw[g] = graw
            if ginst is not None:
                self.group_instance[g] = ginst
            if last is not None:
                self.last_out[g] = last
            for slot in slots.values():
                max_seq = max(max_seq, slot[4])
        # past the snapshot AND the live counter: static sources may have
        # handed out seqs before restore runs, and a duplicate seq would
        # make seq-tie-broken reducers pick a different winner than the
        # pre-restart run (gaps are harmless, collisions are not)
        self._seq = itertools.count(max(max_seq, next(self._seq)) + 1)


class JoinNode(Node):
    """Incremental binary join, all modes (reference: differential
    ``join_core``; python/pathway/internals/joins.py desugaring).

    Port 0 = left, port 1 = right.  Also covers ``ix`` and ``having`` via
    custom key/out functions."""

    def __init__(
        self,
        left_key_fn: Callable[[Pointer, tuple], Any],
        right_key_fn: Callable[[Pointer, tuple], Any],
        out_fn: Callable[[Pointer | None, tuple | None, Pointer | None, tuple | None], tuple],
        out_key_fn: Callable[[Pointer | None, tuple | None, Pointer | None, tuple | None], Pointer],
        left_outer: bool = False,
        right_outer: bool = False,
        exact_match: bool = False,
        name: str = "join",
    ):
        super().__init__(n_inputs=2, name=name)
        self.left_key_fn = left_key_fn
        self.right_key_fn = right_key_fn
        self.out_fn = out_fn
        self.out_key_fn = out_key_fn
        self.left_outer = left_outer
        self.right_outer = right_outer
        self.exact_match = exact_match
        # jk_frozen -> {(key, frozen_row): [count, key, row]}
        self.left_state: dict[Any, dict] = defaultdict(dict)
        self.right_state: dict[Any, dict] = defaultdict(dict)
        self.left_count: Counter = Counter()
        self.right_count: Counter = Counter()
        # padded rows currently emitted, per side: jk -> {slot: [count,key,row]}
        self.left_padded: dict[Any, dict] = defaultdict(dict)
        self.right_padded: dict[Any, dict] = defaultdict(dict)
        #: single-column equi-join fast path (set by the lowering): probe
        #: with the raw cell — no 1-tuple build, no freeze_value walk.
        #: Both sides must be set together so bucket identities agree.
        self.left_key_slot: int | None = None
        self.right_key_slot: int | None = None

    @staticmethod
    def _apply(state: dict, jk, key, row, diff) -> None:
        slot_key = (key, freeze_row(row))
        bucket = state[jk]
        slot = bucket.get(slot_key)
        if slot is None:
            slot = bucket[slot_key] = [0, key, row]
        slot[0] += diff
        if slot[0] == 0:
            del bucket[slot_key]
            if not bucket:
                del state[jk]

    def flush(self, time: int) -> list[Entry]:
        out: list[Entry] = []
        affected: set = set()
        # incremental bilinear form: each entry is applied to state right
        # after emitting products against the *current* other side, so the
        # result is order-independent; port 0 (updates) still drains first to
        # honor updates-before-queries for as-of-now serving.
        for port in (0, 1):
            entries = self.take(port)
            out.extend(self._process(entries, left_side=(port == 0), affected=affected))
        # reconcile outer padding once both ports have settled for this time
        if self.left_outer:
            self._reconcile_padding(affected, left_side=True, out=out)
        if self.right_outer:
            self._reconcile_padding(affected, left_side=False, out=out)
        # raw diffs out: stateful consumers absorb add/retract pairs and
        # OutputNode/DeduplicateNode consolidate their own input — same
        # reasoning as row-wise maps (join emit is the next-hottest path)
        return out

    def _emit(self, lkey, lrow, rkey, rrow, diff, out: list[Entry]) -> None:
        values = self.out_fn(lkey, lrow, rkey, rrow)
        key = self.out_key_fn(lkey, lrow, rkey, rrow)
        out.append((key, values, diff))

    def _process(self, entries: list[Entry], left_side: bool, affected: set) -> list[Entry]:
        out: list[Entry] = []
        my_state = self.left_state if left_side else self.right_state
        other_state = self.right_state if left_side else self.left_state
        my_count = self.left_count if left_side else self.right_count
        slot = self.left_key_slot if left_side else self.right_key_slot
        out_fn = self.out_fn
        key_fn = self.out_key_fn
        append = out.append
        my_key_fn = None
        if slot is None:
            my_key_fn = self.left_key_fn if left_side else self.right_key_fn
        for key, row, diff in entries:
            if my_key_fn is None:
                jk = row[slot]
                if jk is None:
                    # a None CELL is an ordinary join key (the tuple path
                    # matches (None,) with (None,)); only a None result of
                    # a key FUNCTION (ix optional pointer) means no-match.
                    # _NULL_CELL is a process-unique hashable stand-in.
                    jk = _NULL_CELL
                else:
                    try:
                        hash(jk)
                    except TypeError:  # ndarray/Json cell — freeze it
                        jk = freeze_value(jk)
            else:
                jk = freeze_value(my_key_fn(key, row))
            if jk is ERROR or (
                type(jk) is tuple and any(v is ERROR for v in jk)
            ):
                # ERROR join keys never match and never enter join state
                # (reference error.rs semantics): log on addition, skip the
                # matching retraction symmetrically
                if diff > 0:
                    from .errors import register_error

                    register_error(
                        "row with ERROR join key excluded from join",
                        kind="join",
                        operator=self.name,
                    )
                continue
            if jk is None:
                # null join keys never match (SQL semantics); a null-key row
                # still participates in outer padding via a private bucket
                jk = ("__null__", key, left_side)
                affected.add(jk)
                self._apply(my_state, jk, key, row, diff)
                my_count[jk] += diff
                continue
            affected.add(jk)
            # inner products against the current other side; other_state
            # is a different dict from my_state and is only mutated by the
            # other port's drain, so iterating its live bucket is safe.
            # _emit is inlined with hoisted locals: this append is the
            # hottest line of the join (one per output row)
            bucket = other_state.get(jk)
            if bucket:
                if left_side:
                    for cnt, okey, orow in bucket.values():
                        append(
                            (
                                key_fn(key, row, okey, orow),
                                out_fn(key, row, okey, orow),
                                diff * cnt,
                            )
                        )
                else:
                    for cnt, okey, orow in bucket.values():
                        append(
                            (
                                key_fn(okey, orow, key, row),
                                out_fn(okey, orow, key, row),
                                diff * cnt,
                            )
                        )
            self._apply(my_state, jk, key, row, diff)
            my_count[jk] += diff
        return out

    def on_end(self) -> list[Entry]:
        if self.exact_match:
            # reference: joins.py exact-match validation — every row on each
            # side must have found a partner by stream close
            for jk, cnt in self.left_count.items():
                if cnt > 0 and self.right_count.get(jk, 0) <= 0:
                    raise ValueError(
                        "exact_match join: unmatched rows on the left side"
                    )
            for jk, cnt in self.right_count.items():
                if cnt > 0 and self.left_count.get(jk, 0) <= 0:
                    raise ValueError(
                        "exact_match join: unmatched rows on the right side"
                    )
        return []

    def _reconcile_padding(self, affected: set, left_side: bool, out: list[Entry]) -> None:
        my_state = self.left_state if left_side else self.right_state
        other_count = self.right_count if left_side else self.left_count
        padded = self.left_padded if left_side else self.right_padded
        for jk in affected:
            unmatched = (
                isinstance(jk, tuple) and len(jk) == 3 and jk[0] == "__null__"
            ) or other_count[jk] <= 0
            desired = my_state.get(jk, {}) if unmatched else {}
            current = padded.get(jk, {})
            if not desired and not current:
                continue
            for slot, (cnt, key, row) in list(current.items()):
                want = desired.get(slot, [0])[0]
                if want != cnt:
                    d = want - cnt
                    if left_side:
                        self._emit(key, row, None, None, d, out)
                    else:
                        self._emit(None, None, key, row, d, out)
            for slot, (cnt, key, row) in desired.items():
                if slot not in current:
                    if left_side:
                        self._emit(key, row, None, None, cnt, out)
                    else:
                        self._emit(None, None, key, row, cnt, out)
            if desired:
                padded[jk] = {s: [v[0], v[1], v[2]] for s, v in desired.items()}
            else:
                padded.pop(jk, None)


class ConcatNode(Node):
    """Union of inputs (reference: Graph::concat / concat_reindex).
    ``reindex=True`` derives fresh keys derive_subkey(key, port) to keep universes
    disjoint."""

    def __init__(self, n_inputs: int, reindex: bool = False, name: str = "concat"):
        super().__init__(n_inputs=n_inputs, name=name)
        self.reindex = reindex
        # key -> (owner_port, count): detects universe-disjointness violations
        self._owner: dict[Pointer, list] = {}

    def flush(self, time: int) -> list[Entry]:
        out: list[Entry] = []
        for port in range(self.n_inputs):
            for key, row, diff in self.take(port):
                if self.reindex:
                    out.append((derive_subkey(key, port), row, diff))
                    continue
                slot = self._owner.get(key)
                if slot is None:
                    slot = self._owner[key] = [port, 0]
                elif slot[0] != port:
                    raise ValueError(
                        "concat: tables have overlapping keys (universes are "
                        "not disjoint); use concat_reindex instead"
                    )
                slot[1] += diff
                if slot[1] == 0:
                    del self._owner[key]
                out.append((key, row, diff))
        return consolidate(out)


class UpdateRowsNode(Node):
    """``t.update_rows(other)`` — other's rows win on key collision
    (reference: graph.rs update_rows / table.py:1164)."""

    def __init__(self, name: str = "update_rows"):
        super().__init__(n_inputs=2, name=name)
        self.state: dict[Pointer, list] = {}  # key -> [self_row|None, other_row|None]

    def flush(self, time: int) -> list[Entry]:
        out: list[Entry] = []
        touched: dict[Pointer, tuple | None] = {}
        for port in (0, 1):
            # order-independent fold (see net_row_changes)
            for key, new_row in net_row_changes(self.take(port)).items():
                slot = self.state.setdefault(key, [None, None])
                if key not in touched:
                    touched[key] = self._current(slot)
                slot[port] = new_row
        for key, before in touched.items():
            slot = self.state.get(key, [None, None])
            after = self._current(slot)
            if before == after:
                continue
            if before is not None:
                out.append((key, before, -1))
            if after is not None:
                out.append((key, after, 1))
            if slot[0] is None and slot[1] is None:
                self.state.pop(key, None)
        return consolidate(out)

    @staticmethod
    def _current(slot) -> tuple | None:
        return slot[1] if slot[1] is not None else slot[0]


class UpdateCellsNode(Node):
    """``t.update_cells(other)`` — override listed columns where other has
    the key (reference: table.py:1064)."""

    def __init__(self, positions: list[int | None], name: str = "update_cells"):
        # positions[i] = index into other's row for output column i, or None
        super().__init__(n_inputs=2, name=name)
        self.positions = positions
        self.state: dict[Pointer, list] = {}

    def flush(self, time: int) -> list[Entry]:
        out: list[Entry] = []
        touched: dict[Pointer, tuple | None] = {}
        for port in (0, 1):
            # order-independent fold (see net_row_changes)
            for key, new_row in net_row_changes(self.take(port)).items():
                slot = self.state.setdefault(key, [None, None])
                if key not in touched:
                    touched[key] = self._current(slot)
                slot[port] = new_row
        for key, before in touched.items():
            slot = self.state.get(key, [None, None])
            after = self._current(slot)
            if before == after:
                continue
            if before is not None:
                out.append((key, before, -1))
            if after is not None:
                out.append((key, after, 1))
            if slot[0] is None and slot[1] is None:
                self.state.pop(key, None)
        return consolidate(out)

    def _current(self, slot) -> tuple | None:
        base, other = slot
        if base is None:
            return None
        if other is None:
            return base
        return tuple(
            other[p] if p is not None else v
            for v, p in zip(base, self.positions)
        )


class SemiJoinNode(Node):
    """Restrict port-0 rows by presence of their mask-key on port 1
    (intersect / difference / restrict / having).
    reference: graph.rs intersect/restrict/difference."""

    def __init__(
        self,
        mask_key_fn: Callable[[Pointer, tuple], Any],
        right_key_fn: Callable[[Pointer, tuple], Any] | None = None,
        mode: str = "intersect",
        name: str = "semijoin",
    ):
        super().__init__(n_inputs=2, name=name)
        self.mask_key_fn = mask_key_fn
        self.right_key_fn = right_key_fn or (lambda k, r: k)
        self.mode = mode
        self.left_state: dict[Any, dict] = defaultdict(dict)
        self.right_count: Counter = Counter()

    def _passes(self, count: int) -> bool:
        return count > 0 if self.mode == "intersect" else count == 0

    def flush(self, time: int) -> list[Entry]:
        out: list[Entry] = []
        for key, row, diff in self.take(0):
            mk = freeze_value(self.mask_key_fn(key, row))
            JoinNode._apply(self.left_state, mk, key, row, diff)
            if self._passes(self.right_count[mk]):
                out.append((key, row, diff))
        for key, row, diff in self.take(1):
            mk = freeze_value(self.right_key_fn(key, row))
            c0 = self.right_count[mk]
            self.right_count[mk] = c1 = c0 + diff
            flipped = self._passes(c1) != self._passes(c0)
            if flipped:
                sign = 1 if self._passes(c1) else -1
                for cnt, lkey, lrow in list(self.left_state.get(mk, {}).values()):
                    out.append((lkey, lrow, sign * cnt))
        return consolidate(out)


class DeduplicateNode(Node):
    """``t.deduplicate(value=..., acceptor=...)`` — keep one accepted row per
    instance, consulting ``acceptor(new, current)``
    (reference: stdlib/stateful/deduplicate.py + operators/stateful_reduce.rs).
    State survives via operator snapshots when persistence is on."""

    def __init__(
        self,
        instance_fn: Callable[[Pointer, tuple], Any],
        value_fn: Callable[[Pointer, tuple], Any],
        acceptor: Callable[[Any, Any], bool],
        name: str = "deduplicate",
        persistent_id: str | None = None,
    ):
        super().__init__(n_inputs=1, name=name)
        self.instance_fn = instance_fn
        self.value_fn = value_fn
        self.acceptor = acceptor
        self.persistent_id = persistent_id
        self.state: dict[Any, tuple[Pointer, tuple]] = {}
        # chunked operator-snapshot plane attached by the streaming driver
        # when full persistence is on (reference: operator_snapshot.rs);
        # _snap_dirty holds the instance keys touched since the last
        # finalized time, so a commit writes O(delta), not O(state)
        self._op_snapshot = None
        self._snap_dirty: set = set()

    def flush(self, time: int) -> list[Entry]:
        out: list[Entry] = []
        # consolidate here: a transient add+retract pair within one
        # timestamp (possible now that row-wise maps emit raw diffs) must
        # not reach the acceptor
        for key, row, diff in consolidate(self.take(0)):
            if diff <= 0:
                continue  # dedup consumes an append-only stream
            inst = freeze_value(self.instance_fn(key, row))
            new_val = self.value_fn(key, row)
            current = self.state.get(inst)
            if current is None:
                accept = True
            else:
                cur_val = self.value_fn(*current)
                accept = bool(self.acceptor(new_val, cur_val))
            if accept:
                out_key = ref_scalar(*(inst if isinstance(inst, tuple) else (inst,)))
                if current is not None:
                    out.append((out_key, current[1], -1))
                self.state[inst] = (key, row)
                self._snap_dirty.add(inst)
                out.append((out_key, row, 1))
        return consolidate(out)

    def end_of_step(self, time: int) -> None:
        if self._snap_dirty and self._op_snapshot is not None and self.persistent_id:
            upserts = {
                inst: self.state[inst]
                for inst in self._snap_dirty
                if inst in self.state
            }
            deletes = [i for i in self._snap_dirty if i not in self.state]
            self._op_snapshot.save_delta(
                self.persistent_id,
                time,
                upserts,
                deletes,
                live_entries=len(self.state),
            )
        self._snap_dirty.clear()

    def restore_snapshot(self, state: dict) -> None:
        """Adopt a restored base+delta state (streaming driver startup)."""
        self.state = dict(state)


class BufferNode(Node):
    """Delay/cutoff buffer for temporal behaviors
    (reference: src/engine/dataflow/operators/time_column.rs forget/buffer).

    Holds entries until ``threshold_fn(row) <= watermark``; with
    ``forget=True`` also retracts rows older than the cutoff."""

    def __init__(
        self,
        threshold_fn: Callable[[tuple], Any],
        name: str = "buffer",
    ):
        super().__init__(n_inputs=1, name=name)
        self.threshold_fn = threshold_fn
        self.held: list[Entry] = []

    def flush(self, time: int) -> list[Entry]:
        self.held.extend(self.take(0))
        ready: list[Entry] = []
        still: list[Entry] = []
        for key, row, diff in self.held:
            if self.threshold_fn(row) <= time:
                ready.append((key, row, diff))
            else:
                still.append((key, row, diff))
        self.held = still
        return consolidate(ready)

    def on_end(self) -> list[Entry]:
        ready, self.held = self.held, []
        return consolidate(ready)


class AsyncMapNode(Node):
    """Async row-wise apply with bounded fan-out
    (reference: graph.rs:723 ``async_apply_table`` +
    internals/udfs/executors.py AsyncExecutor: capacity/timeout/retries).

    Results are memoized by frozen input so retractions replay identically —
    the same contract the reference enforces for non-deterministic UDFs.

    Batches run on the process's persistent event loop (internals/aio.py).
    With ``pipelined=True`` (the ``fully_async`` executor contract:
    reference python/pathway/internals/udfs/executors.py
    ``FullyAsyncExecutor`` — results land at a *later* engine time) the
    node is double-buffered: flush(t) dispatches batch t to the loop and
    emits the now-resolved batch t-1, so device work for one micro-batch
    overlaps host ingest/parse of the next — the host/device overlap a
    TPU framework needs."""

    def __init__(
        self,
        async_fn: Callable,  # async (row) -> out_row
        capacity: int | None = None,
        pipelined: bool = False,
        name: str = "async_map",
    ):
        super().__init__(n_inputs=1, name=name)
        self.async_fn = async_fn
        self.capacity = capacity
        self.pipelined = pipelined
        self._memo: dict[tuple, tuple] = {}
        # pipelined mode: (dispatch_time, future, frozen_keys, entries)
        self._in_flight: list[tuple] = []
        #: inputs dispatched but possibly unresolved — a retraction whose
        #: addition is still in flight must NOT recompute (it could differ
        #: for a non-deterministic fn and unpair the add/retract)
        self._scheduled: set[tuple] = set()

    def _dispatch(self, rows: list):
        from .aio import gather_bounded, submit

        return submit(gather_bounded(self.async_fn, rows, self.capacity))

    def flush(self, time: int) -> list[Entry]:
        entries = self.take(0)
        to_compute: dict[tuple, tuple] = {}
        for key, row, diff in entries:
            fk = freeze_row(row)
            if (
                fk not in self._memo
                and fk not in to_compute
                and fk not in self._scheduled
            ):
                to_compute[fk] = row
        if not self.pipelined:
            if to_compute:
                results = self._dispatch(list(to_compute.values())).result()
                for fk, res in zip(to_compute.keys(), results):
                    self._memo[fk] = res
            out: list[Entry] = []
            for key, row, diff in entries:
                out.append((key, self._memo[freeze_row(row)], diff))
            return consolidate(out)
        # pipelined: dispatch this batch, emit batches dispatched at
        # earlier timestamps (their device work ran while the host was
        # parsing/ingesting this one)
        if entries:
            fut = (
                self._dispatch(list(to_compute.values())) if to_compute else None
            )
            self._scheduled.update(to_compute.keys())
            self._in_flight.append((time, fut, list(to_compute.keys()), entries))
        return self._drain(lambda t: t < time)

    def _drain(self, ready) -> list[Entry]:
        out: list[Entry] = []
        rest: list[tuple] = []
        for t, fut, fks, batch in self._in_flight:
            if not ready(t):
                rest.append((t, fut, fks, batch))
                continue
            if fut is not None:
                for fk, res in zip(fks, fut.result()):
                    self._memo[fk] = res
            for key, row, diff in batch:
                out.append((key, self._memo[freeze_row(row)], diff))
        self._in_flight = rest
        return consolidate(out)

    def has_pending(self, time: int) -> bool:
        if super().has_pending(time):
            return True
        return self.pipelined and any(t < time for t, *_ in self._in_flight)

    def async_ready(self) -> bool:
        """True when a dispatched batch has resolved and only needs an
        engine step to emit — lets an idle streaming driver drain results
        promptly instead of waiting for the next input."""
        return self.pipelined and any(
            fut is None or fut.done() for _, fut, *_ in self._in_flight
        )

    def on_end(self) -> list[Entry]:
        return self._drain(lambda t: True) if self.pipelined else []


class OutputNode(Node):
    """Terminal node: materializes the table and fires subscribe callbacks
    (reference: graph.rs:733 ``subscribe_table`` / SubscribeCallbacks:548)."""

    def __init__(
        self,
        on_change: Callable | None = None,
        on_time_end: Callable | None = None,
        on_end: Callable | None = None,
        keep_history: bool = True,
        name: str = "output",
    ):
        super().__init__(n_inputs=1, name=name)
        self.on_change = on_change
        self.on_time_end_cb = on_time_end
        self.on_end_cb = on_end
        # debug/materialize needs the full update stream; long-running
        # subscribe sinks must not accumulate it (unbounded growth)
        self.keep_history = keep_history
        self.current: dict[Pointer, tuple] = {}
        self.history: list[tuple[Pointer, tuple, int, int]] = []  # key,row,time,diff

    def flush(self, time: int) -> list[Entry]:
        entries = consolidate(self.take(0))
        self._step_touched = self._step_touched or bool(entries)
        # retractions before additions (an upsert's delete must precede
        # its insert in callbacks); diffs are ±k so a stable partition
        # equals the old sorted(key=diff) at a fraction of the cost, and
        # the common all-additions batch skips the pass entirely
        if any(e[2] < 0 for e in entries):
            entries = [e for e in entries if e[2] < 0] + [
                e for e in entries if e[2] >= 0
            ]
        for key, row, diff in entries:
            if self.keep_history:
                self.history.append((key, row, time, diff))
            if diff > 0:
                self.current[key] = row
            else:
                self.current.pop(key, None)
            if self.on_change is not None:
                for _ in range(abs(diff)):
                    self.on_change(key, row, time, diff > 0)
        return []

    _step_touched = False

    def end_of_step(self, time: int) -> None:
        if self._step_touched and self.on_time_end_cb is not None:
            self.on_time_end_cb(time)
        self._step_touched = False

    def on_stream_close(self) -> None:
        if self.on_end_cb is not None:
            self.on_end_cb()


class Engine:
    """Micro-batch scheduler (replaces the reference's
    ``worker.step_or_park`` event loop, dataflow.rs:5680 area).

    Within one timestamp, nodes are flushed in passes until the whole graph
    is quiescent, so correctness does not depend on node insertion order
    (timely gets the same property from its scheduler)."""

    def __init__(self):
        self.nodes: list[Node] = []
        self.sources: list[SourceNode] = []
        self.frontier: int = -1
        # attached by pw.run when monitoring is on (internals/monitoring.py)
        self.monitor = None
        #: host worker pool (PATHWAY_THREADS, reference timely
        #: Config::process(threads), dataflow/config.rs:63-70): row-wise
        #: operator batches split across threads.  Pure Python mappers are
        #: GIL-bound, but UDFs doing IO or native work (numpy, JAX
        #: dispatch, tokenizers, zlib) release the GIL and scale.
        self.threads: int = 1
        self.host_pool = None
        self.shard_stateful = False

    def set_threads(self, threads: int) -> None:
        if threads > 1 and self.host_pool is None:
            import os as _os
            from concurrent.futures import ThreadPoolExecutor

            self.threads = threads
            self.host_pool = ThreadPoolExecutor(
                max_workers=threads, thread_name_prefix="pw-worker"
            )
            #: shard stateful columnar ingest across the pool only where
            #: threads can actually overlap (numpy releases the GIL, but
            #: a single core just pays the partitioning tax)
            self.shard_stateful = (
                (_os.cpu_count() or 1) > 1
                or _os.environ.get("PATHWAY_FORCE_THREAD_SHARDS") == "1"
            )

    def add(self, node: Node) -> Node:
        node.id = len(self.nodes)
        node.engine = self
        self.nodes.append(node)
        if isinstance(node, SourceNode):
            self.sources.append(node)
        return node

    def connect(self, src: Node, dst: Node, port: int = 0) -> None:
        src.downstream.append((dst, port))

    def step(self, time: int) -> None:
        """Process one timestamp to quiescence (drives :meth:`step_iter`
        straight through — the yields only matter to the distributed
        wavefront scheduler)."""
        for _node in self.step_iter(time):
            pass

    def step_iter(self, time: int, skip_ids: frozenset = frozenset()):
        """Resumable :meth:`step`: processes one timestamp to quiescence,
        yielding each exchange node just before flushing it.

        Two phases per pass: regular nodes run until quiet, then ``late``
        nodes (exchanges, as-of-now index serving) get one pass —
        guaranteeing every index update for this timestamp lands before
        any query is answered.

        The yield protocol is the poor-man's timely frontier (reference:
        src/engine/dataflow.rs:5689-5731 ``step_or_park``): between two
        yields a round's work runs atomically, so a scheduler that
        resumes round ``t+1`` past an exchange only after round ``t``
        passed it preserves per-node timestamp order while rounds overlap
        — a downstream exchange can send round ``t+1`` while an upstream
        straggler still completes ``t`` (io/streaming.py wavefront loop).
        """
        for _pass in range(100_000):
            progressed = False
            for node in self.nodes:
                if (
                    node.late
                    or node.id in skip_ids
                    or not node.has_pending(time)
                ):
                    # skip_ids: the ingest-safe subgraph belongs to the
                    # stage-1 ingest thread in distributed runs — touching
                    # it here would race half-delivered later rounds
                    continue
                progressed = True
                out = self._flush_node(node, time)
                if out:
                    for consumer, port in node.downstream:
                        consumer.receive(port, out)
            if progressed:
                continue
            # one late node per pass: its output must fully propagate (and any
            # downstream late node's inputs settle) before the next late node
            # answers — keeps the barrier correct for chained late nodes
            for node in self.nodes:
                if node.late and node.has_pending(time):
                    progressed = True
                    if getattr(node, "is_exchange", False):
                        # suspension point: local input is settled (all
                        # earlier nodes quiesced) — the scheduler may
                        # prepare()/send now and resume when peers' data
                        # arrived and the wavefront guard clears
                        yield node
                    out = self._flush_node(node, time)
                    if out:
                        for consumer, port in node.downstream:
                            consumer.receive(port, out)
                    break
            if not progressed:
                break
        else:  # pragma: no cover
            raise RuntimeError("engine did not quiesce (cycle without progress?)")
        for node in self.nodes:
            node.end_of_step(time)
        self.frontier = time
        if self.monitor is not None:
            self.monitor.record_step(time)

    def step_ingest(self, time: int, safe_ids: set, first_hop) -> None:
        """Stage 1 of a distributed round, runnable AHEAD of older
        unfinished rounds: flush the ingest-safe subgraph (nodes whose
        outputs flow only into exchange inputs — internals/exchange.py
        ``ingest_safe_nodes``) to quiescence for ``time``, then partition
        and SEND the first-hop exchanges' batches without waiting for
        peers.  Everything else stays queued until ``step`` finishes the
        round in order."""
        for _pass in range(100_000):
            progressed = False
            for node in self.nodes:
                if node.id not in safe_ids or not node.has_pending(time):
                    continue
                progressed = True
                out = self._flush_node(node, time)
                if out:
                    for consumer, port in node.downstream:
                        consumer.receive(port, out)
            if not progressed:
                break
        else:  # pragma: no cover
            raise RuntimeError("step_ingest did not quiesce")
        for node in first_hop:
            node.prepare(time)

    def _flush_node(self, node: Node, time: int) -> list[Entry]:
        logs = node.error_logs
        if logs:
            from .errors import set_current_local

            set_current_local(logs)
        try:
            from .flight_recorder import current_batch_link, span

            # the flight recorder sees every flush even when the stats
            # monitor is off (the default server path): a slow operator
            # window is dumpable from /v1/debug/traces with zero setup,
            # and a step that carries connector rows files its flushes
            # under that batch's trace id
            link = current_batch_link()
            with span(
                f"flush:{node.name}", "engine", stage="engine.flush",
                links=None if link is None else [link], t=time,
            ) as timed:
                out = node.flush(time)
                timed.set(rows=len(out))
            if self.monitor is not None:
                self.monitor.record_flush(
                    node.name, len(out), timed.duration_ms / 1000.0
                )
            return out
        finally:
            if logs:
                from .errors import set_current_local

                set_current_local(())

    def has_async_ready(self) -> bool:
        """Any pipelined async node holding resolved, unemitted results."""
        return any(
            isinstance(n, AsyncMapNode) and n.async_ready() for n in self.nodes
        )

    def has_placement_flush_pending(self) -> bool:
        """Any index node with an unstaged tier-placement change (duck-
        typed — ExternalIndexNode lives a layer above this module).  The
        streaming driver steps once while idle so end_of_step persists
        it; see lowering.ExternalIndexNode.placement_flush_pending."""
        for n in self.nodes:
            fn = getattr(n, "placement_flush_pending", None)
            if fn is not None and fn():
                return True
        return False

    def run_all(self) -> None:
        """Batch mode: drain all queued source times, then close."""
        with gc_batch_mode():
            while True:
                times = sorted(
                    {t for s in self.sources for t in s.pending_times()}
                )
                if not times:
                    break
                for t in times:
                    self.step(t)
        self.finish()

    def finish(self) -> None:
        for node in self.nodes:
            out = node.on_end()
            if out:
                for consumer, port in node.downstream:
                    consumer.receive(port, out)
        # propagate final emissions, then fire close callbacks
        self.step(self.frontier + 1)
        for node in self.nodes:
            node.on_stream_close()
        if self.host_pool is not None:
            # each run builds its own engine — don't leak worker threads
            self.host_pool.shutdown(wait=False)
            self.host_pool = None
