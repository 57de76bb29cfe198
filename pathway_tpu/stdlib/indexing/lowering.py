"""Runtime node + lowering for the external-index operator and sorting.

reference: src/engine/dataflow/operators/external_index.rs
(``use_external_index_as_of_now_core``:81 — updates applied before queries
per time batch :129-160; index stream broadcast :95) and graph.rs:894.

TPU re-design: instead of replicating the index to every worker via
broadcast, the index lives once in device HBM (see ops/knn.py); the node is
marked ``late`` so the engine's per-timestamp barrier guarantees globally
that all index updates for a timestamp land before any query of that
timestamp is answered — the invariant the reference gets from
``batch_by_time`` + local operator ordering.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Any

import numpy as np

from ...internals.engine import Entry, Node, consolidate
from ...internals.evaluator import compile_expression
from ...internals.value import ERROR
from ...internals.runtime import AsyncSlots, GraphRunner, _TableLayout
from ...internals.graph import Operator

__all__ = [
    "ExternalIndexNode",
    "lower_external_index",
    "lower_sort",
    "live_index_node",
]


#: live ExternalIndexNodes keyed by the identity of the factory that built
#: their inner index — the serving scheduler's retrieve plane
#: (xpacks/llm/_scheduler.py) uses this to answer REST queries against the
#: engine-maintained index without riding engine micro-batch cadence.
#: Weak values: a finished engine's nodes drop out with it.
_LIVE_INDEX_NODES: "weakref.WeakValueDictionary[int, Node]" = (
    weakref.WeakValueDictionary()
)


def live_index_node(factory: Any) -> "ExternalIndexNode | None":
    """The running index node lowered from ``factory``, if any."""
    return _LIVE_INDEX_NODES.get(id(factory))


class ExternalIndexNode(Node):
    """Port 0 = index updates (docs), port 1 = queries."""

    late = True

    def __init__(
        self,
        index,
        doc_data_fn,
        doc_meta_fn,
        query_data_fn,
        query_k_fn,
        query_filter_fn,
        doc_payload_fn,
        mode: str = "asof_now",
        name: str = "external_index",
        doc_slots=None,
        query_slots=None,
    ):
        super().__init__(n_inputs=2, name=name)
        #: the async applies (an embedder) lifted out of the document and
        #: query expressions (``AsyncSlots``; empty or None where they hold
        #: none): the ``*_fn`` then read their results from the row
        self.doc_slots = doc_slots
        self.query_slots = query_slots
        self.index = index
        self.doc_data_fn = doc_data_fn
        self.doc_meta_fn = doc_meta_fn
        self.query_data_fn = query_data_fn
        self.query_k_fn = query_k_fn
        self.query_filter_fn = query_filter_fn
        self.doc_payload_fn = doc_payload_fn
        self.mode = mode
        # doc payload snapshot for reply enrichment (as-of-answer-time)
        self.doc_payload: dict[Any, tuple] = {}
        # live-mode query state: qkey -> (row, last_emitted_row)
        self.live_queries: dict[Any, list] = {}
        # asof_now: answered replies kept so a query retraction (REST
        # delete_completed_queries) retracts its reply and frees the state —
        # the reference's ForgetImmediately cleanup on asof-now queries.
        # For keep-queries streams this grows with total queries, the same
        # asymptotics as the downstream reply table those queries requested.
        self.answered: dict[Any, tuple] = {}
        #: chunked operator-snapshot plane (streaming driver attaches it
        #: under OPERATOR_PERSISTING).  Deltas carry the ALREADY-COMPUTED
        #: doc vectors — restore streams them back into HBM without one
        #: encoder call (EdgeRAG: persisting embeddings beats online
        #: regeneration).  ``_snap_pending`` holds this step's net doc
        #: changes: key -> (data, meta, payload) for upserts, None for
        #: deletes; cleared only once the delta chunk is durably written.
        self.persistent_id: str | None = None
        self._op_snapshot = None
        self._snap_pending: dict[Any, tuple | None] = {}
        #: warm-restart health gate: "restoring" while the driver streams
        #: snapshot chunks back into the index — the serving plane
        #: (RetrievePlane) answers from the lexical mirror until cleared
        self._restore_state: str | None = None
        self.restored_rows = 0
        #: serving-cache freshness watermark: a monotone per-index commit
        #: sequence advanced EXACTLY when the corpus visible to queries
        #: changes (flush-applied upserts/deletes, snapshot restore).
        #: Tier migrations (pathway_tpu/tiering) deliberately never pass
        #: through here — scores are tier-independent by construction, so
        #: a migration storm must not flush the result cache.
        self.commit_seq = 0
        #: bounded (seq, wall-time) history backing stale-while-revalidate;
        #: the lock covers bump (engine flush thread) vs read (serving
        #: scheduler thread) — iterating a deque mid-append raises
        self._commit_times: deque[tuple[int, float]] = deque(maxlen=256)
        self._commit_times_lock = threading.Lock()

    def flush(self, time: int) -> list[Entry]:
        out: list[Entry] = []
        index_changed = False
        # 1. apply index updates (updates-before-queries).  Within one
        # timestamp each key's FINAL entry decides its state (add is
        # upsert, remove of an absent key is a no-op), so adds collapse
        # into one batched call — a single staged device scatter per
        # flush instead of one per document
        last: dict[Any, tuple | None] = {}
        payloads: dict[Any, tuple] = {}
        updates = self.take(0)
        if updates:
            index_changed = True
            from ...internals.flight_recorder import (
                batch_link_scope, batch_trace_id, span,
            )
            from ...internals.monitoring import get_freshness

            fresh = get_freshness()
            scope = getattr(self, "_freshness_scope", 0)
            # the index data expression is evaluated here: for a vector
            # index that is the embedder, whose calls for every row of the
            # flush are pending in the tick runtime before any is awaited,
            # so that the flush rides one device tick inside this span.
            # A timestamp that carries connector rows is a traced batch:
            # its embed calls carry the batch's link to the tick runtime,
            # which stamps the ticks that run them on it
            with span(
                "index.doc_data", "index", stage="index.doc_data",
                rows=len(updates),
            ) as timed:
                traced = fresh.note_index(time, timed.start_s, scope=scope)
                link = (batch_trace_id(scope, time), None) if traced else None
                if link is not None:
                    timed.links = [link]
                with batch_link_scope(link):
                    self._collect_updates(updates, last, payloads)
            if traced:
                fresh.note_embedded(
                    time, timed.start_s + timed.duration_ms / 1000.0,
                    scope=scope,
                )
        add_keys = [k for k, v in last.items() if v is not None]
        # the corpus visible to queries changes only when something real
        # applies: an upsert, or a remove of a key actually present.
        # ERROR-skipped docs and removes of absent keys must NOT bump the
        # watermark — a stream of failing UDF docs would otherwise
        # invalidate the whole result cache every flush while serving the
        # exact same corpus (computed BEFORE applying: the apply pops
        # removed keys from doc_payload)
        corpus_changed = bool(add_keys) or any(
            v is None and k in self.doc_payload for k, v in last.items()
        )
        try:
            self._apply_index_updates(last, payloads, add_keys)
        except Exception as exc:  # noqa: BLE001 — classify before routing
            if not self._contain_device_fault(exc):
                raise
            try:
                # one retry against the rebuilt arrays (upserts/removes
                # are idempotent, so a partially-applied first attempt
                # re-applies cleanly)
                self._apply_index_updates(last, payloads, add_keys)
            except Exception as exc2:  # noqa: BLE001
                from ...ops.device_faults import classify_device_error

                if classify_device_error(exc2) is None:
                    raise
                # still failing on the device plane: drop the batch from
                # the DEVICE index but keep the run alive — the snapshot
                # below still records the vectors, so the docs are
                # durable and re-enter on the next rebuild/restart
                from ...internals.errors import register_error

                register_error(
                    f"index update batch dropped after device-fault retry: "
                    f"{type(exc2).__name__}: {exc2}",
                    kind="index",
                    operator=self.name,
                )
        if self._op_snapshot is not None and self.persistent_id:
            snap_vals = self._snap_values(last)
            for key, action in last.items():
                if action is None:
                    self._snap_pending[key] = None
                else:
                    self._snap_pending[key] = (
                        snap_vals[key],
                        action[1],
                        payloads[key],
                    )
        if index_changed:
            # freshness watermark: the updates of engine timestamp `time`
            # are queryable from here on (updates-before-queries), closing
            # the ingest->queryable loop the driver opened when it stamped
            # this timestamp (pathway_index_freshness_seconds{index=...})
            get_freshness().note_indexed(
                self.name, time, scope=getattr(self, "_freshness_scope", 0)
            )
        if corpus_changed:
            # serving result cache: entries cached at an older commit_seq
            # are no longer exact from this point (xpacks/llm/_query_cache)
            self.bump_commit_seq()
        # 2. answer new queries
        new_queries: list[tuple[Any, tuple]] = []
        for key, row, diff in self.take(1):
            if self.mode == "asof_now":
                if diff > 0:
                    new_queries.append((key, row))
                else:
                    answered = self.answered.pop(key, None)
                    if answered is not None:
                        out.append((key, answered, -1))
            else:
                slot = self.live_queries.get(key)
                if diff > 0:
                    self.live_queries[key] = [row, None]
                    new_queries.append((key, row))
                elif slot is not None:
                    if slot[1] is not None:
                        out.append((key, slot[1], -1))
                    del self.live_queries[key]
        if new_queries:
            replies = self._answer([row for _, row in new_queries])
            for (key, row), reply in zip(new_queries, replies):
                out_row = tuple(row) + (reply,)
                out.append((key, out_row, 1))
                if self.mode == "live":
                    self.live_queries[key][1] = out_row
                else:
                    self.answered[key] = out_row
        # 3. live mode: refresh previously-answered queries on index change
        if self.mode == "live" and index_changed and self.live_queries:
            stale = [
                (key, slot)
                for key, slot in self.live_queries.items()
                if slot[1] is not None and not any(key == k for k, _ in new_queries)
            ]
            if stale:
                from ...internals.engine import freeze_row

                replies = self._answer([slot[0] for _, slot in stale])
                for (key, slot), reply in zip(stale, replies):
                    new_row = tuple(slot[0]) + (reply,)
                    if freeze_row(new_row) != freeze_row(slot[1]):
                        out.append((key, slot[1], -1))
                        out.append((key, new_row, 1))
                        slot[1] = new_row
        return consolidate(out)

    def _collect_updates(self, updates, last: dict, payloads: dict) -> None:
        """Evaluate index data, metadata and payload of each update; within
        one timestamp a key's FINAL entry decides its state."""
        ctxs = [(key, row) for key, row, _diff in updates]
        if self.doc_slots:
            ctxs = self.doc_slots.extend_all(ctxs)
        for (key, _row, diff), ctx in zip(updates, ctxs):
            data = self.doc_data_fn(ctx)
            meta = self.doc_meta_fn(ctx)
            if data is ERROR or meta is ERROR:
                # a document whose embedding/metadata errored (failed UDF
                # under terminate_on_error=False) must not poison the
                # index: skip it both ways (its retraction computes the
                # same ERROR and is skipped symmetrically) and log once
                if diff > 0:
                    from ...internals.errors import register_error

                    register_error(
                        "document with ERROR embedding/metadata excluded "
                        "from index",
                        kind="index",
                        operator=self.name,
                    )
                continue
            if diff > 0:
                last[key] = (data, meta)
                payloads[key] = self.doc_payload_fn(ctx)
            else:
                last[key] = None

    # -- serving-cache freshness watermark -------------------------------
    def bump_commit_seq(self) -> None:
        """Advance the per-index commit sequence (see the attribute doc:
        corpus-changing flushes and snapshot restores only — NEVER tier
        migrations)."""
        with self._commit_times_lock:
            self.commit_seq += 1
            self._commit_times.append((self.commit_seq, time.time()))

    def stale_age(self, watermark: int) -> float | None:
        """Seconds since the index FIRST advanced past ``watermark`` —
        i.e. how stale a result cached at that watermark is now.  None
        when unknown (no history, or the advance aged out of the bounded
        ring): callers must treat unknown as too stale."""
        with self._commit_times_lock:
            times = tuple(self._commit_times)
        if not times:
            return None
        if times[0][0] > watermark + 1:
            return None  # the true first-advance time was evicted
        for seq, t in times:
            if seq > watermark:
                return max(0.0, time.time() - t)
        return None

    # -- index-update application + device-fault containment ------------
    def _apply_index_updates(self, last, payloads, add_keys) -> None:
        if not last:
            return
        from ...internals.flight_recorder import record_ingest_docs, span

        with span(
            "index.apply", "index", stage="index.apply",
            added=len(add_keys), removed=len(last) - len(add_keys),
        ):
            for key, action in last.items():
                if action is None:
                    self.index.remove(key)
                    self.doc_payload.pop(key, None)
            if add_keys:
                if hasattr(self.index, "add_batch"):
                    self.index.add_batch(
                        add_keys,
                        [last[k][0] for k in add_keys],
                        [last[k][1] for k in add_keys],
                    )
                else:  # duck-typed custom index without the batched protocol
                    for key in add_keys:
                        self.index.add(key, last[key][0], last[key][1])
                for key in add_keys:
                    self.doc_payload[key] = payloads[key]
                record_ingest_docs(len(add_keys))

    def _contain_device_fault(self, exc: BaseException) -> bool:
        """Containment for device errors raised by index mutation/search:
        transient ones are logged (the caller retries / degrades), fatal
        ones additionally rebuild the device arrays from the host mirror
        or the snapshot.  Returns False for non-device exceptions — plain
        bugs keep their normal routing."""
        from ...internals.errors import register_error
        from ...ops.device_faults import FATAL, classify_device_error

        kind = classify_device_error(exc)
        if kind is None:
            return False
        register_error(
            f"device fault ({kind}) in index {self.name!r}: "
            f"{type(exc).__name__}: {exc}",
            kind="index",
            operator=self.name,
        )
        if kind == FATAL:
            # a rebuild on a still-dead device can itself raise — that
            # must stay inside the containment boundary (the caller's
            # retry will fail and take the degraded/drop path), never
            # escape to kill the engine thread
            try:
                self.rebuild_device_state()
            except Exception as rexc:  # noqa: BLE001 — contained
                register_error(
                    f"index rebuild after device fault failed: "
                    f"{type(rexc).__name__}: {rexc}",
                    kind="index",
                    operator=self.name,
                )
        return True

    def rebuild_device_state(self) -> bool:
        """Recreate the inner index's device arrays after a fatal fault —
        host mirror first, snapshot vectors as the fallback (the
        ``_place()`` rebuild hook re-pins sharded matrices to the mesh).
        Returns True when a rebuild happened."""
        from ...internals.flight_recorder import span

        inner = getattr(self.index, "index", None)
        if inner is None or not hasattr(inner, "rebuild_device_arrays"):
            return False
        with span(f"rebuild:{self.name}", "restore", index=self.name) as timed:
            ok = inner.rebuild_device_arrays()
            source = "host_mirror"
            if not ok:
                vectors = self._snapshot_vectors()
                if vectors:
                    ok = inner.rebuild_device_arrays(vectors)
                    source = "snapshot"
            timed.set(ok=ok, source=source)
        return ok

    def _snapshot_vectors(self) -> dict | None:
        """Doc vectors replayed from the snapshot plane (fatal-rebuild
        fallback when even a D2H copy of the matrix fails).  Quantized
        indexes snapshot ``(codes, scale)`` records — those replay
        straight back as codes (``DeviceKnnIndex.upsert_coded``)."""
        from ...ops.quantized_scoring import is_quant_record

        if self._op_snapshot is None or not self.persistent_id:
            return None
        state = self._op_snapshot.load(self.persistent_id) or {}
        out = {
            key: rec[0]
            for key, rec in state.items()
            if isinstance(rec[0], np.ndarray) or is_quant_record(rec[0])
        }
        return out or None

    def _inner_device_index(self):
        """The inner ``DeviceKnnIndex`` behind this node's index, if
        any (duck-typed custom indexes return None)."""
        return getattr(self.index, "index", None)

    @staticmethod
    def _snap_value(data):
        """Snapshot representation of one doc's index data: array-likes
        (embeddings) are pinned as float32 numpy — a device array must
        not ride a pickle — while text (BM25) passes through."""
        if isinstance(data, np.ndarray):
            return np.asarray(data, dtype=np.float32)
        if hasattr(data, "__array__") or isinstance(data, (list, tuple)):
            return np.asarray(data, dtype=np.float32)
        return data

    def _snap_values(self, last: dict) -> dict:
        """Snapshot values for one flush's net doc changes.

        Unquantized indexes pin raw f32 vectors (``_snap_value``).  A
        QUANTIZED inner index instead exports the EXACT resident
        codes+scale per key in ONE batched gather
        (``DeviceKnnIndex.export_records``): the snapshot then holds
        precisely the bytes the index serves — restore is bit-identical
        with zero re-embeds and zero re-quantization, and the snapshot
        itself shrinks ~4x with the matrix.  If the export fails (the
        device plane may be faulting — durability must not die with it),
        the host-side quantizer produces an equivalent record from the
        raw vector."""
        inner = self._inner_device_index()
        quantized = inner is not None and getattr(inner, "quantized", False)
        out: dict = {}
        vec_keys: list = []
        for key, action in last.items():
            if action is None:
                continue
            data = action[0]
            if quantized and (
                isinstance(data, np.ndarray)
                or hasattr(data, "__array__")
                or isinstance(data, (list, tuple))
            ):
                vec_keys.append(key)
            else:
                out[key] = self._snap_value(data)
        if vec_keys:
            try:
                records = inner.export_records(vec_keys)
            except Exception:  # noqa: BLE001 — device fault: host fallback
                records = {}
            if len(records) < len(vec_keys):
                from ...ops.quantized_scoring import quantize_record_np

                for key in vec_keys:
                    if key not in records:
                        records[key] = quantize_record_np(
                            np.asarray(last[key][0], dtype=np.float32),
                            normalize=inner.metric == "cos",
                        )
            out.update(records)
        return out

    # -- operator snapshots (reference: operator_snapshot.rs) -----------
    _SNAPSHOT_WRITE_ATTEMPTS = 3

    #: reserved snapshot-state key for the tiered index's placement blob
    #: (== pathway_tpu.tiering.TIER_PLACEMENT_KEY — duplicated literally
    #: so reading a snapshot never imports the jax-backed tiering module)
    _TIER_PLACEMENT_KEY = "__pw_tier_placement__"

    def _maybe_stage_placement(self) -> None:
        """Tiered inner index: when the tier assignment changed since the
        last snapshot (online promotions/demotions, hot fills), stage the
        placement blob as a reserved state row so the NEXT delta carries
        it — a warm restart then rebuilds the exact same placement."""
        fn = getattr(self.index, "placement_blob_if_dirty", None)
        if fn is None:
            return
        blob = fn()
        if blob is not None:
            self._snap_pending[self._TIER_PLACEMENT_KEY] = (blob, None, None)

    def placement_flush_pending(self) -> bool:
        """A tiered inner index changed its placement and the change is
        not yet staged for the snapshot plane.  The streaming driver
        checks this while sources are idle: migrations are driven by
        QUERY traffic, so without an idle step a placement mutated
        during an ingest lull would never be persisted and a kill in
        that window would restore the older placement."""
        if self._op_snapshot is None or not self.persistent_id:
            return False
        return bool(getattr(self.index, "placement_dirty", False))

    def _snap_header(self) -> dict | None:
        """Delta-chunk header: the index's routing spec (LSH projector /
        partition router seeds), persisted so a restored process routes
        queries to the same partitions."""
        fn = getattr(self.index, "snapshot_header", None)
        if fn is None:
            return None
        try:
            return fn()
        except Exception:  # noqa: BLE001 — a header must never block a delta
            return None

    def apply_snapshot_header(self, header: dict | None) -> None:
        """Re-apply a restored delta-chunk header (routing specs) to the
        inner index — called by the streaming driver BEFORE the restored
        rows stream back in."""
        if not header:
            return
        fn = getattr(self.index, "apply_snapshot_header", None)
        if fn is not None:
            fn(header)

    def end_of_step(self, time: int) -> None:
        if self._op_snapshot is not None and self.persistent_id:
            self._maybe_stage_placement()
        if not (
            self._snap_pending
            and self._op_snapshot is not None
            and self.persistent_id
        ):
            return
        from ...testing import faults

        upserts = {k: v for k, v in self._snap_pending.items() if v is not None}
        deletes = [k for k, v in self._snap_pending.items() if v is None]
        last_exc: BaseException | None = None
        for _attempt in range(self._SNAPSHOT_WRITE_ATTEMPTS):
            try:
                if faults.enabled:
                    faults.perturb("index.snapshot")
                self._op_snapshot.save_delta(
                    self.persistent_id,
                    time,
                    upserts,
                    deletes,
                    live_entries=len(self.doc_payload),
                    header=self._snap_header(),
                )
                self._snap_pending.clear()
                return
            except Exception as exc:  # noqa: BLE001 — bounded retry
                last_exc = exc
        # a snapshot that cannot be written is a durability failure: the
        # commit record would otherwise advance offsets past rows whose
        # state never landed — fail LOUDLY rather than break exactly-once
        raise RuntimeError(
            f"index {self.name!r} could not write its snapshot delta after "
            f"{self._SNAPSHOT_WRITE_ATTEMPTS} attempts"
        ) from last_exc

    def restore_snapshot(self, state: dict) -> None:
        """Warm restart: stream the snapshotted (vector, metadata,
        payload) rows back into the index through ONE bulk ``add_batch``
        (a single staged device scatter) — zero encoder calls.

        A tiered index additionally restores its tier placement: the
        reserved placement row (hot key set + router spec) is popped
        from the state and pinned BEFORE the rows flow in, so every
        restored key lands straight in the tier it held when the
        snapshot was cut — placement is bit-for-bit, not re-derived
        from restore iteration order."""
        placement = state.pop(self._TIER_PLACEMENT_KEY, None)
        if placement is not None and hasattr(self.index, "restore_placement"):
            self.index.restore_placement(placement[0])
        keys, datas, metas = [], [], []
        for key, (data, meta, payload) in state.items():
            keys.append(key)
            datas.append(data)
            metas.append(meta)
            self.doc_payload[key] = payload
        if keys:
            if hasattr(self.index, "add_batch"):
                self.index.add_batch(keys, datas, metas)
            else:
                for key, data, meta in zip(keys, datas, metas):
                    self.index.add(key, data, meta)
        if placement is not None and hasattr(self.index, "finish_restore"):
            self.index.finish_restore()
        self.restored_rows = len(keys)
        # restore invalidates any serving-cache entry from a previous
        # engine life in this process (xpacks/llm/_query_cache)
        self.bump_commit_seq()

    def _answer(self, rows: list[tuple]) -> list[tuple]:
        queries = []
        ctxs = [(None, row) for row in rows]
        if self.query_slots:
            ctxs = self.query_slots.extend_all(ctxs)
        for ctx in ctxs:
            q = self.query_data_fn(ctx)
            k = self.query_k_fn(ctx)
            flt = self.query_filter_fn(ctx)
            if q is ERROR or k is ERROR or flt is ERROR:
                # an errored query gets an empty reply instead of
                # crashing the whole batch's device search
                from ...internals.errors import register_error

                register_error(
                    "query with ERROR input answered empty",
                    kind="index",
                    operator=self.name,
                )
                queries.append(None)
            else:
                queries.append((q, int(k), flt))
        live = [q for q in queries if q is not None]
        try:
            raw = self.index.search(live)
        except Exception as exc:  # noqa: BLE001 — classify before routing
            if not self._contain_device_fault(exc):
                raise
            try:
                # one retry against rebuilt/recovered arrays
                raw = self.index.search(live)
            except Exception as exc2:  # noqa: BLE001
                from ...ops.device_faults import classify_device_error

                if classify_device_error(exc2) is None:
                    raise
                from ...internals.errors import register_error

                register_error(
                    "query batch answered empty after device fault: "
                    f"{type(exc2).__name__}: {exc2}",
                    kind="index",
                    operator=self.name,
                )
                raw = [[] for _ in live]
        raw_iter = iter(raw)
        replies = []
        for q in queries:
            matches = () if q is None else next(raw_iter)
            replies.append(
                tuple(
                    (key, float(score), self.doc_payload.get(key))
                    for key, score in matches
                )
            )
        return replies


def lower_external_index(runner: GraphRunner, op: Operator) -> None:
    docs_t, query_t = op.inputs
    p = op.params
    index = p["factory"].build_inner_index()
    name = f"index#{op.id}"

    def compile_side(table, exprs: list):
        """One input's expressions (None where the operator has none) as
        functions of a row, their async applies (the embedder) lifted into
        slots the ``select`` lowering's way; expressions that hold none
        evaluate row by row as they are."""
        layout = _TableLayout([table])
        resolve = layout.resolver()
        slots = AsyncSlots(
            [e for e in exprs if e is not None], resolve, layout.width, name
        )
        fns = [
            e if e is None else compile_expression(slots.substitute(e), resolve)
            for e in exprs
        ]
        return fns, slots

    k = p.get("k", 3)
    (doc_data_fn, doc_meta_fn, *payload_fns), doc_slots = compile_side(
        docs_t,
        [p["index_data"], p.get("index_metadata"), *p.get("payload_exprs", [])],
    )
    (query_data_fn, query_k_fn, query_filter_fn), query_slots = compile_side(
        query_t,
        [p["query_data"], k if hasattr(k, "_dtype") else None,
         p.get("query_filter")],
    )
    doc_meta_fn = doc_meta_fn or (lambda ctx: None)
    query_k_fn = query_k_fn or (lambda ctx, _k=k: _k)
    query_filter_fn = query_filter_fn or (lambda ctx: None)

    def doc_payload_fn(ctx):
        return tuple(f(ctx) for f in payload_fns)

    node = ExternalIndexNode(
        index,
        doc_data_fn,
        doc_meta_fn,
        query_data_fn,
        query_k_fn,
        query_filter_fn,
        doc_payload_fn,
        mode=p.get("mode", "asof_now"),
        name=name,
        doc_slots=doc_slots,
        query_slots=query_slots,
    )
    runner.engine.add(node)
    runner._connect_inputs(op, node)
    runner._register(op, node)
    # freshness watermarks are matched per engine (timestamps restart at 1
    # in every run — see FreshnessTracker's scope note)
    node._freshness_scope = id(runner.engine)
    # snapshot keyspace: op ids are deterministic for a given program
    # (graph build order), the same stability contract as the default
    # connector persistent ids — the streaming driver attaches the
    # snapshot plane under OPERATOR_PERSISTING
    node.persistent_id = f"index#{op.id}"
    # pin the factory on the node: the registry key is id(factory), so the
    # factory must stay alive exactly as long as the entry does — otherwise
    # a recycled id could alias a NEW factory to this stale node
    node._factory = p["factory"]
    _LIVE_INDEX_NODES[id(p["factory"])] = node


# ---------------------------------------------------------------------------
# sorting (reference: src/engine/dataflow/operators/prev_next.rs:770
# add_prev_next_pointers; stdlib/indexing/sorting.py)
# ---------------------------------------------------------------------------


class SortNode(Node):
    """Maintains per-instance ordering, emits (prev, next) pointer columns."""

    def __init__(self, key_fn, instance_fn, name: str = "sort"):
        super().__init__(n_inputs=1, name=name)
        self.key_fn = key_fn
        self.instance_fn = instance_fn
        from collections import defaultdict

        self.rows: dict = {}
        self.instances: dict = defaultdict(dict)  # inst -> {key: sort_val}
        self.last_out: dict = {}

    def flush(self, time: int) -> list[Entry]:
        from ...internals.engine import freeze_value

        dirty = set()
        for key, row, diff in self.take(0):
            ctx = (key, row)
            inst = freeze_value(self.instance_fn(ctx))
            dirty.add(inst)
            if diff > 0:
                self.instances[inst][key] = self.key_fn(ctx)
                self.rows[key] = inst
            else:
                self.instances[inst].pop(key, None)
                self.rows.pop(key, None)
        out: list[Entry] = []
        for inst in dirty:
            ordered = sorted(self.instances[inst].items(), key=lambda kv: (kv[1], kv[0]))
            n = len(ordered)
            for i, (key, _val) in enumerate(ordered):
                prev_key = ordered[i - 1][0] if i > 0 else None
                next_key = ordered[i + 1][0] if i < n - 1 else None
                new_row = (prev_key, next_key)
                old = self.last_out.get(key)
                if old != new_row:
                    if old is not None:
                        out.append((key, old, -1))
                    out.append((key, new_row, 1))
                    self.last_out[key] = new_row
        # rows fully removed
        gone = [k for k in self.last_out if k not in self.rows]
        for key in gone:
            out.append((key, self.last_out.pop(key), -1))
        return consolidate(out)


def lower_sort(runner: GraphRunner, op: Operator) -> None:
    table = op.inputs[0]
    layout = _TableLayout([table])
    resolve = layout.resolver()
    key_fn = compile_expression(op.params["key"], resolve)
    instance = op.params.get("instance")
    inst_fn = (
        compile_expression(instance, resolve)
        if instance is not None
        else (lambda ctx: 0)
    )
    node = SortNode(key_fn, inst_fn, name=f"sort#{op.id}")
    runner.engine.add(node)
    runner._connect_inputs(op, node)
    runner._register(op, node)
