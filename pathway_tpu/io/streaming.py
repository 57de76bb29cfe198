"""Connector subjects + the streaming run loop.

reference: src/connectors/mod.rs (``Connector::run`` reader thread :427,
commit ticks every ``commit_duration`` :207-217, ``SessionType`` adaptors)
and python/pathway/io/python/__init__.py:49 (``ConnectorSubject``).

TPU-era shape: connectors stay host-side threads exactly like the
reference's reader threads, but instead of feeding timely input sessions
over crossbeam channels they buffer diffs that the ``StreamingDriver``
stamps with a micro-batch timestamp and pushes through the engine — one
``engine.step(t)`` per commit is the analogue of a timely epoch.
"""

from __future__ import annotations

import json
import logging
import os
import random
import threading
import time as _time
from typing import Any, Callable, Iterable

from ..internals.engine import Engine, Entry, SourceNode
from ..internals.keys import ref_scalar
from ..internals.value import Json, Pointer
from ..testing import faults

__all__ = [
    "ConnectorSubject",
    "ConnectorSupervisor",
    "StreamingDriver",
    "next_autogen_key",
]

logger = logging.getLogger(__name__)

_autogen_lock = threading.Lock()
_autogen_counter = 0


def next_autogen_key(salt: Any = "io") -> Pointer:
    global _autogen_counter
    with _autogen_lock:
        _autogen_counter += 1
        return ref_scalar("__io_autogen__", salt, _autogen_counter)


class ConnectorSubject:
    """Base class for custom Python input connectors.

    Subclass and implement :meth:`run`, emitting rows via :meth:`next` /
    :meth:`next_json` / :meth:`next_str` / :meth:`next_bytes`; call
    :meth:`commit` to make emitted rows visible atomically and
    :meth:`close` when the stream ends (reference
    io/python/__init__.py:49-214).
    """

    #: "streaming" subjects run on their own thread under pw.run;
    #: "static" subjects are drained synchronously at build time so batch
    #: graphs (pw.debug helpers) see their data without a driver.
    _mode: str = "streaming"
    #: "native" = emitted diffs pass through; "upsert" = a second row with
    #: the same key replaces the first (reference SessionType::Upsert)
    _session_type: str = "native"
    #: commit pending rows automatically every N ms even without an
    #: explicit commit() (reference: connector commit_duration ticks,
    #: src/connectors/mod.rs:207-217); None = explicit commits only
    _autocommit_ms: int | None = None
    #: key under which this subject's input snapshot + offsets persist
    #: (reference: persistent_id on connectors).  Snapshotting is opt-in:
    #: subjects that neither set an explicit persistent_id nor override
    #: current_offsets()/seek() are not persisted (replaying them would
    #: double records).  The default key for offset-tracking subjects is
    #: "{datasource_name}-{occurrence}" (occurrence among same-named
    #: sources in graph order), process-scoped in multi-process runs.
    persistent_id: str | None = None
    #: True for sources every process can see identically (fs/s3/sqlite
    #: scanners): in multi-process runs each process keeps only the keys it
    #: owns, so a record enters the system exactly once globally.  False
    #: for process-local subjects (REST requests, custom python sources).
    _shared_source: bool = False
    #: supervision (ConnectorSupervisor): a reader exception no longer
    #: silently kills the source — run() is restarted with exponential
    #: backoff up to ``_max_restarts`` times (None = env
    #: PATHWAY_CONNECTOR_MAX_RESTARTS, default 3), then the connector is
    #: marked failed on /v1/health while the run keeps going.  Set
    #: ``_supervised = False`` for subjects whose run() is not safely
    #: re-enterable (emits non-idempotent rows without dedup/upsert).
    _supervised: bool = True
    _max_restarts: int | None = None
    #: the ``connector`` label of this subject's metric series
    #: (``<datasource>-<occurrence>``); the streaming driver sets it
    _metrics_label: str | None = None
    #: request-scoped sources (REST handlers) whose rows are in-flight
    #: client requests: nothing to restore on restart (clients retry), so
    #: OPERATOR_PERSISTING's seekability coverage check exempts them
    _ephemeral: bool = False
    #: fault-injection site for rows this subject pushes (None = exempt,
    #: e.g. the error-log subjects themselves)
    _fault_site: str | None = "connector.read"
    #: "raise" (default) re-raises malformed payloads into the reader
    #: (supervisor territory); "dead_letter" routes them to the global
    #: error log + dead-letter sinks and keeps consuming
    _on_error: str = "raise"

    def __init__(self, datasource_name: str = "python") -> None:
        self._datasource_name = datasource_name
        self._lock = threading.Lock()
        self._pending: list[tuple[str, Any, tuple | None]] = []  # op, key, values
        self._committed: list[list[tuple[str, Any, tuple | None]]] = []
        self._closed = threading.Event()
        self._started = False
        self._schema = None
        self._column_names: list[str] = []
        self._primary_key: list[str] | None = None
        self._last_by_key: dict[Any, tuple] = {}
        self._data_event: threading.Event | None = None
        # offset frontier snapshotted atomically with commit()/_drain():
        # the persisted frontier must cover EXACTLY the drained entries —
        # reading current_offsets() on the driver thread after _drain()
        # would race the reader (an entry committed in between would be
        # covered by the frontier but missing from the batch, i.e. lost
        # on restart)
        self._offsets_at_commit: Any = None
        self._offsets_at_drain: Any = None
        #: total commit() calls — the driver uses this to detect a
        #: tracking subject that never self-commits (see _live_loop)
        self._commit_count = 0
        #: set by the driver when persistence storage is configured —
        #: without it the frontier snapshot in commit() is never consumed,
        #: so the (possibly large) current_offsets() copy is skipped
        self._record_offsets = False
        # end-to-end freshness stamps (pathway_freshness_seconds): the
        # wall clock of the FIRST row read into the current pending
        # batch, paired at commit() with the commit's own, carried
        # through _drain() so the driver can hand the earliest read time
        # of each engine timestamp, and the commit that carried it, to
        # the freshness tracker — measuring from source READ, not from
        # the driver push, covers connector-side batching delay too
        self._pending_read_wall: float | None = None
        self._committed_read_walls: list[tuple[float, float]] = []
        self._read_wall_at_drain: float | None = None
        self._commit_wall_at_drain: float | None = None

    # -- to be implemented by subclasses --
    def run(self) -> None:
        raise NotImplementedError

    def on_stop(self) -> None:
        """Called once the subject is done (reference: on_stop hook)."""

    @property
    def _deletions_enabled(self) -> bool:
        return True

    # -- emission API --
    def next(self, **kwargs: Any) -> None:
        values = tuple(kwargs.get(name) for name in self._column_names)
        key = self._derive_key(kwargs)
        self._push("insert", key, values)

    def next_json(self, message: dict | str | bytes) -> None:
        try:
            if isinstance(message, (str, bytes)):
                message = json.loads(message)
            if not isinstance(message, dict):
                raise TypeError(
                    f"expected a JSON object, got {type(message).__name__}"
                )
        except (ValueError, TypeError) as exc:
            if self._on_error == "dead_letter":
                self.dead_letter(message, exc)
                return
            raise
        self.next(**message)

    def dead_letter(self, payload: Any, exc: Exception | None = None) -> None:
        """Route a poison record out of the stream: it lands in
        ``pw.global_error_log()`` (kind ``dead_letter``) and every sink
        registered via ``pw.set_dead_letter_sink`` — the pipeline keeps
        consuming."""
        from ..internals.errors import dead_letter as _dead_letter

        reason = (
            f"{type(exc).__name__}: {exc}" if exc is not None else "poison record"
        )
        _dead_letter(payload, reason, source=self._datasource_name)

    def next_str(self, message: str) -> None:
        self.next(data=message)

    def next_bytes(self, message: bytes) -> None:
        self.next(data=message)

    def delete(self, **kwargs: Any) -> None:
        if not self._deletions_enabled:
            raise RuntimeError("deletions not enabled on this subject")
        values = tuple(kwargs.get(name) for name in self._column_names)
        key = self._derive_key(kwargs)
        self._push("delete", key, values)

    def _remove(self, key: Any, values: tuple) -> None:
        self._push("delete", key, values)

    def _add_inner(self, key: Any, values: tuple) -> None:
        self._push("insert", key, values)

    def commit(self) -> None:
        with self._lock:
            if self._pending:
                self._committed.append(self._pending)
                self._pending = []
                if self._pending_read_wall is not None:
                    self._committed_read_walls.append(
                        (self._pending_read_wall, _time.time())
                    )
                    self._pending_read_wall = None
            # every connector updates its offsets before its own commit()
            # (fs: _seen per emitted file; kafka: per consumed message),
            # so this snapshot is exactly the frontier of the batches
            # committed so far.  Skipped without persistence: nobody
            # consumes it, and for fs it copies the whole _seen dict —
            # which a driver-thread autocommit could also race mid-resize
            # (tracking subjects only self-commit once persistence is on)
            if self._record_offsets:
                self._offsets_at_commit = self.current_offsets()
            self._commit_count += 1
        if self._data_event is not None:
            self._data_event.set()

    def close(self) -> None:
        self.commit()
        self._closed.set()
        if self._data_event is not None:
            self._data_event.set()

    # -- persistence hooks (reference: Reader::seek data_storage.rs:398 +
    # OffsetAntichain offsets; overridden by offset-aware subjects) --
    def current_offsets(self) -> Any:
        """Source position to persist with each snapshot chunk."""
        return None

    def seek(self, offsets: Any) -> None:
        """Restore the source position after snapshot replay."""

    def effective_persistent_id(self, occurrence: int | None = None) -> str | None:
        """Key for this subject's snapshot keyspace.

        An explicit ``persistent_id`` wins.  Otherwise a default is derived
        from the datasource name plus this subject's *occurrence number
        among same-named sources* (graph order), so two subjects with the
        same datasource name (two ``fs.read`` of one path, two custom
        python subjects) never share a keyspace, while adding an unrelated
        differently-named source does not shift existing keys.  Without an
        occurrence number no safe default exists and ``None`` is returned
        (persistence stays off for the subject)."""
        if self.persistent_id is not None:
            return self.persistent_id
        if occurrence is None:
            return None
        return f"{self._datasource_name}-{occurrence}"

    def _tracks_offsets(self) -> bool:
        """True when the subclass overrides offset tracking (capability, not
        the runtime value — a seek-capable source legitimately reports no
        offset before its first record)."""
        return type(self).current_offsets is not ConnectorSubject.current_offsets

    # -- plumbing --
    def _derive_key(self, kwargs: dict) -> Any:
        if self._primary_key:
            return ref_scalar(*[kwargs.get(c) for c in self._primary_key])
        return next_autogen_key(self._datasource_name)

    def _push(self, op: str, key: Any, values: tuple | None) -> None:
        if faults.enabled and self._fault_site is not None:
            # chaos harness: "fail" raises into the reader thread (the
            # supervisor's backoff territory), "drop" loses the row
            if faults.perturb(self._fault_site) == "drop":
                return
        with self._lock:
            if not self._pending:
                self._pending_read_wall = _time.time()
            self._pending.append((op, key, values))

    def _configure(self, schema, primary_key: list[str] | None) -> None:
        self._schema = schema
        self._column_names = list(schema.column_names())
        self._primary_key = primary_key

    def _attach(self, src: SourceNode, engine: Engine) -> None:
        self._src = src
        self._engine = engine

    def _drain(self) -> list[Entry]:
        """Convert committed batches to engine entries (upsert-aware)."""
        with self._lock:
            batches, self._committed = self._committed, []
            # pair the batch with the frontier of its last commit — a
            # commit landing after this point belongs to the NEXT drain
            self._offsets_at_drain = self._offsets_at_commit
            # earliest read time across the drained batches: the start of
            # the end-to-end freshness span for this engine timestamp
            walls, self._committed_read_walls = self._committed_read_walls, []
            self._read_wall_at_drain, self._commit_wall_at_drain = (
                min(walls) if walls else (None, None)
            )
        entries: list[Entry] = []
        for batch in batches:
            for op, key, values in batch:
                if self._session_type == "upsert":
                    old = self._last_by_key.pop(key, None)
                    if old is not None:
                        entries.append((key, old, -1))
                    if op == "insert":
                        entries.append((key, values, 1))
                        self._last_by_key[key] = values
                else:
                    entries.append((key, values, 1 if op == "insert" else -1))
        return entries

    _static_entries: list[Entry] | None = None

    def _run_static(self, src: SourceNode) -> None:
        """Drain a static subject synchronously at time 0 (build time).

        The drained entries are cached so the same table can be
        materialized more than once (pw.debug preview + pw.run)."""
        if self._static_entries is None:
            self.run()
            self.close()
            self._static_entries = self._drain()
            self.on_stop()
        if self._static_entries:
            src.push(0, list(self._static_entries))


#: process-lifetime reader-restart counter (chaos soak reporting and
#: operational introspection) — survives finished runs' supervisors
_restart_total = 0


def connector_restart_total() -> int:
    """Total reader restarts across all supervised connectors so far."""
    return _restart_total


class ConnectorSupervisor:
    """Runs one subject's reader under supervision (reference inspiration:
    src/connectors/mod.rs reader threads, which on error poison the whole
    run — here a reader exception instead triggers exponential-backoff
    restarts, bounded by ``max_restarts``, with per-connector state
    surfaced on ``/v1/health``).

    Restart safety: connectors that dedupe (fs/http ``_seen``) or run
    upsert sessions re-enter ``run()`` cleanly; subjects that cannot set
    ``_supervised = False`` and keep the old die-silently behavior, minus
    the silence (the failure is logged and the connector marked failed).
    """

    #: after this long healthy, the restart budget refills
    BACKOFF_RESET_S = 60.0

    def __init__(self, subject: ConnectorSubject, label: str):
        self.subject = subject
        self.label = label
        self.restarts = 0
        self.max_restarts = subject._max_restarts
        if self.max_restarts is None:
            self.max_restarts = int(
                os.environ.get("PATHWAY_CONNECTOR_MAX_RESTARTS", "3")
            )
        self.backoff_s = float(
            os.environ.get("PATHWAY_CONNECTOR_BACKOFF_S", "0.1")
        )
        self.backoff_cap_s = float(
            os.environ.get("PATHWAY_CONNECTOR_BACKOFF_CAP_S", "30")
        )

    def _health(self):
        from ..internals.health import get_health

        return get_health()

    def _set_state(self, state: str, *, ready: bool = True,
                   degraded: bool = False, detail: str = "") -> None:
        # connectors are not individually critical for readiness: one
        # failed source must not mark an otherwise-serving process
        # unready — it shows as degraded instead
        self._health().set_component(
            f"connector:{self.label}", state,
            ready=ready, degraded=degraded, critical=False, detail=detail,
        )

    def run(self) -> None:
        """Reader-thread body: run → (on failure) backoff → rerun."""
        from ..internals.errors import register_error

        subject = self.subject
        attempt = 0
        delay = self.backoff_s
        while True:
            started = _time.monotonic()
            try:
                self._set_state("running")
                subject.run()
                self._set_state("finished")
                return
            except BaseException as exc:  # noqa: BLE001 — supervised
                if subject._closed.is_set():
                    # shutdown race: the failure is a consequence of
                    # closing, not a fault
                    self._set_state("finished")
                    return
                register_error(
                    f"connector {self.label!r} reader failed: "
                    f"{type(exc).__name__}: {exc}",
                    kind="connector",
                    operator=self.label,
                )
                if not subject._supervised:
                    self._set_state(
                        "failed", ready=True, degraded=True,
                        detail=f"unsupervised reader died: {exc}",
                    )
                    logger.error(
                        "connector %r reader died (unsupervised): %s",
                        self.label, exc,
                    )
                    return
                if _time.monotonic() - started > self.BACKOFF_RESET_S:
                    attempt = 0
                    delay = self.backoff_s
                if attempt >= self.max_restarts:
                    self.restarts = attempt
                    self._set_state(
                        "failed", ready=True, degraded=True,
                        detail=(
                            f"gave up after {attempt} restarts: "
                            f"{type(exc).__name__}: {exc}"
                        ),
                    )
                    logger.error(
                        "connector %r failed permanently after %d restarts: %s",
                        self.label, attempt, exc,
                    )
                    return
                attempt += 1
                self.restarts = attempt
                global _restart_total
                _restart_total += 1
                sleep_s = min(delay, self.backoff_cap_s) * (
                    1.0 + random.uniform(0.0, 0.25)
                )
                self._set_state(
                    "backoff", degraded=True,
                    detail=(
                        f"restart {attempt}/{self.max_restarts} in "
                        f"{sleep_s:.2f}s after {type(exc).__name__}: {exc}"
                    ),
                )
                logger.warning(
                    "connector %r reader failed (%s); restart %d/%d in %.2fs",
                    self.label, exc, attempt, self.max_restarts, sleep_s,
                )
                # responsive to shutdown: close() sets _closed
                if subject._closed.wait(sleep_s):
                    self._set_state("finished")
                    return
                delay = min(delay * 2.0, self.backoff_cap_s)


class StreamingDriver:
    """The run loop behind ``pw.run`` (reference: timely's
    ``worker.step_or_park`` pump, dataflow.rs:5689-5731, with connector
    pollers and commit flushers folded in).

    Starts one thread per streaming subject, then repeatedly drains
    committed batches, stamps them with the next micro-batch timestamp and
    advances the engine.  Terminates when every subject has closed and all
    buffers are empty; runs forever if any subject never closes.
    """

    def __init__(
        self,
        engine: Engine,
        runner,
        *,
        persistence_config: Any = None,
        monitoring_level: Any = None,
        with_http_server: bool = False,
        autocommit_ms: int = 20,
        exchange_plane: Any = None,
    ) -> None:
        self.engine = engine
        self.runner = runner
        self.autocommit_ms = autocommit_ms
        self.persistence_config = persistence_config
        self.exchange_plane = exchange_plane
        self.subject_src: list[tuple[ConnectorSubject, SourceNode]] = []
        #: subject -> occurrence number among same-named sources in graph
        #: order, used to derive unique yet stable default persistent ids
        self._pid_occurrence: dict[int, int] = {}
        name_counts: dict[str, int] = {}
        for src, op in runner.source_nodes:
            subject = op.params.get("subject")
            if subject is not None and subject._mode == "streaming":
                self.subject_src.append((subject, src))
                n = name_counts.get(subject._datasource_name, 0)
                name_counts[subject._datasource_name] = n + 1
                self._pid_occurrence[id(subject)] = n
        self._snapshot_writers: dict[int, Any] = {}
        #: OPERATOR_PERSISTING: subject-id -> (pid, subject), offsets ride
        #: the per-tick commit record instead of input snapshot chunks
        self._commit_subjects: dict[int, tuple] = {}
        self._op_snapshot = None
        #: subject-id -> ConnectorSupervisor (restart counts for soak/health)
        self.supervisors: dict[int, ConnectorSupervisor] = {}

    def _snapshot_storage(self):
        """KV storage when full persistence is on (not UDF-caching-only)."""
        cfg = self.persistence_config
        if cfg is None:
            return None
        from ..persistence import PersistenceMode

        if cfg.persistence_mode in (
            PersistenceMode.PERSISTING,
            PersistenceMode.OPERATOR_PERSISTING,
        ):
            return cfg.backend.storage
        return None

    @property
    def _operator_mode(self) -> bool:
        """OPERATOR_PERSISTING: stateful-operator state recovers from the
        chunked snapshot plane (O(delta) per commit); input entries are
        never logged — a single post-step commit record (``commit/record``)
        carries the finalized time + offset frontier, and restart seeks
        rather than replays (replaying on top of restored operator state
        would double every record)."""
        cfg = self.persistence_config
        if cfg is None:
            return False
        from ..persistence import PersistenceMode

        return cfg.persistence_mode is PersistenceMode.OPERATOR_PERSISTING

    def _setup_persistence(self, t: int, step: bool = True) -> int:
        """Replay input snapshots, seek subjects, restore operator state
        (reference: Entry::{Snapshot,RewindFinishSentinel} replay,
        src/connectors/mod.rs:100-104; reader seek data_storage.rs:398;
        operator_snapshot.rs).  ``step=False`` leaves the replayed rows
        queued for the caller's own (barrier-synchronized) stepping."""
        storage = self._snapshot_storage()
        if storage is None:
            return t
        from ..persistence import (
            ChunkedOperatorSnapshot,
            InputSnapshotReader,
            InputSnapshotWriter,
        )

        self._op_snapshot = ChunkedOperatorSnapshot(storage)
        operator_mode = self._operator_mode
        commit_rec = None
        if operator_mode:
            self._check_operator_mode_coverage()
            raw = storage.get(self._commit_record_key())
            if raw is not None:
                import pickle as _pickle

                commit_rec = _pickle.loads(raw)
        pushed = False
        for subject, src in self.subject_src:
            # Opt-in contract (reference: persistent_id on connectors):
            # snapshotting a subject that cannot seek would replay its
            # snapshot AND let run() re-produce the same rows from scratch,
            # doubling every record — so gate on offset tracking or an
            # explicit persistent_id.
            if subject.persistent_id is None and not subject._tracks_offsets():
                continue
            pid = subject.effective_persistent_id(self._pid_occurrence.get(id(subject)))
            if pid is None:
                continue
            # multi-process runs share one backend storage: scope each
            # process's snapshot keyspace so shard-filtered batches don't
            # clobber each other's chunk counters (reference: worker-keyed
            # snapshots, src/persistence/input_snapshot.rs:56-283)
            if self.exchange_plane is not None:
                pid = f"{pid}-p{self.exchange_plane.me}"
            # this subject's commit() frontier now has a consumer (input
            # snapshot chunks or the per-tick commit record)
            subject._record_offsets = True
            if operator_mode:
                # offsets live in the per-tick commit record, written
                # AFTER the operator deltas are durable — entries are
                # never logged, so there is nothing to replay
                self._commit_subjects[id(subject)] = (pid, subject)
                if commit_rec is not None:
                    offsets = commit_rec["offsets"].get(pid)
                    if offsets is not None:
                        subject.seek(offsets)
                        # seed the drain frontier: the next commit record
                        # must carry this restored position forward, not
                        # clobber it with None before the subject's first
                        # own commit (a crash in that window would lose
                        # the frontier and double-apply the whole source)
                        subject._offsets_at_commit = offsets
                        subject._offsets_at_drain = offsets
                continue
            reader = InputSnapshotReader(storage, pid)
            replayed: list[Entry] = []
            for entries in reader.replay():
                replayed.extend(entries)
            if replayed:
                src.push(t, replayed)
                pushed = True
            offsets = reader.last_offsets()
            if offsets is not None:
                subject.seek(offsets)
            self._snapshot_writers[id(subject)] = InputSnapshotWriter(storage, pid)
        # restore stateful-operator snapshots before any replayed data flows
        from ..internals.engine import DeduplicateNode, GroupByNode, ZipNode

        committed_t = commit_rec["time"] if commit_rec is not None else 0
        restored_t = 0
        for node in self.engine.nodes:
            if (
                isinstance(node, (DeduplicateNode, GroupByNode, ZipNode))
                and node.persistent_id
            ):
                if isinstance(node, (GroupByNode, ZipNode)) and not operator_mode:
                    # groupby/zip state is rebuilt by input replay in
                    # PERSISTING mode; only OPERATOR_PERSISTING restores
                    # (and writes) it through the snapshot plane
                    continue
                # per-process keyspace, same as the input snapshots
                node.persistent_id = self._scoped_pid(node.persistent_id)
                # single scan: drops a crashed run's uncommitted tail (its
                # input offsets were never recorded, so the batch re-reads
                # and would double-apply on top of orphaned chunks) and
                # replays base+deltas in one pass over the store
                state, last_t = self._op_snapshot.restore(
                    node.persistent_id,
                    committed_time=committed_t if operator_mode else None,
                )
                if state is not None:
                    node.restore_snapshot(state)
                restored_t = max(restored_t, last_t)
                node._op_snapshot = self._op_snapshot
        if operator_mode:
            restored_t = max(
                restored_t, self._restore_index_nodes(committed_t)
            )
        if operator_mode and commit_rec is not None:
            self._op_snapshot.mark_committed(committed_t)
            t = max(t, committed_t + 1)
        # EVERY mode: resume engine time past the newest restored delta —
        # chunk replay orders deltas by finalized time, so a fresh run
        # re-using earlier times (engine times restart from 1) would make
        # a stale previous-run delta win on the next restore
        t = max(t, restored_t + 1)
        if pushed and step:
            self.engine.step(t)
            t += 1
        return t

    def _commit_record_key(self) -> str:
        if self.exchange_plane is not None:
            return f"commit/record-p{self.exchange_plane.me}"
        return "commit/record"

    def _scoped_pid(self, pid: str) -> str:
        """Per-process snapshot keyspace in multi-process runs: append
        ``-p{me}`` (idempotent) so shard-filtered state never clobbers
        another process's chunk counters (reference: worker-keyed
        snapshots, src/persistence/input_snapshot.rs:56-283)."""
        if self.exchange_plane is None:
            return pid
        suffix = f"-p{self.exchange_plane.me}"
        return pid if pid.endswith(suffix) else f"{pid}{suffix}"

    def _restore_index_nodes(self, committed_t: int) -> int:
        """Warm-restart the live vector index behind a health gate
        (OPERATOR_PERSISTING): stream each covered ``ExternalIndexNode``'s
        snapshot chunks back into HBM via one bulk upsert — zero encoder
        calls — while ``/v1/health`` reports ``index: restoring`` and the
        serving plane answers from the degraded lexical mirror instead of
        503ing.  Chunk reads retry through the seeded ``index.restore``
        fault site; a store that stays unreadable fails the run loudly
        (serving silently empty would look like data loss).  Returns the
        newest restored finalized time (the driver resumes engine time
        past it)."""
        from ..internals.errors import register_error
        from ..internals.flight_recorder import record_span
        from ..internals.health import get_health
        from ..stdlib.indexing.lowering import ExternalIndexNode

        health = get_health()
        newest = 0
        attempts = max(1, int(os.environ.get("PATHWAY_RESTORE_ATTEMPTS", "3")))
        for node in self.engine.nodes:
            if not isinstance(node, ExternalIndexNode) or not node.persistent_id:
                continue
            # per-process keyspace, same as the zip/groupby loop above
            # (defense-in-depth: OPERATOR_PERSISTING is refused in
            # multi-process runs today, but the keyspaces must not
            # collide the day that restriction lifts)
            node.persistent_id = self._scoped_pid(node.persistent_id)
            pid = node.persistent_id
            node._op_snapshot = self._op_snapshot
            comp = f"index:{pid}"
            progress = {"chunks": 0, "entries": 0}

            def on_chunk(key, n, ms, progress=progress, pid=pid):
                progress["chunks"] += 1
                progress["entries"] += n
                health.set_restore(
                    pid, state="restoring",
                    chunks_replayed=progress["chunks"],
                )
                record_span(
                    "restore:chunk", "restore", _time.time(), ms,
                    attrs={"key": key, "entries": n, "index": pid},
                )

            node._restore_state = "restoring"
            health.set_component(
                comp, "restoring", ready=True, degraded=True, critical=False,
                detail="streaming snapshot chunks into the index",
            )
            health.set_restore(
                pid, state="restoring", chunks_replayed=0, rows_restored=0,
            )
            wall = _time.time()
            t0 = _time.monotonic()
            state = None
            last_t = 0
            last_exc: BaseException | None = None
            for attempt in range(attempts):
                progress["chunks"] = progress["entries"] = 0
                try:
                    if faults.enabled:
                        faults.perturb("index.restore")
                    state, last_t = self._op_snapshot.restore(
                        pid, committed_time=committed_t, on_chunk=on_chunk
                    )
                    last_exc = None
                    break
                except Exception as exc:  # noqa: BLE001 — bounded retry
                    last_exc = exc
                    register_error(
                        f"index {pid!r} restore attempt {attempt + 1}/"
                        f"{attempts} failed: {type(exc).__name__}: {exc}",
                        kind="index",
                        operator=pid,
                    )
            if last_exc is not None:
                node._restore_state = None
                health.set_component(
                    comp, "restore_failed", ready=False, degraded=True,
                    detail=f"{type(last_exc).__name__}: {last_exc}",
                )
                health.set_restore(pid, state="failed")
                raise RuntimeError(
                    f"index {pid!r} could not restore its snapshot after "
                    f"{attempts} attempts — refusing to serve an empty "
                    "index over durable state (clear the store to rebuild "
                    f"from replay). Last error: "
                    f"{type(last_exc).__name__}: {last_exc}"
                ) from last_exc
            # routing spec first: the delta-chunk header carries the LSH
            # projector / partition-router specs, and the index must
            # route (and partition) the restored rows exactly as the
            # process that wrote them did
            header = self._op_snapshot.last_restored_header(pid)
            if header:
                node.apply_snapshot_header(header)
            if state:
                node.restore_snapshot(state)
            node._restore_state = None
            duration_ms = (_time.monotonic() - t0) * 1000.0
            health.set_component(
                comp, "ok", ready=True, degraded=False, critical=False,
            )
            health.set_restore(
                pid, state="ok",
                chunks_replayed=progress["chunks"],
                rows_restored=node.restored_rows,
                duration_ms=round(duration_ms, 3),
            )
            # a mesh-sharded index re-pins restored rows to its shards
            # through the placement-preserving scatter; surface the
            # resulting per-shard layout so a warm restart's balance is
            # observable next to its chunk/row counts
            inner = getattr(node.index, "index", None)
            if inner is not None and hasattr(inner, "shard_row_counts"):
                health.set_restore(
                    pid,
                    mesh_devices=int(inner.n_shards),
                    rows_per_shard=inner.shard_row_counts(),
                )
            record_span(
                f"restore:{pid}", "restore", wall, duration_ms,
                attrs={
                    "chunks": progress["chunks"],
                    "rows": node.restored_rows,
                    "index": pid,
                },
            )
            newest = max(newest, last_t)
        return newest

    def _check_operator_mode_coverage(self) -> None:
        """OPERATOR_PERSISTING replays no input entries, so every stateful
        node must recover from the snapshot plane — refuse the mode when
        the graph holds stateful nodes it does not cover, instead of
        silently restarting them empty."""
        from ..internals.engine import (
            AsyncMapNode,
            BufferNode,
            DeduplicateNode,
            GroupByNode,
            JoinNode,
            RowwiseNode,
            SemiJoinNode,
            UpdateCellsNode,
            UpdateRowsNode,
            ZipNode,
        )
        from ..stdlib.indexing.lowering import ExternalIndexNode, SortNode

        if self.exchange_plane is not None:
            raise RuntimeError(
                "PersistenceMode.OPERATOR_PERSISTING is not supported in "
                "multi-process runs yet — the pipelined exchange completes "
                "rounds out of band, so there is no single point to record "
                "the committed offset frontier. Use "
                "PersistenceMode.PERSISTING (input replay) instead."
            )
        # sources too: a subject that opts out of persistence re-produces
        # every row from scratch on restart — harmless under input replay
        # (the state is rebuilt from the same rows), but on top of RESTORED
        # operator state it double-applies everything
        unseekable = []
        for subject, _src in self.subject_src:
            if subject._ephemeral:
                # request-scoped sources (REST handlers): their rows are
                # in-flight HTTP requests, gone with the process — there
                # is nothing to restore and nothing to double-apply
                # (clients retry); they are exempt from seekability
                continue
            pid = subject.effective_persistent_id(
                self._pid_occurrence.get(id(subject))
            )
            # an explicit persistent_id does NOT make a source seekable —
            # without offset tracking there is no frontier to seek to, and
            # run() re-produces every row on top of RESTORED operator state
            if pid is None or not subject._tracks_offsets():
                unseekable.append(subject._datasource_name)
        if unseekable:
            raise RuntimeError(
                "PersistenceMode.OPERATOR_PERSISTING restores operator "
                "state without replaying inputs, so every source must be "
                "seekable; these are not: "
                f"{', '.join(sorted(unseekable))}. Give them a "
                "persistent_id (and offset tracking), or use "
                "PersistenceMode.PERSISTING."
            )
        uncovered = []
        for node in self.engine.nodes:
            if isinstance(node, (DeduplicateNode, GroupByNode, ZipNode)):
                if not node.persistent_id:
                    uncovered.append(f"{node.name} (no persistent_id)")
            elif isinstance(node, ExternalIndexNode):
                # asof_now index nodes are first-class recovery citizens:
                # their doc state (already-computed vectors + payloads)
                # checkpoints through the chunked snapshot plane and
                # restores via one bulk upsert.  live-mode nodes stay
                # refused — their refresh contract needs the live query
                # rows, which this mode never replays
                if node.mode != "asof_now" or not node.persistent_id:
                    uncovered.append(f"{node.name} (live-mode index)")
            elif isinstance(node, AsyncMapNode):
                # the only cross-step state is the retraction memo: with
                # every slot UDF deterministic, an empty memo recomputes
                # identical values — safe to restart uncovered
                if not getattr(node, "_slots_deterministic", False):
                    uncovered.append(
                        f"{node.name} (non-deterministic async map)"
                    )
            elif isinstance(
                node,
                # every node whose flush() folds input into cross-step
                # state: restarting it empty on top of restored downstream
                # state silently corrupts results (missing retractions,
                # empty indexes, unpaired non-deterministic recomputes)
                (JoinNode, BufferNode, UpdateRowsNode,
                 UpdateCellsNode, SemiJoinNode, SortNode),
            ):
                uncovered.append(node.name)
            elif isinstance(node, RowwiseNode) and node.memoize:
                # memoized maps exist precisely because the fn is
                # non-deterministic: an empty memo after restart would
                # recompute a different row for a retraction and unpair it
                uncovered.append(f"{node.name} (memoized non-deterministic map)")
        if uncovered:
            raise RuntimeError(
                "PersistenceMode.OPERATOR_PERSISTING cannot recover these "
                f"stateful operators: {', '.join(sorted(uncovered))}. Give "
                "groupby/deduplicate operators a persistent_id, or use "
                "PersistenceMode.PERSISTING (input replay covers every "
                "operator)."
            )

    def _write_commit_record(self, t: int) -> None:
        """Durably record the finalized time and every subject's offset
        frontier — AFTER the tick's operator deltas are on disk.  A crash
        before this write replays the batch against truncated chunks
        (exactly-once); writing offsets first instead would drop the
        batch entirely."""
        storage = self._snapshot_storage()
        if storage is None or not self._commit_subjects:
            return
        import pickle as _pickle

        offsets = {
            pid: subject._offsets_at_drain
            for pid, subject in self._commit_subjects.values()
        }
        storage.put(
            self._commit_record_key(),
            _pickle.dumps({"time": t, "offsets": offsets}),
        )
        self._op_snapshot.mark_committed(t)
        from ..internals.health import get_health

        get_health().note_commit()

    def run(self) -> None:
        from ..internals.health import get_health

        health = get_health()
        health.begin_run()
        health.set_component("engine", "running", ready=True)
        health.beat("engine")
        if self.exchange_plane is not None:
            self._run_distributed()
            return
        if not self.subject_src:
            self.engine.run_all()
            health.set_component("engine", "finished", ready=True)
            return
        data_event = threading.Event()
        # statically-fed sources (debug tables, static subjects) queued rows
        # at build time — drain those timestamps before going live, or a
        # mixed static+streaming graph would never process them
        static_times = sorted(
            {t for s in self.engine.sources for t in s.pending_times()}
        )
        for t0 in static_times:
            self.engine.step(t0)
        t = self._setup_persistence(max(static_times, default=0) + 1)
        threads = self._start_connector_threads(data_event)

        from ..internals.engine import gc_batch_mode

        last_autocommit = {id(s): _time.monotonic() for s, _ in self.subject_src}
        with gc_batch_mode():
            self._live_loop(data_event, t, last_autocommit)
        self._record_finished_connectors()
        self.engine.finish()
        from ..internals.health import get_health

        get_health().set_component("engine", "finished", ready=True)

    def _live_loop(self, data_event, t, last_autocommit) -> None:
        from ..internals.health import get_health

        health = get_health()
        loop_start = _time.monotonic()
        warned_stalled: set[int] = set()
        while True:
            data_event.wait(timeout=self.autocommit_ms / 1000.0)
            data_event.clear()
            # engine watchdog: a wedged loop stops beating and /v1/health
            # flips unready after health.engine_stall_s
            health.beat("engine")
            now = _time.monotonic()
            persisting = self._snapshot_storage() is not None
            for subject, _src in self.subject_src:
                ac = subject._autocommit_ms
                # under persistence, offset-tracking subjects commit on
                # their own reader thread at consistent boundaries (fs: end
                # of scan, kafka: per message); a driver-thread commit could
                # snapshot a mid-unit frontier that pairs rows already in
                # the batch with an offset that re-reads them on restart.
                # Without persistence no frontier is recorded, so driver
                # autocommit stays on (external ConnectorSubject subclasses
                # may override current_offsets yet rely on it)
                if persisting and subject._tracks_offsets():
                    # a tracking subject that NEVER self-commits would
                    # stall silently here — surface it once, loudly
                    if (
                        ac is not None
                        and subject._commit_count == 0
                        and id(subject) not in warned_stalled
                        and (now - loop_start) * 1000 >= 20 * max(ac, 1500)
                    ):
                        warned_stalled.add(id(subject))
                        import warnings

                        warnings.warn(
                            f"connector {subject._datasource_name!r} tracks "
                            "offsets but has not committed once: under "
                            "persistence the driver never autocommits "
                            "offset-tracking subjects (a driver-paced "
                            "frontier could re-read committed rows after "
                            "restart) — call self.commit() from the "
                            "connector at consistent source boundaries",
                            RuntimeWarning,
                            stacklevel=1,
                        )
                    continue
                if ac is not None and (now - last_autocommit[id(subject)]) * 1000 >= ac:
                    subject.commit()
                    last_autocommit[id(subject)] = now
            pushed = False
            for subject, src in self.subject_src:
                entries = subject._drain()
                if entries:
                    src.push(t, entries)
                    self._write_snapshot(subject, entries)
                    self._record_connector(subject, len(entries), t)
                    pushed = True
            # a finite source next to an unbounded one must report finished
            # while the run continues (reference: ConnectorMonitor finish)
            self._record_finished_connectors()
            if pushed:
                self._step(t)
                self._write_commit_record(t)
                t += 1
                continue
            if self.engine.has_async_ready() or (
                self.persistence_config is not None
                and self.engine.has_placement_flush_pending()
            ):
                # step once while sources are idle: a pipelined async
                # batch resolved (its results should emit now, not at
                # the next input), or a tiered index migrated under pure
                # query traffic (end_of_step must stage + persist the
                # new placement — waiting for input could be forever)
                self._step(t)
                self._write_commit_record(t)
                t += 1
                continue
            if all(s._closed.is_set() for s, _ in self.subject_src):
                # final drain to catch a close() racing the check
                for subject, src in self.subject_src:
                    entries = subject._drain()
                    if entries:
                        src.push(t, entries)
                        self._write_snapshot(subject, entries)
                        self._record_connector(subject, len(entries), t)
                        pushed = True
                if pushed:
                    self._step(t)
                    self._write_commit_record(t)
                    t += 1
                break

    def _step(self, t: int) -> None:
        """``engine.step(t)`` as a span on the profiler's clock (the ring
        keeps the batch's segments instead), under the link of the batch of
        connector rows it carries, so that its operators' flushes file
        under the batch's trace id."""
        from ..internals.flight_recorder import (
            batch_link_scope, batch_trace_id, span,
        )
        from ..internals.monitoring import get_freshness

        scope = id(self.engine)
        with span("engine.step", "engine", record=False, t=t) as timed:
            traced = get_freshness().note_step(t, timed.start_s, scope=scope)
            with batch_link_scope(
                (batch_trace_id(scope, t), None) if traced else None
            ):
                self.engine.step(t)

    def _write_snapshot(self, subject: ConnectorSubject, entries: list[Entry]) -> None:
        # OPERATOR_PERSISTING never registers writers: its offsets are
        # recorded post-step by _write_commit_record, and entries are
        # never logged (operator deltas carry the state)
        writer = self._snapshot_writers.get(id(subject))
        if writer is not None:
            # the drain-time frontier, not current_offsets(): the reader
            # may already have committed entries this batch doesn't hold
            writer.write_batch(entries, subject._offsets_at_drain)

    # -- per-connector progress (reference: connectors/monitoring.rs) --
    def _connector_label(self, subject: ConnectorSubject) -> str:
        idx = self._pid_occurrence.get(id(subject), 0)
        return f"{subject._datasource_name}-{idx}"

    def _record_connector(
        self, subject: ConnectorSubject, n: int, t: int | None = None
    ) -> None:
        label = self._connector_label(subject)
        monitor = getattr(self.engine, "monitor", None)
        if monitor is not None:
            monitor.record_connector_commit(label, n)
        from ..internals.monitoring import get_freshness

        now = _time.time()
        if t is not None:
            # freshness watermark: these rows entered at `now` under engine
            # timestamp `t`; when an index node applies timestamp `t` the
            # ingest->queryable lag becomes observable
            # (pathway_index_freshness_seconds).  Scoped by engine id —
            # timestamps restart per engine
            get_freshness().note_ingest(t, now, scope=id(self.engine))
            # end-to-end variant: the earliest CONNECTOR READ time of the
            # drained batches — closes as
            # pathway_freshness_seconds{connector=} when the index
            # applies timestamp t (read→parse→split→embed→upsert→commit)
            read_wall = getattr(subject, "_read_wall_at_drain", None)
            if read_wall is not None:
                # ...and the batch of them is traced from here to the
                # index: the commit that carried the read and the rows
                # drained open its ingest.commit_to_step segment
                fresh = get_freshness()
                fresh.note_source(label, t, read_wall, scope=id(self.engine))
                fresh.note_commit(
                    label, t, subject._commit_wall_at_drain, n,
                    scope=id(self.engine),
                )
            # fleet watermark hook: the subject learns the engine
            # timestamp its drained rows ride under, so the member can
            # flip the matching ingest watermark to QUERYABLE when an
            # index applies t (fleet/member.py)
            on_drained = getattr(subject, "_on_drained", None)
            if on_drained is not None:
                try:
                    on_drained(t, id(self.engine))
                except Exception:  # noqa: BLE001 — hooks must not stall the drain
                    pass

    def _record_finished_connectors(self) -> None:
        monitor = getattr(self.engine, "monitor", None)
        if monitor is not None:
            for subject, _src in self.subject_src:
                if subject._closed.is_set():
                    monitor.record_connector_finished(self._connector_label(subject))

    def _start_connector_threads(self, data_event=None) -> list:
        from ..internals.flight_recorder import name_thread

        threads = []
        for n, (subject, _src) in enumerate(self.subject_src):
            if data_event is not None:
                subject._data_event = data_event
            subject._metrics_label = self._connector_label(subject)
            supervisor = ConnectorSupervisor(subject, subject._metrics_label)
            self.supervisors[id(subject)] = supervisor

            def runner(s=subject, sup=supervisor, name=f"pw-conn-{n}"):
                name_thread(name)
                try:
                    sup.run()
                finally:
                    s.close()
                    s.on_stop()

            th = threading.Thread(target=runner, daemon=True, name=f"pw-conn-{n}")
            th.start()
            threads.append(th)
        return threads

    # -- multi-process run loop (reference: timely Cluster workers stepping
    # in lockstep; dataflow/config.rs:71-120 + worker-architecture doc) --
    def _run_distributed(self) -> None:
        from ..internals.engine import gc_batch_mode

        with gc_batch_mode():
            self._run_distributed_inner()

    def _run_distributed_inner(self) -> None:
        from ..internals.exchange import owner_of

        plane = self.exchange_plane

        # statically-fed sources (debug rows, static subjects): keep only
        # this process's shard of keys when every process sees identical
        # data, and lift time-0 rows to round 1 (rounds start at 1); later
        # explicit __time__ stamps align with their round number natively
        for src, op in self.runner.source_nodes:
            subject = op.params.get("subject")
            is_static = subject is None or getattr(subject, "_mode", None) == "static"
            if not is_static:
                continue
            if subject is None or subject._shared_source:
                for t0, entries in list(src.queue.items()):
                    src.queue[t0] = [
                        e for e in entries if owner_of(e[0], plane.n) == plane.me
                    ]
            if 0 in src.queue:
                src.queue[1] = src.queue.pop(0) + src.queue.get(1, [])
        # rounds may not stop before the last statically-stamped timestamp
        # (identical on every process, so the bound is symmetric)
        max_static = max(
            (x for s in self.engine.sources for x in s.pending_times()),
            default=0,
        )
        # snapshot replay + seek must complete before connector threads run
        # (seek after a source began scanning would double records; and the
        # startup current_offsets() probe may not race the reader thread)
        self._setup_persistence(1, step=False)
        threads = self._start_connector_threads()

        # asynchronous progress: stage 1 of a round (drain sources,
        # flush the ingest-safe subgraph, partition + SEND first-hop
        # exchange batches and the control flag) may run up to W rounds
        # ahead of the oldest unfinished round, so a straggler's slow
        # rounds overlap the fast workers' later ingest instead of
        # serializing the whole cluster per round (the role timely's
        # frontier-based progress tracking plays in the reference);
        # stage 2 (receive + stateful flush) completes rounds in order.
        from ..internals.exchange import ingest_safe_nodes, wavefront_requirements

        safe_ids, first_hop = ingest_safe_nodes(self.engine)
        safe_frozen = frozenset(safe_ids)
        ex_list, req_start, reqs, ups = wavefront_requirements(
            self.engine, safe_ids
        )
        # the lookahead window counts DATA-CARRYING rounds (real memory);
        # empty ticks are nearly free (a few control frames) and get a
        # separate, much larger cap — otherwise at a 20 ms tick the
        # window fills with empty rounds in a fraction of a second and
        # later batches have no in-flight round to land in
        lookahead = max(
            1, int(os.environ.get("PATHWAY_EXCHANGE_LOOKAHEAD", "4"))
        )
        max_rounds = max(
            lookahead,
            int(os.environ.get("PATHWAY_EXCHANGE_MAX_ROUNDS", "512")),
        )
        if plane.n == 1 or (not first_hop and not reqs):
            # no peers to straggle / nothing can overlap — lookahead
            # would only add dead output latency
            lookahead = 1
            max_rounds = 1

        from collections import deque

        inflight: deque[tuple[int, bool, bool]] = deque()  # (t, done, has_data)
        t_next = 1

        def ingest_round() -> None:
            # pacing is the CALLER's job (the wavefront loop ticks this on
            # the autocommit cadence instead of sleeping here, so a
            # lookahead window never serializes W sleeps ahead of stage 2)
            nonlocal t_next
            t = t_next
            had_data = False
            persisting = self._snapshot_storage() is not None
            for subject, _src in self.subject_src:
                # under persistence, tracking subjects self-commit at
                # consistent boundaries (see _live_loop) — a driver commit
                # could pair a batch with a mid-unit offset frontier
                if subject._autocommit_ms is not None and not (
                    persisting and subject._tracks_offsets()
                ):
                    subject.commit()
            # read the closed flags BEFORE draining: close() commits its
            # final rows first, so a True flag means this round's drain
            # saw everything
            local_closed = all(
                s._closed.is_set() for s, _ in self.subject_src
            ) if self.subject_src else True
            for subject, src in self.subject_src:
                entries = subject._drain()
                if subject._shared_source:
                    entries = [
                        e for e in entries
                        if owner_of(e[0], plane.n) == plane.me
                    ]
                if entries:
                    src.push(t, entries)
                    self._write_snapshot(subject, entries)
                    self._record_connector(subject, len(entries), t)
                    had_data = True
            done = local_closed and t >= max_static
            # the control flag rides ahead with the data plane; every
            # process still sees the same flag set for round t
            plane.send(
                "__ctl__", t,
                {p: [done] for p in range(plane.n) if p != plane.me},
                is_entries=False,
            )
            # static rows queued directly on sources also make a round
            # data-carrying (flow control must bound their memory too)
            had_data = had_data or any(
                src.has_pending(t) for src in self.engine.sources
            )
            self.engine.step_ingest(t, safe_ids, first_hop)
            with inflight_lock:
                inflight.append((t, done, had_data))
            t_next += 1

        # --- cross-round wavefront (VERDICT r3 #4) -------------------
        # Each inflight round owns a resumable engine.step_iter generator
        # that yields at every exchange flush.  Rounds advance oldest
        # first; round t+1 may start (or resume past yield k) only once
        # round t has passed req_start (reqs[k]) exchanges — the static
        # guards from wavefront_requirements that keep every node's
        # timestamp order intact.  At each yield the exchange's batches
        # are SENT immediately, so a downstream exchange ships round
        # t+1's data while an upstream straggler still completes t —
        # previously chained exchanges (groupby→join) fell back to
        # lockstep here.

        _INF = float("inf")

        class _Round:
            __slots__ = ("t", "gen", "started", "waiting", "passed",
                         "finished", "blocked_since")

            def __init__(self, t, gen):
                self.t = t
                self.gen = gen
                self.started = False
                self.waiting = None  # exchange node at the current yield
                self.passed = 0
                self.finished = False
                self.blocked_since = None

        def _resume(r: "_Round") -> None:
            try:
                node = r.gen.send(None)
            except StopIteration:
                r.finished = True
                r.waiting = None
                return
            r.waiting = node
            # send NOW: input for this round is settled (the generator
            # only yields after quiescence); receivers buffer by time
            node.prepare(r.t)
            # eager prepare: any LATER exchange whose whole upstream has
            # already been passed can no longer receive round-r input —
            # snapshot and SEND its batch immediately, so peers stop
            # waiting on it even though this round's own yield is still
            # several hops away (e.g. the sums-side join input while the
            # counts side stalls)
            for k2 in range(r.passed + 1, len(ex_list)):
                if ups[k2] <= r.passed and not ex_list[k2].broadcast:
                    ex_list[k2].prepare(r.t)

        rounds: deque[_Round] = deque()
        # peers' done flags, consumed eagerly so the wavefront can know
        # the FINAL round before running past it: rounds after the
        # globally-done round must never start, or processes would finish
        # at different frontiers and desync the finish()-time exchange
        ctl_cache: dict[int, list] = {}

        def _ctl_ready(t: int) -> bool:
            if t in ctl_cache:
                return True
            if plane.poll("__ctl__", t):
                ctl_cache[t] = plane.recv("__ctl__", t)
                return True
            return False

        def _globally_done(i: int) -> bool:
            t, done_local, _data = inflight[i]
            return done_local and t in ctl_cache and all(ctl_cache[t])

        def _try_advance(i: int) -> bool:
            r = rounds[i]
            prev = rounds[i - 1] if i > 0 else None

            def prev_ok(need) -> bool:
                if prev is None or prev.finished:
                    return True
                need_prepared, need_passed = need
                if need_prepared == _INF or need_passed == _INF:
                    return False  # requires prev to fully finish
                if prev.passed < need_passed:
                    return False
                # prepared-or-flushed, queried per exchange: eager
                # prepares (in _resume) may run far ahead of prev's yield
                for k2 in range(int(need_prepared)):
                    e = ex_list[k2]
                    if prev.t not in e._prepared and e.has_pending(prev.t):
                        return False
                return True

            prog = False
            while not r.finished:
                if not r.started:
                    if prev is not None and (
                        not _ctl_ready(prev.t) or _globally_done(i - 1)
                    ):
                        # don't run past the last real round: every
                        # process must stop at the same frontier
                        break
                    if not prev_ok(req_start):
                        break
                    r.started = True
                    _resume(r)
                elif r.waiting is not None:
                    k = r.passed
                    ready = prev_ok(reqs[k]) and plane.poll(
                        r.waiting.channel, r.t
                    )
                    if not ready:
                        if r.blocked_since is None:
                            r.blocked_since = _time.monotonic()
                        elif (
                            i == 0
                            and _time.monotonic() - r.blocked_since
                            > plane.barrier_timeout
                        ):
                            # hung-but-connected peer: force the flush so
                            # recv raises its descriptive TimeoutError
                            # instead of parking forever
                            r.blocked_since = None
                            r.passed += 1
                            _resume(r)
                            prog = True
                            continue
                        break
                    r.blocked_since = None
                    r.passed += 1
                    _resume(r)
                else:  # pragma: no cover — finished handled by loop guard
                    break
                prog = True
            return prog

        # --- stage-1 ingest thread ----------------------------------
        # A slow operator (long UDF) blocks the engine thread mid-round;
        # if ingest ran on the same thread, this process would also stop
        # shipping ctl flags + first-hop batches for LATER rounds, and
        # every peer's wavefront would stall on us (the reference keeps
        # connector/commit machinery off the worker threads for the same
        # reason, src/connectors/mod.rs reader threads + commit ticks).
        # The ingest thread owns: subjects, source queue pushes, the
        # ingest-safe subgraph (step_ingest), first-hop prepares and ctl
        # sends.  The engine thread never touches those (step_iter skips
        # safe_ids), so the two domains are disjoint; `inflight` hands
        # rounds over under a lock.
        autocommit_s = self.autocommit_ms / 1000.0
        inflight_lock = threading.Lock()
        stop_ingest = threading.Event()
        ingest_error: list[BaseException] = []

        from ..internals.flight_recorder import name_thread
        from ..internals.health import get_health

        health = get_health()
        health.set_component("ingest_thread", "running", ready=True)

        def ingest_loop() -> None:
            name_thread("pw-ingest")
            try:
                while not stop_ingest.is_set():
                    health.beat("ingest_thread")
                    with inflight_lock:
                        data_inflight = sum(1 for e in inflight if e[2])
                        total = len(inflight)
                    if data_inflight >= lookahead or total >= max_rounds:
                        _time.sleep(0.005)
                        continue
                    _time.sleep(autocommit_s)
                    if stop_ingest.is_set():
                        return
                    ingest_round()
            except BaseException as exc:  # noqa: BLE001 — surfaced by main
                ingest_error.append(exc)
                health.set_component(
                    "ingest_thread", "dead", ready=False,
                    detail=f"{type(exc).__name__}: {exc}",
                )

        ingest_thread = threading.Thread(
            target=ingest_loop, daemon=True, name="pw-ingest"
        )
        ingest_thread.start()
        try:
            while True:
                health.beat("engine")
                if ingest_error:
                    raise ingest_error[0]
                with inflight_lock:
                    n_inflight = len(inflight)
                    new_rounds = [
                        inflight[i][0] for i in range(len(rounds), n_inflight)
                    ]
                for t_new in new_rounds:
                    rounds.append(
                        _Round(
                            t_new,
                            self.engine.step_iter(t_new, skip_ids=safe_frozen),
                        )
                    )
                if not rounds:
                    plane.wait_any(0.02)
                    continue
                progressed = False
                for i in range(len(rounds)):
                    if _try_advance(i):
                        progressed = True
                if rounds and rounds[0].finished:
                    rounds.popleft()
                    with inflight_lock:
                        t, done, _data = inflight.popleft()
                    while not _ctl_ready(t):
                        plane.wait_any(0.05)
                    peer_flags = ctl_cache.pop(t)
                    if done and all(f for f in peer_flags):
                        break
                    continue
                if not progressed:
                    # every round is blocked on peer data — park until
                    # inbox activity (bounded so liveness checks re-run)
                    plane.wait_any(0.05)
        finally:
            stop_ingest.set()
            ingest_thread.join(timeout=10)
            if ingest_thread.is_alive():
                # a stuck reader (hung socket, wedged commit) leaks a live
                # daemon thread that keeps draining subjects after "exit":
                # say so loudly and pin it on /v1/health instead of
                # silently returning
                from ..internals.errors import register_error

                detail = (
                    "ingest thread failed to stop within 10s — leaked a "
                    "live thread still draining connector subjects"
                )
                logger.error("%s", detail)
                register_error(detail, kind="connector", operator="ingest_thread")
                health.set_component(
                    "ingest_thread", "leaked", ready=False, detail=detail
                )
            else:
                health.set_component("ingest_thread", "stopped", ready=True)
        self._record_finished_connectors()
        self.engine.finish()
        plane.close()
