"""Operator stats, the monitoring dashboard, and the OpenMetrics endpoint.

reference: python/pathway/internals/monitoring.py:165 (``StatsMonitor``
rich TUI), src/engine/http_server.rs:21-83 (Prometheus/OpenMetrics HTTP
server on ``127.0.0.1:(20000+process_id)/status``), src/engine/
progress_reporter.rs + ``ProberStats`` (graph.rs:533).

The engine calls :meth:`StatsMonitor.record_flush` per node per
micro-batch; the HTTP thread renders the same counters as OpenMetrics
gauges (input/output latency + per-node rows processed), and the rich
table view mirrors the reference's live dashboard.  Per-operator flush
latencies render as fixed-bucket histograms (``pathway_operator_flush_ms``)
— averages hide exactly the tail behavior the serving scheduler exists to
fix.  The endpoint also exposes the freshness watermarks
(:class:`FreshnessTracker`) and the tracing/compile series pulled from
``internals/flight_recorder.py``.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import defaultdict, deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any

from .flight_recorder import batch_trace_id
from .metrics_names import Histogram, escape_label_value

__all__ = [
    "StatsMonitor",
    "start_http_server_thread",
    "MonitoringLevel",
    "register_metrics_provider",
    "register_metrics_provider_once",
    "exposition",
    "FreshnessTracker",
    "get_freshness",
    "record_connector_scan",
    "connector_scans",
    "record_connector_files",
    "connector_files",
]


#: pluggable metric sources (e.g. the serving scheduler,
#: xpacks/llm/_scheduler.py) — weakly held so a test-local provider
#: disappears with its owner.  A provider exposes ``stats() -> dict`` and
#: ``openmetrics_lines() -> list[str]``.
_metrics_providers: "weakref.WeakValueDictionary[str, Any]" = (
    weakref.WeakValueDictionary()
)


def register_metrics_provider(
    name: str, provider: Any, replace: bool = True
) -> None:
    """Surface an external component's counters on every
    :class:`StatsMonitor` snapshot and the OpenMetrics endpoint.

    ``replace=False`` keeps an existing LIVE registration: because the
    table is weak-valued, a transient object replacing an established
    provider's entry would DELETE the name when it is collected — the
    established provider's series would silently vanish from /status.
    Authoritative owners (e.g. the process-global runtime) register with
    the default ``replace=True``."""
    if not replace and _metrics_providers.get(name) is not None:
        return
    _metrics_providers[name] = provider


#: strong refs for providers registered via the once-helper (the table
#: above is weak-valued, so an unheld provider would vanish before its
#: first scrape)
_strong_providers: dict[str, Any] = {}
_strong_providers_lock = threading.Lock()


def register_metrics_provider_once(name: str, factory: Any) -> Any:
    """Idempotent, strong-ref provider registration — the shared form of
    the ``_provider`` / ``_provider_lock`` / ``_ensure_provider``
    boilerplate every metrics-emitting module used to copy.  ``factory``
    is called once, the instance is held strongly here for the process
    lifetime (exactly what the per-module globals did), and repeated
    calls return the existing instance."""
    with _strong_providers_lock:
        provider = _strong_providers.get(name)
        if provider is None:
            provider = _strong_providers[name] = factory()
            register_metrics_provider(name, provider)
        return provider


#: process-wide monitor backing :func:`exposition` — serving processes
#: that never built an engine-owned StatsMonitor (fleet replicas behind a
#: PathwayWebserver) still need a /status exposition surface for the
#: router's federation scrape.
_exposition_monitor: "StatsMonitor | None" = None
_exposition_monitor_lock = threading.Lock()


def exposition() -> str:
    """Render the process's OpenMetrics exposition.

    Every interesting series (registered providers, freshness, tracing)
    lives in module-global registries, not on a particular
    :class:`StatsMonitor` — so a lazily-created module monitor renders
    the full picture even when no engine run owns one."""
    global _exposition_monitor
    with _exposition_monitor_lock:
        if _exposition_monitor is None:
            _exposition_monitor = StatsMonitor()
        monitor = _exposition_monitor
    return monitor.openmetrics()


#: polls of a watched path per (connector label, lister): process-wide like
#: the freshness tracker, because the scanning thread holds no monitor
_connector_scans: dict[tuple[str, str], int] = defaultdict(int)
_connector_scans_lock = threading.Lock()


def record_connector_scan(connector: str, lister: str) -> None:
    """One poll by ``pw.io.fs``; ``lister`` is ``"native"`` or ``"python"``
    (the fallback, which a deployment should never see in steady state)."""
    with _connector_scans_lock:
        _connector_scans[(connector, lister)] += 1


def connector_scans() -> dict[tuple[str, str], int]:
    with _connector_scans_lock:
        return dict(_connector_scans)


#: files emitted per (connector label, the pass of a poll that found them)
_connector_files: dict[tuple[str, str], int] = defaultdict(int)


def record_connector_files(connector: str, found: str, n: int) -> None:
    """``n`` files read and emitted by one pass of a ``pw.io.fs`` poll;
    ``found`` is ``"listing"`` (pass 1: a name the connector did not know)
    or ``"verify"`` (pass 2: a known file whose mtime or size changed)."""
    with _connector_scans_lock:
        _connector_files[(connector, found)] += n


def connector_files() -> dict[tuple[str, str], int]:
    with _connector_scans_lock:
        return dict(_connector_files)


#: flush-latency histogram bucket upper bounds (milliseconds)
_FLUSH_BUCKETS_MS = (
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
    1000.0,
)


class StatsMonitor:
    """Per-node counters: rows, flush latency, last activity."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rows: dict[str, int] = defaultdict(int)
        self.flushes: dict[str, int] = defaultdict(int)
        self.busy_s: dict[str, float] = defaultdict(float)
        self.flush_ms: dict[str, Histogram] = {}
        self.last_time: dict[str, float] = {}
        self.current_timestamp: int = -1
        self.started_at = time.time()
        # per-connector progress (reference: connectors/monitoring.rs
        # ConnectorStats — messages from start / last minute / recently
        # committed / finished flag).  The sliding window is a deque:
        # pruning pops from the LEFT, which list.pop(0) made O(n) per
        # commit on a chatty connector.
        self.connector_total: dict[str, int] = defaultdict(int)
        self.connector_recent: dict[str, deque] = defaultdict(deque)
        self.connector_last_commit: dict[str, int] = defaultdict(int)
        self.connector_finished: dict[str, bool] = {}

    def record_flush(self, node_name: str, n_rows: int, elapsed_s: float) -> None:
        with self._lock:
            self.rows[node_name] += n_rows
            self.flushes[node_name] += 1
            self.busy_s[node_name] += elapsed_s
            hist = self.flush_ms.get(node_name)
            if hist is None:
                hist = self.flush_ms[node_name] = Histogram(_FLUSH_BUCKETS_MS)
            hist.observe(elapsed_s * 1000.0)
            self.last_time[node_name] = time.time()

    def record_step(self, timestamp: int) -> None:
        with self._lock:
            self.current_timestamp = timestamp

    def record_connector_commit(self, name: str, n_messages: int) -> None:
        """One committed micro-batch of ``n_messages`` from connector
        ``name`` (reference: ConnectorMonitor::increment + on_commit)."""
        now = time.time()
        with self._lock:
            self.connector_total[name] += n_messages
            recent = self.connector_recent[name]
            recent.append((now, n_messages))
            cutoff = now - 60.0
            while recent and recent[0][0] < cutoff:
                recent.popleft()
            self.connector_last_commit[name] = n_messages
            self.connector_finished.setdefault(name, False)

    def record_connector_finished(self, name: str) -> None:
        with self._lock:
            self.connector_finished[name] = True

    def _connector_stats_locked(self, name: str, now: float) -> dict[str, Any]:
        """reference: ConnectorStats fields.  Caller holds the lock."""
        recent = [
            n for t, n in self.connector_recent.get(name, ()) if t >= now - 60.0
        ]
        return {
            "num_messages_from_start": self.connector_total.get(name, 0),
            "num_messages_in_last_minute": sum(recent),
            "num_messages_recently_committed": self.connector_last_commit.get(
                name, 0
            ),
            "finished": self.connector_finished.get(name, False),
        }

    def connector_stats(self, name: str) -> dict[str, Any]:
        with self._lock:
            return self._connector_stats_locked(name, time.time())

    def snapshot(self) -> dict[str, Any]:
        now = time.time()
        with self._lock:
            # union: a source that finished without ever committing a
            # message must still appear (finished=True, zero counts)
            names = set(self.connector_total) | set(self.connector_finished)
            connectors = {
                name: self._connector_stats_locked(name, now) for name in names
            }
            snap = {
                "uptime_s": time.time() - self.started_at,
                "timestamp": self.current_timestamp,
                "nodes": {
                    name: {
                        "rows": self.rows[name],
                        "flushes": self.flushes[name],
                        "busy_s": round(self.busy_s[name], 6),
                    }
                    for name in self.rows
                },
                "connectors": connectors,
            }
        providers = {}
        for name, provider in list(_metrics_providers.items()):
            try:
                providers[name] = provider.stats()
            except Exception:  # noqa: BLE001 — a dying provider must not kill /status
                pass
        if providers:
            snap["providers"] = providers
        freshness = get_freshness().stats()
        if freshness:
            snap["freshness"] = freshness
        return snap

    # -- OpenMetrics rendering (reference: http_server.rs:25
    # ``metrics_from_stats``) --
    def openmetrics(self) -> str:
        snap = self.snapshot()
        lines = [
            "# TYPE pathway_uptime_seconds gauge",
            f"pathway_uptime_seconds {snap['uptime_s']:.3f}",
            "# TYPE pathway_current_timestamp gauge",
            f"pathway_current_timestamp {snap['timestamp']}",
            "# TYPE pathway_operator_rows_total counter",
        ]
        for name, st in snap["nodes"].items():
            safe = escape_label_value(name)
            lines.append(
                f'pathway_operator_rows_total{{operator="{safe}"}} {st["rows"]}'
            )
        lines.append("# TYPE pathway_operator_busy_seconds counter")
        for name, st in snap["nodes"].items():
            safe = escape_label_value(name)
            lines.append(
                f'pathway_operator_busy_seconds{{operator="{safe}"}} {st["busy_s"]}'
            )
        with self._lock:
            flush_hists = list(self.flush_ms.items())
        if flush_hists:
            lines.append("# TYPE pathway_operator_flush_ms histogram")
            for name, hist in flush_hists:
                with self._lock:
                    rendered = hist.openmetrics_lines(
                        "pathway_operator_flush_ms",
                        f'operator="{escape_label_value(name)}"',
                    )
                lines.extend(rendered)
        lines.append("# TYPE pathway_connector_messages_total counter")
        for name, st in snap.get("connectors", {}).items():
            safe = escape_label_value(name)
            lines.append(
                f'pathway_connector_messages_total{{connector="{safe}"}} '
                f'{st["num_messages_from_start"]}'
            )
        lines.append("# TYPE pathway_connector_finished gauge")
        for name, st in snap.get("connectors", {}).items():
            safe = escape_label_value(name)
            lines.append(
                f'pathway_connector_finished{{connector="{safe}"}} '
                f'{1 if st["finished"] else 0}'
            )
        for family, second, counts in (
            ("pathway_connector_scans_total", "lister", connector_scans()),
            ("pathway_connector_files_total", "found", connector_files()),
        ):
            if counts:
                lines.append(f"# TYPE {family} counter")
            for (name, value), n in sorted(counts.items()):
                lines.append(
                    f'{family}{{connector="{escape_label_value(name)}",'
                    f'{second}="{value}"}} {n}'
                )
        for _name, provider in list(_metrics_providers.items()):
            try:
                lines.extend(provider.openmetrics_lines())
            except Exception:  # noqa: BLE001 — a dying provider must not kill /status
                pass
        lines.extend(get_freshness().openmetrics_lines())
        # tracing stage histograms + XLA compile counters + recorder stats
        # (lazy import: flight_recorder must stay import-light, and
        # monitoring is the one that renders)
        from .flight_recorder import observability_metrics_lines

        lines.extend(observability_metrics_lines())
        lines.append("# EOF")
        return "\n".join(lines) + "\n"

    # -- rich dashboard (reference: monitoring.py:165 StatsMonitor TUI) --
    def render_table(self):
        from rich.table import Table as RichTable

        snap = self.snapshot()
        table = RichTable(title=f"pathway_tpu — t={snap['timestamp']}")
        table.add_column("operator")
        table.add_column("rows", justify="right")
        table.add_column("flushes", justify="right")
        table.add_column("busy (s)", justify="right")
        for name, st in sorted(snap["nodes"].items()):
            table.add_row(
                name, str(st["rows"]), str(st["flushes"]), f"{st['busy_s']:.3f}"
            )
        return table


# ---------------------------------------------------------------------------
# data-freshness watermarks (ingest -> queryable lag per index)
# ---------------------------------------------------------------------------


#: the seven segments of a batch's way from the connector's read to the
#: index, each from one milestone of :class:`_Batch` to the next; observed
#: once per indexed timestamp and connector, so their means add up to
#: ``ingest.read_to_indexed``'s (PERF.md 3)
INGEST_SEGMENTS = (
    "ingest.read_to_commit",
    "ingest.commit_to_step",
    "ingest.step_to_index",
    "ingest.index_to_tick",
    "ingest.tick",
    "ingest.tick_to_embedded",
    "ingest.embedded_to_indexed",
)


class _Batch:
    """One engine timestamp's milestones, on the wall clock (``time.time()``,
    the span primitive's ``start_s``): per connector its earliest read, the
    commit that carried that read and the rows drained; then the driver's
    ``engine.step`` begins, the index node's ``index.doc_data`` begins, the
    first tick carrying its embed calls begins, the last one ends, and
    ``index.doc_data`` ends.  ``note_indexed`` is the eighth."""

    __slots__ = ("sources", "step", "index", "tick_start", "tick_end", "embedded")

    def __init__(self) -> None:
        #: connector label -> [read wall, commit wall, messages]
        self.sources: dict[str, list] = {}
        self.step: float | None = None
        self.index: float | None = None
        self.tick_start: float | None = None
        self.tick_end: float | None = None
        self.embedded: float | None = None

    def milestones(self, read: float, commit: float | None, now: float) -> list:
        """The eight milestones of one connector's rows.  A milestone not
        stamped takes the next one's time (a timestamp whose rows rode no
        tick reads 0 in ``ingest.tick`` and ``ingest.tick_to_embedded``),
        and none lies after the next, so the segments add up to
        ``now - read`` exactly."""
        ms = [read, commit, self.step, self.index, self.tick_start,
              self.tick_end, self.embedded, now]
        for i in range(6, 0, -1):
            ms[i] = ms[i + 1] if ms[i] is None else min(ms[i], ms[i + 1])
        return ms


class FreshnessTracker:
    """High-watermark plumbing for ``pathway_index_freshness_seconds``.

    The streaming driver stamps wall-clock ingest time per engine
    timestamp as it pushes connector batches (:meth:`note_ingest`); when
    ``ExternalIndexNode.flush`` applies the index updates of that
    timestamp the rows become queryable and :meth:`note_indexed` turns
    the pair into an observed ingest->queryable lag, per index.  The
    timestamp map is bounded — an engine stamping faster than indexes
    drain simply ages out the oldest entries (their lag would have been
    reported by a later timestamp anyway).

    ``scope`` disambiguates engines: timestamps are small per-engine
    integers, so without it a long-lived process running several engines
    (threaded servers, test suites) would join engine B's ``t=5`` apply
    against engine A's hours-old ``t=5`` stamp and report phantom lag.
    Both sides pass ``id(engine)``.

    A timestamp with connector read stamps is a traced batch
    (:class:`_Batch`): the driver, the index node and the tick runtime stamp
    its milestones, and :meth:`note_indexed` observes the seven
    :data:`INGEST_SEGMENTS` between them and files them in the ring under
    ``flight_recorder.batch_trace_id(scope, t)``.
    """

    MAX_PENDING = 4096

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ingest_wall: dict[tuple[int, int], float] = {}
        self._ingest_order: deque[tuple[int, int]] = deque()
        #: index name -> (last observed lag seconds, observed wall time)
        self._lag: dict[str, tuple[float, float]] = {}
        #: batch trace id (``flight_recorder.batch_trace_id(scope,
        #: engine_time)``) -> its milestones, keyed by the earliest READ
        #: wall per connector — the end-to-end half: connectors stamp
        #: when the row was READ from the source (io/streaming.py
        #: ``_push``), not when the driver pushed the batch, so the
        #: freshness SLO covers parse→split→embed→upsert→commit including
        #: connector-side batching delay
        self._batches: dict[str, _Batch] = {}
        self._source_order: deque[str] = deque()
        #: connector label -> (end-to-end lag seconds, observed wall)
        self._source_lag: dict[str, tuple[float, float]] = {}
        #: ``fn(index_name, engine_time, scope)`` callbacks fired on
        #: every index apply — the fleet member advances its queryable
        #: watermark here (a router-fanned write is answerable on this
        #: replica exactly when the timestamp that carried it indexes)
        self._indexed_listeners: list = []

    def add_indexed_listener(self, fn) -> None:
        """Register an index-apply callback (idempotent by identity);
        called OUTSIDE the tracker lock, exceptions swallowed."""
        with self._lock:
            if fn not in self._indexed_listeners:
                self._indexed_listeners.append(fn)

    def note_ingest(
        self, engine_time: int, wall_time: float | None = None, scope: int = 0
    ) -> None:
        if wall_time is None:
            wall_time = time.time()
        key = (scope, engine_time)
        with self._lock:
            if key in self._ingest_wall:
                return  # first stamp wins: earliest ingest is the watermark
            self._ingest_wall[key] = wall_time
            self._ingest_order.append(key)
            while len(self._ingest_order) > self.MAX_PENDING:
                self._ingest_wall.pop(self._ingest_order.popleft(), None)

    def note_source(
        self,
        connector: str,
        engine_time: int,
        read_wall: float,
        scope: int = 0,
    ) -> None:
        """Stamp the earliest connector READ time contributing to
        ``engine_time`` — the start of the end-to-end freshness span
        (``pathway_freshness_seconds{connector=}``).  Earliest wins, as
        with :meth:`note_ingest`."""
        key = batch_trace_id(scope, engine_time)
        with self._lock:
            batch = self._batches.get(key)
            if batch is None:
                batch = self._batches[key] = _Batch()
                self._source_order.append(key)
                while len(self._source_order) > self.MAX_PENDING:
                    self._batches.pop(self._source_order.popleft(), None)
            prev = batch.sources.get(connector)
            if prev is None or read_wall < prev[0]:
                batch.sources[connector] = [read_wall, None, 0]

    def note_commit(
        self,
        connector: str,
        engine_time: int,
        commit_wall: float | None,
        messages: int,
        scope: int = 0,
    ) -> None:
        """Stamp the connector's ``commit()`` of the batch that holds its
        earliest read of ``engine_time`` (and the rows drained)."""
        with self._lock:
            batch = self._batches.get(batch_trace_id(scope, engine_time))
            source = batch.sources.get(connector) if batch else None
            if source is not None:
                source[1] = commit_wall
                source[2] = messages

    def _stamp(self, engine_time: int, scope: int, field: str, wall: float) -> bool:
        """First stamp of a milestone wins; True when the timestamp is a
        traced batch."""
        with self._lock:
            batch = self._batches.get(batch_trace_id(scope, engine_time))
            if batch is None:
                return False
            if getattr(batch, field) is None:
                setattr(batch, field, wall)
            return True

    def note_step(self, engine_time: int, wall: float, scope: int = 0) -> bool:
        """The driver's ``engine.step(engine_time)`` begins."""
        return self._stamp(engine_time, scope, "step", wall)

    def note_index(self, engine_time: int, wall: float, scope: int = 0) -> bool:
        """The index node's ``index.doc_data`` begins."""
        return self._stamp(engine_time, scope, "index", wall)

    def note_embedded(self, engine_time: int, wall: float, scope: int = 0) -> None:
        """The index node's ``index.doc_data`` ends."""
        self._stamp(engine_time, scope, "embedded", wall)

    def note_tick(self, links, start: float, end: float) -> None:
        """A tick carried work linked to ``links`` (``(trace_id, parent)``
        pairs): stamp its start and end on the batches among them whose
        index flush has begun.  Called before the tick's futures resolve,
        so before the engine thread can close the batch."""
        with self._lock:
            for trace_id, _parent in links:
                batch = self._batches.get(trace_id)
                if batch is None or batch.index is None:
                    continue
                if batch.tick_start is None or start < batch.tick_start:
                    batch.tick_start = start
                if batch.tick_end is None or end > batch.tick_end:
                    batch.tick_end = end

    def note_indexed(
        self, index_name: str, engine_time: int, scope: int = 0
    ) -> float | None:
        """Record that ``index_name`` applied the updates of
        ``engine_time``; returns the observed lag (None when the
        timestamp was never stamped — static/batch data).  Also closes
        the END-TO-END loop per connector: read-time stamps for this
        timestamp become ``pathway_freshness_seconds{connector=}``
        observations and feed the freshness SLO burn windows."""
        now = time.time()
        lag: float | None = None
        batch: _Batch | None = None
        with self._lock:
            wall = self._ingest_wall.get((scope, engine_time))
            if wall is not None:
                lag = max(0.0, now - wall)
                self._lag[index_name] = (lag, now)
                # CONSUME the read stamps: the end-to-end lag closes when
                # the timestamp FIRST becomes queryable — without the pop,
                # a pipeline with k index nodes would feed the freshness
                # burn ring k times per ingest batch (k−1 of them fresh),
                # diluting a stale connector's bad fraction k-fold and
                # flapping the gauge to whichever index flushed last.
                # Per-index staleness stays on
                # pathway_index_freshness_seconds{index=}.
                batch = self._batches.pop(
                    batch_trace_id(scope, engine_time), None
                )
                for connector, (read_wall, _c, _n) in (
                    batch.sources.items() if batch else ()
                ):
                    self._source_lag[connector] = (
                        max(0.0, now - read_wall), now,
                    )
            listeners = tuple(self._indexed_listeners)
        # listeners fire even for timestamps without an ingest stamp
        # (static/replayed data): an index APPLY is the queryability
        # event the fleet watermark keys on, stamped or not
        for fn in listeners:
            try:
                fn(index_name, engine_time, scope)
            except Exception:  # noqa: BLE001 — listeners must not break flush
                pass
        if lag is None:
            return None
        # burn-rate treatment (observability/slo.py) — lazy and fail-open:
        # freshness accounting must never take down an index flush
        if batch is not None and batch.sources:
            self._observe_batch(batch, engine_time, scope, now)
            try:
                from ..observability import slo

                for connector, (read_wall, _c, _n) in batch.sources.items():
                    slo.observe_freshness(connector, max(0.0, now - read_wall))
            except Exception:  # noqa: BLE001
                pass
        return lag

    @staticmethod
    def _observe_batch(batch: _Batch, engine_time: int, scope: int, now: float) -> None:
        """Per connector: ``ingest.read_to_indexed`` (connector read ->
        queryable, the part of a document's way the program sees from the
        inside) and its seven segments, as stages and as ring records under
        the batch's trace id (the whole way a root, the segments its
        children)."""
        from .flight_recorder import get_recorder, new_span_id, observe_stage

        rec = get_recorder()
        trace_id = batch_trace_id(scope, engine_time)
        for connector, (read_wall, commit_wall, messages) in batch.sources.items():
            observe_stage(
                "ingest.read_to_indexed", max(0.0, now - read_wall) * 1000.0
            )
            ms = batch.milestones(read_wall, commit_wall, now)
            for i, stage in enumerate(INGEST_SEGMENTS):
                observe_stage(stage, (ms[i + 1] - ms[i]) * 1000.0)
            if not rec.enabled:
                continue
            root = new_span_id()
            attrs = {"t": engine_time, "connector": connector}
            rec.record(
                "ingest.read_to_indexed", "ingest", read_wall,
                (now - read_wall) * 1000.0, trace_id, root, None, attrs,
            )
            for i, stage in enumerate(INGEST_SEGMENTS):
                rec.record(
                    stage, "ingest", ms[i], (ms[i + 1] - ms[i]) * 1000.0,
                    trace_id, new_span_id(), root,
                    {**attrs, "messages": messages} if i == 1 else attrs,
                )

    def stats(self) -> dict[str, Any]:
        """Per-INDEX lag view (shape unchanged since PR 4 — consumers
        iterate it; the per-connector end-to-end view lives in
        :meth:`connector_stats`)."""
        with self._lock:
            return {
                name: {"lag_s": round(lag, 6), "age_s": round(time.time() - at, 3)}
                for name, (lag, at) in self._lag.items()
            }

    def connector_stats(self) -> dict[str, Any]:
        """End-to-end (connector read → queryable) lag per connector."""
        with self._lock:
            return {
                name: {
                    "lag_s": round(lag, 6),
                    "age_s": round(time.time() - at, 3),
                }
                for name, (lag, at) in self._source_lag.items()
            }

    def connector_lags(self) -> dict[str, float]:
        """Latest end-to-end (read→queryable) lag per connector."""
        with self._lock:
            return {name: lag for name, (lag, _at) in self._source_lag.items()}

    def openmetrics_lines(self) -> list[str]:
        with self._lock:
            items = sorted(self._lag.items())
            sources = sorted(self._source_lag.items())
        lines: list[str] = []
        if items:
            lines.append("# TYPE pathway_index_freshness_seconds gauge")
            for name, (lag, _at) in items:
                lines.append(
                    f'pathway_index_freshness_seconds{{index="{escape_label_value(name)}"}} '
                    f"{lag:.6f}"
                )
        if sources:
            lines.append("# TYPE pathway_freshness_seconds gauge")
            for name, (lag, _at) in sources:
                lines.append(
                    f'pathway_freshness_seconds{{connector="{escape_label_value(name)}"}} '
                    f"{lag:.6f}"
                )
        return lines

    def reset(self) -> None:
        with self._lock:
            self._ingest_wall.clear()
            self._ingest_order.clear()
            self._lag.clear()
            self._batches.clear()
            self._source_order.clear()
            self._source_lag.clear()


#: process-global: the driver and the index nodes live in different layers
#: and meet only here (one live engine per process — health.py scope note)
_freshness = FreshnessTracker()


def get_freshness() -> FreshnessTracker:
    return _freshness


# ---------------------------------------------------------------------------
# the /status HTTP thread
# ---------------------------------------------------------------------------

_server_lock = threading.Lock()
_last_server: ThreadingHTTPServer | None = None


def start_http_server_thread(
    monitor: StatsMonitor, port: int | None = None, process_id: int = 0
) -> ThreadingHTTPServer:
    """Serve ``/status`` OpenMetrics on 127.0.0.1:(20000+process_id)
    (reference: http_server.rs:76-83; PATHWAY_MONITORING_HTTP_PORT
    overrides).

    One metrics server per process: calling this again (a second
    ``pw.run`` in the same test process) shuts the previous server down
    and releases its socket first, instead of leaking the port thread.
    """
    if port is None:
        import os

        env_port = os.environ.get("PATHWAY_MONITORING_HTTP_PORT")
        port = int(env_port) if env_port else 20000 + process_id

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — stdlib API
            if self.path not in ("/status", "/metrics"):
                self.send_response(404)
                self.end_headers()
                return
            body = monitor.openmetrics().encode()
            self.send_response(200)
            self.send_header(
                "Content-Type", "application/openmetrics-text; version=1.0.0"
            )
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):  # silence request logging
            pass

    global _last_server
    with _server_lock:
        if _last_server is not None:
            try:
                _last_server.shutdown()
                _last_server.server_close()
            except Exception:  # noqa: BLE001 — an already-dead server is fine
                pass
            _last_server = None
        server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        _last_server = server
    th = threading.Thread(target=server.serve_forever, daemon=True, name="pw-metrics")
    th.start()
    return server


# re-exported for parity with reference run.py imports
from .run import MonitoringLevel  # noqa: E402  (cycle-safe: run has no monitoring import at module level)
