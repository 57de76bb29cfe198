"""Request tracing + the in-process flight recorder.

Dapper-style per-request, per-stage attribution with zero external
infrastructure: every ``PathwayWebserver`` request gets a trace id (W3C
``traceparent`` honored when the caller sends one, minted otherwise), the
serving scheduler threads the trace through admission -> batch dispatch,
and the batch handlers stamp stage spans (queue wait, embed, search,
serialize).  Finished spans ALWAYS land here — a bounded, lock-cheap ring
buffer of spans from every plane:

* HTTP requests + their per-stage child spans (``io/http/_server.py``
  tracing middleware + ``xpacks/llm/_scheduler.py``),
* engine operator flushes (``internals/engine.py`` ``_flush_node``),
* batches of connector rows: an engine timestamp that carries them is
  traced like a request, under :func:`batch_trace_id`, in seven segments
  from the connector's read to the index (``internals/monitoring.py``
  ``FreshnessTracker``; ``?category=ingest``),
* scheduler device ticks, breaker transitions, injected faults,
* unified-runtime ticks (``pathway_tpu/runtime/executor.py``): one
  ``tick:runtime`` span per composed tick (category ``runtime``, attrs:
  occupancy, token mass, per-QoS-class counts, ``preempted``) plus the
  per-group ``tick:<label>`` execute spans (category ``scheduler``,
  now carrying a ``qos`` attr — filter ``/v1/debug/traces?category=``
  on either to see how interactive/ingest work interleaves).

Timed blocks go through :class:`span`: one measurement lands in the ring,
in ``pathway_request_stage_ms{stage=}`` and, while a ``jax.profiler``
session is open, in the profiler's host plane as ``pw.<category>.<name>``
on the profiler's own clock; :func:`name_thread` gives the program's
threads the OS names that trace shows.  After-the-fact :func:`record_span`
stays for spans a callback reports or that have no duration.

``GET /v1/debug/traces`` (every webserver) filters the ring by trace id /
duration floor and the ``format=perfetto`` exporter dumps Chrome-tracing
JSON — a slow window can be captured and opened in ``chrome://tracing`` /
Perfetto with no collector deployed.  When an OpenTelemetry SDK tracer
provider is configured in-process, finished request traces are ALSO
emitted as real OTel spans with correct parentage; with only the OTel API
installed (this image) that path is skipped entirely.

Env knobs: ``PATHWAY_TRACE_SAMPLE`` (fraction of requests that record
stage spans, default 1.0 — the ring append is cheap enough to keep on),
``PATHWAY_FLIGHT_RECORDER_CAPACITY`` (ring size in spans, default 4096,
0 disables recording; the trace-id header is still returned).

Import discipline: this module is engine-hot-path adjacent and is
imported at module level by ``internals/engine.py`` — it must only import
stdlib and the :mod:`metrics_names` leaf, never ``monitoring``/``run``,
and never ``jax`` (:class:`span` finds it in ``sys.modules`` or does without).
``monitoring.py`` pulls :func:`observability_metrics_lines` lazily
instead.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import random
import re
import sys
import threading
import time
from collections import deque
from typing import Any, Iterator

from .metrics_names import Histogram, escape_label_value

__all__ = [
    "Span",
    "FlightRecorder",
    "RequestTrace",
    "get_recorder",
    "reset_recorder",
    "configure_tracing",
    "tracing_settings",
    "start_request",
    "trace_stage",
    "batch_traces",
    "batch_stage",
    "current_trace_link",
    "batch_trace_id",
    "batch_link_scope",
    "current_batch_link",
    "new_trace_id",
    "new_span_id",
    "parse_traceparent",
    "format_traceparent",
    "record_span",
    "span",
    "name_thread",
    "observe_stage",
    "record_xla_compile",
    "instrument_jit",
    "compile_stats",
    "record_padding",
    "record_attention_impl",
    "attention_impl_stats",
    "active_attention_impl",
    "record_ingest_docs",
    "record_inputs_rows",
    "record_tokenizer_cache",
    "ingest_stats",
    "record_moe_launch",
    "moe_grouped_stats",
    "mla_stats",
    "conv_stats",
    "moe_stats",
    "record_ssm_launch",
    "ssm_stats",
    "observability_metrics_lines",
]


# ---------------------------------------------------------------------------
# W3C trace context
# ---------------------------------------------------------------------------

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    # not os.urandom: a getrandom call costs some 10 us on the chip's
    # gVisor host, and a batch of connector rows mints some thirty span
    # ids (``random`` reseeds itself in a forked child)
    return f"{random.getrandbits(64) or 1:016x}"


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
    """``(trace_id, parent_span_id)`` from a W3C ``traceparent`` header,
    or None when absent/malformed (spec: restart the trace, don't fail
    the request).  All-zero ids are invalid per spec."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None or m.group(1) == "ff":
        return None
    trace_id, span_id = m.group(2), m.group(3)
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str, sampled: bool = True) -> str:
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def _env_number(name: str, default, parse):
    """Lenient env parse: a typo in an observability knob must never take
    down the serving path it observes — warn once and keep the default."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        return parse(raw)
    except (TypeError, ValueError):
        import logging

        logging.getLogger("pathway_tpu").warning(
            "ignoring malformed %s=%r (using default %r)", name, raw, default
        )
        return default


# ---------------------------------------------------------------------------
# spans + the ring buffer
# ---------------------------------------------------------------------------


class Span:
    """One finished span: wall-clock start + duration, optional trace
    lineage, small attrs dict."""

    __slots__ = (
        "name", "category", "start_s", "duration_ms",
        "trace_id", "span_id", "parent_id", "attrs",
    )

    def __init__(
        self,
        name: str,
        category: str,
        start_s: float,
        duration_ms: float,
        trace_id: str | None = None,
        span_id: str | None = None,
        parent_id: str | None = None,
        attrs: dict[str, Any] | None = None,
    ):
        self.name = name
        self.category = category
        self.start_s = start_s
        self.duration_ms = duration_ms
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs

    def to_dict(self) -> dict[str, Any]:
        d: dict[str, Any] = {
            "name": self.name,
            "category": self.category,
            "start_s": round(self.start_s, 6),
            "duration_ms": round(self.duration_ms, 3),
        }
        if self.trace_id is not None:
            d["trace_id"] = self.trace_id
        if self.span_id is not None:
            d["span_id"] = self.span_id
        if self.parent_id is not None:
            d["parent_id"] = self.parent_id
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class FlightRecorder:
    """Bounded ring of finished spans (``deque(maxlen=...)`` appends are
    O(1) and evict the oldest span automatically — recording can never
    grow without bound or block a hot path on anything slower than one
    short lock)."""

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            capacity = _env_number(
                "PATHWAY_FLIGHT_RECORDER_CAPACITY", 4096, int
            )
        self.capacity = max(0, capacity)
        self.enabled = self.capacity > 0
        self._lock = threading.Lock()
        self._ring: deque[Span] = deque(maxlen=self.capacity or 1)
        self._recorded_total = 0
        # overflow visibility: a span evicted before ANY spans() read was
        # never observable — without a counter, drops under load are
        # silent and a "no slow spans found" answer can be a lie.
        # Sequence arithmetic instead of per-span flags: the oldest
        # buffered span's append-seq is recorded_total - len(ring), and
        # spans() advances the read watermark to recorded_total.
        self._read_seq = 0
        self._dropped: dict[str, int] = {}

    def _note_evict_locked(self) -> None:
        """Caller holds the lock and is about to append while full."""
        if len(self._ring) == self.capacity and self.capacity > 0:
            evicted = self._ring[0]
            evict_seq = self._recorded_total - len(self._ring)
            if evict_seq >= self._read_seq:
                cat = evicted.category
                self._dropped[cat] = self._dropped.get(cat, 0) + 1

    def record(
        self,
        name: str,
        category: str,
        start_s: float,
        duration_ms: float,
        trace_id: str | None = None,
        span_id: str | None = None,
        parent_id: str | None = None,
        attrs: dict[str, Any] | None = None,
    ) -> None:
        if not self.enabled:
            return
        span = Span(
            name, category, start_s, duration_ms,
            trace_id, span_id, parent_id, attrs,
        )
        with self._lock:
            self._note_evict_locked()
            self._ring.append(span)
            self._recorded_total += 1

    def record_span(self, span: Span) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._note_evict_locked()
            self._ring.append(span)
            self._recorded_total += 1

    def spans(
        self,
        trace_id: str | None = None,
        min_duration_ms: float | None = None,
        category: str | None = None,
        limit: int | None = None,
        mark_read: bool = True,
    ) -> list[Span]:
        """Matching spans, oldest first (a trace reads top-down).

        ``mark_read=False`` is for INTERNAL consumers (the profiler's
        window export) whose read is not an operator looking at the
        evidence — they must not advance the drop watermark, or a
        periodic profile capture would silently zero
        ``pathway_trace_dropped_total``."""
        # the drop watermark advances only when the reader receives the
        # WHOLE buffer: a filtered or limit-capped read delivers a
        # subset, and marking the undelivered spans "read" would make
        # pathway_trace_dropped_total undercount exactly the silent
        # drops it exists to expose.  (The scalar watermark cannot
        # represent a sparse read, so partial reads leave it alone —
        # drops may overcount for a reader who filters aggressively,
        # which is the safe direction for an alarm signal.)  The advance
        # happens INSIDE the snapshot's lock section: a second
        # acquisition would race record() and count spans evicted
        # mid-serialization as dropped even though this read returns
        # them.
        full_read = (
            trace_id is None
            and min_duration_ms is None
            and category is None
            and limit is None
        )
        with self._lock:
            snap = list(self._ring)
            if mark_read and full_read:
                self._read_seq = self._recorded_total
        out = [
            s
            for s in snap
            if (trace_id is None or s.trace_id == trace_id)
            and (min_duration_ms is None or s.duration_ms >= min_duration_ms)
            and (category is None or s.category == category)
        ]
        if limit is not None and len(out) > limit:
            out = out[-limit:]  # keep the newest spans under a cap
        return out

    def stats(self) -> dict[str, Any]:
        with self._lock:
            out = {
                "capacity": self.capacity,
                "recorded_total": self._recorded_total,
                "buffered": len(self._ring),
                "dropped_before_read_total": sum(self._dropped.values()),
            }
            if self._dropped:
                out["dropped_by_category"] = dict(self._dropped)
            return out

    def dropped_by_category(self) -> dict[str, int]:
        with self._lock:
            return dict(self._dropped)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    # -- Perfetto / chrome://tracing export -----------------------------
    @staticmethod
    def perfetto(spans: list[Span]) -> dict[str, Any]:
        """Chrome-tracing JSON: one ``X`` (complete) event per span, one
        lane (tid) per category — requests with a trace id get their own
        lane so concurrent requests don't visually overlap."""
        lanes: dict[str, int] = {}
        events: list[dict[str, Any]] = []

        def lane(key: str) -> int:
            if key not in lanes:
                lanes[key] = len(lanes) + 1
            return lanes[key]

        for s in spans:
            key = f"trace:{s.trace_id[:8]}" if s.trace_id else s.category
            args: dict[str, Any] = dict(s.attrs or {})
            if s.trace_id:
                args["trace_id"] = s.trace_id
            events.append(
                {
                    "ph": "X",
                    "name": s.name,
                    "cat": s.category,
                    "ts": s.start_s * 1e6,  # microseconds
                    "dur": max(s.duration_ms, 1e-3) * 1e3,
                    "pid": 1,
                    "tid": lane(key),
                    "args": args,
                }
            )
        meta = [
            {
                "ph": "M",
                "name": "thread_name",
                "pid": 1,
                "tid": tid,
                "args": {"name": key},
            }
            for key, tid in lanes.items()
        ]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


_recorder_lock = threading.Lock()
_recorder: FlightRecorder | None = None


def get_recorder() -> FlightRecorder:
    global _recorder
    rec = _recorder
    if rec is None:
        with _recorder_lock:
            if _recorder is None:
                _recorder = FlightRecorder()
            rec = _recorder
    return rec


def reset_recorder() -> None:
    """Test isolation hook: drop the ring (re-reads env capacity)."""
    global _recorder
    with _recorder_lock:
        _recorder = None


def record_span(
    name: str,
    category: str,
    start_s: float,
    duration_ms: float,
    **kwargs: Any,
) -> None:
    """Module-level convenience used by the non-request call sites
    (engine flushes, connector commits, breaker transitions, faults)."""
    get_recorder().record(name, category, start_s, duration_ms, **kwargs)


# ---------------------------------------------------------------------------
# request traces
# ---------------------------------------------------------------------------

_SETTINGS = {
    "sample": _env_number("PATHWAY_TRACE_SAMPLE", 1.0, float),
}


def configure_tracing(sample: float | None = None) -> None:
    """Adjust the live sampling rate (``PATHWAY_TRACE_SAMPLE`` sets the
    process default)."""
    if sample is not None:
        _SETTINGS["sample"] = max(0.0, min(1.0, float(sample)))


def tracing_settings() -> dict[str, Any]:
    return dict(_SETTINGS)


#: fixed buckets for request stage latencies (ms)
_STAGE_BUCKETS_MS = (
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
)
_stage_lock = threading.Lock()
_stage_hists: dict[str, Histogram] = {}


def observe_stage(stage: str, duration_ms: float) -> None:
    """Feed ``pathway_request_stage_ms{stage=...}``."""
    with _stage_lock:
        hist = _stage_hists.get(stage)
        if hist is None:
            hist = _stage_hists[stage] = Histogram(_STAGE_BUCKETS_MS)
        hist.observe(duration_ms)


# ---------------------------------------------------------------------------
# the span primitive: ring + stage histogram + the profiler's host plane
# ---------------------------------------------------------------------------

_trace_annotation: Any = None


def _annotation_class() -> Any:
    """``jax.profiler.TraceAnnotation`` once ``jax`` is loaded, else None.
    Looked up in ``sys.modules``: this module never imports ``jax``."""
    global _trace_annotation
    if _trace_annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        # None while jax itself is still importing: ask again next time
        _trace_annotation = getattr(profiler, "TraceAnnotation", None)
    return _trace_annotation


class span:
    """Time a block once and report it everywhere a span is read:

    * the flight-recorder ring, as ``name`` / ``category`` with ``attrs``
      (once per ``(trace_id, parent_id)`` of ``links`` when given, so
      deferred work hangs under the request that caused it);
    * ``pathway_request_stage_ms{stage=}`` when ``stage`` is set;
    * while a ``jax.profiler`` session is open, the profiler's
      ``/host:CPU`` plane as ``pw.<category>.<name>`` with ``attrs`` as
      event stats, on the profiler's own clock beside the device events.

    ``set(**attrs)`` adds what is known only at the end (row counts); a
    block that raises reads ``ok=False``; ``stage``, ``links`` and
    ``record`` may be assigned inside the block.  ``record=False`` keeps
    the span out of the ring for callers that file it themselves (request
    stages ride their ``RequestTrace``).  After the block ``start_mono``
    / ``end_mono`` / ``duration_ms`` hold the one measurement.  Keep
    attrs to counts and short labels: they are built on the hot path."""

    __slots__ = (
        "name", "category", "stage", "links", "record", "attrs",
        "start_s", "start_mono", "end_mono", "duration_ms", "_annotation",
    )

    def __init__(
        self,
        name: str,
        category: str,
        *,
        stage: str | None = None,
        links: list[tuple[str, str]] | None = None,
        record: bool = True,
        **attrs: Any,
    ):
        self.name = name
        self.category = category
        self.stage = stage
        self.links = links
        self.record = record
        self.attrs = attrs
        self._annotation = None

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)

    def __enter__(self) -> "span":
        annotation = _trace_annotation or _annotation_class()
        if annotation is not None and annotation.is_enabled():
            self._annotation = annotation(
                f"pw.{self.category}.{self.name}", **self.attrs
            )
            self._annotation.__enter__()
        self.start_s = time.time()
        self.start_mono = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end_mono = time.monotonic()
        self.duration_ms = (self.end_mono - self.start_mono) * 1000.0
        if exc_type is not None:
            self.set(ok=False)
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        if self.record:
            rec = get_recorder()
            if rec.enabled:
                attrs = self.attrs or None
                if self.links:
                    for trace_id, parent_id in self.links:
                        rec.record(
                            self.name, self.category, self.start_s,
                            self.duration_ms, trace_id, new_span_id(),
                            parent_id, attrs,
                        )
                else:
                    rec.record(
                        self.name, self.category, self.start_s,
                        self.duration_ms, attrs=attrs,
                    )
        if self.stage is not None:
            observe_stage(self.stage, self.duration_ms)


_libc: Any = None


def name_thread(name: str) -> None:
    """Give the CALLING thread an OS-level name (15 bytes at most): what
    ``top -H`` shows and what the profiler calls the thread's host line.
    Python 3.12 never sets it, so every thread reads ``python3``.  Call it
    first thing in a thread's body, before the thread's first span.
    Linux only (``prctl(PR_SET_NAME)``); never on the main thread, where
    the call would rename the process."""
    global _libc
    if (
        not sys.platform.startswith("linux")
        or threading.current_thread() is threading.main_thread()
    ):
        return
    try:
        if _libc is None:
            import ctypes

            libc = ctypes.CDLL(None)
            libc.prctl.argtypes = [
                ctypes.c_int, ctypes.c_char_p,
                ctypes.c_ulong, ctypes.c_ulong, ctypes.c_ulong,
            ]
            libc.prctl.restype = ctypes.c_int
            _libc = libc
        _libc.prctl(15, name.encode()[:15], 0, 0, 0)  # 15 = PR_SET_NAME
    except Exception:  # noqa: BLE001 - a label, never worth a failed thread
        pass


class RequestTrace:
    """Mutable per-request trace context.

    Built by the webserver's tracing middleware, carried through the
    scheduler on the work item, finished by the middleware.  Stage
    appends come from the scheduler/device thread while the handler
    coroutine owns the object — the tiny lock keeps the stage list
    coherent.  ``sampled=False`` traces skip stage collection and
    recording entirely but still carry the trace id for the response
    header.
    """

    __slots__ = (
        "trace_id", "span_id", "remote_parent", "name", "sampled",
        "start_s", "start_mono", "attrs", "_stages", "_lock", "_finished",
        "duration_ms",
    )

    def __init__(
        self,
        name: str,
        trace_id: str,
        remote_parent: str | None,
        sampled: bool,
    ):
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.remote_parent = remote_parent
        self.sampled = sampled
        self.start_s = time.time()
        self.start_mono = time.monotonic()
        self.attrs: dict[str, Any] = {}
        #: (stage_name, start_s, duration_ms)
        self._stages: list[tuple[str, float, float]] = []
        self._lock = threading.Lock()
        self._finished = False
        #: total request latency, set by finish() even when unsampled —
        #: the SLO engine observes latency for EVERY request, tracing
        #: sample rate only decides whether stage spans are collected
        self.duration_ms: float | None = None

    # -- stage recording -------------------------------------------------
    def _mono_to_wall(self, mono: float) -> float:
        return self.start_s + (mono - self.start_mono)

    def add_stage_mono(self, name: str, mono_start: float, mono_end: float) -> None:
        """Record a stage from monotonic endpoints (scheduler clocks)."""
        if not self.sampled:
            return
        dur_ms = max(0.0, (mono_end - mono_start) * 1000.0)
        with self._lock:
            self._stages.append((name, self._mono_to_wall(mono_start), dur_ms))

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        timed = span(name, "request", record=False)
        try:
            with timed:
                yield
        finally:
            self.add_stage_mono(name, timed.start_mono, timed.end_mono)

    def set_attr(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def stages(self) -> list[tuple[str, float, float]]:
        with self._lock:
            return list(self._stages)

    # -- completion ------------------------------------------------------
    def finish(self, status: int | None = None) -> None:
        """Record the request span + one child span per stage, feed the
        stage histograms, and emit OTel spans when an SDK is configured.
        Idempotent (middleware error paths may double-call)."""
        if self._finished:
            return
        self._finished = True
        duration_ms = (time.monotonic() - self.start_mono) * 1000.0
        self.duration_ms = duration_ms
        if status is not None:
            self.attrs["http.status"] = status
        if not self.sampled:
            return
        stages = self.stages()
        rec = get_recorder()
        rec.record(
            self.name,
            "request",
            self.start_s,
            duration_ms,
            trace_id=self.trace_id,
            span_id=self.span_id,
            parent_id=self.remote_parent,
            attrs=dict(self.attrs) if self.attrs else None,
        )
        for name, start_s, dur_ms in stages:
            rec.record(
                name,
                "request",
                start_s,
                dur_ms,
                trace_id=self.trace_id,
                span_id=new_span_id(),
                parent_id=self.span_id,
            )
            observe_stage(name, dur_ms)
        observe_stage("total", duration_ms)
        _emit_otel(self, duration_ms, stages)


def start_request(name: str, traceparent: str | None = None) -> RequestTrace:
    """Mint (or adopt) a trace for one inbound request.  Always returns a
    trace — the id rides the response header either way; ``sampled``
    (PATHWAY_TRACE_SAMPLE) and the recorder's capacity decide whether
    stage spans are collected."""
    parsed = parse_traceparent(traceparent)
    if parsed is not None:
        trace_id, remote_parent = parsed
    else:
        trace_id, remote_parent = new_trace_id(), None
    sample = _SETTINGS["sample"]
    sampled = (
        get_recorder().enabled
        and sample > 0.0
        and (sample >= 1.0 or random.random() < sample)
    )
    return RequestTrace(name, trace_id, remote_parent, sampled)


@contextlib.contextmanager
def trace_stage(trace: RequestTrace | None, name: str) -> Iterator[None]:
    """No-op-safe stage timer for call sites that may run untraced."""
    if trace is None or not trace.sampled:
        yield
        return
    with trace.stage(name):
        yield


# -- batch-scoped stage attribution -----------------------------------------
# A scheduler tick executes ONE device batch on behalf of MANY requests;
# the batch handler times its internal stages once and the timing is
# attributed to every trace riding the batch.  Thread-local because batch
# handlers run on the scheduler thread (or inline on a submitter).

_tls = threading.local()


@contextlib.contextmanager
def batch_traces(traces: list[RequestTrace]) -> Iterator[None]:
    """Scope: the traces whose work the current batch executes."""
    prev = getattr(_tls, "traces", None)
    _tls.traces = traces
    try:
        yield
    finally:
        _tls.traces = prev


def current_trace_link() -> tuple[str, str] | None:
    """``(trace_id, span_id)`` of the request whose work is executing on
    this thread, or None outside any trace scope.

    Deferred runtime work (query-cache refresh, tier migration) is
    SUBMITTED from inside a request's batch scope but EXECUTES on a later
    tick, after the scope is gone — the submitter captures this link at
    submit time and threads it through the WorkItem so the deferred
    tick's spans carry ``parent_id`` = the triggering request's span
    instead of starting trace-orphaned.  First sampled trace wins: a
    multi-request batch that triggers one refresh attributes it to one
    requester, which beats attributing it to nobody."""
    traces = getattr(_tls, "traces", None)
    if not traces:
        return None
    for tr in traces:
        if tr.sampled:
            return tr.trace_id, tr.span_id
    return None


# -- batches of connector rows ----------------------------------------------
# An engine timestamp that carries connector rows is traced like a request:
# its trace id is derived from the engine and the timestamp, so the driver,
# the engine's flushes, the index node and the tick runtime name it alike
# without handing it around.  The link rides a context variable: the index
# node's embed calls run on the persistent loop, and a coroutine scheduled
# from the engine thread runs in a copy of that thread's context, so the
# calls carry the link of the flush that made them to ``submit``.

_M64 = (1 << 64) - 1
_batch_link: contextvars.ContextVar[tuple[str, None] | None] = (
    contextvars.ContextVar("pw_batch_link", default=None)
)


def batch_trace_id(scope: int, t: int) -> str:
    """The trace id of engine timestamp ``t`` of engine ``scope``
    (``id(engine)``, the freshness tracker's scope): 16 hex digits each."""
    return f"{scope & _M64:016x}{t & _M64:016x}"


@contextlib.contextmanager
def batch_link_scope(link: tuple[str, None] | None) -> Iterator[None]:
    """Scope: the batch whose work runs here (``(trace_id, None)``: spans
    recorded under it are roots of the batch's trace), or None."""
    token = _batch_link.set(link)
    try:
        yield
    finally:
        _batch_link.reset(token)


def current_batch_link() -> tuple[str, None] | None:
    """The link of the batch in scope in this context, or None."""
    return _batch_link.get()


@contextlib.contextmanager
def batch_stage(name: str) -> Iterator[None]:
    """Time a batch-internal stage (embed, search, ...) and stamp it onto
    every trace in the current batch scope.  Untraced it still shows in
    a profiler session (``pw.request.<name>``)."""
    timed = span(name, "request", record=False)
    try:
        with timed:
            yield
    finally:
        for tr in getattr(_tls, "traces", None) or ():
            tr.add_stage_mono(name, timed.start_mono, timed.end_mono)


# ---------------------------------------------------------------------------
# OTel emission (only when an SDK tracer provider is installed)
# ---------------------------------------------------------------------------

_otel_tracer: Any = None


def _sdk_tracer() -> Any:
    """A real (SDK-backed) tracer, or None with only the no-op API
    installed.  Positive result cached; the negative probe is one module
    check per request — cheap, and it lets a test configure the SDK
    provider after import."""
    global _otel_tracer
    if _otel_tracer is not None:
        return _otel_tracer
    try:
        from opentelemetry import trace as otel_trace
    except ImportError:
        return None
    provider = otel_trace.get_tracer_provider()
    if not type(provider).__module__.startswith("opentelemetry.sdk"):
        return None
    _otel_tracer = otel_trace.get_tracer("pathway_tpu.request")
    return _otel_tracer


def _emit_otel(
    trace: RequestTrace,
    duration_ms: float,
    stages: list[tuple[str, float, float]],
) -> None:
    tracer = _sdk_tracer()
    if tracer is None:
        return
    try:
        from opentelemetry import trace as otel_trace
        from opentelemetry.trace import (
            NonRecordingSpan,
            SpanContext,
            TraceFlags,
        )

        parent_ctx = None
        if trace.remote_parent is not None:
            parent_ctx = otel_trace.set_span_in_context(
                NonRecordingSpan(
                    SpanContext(
                        int(trace.trace_id, 16),
                        int(trace.remote_parent, 16),
                        is_remote=True,
                        trace_flags=TraceFlags(TraceFlags.SAMPLED),
                    )
                )
            )
        start_ns = int(trace.start_s * 1e9)
        root = tracer.start_span(
            trace.name,
            context=parent_ctx,
            start_time=start_ns,
            attributes={
                k: v
                for k, v in trace.attrs.items()
                if isinstance(v, (str, int, float, bool))
            },
        )
        child_ctx = otel_trace.set_span_in_context(root)
        for name, start_s, dur_ms in stages:
            s_ns = int(start_s * 1e9)
            child = tracer.start_span(name, context=child_ctx, start_time=s_ns)
            child.end(end_time=s_ns + int(dur_ms * 1e6))
        root.end(end_time=start_ns + int(duration_ms * 1e6))
    except Exception:  # noqa: BLE001 — telemetry must never fail a request
        pass


# ---------------------------------------------------------------------------
# ingest-plane counters (padding efficiency, docs ingested, tokenizer cache)
# ---------------------------------------------------------------------------

_ingest_lock = threading.Lock()
_ingest_counters = {
    "docs_total": 0,
    "real_tokens": 0,
    "padded_tokens": 0,
    "row_tokens": 0,
    "tokenizer_cache_hits": 0,
    "tokenizer_cache_misses": 0,
    "inputs_rows": 0,
}

#: per-encoder tokenizer-cache counters (encoder label -> [hits, misses]).
#: The shared TokenCache serves every tokenizer in the process; without
#: the label one server running the hashing tokenizer AND an HF one (or
#: the query-embedding cache next to an ingest encoder) would alias their
#: hit rates into one number.
_tokenizer_cache_by_encoder: dict[str, list[int]] = {}

#: attention implementations active in this process (impl -> encoders
#: built with it); surfaced on /status and the /v1/health runtime block
_attn_impls: dict[str, int] = {}


def record_padding(
    real_tokens: int, padded_tokens: int, row_tokens: int | None = None
) -> None:
    """One dispatch's token accounting — feeds the
    ``pathway_embed_padding_efficiency`` gauge (real / padded; 1.0 means
    every FLOP the device spent was on a real token).

    ``row_tokens`` decomposes the waste: the token mass attributable to
    REAL rows at their dispatch layout (rows x their seq bucket on the
    packed-bucket path; exactly ``real_tokens`` on the ragged path).
    ``real/row`` is then the INTRA-BUCKET token padding (short rows
    inside their bucket — ~0.906 packed, ~1.0 ragged) and ``row/padded``
    the bucket-level waste (pad rows + tail alignment).  Callers that
    don't decompose (legacy external callers) default ``row_tokens`` to
    ``padded_tokens`` — intra-bucket then degrades to the old
    whole-ratio semantics instead of lying."""
    with _ingest_lock:
        _ingest_counters["real_tokens"] += int(real_tokens)
        _ingest_counters["padded_tokens"] += int(padded_tokens)
        _ingest_counters["row_tokens"] += int(
            padded_tokens if row_tokens is None else row_tokens
        )


def record_attention_impl(impl: str) -> None:
    """An encoder was built with ``impl`` (flax/fused/pallas/ragged) —
    the observable form of the PATHWAY_ATTENTION_IMPL knob."""
    with _ingest_lock:
        # pop+reinsert: dict order then IS build recency, which
        # active_attention_impl leans on
        _attn_impls[str(impl)] = _attn_impls.pop(str(impl), 0) + 1


def attention_impl_stats() -> dict[str, int]:
    with _ingest_lock:
        return dict(_attn_impls)


def active_attention_impl() -> str | None:
    """The attention impl serving this process (the most-recently built
    encoder's), for the /v1/health runtime block."""
    with _ingest_lock:
        if not _attn_impls:
            return None
        return next(reversed(_attn_impls))


def record_ingest_docs(n: int) -> None:
    """Documents embedded+upserted through an ingest plane
    (``pathway_ingest_docs_total``)."""
    with _ingest_lock:
        _ingest_counters["docs_total"] += int(n)


def record_inputs_rows(n: int) -> None:
    """Metadata rows assembled by one evaluation of a ``/v1/inputs``
    answer (``pathway_inputs_rows_total``): 0 while no such query is
    pending, the corpus size at each evaluation of a pending one."""
    with _ingest_lock:
        _ingest_counters["inputs_rows"] += int(n)


def record_tokenizer_cache(
    hits: int = 0, misses: int = 0, encoder: str = "default"
) -> None:
    """One tokenizer-cache lookup batch's accounting, labeled by the
    encoder it served (``pathway_tokenizer_cache_*_total{encoder=}``).
    The unlabeled process totals stay available in :func:`ingest_stats`
    (and render on the exposition only until the first labeled lookup —
    the labeled series REPLACE the unlabeled one there, so a
    ``sum()`` over the family never double-counts; see MIGRATION)."""
    with _ingest_lock:
        _ingest_counters["tokenizer_cache_hits"] += int(hits)
        _ingest_counters["tokenizer_cache_misses"] += int(misses)
        slot = _tokenizer_cache_by_encoder.setdefault(str(encoder), [0, 0])
        slot[0] += int(hits)
        slot[1] += int(misses)


def ingest_stats() -> dict[str, Any]:
    with _ingest_lock:
        snap = dict(_ingest_counters)
        if _attn_impls:
            snap["attention_impls"] = dict(_attn_impls)
    snap["padding_efficiency"] = (
        snap["real_tokens"] / snap["padded_tokens"]
        if snap["padded_tokens"]
        else 1.0
    )
    # intra-bucket token padding only (short rows inside their seq
    # bucket): ~0.906 packed-bucket, ~1.0 ragged — the decomposition the
    # total gauge can't show once pad rows/tail alignment mix in
    snap["intra_bucket_efficiency"] = (
        snap["real_tokens"] / snap["row_tokens"]
        if snap["row_tokens"]
        else 1.0
    )
    hits, misses = snap["tokenizer_cache_hits"], snap["tokenizer_cache_misses"]
    snap["tokenizer_cache_hit_rate"] = (
        hits / (hits + misses) if hits + misses else 0.0
    )
    with _ingest_lock:
        if _tokenizer_cache_by_encoder:
            snap["tokenizer_cache_by_encoder"] = {
                enc: {"hits": s[0], "misses": s[1]}
                for enc, s in _tokenizer_cache_by_encoder.items()
            }
    return snap


# ---------------------------------------------------------------------------
# launch counters of a language-model embedder (pathway_moe_*, pathway_mla_*,
# pathway_conv_*, pathway_ssm_*):
# computed on the device by the forward, they come back with its result and
# are added up here once the launch has finished: recording one never waits
# for the device
# ---------------------------------------------------------------------------


class _LaunchCounters:
    """Totals over the launches of one kind of forward.  ``names`` are the
    totals; ``add(totals, values)`` folds one launch's int32 array into
    them.  Device arrays wait in line until they are ready."""

    def __init__(self, names: tuple[str, ...], add):
        self._lock = threading.Lock()
        self._pending: deque = deque()
        self._totals = dict.fromkeys(names, 0)
        self._add = add

    def _drain(self, wait: bool) -> None:
        import numpy as np

        while self._pending:
            counters = self._pending[0]
            if not wait and not counters.is_ready():
                return
            self._pending.popleft()
            self._add(self._totals, [int(v) for v in np.asarray(counters)])

    def record(self, counters: Any) -> None:
        with self._lock:
            self._pending.append(counters)
            self._drain(wait=False)

    def stats(self, wait: bool) -> dict[str, int]:
        with self._lock:
            self._drain(wait=wait)
            return dict(self._totals)


_MOE_NAMES = ("launches_total", "routed_tokens_total", "experts_touched_total",
              "max_expert_tokens_sum", "max_expert_tokens")
_MLA_NAMES = ("launches_total", "documents_total", "tokens_total", "bucket_tokens_total",
              "attention_pairs_total")
_CONV_NAMES = _MLA_NAMES[:4]


def _add_moe(totals: dict, values: list) -> None:
    routed, touched, fullest_sum, fullest = values[:4]
    totals["launches_total"] += 1
    totals["routed_tokens_total"] += routed
    totals["experts_touched_total"] += touched
    totals["max_expert_tokens_sum"] += fullest_sum
    totals["max_expert_tokens"] = fullest
    # a forward with latent attention carries four more (pathway_mla_*), one
    # with conv layers three (pathway_conv_*)
    held = values[4:]
    if held:
        family = "mla_" if len(held) == len(_MLA_NAMES) - 1 else "conv_"
        for name, value in zip(_MLA_NAMES, [1] + held):
            totals[family + name] += value


def _add_ssm(totals: dict, values: list) -> None:
    for name, value in zip(totals, values):
        totals[name] += value


_moe_launches = _LaunchCounters(
    _MOE_NAMES + tuple("mla_" + name for name in _MLA_NAMES)
    + tuple("conv_" + name for name in _CONV_NAMES), _add_moe)
_ssm_launches = _LaunchCounters(
    ("launches_total", "documents_total", "tokens_total", "bucket_tokens_total"),
    _add_ssm)


#: launches of a forward with routed experts by the grouped product's
#: implementation its program was traced with (``pathway_moe_grouped_launches_total``)
_moe_grouped_lock = threading.Lock()
_moe_grouped_launches: dict[str, int] = {}


def record_moe_launch(counters: Any, grouped_impl: str | None = None) -> None:
    """One launch of a forward with routed experts.  ``counters`` is the
    int32 device array the forward returned beside its result: token-expert
    pairs routed to an expert held here and held experts that got a token
    (summed over the routed layers), each layer's fullest expert summed, and
    the fullest of all; from a forward with latent attention four more
    behind them (documents, real tokens, the tokens of its bucket, the
    (query, key) pairs its causal mask let through: :func:`mla_stats`), from
    one with conv layers the first three of those (:func:`conv_stats`).
    Launches that have finished
    are added up; this one waits in line until a later call or
    :func:`moe_stats`.  ``grouped_impl`` (``"pallas"`` or ``"xla"``, what
    the program's grouped product was traced with) is counted at once, on
    the host (:func:`moe_grouped_stats`)."""
    if grouped_impl is not None:
        with _moe_grouped_lock:
            _moe_grouped_launches[grouped_impl] = _moe_grouped_launches.get(grouped_impl, 0) + 1
    _moe_launches.record(counters)


def moe_grouped_stats() -> dict[str, int]:
    """Launches of a forward with routed experts by the implementation of
    its grouped product (``pathway_moe_grouped_launches_total{impl=}``)."""
    with _moe_grouped_lock:
        return dict(_moe_grouped_launches)


def moe_stats(wait: bool = True) -> dict[str, int]:
    """The ``pathway_moe_*`` counters over every launch so far.  ``wait``
    waits for the launches still in flight; a scrape does not (it holds
    the lock the launching thread takes) and counts them the next time."""
    totals = _moe_launches.stats(wait)
    return {name: totals[name] for name in _MOE_NAMES}


def mla_stats(wait: bool = True) -> dict[str, int]:
    """The ``pathway_mla_*`` counters over every launch of a forward with
    latent attention so far (they ride the array :func:`record_moe_launch`
    takes); ``wait`` as :func:`moe_stats`."""
    totals = _moe_launches.stats(wait)
    return {name: totals["mla_" + name] for name in _MLA_NAMES}


def conv_stats(wait: bool = True) -> dict[str, int]:
    """The ``pathway_conv_*`` counters over every launch of a forward with
    conv layers so far (they ride the array :func:`record_moe_launch`
    takes); ``wait`` as :func:`moe_stats`."""
    totals = _moe_launches.stats(wait)
    return {name: totals["conv_" + name] for name in _CONV_NAMES}


def record_ssm_launch(counters: Any) -> None:
    """One launch of a forward with state-space layers.  ``counters`` is the
    int32 device array the forward returned beside its result: launches
    (1), documents, real tokens, the tokens of its bucket.  It waits in line
    as :func:`record_moe_launch`'s does."""
    _ssm_launches.record(counters)


def ssm_stats(wait: bool = True) -> dict[str, int]:
    """The ``pathway_ssm_*`` counters over every launch so far; ``wait`` as
    :func:`moe_stats`."""
    return _ssm_launches.stats(wait)


# ---------------------------------------------------------------------------
# XLA compile counters (pathway_xla_compile_total{site=...})
# ---------------------------------------------------------------------------

_compile_lock = threading.Lock()
_compile_counts: dict[str, int] = {}


def record_xla_compile(site: str, n: int = 1) -> None:
    with _compile_lock:
        _compile_counts[site] = _compile_counts.get(site, 0) + n


def compile_stats() -> dict[str, int]:
    with _compile_lock:
        return dict(_compile_counts)


def instrument_jit(jit_fn: Any, site: str) -> Any:
    """Wrap a jitted callable so cache growth (``_cache_size()``) bumps
    ``pathway_xla_compile_total{site=...}`` — the observable form of the
    bucket_q/bucket_k no-recompile guarantees.  ``_cache_size`` and the
    underlying function stay reachable on the wrapper (tests poke both).
    Degrades to a passthrough if the installed JAX drops the API."""
    state = {"seen": 0}

    def wrapper(*args: Any, **kwargs: Any) -> Any:
        out = jit_fn(*args, **kwargs)
        try:
            size = jit_fn._cache_size()
        except Exception:  # noqa: BLE001 — JAX internals moved; stop counting
            return out
        if size > state["seen"]:
            record_xla_compile(site, size - state["seen"])
            state["seen"] = size
        return out

    wrapper.__name__ = getattr(jit_fn, "__name__", site)
    wrapper.__doc__ = getattr(jit_fn, "__doc__", None)
    wrapper.__wrapped__ = jit_fn
    try:
        wrapper._cache_size = jit_fn._cache_size
    except AttributeError:
        pass
    return wrapper


# ---------------------------------------------------------------------------
# OpenMetrics lines pulled by internals/monitoring.py
# ---------------------------------------------------------------------------


def observability_metrics_lines() -> list[str]:
    """Stage histograms + compile counters + recorder counter, rendered
    for the ``/status`` exposition (monitoring.py appends these)."""
    lines: list[str] = []
    with _stage_lock:
        stage_items = [(name, hist) for name, hist in sorted(_stage_hists.items())]
        if stage_items:
            lines.append("# TYPE pathway_request_stage_ms histogram")
            for name, hist in stage_items:
                lines.extend(
                    hist.openmetrics_lines(
                        "pathway_request_stage_ms",
                        f'stage="{escape_label_value(name)}"',
                    )
                )
    compiles = compile_stats()
    if compiles:
        lines.append("# TYPE pathway_xla_compile_total counter")
        for site, n in sorted(compiles.items()):
            lines.append(
                f'pathway_xla_compile_total{{site="{escape_label_value(site)}"}} {n}'
            )
    rec = get_recorder()
    lines.append("# TYPE pathway_flight_recorder_spans_total counter")
    lines.append(
        f"pathway_flight_recorder_spans_total {rec.stats()['recorded_total']}"
    )
    # ring-overflow visibility: spans evicted before any read, per
    # category — the "did we silently drop the evidence" counter
    dropped = rec.dropped_by_category()
    lines.append("# TYPE pathway_trace_dropped_total counter")
    if dropped:
        for cat in sorted(dropped):
            lines.append(
                f'pathway_trace_dropped_total{{category="'
                f'{escape_label_value(cat)}"}} {dropped[cat]}'
            )
    else:
        lines.append("pathway_trace_dropped_total 0")
    ing = ingest_stats()
    lines.append("# TYPE pathway_ingest_docs_total counter")
    lines.append(f"pathway_ingest_docs_total {ing['docs_total']}")
    lines.append("# TYPE pathway_inputs_rows_total counter")
    lines.append(f"pathway_inputs_rows_total {ing['inputs_rows']}")
    lines.append("# TYPE pathway_embed_padding_efficiency gauge")
    lines.append(
        f"pathway_embed_padding_efficiency {ing['padding_efficiency']:.4f}"
    )
    lines.append("# TYPE pathway_embed_intra_bucket_efficiency gauge")
    lines.append(
        "pathway_embed_intra_bucket_efficiency "
        f"{ing['intra_bucket_efficiency']:.4f}"
    )
    for family, totals in (("moe", moe_stats(wait=False)), ("mla", mla_stats(wait=False)),
                           ("conv", conv_stats(wait=False)), ("ssm", ssm_stats(wait=False))):
        if totals["launches_total"]:
            for name, value in totals.items():
                kind = "counter" if name.endswith(("_total", "_sum")) else "gauge"
                lines.append(f"# TYPE pathway_{family}_{name} {kind}")
                lines.append(f"pathway_{family}_{name} {value}")
    grouped = moe_grouped_stats()
    if grouped:
        lines.append("# TYPE pathway_moe_grouped_launches_total counter")
        for impl, n in sorted(grouped.items()):
            lines.append(
                f'pathway_moe_grouped_launches_total{{impl="{escape_label_value(impl)}"}} {n}'
            )
    impls = attention_impl_stats()
    if impls:
        lines.append("# TYPE pathway_attention_impl gauge")
        for impl, n in sorted(impls.items()):
            lines.append(
                f'pathway_attention_impl{{impl="{escape_label_value(impl)}"}} {n}'
            )
    # per-encoder labels so two caches in one server (e.g. the ingest
    # tokenizer next to the query-embedding cache's key pass) don't
    # alias; the unlabeled process total is the no-label-set fallback
    # when nothing recorded an encoder yet
    with _ingest_lock:
        by_encoder = {
            enc: tuple(s) for enc, s in _tokenizer_cache_by_encoder.items()
        }
    lines.append("# TYPE pathway_tokenizer_cache_hits_total counter")
    if by_encoder:
        for enc in sorted(by_encoder):
            lines.append(
                f'pathway_tokenizer_cache_hits_total{{encoder="'
                f'{escape_label_value(enc)}"}} {by_encoder[enc][0]}'
            )
    else:
        lines.append(
            f"pathway_tokenizer_cache_hits_total {ing['tokenizer_cache_hits']}"
        )
    lines.append("# TYPE pathway_tokenizer_cache_misses_total counter")
    if by_encoder:
        for enc in sorted(by_encoder):
            lines.append(
                f'pathway_tokenizer_cache_misses_total{{encoder="'
                f'{escape_label_value(enc)}"}} {by_encoder[enc][1]}'
            )
    else:
        lines.append(
            "pathway_tokenizer_cache_misses_total "
            f"{ing['tokenizer_cache_misses']}"
        )
    return lines


def reset_stage_metrics() -> None:
    """Test isolation hook."""
    with _stage_lock:
        _stage_hists.clear()
    with _compile_lock:
        _compile_counts.clear()
    with _ingest_lock:
        for k in _ingest_counters:
            _ingest_counters[k] = 0
        _tokenizer_cache_by_encoder.clear()
        # _attn_impls is deliberately NOT cleared: it is configuration
        # state (which kernel the live encoders serve with), recorded
        # only at construction — a stats reset must not blank the
        # /v1/health attention_impl while the same encoder keeps serving
