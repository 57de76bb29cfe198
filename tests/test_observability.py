"""Observability-plane tests (ISSUE 4): W3C trace propagation, the
in-process flight recorder + /v1/debug/traces, per-stage request spans
through the serving scheduler, OpenMetrics strictness (escaping, types,
histogram consistency), freshness watermarks, XLA compile counters, and
the metric-name registry lint that keeps future PRs honest."""

import json
import re
import socket
import time
import urllib.request
from collections import deque

import pytest

import pathway_tpu as pw
from pathway_tpu.internals import flight_recorder as fr
from pathway_tpu.internals.metrics_names import (
    METRICS,
    declared_metric_names,
    escape_label_value,
)
from pathway_tpu.internals.monitoring import (
    INGEST_SEGMENTS,
    StatsMonitor,
    get_freshness,
    start_http_server_thread,
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait(call, timeout=30.0):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            out = call()
            if out:
                return out
        except Exception as exc:  # noqa: BLE001 — server still starting
            last = exc
        time.sleep(0.25)
    raise TimeoutError(f"condition never met: {last}")


# ---------------------------------------------------------------------------
# trace context + flight recorder units
# ---------------------------------------------------------------------------


def test_traceparent_parse_format_roundtrip():
    tid, sid = "ab" * 16, "cd" * 8
    header = fr.format_traceparent(tid, sid)
    assert fr.parse_traceparent(header) == (tid, sid)
    assert fr.parse_traceparent(header.upper()) == (tid, sid)  # case-insensitive
    assert fr.parse_traceparent(None) is None
    assert fr.parse_traceparent("not-a-traceparent") is None
    # all-zero ids are invalid per the W3C spec
    assert fr.parse_traceparent("00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None
    assert fr.parse_traceparent("00-" + "a" * 32 + "-" + "0" * 16 + "-01") is None


def test_flight_recorder_ring_bounds_and_filters():
    rec = fr.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record(f"s{i}", "x", float(i), float(i))
    spans = rec.spans()
    assert len(spans) == 8, "ring must stay bounded"
    assert spans[0].name == "s12" and spans[-1].name == "s19"
    assert [s.name for s in rec.spans(min_duration_ms=18.0)] == ["s18", "s19"]
    rec.record("traced", "y", 0.0, 1.0, trace_id="ab12")
    assert [s.name for s in rec.spans(trace_id="ab12")] == ["traced"]
    assert [s.name for s in rec.spans(category="y")] == ["traced"]
    assert rec.stats()["recorded_total"] == 21
    # capacity 0 disables recording entirely
    off = fr.FlightRecorder(capacity=0)
    off.record("z", "x", 0.0, 1.0)
    assert not off.enabled and off.spans() == []


def test_request_trace_builds_parented_stage_spans():
    trace = fr.start_request("POST /x", fr.format_traceparent("ef" * 16, "12" * 8))
    assert trace.trace_id == "ef" * 16
    assert trace.remote_parent == "12" * 8
    with trace.stage("embed"):
        time.sleep(0.002)
    with trace.stage("search"):
        pass
    trace.finish(status=200)
    trace.finish(status=200)  # idempotent
    spans = fr.get_recorder().spans(trace_id="ef" * 16)
    root = [s for s in spans if s.name == "POST /x"]
    assert len(root) == 1
    root = root[0]
    assert root.parent_id == "12" * 8  # remote parent preserved
    assert root.attrs["http.status"] == 200
    children = {s.name: s for s in spans if s.parent_id == root.span_id}
    assert {"embed", "search"} <= set(children)
    assert children["embed"].duration_ms >= 1.0


def test_trace_sampling_zero_keeps_id_but_records_nothing():
    fr.configure_tracing(sample=0.0)
    try:
        trace = fr.start_request("GET /y", None)
        assert trace.trace_id and not trace.sampled
        before = fr.get_recorder().stats()["recorded_total"]
        with trace.stage("embed"):
            pass
        trace.finish(status=200)
        assert fr.get_recorder().stats()["recorded_total"] == before
    finally:
        fr.configure_tracing(sample=1.0)


def test_batch_stage_attributes_to_every_trace_in_scope():
    """One device batch serves many requests: its stage timers must stamp
    every riding trace (the scheduler-tick attribution model)."""
    traces = [fr.start_request(f"POST /r{i}", None) for i in range(3)]
    with fr.batch_traces(traces):
        with fr.batch_stage("embed"):
            time.sleep(0.001)
    for t in traces:
        assert [s[0] for s in t.stages()] == ["embed"]
    # no scope, no effect (engine-plane work without traces)
    with fr.batch_stage("embed"):
        pass


def test_perfetto_export_shape():
    rec = fr.FlightRecorder(capacity=16)
    rec.record("flush:op", "engine", 100.0, 2.0, attrs={"rows": 3})
    rec.record("req", "request", 100.0, 5.0, trace_id="aa" * 16, span_id="b" * 16)
    doc = fr.FlightRecorder.perfetto(rec.spans())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2 and len(metas) == 2  # one lane per category/trace
    req = [e for e in xs if e["name"] == "req"][0]
    assert req["ts"] == pytest.approx(100.0 * 1e6)
    assert req["dur"] == pytest.approx(5000.0)
    assert req["args"]["trace_id"] == "aa" * 16


# ---------------------------------------------------------------------------
# OTel emission (API-level fake; real SDK exporter when installed)
# ---------------------------------------------------------------------------


def test_otel_spans_emitted_with_request_stage_parentage(monkeypatch):
    """The OTel emission path must parent every stage span under the
    request span (checked through the real API's context plumbing with a
    capturing tracer — the SDK is optional in this image)."""
    from opentelemetry import trace as otel_trace
    from opentelemetry.trace import NonRecordingSpan, SpanContext, TraceFlags

    import random

    emitted = []

    # must be a real otel Span subclass: get_current_span() type-checks
    # against the ABC and hides anything else behind INVALID_SPAN
    class FakeSpan(NonRecordingSpan):
        def __init__(self, name, parent):
            super().__init__(
                SpanContext(
                    random.getrandbits(127) + 1,
                    random.getrandbits(63) + 1,
                    is_remote=False,
                    trace_flags=TraceFlags(TraceFlags.SAMPLED),
                )
            )
            self.name = name
            self.parent = parent

        def end(self, end_time=None):
            self.end_time = end_time

    class FakeTracer:
        def start_span(self, name, context=None, start_time=None, attributes=None):
            parent = (
                otel_trace.get_current_span(context) if context is not None else None
            )
            span = FakeSpan(name, parent)
            emitted.append(span)
            return span

    monkeypatch.setattr(fr, "_sdk_tracer", lambda: FakeTracer())
    trace = fr.start_request("POST /v1/retrieve", None)
    with trace.stage("queue_wait"):
        pass
    with trace.stage("embed"):
        pass
    with trace.stage("search"):
        pass
    trace.finish(status=200)

    assert [s.name for s in emitted] == [
        "POST /v1/retrieve", "queue_wait", "embed", "search",
    ]
    root = emitted[0]
    assert root.parent is None or not isinstance(root.parent, FakeSpan)
    for child in emitted[1:]:
        assert child.parent is root, f"{child.name} not parented under request"
    assert all(hasattr(s, "end_time") for s in emitted), "spans must be ended"


def test_otel_in_memory_exporter_parentage():
    """Full-SDK variant: runs only where opentelemetry-sdk is installed."""
    pytest.importorskip("opentelemetry.sdk")
    from opentelemetry.sdk.trace import TracerProvider
    from opentelemetry.sdk.trace.export import SimpleSpanProcessor
    from opentelemetry.sdk.trace.export.in_memory_span_exporter import (
        InMemorySpanExporter,
    )

    exporter = InMemorySpanExporter()
    provider = TracerProvider()
    provider.add_span_processor(SimpleSpanProcessor(exporter))
    tracer = provider.get_tracer("pathway_tpu.request")
    old = fr._otel_tracer
    fr._otel_tracer = tracer
    try:
        trace = fr.start_request("POST /v1/retrieve", None)
        with trace.stage("embed"):
            pass
        trace.finish(status=200)
    finally:
        fr._otel_tracer = old
    spans = exporter.get_finished_spans()
    by_name = {s.name: s for s in spans}
    root = by_name["POST /v1/retrieve"]
    child = by_name["embed"]
    assert child.parent is not None
    assert child.parent.span_id == root.context.span_id


# ---------------------------------------------------------------------------
# end-to-end: traced serving through the scheduler
# ---------------------------------------------------------------------------


@pytest.fixture
def corpus_dir(tmp_path):
    for i in range(5):
        (tmp_path / f"doc{i}.txt").write_text(
            f"Document {i} about topic-{i % 2} with unique marker m{i}."
        )
    return tmp_path


def _start_server(corpus_dir):
    from pathway_tpu.xpacks.llm import mocks
    from pathway_tpu.xpacks.llm.vector_store import (
        VectorStoreClient,
        VectorStoreServer,
    )

    docs = pw.io.fs.read(
        corpus_dir, format="binary", mode="streaming", with_metadata=True,
        refresh_interval=0.2,
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=8))
    port = _free_port()
    vs.run_server(
        host="127.0.0.1", port=port, threaded=True, with_cache=False,
        with_scheduler=True,
    )
    return vs, VectorStoreClient(host="127.0.0.1", port=port), port


def test_request_trace_end_to_end(corpus_dir):
    """Acceptance pin: /v1/retrieve under the scheduler returns an
    x-pathway-trace-id whose queue_wait/embed/search breakdown is
    retrievable from /v1/debug/traces; freshness collapses to ~0 after
    ingest; the stage histograms + freshness + compile series render on a
    /status scrape."""
    _vs, client, port = _start_server(corpus_dir)
    probe = "Document 2 about topic-0 with unique marker m2."
    res = _wait(lambda: client.query(probe, k=2))
    assert res[0]["text"] == probe
    trace_id = client.last_trace_id
    assert trace_id and re.fullmatch(r"[0-9a-f]{32}", trace_id)

    # per-stage breakdown by trace id
    body = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/debug/traces?trace_id={trace_id}",
            timeout=10,
        ).read()
    )
    spans = body["spans"]
    roots = [s for s in spans if s["name"].startswith("POST /v1/retrieve")]
    assert len(roots) == 1
    root = roots[0]
    children = {
        s["name"]: s for s in spans if s.get("parent_id") == root["span_id"]
    }
    assert {"queue_wait", "embed", "search", "serialize"} <= set(children)
    stage_sum = sum(s["duration_ms"] for s in children.values())
    assert stage_sum <= root["duration_ms"] * 1.5 + 5.0  # stages nest in root

    # duration-floor filter drops the fast spans
    floored = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/debug/traces"
            f"?trace_id={trace_id}&min_ms={root['duration_ms'] + 1000}",
            timeout=10,
        ).read()
    )
    assert floored["spans"] == []

    # perfetto export is chrome://tracing-loadable JSON
    perfetto = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/debug/traces?format=perfetto"
            f"&trace_id={trace_id}",
            timeout=10,
        ).read()
    )
    assert any(e.get("ph") == "X" for e in perfetto["traceEvents"])

    # caller-sent W3C traceparent is adopted, not replaced
    sent_tid = "ab" * 16
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/retrieve",
        data=json.dumps({"query": probe, "k": 1}).encode(),
        headers={
            "Content-Type": "application/json",
            "traceparent": fr.format_traceparent(sent_tid, "cd" * 8),
        },
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        assert resp.headers["x-pathway-trace-id"] == sent_tid

    # freshness: the ingested docs' lag was observed and is small.
    # Restrict to THIS server's observations (age < the test's lifetime) —
    # the tracker is process-global and other tests' servers also record
    freshness = {
        name: v
        for name, v in get_freshness().stats().items()
        if v["age_s"] < 120.0
    }
    assert freshness, "no index freshness recorded after ingest"
    lag = min(v["lag_s"] for v in freshness.values())
    assert 0.0 <= lag < 30.0

    # /status scrape: the observability series render (strict parse below
    # has its own test; here pin the acceptance series)
    monitor = StatsMonitor()
    server = start_http_server_thread(monitor, port=_free_port())
    try:
        status = urllib.request.urlopen(
            f"http://127.0.0.1:{server.server_address[1]}/status", timeout=10
        ).read().decode()
    finally:
        server.shutdown()
    for needle in (
        'pathway_request_stage_ms_bucket{stage="queue_wait"',
        'pathway_request_stage_ms_bucket{stage="embed"',
        'pathway_request_stage_ms_bucket{stage="search"',
        'pathway_request_stage_ms_count{stage="total"}',
        "pathway_index_freshness_seconds{index=",
        "pathway_xla_compile_total{site=",
        "pathway_flight_recorder_spans_total",
        # the served ingest's batches, in seven segments (ISSUE 38)
        *(f'pathway_request_stage_ms_count{{stage="{stage}"}}'
          for stage in INGEST_SEGMENTS),
    ):
        assert needle in status, f"missing on /status: {needle}"


# ---------------------------------------------------------------------------
# OpenMetrics strictness
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (-?(?:\d+\.?\d*(?:[eE][+-]?\d+)?|inf|nan))$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
#: OpenMetrics exemplar: `# {labelset} value [timestamp]` after a sample
_EXEMPLAR_RE = re.compile(
    r'^\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\} '
    r"-?\d+\.?\d*(?:[eE][+-]?\d+)?(?: \d+\.?\d*)?$"
)


def _strict_parse(body: str):
    """Strict-ish OpenMetrics text parse: TYPE declared before samples,
    consistent re-declarations only, parseable samples/labels, histogram
    bucket monotonicity and _bucket/_sum/_count consistency, exemplar
    syntax on ``# {...}``-suffixed samples (histogram buckets only),
    # EOF last."""
    lines = body.rstrip("\n").split("\n")
    assert lines[-1] == "# EOF", "exposition must end with # EOF"
    types: dict[str, str] = {}
    samples: list[tuple[str, str, dict, float]] = []
    for line in lines[:-1]:
        assert line == line.strip() and line, f"ragged line: {line!r}"
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, f"malformed TYPE line: {line!r}"
            _, _, family, kind = parts
            if family in types:
                assert types[family] == kind, f"conflicting TYPE for {family}"
            types[family] = kind
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None and " # " in line:
            # exemplar-carrying sample: validate the exemplar half, then
            # parse the sample half normally (only histogram _bucket
            # lines carry exemplars here)
            line, exemplar = line.split(" # ", 1)
            assert _EXEMPLAR_RE.match(exemplar), f"malformed exemplar: {exemplar!r}"
            assert "_bucket" in line, f"exemplar on a non-bucket sample: {line!r}"
            m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample: {line!r}"
        name, labels_raw, value = m.group(1), m.group(2) or "", m.group(3)
        labels = dict(_LABEL_RE.findall(labels_raw))
        reconstructed = ",".join(f'{k}="{v}"' for k, v in labels.items())
        assert reconstructed == labels_raw, f"malformed labels: {labels_raw!r}"
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and types.get(base) == "histogram":
                family = base
        assert family in types, f"sample before TYPE declaration: {line!r}"
        samples.append((family, name, labels, float(value)))
    # histogram consistency per (family, labels-minus-le)
    series: dict = {}
    for family, name, labels, value in samples:
        if types[family] != "histogram":
            continue
        key = (family, tuple(sorted((k, v) for k, v in labels.items() if k != "le")))
        slot = series.setdefault(key, {"buckets": [], "sum": None, "count": None})
        if name.endswith("_bucket"):
            slot["buckets"].append((labels["le"], value))
        elif name.endswith("_sum"):
            slot["sum"] = value
        elif name.endswith("_count"):
            slot["count"] = value
    for key, slot in series.items():
        assert slot["buckets"], f"histogram without buckets: {key}"
        assert slot["buckets"][-1][0] == "+Inf", f"no +Inf bucket: {key}"
        counts = [v for _, v in slot["buckets"]]
        assert counts == sorted(counts), f"non-cumulative buckets: {key}"
        assert slot["count"] == counts[-1], f"_count != +Inf bucket: {key}"
        assert slot["sum"] is not None, f"histogram without _sum: {key}"
    return types, samples


def test_status_exposition_is_strictly_parseable():
    from pathway_tpu.xpacks.llm._scheduler import ServingScheduler, WorkGroup

    monitor = StatsMonitor()
    # exercise every emitter family, including hostile label values
    monitor.record_flush('op"quoted\\back\nslash', 2, 0.0015)
    monitor.record_flush("plain_op", 1, 0.1)
    monitor.record_connector_commit('conn"1', 7)
    monitor.record_connector_finished('conn"1')
    monitor.record_step(42)
    sched = ServingScheduler(max_wait_ms=5, name='om"strict')
    group = WorkGroup("echo", lambda xs: xs)
    assert sched.submit(group, 1).result(timeout=5) == 1
    fr.observe_stage("embed", 1.25)
    fr.record_xla_compile("test.site", 2)
    get_freshness().note_ingest(7)
    get_freshness().note_indexed('index"7', 7)

    server = start_http_server_thread(monitor, port=_free_port())
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{server.server_address[1]}/status", timeout=10
        ).read().decode()
    finally:
        server.shutdown()

    types, samples = _strict_parse(body)
    sample_families = {family for family, _, _, _ in samples}
    for family in (
        "pathway_operator_rows_total",
        "pathway_operator_flush_ms",
        "pathway_connector_messages_total",
        "pathway_scheduler_wait_ms",
        "pathway_request_stage_ms",
        "pathway_index_freshness_seconds",
        "pathway_xla_compile_total",
        "pathway_errors_last_minute",
    ):
        assert family in sample_families, f"family missing from /status: {family}"
    # every emitted family is registry-declared with the declared type
    for family in types:
        assert family in METRICS, f"undeclared family emitted: {family}"
        assert types[family] == METRICS[family][0], family
    # hostile labels round-tripped through escaping
    ops = {
        labels.get("operator")
        for _, name, labels, _ in samples
        if name == "pathway_operator_rows_total"
    }
    assert 'op\\"quoted\\\\back\\nslash' in ops


def test_escape_label_value_spec_order():
    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"
    # backslash escaped first — a pre-escaped quote must not double-escape
    assert escape_label_value('\\"') == '\\\\\\"'
    assert escape_label_value(123) == "123"


def test_metric_registry_lint_no_undeclared_series():
    """Grep the package for emitted pathway_* literals; every one must be
    a declared family (or a histogram suffix of one) — silent metric
    drift fails here before it breaks a dashboard."""
    import pathlib

    root = pathlib.Path(pw.__file__).parent
    # lookbehind: `_pathway_endpoint` / `get_pathway_config` are python
    # identifiers, not metric emissions
    pattern = re.compile(r"(?<![A-Za-z0-9_])pathway_[a-z][a-z0-9_]*")
    allowed = declared_metric_names()
    offenders: dict[str, list[str]] = {}
    for path in sorted(root.rglob("*.py")):
        for literal in set(pattern.findall(path.read_text())):
            if literal.startswith("pathway_tpu"):
                continue  # the package's own name, not a metric
            if literal in allowed:
                continue
            # allow bare prefixes of declared families only when they are
            # format-string stems (e.g. "pathway_scheduler_" + metric)
            if any(name.startswith(literal) for name in allowed):
                continue
            offenders.setdefault(literal, []).append(
                str(path.relative_to(root))
            )
    assert not offenders, (
        f"undeclared pathway_* series emitted (declare in "
        f"internals/metrics_names.py): {offenders}"
    )


# ---------------------------------------------------------------------------
# satellites: RSS units, deque window, server close, compile counter
# ---------------------------------------------------------------------------


def test_sys_metrics_rss_normalized_to_bytes():
    import inspect

    from pathway_tpu.internals.telemetry import Telemetry, max_rss_bytes

    metrics = Telemetry().sys_metrics()
    assert "process.memory.max_rss_bytes" in metrics
    assert "process.memory.max_rss_kb" not in metrics
    rss = metrics["process.memory.max_rss_bytes"]
    assert rss == max_rss_bytes()
    # a CPython test process with JAX loaded sits far above 10 MB; the KB
    # value un-multiplied would fail this on Linux
    assert 10 * 1024**2 < rss < 10 * 1024**4
    # the dead `enabled` knob is gone
    assert "enabled" not in inspect.signature(Telemetry.__init__).parameters


def test_connector_window_uses_deque_and_prunes():
    monitor = StatsMonitor()
    monitor.record_connector_commit("c", 1)
    assert isinstance(monitor.connector_recent["c"], deque)
    # an entry older than the 60 s window is pruned by the next commit
    monitor.connector_recent["c"].appendleft((time.time() - 120.0, 99))
    monitor.record_connector_commit("c", 2)
    stats = monitor.connector_stats("c")
    assert stats["num_messages_in_last_minute"] == 3
    assert stats["num_messages_from_start"] == 3
    assert all(t > time.time() - 61 for t, _ in monitor.connector_recent["c"])


def test_start_http_server_thread_closes_previous_server():
    monitor = StatsMonitor()
    port = _free_port()
    first = start_http_server_thread(monitor, port=port)
    assert first.server_address[1] == port
    # rebinding the SAME port succeeds because the previous server (and
    # its socket) are shut down first — this leaked before
    second = start_http_server_thread(monitor, port=port)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status", timeout=10
        ).read().decode()
        assert body.endswith("# EOF\n")
    finally:
        second.shutdown()


def test_xla_compile_counter_pins_no_recompile_buckets():
    """pathway_xla_compile_total{site="serving.fused_topk"} is the
    observable form of the bucket_q/bucket_k guarantee: after warming
    the buckets, heterogeneous (Q, k) serving traffic adds ZERO
    compilations.  The fused serving jit folds query prep into the same
    dispatch, so the pin now also covers the prep stage."""
    import numpy as np

    from pathway_tpu.ops.knn import DeviceKnnIndex

    idx = DeviceKnnIndex(dim=8, capacity=64)
    rng = np.random.default_rng(0)
    for i in range(40):
        idx.upsert(i, rng.standard_normal(8))
    # warm one variant per k bucket in play (k<=8 -> buckets 4 and 8)
    idx.search(rng.standard_normal((3, 8)), k=4)
    idx.search(rng.standard_normal((3, 8)), k=8)
    warm = fr.compile_stats().get("serving.fused_topk", 0)
    assert warm >= 1, "compile counter never observed a compilation"
    for k in (3, 4, 5, 6, 7, 8):
        for q in (1, 2, 5, 8):
            idx.search(rng.standard_normal((q, 8)), k=k)
    assert fr.compile_stats().get("serving.fused_topk", 0) == warm, (
        "a bucketed (Q, k) combination recompiled — the no-recompile "
        "guarantee regressed"
    )
    # scatter sites counted too (upserts compiled at least once)
    assert fr.compile_stats().get("knn.scatter_rows", 0) >= 1


# ---------------------------------------------------------------------------
# ISSUE 15 tentpole: unified HBM ledger
# ---------------------------------------------------------------------------


def _ledger_map():
    from pathway_tpu.observability.hbm_ledger import get_ledger

    out = {}
    for component, shard, b in get_ledger().entries():
        out.setdefault(component, {})[shard] = b
    return out


def test_hbm_ledger_exact_for_device_index_dtypes():
    """Off-TPU the ledger is exact by construction: each index's
    component entry equals its own hbm_bytes() self-report, across
    storage dtypes, and the staged-scatter debt entry drains to zero
    once a search applies the staged rows."""
    import numpy as np

    from pathway_tpu.ops.knn import DeviceKnnIndex

    rng = np.random.default_rng(0)
    f32 = DeviceKnnIndex(dim=8, capacity=64)
    i8 = DeviceKnnIndex(dim=8, capacity=64, index_dtype="int8")
    for i in range(16):
        f32.upsert(i, rng.standard_normal(8))
        i8.upsert(i, rng.standard_normal(8))
    ledger = _ledger_map()
    for idx in (f32, i8):
        assert ledger[f"knn:{idx.quant_label}"][None] == idx.hbm_bytes()
        assert ledger[f"knn_staged:{idx.quant_label}"][None] == idx.staged_hbm_bytes()
    # staged-debt entry drains with the apply (search flushes staging)
    f32.search(rng.standard_normal((1, 8)), k=2)
    assert _ledger_map()[f"knn_staged:{f32.quant_label}"][None] == 0


@pytest.mark.parametrize("mesh_n", [1, 2])
def test_hbm_ledger_sharded_shards_sum_exactly(mesh_n):
    import numpy as np

    from pathway_tpu.parallel import make_mesh
    from pathway_tpu.parallel.index import ShardedKnnIndex

    idx = ShardedKnnIndex(dim=16, mesh=make_mesh(mesh_n), capacity=64)
    rng = np.random.default_rng(1)
    for i in range(24):
        idx.upsert(i, rng.standard_normal(16))
    shards = _ledger_map()[f"knn:{idx.quant_label}"]
    assert set(shards) == {str(i) for i in range(mesh_n)}
    assert sum(shards.values()) == idx.hbm_bytes(), (
        "per-shard ledger rows must sum to the index's own self-report"
    )


def test_hbm_ledger_tiered_and_paged_kv_session():
    """The tiered index's hot tier registers through its DeviceKnnIndex,
    the router's centroid matrix registers separately, and a live
    paged-KV session's block pools appear under kv_pool:<name> — each
    equal to the subsystem's own report."""
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.generation.engine import DecodeSession
    from pathway_tpu.models.decoder import CausalLM, DecoderConfig
    from pathway_tpu.tiering.index import TieredKnnIndex

    tiered = TieredKnnIndex(
        dim=16, hot_rows=32, capacity=128, n_partitions=8,
        probe_partitions=2, migrate_batch=0,
    )
    rng = np.random.default_rng(2)
    tiered.upsert_batch(list(range(48)), rng.standard_normal((48, 16)))
    cfg = DecoderConfig(
        vocab_size=97, hidden_dim=32, num_layers=1, num_heads=2, mlp_dim=64,
        max_len=64, dtype=jnp.float32,
    )
    lm = CausalLM(cfg=cfg, seed=0)
    session = DecodeSession(
        cfg, lm.params, auto=False, pool_tokens=256, block_size=16,
        name="ledger-test",
    )
    ledger = _ledger_map()
    assert (
        ledger[f"knn:{tiered.hot.quant_label}"][None] == tiered.hbm_bytes()
    ), "the hot tier IS the tiered index's HBM bill"
    assert ledger[f"tier_router:{tiered.tier_label}"][None] == int(
        tiered.router.centroids.nbytes
    )
    kv_components = {
        c: v for c, v in ledger.items() if c.startswith("kv_pool:ledger-test#")
    }
    assert len(kv_components) == 1
    assert next(iter(kv_components.values()))[None] == session.pool.hbm_bytes()
    # decoder params registered too, equal to the tree's own byte count
    from pathway_tpu.observability.hbm_ledger import get_ledger, tree_nbytes

    decoder_rows = [
        b
        for c, _s, b in get_ledger().entries()
        if c.startswith("decoder_params:")
    ]
    assert tree_nbytes(lm.params) in decoder_rows
    # the process total is the plain sum of every entry
    assert get_ledger().total_bytes() == sum(
        b for _c, _s, b in get_ledger().entries()
    )
    session.close()


def test_hbm_ledger_release_and_weak_owner():
    import gc

    from pathway_tpu.observability.hbm_ledger import get_ledger

    class Owner:
        pass

    led = get_ledger()
    o1, o2 = Owner(), Owner()
    t1 = led.register("test_comp:a", o1, lambda _o: 123)
    led.register("test_comp:b", o2, lambda _o: {"0": 10, "1": 20})
    rows = {(c, s): b for c, s, b in led.entries()}
    assert rows[("test_comp:a", None)] == 123
    assert rows[("test_comp:b", "0")] == 10 and rows[("test_comp:b", "1")] == 20
    led.release(t1)
    assert ("test_comp:a", None) not in {
        (c, s) for c, s, _ in led.entries()
    }
    del o2
    gc.collect()
    assert not any(c == "test_comp:b" for c, _s, _b in led.entries())


def test_hbm_ledger_reconcile_drift_flags_unattributed(monkeypatch):
    """Fake device memory stats: drift beyond PATHWAY_HBM_DRIFT_FRAC
    flags an `unattributed` component loudly (status + metric line);
    within tolerance nothing is flagged."""
    from pathway_tpu.observability import hbm_ledger as hl

    led = hl.get_ledger()

    class Owner:
        pass

    owner = Owner()
    led.register("test_recon", owner, lambda _o: 1000)
    total = led.total_bytes()
    # 50% unattributed -> flagged
    monkeypatch.setattr(
        hl, "device_memory_view",
        lambda: {"bytes_in_use": total * 2, "bytes_limit": total * 4},
    )
    recon = led.reconcile()
    assert recon["flagged"] and recon["unattributed_bytes"] == total
    status = hl.hbm_status()
    assert status["device"]["flagged"]
    lines = hl._LedgerMetricsProvider().openmetrics_lines()
    assert any("pathway_hbm_unattributed_bytes" in ln for ln in lines)
    assert any('component="unattributed"' in ln for ln in lines)
    # capacity block reports free HBM for the router
    cap = hl.capacity_status()
    assert cap["hbm_free_bytes"] == total * 2
    # within tolerance -> clear
    monkeypatch.setattr(
        hl, "device_memory_view",
        lambda: {"bytes_in_use": int(total * 1.05), "bytes_limit": total * 4},
    )
    recon = led.reconcile()
    assert not recon["flagged"]
    lines = hl._LedgerMetricsProvider().openmetrics_lines()
    assert not any("pathway_hbm_unattributed_bytes" in ln for ln in lines)


# ---------------------------------------------------------------------------
# ISSUE 15 tentpole: SLO burn-rate engine
# ---------------------------------------------------------------------------


@pytest.fixture
def slo_reset():
    from pathway_tpu.observability import slo

    slo.reset_slo()
    yield slo
    slo.reset_slo()


def test_slo_burn_rate_hand_computed(monkeypatch, slo_reset):
    """Window math pinned against hand-computed fixtures: burn =
    (bad fraction) / (error budget), latency budget fixed at 1% for a
    p99 target, availability budget = 1 - target."""
    slo = slo_reset
    monkeypatch.setenv("PATHWAY_SLO_RETRIEVE_P99_MS", "50")
    monkeypatch.setenv("PATHWAY_SLO_RETRIEVE_AVAIL", "0.99")
    monkeypatch.setenv("PATHWAY_SLO_FAST_S", "60")
    monkeypatch.setenv("PATHWAY_SLO_SLOW_S", "600")
    now = 1000.0
    for _ in range(90):
        slo.observe_request("/v1/retrieve", 10.0, 200, None, now=now)
    for _ in range(10):
        slo.observe_request("/v1/retrieve", 100.0, 200, None, now=now)
    ev = slo.slo_status(now=now)["endpoints"]["/v1/retrieve"]
    lat = ev["objectives"]["latency"]
    # 10 of 100 over target -> bad_frac 0.10 -> burn 0.10/0.01 = 10.0
    assert lat["burn_fast"] == pytest.approx(10.0)
    assert lat["burn_slow"] == pytest.approx(10.0)
    assert lat["samples_fast"] == 100
    # 10 >= warn(6) in both windows but < hot(14.4) -> warn
    assert ev["verdict"] == "warn"
    # availability: 5 of 105 five-hundreds -> bad_frac ~0.0476 -> /0.01
    for _ in range(5):
        slo.observe_request("/v1/retrieve", 10.0, 503, None, now=now)
    av = slo.slo_status(now=now)["endpoints"]["/v1/retrieve"]["objectives"][
        "availability"
    ]
    assert av["burn_fast"] == pytest.approx((5 / 105) / 0.01, abs=0.01)
    # push latency past hot in both windows -> burning
    for _ in range(20):
        slo.observe_request("/v1/retrieve", 100.0, 200, None, now=now)
    ev = slo.slo_status(now=now)["endpoints"]["/v1/retrieve"]
    assert ev["objectives"]["latency"]["burn_fast"] >= 14.4
    assert ev["verdict"] == "burning"


def test_slo_verdict_flips_burning_and_recovers(monkeypatch, slo_reset):
    """The acceptance timeline with explicit clocks: injection flips
    ok->burning within the fast window; after it stops, the fast window
    drains first (warn) and the slow window drains last (ok)."""
    slo = slo_reset
    monkeypatch.setenv("PATHWAY_SLO_RETRIEVE_P99_MS", "50")
    monkeypatch.setenv("PATHWAY_SLO_FAST_S", "10")
    monkeypatch.setenv("PATHWAY_SLO_SLOW_S", "100")
    status = lambda t: slo.slo_status(now=t)["endpoints"]["/v1/retrieve"]
    for _ in range(50):
        slo.observe_request("/v1/retrieve", 10.0, 200, None, now=5.0)
    assert status(5.0)["verdict"] == "ok"
    # synthetic latency injection: 30 slow requests at t=6
    for _ in range(30):
        slo.observe_request("/v1/retrieve", 500.0, 200, None, now=6.0)
    assert status(6.0)["verdict"] == "burning", (
        "both windows see 30/80 bad -> burn 37.5 >= 14.4"
    )
    # injection stops; healthy traffic continues
    for _ in range(20):
        slo.observe_request("/v1/retrieve", 10.0, 200, None, now=15.0)
    ev = status(20.0)
    assert ev["objectives"]["latency"]["burn_fast"] == 0.0, (
        "fast window drained: only the t=15 good samples remain in it"
    )
    assert ev["verdict"] == "warn", "slow window still carries the incident"
    # past the slow window everything ages out
    assert status(200.0)["verdict"] == "ok"


def test_slo_endpoint_env_key():
    from pathway_tpu.observability.slo import endpoint_env_key

    assert endpoint_env_key("/v1/retrieve") == "RETRIEVE"
    assert endpoint_env_key("/v1/pw_ai_answer") == "PW_AI_ANSWER"
    assert endpoint_env_key("/v1/pw_ai_answer_stream") == "PW_AI_ANSWER_STREAM"
    assert endpoint_env_key("/v2/weird-path") == "V2_WEIRD_PATH"
    assert endpoint_env_key("/") == "ROOT"


def test_slo_endpoint_cardinality_bounded(slo_reset):
    """Unknown-path scans must not mint unbounded series: past the cap
    observations aggregate under 'other'."""
    slo = slo_reset
    for i in range(200):
        slo.observe_request(f"/scan/{i}", 1.0, 404, None, now=1.0)
    status = slo.slo_status(now=1.0)
    assert len(status["endpoints"]) <= 64, "cap includes the overflow series"
    assert "other" in status["endpoints"]


# ---------------------------------------------------------------------------
# ISSUE 15: e2e — health slo/capacity blocks, exemplars, freshness, profile
# ---------------------------------------------------------------------------


def _get_json(url, timeout=10):
    req = urllib.request.Request(url)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode()), dict(resp.headers)
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read().decode() or "{}"), dict(exc.headers)


def test_health_slo_capacity_blocks_and_exemplar_resolution(
    corpus_dir, monkeypatch, slo_reset
):
    """Acceptance: after real traffic the /v1/health payload carries an
    "slo" block (burning under an aggressive target) and a "capacity"
    block (ledger totals + runtime occupancy); at least one burning
    histogram bucket line carries a parseable exemplar whose trace id
    resolves in /v1/debug/traces."""
    # every request is "bad" against a sub-microsecond target -> the
    # verdict burns within the fast window of real traffic
    monkeypatch.setenv("PATHWAY_SLO_RETRIEVE_P99_MS", "0.0001")
    monkeypatch.setenv("PATHWAY_SLO_FAST_S", "30")
    monkeypatch.setenv("PATHWAY_SLO_SLOW_S", "300")
    _vs, client, port = _start_server(corpus_dir)
    probe = "Document 2 about topic-0 with unique marker m2."
    _wait(lambda: client.query(probe, k=2))
    for _ in range(5):
        client.query(probe, k=1)

    code, health, _ = _get_json(f"http://127.0.0.1:{port}/v1/health")
    assert code == 200
    slo_block = health["slo"]
    ep = slo_block["endpoints"]["/v1/retrieve"]
    assert ep["verdict"] == "burning"
    assert ep["objectives"]["latency"]["burn_fast"] >= 14.4
    cap = health["capacity"]
    assert cap["hbm_total_bytes"] > 0
    assert any(c.startswith("knn:") for c in cap["hbm_components"])
    # (encoder_params:* appears too when the embedder is model-backed;
    # this server runs the mock UDF embedder, which holds no param tree)
    if "runtime" in cap:
        assert "queue_depth" in cap["runtime"]

    # /status scrape: exemplar-carrying burning bucket -> resolvable trace
    monitor = StatsMonitor()
    server = start_http_server_thread(monitor, port=_free_port())
    try:
        status = urllib.request.urlopen(
            f"http://127.0.0.1:{server.server_address[1]}/status", timeout=10
        ).read().decode()
    finally:
        server.shutdown()
    _strict_parse(status)  # exemplar syntax round-trips the strict parser
    exemplar_lines = [
        ln
        for ln in status.splitlines()
        if ln.startswith("pathway_endpoint_latency_ms_bucket")
        and 'endpoint="/v1/retrieve"' in ln
        and " # {trace_id=" in ln
    ]
    assert exemplar_lines, "no exemplar on the retrieve latency histogram"
    tid = re.search(r'trace_id="([0-9a-f]{32})"', exemplar_lines[-1]).group(1)
    body = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/debug/traces?trace_id={tid}",
            timeout=10,
        ).read()
    )
    assert body["spans"], "exemplar trace id must resolve in the recorder"
    # burn-rate gauges render too
    assert 'pathway_slo_burn_rate{slo="/v1/retrieve"' in status


def test_freshness_end_to_end_live_file_drop(corpus_dir):
    """pathway_freshness_seconds measures connector READ -> queryable,
    per connector, through a live file drop."""
    _vs, client, port = _start_server(corpus_dir)
    probe = "Document 2 about topic-0 with unique marker m2."
    _wait(lambda: client.query(probe, k=2))
    drop_marker = "Fresh document with unique marker freshdrop77."
    (corpus_dir / "fresh.txt").write_text(drop_marker)
    # FakeEmbedder is content-hash based: the exact text ranks itself
    # first once (and only once) the drop is ingested and queryable
    _wait(
        lambda: any(
            "freshdrop77" in r["text"]
            for r in client.query(drop_marker, k=1)
        )
    )
    lags = get_freshness().connector_lags()
    assert lags, "no end-to-end connector freshness recorded"
    # the fs connector's read->queryable lag is recent and sane
    stats = get_freshness().connector_stats()
    fresh = {k: v for k, v in stats.items() if v["age_s"] < 60.0}
    assert fresh, f"no fresh connector lag: {stats}"
    assert min(v["lag_s"] for v in fresh.values()) < 30.0
    # and it renders on the exposition under the new family
    lines = "\n".join(get_freshness().openmetrics_lines())
    assert "pathway_freshness_seconds{connector=" in lines


def test_debug_profile_endpoint_single_flight_and_artifact(
    tmp_path, monkeypatch
):
    """/v1/debug/profile: single-flight (409 for the overlapping call),
    artifact served (off-TPU: flight-recorder Perfetto JSON), 400 on a
    garbage ms, 503 when disabled."""
    import threading as _threading

    from pathway_tpu.io.http import PathwayWebserver

    monkeypatch.setenv("PATHWAY_PROFILE_DIR", str(tmp_path / "spool"))
    ws = PathwayWebserver(host="127.0.0.1", port=_free_port())
    ws._ensure_started()
    base = f"http://127.0.0.1:{ws.port}"

    results: dict = {}

    def long_capture():
        results["long"] = _get_json(f"{base}/v1/debug/profile?ms=900", timeout=30)

    th = _threading.Thread(target=long_capture)
    th.start()
    time.sleep(0.3)  # the long capture is inside its sleep window
    # a span recorded DURING the window must land in the export
    fr.record_span("bench:seed", "test", time.time(), 5.0)
    code409, body409, _ = _get_json(f"{base}/v1/debug/profile?ms=50")
    th.join(timeout=30)
    assert code409 == 409, f"overlapping capture must 409: {body409}"
    code, doc, headers = results["long"]
    assert code == 200
    assert headers.get("x-pathway-profile-kind") == "flight_recorder"
    assert "traceEvents" in doc and doc["pw_profile"]["spans"] >= 1
    # follow-up capture succeeds (single-flight released)
    code, doc, _ = _get_json(f"{base}/v1/debug/profile?ms=30")
    assert code == 200 and "traceEvents" in doc
    # garbage duration -> 400 (incl. nan/inf, which parse as floats but
    # would blow up the capture sleep)
    for bad in ("abc", "nan", "inf"):
        code, _, _ = _get_json(f"{base}/v1/debug/profile?ms={bad}")
        assert code == 400, f"ms={bad} must 400"
    # spool stays bounded
    from pathway_tpu.observability.profiler import keep_artifacts

    import os as _os

    assert len(_os.listdir(tmp_path / "spool")) <= keep_artifacts()
    # disabled -> 503
    monkeypatch.setenv("PATHWAY_PROFILE_DIR", "off")
    code, _, _ = _get_json(f"{base}/v1/debug/profile?ms=30")
    assert code == 503


def test_profile_duration_capped(tmp_path, monkeypatch):
    from pathway_tpu.observability import profiler

    monkeypatch.setenv("PATHWAY_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("PATHWAY_PROFILE_MAX_MS", "50")
    t0 = time.monotonic()
    res = profiler.capture(60_000)
    assert time.monotonic() - t0 < 5.0, "cap must bound the window"
    assert res["duration_ms"] == 50.0


# ---------------------------------------------------------------------------
# ISSUE 15 satellites: ring-drop counter, client traceparent, reverse lint
# ---------------------------------------------------------------------------


def test_flight_recorder_counts_evictions_before_read():
    rec = fr.FlightRecorder(capacity=4)
    for i in range(4):
        rec.record(f"a{i}", "catA", 0.0, 1.0)
    rec.spans()  # everything buffered has now been read
    for i in range(4):
        rec.record(f"b{i}", "catB", 0.0, 1.0)
    # the 4 evicted catA spans were read first -> not drops
    assert rec.stats()["dropped_before_read_total"] == 0
    for i in range(6):
        rec.record(f"c{i}", "catC", 0.0, 1.0)
    # 4 catB + 2 catC evicted without any intervening read
    assert rec.dropped_by_category() == {"catB": 4, "catC": 2}
    assert rec.stats()["dropped_before_read_total"] == 6
    # a filtered / limit-truncated read does NOT clear the watermark —
    # the undelivered spans were never seen, and marking them read would
    # make the counter undercount the next overflow
    rec.spans(limit=2)
    rec.spans(category="nope")
    for i in range(4):
        rec.record(f"d{i}", "catD", 0.0, 1.0)
    assert rec.stats()["dropped_before_read_total"] == 10
    # an unfiltered full read clears; the next overflow counts fresh
    rec.spans()
    rec.record("e0", "catE", 0.0, 1.0)
    assert rec.stats()["dropped_before_read_total"] == 10
    # the family renders on the exposition (global recorder; zero-safe)
    lines = "\n".join(fr.observability_metrics_lines())
    assert "pathway_trace_dropped_total" in lines


def test_rest_client_traceparent_stitches_retries():
    """A retried logical call carries ONE trace id across attempts: the
    server sees the same traceparent on the 503'd attempt and the
    successful retry."""
    import threading as _threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    from pathway_tpu.xpacks.llm._utils import RestClientBase

    seen: list = []

    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            seen.append(self.headers.get("traceparent"))
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            if len(seen) == 1:
                self.send_response(503)
                self.send_header("Retry-After", "0.01")
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")
            else:
                tid = fr.parse_traceparent(seen[-1])[0]
                body = b'{"ok": true}'
                self.send_response(200)
                self.send_header("x-pathway-trace-id", tid)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        def log_message(self, *a):
            pass

    server = HTTPServer(("127.0.0.1", 0), Handler)
    th = _threading.Thread(target=server.serve_forever, daemon=True)
    th.start()
    try:
        client = RestClientBase(
            host="127.0.0.1", port=server.server_address[1],
            retry_on_unavailable=True, backoff_initial_s=0.01,
        )
        out = client._post("/x", {"q": 1})
        assert out == {"ok": True}
    finally:
        server.shutdown()
    assert len(seen) == 2
    assert seen[0] is not None and seen[0] == seen[1], (
        f"retry minted a fresh trace: {seen}"
    )
    tid = fr.parse_traceparent(seen[0])[0]
    assert client.last_trace_id == tid
    # a second logical call mints a NEW trace (no accidental reuse)
    seen.clear()
    try:
        client._post("/x", {"q": 2})
    except Exception:
        pass


#: declared families whose emission is gated on real-TPU-only paths —
#: the reverse lint skips them so tier-1 stays green off-chip.  Keep
#: this list SHORT: a family lands here only when its emitting literal
#: genuinely cannot appear in off-TPU-importable code.
_TPU_GATED_FAMILIES: set = set()


def test_metric_registry_lint_no_orphan_declared_families():
    """Reverse of the undeclared-series lint: every family declared in
    METRICS must be emitted somewhere in the package (full literal, or a
    `stem_` format-string prefix) — a declared-but-never-emitted family
    is dashboard documentation for a series that does not exist."""
    import pathlib

    root = pathlib.Path(pw.__file__).parent
    pattern = re.compile(r"(?<![A-Za-z0-9_])pathway_[a-z][a-z0-9_]*")
    tokens: set = set()
    for path in sorted(root.rglob("*.py")):
        if path.name == "metrics_names.py":
            continue  # the declaration itself is not an emission
        tokens |= set(pattern.findall(path.read_text()))
    orphans = []
    for family in METRICS:
        if family in _TPU_GATED_FAMILIES:
            continue
        emitted = family in tokens or any(
            t.endswith("_") and family.startswith(t) and t != family
            for t in tokens
        )
        if not emitted:
            orphans.append(family)
    assert not orphans, (
        "declared but never emitted (remove from metrics_names.py or add "
        f"the emitter; TPU-gated families go in _TPU_GATED_FAMILIES): {orphans}"
    )


def test_openapi_schema_advertises_slo_knobs(corpus_dir):
    """Route registration stamps the exact PATHWAY_SLO_* knob names into
    /_schema — SLO discoverability without reading the README."""
    _vs, client, port = _start_server(corpus_dir)
    _wait(lambda: client.query("Document 2", k=1))
    schema = json.loads(
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/_schema", timeout=10
        ).read()
    )
    retrieve = schema["paths"]["/v1/retrieve"]
    knobs = next(iter(retrieve.values()))["x-pathway-slo-knobs"]
    assert "PATHWAY_SLO_RETRIEVE_P99_MS" in knobs
    assert "PATHWAY_SLO_RETRIEVE_AVAIL" in knobs


# ---------------------------------------------------------------------------
# the span primitive (ISSUE 26): ring + stage histogram + profiler host plane
# ---------------------------------------------------------------------------


def _stage_counts() -> dict[str, float]:
    """``{stage: count}`` from the exposition, as the benchmark reads it."""
    out = {}
    for line in fr.observability_metrics_lines():
        m = re.match(r'pathway_request_stage_ms_count\{stage="([^"]+)"\} (\S+)', line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def _profiled(body, tmp_path):
    """Run ``body`` inside a CPU profiler session with the harness's
    options and return the planes as ``perfbench/trace_reduce.load`` reads
    them."""
    import os
    import sys

    import jax

    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)), "perfbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import trace_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[1] + child[2] <= parent[1] + parent[2]


def test_span_writes_ring_stage_and_profiler_event(tmp_path):
    import threading

    fr.reset_recorder()
    fr.reset_stage_metrics()

    def body():
        fr.name_thread("pw-span-test")
        with fr.span("unit.work", "unit", stage="unit.stage", rows=3) as timed:
            time.sleep(0.002)
            timed.set(done=True)
        with pytest.raises(ValueError):
            with fr.span("unit.fails", "unit"):
                raise ValueError("boom")

    def run():
        th = threading.Thread(target=body)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()

    planes = _profiled(run, tmp_path)
    work, fails = fr.get_recorder().spans(category="unit")
    assert (work.name, work.attrs) == ("unit.work", {"rows": 3, "done": True})
    assert work.duration_ms >= 2.0 and abs(time.time() - work.start_s) < 60.0
    assert (fails.name, fails.attrs) == ("unit.fails", {"ok": False})
    # an earlier test's server may still poll its directory on a daemon
    # thread, and every poll stages its second pass (connector.verify)
    assert {stage: n for stage, n in _stage_counts().items()
            if stage.startswith("unit.")} == {"unit.stage": 1.0}
    names = [name for name, _s, _d in planes["host"]["pw-span-test"]]
    assert names == ["pw.unit.unit.work", "pw.unit.unit.fails"]


def test_span_never_imports_jax():
    """The recorder stays engine-hot-path adjacent: a process that has not
    loaded ``jax`` gets ring and stage and no import."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from pathway_tpu.internals import flight_recorder as fr\n"
        "with fr.span('a', 'b', stage='s', n=1):\n"
        "    pass\n"
        "fr.name_thread('pw-main')  # a no-op on the main thread\n"
        "assert [s.name for s in fr.get_recorder().spans()] == ['a']\n"
        "assert 'jax' not in sys.modules, 'span imported jax'\n"
        "print(open('/proc/self/comm').read().strip())\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() != "pw-main", "name_thread renamed the process"


def test_nested_spans_on_named_threads_reach_the_profiler_trace(tmp_path):
    """Two threads, each named, each with a child span inside a parent:
    the trace holds a host line per thread under its own name, on the
    profiler's clock, with the attrs' names intact."""
    import threading

    def worker(name):
        fr.name_thread(name)
        for _ in range(3):
            with fr.span("parent", "t", who=name):
                time.sleep(0.002)
                with fr.span("child", "t"):
                    time.sleep(0.002)

    def body():
        threads = [
            threading.Thread(target=worker, args=(n,))
            for n in ("pw-test-a", "pw-test-b")
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
            assert not th.is_alive()

    planes = _profiled(body, tmp_path)
    for name in ("pw-test-a", "pw-test-b"):
        events = planes["host"][name]
        parents = [e for e in events if e[0] == "pw.t.parent"]
        children = [e for e in events if e[0] == "pw.t.child"]
        assert len(parents) == len(children) == 3
        for child in children:
            assert sum(_inside(child, p) for p in parents) == 1
        assert all(d >= 2_000_000 for _n, _s, d in children)  # nanoseconds


def test_runtime_tick_spans_account_for_the_tick():
    """One tick on a stub group: the loop's waits and the tick's parts are
    spans with stages, and the execute span lies inside ``tick:runtime``."""
    from pathway_tpu.runtime import DeviceTickRuntime, QoS, WorkGroup

    fr.reset_recorder()
    fr.reset_stage_metrics()
    rt = DeviceTickRuntime(tick_tokens=100, max_wait_ms=20, name="t-spans")

    def work(xs):
        time.sleep(0.005)
        return xs

    group = WorkGroup("stub", work, max_batch=8)
    rt.submit(group, 0, qos=QoS.LLM_RERANK).result(timeout=30)
    time.sleep(0.05)  # the loop is back in its idle wait
    rt.submit(group, 1, qos=QoS.LLM_RERANK).result(timeout=30)

    def done():
        return _stage_counts().get("tick.run", 0) >= 2

    _wait(done)
    stages = _stage_counts()
    assert stages["tick.idle"] >= 1 and stages["tick.admit"] >= 2
    assert stages["tick.run"] == 2 and stages["tick.execute.llm_rerank"] == 2
    rec = fr.get_recorder()
    ticks = [s for s in rec.spans(category="runtime") if s.name == "tick:runtime"]
    executes = [s for s in rec.spans(category="scheduler") if s.name == "tick:stub"]
    assert len(ticks) == len(executes) == 2
    for tick, execute in zip(ticks, executes):
        assert tick.attrs["occupancy"] == 1 and tick.attrs["llm_rerank"] == 1
        assert execute.attrs == {
            "runtime": "t-spans", "qos": "llm_rerank", "occupancy": 1, "ok": True,
        }
        assert tick.start_s <= execute.start_s + 1e-4
        assert execute.duration_ms <= tick.duration_ms + 0.1
        assert execute.duration_ms >= 5.0
    waits = {s.name for s in rec.spans(category="runtime")} - {"tick:runtime"}
    assert waits == {"tick.idle", "tick.admit"}


def test_debug_profile_capture_keeps_the_python_tracer_off(tmp_path, monkeypatch):
    """``/v1/debug/profile`` on a live server takes the harness's options:
    host tracer on, Python tracer off (it made the host 25 times slower)."""
    import jax

    from pathway_tpu.observability import profiler

    seen = {}

    def start_trace(logdir, profiler_options=None, **_kw):
        seen["python"] = profiler_options.python_tracer_level
        seen["host"] = profiler_options.host_tracer_level
        import os

        os.makedirs(logdir, exist_ok=True)

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    artifact, kind = profiler._capture_jax(str(tmp_path), "t", 1.0)
    assert kind == "jax" and artifact.endswith(".zip")
    assert seen == {"python": 0, "host": 2}
