"""OpenTelemetry hooks: spans around graph build/run + runtime gauges.

reference: src/engine/telemetry.rs (OTLP traces + 60 s periodic metrics,
process mem/CPU gauges :316-350, off unless configured) and the Python
spans ``graph_runner.build`` / ``graph_runner.run``
(graph_runner/__init__.py:146,166).

Only the opentelemetry *API* ships in this image — without an SDK +
exporter configured by the embedding application, every call below is a
no-op (the API's default tracer), which matches the reference's
off-by-default posture.
"""

from __future__ import annotations

import contextlib
import sys
from typing import Any, Iterator

__all__ = ["Telemetry", "get_telemetry", "max_rss_bytes"]


def max_rss_bytes() -> int:
    """Peak RSS of this process in BYTES.  ``getrusage().ru_maxrss`` is
    kilobytes on Linux but bytes on macOS — every consumer must go
    through this one normalization instead of guessing a unit."""
    import resource

    ru_maxrss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return ru_maxrss if sys.platform == "darwin" else ru_maxrss * 1024


class Telemetry:
    def __init__(self):
        self._tracer = None
        self._meter = None
        self._monitor = None
        try:
            from opentelemetry import trace

            self._tracer = trace.get_tracer("pathway_tpu")
        except ImportError:
            pass

    @contextlib.contextmanager
    def span(self, name: str, **attributes: Any) -> Iterator[None]:
        """``with telemetry.span("graph_runner.run"): ...`` — OTel span
        when a tracer is available, and ALWAYS a flight-recorder span
        (the zero-infra trace dump must show build/run windows too)."""
        from .flight_recorder import span

        with span(name, "runtime", **attributes):
            if self._tracer is None:
                yield
                return
            with self._tracer.start_as_current_span(name) as s:
                for k, v in attributes.items():
                    try:
                        s.set_attribute(k, v)
                    except Exception:  # noqa: BLE001 — non-serializable attr
                        pass
                yield

    def sys_metrics(self) -> dict:
        """Process memory/CPU snapshot (reference telemetry.rs:350
        ``register_sys_metrics``); resource module, no psutil needed.
        RSS is normalized to bytes (see :func:`max_rss_bytes`)."""
        import os
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {
            "process.memory.max_rss_bytes": max_rss_bytes(),
            "process.cpu.user_s": ru.ru_utime,
            "process.cpu.system_s": ru.ru_stime,
            "process.pid": os.getpid(),
        }

    def register_metrics(self, monitor: Any = None) -> bool:
        """Register process mem/CPU (+ per-operator latency, when a
        StatsMonitor is supplied) as OTel observable gauges
        (reference: telemetry.rs:316-350 register_stats_metrics /
        register_sys_metrics + the 60 s periodic reader).

        Uses the opentelemetry *metrics API*: with only the API installed
        (this image) the no-op meter swallows everything; when the
        embedding application configures an SDK ``MeterProvider`` (OTLP,
        Prometheus, in-memory reader...), its periodic reader drives the
        callbacks below.  Idempotent; returns True when gauges were
        registered on a meter."""
        if self._meter is not None:
            # gauges exist — repoint the latency callback at the newest
            # monitor (each pw.run builds a fresh StatsMonitor)
            self._monitor = monitor
            return True
        try:
            from opentelemetry import metrics
            from opentelemetry.metrics import Observation
        except ImportError:
            return False
        meter = metrics.get_meter("pathway_tpu")
        self._meter = meter
        self._monitor = monitor

        def observe_memory(options):
            try:
                import psutil

                rss = psutil.Process().memory_info().rss
            except Exception:
                rss = max_rss_bytes()
            return [Observation(rss)]

        def observe_cpu(options):
            import resource

            ru = resource.getrusage(resource.RUSAGE_SELF)
            return [Observation(ru.ru_utime + ru.ru_stime)]

        def observe_latency(options):
            mon = self._monitor
            if mon is None:
                return []
            try:
                snap = mon.snapshot()
            except Exception:
                return []
            out = []
            for name, st in snap.get("nodes", {}).items():
                flushes = st.get("flushes", 0)
                avg_ms = (
                    st.get("busy_s", 0.0) / flushes * 1000.0 if flushes else 0.0
                )
                out.append(Observation(avg_ms, {"operator": name}))
            return out

        meter.create_observable_gauge(
            "pathway.process.memory_rss_bytes",
            callbacks=[observe_memory],
            unit="By",
            description="resident set size of the engine process",
        )
        meter.create_observable_gauge(
            "pathway.process.cpu_seconds",
            callbacks=[observe_cpu],
            unit="s",
            description="cumulative user+system CPU time",
        )
        meter.create_observable_gauge(
            "pathway.operator.avg_latency_ms",
            callbacks=[observe_latency],
            unit="ms",
            description="per-operator mean flush latency",
        )
        return True


#: reference: telemetry.rs:38-39
PERIODIC_READER_INTERVAL_MS = 60_000
EXPORT_TIMEOUT_MS = 3_000

_otlp_configured_endpoint: str | None = None


def setup_otlp(
    endpoint: str,
    *,
    service_name: str = "pathway_tpu",
    run_id: str | None = None,
) -> bool:
    """Push-pipeline parity with the reference (telemetry.rs:94-145
    ``init_meter_provider``/``init_tracer_provider``): build SDK
    Tracer/Meter providers with OTLP exporters and a 60 s PeriodicReader
    against ``endpoint``, set them globally, and tag the resource with
    service name / instance / run id.

    Config-gated and inert without the SDK: this image ships only the
    OTel *API*, so the function logs one debug line and returns False —
    exactly the reference's off-unless-configured posture.  Returns True
    when providers were installed (idempotent per endpoint)."""
    global _otlp_configured_endpoint
    if _otlp_configured_endpoint == endpoint:
        return True
    if _otlp_configured_endpoint is not None:
        # OpenTelemetry refuses to override already-set global providers —
        # claiming success would silently keep exporting to the OLD
        # endpoint.  Be loud and honest instead.
        import logging

        logging.getLogger("pathway_tpu").warning(
            "telemetry already configured for %s; cannot re-point to %s "
            "in the same process (OTel global providers are set once)",
            _otlp_configured_endpoint,
            endpoint,
        )
        return False
    try:
        from opentelemetry import metrics, trace
        from opentelemetry.exporter.otlp.proto.grpc.metric_exporter import (
            OTLPMetricExporter,
        )
        from opentelemetry.exporter.otlp.proto.grpc.trace_exporter import (
            OTLPSpanExporter,
        )
        from opentelemetry.sdk.metrics import MeterProvider
        from opentelemetry.sdk.metrics.export import PeriodicExportingMetricReader
        from opentelemetry.sdk.resources import Resource
        from opentelemetry.sdk.trace import TracerProvider
        from opentelemetry.sdk.trace.export import BatchSpanProcessor
    except ImportError:
        import logging

        logging.getLogger("pathway_tpu").debug(
            "PATHWAY_MONITORING_SERVER set (%s) but the OpenTelemetry SDK "
            "is not installed — telemetry push disabled",
            endpoint,
        )
        return False

    import os
    import uuid

    resource = Resource.create(
        {
            "service.name": service_name,
            "service.instance.id": str(os.getpid()),
            "pathway.run_id": run_id or str(uuid.uuid4()),
        }
    )
    reader = PeriodicExportingMetricReader(
        OTLPMetricExporter(
            endpoint=endpoint, timeout=EXPORT_TIMEOUT_MS / 1000
        ),
        export_interval_millis=PERIODIC_READER_INTERVAL_MS,
        export_timeout_millis=EXPORT_TIMEOUT_MS,
    )
    metrics.set_meter_provider(
        MeterProvider(resource=resource, metric_readers=[reader])
    )
    tracer_provider = TracerProvider(resource=resource)
    tracer_provider.add_span_processor(
        BatchSpanProcessor(
            OTLPSpanExporter(endpoint=endpoint, timeout=EXPORT_TIMEOUT_MS / 1000)
        )
    )
    trace.set_tracer_provider(tracer_provider)
    _otlp_configured_endpoint = endpoint
    # rebuild the singleton so its tracer/meter bind to the new providers
    global _singleton
    _singleton = None
    return True


_singleton: Telemetry | None = None


def get_telemetry() -> Telemetry:
    global _singleton
    if _singleton is None:
        _singleton = Telemetry()
    return _singleton
