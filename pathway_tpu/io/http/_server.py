"""aiohttp REST server connector.

reference: python/pathway/io/http/_server.py — ``PathwayWebserver``:329,
``rest_connector``:624, ``RestServerSubject``:490 (requests become input
rows; responses resolved by an ``internal_subscribe`` callback setting a
per-request asyncio event, :778-806), OpenAPI docs (``EndpointDocumentation``
:126).

The aiohttp loop runs on its own thread; the engine loop (StreamingDriver)
delivers response diffs via ``pw.io.subscribe`` and wakes the waiting
handler with ``loop.call_soon_threadsafe`` — same two-plane split as the
reference (webserver thread ↔ engine workers).
"""

from __future__ import annotations

import asyncio
import json
import threading
from typing import Any, Callable, Sequence

from ...internals.schema import SchemaMetaclass
from ...internals.table import Table
from ...internals.value import Json, Pointer
from .._subscribe import subscribe
from .._utils import coerce_row, input_table
from ..streaming import ConnectorSubject, next_autogen_key

__all__ = ["PathwayWebserver", "rest_connector", "EndpointDocumentation"]


class EndpointDocumentation:
    """OpenAPI metadata for one route (reference _server.py:126)."""

    def __init__(
        self,
        *,
        summary: str | None = None,
        description: str | None = None,
        tags: Sequence[str] = (),
        method_types: Sequence[str] | None = None,
    ):
        self.summary = summary
        self.description = description
        self.tags = list(tags)
        self.method_types = method_types


class PathwayWebserver:
    """Shared aiohttp server hosting any number of rest_connector routes
    (reference _server.py:329)."""

    def __init__(self, host: str = "0.0.0.0", port: int = 8080, with_cors: bool = False):
        self.host = host
        self.port = port
        self.with_cors = with_cors
        self._loop: asyncio.AbstractEventLoop | None = None
        self._routes: list[tuple[str, Sequence[str], Callable]] = []
        self._openapi_routes: dict[str, dict] = {}
        self._started = threading.Event()
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None

    def add_raw_route(
        self,
        route: str,
        methods: Sequence[str],
        handler: Callable,
        documentation: "EndpointDocumentation | None" = None,
    ) -> None:
        """Serve ``route`` with a plain aiohttp handler instead of a
        dataflow-backed rest_connector — the serving scheduler's fused
        retrieve plane uses this to answer off the admission queue
        (xpacks/llm/_scheduler.py) while other routes ride the engine."""
        self._register(route, methods, handler, documentation)

    def _register(self, route: str, methods: Sequence[str], handler, doc) -> None:
        with self._lock:
            if self._thread is not None:
                raise RuntimeError("cannot add routes after the server started")
            self._routes.append((route, methods, handler))
            entry: dict[str, Any] = {}
            # SLO discoverability: the exact env knob names that put this
            # route under burn-rate evaluation ride the OpenAPI entry, so
            # `curl /_schema` answers "what do I export to SLO this
            # endpoint" without reading the docs
            try:
                from ...observability.slo import endpoint_env_key

                key = endpoint_env_key(route)
                slo_knobs = [
                    f"PATHWAY_SLO_{key}_P99_MS",
                    f"PATHWAY_SLO_{key}_AVAIL",
                ]
            except Exception:  # noqa: BLE001 — schema must never fail a route add
                slo_knobs = []
            for m in methods:
                entry[m.lower()] = {
                    "summary": getattr(doc, "summary", None) or route,
                    "description": getattr(doc, "description", None) or "",
                    "tags": list(getattr(doc, "tags", []) or []),
                    "responses": {"200": {"description": "OK"}},
                }
                if slo_knobs:
                    entry[m.lower()]["x-pathway-slo-knobs"] = slo_knobs
            self._openapi_routes[route] = entry

    def openapi_description_json(self) -> dict:
        return {
            "openapi": "3.0.3",
            "info": {"title": "Pathway-TPU API", "version": "1.0"},
            "paths": self._openapi_routes,
        }

    def _ensure_started(self) -> None:
        with self._lock:
            if self._thread is not None:
                return
            self._thread = threading.Thread(
                target=self._serve, daemon=True, name="pw-http"
            )
            self._thread.start()
        self._started.wait()

    def _serve(self) -> None:
        from aiohttp import web

        from ...internals.flight_recorder import name_thread

        name_thread("pw-http")
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)

        @web.middleware
        async def tracing_mw(request, handler):
            """Every request gets a trace: a caller-sent W3C
            ``traceparent`` is adopted, otherwise a trace id is minted.
            The id rides back on ``x-pathway-trace-id`` and the finished
            span (plus any per-stage children the serving planes stamped)
            lands in the in-process flight recorder — retrievable from
            ``/v1/debug/traces`` with zero external infra."""
            if request.path.startswith("/v1/debug/"):
                # reading the recorder must not write to it
                return await handler(request)
            from ...internals.flight_recorder import start_request

            trace = start_request(
                f"{request.method} {request.path}",
                request.headers.get("traceparent"),
            )
            request["pw_trace"] = trace

            def observe_slo(status: int | None) -> None:
                """Feed the SLO engine for EVERY finished request —
                latency observation is independent of trace sampling,
                and the trace id becomes the histogram exemplar linking
                a burning bucket to /v1/debug/traces."""
                try:
                    from ...observability import slo

                    slo.observe_request(
                        request.path,
                        trace.duration_ms or 0.0,
                        status,
                        # exemplars must link to traces that EXIST: an
                        # unsampled request records no spans, so its id
                        # would dead-end in /v1/debug/traces
                        trace.trace_id if trace.sampled else None,
                    )
                except Exception:  # noqa: BLE001 — SLOs must never fail a request
                    pass

            try:
                resp = await handler(request)
            except web.HTTPException as exc:
                exc.headers["x-pathway-trace-id"] = trace.trace_id
                trace.finish(status=exc.status)
                observe_slo(exc.status)
                raise
            except asyncio.CancelledError:
                # client went away mid-request — no response was sent, so
                # recording a 500 would plant phantom errors in the trace
                # dump during load spikes (and the SLO engine skips it:
                # an aborted client is not a server availability event)
                trace.set_attr("cancelled", True)
                trace.finish()
                raise
            except BaseException:
                trace.finish(status=500)
                observe_slo(500)
                raise
            resp.headers["x-pathway-trace-id"] = trace.trace_id
            trace.finish(status=resp.status)
            observe_slo(resp.status)
            return resp

        #: routes a DRAINING replica keeps answering: health/metrics
        #: probes, debug surfaces, and the fleet control plane (the
        #: router needs /v1/fleet/drain acks and watermark reads from a
        #: draining member — that is how the drain completes)
        _drain_exempt = ("/v1/health", "/v1/debug/", "/_schema",
                         "/v1/fleet/", "/status")

        @web.middleware
        async def drain_guard_mw(request, handler):
            """Graceful drain: once the fleet member starts draining,
            serving endpoints answer 503 with a REAL ``Retry-After`` so
            clients back off with jitter instead of hammering, while
            requests already in flight run to completion (this guard
            only rejects NEW arrivals).  Gated on the fleet module
            already being imported — a fleet-less server never pays the
            check beyond one dict lookup."""
            import sys as _sys

            member_mod = _sys.modules.get("pathway_tpu.fleet.member")
            if (
                member_mod is not None
                and member_mod.is_draining()
                and not any(request.path.startswith(p) for p in _drain_exempt)
            ):
                retry_after = member_mod.drain_retry_after_s()
                return web.json_response(
                    {"detail": "replica is draining", "draining": True},
                    status=503,
                    headers={"Retry-After": f"{retry_after:g}"},
                )
            return await handler(request)

        @web.middleware
        async def sanitize_errors_mw(request, handler):
            """An unhandled handler exception must not leak a traceback
            body to the client: return structured JSON 500, count it, and
            log with route context (the traceback goes to the log)."""
            try:
                return await handler(request)
            except (web.HTTPException, asyncio.CancelledError):
                raise
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "unhandled REST handler error on %s %s",
                    request.method, request.path,
                )
                from ...internals.errors import register_error

                register_error(
                    f"unhandled REST handler error on "
                    f"{request.method} {request.path}",
                    kind="http",
                    operator=request.path,
                )
                body = {
                    "error": "internal server error",
                    "route": request.path,
                }
                trace = request.get("pw_trace")
                if trace is not None:
                    # the envelope carries the trace id so a 500 report
                    # can be joined to its /v1/debug/traces breakdown
                    body["trace_id"] = trace.trace_id
                return web.json_response(body, status=500)

        app = web.Application(
            middlewares=[tracing_mw, drain_guard_mw, sanitize_errors_mw]
        )
        for route, methods, handler in self._routes:
            for m in methods:
                app.router.add_route(m, route, handler)

        async def openapi_handler(_request):
            return web.json_response(self.openapi_description_json())

        app.router.add_get("/_schema", openapi_handler)

        async def health_handler(_request):
            """Liveness/readiness: engine watchdog + connector supervision
            + breaker states + error-log counters, from the process-global
            health registry.  503 while unready (warmup, stalled engine,
            leaked ingest thread); 200 when ready — ``status`` flips to
            ``"degraded"`` when a breaker is open or a connector is in
            backoff but the service still answers."""
            from ...internals.health import get_health

            snap = get_health().snapshot()
            if snap["ready"]:
                return web.json_response(snap)
            # a real Retry-After on the unready 503: restore progress is
            # measured in seconds, and RestClientBase turns the hint into
            # jittered backoff instead of a fixed-cadence hammer
            return web.json_response(
                snap, status=503, headers={"Retry-After": "1.0"}
            )

        async def debug_traces_handler(request):
            """Flight-recorder dump: ``?trace_id=`` / ``?min_ms=`` /
            ``?category=`` / ``?limit=`` filters; ``?format=perfetto``
            returns Chrome-tracing JSON openable in chrome://tracing or
            ui.perfetto.dev — per-request stage attribution with no
            collector deployed."""
            from ...internals.flight_recorder import FlightRecorder, get_recorder

            q = request.query
            try:
                min_ms = float(q["min_ms"]) if "min_ms" in q else None
                # default: the WHOLE ring (it is already bounded by
                # PATHWAY_FLIGHT_RECORDER_CAPACITY).  A sub-ring default
                # would silently truncate every read once the ring fills,
                # and truncated reads deliberately do not clear the
                # dropped-before-read watermark — the drop alarm would
                # then read permanently hot under steady load
                limit = int(q["limit"]) if "limit" in q else None
            except (TypeError, ValueError):
                return web.json_response(
                    {"detail": "min_ms/limit must be numeric"}, status=400
                )
            rec = get_recorder()
            spans = rec.spans(
                trace_id=q.get("trace_id"),
                min_duration_ms=min_ms,
                category=q.get("category"),
                limit=limit,
            )
            if q.get("format") == "perfetto":
                return web.json_response(FlightRecorder.perfetto(spans))
            return web.json_response(
                {
                    "spans": [s.to_dict() for s in spans],
                    "recorder": rec.stats(),
                }
            )

        async def debug_profile_handler(request):
            """On-demand device profiling: capture a ``?ms=`` trace
            window (``jax.profiler`` on TPU, flight-recorder Perfetto
            export elsewhere) and serve the artifact.  Single-flight —
            409 while a capture is running; 503 when
            ``PATHWAY_PROFILE_DIR=off``.  The capture sleeps through the
            window off the event loop, so concurrent serving requests
            are untouched (that is the point: profile the LIVE load)."""
            from ...observability import profiler

            import math

            try:
                ms = float(request.query.get("ms", "500"))
            except (TypeError, ValueError):
                ms = float("nan")
            if not math.isfinite(ms):
                # nan/inf parse as floats but would blow up the sleep —
                # they are the caller's mistake, not a 500
                return web.json_response(
                    {"detail": "ms must be a finite number"}, status=400
                )
            try:
                res = await asyncio.to_thread(profiler.capture, ms)
            except profiler.ProfileInFlight as exc:
                return web.json_response({"detail": str(exc)}, status=409)
            except profiler.ProfilerDisabled as exc:
                return web.json_response({"detail": str(exc)}, status=503)
            # FileResponse streams the artifact in chunks off disk — a
            # TPU trace zip can be tens of MB, and a blocking whole-file
            # read here would stall the very serving traffic being
            # profiled (content type comes from the extension:
            # .json = flight-recorder export, .zip = jax trace)
            return web.FileResponse(
                res["path"],
                headers={
                    "x-pathway-profile-kind": res["kind"],
                    "x-pathway-profile-ms": f'{res["duration_ms"]:g}',
                    "x-pathway-profile-path": res["path"],
                },
            )

        async def status_handler(_request):
            """OpenMetrics exposition for this process.  Fleet routers
            scrape it on the health-poll cadence (telemetry federation);
            rendering walks every provider under locks, so it runs off
            the event loop."""
            from ...internals.monitoring import exposition

            text = await asyncio.to_thread(exposition)
            return web.Response(text=text, content_type="text/plain")

        if not any(route == "/v1/health" for route, _, _ in self._routes):
            app.router.add_get("/v1/health", health_handler)
        if not any(route == "/status" for route, _, _ in self._routes):
            app.router.add_get("/status", status_handler)
        if not any(route == "/v1/debug/traces" for route, _, _ in self._routes):
            app.router.add_get("/v1/debug/traces", debug_traces_handler)
        if not any(route == "/v1/debug/profile" for route, _, _ in self._routes):
            app.router.add_get("/v1/debug/profile", debug_profile_handler)
            app.router.add_post("/v1/debug/profile", debug_profile_handler)
        if self.with_cors:

            @web.middleware
            async def cors_mw(request, handler):
                if request.method == "OPTIONS":
                    resp = web.Response()
                else:
                    resp = await handler(request)
                resp.headers["Access-Control-Allow-Origin"] = "*"
                resp.headers["Access-Control-Allow-Headers"] = "*"
                resp.headers["Access-Control-Allow-Methods"] = "*"
                return resp

            app.middlewares.append(cors_mw)

        runner = web.AppRunner(app)
        self._loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, self.host, self.port)
        self._loop.run_until_complete(site.start())
        self._started.set()
        self._loop.run_forever()


def _jsonable(v: Any) -> Any:
    if isinstance(v, Json):
        return v.value
    if isinstance(v, Pointer):
        return str(v)
    if isinstance(v, bytes):
        return v.decode(errors="replace")
    if isinstance(v, tuple):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    return v


class RestServerSubject(ConnectorSubject):
    """Ingests HTTP requests as rows (reference _server.py:490)."""

    #: rows are in-flight HTTP requests — request-scoped, not durable
    #: state; clients retry after a restart (recovery-plane coverage)
    _ephemeral = True

    def __init__(
        self,
        webserver: PathwayWebserver,
        route: str,
        methods: Sequence[str],
        schema: SchemaMetaclass,
        delete_completed_queries: bool,
        request_validator: Callable | None = None,
        documentation: EndpointDocumentation | None = None,
    ):
        super().__init__(datasource_name=f"rest:{route}")
        self.webserver = webserver
        self.route = route
        self.methods = methods
        self.schema = schema
        self.delete_completed_queries = delete_completed_queries
        self.request_validator = request_validator
        self._awaiting: dict[Any, tuple[asyncio.Event, list]] = {}
        self._awaiting_lock = threading.Lock()
        webserver._register(route, methods, self._handle, documentation)

    def run(self) -> None:
        self.webserver._ensure_started()
        self._closed.wait()

    async def _handle(self, request):
        from aiohttp import web

        if request.method in ("POST", "PUT", "PATCH"):
            try:
                payload = await request.json()
            except (json.JSONDecodeError, UnicodeDecodeError):
                return web.json_response(
                    {"detail": "request body is not valid JSON"}, status=400
                )
        else:
            payload = dict(request.query)
        if self.request_validator is not None:
            err = self.request_validator(payload)
            if err is not None:
                return web.json_response({"detail": str(err)}, status=400)
        row = coerce_row(self.schema, payload)
        values = tuple(row.get(n) for n in self._column_names)
        key = next_autogen_key("rest")
        event = asyncio.Event()
        holder: list = []
        with self._awaiting_lock:
            self._awaiting[key] = (event, holder)
        self._add_inner(key, values)
        self.commit()
        await event.wait()
        with self._awaiting_lock:
            self._awaiting.pop(key, None)
        if self.delete_completed_queries:
            self._remove(key, values)
            self.commit()
        result = holder[0] if holder else None
        return web.json_response(_jsonable(result))

    def _resolve(self, key, result) -> None:
        """Called from the engine thread when the response row lands."""
        with self._awaiting_lock:
            slot = self._awaiting.get(key)
        if slot is None:
            return
        event, holder = slot
        holder.append(result)
        loop = self.webserver._loop
        if loop is not None:
            loop.call_soon_threadsafe(event.set)


def rest_connector(
    host: str | None = None,
    port: int | None = None,
    *,
    webserver: PathwayWebserver | None = None,
    route: str = "/",
    schema: SchemaMetaclass | None = None,
    methods: Sequence[str] = ("POST",),
    autocommit_duration_ms: int | None = 1500,
    keep_queries: bool | None = None,
    delete_completed_queries: bool = False,
    request_validator: Callable | None = None,
    documentation: EndpointDocumentation | None = None,
) -> tuple[Table, Callable[[Table], None]]:
    """HTTP endpoint as a (query table, response writer) pair
    (reference _server.py:624).

    The returned ``response_writer`` must be called with a table keyed by
    the query table's ids and holding a ``result`` column; each request
    blocks until its row arrives.
    """
    if webserver is None:
        if host is None or port is None:
            raise ValueError("provide either webserver= or host= and port=")
        webserver = PathwayWebserver(host=host, port=port)
    if schema is None:
        raise ValueError("rest_connector requires schema=")
    if keep_queries is not None:
        delete_completed_queries = not keep_queries

    subject = RestServerSubject(
        webserver,
        route,
        methods,
        schema,
        delete_completed_queries,
        request_validator,
        documentation,
    )
    subject._configure(schema, None)
    table = input_table(schema, subject=subject)

    def response_writer(response_table: Table) -> None:
        names = response_table.column_names()
        if "result" not in names:
            raise ValueError("response table must have a 'result' column")

        def on_change(key, row: dict, time: int, is_addition: bool) -> None:
            if is_addition:
                subject._resolve(key, row["result"])

        subscribe(response_table, on_change=on_change, name=f"rest_resp:{subject.route}")

    return table, response_writer
