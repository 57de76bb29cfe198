"""REST servers for the LLM xpack.

reference: python/pathway/xpacks/llm/servers.py — ``BaseRestServer``:25
(``serve``), ``DocumentStoreServer``:92, ``QARestServer``:140,
``QASummaryRestServer``:193, ``serve_callable``:227.
"""

from __future__ import annotations

from typing import Any, Callable

from ...internals.schema import Schema, SchemaMetaclass, schema_from_types
from ...internals.table import Table
from ...io.http import EndpointDocumentation, PathwayWebserver, rest_connector

__all__ = [
    "BaseRestServer",
    "DocumentStoreServer",
    "QARestServer",
    "QASummaryRestServer",
    "serve_callable",
]


class BaseRestServer:
    """reference: servers.py:25"""

    def __init__(self, host: str, port: int, **rest_kwargs):
        self.webserver = PathwayWebserver(host=host, port=port)
        self.rest_kwargs = rest_kwargs

    def serve(
        self,
        route: str,
        schema: SchemaMetaclass,
        handler: Callable[[Table], Table],
        documentation: EndpointDocumentation | None = None,
        **additional_endpoint_kwargs,
    ) -> None:
        queries, writer = rest_connector(
            webserver=self.webserver,
            route=route,
            methods=("GET", "POST"),
            schema=schema,
            delete_completed_queries=True,
            documentation=documentation,
            **{**self.rest_kwargs, **additional_endpoint_kwargs},
        )
        writer(handler(queries))

    def run(
        self,
        threaded: bool = False,
        with_cache: bool = True,
        cache_backend: Any = None,
        terminate_on_error: bool = True,
        persistence_config: Any = None,
        **kwargs,
    ):
        """reference: servers.py run — wires UDF caching persistence.
        An explicit ``persistence_config`` (e.g. the durable
        OPERATOR_PERSISTING recovery plane) takes precedence over the
        in-memory UDF cache."""
        from ._utils import run_with_cache

        return run_with_cache(
            threaded=threaded,
            with_cache=with_cache,
            cache_backend=cache_backend,
            terminate_on_error=terminate_on_error,
            persistence_config=persistence_config,
        )

    run_server = run


class DocumentStoreServer(BaseRestServer):
    """reference: servers.py:92

    With the serving scheduler enabled (default), ``/v1/retrieve``
    answers off the shared cross-request scheduler (fused embed→search,
    deadline shedding) when the store exposes a plane for it; hybrid or
    embedder-less stores keep the engine-routed endpoint.  Those
    ticks run as ``INTERACTIVE``-class work on the shared QoS executor,
    ahead of engine-plane rerank/embed micro-batches (``LLM_RERANK``)
    and bulk ingest (``BULK_INGEST``).
    """

    def __init__(
        self,
        host: str,
        port: int,
        document_store,
        with_scheduler: bool | None = None,
        deadline_ms: float | None = None,
        **rest_kwargs,
    ):
        super().__init__(host, port, **rest_kwargs)
        self.document_store = document_store
        ds = document_store
        plane = None
        if with_scheduler is None:
            from ._scheduler import scheduler_enabled

            with_scheduler = scheduler_enabled()
        if with_scheduler and hasattr(ds, "scheduler_retrieve_plane"):
            plane = ds.scheduler_retrieve_plane(deadline_ms=deadline_ms)
        self._retrieve_plane = plane
        if plane is not None:
            from .vector_store import _wire_index_maintenance

            self.webserver.add_raw_route(
                "/v1/retrieve",
                ("GET", "POST"),
                plane.aiohttp_handler(),
                EndpointDocumentation(summary="Retrieve documents", tags=["pathway"]),
            )
            _wire_index_maintenance(
                ds.retrieve_query,
                ds.RetrieveQuerySchema if hasattr(ds, "RetrieveQuerySchema") else _retrieve_schema(),
            )
        else:
            self.serve(
                "/v1/retrieve",
                ds.RetrieveQuerySchema if hasattr(ds, "RetrieveQuerySchema") else _retrieve_schema(),
                ds.retrieve_query,
                EndpointDocumentation(summary="Retrieve documents", tags=["pathway"]),
            )
        self.serve(
            "/v1/statistics",
            ds.StatisticsQuerySchema if hasattr(ds, "StatisticsQuerySchema") else _stats_schema(),
            ds.statistics_query,
            EndpointDocumentation(summary="Document store statistics", tags=["pathway"]),
        )
        self.serve(
            "/v1/inputs",
            ds.InputsQuerySchema if hasattr(ds, "InputsQuerySchema") else _inputs_schema(),
            ds.inputs_query,
            EndpointDocumentation(summary="Indexed input files", tags=["pathway"]),
        )


def _retrieve_schema():
    from .vector_store import RetrieveQuerySchema

    return RetrieveQuerySchema


def _stats_schema():
    from .vector_store import StatisticsQuerySchema

    return StatisticsQuerySchema


def _inputs_schema():
    from .vector_store import InputsQuerySchema

    return InputsQuerySchema


class QARestServer(BaseRestServer):
    """reference: servers.py:140"""

    def __init__(self, host: str, port: int, rag_question_answerer, **rest_kwargs):
        super().__init__(host, port, **rest_kwargs)
        self.rag_question_answerer = rag_question_answerer
        qa = rag_question_answerer
        self.serve(
            "/v1/retrieve",
            qa.RetrieveQuerySchema,
            qa.retrieve,
            EndpointDocumentation(summary="Retrieve documents", tags=["pathway"]),
        )
        self.serve(
            "/v1/statistics",
            qa.StatisticsQuerySchema,
            qa.statistics,
            EndpointDocumentation(summary="Index statistics", tags=["pathway"]),
        )
        self.serve(
            "/v1/pw_list_documents",
            qa.InputsQuerySchema,
            qa.list_documents,
            EndpointDocumentation(summary="List indexed documents", tags=["pathway"]),
        )
        self.serve(
            "/v1/pw_ai_answer",
            qa.AnswerQuerySchema,
            qa.answer_query,
            EndpointDocumentation(summary="Ask a question", tags=["pathway"]),
        )

    # reference keeps /v2/answer aliases in newer versions; /v1 is canonical


class QASummaryRestServer(QARestServer):
    """reference: servers.py:193"""

    def __init__(self, host: str, port: int, rag_question_answerer, **rest_kwargs):
        super().__init__(host, port, rag_question_answerer, **rest_kwargs)
        qa = rag_question_answerer
        self.serve(
            "/v1/pw_ai_summary",
            qa.SummarizeQuerySchema,
            qa.summarize_query,
            EndpointDocumentation(summary="Summarize texts", tags=["pathway"]),
        )


def serve_callable(
    route: str,
    schema: SchemaMetaclass | None = None,
    host: str = "0.0.0.0",
    port: int = 8000,
    webserver: PathwayWebserver | None = None,
    **kwargs,
):
    """Expose an (async) Python function as a REST endpoint wired through
    the dataflow (reference: servers.py:227).

    Use as a decorator::

        @serve_callable(route="/echo", schema=MySchema, host=..., port=...)
        def handler(**row) -> str: ...

    Returns the decorated function; the endpoint serves once ``pw.run``
    (or a threaded server run) starts.
    """

    def decorate(fn: Callable):
        from ... import apply_async
        from ...internals.udfs import coerce_async

        nonlocal schema, webserver
        if schema is None:
            import inspect

            params = [
                p
                for p in inspect.signature(fn).parameters.values()
                if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)
            ]
            types = {
                p.name: (p.annotation if p.annotation is not inspect._empty else str)
                for p in params
            }
            schema = schema_from_types(**types)
        ws = webserver or PathwayWebserver(host=host, port=port)
        queries, writer = rest_connector(
            webserver=ws, route=route, schema=schema,
            delete_completed_queries=True, **kwargs,
        )
        afn = coerce_async(fn)

        async def row_fn(*args):
            return await afn(*[_unwrap(a) for a in args])

        cols = [queries[n] for n in schema.column_names()]
        result = queries.select(result=apply_async(row_fn, *cols))
        writer(result)
        fn._pathway_endpoint = (ws, route)  # type: ignore[attr-defined]
        return fn

    def _unwrap(v):
        from ...internals.value import Json

        return v.value if isinstance(v, Json) else v

    return decorate
