"""VectorStoreServer — live document indexing + retrieval serving.

reference: python/pathway/xpacks/llm/vector_store.py —
``VectorStoreServer``:39 (pipeline ``_build_graph``:227: sources → parse →
flatten → post-process → split → flatten → index:289; stats reduce :303;
REST endpoints ``/v1/retrieve|statistics|inputs`` :523-556;
``run_server``:558), ``VectorStoreClient``:651, LangChain :92 /
LlamaIndex :136 adapters.

TPU shape: chunks stream through the jit-compiled embedder (one padded
device batch per engine micro-batch) into the HBM-resident KNN index
(ops/knn.py); queries ride the same as-of-now external-index operator the
reference uses (updates-before-queries per timestamp, lowering.py).
"""

from __future__ import annotations

from typing import Any, Callable

from ...internals import dtype as dt
from ...internals import reducers
from ...internals.expression import ApplyExpression
from ...internals.schema import Schema, column_definition
from ...internals.table import Table
from ...internals.udfs import udf
from ...internals.value import Json
from ...stdlib.indexing.data_index import DataIndex
from ...stdlib.indexing.retrievers import UsearchKnnFactory
from ._utils import RestClientBase, coerce_str, run_with_cache
from .parsers import Utf8Parser
from .splitters import null_splitter

__all__ = ["VectorStoreServer", "VectorStoreClient", "SlidesVectorStoreServer"]


# ---------------------------------------------------------------------------
# query schemas (reference: vector_store.py RetrieveQuerySchema et al.)
# ---------------------------------------------------------------------------


class RetrieveQuerySchema(Schema):
    query: str
    k: int = column_definition(default_value=3)
    metadata_filter: str | None = column_definition(default_value=None)
    filepath_globpattern: str | None = column_definition(default_value=None)


class StatisticsQuerySchema(Schema):
    req: str | None = column_definition(default_value=None)


class InputsQuerySchema(Schema):
    metadata_filter: str | None = column_definition(default_value=None)
    filepath_globpattern: str | None = column_definition(default_value=None)


class QueryResultSchema(Schema):
    result: Json


@udf(deterministic=True)
def _merge_filters(metadata_filter: str | None, filepath_globpattern: str | None) -> str | None:
    """Combine the two request filters into one expression
    (reference: vector_store.py:358 ``merge_filters``).  Deterministic:
    a pure string merge — marking it so keeps its select un-memoized,
    which OPERATOR_PERSISTING's coverage check requires (a memoized map
    cannot restart empty over restored downstream state)."""
    from ._utils import merge_filter_exprs

    return merge_filter_exprs(metadata_filter, filepath_globpattern)


from ._pipeline import build_document_pipeline, component_expr as _component_expr


def _wire_index_maintenance(retrieve_query_fn, query_schema) -> None:
    """Keep the external-index operator in the graph when the scheduler
    plane answers queries: an empty static query stream through the same
    ``retrieve_query`` pipeline makes the engine build and continuously
    maintain the index (docs embed/upsert per micro-batch) while REST
    retrieval reads it through the admission queue instead."""
    from ...debug import table_from_rows
    from ...io._subscribe import subscribe

    queries = table_from_rows(query_schema, [])
    result = retrieve_query_fn(queries)
    subscribe(result, on_change=lambda *a, **k: None, name="index-maintain")


class VectorStoreServer:
    """reference: vector_store.py:39"""

    def __init__(
        self,
        *docs: Table,
        embedder: Callable | None = None,
        parser: Callable | None = None,
        splitter: Callable | None = None,
        doc_post_processors: list[Callable] | None = None,
        index_factory: Any = None,
        mesh: Any = None,
    ):
        self.docs = list(docs)
        self.embedder = embedder
        self.parser = parser if parser is not None else Utf8Parser()
        self.splitter = splitter if splitter is not None else null_splitter
        self.doc_post_processors = [p for p in (doc_post_processors or []) if p is not None]
        if mesh is None:
            # PATHWAY_SERVING_MESH: env-default multi-chip serving — the
            # live index shards over the mesh's data axis and every fused
            # serving tick merges per-shard top-k over ICI
            from ...parallel.mesh import serving_mesh

            mesh = serving_mesh()
        # a model-backed embedder whose encoder is not built yet inherits
        # the serving mesh: query/ingest encodes then run data-parallel
        # over the same device set the index shards on
        from ._utils import seed_embedder_mesh

        seed_embedder_mesh(embedder, mesh)
        if index_factory is None:
            if embedder is None:
                raise ValueError("provide embedder= or index_factory=")
            index_factory = UsearchKnnFactory(embedder=embedder, mesh=mesh)
        elif mesh is not None and getattr(index_factory, "mesh", "-") is None:
            # device-mesh knob (SURVEY §2.7): shard the KNN matrix over the
            # mesh's data axis instead of replicating per worker like the
            # reference (external_index.rs:95-98 broadcast replica).  Only
            # factories exposing an unset ``mesh`` field participate; the
            # caller's factory object is left untouched.
            import dataclasses as _dc

            index_factory = _dc.replace(index_factory, mesh=mesh)
        self.mesh = mesh
        self.index_factory = index_factory
        self._graph = self._build_graph()

    # -- classmethod adapters (reference: vector_store.py:92,136) --
    @classmethod
    def from_langchain_components(
        cls, *docs, embedder, parser=None, splitter=None, **kwargs
    ) -> "VectorStoreServer":
        """Wrap LangChain embeddings + text splitter."""

        @udf
        async def generic_embedder(x: str):
            import numpy as np

            res = await embedder.aembed_query(coerce_str(x))
            return np.asarray(res)

        generic_splitter = None
        if splitter is not None:
            generic_splitter = lambda x: [  # noqa: E731
                (c, {}) for c in splitter.split_text(coerce_str(x))
            ]
        return cls(
            *docs, embedder=generic_embedder, parser=parser,
            splitter=generic_splitter, **kwargs,
        )

    @classmethod
    def from_llamaindex_components(
        cls, *docs, transformations: list, parser=None, **kwargs
    ) -> "VectorStoreServer":
        """Wrap a LlamaIndex embedding + node-parser transformation chain."""
        try:
            from llama_index.core.base.embeddings.base import BaseEmbedding
            from llama_index.core.node_parser.interface import TextSplitter
        except ImportError as exc:  # pragma: no cover - optional dependency
            raise ImportError("llama-index-core is required") from exc

        embedders_ = [t for t in transformations if isinstance(t, BaseEmbedding)]
        if len(embedders_) != 1:
            raise ValueError("transformations must include exactly one embedder")
        embedder = embedders_[0]

        @udf
        async def generic_embedder(x: str):
            import numpy as np

            return np.asarray(await embedder.aget_text_embedding(coerce_str(x)))

        splitters_ = [t for t in transformations if isinstance(t, TextSplitter)]
        generic_splitter = None
        if splitters_:
            sp = splitters_[0]
            generic_splitter = lambda x: [(c, {}) for c in sp.split_text(coerce_str(x))]  # noqa: E731
        return cls(
            *docs, embedder=generic_embedder, parser=parser,
            splitter=generic_splitter, **kwargs,
        )

    # -- pipeline (reference: vector_store.py:227 _build_graph) --
    def _build_graph(self) -> dict:
        graph = build_document_pipeline(
            self.docs, self.parser, self.splitter, self.doc_post_processors
        )
        graph["index"] = DataIndex(
            graph["chunked_docs"],
            self.index_factory,
            data_column=graph["chunked_docs"].text,
            metadata_column=graph["chunked_docs"].metadata,
            embedder=self.embedder,
        )
        return graph

    # -- embedding dimension probe (reference: vector_store.py embedder probe) --
    @property
    def embedding_dimension(self) -> int:
        factory = self.index_factory
        return factory._resolve_dim(getattr(factory, "dimensions", None), self.embedder)

    # -- query pipelines --
    def retrieve_query(self, retrieval_queries: Table) -> Table:
        """reference: vector_store.py:439"""
        queries = retrieval_queries.select(
            query=retrieval_queries.query,
            k=retrieval_queries.k,
            metadata_filter=_merge_filters(
                retrieval_queries.metadata_filter,
                retrieval_queries.filepath_globpattern,
            ),
        )
        index: DataIndex = self._graph["index"]
        res = index.query_as_of_now(
            queries.query,
            number_of_matches=queries.k,
            metadata_filter=queries.metadata_filter,
            collapse_rows=True,
        )

        def pack(texts, metas, scores) -> Json:
            out = []
            for t, m, s in zip(texts or (), metas or (), scores or ()):
                out.append(
                    {
                        "text": coerce_str(t),
                        "metadata": m.value if isinstance(m, Json) else m,
                        "dist": -float(s),
                    }
                )
            return Json(out)

        from ...internals.thisclass import right

        return res.select(
            result=ApplyExpression(
                pack,
                Json,
                right.text,
                right.metadata,
                right["_pw_index_reply_score"],
            )
        )

    def statistics_query(self, info_queries: Table) -> Table:
        """reference: vector_store.py statistics endpoint"""
        stats = self._graph["stats"]

        def pack_stats(count, last_modified, last_indexed) -> Json:
            return Json(
                {
                    "file_count": int(count or 0),
                    "last_modified": last_modified,
                    "last_indexed": last_indexed,
                }
            )

        joined = info_queries.join_left(stats, id=info_queries.id).select(
            result=ApplyExpression(
                pack_stats, Json, stats.count, stats.last_modified, stats.last_indexed
            )
        )
        return joined

    def inputs_query(self, input_queries: Table) -> Table:
        """reference: vector_store.py inputs endpoint"""
        docs = self._graph["parsed_docs"]
        all_meta = docs.reduce(
            metadatas=reducers.tuple(docs.metadata),
        )

        @udf
        def format_inputs(metadatas, metadata_filter: str | None) -> Json:
            from ...utils.jmespath_lite import compile_filter

            metas = [m.value if isinstance(m, Json) else m for m in (metadatas or ())]
            if metadata_filter:
                flt = compile_filter(metadata_filter)
                metas = [m for m in metas if flt(m)]
            return Json(metas)

        queries = input_queries.select(
            metadata_filter=_merge_filters(
                input_queries.metadata_filter, input_queries.filepath_globpattern
            )
        )
        return queries.join_left(all_meta, id=queries.id).select(
            result=format_inputs(all_meta.metadatas, queries.metadata_filter)
        )

    # -- serving (reference: vector_store.py:523-582) --
    def build_server(
        self,
        host: str,
        port: int,
        *,
        with_scheduler: bool | None = None,
        deadline_ms: float | None = None,
        aux_endpoints: bool = True,
        **rest_kwargs,
    ) -> None:
        """Register the REST routes.

        ``with_scheduler`` (default: the global setting, on unless
        ``PATHWAY_SERVING_SCHEDULER=0``) serves ``/v1/retrieve`` off the
        continuous cross-request scheduler — concurrent queries coalesce
        into one fused embed→search device tick instead of riding engine
        micro-batch cadence — with ``deadline_ms``-based shedding
        (503 + Retry-After).  Statistics/inputs stay engine-routed.

        Those ticks execute as ``INTERACTIVE``-class work on the
        process-wide QoS executor (the unified device-tick runtime): they preempt bulk-ingest
        chunks at tick granularity, so serving p99 survives ingest
        bursts (see README "Operations: unified runtime & QoS classes";
        per-class state rides ``/v1/health`` and ``/status``).

        ``aux_endpoints=False`` registers only ``/v1/retrieve`` (plus the
        always-on ``/v1/health`` and ``/v1/debug/traces``): the
        statistics/inputs pipelines join REST queries against engine
        state, and those joins are not yet covered by the
        OPERATOR_PERSISTING recovery plane — a durable serving deployment
        (see README "Operations: recovery & durability") runs
        retrieve-only.

        Every route is traced: responses carry ``x-pathway-trace-id``
        (a caller-sent W3C ``traceparent`` is honored) and the scheduler
        path records a per-stage breakdown (queue wait / embed / search /
        serialize) retrievable from ``GET /v1/debug/traces`` on the same
        server — see README "Operations: observability".
        """
        from ...io.http import PathwayWebserver, rest_connector

        webserver = PathwayWebserver(host=host, port=port)
        self._webserver = webserver

        # fleet membership control surface (/v1/fleet/ingest|drain|
        # watermark): wired only when this process activated a member —
        # a standalone server never registers the routes
        import sys as _sys

        _member_mod = _sys.modules.get("pathway_tpu.fleet.member")
        if _member_mod is not None:
            _member = _member_mod.get_member()
            if _member is not None:
                _member.wire_routes(webserver)

        embedder = self.embedder or getattr(self.index_factory, "embedder", None)
        if with_scheduler is None:
            from ._scheduler import scheduler_enabled

            with_scheduler = scheduler_enabled() and embedder is not None
        elif with_scheduler and embedder is None:
            # fail at build time, not as a 500 on every query
            raise ValueError(
                "with_scheduler=True needs an embedder (the fused retrieve "
                "plane embeds queries itself); pass embedder= or use an "
                "index factory that carries one"
            )
        if with_scheduler:
            from ._scheduler import RetrievePlane

            self._retrieve_plane = RetrievePlane(
                index_factory=self.index_factory,
                embedder=embedder,
                payload_columns=self._graph["chunked_docs"].column_names(),
                deadline_ms=deadline_ms,
            )
            webserver.add_raw_route(
                "/v1/retrieve", ("GET", "POST"), self._retrieve_plane.aiohttp_handler()
            )
            _wire_index_maintenance(self.retrieve_query, RetrieveQuerySchema)
        else:
            retrieval_queries, retrieval_writer = rest_connector(
                webserver=webserver,
                route="/v1/retrieve",
                schema=RetrieveQuerySchema,
                methods=("GET", "POST"),
                delete_completed_queries=True,
            )
            retrieval_writer(self.retrieve_query(retrieval_queries))

        if not aux_endpoints:
            # no rest_connector subject will start the listener (the
            # scheduler plane serves /v1/retrieve directly) — bring it up
            # now so /v1/health is observable through warm restore, with
            # queries answering degraded until the index is ready
            webserver._ensure_started()
            return

        stats_queries, stats_writer = rest_connector(
            webserver=webserver,
            route="/v1/statistics",
            schema=StatisticsQuerySchema,
            methods=("GET", "POST"),
            delete_completed_queries=True,
        )
        stats_writer(self.statistics_query(stats_queries))

        input_queries, inputs_writer = rest_connector(
            webserver=webserver,
            route="/v1/inputs",
            schema=InputsQuerySchema,
            methods=("GET", "POST"),
            delete_completed_queries=True,
        )
        inputs_writer(self.inputs_query(input_queries))

    def run_server(
        self,
        host: str = "0.0.0.0",
        port: int = 8000,
        threaded: bool = False,
        with_cache: bool = True,
        cache_backend: Any = None,
        terminate_on_error: bool = True,
        with_scheduler: bool | None = None,
        deadline_ms: float | None = None,
        aux_endpoints: bool = True,
        persistence_config: Any = None,
    ):
        """Start serving; ``threaded=True`` runs the engine loop on a daemon
        thread and returns it (reference: vector_store.py:558-582).
        ``with_scheduler``/``deadline_ms``/``aux_endpoints`` — see
        :meth:`build_server`.  ``persistence_config`` (a
        ``pw.persistence.Config``) makes the server durable: with
        ``PersistenceMode.OPERATOR_PERSISTING`` the live HBM index
        checkpoints already-computed vectors per commit and warm-restarts
        from them (zero re-embeddings) behind the ``/v1/health`` gate."""
        self.build_server(
            host=host, port=port,
            with_scheduler=with_scheduler, deadline_ms=deadline_ms,
            aux_endpoints=aux_endpoints,
        )
        return run_with_cache(
            threaded=threaded,
            with_cache=with_cache,
            cache_backend=cache_backend,
            terminate_on_error=terminate_on_error,
            persistence_config=persistence_config,
        )


class SlidesVectorStoreServer(VectorStoreServer):
    """Parity alias for the slide-deck flavor (reference:
    vector_store.py SlidesVectorStoreServer)."""


class VectorStoreClient(RestClientBase):
    """HTTP client for :class:`VectorStoreServer`
    (reference: vector_store.py:651).

    ``retry_on_unavailable=True`` honors the scheduler's
    503 + ``Retry-After`` shedding with one bounded retry (off by
    default — callers owning their own backoff keep full control).
    ``last_trace_id`` holds the server's trace id for the most recent
    call — feed it to ``/v1/debug/traces?trace_id=`` for the per-stage
    latency breakdown of that exact request."""

    def __init__(self, *args, timeout: float = 15.0, **kwargs):
        super().__init__(*args, timeout=timeout, **kwargs)
        #: True when the last /v1/retrieve answer came from the degraded
        #: (lexical fallback) path — see RetrievePlane's breaker
        self.last_degraded = False

    def query(
        self,
        query: str,
        k: int = 3,
        metadata_filter: str | None = None,
        filepath_globpattern: str | None = None,
    ) -> list[dict]:
        payload = {"query": query, "k": k}
        if metadata_filter is not None:
            payload["metadata_filter"] = metadata_filter
        if filepath_globpattern is not None:
            payload["filepath_globpattern"] = filepath_globpattern
        res = self._post("/v1/retrieve", payload)
        if isinstance(res, dict) and "results" in res:
            self.last_degraded = bool(res.get("degraded"))
            return res["results"]
        self.last_degraded = False
        return res

    __call__ = query

    def get_vectorstore_statistics(self) -> dict:
        return self._post("/v1/statistics", {})

    def get_input_files(
        self,
        metadata_filter: str | None = None,
        filepath_globpattern: str | None = None,
    ) -> list:
        return self._post(
            "/v1/inputs",
            {
                "metadata_filter": metadata_filter,
                "filepath_globpattern": filepath_globpattern,
            },
        )
