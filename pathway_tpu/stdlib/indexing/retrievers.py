"""Inner index implementations + factories.

reference: python/pathway/stdlib/indexing/nearest_neighbors.py (USearchKnn:65,
BruteForceKnn:170, LshKnn:262; factories :428-560 with auto dim probing) and
src/external_integration/ (brute force, usearch HNSW, tantivy BM25).

TPU design: vector retrieval is exact brute-force or LSH over HBM via
``ops/`` (one fused MXU matmul + top-k beats HNSW graph walks on TPU for
realistic corpus sizes; the USearch factory name is kept for API parity and
maps to the HBM index).  BM25 is host-side (tiny state, string-heavy).
Metadata filtering applies the JMESPath-lite filter post-search with
oversampling, like DerivedFilteredSearchIndex (mod.rs:248-310).
"""

from __future__ import annotations

import math
import threading
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Any, Callable, Hashable

import numpy as np

from ...ops.knn import DeviceKnnIndex
from ...ops.lsh import LshProjector
from ...ops.quantized_scoring import is_quant_record
from ...ops.topk import topk_search
from ...utils.jmespath_lite import compile_filter

__all__ = [
    "InnerIndexImpl",
    "InnerIndexFactory",
    "BruteForceKnnFactory",
    "UsearchKnnFactory",
    "LshKnnFactory",
    "TantivyBM25Factory",
    "BM25Factory",
    "USearchMetricKind",
    "BruteForceKnnMetricKind",
]


class USearchMetricKind:
    COS = "cos"
    L2SQ = "l2sq"
    IP = "dot"


BruteForceKnnMetricKind = USearchMetricKind


class InnerIndexImpl:
    """Runtime index protocol consumed by the external-index operator
    (reference: src/external_integration/mod.rs:40 ``ExternalIndex`` trait)."""

    query_is_text = False

    def add(self, key: Hashable, data: Any, metadata: Any) -> None:
        raise NotImplementedError

    def add_batch(self, keys, datas, metadatas) -> None:
        """One flush's worth of adds; implementations that can stage a
        whole batch (one device scatter instead of N) override this."""
        for key, data, meta in zip(keys, datas, metadatas):
            self.add(key, data, meta)

    def remove(self, key: Hashable) -> None:
        raise NotImplementedError

    def search(
        self, queries: list[tuple[Any, int, str | None]]
    ) -> list[list[tuple[Hashable, float]]]:
        raise NotImplementedError


class _FilteredMixin:
    """Post-search metadata filtering with oversampling."""

    OVERSAMPLE = 4

    def __init__(self):
        self.metadata: dict[Hashable, Any] = {}
        self._filter_cache: dict[str, Callable] = {}

    def _store_meta(self, key, metadata):
        if metadata is not None:
            from ...internals.value import Json

            if isinstance(metadata, Json):
                metadata = metadata.value
            self.metadata[key] = metadata

    def _drop_meta(self, key):
        self.metadata.pop(key, None)

    def _filter_fn(self, expr: str) -> Callable:
        fn = self._filter_cache.get(expr)
        if fn is None:
            fn = self._filter_cache[expr] = compile_filter(expr)
        return fn

    def _apply_filter(
        self, results: list[tuple[Hashable, float]], flt: str | None, k: int
    ) -> list[tuple[Hashable, float]]:
        if flt is None:
            return results[:k]
        fn = self._filter_fn(flt)
        out = []
        for key, score in results:
            if fn(self.metadata.get(key)):
                out.append((key, score))
                if len(out) == k:
                    break
        return out


class BruteForceKnnIndex(_FilteredMixin, InnerIndexImpl):
    """Exact KNN in HBM (ops/knn.py) — replaces both the reference's
    brute-force index and, on TPU, the USearch HNSW one.

    With ``mesh`` the vector matrix is row-sharded over the mesh's data
    axis and queries merge across chips over ICI (parallel/index.py) —
    the multi-chip inversion of the reference's full-replica-per-worker
    design (src/engine/dataflow/operators/external_index.rs:95-98)."""

    def __init__(
        self,
        dim: int,
        metric: str = "cos",
        capacity: int = 1024,
        mesh=None,
        index_dtype: str | None = None,
        hot_rows: int | None = None,
    ):
        _FilteredMixin.__init__(self)
        if hot_rows is None:
            from ...tiering import tier_hot_rows_default

            hot_rows = tier_hot_rows_default()
        if hot_rows and hot_rows > 0:
            # tiered serving: HBM hot tier (per-shard when a mesh is
            # given) + routed host-RAM cold tier — the corpus is no
            # longer bounded by device HBM (pathway_tpu/tiering)
            from ...tiering import TieredKnnIndex

            self.index = TieredKnnIndex(
                dim=dim, hot_rows=int(hot_rows), metric=metric,
                capacity=capacity, mesh=mesh, index_dtype=index_dtype,
            )
        elif mesh is not None:
            from ...parallel.index import ShardedKnnIndex

            self.index = ShardedKnnIndex(
                dim=dim, mesh=mesh, metric=metric, capacity=capacity,
                index_dtype=index_dtype,
            )
        else:
            self.index = DeviceKnnIndex(
                dim=dim, metric=metric, capacity=capacity,
                index_dtype=index_dtype,
            )

    def add(self, key, data, metadata) -> None:
        if is_quant_record(data):
            self.index.upsert_coded(key, data)
        else:
            self.index.upsert(key, np.asarray(data, dtype=np.float32))
        self._store_meta(key, metadata)

    def add_batch(self, keys, datas, metadatas) -> None:
        """Batched add: one staged scatter for the whole flush.  A DEVICE
        array batch (the ingest pipeline's encoder output, rows beyond
        ``len(keys)`` being dispatch pads) is handed to the index without
        a host round trip (``DeviceKnnIndex.upsert_batch``).  Snapshot
        restore batches may carry quantized records (possibly mixed with
        raw f32 rows across a dtype transition) — records go straight to
        the coded staging path, zero re-quantization."""
        if hasattr(datas, "shape") and not isinstance(datas, np.ndarray):
            self.index.upsert_batch(list(keys), datas)  # device batch
        elif isinstance(datas, np.ndarray):
            self.index.upsert_batch(
                list(keys), datas.astype(np.float32, copy=False)
            )
        else:
            # stage in ORDER, flushing buffered raw rows before each
            # record — a key appearing twice in one batch (raw then
            # record or vice versa) must keep its LAST value, the same
            # last-write-wins contract upsert_batch documents
            raw_keys, raw_rows = [], []

            def _flush_raw():
                if raw_keys:
                    self.index.upsert_batch(list(raw_keys), np.stack(raw_rows))
                    raw_keys.clear()
                    raw_rows.clear()

            for key, data in zip(keys, datas):
                if is_quant_record(data):
                    _flush_raw()
                    self.index.upsert_coded(key, data)
                else:
                    raw_keys.append(key)
                    raw_rows.append(
                        np.asarray(data, dtype=np.float32).reshape(-1)
                    )
            _flush_raw()
        for key, meta in zip(keys, metadatas):
            self._store_meta(key, meta)

    def remove(self, key) -> None:
        self.index.remove(key)
        self._drop_meta(key)

    def search(self, queries):
        if not queries:
            return []
        vecs = np.stack([np.asarray(q[0], dtype=np.float32) for q in queries])
        return self.search_embedded(vecs, [(k, flt) for _, k, flt in queries])

    def search_embedded(self, vecs, specs):
        """Fused-path search over pre-embedded queries: ``vecs`` is the
        whole ``[Q, D]`` batch (numpy or device array) handed straight to
        the device index — the serving scheduler's embed→search tick
        never re-stages per-query rows on host.  ``specs`` is one
        ``(k, metadata_filter)`` pair per query."""
        if not specs:
            return []
        max_k = max(k for k, _ in specs)
        oversample = self.OVERSAMPLE if any(flt for _, flt in specs) else 1
        # n_valid: a fused device batch carries dispatch-pad rows past
        # len(specs) — skip their host-side result assembly entirely
        raw = self.index.search(vecs, max_k * oversample, n_valid=len(specs))
        return [
            self._apply_filter(row, flt, k)
            for row, (k, flt) in zip(raw, specs)
        ]

    # -- snapshot routing/placement protocol (tiered inner index) -------
    # ExternalIndexNode persists the routing spec in the delta-chunk
    # header and the tier placement as a reserved state row; these
    # delegations surface the inner index's half of that contract.
    def snapshot_header(self) -> dict | None:
        fn = getattr(self.index, "snapshot_header", None)
        return fn() if fn is not None else None

    def apply_snapshot_header(self, header: dict) -> None:
        fn = getattr(self.index, "apply_snapshot_header", None)
        if fn is not None:
            fn(header)

    @property
    def placement_dirty(self) -> bool:
        return bool(getattr(self.index, "placement_dirty", False))

    def placement_blob_if_dirty(self) -> dict | None:
        fn = getattr(self.index, "placement_blob_if_dirty", None)
        return fn() if fn is not None else None

    def restore_placement(self, blob: dict) -> None:
        fn = getattr(self.index, "restore_placement", None)
        if fn is not None:
            fn(blob)

    def finish_restore(self) -> None:
        fn = getattr(self.index, "finish_restore", None)
        if fn is not None:
            fn()


class LshKnnIndex(_FilteredMixin, InnerIndexImpl):
    """LSH bucketed KNN (reference: _knn_lsh.py semantics; device scoring)."""

    def __init__(
        self,
        dim: int,
        metric: str = "cos",
        n_or: int = 8,
        n_and: int = 10,
        bucket_length: float = 10.0,
        capacity: int = 1024,
        seed: int = 0,
    ):
        _FilteredMixin.__init__(self)
        self.projector = LshProjector(dim, n_or=n_or, n_and=n_and, seed=seed)
        self.index = DeviceKnnIndex(dim=dim, metric=metric, capacity=capacity)
        self.buckets: dict[tuple[int, int], set] = defaultdict(set)
        self.sig_of_key: dict[Hashable, np.ndarray] = {}
        self._pending: dict[Hashable, np.ndarray] = {}
        # serving threads query while an ingest thread adds — same
        # contract as DeviceKnnIndex (ops/knn.py), which this class wraps
        self._lock = threading.RLock()

    def add(self, key, data, metadata) -> None:
        # flatten up front: upsert accepts any shape via reshape(-1), and the
        # staging dict must stay np.stack-homogeneous for the batched flush
        vec = np.asarray(data, dtype=np.float32).reshape(-1)
        with self._lock:
            self.index.upsert(key, vec)
            # Signature computation is deferred and batched: one device
            # matmul per flush instead of one per add: a per-add dispatch
            # pays launch latency 30k times where one batched 30k x dim
            # matmul is milliseconds.
            self._pending[key] = vec
            self._store_meta(key, metadata)

    def _flush_pending(self) -> None:
        if not self._pending:
            return
        keys = list(self._pending)
        vecs = np.stack([self._pending[k] for k in keys])
        # compute signatures BEFORE dropping the staging dict: a transient
        # device failure here must leave the flush retryable, not silently
        # strip these keys out of every future candidate set
        sigs = self.projector.signatures(vecs)
        for k in keys:
            self._pending.pop(k, None)
        for key, sig in zip(keys, sigs):
            old = self.sig_of_key.get(key)
            if old is not None:  # re-add: drop stale bucket entries
                for band, bucket in enumerate(old):
                    self.buckets[(band, int(bucket))].discard(key)
            self.sig_of_key[key] = sig
            for band, bucket in enumerate(sig):
                self.buckets[(band, int(bucket))].add(key)

    def remove(self, key) -> None:
        with self._lock:
            self._pending.pop(key, None)
            self.index.remove(key)
            sig = self.sig_of_key.pop(key, None)
            if sig is not None:
                for band, bucket in enumerate(sig):
                    self.buckets[(band, int(bucket))].discard(key)
            self._drop_meta(key)

    def search(self, queries):
        if not queries:
            return []
        vecs = np.stack([np.asarray(q[0], dtype=np.float32) for q in queries])
        # query signatures only read the (immutable) projections — no lock
        sigs = self.projector.signatures(vecs)
        # hold the lock just long enough to flush staged adds and snapshot
        # candidate sets; the single batched device rescoring call below
        # must NOT serialize ingest (search_among_batched resolves/filters
        # keys under DeviceKnnIndex's own lock, tolerating concurrent
        # removals)
        with self._lock:
            self._flush_pending()
            cand_lists = []
            for sig in sigs:
                candidates: set = set()
                for band, bucket in enumerate(sig):
                    candidates |= self.buckets.get((band, int(bucket)), set())
                cand_lists.append(list(candidates))
        # exact rescoring over the candidate sets only, ALL queries in one
        # device call (reference: _knn_lsh.py:219-256 knn candidate
        # rescoring); the per-query form pays one dispatch per query.
        kmax = max(
            q[1] * (self.OVERSAMPLE if q[2] else 1) for q in queries
        )
        raw_rows = self.index.search_among_batched(vecs, cand_lists, kmax)
        results = []
        for (data, k, flt), raw in zip(queries, raw_rows):
            oversample = self.OVERSAMPLE if flt else 1
            results.append(self._apply_filter(raw[: k * oversample], flt, k))
        return results

    # -- snapshot routing spec ------------------------------------------
    # Bugfix (ISSUE 12): the projector's seed/projections were not part
    # of any snapshot — a process restored from a snapshot written under
    # a different seed (or a changed code default) would bucket the SAME
    # vectors differently and route queries to the wrong partitions.
    # The spec now rides the index delta-chunk header (PR 6 framing,
    # FORMAT_VERSION-compatible) and is re-applied before restore.
    def snapshot_header(self) -> dict:
        return {"lsh": self.projector.spec()}

    def apply_snapshot_header(self, header: dict) -> None:
        spec = (header or {}).get("lsh")
        if not spec or self.projector.spec() == spec:
            return
        with self._lock:
            if self.sig_of_key or self._pending:
                # applied mid-life (not the usual empty-at-restore case):
                # existing signatures were computed under the old
                # projections and must not mix with new ones — the raw
                # vectors needed to recompute them are not retained, so
                # refuse (BEFORE touching the projector — a half-applied
                # swap would corrupt the very buckets the guard protects)
                raise RuntimeError(
                    "LSH projector spec can only be applied to an empty "
                    "index (restore order applies the header before rows)"
                )
            self.projector = LshProjector.from_spec(spec)


class BM25Index(_FilteredMixin, InnerIndexImpl):
    """Okapi BM25 full-text index, host-side
    (reference: src/external_integration/tantivy_integration.rs;
    stdlib/indexing/bm25.py:41 TantivyBM25)."""

    query_is_text = True

    def __init__(self, k1: float = 1.2, b: float = 0.75):
        _FilteredMixin.__init__(self)
        self.k1 = k1
        self.b = b
        self.doc_terms: dict[Hashable, Counter] = {}
        self.doc_len: dict[Hashable, int] = {}
        self.postings: dict[str, set] = defaultdict(set)
        self.total_len = 0
        # the serving scheduler searches from its own thread while the
        # engine thread mutates — same contract as DeviceKnnIndex's lock
        self._lock = threading.RLock()

    @staticmethod
    def _terms(text: str) -> list[str]:
        import re

        return re.findall(r"\w+", str(text).lower())

    def add(self, key, data, metadata) -> None:
        with self._lock:
            if key in self.doc_terms:
                self.remove(key)
            terms = Counter(self._terms(data))
            self.doc_terms[key] = terms
            n = sum(terms.values())
            self.doc_len[key] = n
            self.total_len += n
            for t in terms:
                self.postings[t].add(key)
            self._store_meta(key, metadata)

    def remove(self, key) -> None:
        with self._lock:
            terms = self.doc_terms.pop(key, None)
            if terms is None:
                return
            self.total_len -= self.doc_len.pop(key, 0)
            for t in terms:
                self.postings[t].discard(key)
            self._drop_meta(key)

    def search(self, queries):
        with self._lock:
            return self._search_locked(queries)

    def _search_locked(self, queries):
        n_docs = len(self.doc_terms)
        if n_docs == 0:
            return [[] for _ in queries]
        avg_len = self.total_len / n_docs
        results = []
        for data, k, flt in queries:
            scores: dict[Hashable, float] = defaultdict(float)
            for term in self._terms(data):
                docs = self.postings.get(term)
                if not docs:
                    continue
                idf = math.log(1 + (n_docs - len(docs) + 0.5) / (len(docs) + 0.5))
                for key in docs:
                    tf = self.doc_terms[key][term]
                    dl = self.doc_len[key]
                    scores[key] += (
                        idf
                        * tf
                        * (self.k1 + 1)
                        / (tf + self.k1 * (1 - self.b + self.b * dl / avg_len))
                    )
            ranked = sorted(scores.items(), key=lambda kv: -kv[1])
            results.append(self._apply_filter(ranked, flt, k))
        return results


# ---------------------------------------------------------------------------
# factories (reference: nearest_neighbors.py:428-560; bm25.py:109)
# ---------------------------------------------------------------------------


@dataclass
class InnerIndexFactory:
    """Builds an InnerIndexImpl per run (reference:
    AbstractRetrieverFactory / ExternalIndexFactory)."""

    def build_inner_index(self) -> InnerIndexImpl:
        raise NotImplementedError

    # reference probes the embedder with "." to learn the dimension
    # (nearest_neighbors.py:411 _get_embed_dimensions)
    def _resolve_dim(self, dim, embedder) -> int:
        if dim is not None:
            return dim
        if embedder is not None:
            if hasattr(embedder, "get_embedding_dimension"):
                d = embedder.get_embedding_dimension()
                if d:
                    return d
            probe = _call_embedder(embedder, ".")
            return int(np.asarray(probe).reshape(-1).shape[0])
        raise ValueError("either dimensions or embedder must be provided")


def _call_embedder(embedder, text: str):
    import asyncio
    import inspect

    fn = getattr(embedder, "__wrapped__", embedder)
    if inspect.iscoroutinefunction(fn):
        return asyncio.run(fn(text))
    result = fn(text)
    if inspect.iscoroutine(result):
        return asyncio.run(result)
    return result


@dataclass
class BruteForceKnnFactory(InnerIndexFactory):
    """reference: nearest_neighbors.py:482.  ``mesh`` shards the index
    over a device mesh (ShardedKnnIndex) for multi-chip serving."""

    dimensions: int | None = None
    reserved_space: int = 1024
    metric: str = USearchMetricKind.COS
    embedder: Any = None
    mesh: Any = None
    #: "f32" / "bf16" / "int8"; None = the PATHWAY_INDEX_DTYPE default
    index_dtype: str | None = None
    #: >0 = tiered index with this HBM hot-row budget;
    #: None = the PATHWAY_TIER_HOT_ROWS default (0 keeps it untiered)
    hot_rows: int | None = None

    def build_inner_index(self) -> InnerIndexImpl:
        dim = self._resolve_dim(self.dimensions, self.embedder)
        return BruteForceKnnIndex(
            dim=dim, metric=self.metric, capacity=self.reserved_space,
            mesh=self.mesh, index_dtype=self.index_dtype,
            hot_rows=self.hot_rows,
        )


@dataclass
class UsearchKnnFactory(InnerIndexFactory):
    """reference: nearest_neighbors.py:428 — HNSW there; on TPU the exact
    HBM matmul index answers faster than a host HNSW walk, so this maps to
    the same device index (connectivity/ef params accepted, unused)."""

    dimensions: int | None = None
    reserved_space: int = 1024
    metric: str = USearchMetricKind.COS
    connectivity: int = 0
    expansion_add: int = 0
    expansion_search: int = 0
    embedder: Any = None
    mesh: Any = None
    #: "f32" / "bf16" / "int8"; None = the PATHWAY_INDEX_DTYPE default
    index_dtype: str | None = None
    #: >0 = tiered index with this HBM hot-row budget;
    #: None = the PATHWAY_TIER_HOT_ROWS default (0 keeps it untiered)
    hot_rows: int | None = None

    def build_inner_index(self) -> InnerIndexImpl:
        dim = self._resolve_dim(self.dimensions, self.embedder)
        return BruteForceKnnIndex(
            dim=dim, metric=self.metric, capacity=self.reserved_space,
            mesh=self.mesh, index_dtype=self.index_dtype,
            hot_rows=self.hot_rows,
        )


@dataclass
class LshKnnFactory(InnerIndexFactory):
    """reference: nearest_neighbors.py:528"""

    dimensions: int | None = None
    n_or: int = 8
    n_and: int = 10
    bucket_length: float = 10.0
    distance_type: str = "cosine"
    embedder: Any = None
    #: projection seed — persisted in the snapshot header so a restored
    #: process routes queries to the same buckets
    seed: int = 0

    def build_inner_index(self) -> InnerIndexImpl:
        dim = self._resolve_dim(self.dimensions, self.embedder)
        metric = "cos" if self.distance_type.startswith("cos") else "l2sq"
        return LshKnnIndex(
            dim=dim, metric=metric, n_or=self.n_or, n_and=self.n_and,
            bucket_length=self.bucket_length, seed=self.seed,
        )


@dataclass
class TantivyBM25Factory(InnerIndexFactory):
    """reference: bm25.py:109 (name kept for parity; host-side Okapi BM25)."""

    ram_budget: int = 50_000_000
    in_memory_index: bool = True

    def build_inner_index(self) -> InnerIndexImpl:
        return BM25Index()


BM25Factory = TantivyBM25Factory
