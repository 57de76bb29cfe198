"""Continuous cross-request serving scheduler.

The engine's :class:`~pathway_tpu.xpacks.llm._utils.AsyncMicroBatcher`
coalesces only the calls that land in the *same* engine micro-batch, so
under concurrent REST load the device would see one small embed/search
dispatch per request and query p99 balloons (serving_bench: p99 ≈ 2.4×
p50 on CPU).  Serving decouples device batching from engine cadence the
way WindVE (arXiv:2504.14941) decouples a host-side concurrency queue
from the accelerator — and the ONE admission queue and device-step loop
that does so is the unified device-tick runtime
(:mod:`pathway_tpu.runtime`):

* :class:`ServingScheduler` is a **counter-keeping facade** over it:
  ``submit`` hands each work item (embed texts, rerank pairs, fused
  retrieve requests) to ``get_runtime().submit`` as ``INTERACTIVE`` work
  (so it preempts bulk-ingest chunks at tick granularity) with this
  scheduler's ``max_wait_ms`` as the item's coalesce window, so one tick
  carries embeds from request A, KNN probes from request B and rerank
  pairs from request C, each kind as one padded device dispatch (the
  power-of-two bucketing in ``models/encoder.py`` / ``ops/topk.bucket_k``
  keeps XLA compile counts flat across the ragged batch sizes this
  produces).  The facade holds no thread, queue or condition variable;
* requests carry an optional **deadline**: items whose deadline passed
  before dispatch are shed by the runtime with :class:`DeadlineExceeded`
  (REST planes map it to 503 + ``Retry-After``) and their device work
  never runs — backpressure, not collapse.  Admission beyond this
  scheduler's own ``max_queue`` pending items is refused immediately
  with :class:`SchedulerOverloaded`.

Observability (queue depth, batch occupancy, wait-time histogram,
deadline drops) is kept per scheduler through the runtime's observer
hooks (``_obs_*``), registers with ``internals/monitoring.py`` and
renders on the OpenMetrics ``/status`` endpoint as
``pathway_scheduler_*`` series.
"""

from __future__ import annotations

import asyncio
import os
import threading
from concurrent.futures import Future
from typing import Any

import numpy as np

from ...runtime import (
    AdmissionRefused,
    DeadlineExceeded,
    QoS,
    WorkGroup,
    get_runtime,
)

__all__ = [
    "ServingScheduler",
    "WorkGroup",
    "DeadlineExceeded",
    "SchedulerOverloaded",
    "ServingNotReady",
    "RetrievePlane",
    "get_scheduler",
    "configure",
    "scheduler_enabled",
    "serving_settings",
]


#: admission refused: the queue is at capacity (the runtime's exception,
#: kept under its historical serving name)
SchedulerOverloaded = AdmissionRefused


class ServingNotReady(DeadlineExceeded):
    """The live index is not lowered yet (engine still starting up)."""


#: wait-time histogram bucket upper bounds (milliseconds)
_WAIT_BUCKETS_MS = (1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0)


class ServingScheduler:
    """Per-scheduler admission cap and counters over the shared runtime
    (see module docstring)."""

    def __init__(
        self,
        *,
        max_batch: int = 256,
        max_wait_ms: float = 2.0,
        max_queue: int = 1024,
        retry_after_s: float = 1.0,
        name: str = "serving",
    ):
        self.max_batch = max_batch
        self.max_wait_ms = max_wait_ms
        self.max_queue = max_queue
        self.retry_after_s = retry_after_s
        self.name = name
        #: items currently enqueued on the shared runtime on this
        #: scheduler's behalf (its queue-depth/admission view)
        self._runtime_pending = 0
        # metrics — the runtime's tick thread updates them through the
        # observer hooks while submitters read them
        self._mx = threading.Lock()
        self._counters = {
            "submitted_total": 0,
            "completed_total": 0,
            "failed_total": 0,
            "shed_deadline_total": 0,
            "shed_queue_total": 0,
            "batches_total": 0,
            "multi_item_batches_total": 0,
        }
        self._occupancy_sum = 0
        self._occupancy_max = 0
        self._queue_depth_max = 0
        self._wait_buckets = [0] * (len(_WAIT_BUCKETS_MS) + 1)
        self._wait_sum_ms = 0.0
        self._wait_count = 0
        from ...internals.monitoring import register_metrics_provider

        register_metrics_provider(name, self)

    # -- submission ------------------------------------------------------
    def submit(
        self,
        group: WorkGroup,
        payload: Any,
        *,
        deadline_s: float | None = None,
        sheddable: bool | None = None,
        trace: Any = None,
    ) -> Future:
        """Enqueue one payload; the future resolves when its batch ran.

        ``deadline_s`` is a relative budget: if the item is still queued
        that long after submission it is shed with :class:`DeadlineExceeded`
        and its work never executes.  ``None`` (engine-plane work) is
        never shed.

        ``sheddable`` work (default: anything with a deadline; serving
        planes pass True explicitly) is additionally subject to
        ``max_queue`` admission control.  Engine-plane work is exempt:
        refusing an ingest micro-batch's embeds would error the engine,
        and its volume is already bounded by engine batch sizes.

        ``trace`` (a sampled ``RequestTrace``) rides the item: the drain
        stamps its queue wait and the batch handler's stage timers
        (embed, search) attribute device time back to the request.
        """
        if sheddable is None:
            sheddable = deadline_s is not None
        if trace is not None and not trace.sampled:
            trace = None
        # This scheduler keeps its own admission cap (max_queue over ITS
        # OWN pending items) and its pathway_scheduler_* counters via
        # the observer hooks below; re-entrant submits from the runtime
        # thread are handled by the runtime itself (inline, inheriting
        # the running tick's class — no class inversion, no deadlock).
        rt = get_runtime()
        if (
            sheddable
            and not rt.on_runtime_thread()
            and self._runtime_pending >= self.max_queue
        ):
            with self._mx:
                self._counters["shed_queue_total"] += 1
            fut: Future = Future()
            fut.set_exception(
                SchedulerOverloaded(
                    f"scheduler queue full ({self.max_queue} pending)",
                    retry_after_s=self.retry_after_s,
                )
            )
            return fut
        with self._mx:
            self._counters["submitted_total"] += 1
        return rt.submit(
            group,
            payload,
            qos=QoS.INTERACTIVE,
            deadline_s=deadline_s,
            sheddable=sheddable,
            trace=trace,
            coalesce_s=self.max_wait_ms / 1000.0,
            observer=self,
            retry_after_s=self.retry_after_s,
        )

    async def submit_async(
        self,
        group: WorkGroup,
        payload: Any,
        *,
        deadline_s: float | None = None,
        sheddable: bool | None = None,
        trace: Any = None,
    ) -> Any:
        return await asyncio.wrap_future(
            self.submit(
                group, payload,
                deadline_s=deadline_s, sheddable=sheddable, trace=trace,
            )
        )

    def executor_alive(self) -> bool:
        """Is the device-step executor serving this scheduler (the shared
        runtime's tick thread) alive?  (The containment tests' "the loop
        survived the fault" observable.)"""
        rt = get_runtime()
        return rt._thread is not None and rt._thread.is_alive()

    # -- runtime observer hooks ------------------------------------------
    # The shared runtime calls these (never under its condition variable)
    # so this scheduler's per-instance counters — queue depth, wait
    # histogram, occupancy, shed/completed/failed — stay truthful while
    # the actual draining happens on the unified executor.
    def _obs_enqueued(self) -> None:
        with self._mx:
            self._runtime_pending += 1
            if self._runtime_pending > self._queue_depth_max:
                self._queue_depth_max = self._runtime_pending

    def _obs_drained(self) -> None:
        with self._mx:
            self._runtime_pending -= 1

    def _obs_wait(self, wait_ms: float) -> None:
        self._observe_wait(wait_ms)

    def _obs_shed_deadline(self) -> None:
        with self._mx:
            self._counters["shed_deadline_total"] += 1

    def _obs_refused(self) -> None:
        with self._mx:
            self._counters["shed_queue_total"] += 1

    def _obs_batch(self, n: int) -> None:
        with self._mx:
            self._counters["batches_total"] += 1
            if n > 1:
                self._counters["multi_item_batches_total"] += 1
            self._occupancy_sum += n
            if n > self._occupancy_max:
                self._occupancy_max = n

    def _obs_done(self, n: int, ok: bool) -> None:
        with self._mx:
            self._counters["completed_total" if ok else "failed_total"] += n

    def _observe_wait(self, wait_ms: float) -> None:
        with self._mx:
            self._wait_sum_ms += wait_ms
            self._wait_count += 1
            for i, le in enumerate(_WAIT_BUCKETS_MS):
                if wait_ms <= le:
                    self._wait_buckets[i] += 1
                    break
            else:
                self._wait_buckets[-1] += 1

    # -- observability ---------------------------------------------------
    def stats(self) -> dict[str, Any]:
        with self._mx:
            batches = self._counters["batches_total"]
            return {
                **self._counters,
                # pending items live on the shared runtime's interactive
                # queue, tracked per scheduler via the hooks
                "queue_depth": self._runtime_pending,
                "queue_depth_max": self._queue_depth_max,
                "batch_occupancy_mean": (
                    self._occupancy_sum / batches if batches else 0.0
                ),
                "batch_occupancy_max": self._occupancy_max,
                "wait_ms_sum": self._wait_sum_ms,
                "wait_ms_count": self._wait_count,
                "wait_ms_buckets": [
                    (le, n)
                    for le, n in zip(
                        (*_WAIT_BUCKETS_MS, float("inf")), self._wait_buckets
                    )
                ],
            }

    def openmetrics_lines(self) -> list[str]:
        """``pathway_scheduler_*`` series for the /status endpoint."""
        from ...internals.metrics_names import escape_label_value

        s = self.stats()
        lbl = f'scheduler="{escape_label_value(self.name)}"'
        lines = []
        for metric, kind in (
            ("submitted_total", "counter"),
            ("completed_total", "counter"),
            ("failed_total", "counter"),
            ("shed_deadline_total", "counter"),
            ("shed_queue_total", "counter"),
            ("batches_total", "counter"),
            ("multi_item_batches_total", "counter"),
            ("queue_depth", "gauge"),
            ("queue_depth_max", "gauge"),
            ("batch_occupancy_max", "gauge"),
        ):
            lines.append(f"# TYPE pathway_scheduler_{metric} {kind}")
            lines.append(f"pathway_scheduler_{metric}{{{lbl}}} {s[metric]}")
        lines.append("# TYPE pathway_scheduler_batch_occupancy_mean gauge")
        lines.append(
            f"pathway_scheduler_batch_occupancy_mean{{{lbl}}} "
            f"{s['batch_occupancy_mean']:.3f}"
        )
        lines.append("# TYPE pathway_scheduler_wait_ms histogram")
        cum = 0
        for le, n in s["wait_ms_buckets"]:
            cum += n
            le_s = "+Inf" if le == float("inf") else f"{le:g}"
            lines.append(
                f'pathway_scheduler_wait_ms_bucket{{{lbl},le="{le_s}"}} {cum}'
            )
        lines.append(
            f"pathway_scheduler_wait_ms_sum{{{lbl}}} {s['wait_ms_sum']:.3f}"
        )
        lines.append(
            f"pathway_scheduler_wait_ms_count{{{lbl}}} {s['wait_ms_count']}"
        )
        return lines


# ---------------------------------------------------------------------------
# process-global scheduler + settings
# ---------------------------------------------------------------------------


def _env_flag(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("0", "false", "off", "no", "")


_SETTINGS: dict[str, Any] = {
    "enabled": _env_flag("PATHWAY_SERVING_SCHEDULER", True),
    "max_batch": int(os.environ.get("PATHWAY_SERVING_MAX_BATCH", "256")),
    # 5 ms absorbs the few-ms arrival stagger of a burst (e.g. responses
    # of one tick fanning back out through HTTP and returning) so bursts
    # stay coalesced instead of splitting into alternating half-full
    # ticks; singleton queries pay at most this much extra
    "max_wait_ms": float(os.environ.get("PATHWAY_SERVING_MAX_WAIT_MS", "5.0")),
    "max_queue": int(os.environ.get("PATHWAY_SERVING_MAX_QUEUE", "1024")),
    "deadline_ms": (
        float(os.environ["PATHWAY_SERVING_DEADLINE_MS"])
        if os.environ.get("PATHWAY_SERVING_DEADLINE_MS")
        else None
    ),
    "retry_after_s": float(os.environ.get("PATHWAY_SERVING_RETRY_AFTER_S", "1.0")),
}
_GLOBAL_LOCK = threading.Lock()
_GLOBAL: ServingScheduler | None = None


def scheduler_enabled() -> bool:
    return bool(_SETTINGS["enabled"])


def serving_settings() -> dict[str, Any]:
    return dict(_SETTINGS)


def configure(**kwargs: Any) -> None:
    """Adjust the global serving policy (``enabled``, ``max_batch``,
    ``max_wait_ms``, ``max_queue``, ``deadline_ms``, ``retry_after_s``).
    Live knobs apply to the already-running global scheduler too."""
    unknown = set(kwargs) - set(_SETTINGS)
    if unknown:
        raise TypeError(f"unknown serving settings: {sorted(unknown)}")
    _SETTINGS.update(kwargs)
    with _GLOBAL_LOCK:
        sched = _GLOBAL
    if sched is not None:
        for knob in ("max_batch", "max_wait_ms", "max_queue", "retry_after_s"):
            if knob in kwargs:
                setattr(sched, knob, kwargs[knob])


def get_scheduler() -> ServingScheduler:
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = ServingScheduler(
                max_batch=_SETTINGS["max_batch"],
                max_wait_ms=_SETTINGS["max_wait_ms"],
                max_queue=_SETTINGS["max_queue"],
                retry_after_s=_SETTINGS["retry_after_s"],
            )
        return _GLOBAL


# ---------------------------------------------------------------------------
# fused retrieve plane (embed → KNN in one scheduler tick)
# ---------------------------------------------------------------------------


def _encode_under_dispatch_lock(embedder, encode_fn, texts: list[str]):
    """Run one model encode holding the batcher's dispatch lock: with a
    mixed configuration (e.g. use_scheduler=False on the embedder)
    engine-plane encodes run off this thread under the same lock, and the
    model is not thread-safe across concurrent callers.  The one lock
    contract for both the host and the fused device embed paths."""
    from ._utils import coerce_str

    batcher = getattr(embedder, "_batcher", None)
    lock = getattr(batcher, "_dispatch_lock", None)
    coerced = [coerce_str(t) for t in texts]
    if lock is not None:
        with lock:
            return encode_fn(coerced)
    return encode_fn(coerced)


def _batch_embed(embedder, texts: list[str]):
    """One padded device dispatch for a batch of query texts.

    Model-backed embedders expose their underlying encoder
    (``_ensure_encoder``) — calling it directly keeps the embeddings as
    one device array handed straight to the index search (the fused
    path) AND avoids re-entering the scheduler from its own thread.
    Generic UDF embedders fall back to per-text calls.
    """
    from ._utils import coerce_str

    ensure = getattr(embedder, "_ensure_encoder", None)
    if ensure is not None:
        enc = ensure()
        return _encode_under_dispatch_lock(embedder, enc.encode, texts)
    from .embedders import _call_sync

    fn = getattr(embedder, "__wrapped__", embedder)
    return np.stack(
        [np.asarray(_call_sync(fn, coerce_str(t))).reshape(-1) for t in texts]
    )


def _batch_embed_device(embedder, texts: list[str]):
    """Device-resident variant of :func:`_batch_embed` for the fused
    embed→search tick: ONE whole-batch launch whose device output is
    handed straight to the index search (``SentenceEncoder.encode_padded``
    — rows past ``len(texts)`` are dispatch pads the search discards by
    construction).  Returns ``None`` when the embedder has no model-backed
    encoder or the batch falls outside the padded dispatch's envelope —
    callers fall back to the host path.  ``PATHWAY_FUSED_SERVING=0``
    disables the device handoff for A/B runs (the host path is the
    pre-PR8 behavior: embeddings round-trip D2H then re-stage H2D for
    the search)."""
    if not _env_flag("PATHWAY_FUSED_SERVING", True):
        return None
    ensure = getattr(embedder, "_ensure_encoder", None)
    if ensure is None:
        return None
    enc = ensure()
    encode_padded = getattr(enc, "encode_padded", None)
    if encode_padded is None:
        return None
    try:
        embs, _n = _encode_under_dispatch_lock(
            embedder, encode_padded, texts
        )
    except ValueError:
        return None  # outside the dispatch buckets — host path handles it
    import jax.numpy as jnp

    from ...ops.fused_serving import record_launch, serving_wire_dtype

    if serving_wire_dtype() == "bf16" and embs.dtype == jnp.float32:
        # bf16-on-the-wire (the serving default): half the bytes on the
        # encoder→search handoff.  The fused search and the query-cache
        # combine both widen back to f32 in-register before any
        # normalization or cache fill — bf16→f32 is exact, so scores
        # and cache hit/miss bit-exactness are unchanged
        # (PATHWAY_SERVING_WIRE_DTYPE=f32 opts out, see MIGRATION).
        embs = embs.astype(jnp.bfloat16)
        record_launch("wire")
    return embs


class _LexicalMirror:
    """Degraded-mode lexical fallback: a host-side BM25 index (the same
    scoring the hybrid index's lexical side uses,
    ``stdlib/indexing/retrievers.BM25Index``) mirrored lazily from the
    live index node's doc payloads.  When the embedder breaker is open,
    ``/v1/retrieve`` answers from here — wrong ranking beats no answer
    for a RAG service (EdgeRAG, arXiv 2412.21023)."""

    def __init__(self, text_i: int, meta_i: int):
        from ...stdlib.indexing.retrievers import BM25Index
        from ...internals.value import Json

        self._Json = Json
        self._bm25 = BM25Index()
        self._text_i = text_i
        self._meta_i = meta_i
        self._have: set = set()
        self._lock = threading.Lock()

    def _sync(self, node) -> None:
        # dict(d) is one C-level copy under the GIL — safe against the
        # engine thread mutating doc_payload mid-snapshot
        snap = dict(node.doc_payload)
        with self._lock:
            for key in self._have - snap.keys():
                self._bm25.remove(key)
            for key, payload in snap.items():
                if key in self._have:
                    continue
                meta = payload[self._meta_i]
                if isinstance(meta, self._Json):
                    meta = meta.value
                from ._utils import coerce_str

                self._bm25.add(key, coerce_str(payload[self._text_i]), meta)
            self._have = set(snap)

    def search(self, node, items: list[tuple[str, int, str | None]]):
        self._sync(node)
        return self._bm25.search(list(items))


class RetrievePlane:
    """Scheduler-served ``/v1/retrieve``: concurrent REST requests coalesce
    into one fused embed→search tick over the LIVE index (the engine keeps
    maintaining it; queries no longer ride engine micro-batch cadence).

    Answers are as-of-now: each batch reads the index's current state
    under its own lock, the same contract ``query_as_of_now`` serves.

    Failure domain: consecutive embed failures trip ``breaker`` (a
    :class:`~pathway_tpu.xpacks.llm._breaker.CircuitBreaker`); while it is
    open, queries are served from the BM25 lexical mirror and responses
    carry ``"degraded": true`` instead of 5xx-ing.  A half-open probe
    batch restores the vector path automatically once the embedder heals.
    """

    def __init__(
        self,
        *,
        index_factory: Any,
        embedder: Any,
        payload_columns: list[str],
        scheduler: ServingScheduler | None = None,
        deadline_ms: float | None = None,
        include_score: bool = False,
        max_batch: int | None = None,
        label: str = "retrieve",
        breaker: Any = None,
        lexical_fallback: bool = True,
    ):
        self.scheduler = scheduler if scheduler is not None else get_scheduler()
        self.index_factory = index_factory
        self.embedder = embedder
        self.include_score = include_score
        self._deadline_ms_override = deadline_ms
        self._text_i = payload_columns.index("text")
        self._meta_i = payload_columns.index("metadata")
        if breaker is None and embedder is not None:
            from ._breaker import CircuitBreaker

            breaker = CircuitBreaker(f"embedder:{label}")
        self.breaker = breaker
        self._mirror = (
            _LexicalMirror(self._text_i, self._meta_i)
            if lexical_fallback
            else None
        )
        if max_batch is None:
            max_batch = self.scheduler.max_batch
        from ._utils import estimate_tokens

        # token estimate = the query text's mass: the runtime's tick
        # budget then sees retrieve work at the same scale as embed work
        self.group = WorkGroup(
            label,
            self._batch,
            max_batch=max_batch,
            token_estimate=lambda payload: estimate_tokens(payload[0]),
        )
        # serving cache stack (xpacks/llm/_query_cache): embedding +
        # result caches and the collaborative CPU embed path, built
        # lazily on first healthy batch so env knobs read at serve time
        self._query_cache_stack = None
        self._query_cache_tried = False
        self._query_cache_build_logged = False
        self._refresh_group: WorkGroup | None = None

    @property
    def deadline_ms(self) -> float | None:
        """Per-plane override, else the LIVE global setting — so
        ``configure(deadline_ms=...)`` applies to running servers too."""
        if self._deadline_ms_override is not None:
            return self._deadline_ms_override
        return _SETTINGS["deadline_ms"]

    # -- batch handler (scheduler thread) --
    def _batch(
        self, items: list[tuple[str, int, str | None]]
    ) -> list[dict]:
        from ...stdlib.indexing.lowering import live_index_node

        node = live_index_node(self.index_factory)
        if node is None:
            raise ServingNotReady(
                "index is not serving yet (engine starting)",
                retry_after_s=self.scheduler.retry_after_s,
            )
        index = node.index
        # warm-restart health gate, checked BEFORE any index read: while
        # the driver streams snapshot chunks back in, results come from
        # half-restored state and must never be presented as authoritative
        restoring = getattr(node, "_restore_state", None) == "restoring"
        if getattr(index, "query_is_text", False):
            from ...internals.flight_recorder import batch_stage as _bs

            # a restoring lexical index still answers (restore is
            # host-side and monotone) but the reply is tagged degraded —
            # partial results, not authoritative ones
            with _bs("search"):
                raw = index.search(list(items))
            return [
                {"results": self._pack(node, row), "degraded": restoring}
                for row in raw
            ]
        from ...internals.flight_recorder import batch_stage

        # vector path while restoring: answer from the lexical mirror
        # (tagged degraded) until the restored frontier catches the
        # commit record, never 503
        if restoring:
            if self._mirror is None:
                raise ServingNotReady(
                    "index is restoring from snapshot",
                    retry_after_s=self.scheduler.retry_after_s,
                )
            with batch_stage("lexical_search"):
                raw = self._mirror.search(node, items)
            return [
                {"results": self._pack(node, row), "degraded": True}
                for row in raw
            ]
        if self.embedder is None:
            raise RuntimeError(
                "retrieve plane needs an embedder for a vector index"
            )
        raw = None
        if self.breaker is None or self.breaker.allow():
            try:
                from ...testing import faults

                if faults.enabled:
                    faults.perturb("embedder")
                texts = [q for q, _, _ in items]
                specs = [(k, flt) for _, k, flt in items]
                stack = self._cache_stack()
                # the cache stack fronts only the fully-healthy fused
                # path: a half-open breaker's probe batch must actually
                # probe the device (a cache hit would "heal" a dead
                # embedder), and custom indexes without search_embedded
                # keep the legacy per-row path
                use_stack = (
                    stack is not None
                    and hasattr(index, "search_embedded")
                    and getattr(node, "commit_seq", None) is not None
                    and (self.breaker is None or self.breaker.state == "closed")
                )
                if use_stack:
                    raw = stack.serve(self, node, index, texts, specs, items)
                else:
                    with batch_stage("embed"):
                        # fused handoff: keep the tick's embeddings ON
                        # DEVICE between encode and search when the index
                        # consumes whole-batch queries (search discards
                        # the dispatch pad rows; the sharded index
                        # replicates the batch across the mesh and merges
                        # per-shard top-k over ICI)
                        embs = None
                        if hasattr(index, "search_embedded"):
                            embs = _batch_embed_device(self.embedder, texts)
                        if embs is None:
                            embs = _batch_embed(self.embedder, texts)
                    with batch_stage("search"):
                        if hasattr(index, "search_embedded"):
                            raw = index.search_embedded(embs, specs)
                        else:
                            raw = index.search(
                                [(embs[i], k, flt) for i, (k, flt) in enumerate(specs)]
                            )
            except Exception as exc:  # noqa: BLE001 — degrade, don't 5xx
                # record FIRST: even without a fallback the breaker must
                # trip so repeated failures fail fast (ServingNotReady)
                # instead of paying the full embed timeout per request
                if self.breaker is not None:
                    self.breaker.record_failure(exc)
                from ...internals.errors import register_error

                register_error(
                    f"serving embed/search failed, degrading to lexical: "
                    f"{type(exc).__name__}: {exc}",
                    kind="serving",
                    operator=self.group.label,
                )
                # device-fault containment: a FATAL device error (HBM
                # OOM, XLA runtime error, dead transfer) means the index
                # arrays are suspect — rebuild them from the host mirror
                # / snapshot now, so the breaker's half-open probe runs
                # against healthy buffers instead of re-tripping forever
                from ...ops.device_faults import FATAL, classify_device_error

                if classify_device_error(exc) == FATAL and hasattr(
                    node, "rebuild_device_state"
                ):
                    try:
                        node.rebuild_device_state()
                    except Exception as rexc:  # noqa: BLE001 — degraded
                        register_error(
                            f"index rebuild after device fault failed: "
                            f"{type(rexc).__name__}: {rexc}",
                            kind="serving",
                            operator=self.group.label,
                        )
                if self.breaker is None or self._mirror is None:
                    raise
            else:
                if self.breaker is not None:
                    self.breaker.record_success()
        if raw is not None:
            return [
                {"results": self._pack(node, row), "degraded": False}
                for row in raw
            ]
        # degraded path: breaker open (or this batch just tripped it) —
        # lexical BM25 over the live doc payloads, tagged degraded
        if self._mirror is None:
            raise ServingNotReady(
                "embedder unavailable and lexical fallback disabled",
                retry_after_s=self.scheduler.retry_after_s,
            )
        with batch_stage("lexical_search"):
            raw = self._mirror.search(node, items)
        return [
            {"results": self._pack(node, row), "degraded": True}
            for row in raw
        ]

    # -- serving cache stack (xpacks/llm/_query_cache) -------------------
    def _cache_stack(self):
        """The plane's cache stack, built once (None when every layer is
        disabled or the embedder can't be keyed).  A build failure (e.g.
        the embedder's lazy model load hiccuping) must neither ride the
        serving tick's except — a cache is an optimization, charging the
        breaker for it would degrade a healthy device — nor latch: the
        tried-flag is set only on success, so the next batch retries
        (the same lazy load _batch_embed is about to do anyway)."""
        if not self._query_cache_tried:
            from ._query_cache import build_stack

            try:
                self._query_cache_stack = build_stack(
                    self.embedder, label=self.group.label
                )
            except Exception as exc:  # noqa: BLE001 — cache is optional
                if not self._query_cache_build_logged:
                    self._query_cache_build_logged = True
                    from ...internals.errors import register_error

                    register_error(
                        f"query-cache stack build failed (serving "
                        f"uncached, will retry): "
                        f"{type(exc).__name__}: {exc}",
                        kind="serving",
                        operator=self.group.label,
                    )
            else:
                self._query_cache_tried = True
        return self._query_cache_stack

    def _cache_refresh_group(self) -> WorkGroup:
        """WorkGroup for deferred stale-entry refreshes: same handler
        surface as the serving group but its batches recompute WITHOUT
        reading the result cache (a read would re-serve the same stale
        entry and never converge)."""
        if self._refresh_group is None:
            from ._utils import estimate_tokens

            self._refresh_group = WorkGroup(
                f"{self.group.label}:cache_refresh",
                self._refresh_batch,
                max_batch=self.group.max_batch,
                token_estimate=lambda payload: estimate_tokens(payload[0]),
            )
        return self._refresh_group

    def _refresh_batch(self, payloads: list[tuple]):
        """Deferred-refresh batch handler (BULK_INGEST class, nobody
        waits on the futures): payloads are ``(query, k, filter, rkey)``.
        Best-effort — a failure or bypass (restoring, breaker open)
        keeps the stale entry in place for its window and is logged,
        never raised into the runtime loop — but the in-flight markers
        are ALWAYS released, so the next stale serve can re-schedule."""
        from ...stdlib.indexing.lowering import live_index_node

        out = [None] * len(payloads)
        stack = self._query_cache_stack
        if stack is None:
            return out
        rkeys = [p[3] for p in payloads]
        try:
            node = live_index_node(self.index_factory)
            if node is None:
                return out
            if getattr(node, "_restore_state", None) == "restoring":
                return out
            if self.breaker is not None and self.breaker.state != "closed":
                return out
            stack.refresh(
                self, node, node.index, [p[:3] for p in payloads], rkeys
            )
        except Exception as exc:  # noqa: BLE001 — best-effort
            from ...internals.errors import register_error

            register_error(
                f"query-cache deferred refresh failed: "
                f"{type(exc).__name__}: {exc}",
                kind="serving",
                operator=self.group.label,
            )
        finally:
            stack.release_refresh(rkeys)
        return out

    def _pack(self, node, row) -> list[dict]:
        from ...internals.value import Json
        from ._utils import coerce_str

        out = []
        for key, score in row:
            payload = node.doc_payload.get(key)
            if payload is None:  # retracted between search and pack
                continue
            meta = payload[self._meta_i]
            if isinstance(meta, Json):
                meta = meta.value
            entry = {
                "text": coerce_str(payload[self._text_i]),
                "metadata": meta,
                "dist": -float(score),
            }
            if self.include_score:
                entry["score"] = float(score)
            out.append(entry)
        return out

    # -- HTTP handler (webserver thread) --
    def aiohttp_handler(self):
        from ._utils import coerce_str, merge_filter_exprs

        async def handle(request):
            from aiohttp import web

            if request.method in ("POST", "PUT", "PATCH"):
                try:
                    payload = await request.json()
                except Exception:  # noqa: BLE001 — malformed body
                    return web.json_response(
                        {"detail": "request body is not valid JSON"}, status=400
                    )
            else:
                payload = dict(request.query)
            query = coerce_str(payload.get("query", ""))
            try:
                k = int(payload.get("k", 3))
            except (TypeError, ValueError):
                return web.json_response({"detail": "invalid k"}, status=400)
            flt = merge_filter_exprs(
                payload.get("metadata_filter"),
                payload.get("filepath_globpattern"),
            )
            deadline_ms = payload.get("deadline_ms", self.deadline_ms)
            try:
                deadline_s = (
                    None if deadline_ms is None else float(deadline_ms) / 1000.0
                )
            except (TypeError, ValueError):
                return web.json_response(
                    {"detail": "invalid deadline_ms"}, status=400
                )
            # trace context minted/adopted by the webserver's tracing
            # middleware: the scheduler stamps queue_wait, the batch
            # handler embed/search — the full per-stage breakdown lands
            # in the flight recorder under this request's trace id
            trace = request.get("pw_trace")
            from ...internals.flight_recorder import trace_stage

            try:
                result = await self.scheduler.submit_async(
                    self.group, (query, k, flt),
                    deadline_s=deadline_s, sheddable=True, trace=trace,
                )
            except DeadlineExceeded as exc:
                shed_body = {"detail": str(exc)}
                if trace is not None:
                    shed_body["trace_id"] = trace.trace_id
                return web.json_response(
                    shed_body,
                    status=503,
                    headers={"Retry-After": f"{exc.retry_after_s:g}"},
                )
            with trace_stage(trace, "serialize"):
                if result["degraded"]:
                    # degraded-mode contract: an object tagging the
                    # fallback, so callers/monitors can tell lexical
                    # answers apart; the healthy path keeps the
                    # plain-list shape for back-compat (the trace id
                    # rides the x-pathway-trace-id header either way)
                    body = {"results": result["results"], "degraded": True}
                    if trace is not None:
                        body["trace_id"] = trace.trace_id
                    resp = web.json_response(body)
                else:
                    resp = web.json_response(result["results"])
            return resp

        return handle
