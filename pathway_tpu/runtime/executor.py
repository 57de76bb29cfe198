"""Unified device-tick runtime with QoS classes.

PRs 2/5 grew three independently-built producer/consumer loops that all
compete for the same device: the serving scheduler
(``xpacks/llm/_scheduler.py``), the engine-plane micro-batcher
(``xpacks/llm/_utils.AsyncMicroBatcher``) and the ingest pipeline's
device worker (``xpacks/llm/_ingest.py``).  Each had its own queue, its
own drain policy and its own token budget — so a bulk ingest burst could
stall interactive ``/v1/retrieve`` ticks, and there was no single place
to make ticks mesh-aware or route tiered-index work (ROADMAP item 4).

This module is the ONE executor those planes now submit to.  Every
submission is a :class:`WorkItem` carrying a QoS class, a token
estimate, an optional deadline and an optional request trace; the
executor composes each device tick from the class queues under a
**strict-priority-with-budget** policy:

* classes drain in priority order ``INTERACTIVE > LLM_RERANK >
  GENERATE > BULK_INGEST`` — an interactive query arriving while an
  ingest (or decode) backlog
  is queued rides the very next tick, ahead of every queued ingest
  chunk (preemption at tick granularity; ingest submits tick-sized
  chunks precisely so a tick is never longer than one bounded dispatch);
* each tick has a token budget (``tick_tokens``): higher classes fill
  it first, but every lower class with pending work is guaranteed a
  **starvation-bounded minimum share** (``min_share``, ≥ 1 item per
  tick) so sustained interactive load cannot starve ingest to zero;
* per-class **admission control** follows WindVE's (arXiv:2504.14941)
  CPU↔device queue-depth decoupling: each class has a queue-depth
  target and sheddable submissions beyond it are refused immediately
  with :class:`AdmissionRefused` (HTTP planes map it to
  503 + ``Retry-After``) — backpressure, not collapse.  Engine-plane
  work (no deadline) is exempt: refusing it would error the engine.

Existing guarantees ride along unchanged because they live in the batch
handlers, not the loop: breaker/degraded serving (PR 3) and the
restore gate (PR 6) sit inside ``RetrievePlane._batch``, deadline
shedding keeps the 503+Retry-After contract, traces are stamped with
``queue_wait`` and batch-scoped stage spans exactly as the legacy
scheduler did, and every tick lands in the flight recorder.

Re-entrancy: a submit *from the executor thread itself* (e.g. a rerank
triggered inside a retrieve tick) executes inline and **inherits the
running tick's class and budget** instead of jumping the queue — an
inline ``LLM_RERANK`` submit inside an ``INTERACTIVE`` tick is
accounted to the interactive tick, never enqueued ahead of it
(class-inversion fix, PR 7).

See README "Operations: unified runtime & QoS classes".

Import discipline: this package sits below ``xpacks`` (the planes import
it, never the reverse) and only pulls the ``internals`` observability
leaves (``metrics_names``, ``flight_recorder``, ``monitoring``'s
provider hook) lazily.
"""

from __future__ import annotations

import asyncio
import enum
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Callable, Sequence

__all__ = [
    "QoS",
    "WorkItem",
    "WorkGroup",
    "DeviceTickRuntime",
    "DeadlineExceeded",
    "AdmissionRefused",
    "estimate_tokens",
    "budget_chunks",
    "get_runtime",
    "runtime_settings",
    "runtime_stats_if_active",
    "configure",
    "reset_runtime",
]


class QoS(enum.IntEnum):
    """Strict-priority QoS classes (lower value = higher priority)."""

    INTERACTIVE = 0  # latency-critical serving (/v1/retrieve ticks)
    LLM_RERANK = 1   # engine-plane embed/rerank/LLM-guard micro-batches
    GENERATE = 2     # paged-KV decode ticks (token streams tolerate a
                     # bounded inter-token gap; retrieval p99 does not)
    BULK_INGEST = 3  # backlog-tolerant bulk embed→upsert chunks

    @property
    def label(self) -> str:
        return self.name.lower()


#: every class an INTERACTIVE tick may preempt (strict-priority order)
_LOWER_CLASSES = (QoS.LLM_RERANK, QoS.GENERATE, QoS.BULK_INGEST)
#: classes whose "highest nonempty" tick is share-capped so the
#: preemption horizon an arriving query faces stays one short tick —
#: decode steps and ingest chunks are independent dispatches with no
#: cross-item fusion benefit, so a budget-full train only adds latency
_SHARE_CAPPED_CLASSES = (QoS.GENERATE, QoS.BULK_INGEST)


class DeadlineExceeded(Exception):
    """The request was shed: its deadline passed before dispatch.

    ``retry_after_s`` is the server's backoff hint (HTTP ``Retry-After``).
    """

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class AdmissionRefused(DeadlineExceeded):
    """Admission refused: the class queue is at its depth target."""


def _device_count() -> int | None:
    """How many accelerator devices the executor's ticks dispatch over
    (mesh-sharded ticks fan each dispatch across all of them).  Reported
    only when jax is already imported — a bare stats/health probe must
    not pull in (or initialize) a backend."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return int(jax.device_count())
    except Exception:  # noqa: BLE001 — stats must never raise
        return None


def _active_attention_impl() -> str | None:
    """The process's serving attention impl (most recently built
    encoder), for the runtime stats/health block."""
    try:
        from ..internals.flight_recorder import active_attention_impl

        return active_attention_impl()
    except Exception:  # noqa: BLE001 — stats must never raise
        return None


def estimate_tokens(item: Any) -> int:
    """Cheap token-mass estimate for budget batching: whitespace words
    + CLS/SEP for text (wordpiece splits only lengthen it, which errs on
    the safe — smaller — batch side), 1 for opaque payloads (images)."""
    if isinstance(item, bytes):
        item = item.decode("utf-8", errors="replace")
    if isinstance(item, str):
        return len(item.split()) + 2
    return 1


class WorkGroup:
    """One batchable kind of device work.

    ``batch_fn(list_of_payloads) -> list_of_results`` runs on the
    executor thread; items of the same group drained in one tick execute
    as one call (chunked at ``max_batch`` and, when ``max_tokens`` /
    ``token_estimate`` are set, at that token budget too).

    CONTRACT: a handler must SYNCHRONIZE the device work it dispatches
    (a host read, ``np.asarray``, ``jax.block_until_ready``) before
    returning.  The executor's preemption guarantee is "at most one
    tick in flight on the device" — a handler that returns unfinished
    async dispatches rebuilds the unprioritized device queue this
    runtime exists to replace, and higher-class work submitted next
    tick will silently wait behind the backlog anyway.
    """

    def __init__(
        self,
        label: str,
        batch_fn: Callable[[list], Sequence],
        max_batch: int = 1024,
        max_tokens: int | None = None,
        token_estimate: Callable[[Any], int] | None = None,
    ):
        self.label = label
        self.batch_fn = batch_fn
        self.max_batch = max_batch
        self.max_tokens = max_tokens
        self.token_estimate = token_estimate


def budget_chunks(group: Any, items: list["WorkItem"]) -> list[list["WorkItem"]]:
    """Split a tick's items into execute chunks: ``max_batch`` count cap
    plus, when the group declares one (``AsyncMicroBatcher.max_tokens``),
    a token-mass cap so a run of long documents dispatches in
    length-adapted batches.  Every chunk carries at least one item.

    THE budget-chunking implementation."""
    max_tokens = getattr(group, "max_tokens", None)
    estimate = getattr(group, "token_estimate", None)
    if max_tokens is None or estimate is None:
        return [
            items[start : start + group.max_batch]
            for start in range(0, len(items), group.max_batch)
        ]
    chunks: list[list[WorkItem]] = []
    cur: list[WorkItem] = []
    cur_tokens = 0
    for it in items:
        t = estimate(it.payload)
        if cur and (len(cur) >= group.max_batch or cur_tokens + t > max_tokens):
            chunks.append(cur)
            cur, cur_tokens = [], 0
        cur.append(it)
        cur_tokens += t
    if cur:
        chunks.append(cur)
    return chunks


class WorkItem:
    """One scheduled submission: ``(class, tokens_est, deadline, trace)``
    plus the bookkeeping the executor needs (group, payload, future)."""

    __slots__ = (
        "group", "payload", "qos", "tokens", "future",
        "enqueued_at", "deadline_at", "coalesce_s", "trace", "observer",
        "retry_after_s", "trace_link",
    )

    def __init__(
        self,
        group,
        payload,
        qos: QoS,
        tokens: int,
        future: Future,
        enqueued_at: float,
        deadline_at: float | None,
        coalesce_s: float,
        trace=None,
        observer=None,
        retry_after_s: float | None = None,
        trace_link: tuple[str, str] | None = None,
    ):
        self.group = group
        self.payload = payload
        self.qos = qos
        self.tokens = max(int(tokens), 1)
        self.future = future
        self.enqueued_at = enqueued_at
        self.deadline_at = deadline_at
        #: how long this item is willing to wait for tick-mates (the
        #: legacy per-scheduler ``max_wait_ms``, carried per item now
        #: that the tick cadence is shared; ingest chunks pass 0)
        self.coalesce_s = coalesce_s
        #: sampled RequestTrace riding this item (internals/flight_recorder)
        self.trace = trace
        #: legacy-facade stats observer (``ServingScheduler``) — receives
        #: ``_obs_*`` callbacks so per-facade counters keep working
        self.observer = observer
        #: per-item Retry-After override (the submitting plane's hint);
        #: None uses the runtime default
        self.retry_after_s = retry_after_s
        #: ``(trace_id, parent_span_id)`` of the request that CAUSED this
        #: item — deferred work executes after the request's batch scope
        #: is gone, so the link captured at submit time is the only way
        #: its tick spans stay attributable to the trigger
        self.trace_link = trace_link


def _note_batch_ticks(links, began: float | None, timed: Any) -> None:
    """Stamp a tick's start and end on the batches of connector rows whose
    embed calls it ran (``FreshnessTracker.note_tick``), BEFORE their
    futures resolve: the engine thread closes a batch as soon as its rows
    are embedded and applied."""
    if links:
        from ..internals.monitoring import get_freshness

        get_freshness().note_tick(
            links,
            timed.start_s if began is None else began,
            timed.start_s + timed.duration_ms / 1000.0,
        )


#: wait-time histogram bucket upper bounds (milliseconds)
_WAIT_BUCKETS_MS = (1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 1000.0)
#: items-per-tick histogram buckets
_OCCUPANCY_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0)
#: tokens-per-tick histogram buckets
_TICK_TOKEN_BUCKETS = (64.0, 256.0, 1024.0, 4096.0, 16384.0, 65536.0)
#: lower-class share-of-tick buckets (fractions)
_SHARE_BUCKETS = (0.01, 0.02, 0.05, 0.1, 0.2, 0.35, 0.5, 0.75, 1.0)


# one shared warn-and-default parser for the whole repo (also used by
# the serving query-cache knobs)
from ..internals.config import env_float as _env_float  # noqa: E402
from ..internals.config import env_int as _env_int  # noqa: E402


class DeviceTickRuntime:
    """Token-budget device-tick executor with QoS classes (module doc)."""

    def __init__(
        self,
        *,
        tick_tokens: int = 16384,
        max_batch: int = 256,
        max_wait_ms: float = 5.0,
        retry_after_s: float = 1.0,
        depth: dict[QoS, int] | None = None,
        min_share: dict[QoS, float] | None = None,
        name: str = "runtime",
    ):
        self.tick_tokens = int(tick_tokens)
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.retry_after_s = float(retry_after_s)
        self.name = name
        #: per-class queue-depth targets (WindVE-style admission control)
        self.depth = {
            QoS.INTERACTIVE: 1024,
            QoS.LLM_RERANK: 4096,
            QoS.GENERATE: 256,
            QoS.BULK_INGEST: 512,
            **(depth or {}),
        }
        #: starvation bound: fraction of the tick budget reserved for a
        #: lower class whenever it has pending work (always ≥ 1 item)
        self.min_share = {
            QoS.INTERACTIVE: 1.0,
            QoS.LLM_RERANK: 0.2,
            QoS.GENERATE: 0.15,
            QoS.BULK_INGEST: 0.1,
            **(min_share or {}),
        }
        self._cv = threading.Condition()
        self._queues: dict[QoS, deque[WorkItem]] = {c: deque() for c in QoS}
        self._pending_tokens: dict[QoS, int] = {c: 0 for c in QoS}
        self._thread: threading.Thread | None = None
        #: class of the tick currently executing (executor thread only) —
        #: inline re-entrant submits inherit it instead of queue-jumping
        self._tick_qos: QoS | None = None
        # metrics — guarded by _mx, not _cv: ticks update them while
        # submitters hold _cv
        from ..internals.metrics_names import Histogram

        self._mx = threading.Lock()
        self._class_counters: dict[QoS, dict[str, int]] = {
            c: {
                "submitted_total": 0,
                "completed_total": 0,
                "failed_total": 0,
                "shed_deadline_total": 0,
                "admission_rejected_total": 0,
                "inline_total": 0,
                "queue_depth_max": 0,
            }
            for c in QoS
        }
        self._wait_hist: dict[QoS, Any] = {
            c: Histogram(_WAIT_BUCKETS_MS) for c in QoS
        }
        self._ticks_total = 0
        self._preemptions_total = 0
        self._occupancy_hist = Histogram(_OCCUPANCY_BUCKETS)
        self._tick_tokens_hist = Histogram(_TICK_TOKEN_BUCKETS)
        self._share_hist = Histogram(_SHARE_BUCKETS)
        from ..internals.monitoring import register_metrics_provider

        # replace=False: an ad-hoc instance must not steal (and, being
        # weakly held, later delete) an established registration under
        # the same name — the process-global runtime re-registers
        # authoritatively in get_runtime()
        register_metrics_provider(name, self, replace=False)

    # -- submission ------------------------------------------------------
    def queue_depth(self, qos: QoS) -> int:
        """Current queued (not yet drained) items of one class — the
        WindVE-style pressure signal the serving cache stack's
        collaborative CPU embed path keys on.  A GIL-atomic ``len`` read:
        no lock, never spawns the executor thread."""
        return len(self._queues[QoS(qos)])

    def on_runtime_thread(self) -> bool:
        return (
            self._thread is not None
            and threading.current_thread() is self._thread
        )

    def submit(
        self,
        group: Any,
        payload: Any,
        *,
        qos: QoS = QoS.INTERACTIVE,
        deadline_s: float | None = None,
        sheddable: bool | None = None,
        trace: Any = None,
        tokens: int | None = None,
        coalesce_s: float | None = None,
        observer: Any = None,
        retry_after_s: float | None = None,
        defer: bool = False,
        trace_link: tuple[str, str] | None = None,
    ) -> Future:
        """Enqueue one payload under a QoS class; the future resolves
        when its batch ran.

        ``deadline_s`` is a relative budget: if the item is still queued
        that long after submission it is shed with
        :class:`DeadlineExceeded` and its work never executes.  ``None``
        (engine-plane work) is never shed.

        ``sheddable`` work (default: anything with a deadline) is
        additionally subject to the class's queue-depth target.  Engine
        and ingest planes are exempt: refusing their work would error
        the engine, and their volume is bounded upstream (engine batch
        sizes, the ingest pipeline's hand-off depth).

        ``tokens`` overrides the estimate used for tick-budget
        composition (``group.token_estimate`` / :func:`estimate_tokens`
        otherwise).  ``coalesce_s`` is how long the item will wait for
        tick-mates (default: the runtime's ``max_wait_ms``).

        ``defer=True`` marks FIRE-AND-FORGET work: a submit from the
        executor thread itself ENQUEUES for a later tick instead of
        running inline.  The inline shortcut exists for handlers that
        block on the returned future (a queued item could never drain
        while the loop is inside the current tick); background work
        nobody waits on inside the tick — e.g. a tier-migration batch
        triggered by a serving search — must NOT ride the triggering
        tick's class/budget, or an INTERACTIVE query pays for
        BULK_INGEST work in its own latency.  Never block on a
        defer=True future from a batch handler.
        """
        qos = QoS(qos)
        if sheddable is None:
            sheddable = deadline_s is not None
        if trace is not None and not trace.sampled:
            trace = None
        if trace_link is None:
            # deferred work submitted from inside a request's batch scope
            # (query-cache refresh, tier migration) would otherwise start
            # trace-orphaned — capture the triggering request's span now,
            # while the scope still exists.  The embed calls of an index
            # flush carry the batch of connector rows they embed (a
            # context variable: it reaches the persistent loop with them)
            from ..internals.flight_recorder import (
                current_batch_link,
                current_trace_link,
            )

            trace_link = (
                current_trace_link() if defer else None
            ) or current_batch_link()
        if tokens is None:
            estimate = getattr(group, "token_estimate", None)
            tokens = (estimate or estimate_tokens)(payload)
        fut: Future = Future()
        if self.on_runtime_thread() and not defer:
            # re-entrant submit from inside a batch handler (e.g. a
            # rerank fired by a retrieve handler): run inline — a queued
            # item could never drain while the loop is inside this very
            # tick.  The work inherits the RUNNING tick's class and
            # budget instead of jumping the queue: an inline LLM_RERANK
            # inside an INTERACTIVE tick is interactive-tick work, and
            # an inline INTERACTIVE inside a BULK_INGEST tick must not
            # let ingest impersonate the interactive class.
            tick_qos = self._tick_qos if self._tick_qos is not None else qos
            with self._mx:
                self._class_counters[qos]["inline_total"] += 1
            item = WorkItem(
                group, payload, tick_qos, tokens, fut,
                time.monotonic(), None, 0.0, trace, observer, retry_after_s,
                trace_link,
            )
            self._execute(group, [item], tick_qos, inline=True)
            return fut
        now = time.monotonic()
        item = WorkItem(
            group,
            payload,
            qos,
            tokens,
            fut,
            now,
            None if deadline_s is None else now + deadline_s,
            self.max_wait_ms / 1000.0 if coalesce_s is None else coalesce_s,
            trace,
            observer,
            retry_after_s,
            trace_link,
        )
        refused = False
        with self._cv:
            if sheddable and len(self._queues[qos]) >= self.depth[qos]:
                refused = True
            else:
                self._ensure_thread()
                if observer is not None:
                    # BEFORE the item becomes visible to the tick thread:
                    # with a 0-coalesce window the drain (and its
                    # _obs_drained) can otherwise run before the
                    # enqueue hook, driving the facade's pending count
                    # negative and weakening its admission cap.  Safe
                    # under _cv: no caller holds the observer's lock
                    # across a submit.
                    observer._obs_enqueued()
                self._queues[qos].append(item)
                self._pending_tokens[qos] += item.tokens
                depth = len(self._queues[qos])
                self._cv.notify_all()
        if refused:
            with self._mx:
                self._class_counters[qos]["admission_rejected_total"] += 1
            fut.set_exception(
                AdmissionRefused(
                    f"runtime {qos.label} queue full "
                    f"({self.depth[qos]} pending)",
                    retry_after_s=(
                        self.retry_after_s
                        if retry_after_s is None
                        else retry_after_s
                    ),
                )
            )
            if observer is not None:
                observer._obs_refused()
            return fut
        with self._mx:
            c = self._class_counters[qos]
            c["submitted_total"] += 1
            if depth > c["queue_depth_max"]:
                c["queue_depth_max"] = depth
        return fut

    async def submit_async(self, group: Any, payload: Any, **kwargs: Any) -> Any:
        return await asyncio.wrap_future(self.submit(group, payload, **kwargs))

    # -- device-tick loop ------------------------------------------------
    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="pw-tick"
            )
            self._thread.start()

    def _pending_locked(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def _should_flush_locked(self) -> bool:
        if any(len(q) >= self.max_batch for q in self._queues.values()):
            return True
        return sum(self._pending_tokens.values()) >= self.tick_tokens

    def _window_s_locked(self) -> float:
        """Admission window for the next tick: the largest coalesce wish
        among the class-queue HEADS (a lone 0-coalesce ingest chunk
        flushes immediately; a facade configured with max_wait_ms=80
        keeps its legacy window).  Heads only — scanning every queued
        item would hold ``_cv`` for O(backlog) per tick, and a plane
        submits one coalesce value for all its items anyway (the head
        is its oldest)."""
        window = 0.0
        for q in self._queues.values():
            if q and q[0].coalesce_s > window:
                window = q[0].coalesce_s
        return window

    def _loop(self) -> None:
        from ..internals.flight_recorder import name_thread, span

        name_thread("pw-tick")
        while True:
            with self._cv:
                if self._pending_locked() == 0:
                    # "waiting for work" is one of the answers an idle
                    # device gap can get
                    with span("tick.idle", "runtime", stage="tick.idle"):
                        while self._pending_locked() == 0:
                            self._cv.wait()
                # admission window: from the first pending item, wait for
                # concurrent requests to join the tick, flushing early on
                # max_batch / a full token budget
                flush_at = time.monotonic() + self._window_s_locked()
                if (
                    flush_at > time.monotonic()
                    and not self._should_flush_locked()
                ):
                    with span("tick.admit", "runtime", stage="tick.admit"):
                        while not self._should_flush_locked():
                            remaining = flush_at - time.monotonic()
                            if remaining <= 0:
                                break
                            self._cv.wait(timeout=remaining)
                items, tick_stats = self._compose_tick_locked()
            if not items:
                continue
            try:
                self._run_tick(items, tick_stats)
            except BaseException as exc:  # noqa: BLE001 — the loop must
                # survive; per-item errors are already routed to futures in
                # _execute, so anything landing here is a harness bug: fail
                # the unresolved items with the ACTUAL exception (a generic
                # wrapper would make the defect undiagnosable)
                for it in items:
                    if not it.future.done():
                        it.future.set_exception(exc)

    def _compose_tick_locked(self) -> tuple[list[WorkItem], dict]:
        """Strict priority with budget + starvation-bounded reservations
        (see module docstring).  Returns (items, accounting).

        Only the HIGHEST nonempty class fills the tick (that is where
        coalescing pays — concurrent queries fuse into one dispatch);
        every lower class gets exactly its reserved minimum share
        (≥ 1 item).  Backfilling lower-class work into a tick's leftover
        budget would only lengthen the tick — bulk chunks are
        independent dispatches with no cross-item fusion benefit, and
        every extra one pushes the next interactive arrival's wait out
        by a full dispatch (measured: leftover-backfill inflated
        contended p99 ~2× over the legacy loops; share-capped
        composition is what makes preemption at tick granularity real).
        A BULK_INGEST-only tick is likewise capped at the class's share
        so the preemption horizon an arriving query faces is one short
        tick, never a budget-full train of chunks — back-to-back ticks
        keep idle-device ingest throughput identical."""
        reserved: dict[QoS, int] = {}
        for c in _LOWER_CLASSES:
            if self._queues[c] and self.min_share.get(c, 0.0) > 0.0:
                reserved[c] = max(1, int(self.min_share[c] * self.tick_tokens))
        lower_pending_at_start = {
            c: bool(self._queues[c]) for c in _LOWER_CLASSES
        }
        highest = next((c for c in QoS if self._queues[c]), None)
        take: list[WorkItem] = []
        per_class = {c: [0, 0] for c in QoS}  # class -> [count, tokens]
        remaining = self.tick_tokens
        for c in QoS:
            q = self._queues[c]
            guaranteed = reserved.pop(c, 0)
            if not q:
                continue
            if c == highest and c not in _SHARE_CAPPED_CLASSES:
                allowed = remaining - sum(reserved.values())
            elif c == highest:
                # decode/bulk-only tick: one share's worth, then recompose
                # — the horizon for a preempting query stays one short tick
                allowed = max(
                    guaranteed,
                    max(1, int(self.min_share.get(c, 0.0) * self.tick_tokens)),
                )
            else:
                allowed = guaranteed
            used = count = 0
            while q and count < self.max_batch:
                tok = q[0].tokens
                if count and used + tok > allowed:
                    break
                if not count and allowed <= 0:
                    break
                item = q.popleft()
                self._pending_tokens[c] -= item.tokens
                take.append(item)
                used += tok
                count += 1
            remaining -= used
            per_class[c] = [count, used]
        leftover = {c: len(self._queues[c]) for c in QoS}
        return take, {
            "per_class": per_class,
            "leftover": leftover,
            "lower_pending_at_start": lower_pending_at_start,
        }

    def _run_tick(self, items: list[WorkItem], tick_stats: dict) -> None:
        from ..internals.flight_recorder import span

        with span("tick:runtime", "runtime", stage="tick.run") as timed:
            self._run_tick_timed(items, tick_stats, timed)

    def _run_tick_timed(
        self, items: list[WorkItem], tick_stats: dict, timed: Any
    ) -> None:
        now = time.monotonic()
        live_groups: dict[int, tuple[Any, list[WorkItem]]] = {}
        live_tokens = 0
        for it in items:  # already in priority+submission order
            wait_ms = (now - it.enqueued_at) * 1000.0
            with self._mx:
                self._wait_hist[it.qos].observe(wait_ms)
            obs = it.observer
            if obs is not None:
                obs._obs_wait(wait_ms)
                obs._obs_drained()
            if it.trace is not None:
                it.trace.add_stage_mono("queue_wait", it.enqueued_at, now)
            if it.deadline_at is not None and now > it.deadline_at:
                with self._mx:
                    self._class_counters[it.qos]["shed_deadline_total"] += 1
                if obs is not None:
                    obs._obs_shed_deadline()
                if not it.future.done():  # client may have cancelled
                    it.future.set_exception(
                        DeadlineExceeded(
                            "deadline exceeded before dispatch "
                            f"(queued {wait_ms:.1f} ms)",
                            retry_after_s=(
                                self.retry_after_s
                                if it.retry_after_s is None
                                else it.retry_after_s
                            ),
                        )
                    )
            else:
                live_groups.setdefault(id(it.group), (it.group, []))[1].append(it)
                live_tokens += it.tokens
        per_class = tick_stats["per_class"]
        # a tick that carries interactive work while lower-class work
        # stays queued behind it preempted that work at tick granularity
        preempted = per_class[QoS.INTERACTIVE][0] > 0 and any(
            tick_stats["leftover"][c] > 0 for c in _LOWER_CLASSES
        )
        with self._mx:
            self._ticks_total += 1
            if preempted:
                self._preemptions_total += 1
            self._occupancy_hist.observe(float(len(items)))
            self._tick_tokens_hist.observe(float(live_tokens))
            if per_class[QoS.INTERACTIVE][0] > 0 and (
                tick_stats["lower_pending_at_start"][QoS.BULK_INGEST]
                or per_class[QoS.BULK_INGEST][0] > 0
            ):
                # observed share of a contended tick granted to bulk
                # ingest — the starvation bound made measurable
                total = sum(t for _n, t in per_class.values()) or 1
                self._share_hist.observe(
                    per_class[QoS.BULK_INGEST][1] / total
                )
        for group, gitems in live_groups.values():
            for chunk in budget_chunks(group, gitems):
                self._execute(group, chunk, chunk[0].qos, began=timed.start_s)
        timed.set(
            occupancy=len(items),
            tokens=live_tokens,
            preempted=preempted,
            **{c.label: per_class[c][0] for c in QoS if per_class[c][0]},
        )

    def _execute(
        self,
        group: Any,
        chunk: list[WorkItem],
        qos: QoS,
        inline: bool = False,
        began: float | None = None,
    ) -> None:
        """Run one chunk and resolve its futures.  ``began``: the wall
        clock the tick began at (the chunk's own start when inline)."""
        if not chunk:
            return
        from ..internals.flight_recorder import batch_traces, span

        obs = chunk[0].observer
        if obs is not None:
            obs._obs_batch(len(chunk))
        # honor the plane's dispatch lock: build-time probes may call the
        # model off-thread while the loop runs
        lock = getattr(group, "_dispatch_lock", None)
        traces = [it.trace for it in chunk if it.trace is not None]
        # deferred items carry the (trace_id, span_id) of the request
        # that caused them: the tick span is recorded once per distinct
        # triggering trace so the stitched tree shows the background
        # work under its requester, and once unlinked otherwise
        links: list[tuple[str, str]] = []
        for it in chunk:
            if it.trace_link is not None and it.trace_link not in links:
                links.append(it.trace_link)
        timed = span(
            f"tick:{group.label}",
            "scheduler",
            stage=f"tick.execute.{qos.label}",
            links=links or None,
            runtime=self.name,
            qos=qos.label,
            occupancy=len(chunk),
            ok=True,
        )
        if inline:
            timed.set(inline=True)
        if any(parent is not None for _trace, parent in links):
            # a request's deferred work links to the span that caused it;
            # a batch of connector rows links to its trace alone
            timed.set(deferred=True)
        prev_qos = self._tick_qos
        self._tick_qos = qos
        try:
            with timed:  # a raising body reads ok=False
                from ..testing import faults

                if faults.enabled:
                    # chaos site "scheduler.step": a failed device step
                    # fans out to the batch's waiters like any handler
                    # error
                    faults.perturb("scheduler.step")
                # batch-scope the riding traces: the handler's stage
                # timers (embed, search) stamp onto every request in
                # the tick
                with batch_traces(traces):
                    if lock is not None:
                        with lock:
                            results = group.batch_fn(
                                [it.payload for it in chunk]
                            )
                    else:
                        results = group.batch_fn(
                            [it.payload for it in chunk]
                        )
                if len(results) != len(chunk):
                    raise RuntimeError(
                        f"batch handler {group.label!r} returned "
                        f"{len(results)} results for {len(chunk)} items"
                    )
        except BaseException as exc:  # noqa: BLE001 — propagate to every waiter
            _note_batch_ticks(links, began, timed)
            with self._mx:
                self._class_counters[qos]["failed_total"] += len(chunk)
            if obs is not None:
                obs._obs_done(len(chunk), ok=False)
            for it in chunk:
                if not it.future.done():
                    it.future.set_exception(exc)
            return
        finally:
            self._tick_qos = prev_qos
        _note_batch_ticks(links, began, timed)
        with self._mx:
            self._class_counters[qos]["completed_total"] += len(chunk)
        if obs is not None:
            obs._obs_done(len(chunk), ok=True)
        for it, res in zip(chunk, results):
            if not it.future.done():
                it.future.set_result(res)

    # -- observability ---------------------------------------------------
    def stats(self) -> dict[str, Any]:
        with self._cv:
            depths = {c.label: len(self._queues[c]) for c in QoS}
        with self._mx:
            classes = {
                c.label: {
                    **self._class_counters[c],
                    "queue_depth": depths[c.label],
                    "wait_ms_sum": self._wait_hist[c].sum,
                    "wait_ms_count": self._wait_hist[c].count,
                }
                for c in QoS
            }
            return {
                "classes": classes,
                "ticks_total": self._ticks_total,
                "preemptions_total": self._preemptions_total,
                "tick_occupancy_mean": (
                    self._occupancy_hist.sum / self._occupancy_hist.count
                    if self._occupancy_hist.count
                    else 0.0
                ),
                "tick_tokens_mean": (
                    self._tick_tokens_hist.sum / self._tick_tokens_hist.count
                    if self._tick_tokens_hist.count
                    else 0.0
                ),
                "bulk_share_mean": (
                    self._share_hist.sum / self._share_hist.count
                    if self._share_hist.count
                    else None
                ),
                "tick_tokens_budget": self.tick_tokens,
                "min_share": {c.label: self.min_share[c] for c in QoS},
                "depth_targets": {c.label: self.depth[c] for c in QoS},
                "devices": _device_count(),
                # which attention kernel the tick's embed work runs on
                # (PATHWAY_ATTENTION_IMPL observable; None = no encoder
                # built in this process yet)
                "attention_impl": _active_attention_impl(),
            }

    def openmetrics_lines(self) -> list[str]:
        """``pathway_runtime_*`` series for the /status endpoint."""
        from ..internals.metrics_names import escape_label_value
        # (mesh-sharded tick series live with the sharded index itself —
        # parallel/index.py's provider — since a tick is mesh-wide work
        # regardless of which QoS class submitted it)

        with self._cv:
            depths = {c: len(self._queues[c]) for c in QoS}
        lines: list[str] = []
        with self._mx:
            per_class_metrics = (
                ("submitted_total", "counter"),
                ("completed_total", "counter"),
                ("failed_total", "counter"),
                ("shed_deadline_total", "counter"),
                ("admission_rejected_total", "counter"),
                ("inline_total", "counter"),
                ("queue_depth_max", "gauge"),
            )
            for metric, kind in per_class_metrics:
                lines.append(f"# TYPE pathway_runtime_{metric} {kind}")
                for c in QoS:
                    lbl = f'qos="{escape_label_value(c.label)}"'
                    lines.append(
                        f"pathway_runtime_{metric}{{{lbl}}} "
                        f"{self._class_counters[c][metric]}"
                    )
            lines.append("# TYPE pathway_runtime_queue_depth gauge")
            for c in QoS:
                lbl = f'qos="{escape_label_value(c.label)}"'
                lines.append(
                    f"pathway_runtime_queue_depth{{{lbl}}} {depths[c]}"
                )
            lines.append("# TYPE pathway_runtime_ticks_total counter")
            lines.append(f"pathway_runtime_ticks_total {self._ticks_total}")
            lines.append("# TYPE pathway_runtime_preemptions_total counter")
            lines.append(
                f"pathway_runtime_preemptions_total {self._preemptions_total}"
            )
            lines.append("# TYPE pathway_runtime_wait_ms histogram")
            for c in QoS:
                lbl = f'qos="{escape_label_value(c.label)}"'
                lines.extend(
                    self._wait_hist[c].openmetrics_lines(
                        "pathway_runtime_wait_ms", lbl
                    )
                )
            lines.append("# TYPE pathway_runtime_tick_occupancy histogram")
            lines.extend(
                self._occupancy_hist.openmetrics_lines(
                    "pathway_runtime_tick_occupancy"
                )
            )
            lines.append("# TYPE pathway_runtime_tick_tokens histogram")
            lines.extend(
                self._tick_tokens_hist.openmetrics_lines(
                    "pathway_runtime_tick_tokens"
                )
            )
            lines.append("# TYPE pathway_runtime_starvation_share histogram")
            lines.extend(
                self._share_hist.openmetrics_lines(
                    "pathway_runtime_starvation_share"
                )
            )
        return lines


# ---------------------------------------------------------------------------
# process-global runtime + settings (compat shims read the legacy
# PATHWAY_SERVING_* knobs when the PATHWAY_RUNTIME_* ones are unset)
# ---------------------------------------------------------------------------

_SETTINGS: dict[str, Any] = {
    "tick_tokens": _env_int("PATHWAY_RUNTIME_TICK_TOKENS", 16384),
    "max_batch": _env_int(
        "PATHWAY_RUNTIME_MAX_BATCH",
        _env_int("PATHWAY_SERVING_MAX_BATCH", 256),
    ),
    "max_wait_ms": _env_float(
        "PATHWAY_RUNTIME_MAX_WAIT_MS",
        _env_float("PATHWAY_SERVING_MAX_WAIT_MS", 5.0),
    ),
    "retry_after_s": _env_float(
        "PATHWAY_RUNTIME_RETRY_AFTER_S",
        _env_float("PATHWAY_SERVING_RETRY_AFTER_S", 1.0),
    ),
    "depth": {
        QoS.INTERACTIVE: _env_int(
            "PATHWAY_RUNTIME_DEPTH_INTERACTIVE",
            _env_int("PATHWAY_SERVING_MAX_QUEUE", 1024),
        ),
        QoS.LLM_RERANK: _env_int("PATHWAY_RUNTIME_DEPTH_LLM_RERANK", 4096),
        QoS.GENERATE: _env_int("PATHWAY_RUNTIME_DEPTH_GENERATE", 256),
        QoS.BULK_INGEST: _env_int("PATHWAY_RUNTIME_DEPTH_BULK_INGEST", 512),
    },
    "min_share": {
        QoS.INTERACTIVE: 1.0,
        QoS.LLM_RERANK: _env_float("PATHWAY_RUNTIME_MIN_SHARE_LLM_RERANK", 0.2),
        QoS.GENERATE: _env_float("PATHWAY_RUNTIME_MIN_SHARE_GENERATE", 0.15),
        QoS.BULK_INGEST: _env_float(
            "PATHWAY_RUNTIME_MIN_SHARE_BULK_INGEST", 0.1
        ),
    },
}
_GLOBAL_LOCK = threading.Lock()
_GLOBAL: DeviceTickRuntime | None = None


def runtime_settings() -> dict[str, Any]:
    out = dict(_SETTINGS)
    out["depth"] = dict(_SETTINGS["depth"])
    out["min_share"] = dict(_SETTINGS["min_share"])
    return out


def configure(**kwargs: Any) -> None:
    """Adjust the global runtime policy (``tick_tokens``, ``max_batch``,
    ``max_wait_ms``, ``retry_after_s``, ``depth``, ``min_share``).  ``depth``/``min_share`` take partial ``{QoS: value}``
    dicts and merge.  Live knobs apply to the already-running global
    runtime too."""
    unknown = set(kwargs) - set(_SETTINGS)
    if unknown:
        raise TypeError(f"unknown runtime settings: {sorted(unknown)}")
    for key, value in kwargs.items():
        if key in ("depth", "min_share"):
            _SETTINGS[key] = {
                **_SETTINGS[key],
                **{QoS(k): v for k, v in value.items()},
            }
        else:
            _SETTINGS[key] = value
    with _GLOBAL_LOCK:
        rt = _GLOBAL
    if rt is None:
        return
    for knob in ("tick_tokens", "max_batch", "max_wait_ms", "retry_after_s"):
        if knob in kwargs:
            setattr(rt, knob, kwargs[knob])
    if "depth" in kwargs:
        rt.depth = {**rt.depth, **{QoS(k): v for k, v in kwargs["depth"].items()}}
    if "min_share" in kwargs:
        rt.min_share = {
            **rt.min_share,
            **{QoS(k): v for k, v in kwargs["min_share"].items()},
        }


def get_runtime() -> DeviceTickRuntime:
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = DeviceTickRuntime(
                tick_tokens=_SETTINGS["tick_tokens"],
                max_batch=_SETTINGS["max_batch"],
                max_wait_ms=_SETTINGS["max_wait_ms"],
                retry_after_s=_SETTINGS["retry_after_s"],
                depth=dict(_SETTINGS["depth"]),
                min_share=dict(_SETTINGS["min_share"]),
            )
            # the global runtime is the authoritative "runtime" metrics
            # provider — claim the name even if an ad-hoc instance
            # registered first
            from ..internals.monitoring import register_metrics_provider

            register_metrics_provider(_GLOBAL.name, _GLOBAL)
        return _GLOBAL


def runtime_stats_if_active() -> dict[str, Any] | None:
    """The global runtime's stats WITHOUT creating it — health/status
    surfaces call this so a process that never used the runtime does not
    spawn its thread just by being probed."""
    with _GLOBAL_LOCK:
        rt = _GLOBAL
    return None if rt is None else rt.stats()


def runtime_capacity_if_active() -> dict[str, Any] | None:
    """Lean occupancy view for the ``/v1/health`` ``"capacity"`` block
    (observability/hbm_ledger.capacity_status): per-class queue depth +
    depth targets + the tick token budget — the admission headroom a
    fleet router compares across replicas.  Lock-light (GIL-atomic len
    reads) and never spawns the executor thread."""
    with _GLOBAL_LOCK:
        rt = _GLOBAL
    if rt is None:
        return None
    return {
        "queue_depth": {c.label: len(rt._queues[c]) for c in QoS},
        "depth_targets": {c.label: rt.depth[c] for c in QoS},
        "tick_tokens_budget": rt.tick_tokens,
        "ticks_total": rt._ticks_total,
    }


def reset_runtime() -> None:
    """Test-isolation hook: forget the process-global runtime (its
    daemon thread parks forever on an abandoned condition variable)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = None
