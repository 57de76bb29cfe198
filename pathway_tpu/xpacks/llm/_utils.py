"""Shared helpers for the LLM xpack.

reference: python/pathway/xpacks/llm/_utils.py (coerce helpers) — the
``_AsyncMicroBatcher`` is new here: it is the device-batching half of the
TPU design.  The reference embeds one string per async-UDF call and gets
concurrency from the executor only (embedders.py async UDF w/ capacity);
here all calls that are in flight on the same event loop coalesce into one
padded device batch, so a micro-batch of N chunks costs one jit dispatch
instead of N model calls.
"""

from __future__ import annotations

import asyncio
import threading
from typing import Any, Callable, Sequence

# ONE token-estimate implementation for every budget-batching plane —
# it lives in the runtime package now (the unified executor composes
# ticks from the same estimates); re-exported here for back-compat
from ...runtime import estimate_tokens

__all__ = [
    "coerce_str",
    "estimate_tokens",
    "AsyncMicroBatcher",
    "RestClientBase",
    "run_with_cache",
    "merge_filter_exprs",
    "_check_model_accepts_arg",
]


def coerce_str(value: Any) -> str:
    if isinstance(value, bytes):
        return value.decode("utf-8", errors="replace")
    return str(value)


def seed_embedder_mesh(embedder: Any, mesh: Any) -> None:
    """Thread a serving mesh into a model-backed embedder whose encoder
    is not built yet (``_encoder is None`` + ``_init_kwargs``): query and
    ingest encodes then run data-parallel over the same device set the
    index shards on.  Already-built encoders and plain UDF embedders are
    left alone.  Shared by ``VectorStoreServer`` and ``DocumentStore``
    so the ``mesh=``/``PATHWAY_SERVING_MESH`` knob behaves identically
    through both entry points."""
    if (
        mesh is not None
        and embedder is not None
        and getattr(embedder, "_encoder", "-") is None
        and hasattr(embedder, "_init_kwargs")
    ):
        existing = embedder._init_kwargs.get("mesh")
        if existing is None:
            embedder._init_kwargs["mesh"] = mesh
        elif existing is not mesh:
            # one embedder reused across servers with DIFFERENT meshes
            # keeps the first mesh it bound — its encoder is (or will
            # be) committed to those devices, and silently rebinding
            # would feed one server queries placed on the other's mesh.
            # Loud, because the fused tick will degrade on the mismatch.
            import warnings

            warnings.warn(
                "embedder already bound to a different serving mesh; "
                "reusing one embedder across servers with different "
                "meshes keeps the first — pass a fresh embedder per mesh",
                stacklevel=3,
            )


def merge_filter_exprs(
    metadata_filter: str | None, filepath_globpattern: str | None
) -> str | None:
    """Combine the two request filters into one expression
    (reference: vector_store.py:358 ``merge_filters``) — plain-function
    form shared by the dataflow UDF and the scheduler retrieve plane."""
    parts = []
    if metadata_filter:
        parts.append(f"({metadata_filter})")
    if filepath_globpattern:
        parts.append(f"globmatch('{filepath_globpattern}', path)")
    return " && ".join(parts) if parts else None


def _check_model_accepts_arg(model_cls_or_fn: Any, arg: str) -> bool:
    import inspect

    try:
        sig = inspect.signature(model_cls_or_fn)
    except (TypeError, ValueError):
        return False
    return arg in sig.parameters


class RestClientBase:
    """Shared urllib JSON client (VectorStoreClient / RAGClient).

    ``retry_on_unavailable`` (off by default) makes a 503 response —
    the serving scheduler's deadline/overload shedding — degrade
    gracefully: the client retries with jittered exponential backoff
    (``backoff_initial_s`` · ``backoff_factor``^attempt, up to
    ``max_retries`` attempts), honoring the server's ``Retry-After``
    hint when present.  Every individual sleep is clamped to
    ``max_retry_after_s`` and the whole retry budget to
    ``retry_deadline_s`` of wall clock — a saturated server makes the
    client fail fast after the deadline instead of piling on.

    Every response's ``x-pathway-trace-id`` header is captured as
    ``last_trace_id`` — paste it into the server's
    ``/v1/debug/traces?trace_id=...`` to see where that exact request's
    time went (queue wait / embed / search / serialize).

    Every logical call mints ONE W3C ``traceparent`` and reuses it
    across its 503 retries: the retried attempts stitch into a single
    trace on the server instead of minting a fresh id per attempt — a
    retried request used to be invisible as such in the trace dump,
    which hid exactly the client-side pile-on behavior the retry knobs
    bound.
    """

    def __init__(
        self,
        host: str | None = None,
        port: int | None = None,
        url: str | None = None,
        timeout: float = 30.0,
        additional_headers: dict | None = None,
        retry_on_unavailable: bool = False,
        max_retry_after_s: float = 5.0,
        max_retries: int = 4,
        backoff_initial_s: float = 0.25,
        backoff_factor: float = 2.0,
        backoff_jitter_s: float = 0.1,
        retry_deadline_s: float = 10.0,
    ):
        if url is None:
            if host is None or port is None:
                raise ValueError("provide url= or host= and port=")
            url = f"http://{host}:{port}"
        self.url = url.rstrip("/")
        self.timeout = timeout
        self.additional_headers = additional_headers or {}
        self.retry_on_unavailable = retry_on_unavailable
        self.max_retry_after_s = max_retry_after_s
        self.max_retries = max_retries
        self.backoff_initial_s = backoff_initial_s
        self.backoff_factor = backoff_factor
        self.backoff_jitter_s = backoff_jitter_s
        self.retry_deadline_s = retry_deadline_s
        #: trace id of the most recent response (server-minted, or the
        #: caller's own traceparent's trace id when one was sent)
        self.last_trace_id: str | None = None

    def _new_traceparent(self) -> str:
        """One trace context per LOGICAL call (shared by every retry of
        it; adaptive re-ask rounds that reuse one client call stitch in
        too)."""
        from ...internals.flight_recorder import (
            format_traceparent,
            new_span_id,
            new_trace_id,
        )

        return format_traceparent(new_trace_id(), new_span_id())

    def _post(self, route: str, payload: dict):
        import random
        import time
        import urllib.error

        deadline = time.monotonic() + self.retry_deadline_s
        attempt = 0
        traceparent = self._new_traceparent()
        while True:
            try:
                return self._post_once(route, payload, traceparent=traceparent)
            except urllib.error.HTTPError as exc:
                if not (self.retry_on_unavailable and exc.code == 503):
                    raise
                if attempt >= self.max_retries:
                    raise
                retry_after = None
                try:
                    header = exc.headers.get("Retry-After")
                    if header is not None:
                        retry_after = float(header)
                except (TypeError, ValueError):
                    retry_after = None
                delay = (
                    retry_after
                    if retry_after is not None
                    else self.backoff_initial_s
                    * (self.backoff_factor ** attempt)
                )
                # jitter scales with the delay (≥ the configured floor):
                # a draining/overloaded replica hands every client the
                # SAME Retry-After, and a fixed sleep would march them
                # all back in lockstep — proportional jitter decorrelates
                # the herd
                delay += random.uniform(
                    0.0, max(self.backoff_jitter_s, 0.25 * delay)
                )
                delay = max(0.0, min(delay, self.max_retry_after_s))
                if time.monotonic() + delay > deadline:
                    # total-deadline cap: fail fast instead of sleeping
                    # past the caller's patience
                    raise
                time.sleep(delay)
                attempt += 1

    def _post_once(
        self, route: str, payload: dict, traceparent: str | None = None
    ):
        import json
        import urllib.request

        headers = {"Content-Type": "application/json", **self.additional_headers}
        if traceparent is not None and "traceparent" not in {
            k.lower() for k in headers
        }:
            headers["traceparent"] = traceparent
        req = urllib.request.Request(
            self.url + route,
            data=json.dumps(payload).encode(),
            headers=headers,
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=self.timeout) as resp:
            trace_id = resp.headers.get("x-pathway-trace-id")
            if trace_id is not None:
                self.last_trace_id = trace_id
            return json.loads(resp.read().decode())


def run_with_cache(
    threaded: bool = False,
    with_cache: bool = True,
    cache_backend: Any = None,
    terminate_on_error: bool = True,
    persistence_config: Any = None,
):
    """Start ``pw.run`` with UDF_CACHING persistence wired (reference:
    vector_store.py:558-582 / servers.py run) — shared by every xpack
    ``run_server``.  Returns the thread when ``threaded=True``.

    An explicit ``persistence_config`` (durable serving: the recovery
    plane under ``PersistenceMode.OPERATOR_PERSISTING``) takes precedence
    over the default in-memory UDF cache."""
    from ...internals.flight_recorder import name_thread
    from ...internals.run import run

    if persistence_config is None and with_cache:
        from ...persistence import Backend, Config

        backend = cache_backend or Backend.mock()
        persistence_config = Config(backend, persistence_mode="UDF_CACHING")

    def target():
        # the engine's thread when threaded (a no-op on the main thread)
        name_thread("pw-engine")
        run(
            persistence_config=persistence_config,
            terminate_on_error=terminate_on_error,
        )

    if threaded:
        th = threading.Thread(target=target, daemon=True, name="pw-engine")
        th.start()
        return th
    target()


class AsyncMicroBatcher:
    """Coalesces concurrent async calls into one batched device call.

    ``batch_fn(list_of_items) -> list_of_results`` is invoked once per
    scheduling round of the event loop (or when ``max_batch`` items are
    pending).  The engine's AsyncMapNode fans out every row of a micro-batch
    as a concurrent task on one loop, so all rows of the timestamp land in
    the same device batch — the bucketed-padding path of
    ``models/encoder.py`` then compiles once per shape bucket.

    When shared-executor serving is enabled (the default) calls delegate
    to the process-wide executor instead: work coalesces ACROSS engine
    steps and REST planes, not just within one loop round, and every
    device dispatch serializes on the executor thread.  The batcher
    submits its items to the unified device-tick runtime as
    ``LLM_RERANK``-class work — below interactive serving ticks, above
    bulk ingest.  ``use_scheduler`` pins the behavior per batcher (None =
    follow the global ``PATHWAY_SERVING_SCHEDULER`` setting; False =
    per-loop micro-batching only).
    """

    def __init__(
        self,
        batch_fn: Callable[[list], Sequence],
        max_batch: int = 1024,
        use_scheduler: bool | None = None,
        max_tokens: int | None = None,
        token_estimate: Callable[[Any], int] | None = None,
    ):
        self.batch_fn = batch_fn
        self.max_batch = max_batch
        # token-budget admission: a flush fires once the PENDING batch's
        # estimated token mass reaches ``max_tokens`` — batch size adapts
        # to document length, so a run of long documents flushes small
        # while a run of tweets still fills ``max_batch``.  The runtime
        # honors the same attributes when it chunk-drains this batcher
        # as a WorkGroup.
        self.max_tokens = max_tokens
        self.token_estimate = token_estimate or estimate_tokens
        self.label = getattr(batch_fn, "__name__", "batch")
        self.use_scheduler = use_scheduler
        # device dispatch is serialized; the model call itself is not
        # thread-safe across loops
        self._dispatch_lock = threading.Lock()
        self._pending: dict[int, list[tuple[Any, asyncio.Future]]] = {}
        self._pending_tokens: dict[int, int] = {}

    async def call(self, item: Any) -> Any:
        use = self.use_scheduler
        if use is None:
            from ._scheduler import scheduler_enabled

            use = scheduler_enabled()
        if use:
            from ...runtime import QoS, get_runtime

            # engine-plane embed/rerank/LLM-guard work rides the
            # unified runtime as LLM_RERANK: below interactive
            # serving, above bulk ingest, never shed (no deadline)
            return await get_runtime().submit_async(
                self, item, qos=QoS.LLM_RERANK
            )
        loop = asyncio.get_running_loop()
        lid = id(loop)
        lst = self._pending.setdefault(lid, [])
        fut: asyncio.Future = loop.create_future()
        lst.append((item, fut))
        over_tokens = False
        if self.max_tokens is not None:
            tokens = self._pending_tokens.get(lid, 0) + self.token_estimate(item)
            self._pending_tokens[lid] = tokens
            over_tokens = tokens >= self.max_tokens
        if len(lst) >= self.max_batch or over_tokens:
            self._flush(lid)
        elif len(lst) == 1:
            # flush after the current scheduling round: every concurrent
            # task gets to append before the callback runs
            loop.call_soon(self._flush, lid)
        return await fut

    def _flush(self, lid: int) -> None:
        lst = self._pending.get(lid)
        if not lst:
            return
        self._pending[lid] = []
        self._pending_tokens[lid] = 0
        items = [it for it, _ in lst]
        try:
            with self._dispatch_lock:
                results = self.batch_fn(items)
            for (_, fut), res in zip(lst, results):
                if not fut.done():
                    fut.set_result(res)
        except Exception as exc:  # noqa: BLE001 — propagate to every waiter
            for _, fut in lst:
                if not fut.done():
                    fut.set_exception(exc)


#: static per-provider parameter tables — the reference resolves these
#: through litellm.get_supported_openai_params (llms.py _utils); when
#: litellm is importable we do the same, else these serve as the offline
#: fallback so _accepts_call_arg stays accurate without the dependency
_PROVIDER_PARAMS = {
    "openai": {
        "model", "temperature", "max_tokens", "max_completion_tokens",
        "top_p", "n", "stop", "seed", "presence_penalty",
        "frequency_penalty", "logit_bias", "logprobs", "top_logprobs",
        "response_format", "tools", "tool_choice", "user", "stream",
    },
    "cohere": {
        "model", "temperature", "max_tokens", "p", "k", "seed",
        "stop_sequences", "frequency_penalty", "presence_penalty",
        "documents",
    },
}


def check_provider_accepts_arg(model: str, provider: str, arg: str) -> bool:
    """reference: xpacks/llm/_utils.py ``_check_model_accepts_arg`` —
    ask litellm for the model's supported OpenAI-style params, falling
    back to a static provider table offline."""
    try:
        import litellm

        params = litellm.get_supported_openai_params(
            model=model, custom_llm_provider=provider
        )
        if params:
            return arg in params
    except Exception:
        pass
    return arg in _PROVIDER_PARAMS.get(provider, set())


def prep_message_log(messages: list, verbose: bool) -> str:
    """Shorten chat messages for structured request logs (reference:
    llms.py:55 ``_prep_message_log``): verbose mode redacts inline
    images, non-verbose truncates."""
    import copy
    import json as _json

    if verbose:
        log_messages = copy.deepcopy(messages)
        for message in log_messages:
            content = message.get("content")
            if isinstance(content, list):
                for part in content:
                    if isinstance(part, dict) and part.get("type") == "image_url":
                        part["image_url"] = {"url": "<redacted image>"}
        return _json.dumps(log_messages, ensure_ascii=False, default=str)
    text = _json.dumps(messages, ensure_ascii=False, default=str)
    return text[:500] + ("..." if len(text) > 500 else "")
