"""Persistent background event loop for async operators.

reference: the engine keeps one tokio runtime alive for all async_apply
operators (src/engine/dataflow.rs YieldingRuntime / graph.rs:723
``async_apply_table``) instead of spinning a runtime per batch.  This is
the same contract for the host engine: one daemon thread runs a single
asyncio loop for the process; nodes submit coroutines and receive
concurrent futures.  On TPU this is what lets device dispatch (an async
embed/score batch) run while the engine keeps flushing host dataflow —
the host/device overlap a TPU framework must get right.
"""

from __future__ import annotations

import asyncio
import atexit
import threading
from concurrent.futures import Future
from typing import Any, Awaitable, Callable, Coroutine, Iterable

__all__ = ["get_loop", "submit", "gather_bounded"]

_lock = threading.Lock()
_loop: asyncio.AbstractEventLoop | None = None
_thread: threading.Thread | None = None


def get_loop() -> asyncio.AbstractEventLoop:
    """The process-wide background event loop (started on first use)."""
    global _loop, _thread
    with _lock:
        if _loop is None:
            loop = asyncio.new_event_loop()
            started = threading.Event()

            def _run() -> None:
                asyncio.set_event_loop(loop)
                loop.call_soon(started.set)
                loop.run_forever()

            th = threading.Thread(
                target=_run, name="pathway-aio", daemon=True
            )
            th.start()
            started.wait()
            _loop, _thread = loop, th
            atexit.register(_shutdown)
        return _loop


def submit(coro: Coroutine[Any, Any, Any]) -> Future:
    """Schedule ``coro`` on the persistent loop; returns a concurrent
    Future resolvable from any thread."""
    return asyncio.run_coroutine_threadsafe(coro, get_loop())


async def gather_bounded(
    fn: Callable[[Any], Awaitable[Any]], items: Iterable[Any],
    capacity: int | None = None,
) -> list:
    """``await fn(item)`` for every item at once, in order, at most
    ``capacity`` of them in flight (an async UDF's ``capacity``): what lets
    every row of an engine batch be pending in the device-tick runtime
    before any is awaited, so that they ride one tick."""
    if not capacity:
        return await asyncio.gather(*[fn(item) for item in items])
    sem = asyncio.Semaphore(capacity)

    async def one(item):
        async with sem:
            return await fn(item)

    return await asyncio.gather(*[one(item) for item in items])


def _shutdown() -> None:
    global _loop
    with _lock:
        if _loop is not None and _loop.is_running():
            _loop.call_soon_threadsafe(_loop.stop)
        _loop = None
