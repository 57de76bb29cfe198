#!/usr/bin/env python3
"""What tracing a batch of connector rows (ISSUE 38) adds to the host per
engine timestamp, with no profiler session open and with one open.

    python3 perfbench/probes/batch_trace_cost.py [--jax] [--flushes N] [--calls N]

One timestamp's tracing work as the program does it, on the tracker and the
recorder the program uses: the read and commit stamps, ``engine.step`` as a
span with the step stamp and the batch's link in scope, ``--flushes``
operator flushes filed under the link, ``index.doc_data`` with its two
stamps and ``--calls`` embed calls that look the link up, one tick stamp,
and the close (eight stage observations, a root and seven segments in the
ring).  Beside it the same timestamp as the parent traced it (the zero-length
``commit:<label>`` record, unlinked flushes, one observation at the close).
Prints microseconds per timestamp for both and their difference.  A host
number: it depends on the machine's cores, not on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from pathway_tpu.internals import flight_recorder as fr  # noqa: E402
from pathway_tpu.internals.monitoring import FreshnessTracker  # noqa: E402

SCOPE = 0x7F00_1234_5678


def traced(tracker: FreshnessTracker, t: int, flushes: int, calls: int) -> None:
    wall = time.time()
    tracker.note_source("fs-0", t, wall, scope=SCOPE)
    tracker.note_commit("fs-0", t, wall, calls, scope=SCOPE)
    tracker.note_ingest(t, wall, scope=SCOPE)
    with fr.span("engine.step", "engine", record=False, t=t) as step:
        ok = tracker.note_step(t, step.start_s, scope=SCOPE)
        with fr.batch_link_scope((fr.batch_trace_id(SCOPE, t), None) if ok else None):
            for _ in range(flushes):
                link = fr.current_batch_link()
                with fr.span("flush:probe", "engine", stage="engine.flush",
                             links=None if link is None else [link], t=t):
                    pass
            with fr.span("index.doc_data", "index", stage="index.doc_data",
                         rows=calls) as doc:
                ok = tracker.note_index(t, doc.start_s, scope=SCOPE)
                link = (fr.batch_trace_id(SCOPE, t), None) if ok else None
                doc.links = [link]
                with fr.batch_link_scope(link):
                    for _ in range(calls):
                        fr.current_trace_link() or fr.current_batch_link()
                    now = time.time()
                    tracker.note_tick([link], now, now)
            tracker.note_embedded(t, doc.start_s + doc.duration_ms / 1000.0, scope=SCOPE)
    tracker.note_indexed("index#probe", t, scope=SCOPE)


def parent(tracker: FreshnessTracker, t: int, flushes: int, calls: int) -> None:
    """The parent's tracing of the same timestamp (the parent's
    ``note_indexed`` observed ``ingest.read_to_indexed`` alone)."""
    wall = time.time()
    fr.record_span("commit:fs-0", "connector", wall, 0.0,
                   attrs={"messages": calls, "t": t})
    tracker.note_ingest(t, wall, scope=SCOPE)
    for _ in range(flushes):
        with fr.span("flush:probe", "engine", stage="engine.flush", t=t):
            pass
    with fr.span("index.doc_data", "index", stage="index.doc_data", rows=calls):
        for _ in range(calls):
            fr.current_trace_link()
    tracker.note_indexed("index#probe", t, scope=SCOPE)
    fr.observe_stage("ingest.read_to_indexed", (time.time() - wall) * 1000.0)


def us(fn, args, number: int) -> float:
    tracker = FreshnessTracker()
    counter = iter(range(1, 10**9))

    def one() -> None:
        fn(tracker, next(counter), *args)

    one()
    return min(timeit.repeat(one, number=number, repeat=5)) / number * 1e6


def measure(args, number: int) -> dict:
    shape = (args.flushes, args.calls)
    new, old = us(traced, shape, number), us(parent, shape, number)
    return {"traced_us": new, "parent_us": old, "added_us": new - old}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--jax", action="store_true")
    ap.add_argument("--flushes", type=int, default=20)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--number", type=int, default=5000)
    args = ap.parse_args()
    out = {"flushes": args.flushes, "calls": args.calls, "jax_loaded": False,
           "closed": measure(args, args.number)}
    if args.jax:
        import tempfile

        import jax

        out["jax_loaded"] = True
        out["platform"] = jax.devices()[0].platform
        out["closed.jax"] = measure(args, args.number)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        with tempfile.TemporaryDirectory() as logdir:
            jax.profiler.start_trace(logdir, profiler_options=options)
            try:
                out["open"] = measure(args, max(1, args.number // 10))
            finally:
                jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
