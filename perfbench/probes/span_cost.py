#!/usr/bin/env python3
"""What one span of the flight recorder costs the host with no profiler
session open, which is how every end-to-end number is taken.

    python3 perfbench/probes/span_cost.py [--jax]

Prints microseconds per span for the primitive (`flight_recorder.span`: ring
append, stage histogram, the profiler check) beside the hand-written block it
replaced (`time.time(); time.monotonic(); ...; record_span(...)`), and, with
``--jax``, the same with ``jax`` loaded and with a session open.  A host
number: it depends on the machine's cores, not on the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from pathway_tpu.internals import flight_recorder as fr  # noqa: E402

N = 200_000


def with_span() -> None:
    with fr.span("tick:probe", "probe", stage="probe.stage", occupancy=1, qos="x"):
        pass


def bare_span() -> None:
    with fr.span("tick:probe", "probe"):
        pass


def by_hand() -> None:
    wall = time.time()
    t0 = time.monotonic()
    fr.record_span("tick:probe", "probe", wall, (time.monotonic() - t0) * 1000.0,
                   attrs={"occupancy": 1, "qos": "x"})


def us(fn) -> float:
    fn()
    return min(timeit.repeat(fn, number=N, repeat=3)) / N * 1e6


def main() -> int:
    out = {"span_stage_attrs_us": us(with_span), "span_bare_us": us(bare_span),
           "by_hand_us": us(by_hand), "jax_loaded": False}
    if "--jax" in sys.argv:
        import tempfile

        import jax

        out["jax_loaded"] = True
        out["platform"] = jax.devices()[0].platform
        out["span_stage_attrs_us.jax"] = us(with_span)
        out["span_bare_us.jax"] = us(bare_span)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        with tempfile.TemporaryDirectory() as logdir:
            jax.profiler.start_trace(logdir, profiler_options=options)
            try:
                fn = with_span
                fn()
                out["span_stage_attrs_us.session"] = timeit.timeit(fn, number=20_000) / 20_000 * 1e6
            finally:
                jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
