"""From a profiler trace (``.xplane.pb``) to device busy and idle time, device
time per program and per operation, and the longest idle gaps with what the
host was doing in each.

Two steps, so that the arithmetic can be checked on a small recorded trace
(``tests/trace_small.json``): ``load`` reads the file into plain lists with
``jax.profiler.ProfileData`` and ``reduce`` does the rest.

What the trace of a TPU v5e holds today (jax 0.9): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per operation that
ran on the device and whose line ``XLA Modules`` has one event per launched
program (``jit_<function>(<fingerprint>)``); the plane ``/host:CPU`` has one
line per host thread with the runtime's own spans.  No kernel carries a
``named_scope`` yet, so operations are matched by the names shown.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str) -> dict:
    """``{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "host": {thread: [[name, start_ns, dur_ns], ...]}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {"devices": {}, "host": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = out["devices"].setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)] for ev in line.events
                    ]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                          for ev in line.events]
                if events:
                    out["host"][line.name] = events
    return out


def union_ns(events: list) -> tuple[int, list[tuple[int, int]]]:
    """Length of the union of ``[start, start + dur)`` and its intervals."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    merged: list[tuple[int, int]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def short_name(name: str) -> str:
    """``jit_dense_fused_search(1234)`` -> ``jit_dense_fused_search``;
    ``%fusion.12 = ...`` -> ``fusion``."""
    name = name.split(" = ")[0].lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[._]\d+$", "", name)


def _covering_host_span(host: dict, lo: int, hi: int) -> str:
    """The host span that covers most of ``[lo, hi)``; the innermost (the
    shortest) among those that cover the same part."""
    best, best_cover, best_len = "no host span", 0, 0
    for thread, events in host.items():
        for name, s, d in events:
            cover = min(hi, s + d) - max(lo, s)
            if cover <= 0:
                continue
            if cover > best_cover or (cover == best_cover and d < best_len):
                best, best_cover, best_len = f"{short_name(name)} [{thread}]", cover, d
    return best


def program_time(trace: dict, names: tuple) -> tuple[float, float]:
    """(device seconds, launches) of the programs of a reduced trace whose
    name starts with one of ``names``."""
    names = tuple(names)
    if not names:
        return 0.0, 0.0
    return (sum(s for name, s in trace["programs"].items() if name.startswith(names)),
            sum(n for name, n in trace["launches"].items() if name.startswith(names)))


def reduce(planes: dict, top: int = 10) -> dict:
    """``busy_s`` and ``window_s`` (mean over the chips that ran something),
    device seconds by program and by operation, launches by program, and
    the idle gaps summed by the host span that covers them."""
    devices = {name: lines for name, lines in planes["devices"].items()
               if lines.get(OPS_LINE) or lines.get(MODULES_LINE)}
    if not devices:
        raise ValueError("the trace holds no device operation")
    every = [ev for lines in devices.values() for line in lines.values() for ev in line]
    lo = min(s for _n, s, _d in every)
    hi = max(s + d for _n, s, d in every)
    busy_total = 0
    by_op: dict[str, int] = {}
    by_program: dict[str, int] = {}
    launches: dict[str, int] = {}
    gaps: dict[str, int] = {}
    for lines in devices.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        busy, merged = union_ns(ops)
        busy_total += busy
        for name, _s, d in ops:
            by_op[short_name(name)] = by_op.get(short_name(name), 0) + d
        for name, _s, d in lines.get(MODULES_LINE, []):
            by_program[short_name(name)] = by_program.get(short_name(name), 0) + d
            launches[short_name(name)] = launches.get(short_name(name), 0) + 1
        edges = [(lo, lo)] + merged + [(hi, hi)]
        idle = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(edges, edges[1:])
                       if b[0] > a[1]), reverse=True)
        for length, g_lo, g_hi in idle[:200]:  # the longest carry the time
            what = _covering_host_span(planes["host"], g_lo, g_hi)
            gaps[what] = gaps.get(what, 0) + length
    n = len(devices)

    def ranked(table: dict[str, int]) -> list:
        return [[k, v / 1e9 / n] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": busy_total / 1e9 / n,
        "window_s": (hi - lo) / 1e9,
        "device_ops": ranked(by_op),
        "programs": {k: v / 1e9 / n for k, v in by_program.items()},
        "launches": {k: v / n for k, v in launches.items()},
        "idle_gaps": ranked(gaps),
    }
