"""On-demand device profiling behind ``/v1/debug/profile?ms=``.

The only device-time numbers used to come from offline benches; when a
serving replica misbehaves NOW, the operator needs a trace window from
the LIVE process.  ``capture(ms)``:

* on a real TPU (jax already imported, backend exposes a profiler):
  ``jax.profiler`` traces the window into a spool directory and the
  artifact (a zip of the trace dir, openable in TensorBoard/XProf /
  Perfetto) is served back;
* everywhere else: a pure flight-recorder fallback — the window's spans
  exported as Chrome-tracing/Perfetto JSON — so tier-1 exercises the
  whole handler path without jax profiling and a CPU smoke still gets a
  usable timeline.

Operational guardrails: SINGLE-FLIGHT (a second capture while one runs
gets 409 — two overlapping device traces corrupt each other), duration
capped at ``PATHWAY_PROFILE_MAX_MS`` (default 10 s — a forgotten
``ms=3600000`` must not pin the profiler for an hour), bounded spool
(``PATHWAY_PROFILE_KEEP`` newest artifacts, default 4), and a
``PATHWAY_PROFILE_DIR`` knob (``off`` disables the endpoint entirely;
default is a per-process tempdir).

Import discipline: stdlib + flight_recorder only; jax is touched solely
behind a ``sys.modules`` gate inside the capture body.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any

from ..internals.config import env_int as _env_int

__all__ = [
    "ProfileInFlight",
    "ProfilerDisabled",
    "capture",
    "profile_dir",
    "profiler_stats",
]


class ProfileInFlight(RuntimeError):
    """A capture is already running (handler answers 409)."""


class ProfilerDisabled(RuntimeError):
    """``PATHWAY_PROFILE_DIR=off`` (handler answers 503)."""


def profile_dir() -> str | None:
    """Spool directory for capture artifacts; ``None`` when disabled."""
    raw = os.environ.get("PATHWAY_PROFILE_DIR", "").strip()
    if raw.lower() in ("off", "0", "none", "disabled"):
        return None
    if raw:
        return raw
    # ("pw_profiles", not the package name: the metrics registry lint
    # greps for pathway-prefixed literals)
    return os.path.join(tempfile.gettempdir(), f"pw_profiles_{os.getpid()}")


def max_ms() -> float:
    return float(max(1, _env_int("PATHWAY_PROFILE_MAX_MS", 10_000)))


def keep_artifacts() -> int:
    return max(1, _env_int("PATHWAY_PROFILE_KEEP", 4))


#: single-flight gate — two overlapping jax profiler sessions abort the
#: runtime, and two overlapping window exports would interleave spools
_capture_lock = threading.Lock()
_stats_lock = threading.Lock()
_stats = {"captures_total": 0, "last_kind": None, "last_size_bytes": 0}


def _jax_profiler_available() -> bool:
    """True only on a live non-CPU backend that is ALREADY imported —
    capture must never initialize a device runtime, and jax.profiler on
    the CPU backend produces empty traces at real cost."""
    if "jax" not in sys.modules:
        return False
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:  # noqa: BLE001 — backend gone / not initialized
        return False


def _prune_spool(root: str, keep: int | None = None) -> None:
    if keep is None:
        keep = keep_artifacts()
    try:
        entries = sorted(
            (os.path.join(root, e) for e in os.listdir(root)),
            key=os.path.getmtime,
        )
    except OSError:
        return
    for path in entries[:-keep]:
        try:
            if os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)
            else:
                os.unlink(path)
        except OSError:
            pass


def _zip_dir(src_dir: str, dest_zip_base: str) -> str:
    return shutil.make_archive(dest_zip_base, "zip", src_dir)


def capture(ms: float) -> dict[str, Any]:
    """Trace a ``ms``-long window and return the artifact description
    (``path``/``kind``/``size_bytes``/``duration_ms``).  Raises
    :class:`ProfileInFlight` when a capture is running and
    :class:`ProfilerDisabled` when the knob is off."""
    root = profile_dir()
    if root is None:
        raise ProfilerDisabled("profiling disabled (PATHWAY_PROFILE_DIR=off)")
    if not _capture_lock.acquire(blocking=False):
        raise ProfileInFlight("a profile capture is already running")
    try:
        ms = min(max(float(ms), 1.0), max_ms())
        os.makedirs(root, exist_ok=True)
        # prune BEFORE producing the new artifact: pruning after would
        # let capture B delete capture A's artifact while A's response
        # is still streaming it (KEEP=1 made the window certain) — at
        # capture start the previous artifact is still among the newest
        _prune_spool(root, keep=max(1, keep_artifacts() - 1))
        tag = f"profile_{time.strftime('%Y%m%d_%H%M%S')}_{os.getpid()}_{int(time.monotonic() * 1000) % 100000}"
        if _jax_profiler_available():
            artifact, kind = _capture_jax(root, tag, ms)
        else:
            artifact, kind = _capture_flight_recorder(root, tag, ms)
        size = os.path.getsize(artifact)
        with _stats_lock:
            _stats["captures_total"] += 1
            _stats["last_kind"] = kind
            _stats["last_size_bytes"] = int(size)
        return {
            "path": artifact,
            "kind": kind,
            "size_bytes": int(size),
            "duration_ms": ms,
        }
    finally:
        _capture_lock.release()


def _capture_jax(root: str, tag: str, ms: float) -> tuple[str, str]:
    import jax

    trace_dir = os.path.join(root, tag)
    # the Python tracer makes a live server many times slower; the
    # program's own spans (flight_recorder.span) are in the host plane
    # without it
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        time.sleep(ms / 1000.0)
    finally:
        jax.profiler.stop_trace()
    artifact = _zip_dir(trace_dir, os.path.join(root, tag))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return artifact, "jax"


def _capture_flight_recorder(root: str, tag: str, ms: float) -> tuple[str, str]:
    """Off-TPU window: sleep through it and export every span that
    OVERLAPS it (ended inside or started inside) as Perfetto JSON."""
    from ..internals.flight_recorder import FlightRecorder, get_recorder

    t0 = time.time()
    time.sleep(ms / 1000.0)
    t1 = time.time()
    rec = get_recorder()
    # mark_read=False: this export is machinery, not an operator read —
    # it must not reset the ring's dropped-before-read watermark
    spans = [
        s
        for s in rec.spans(mark_read=False)
        if s.start_s <= t1 and s.start_s + s.duration_ms / 1000.0 >= t0
    ]
    doc = FlightRecorder.perfetto(spans)
    doc["pw_profile"] = {
        "window_start_s": t0,
        "window_end_s": t1,
        "spans": len(spans),
        "kind": "flight_recorder",
    }
    artifact = os.path.join(root, f"{tag}.json")
    with open(artifact, "w") as f:
        json.dump(doc, f)
    return artifact, "flight_recorder"


def profiler_stats() -> dict[str, Any]:
    with _stats_lock:
        snap = dict(_stats)
    snap["in_flight"] = _capture_lock.locked()
    snap["dir"] = profile_dir()
    snap["max_ms"] = max_ms()
    return snap
