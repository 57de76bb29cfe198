"""Observability-plane overhead A/B (ISSUE 15 acceptance: ≤2% p50).

Everything the observability plane does on the serving hot path —
per-request trace minting, stage spans into the flight-recorder ring,
SLO sample appends + exemplar histogram updates, HBM-ledger gauge reads
on scrapes — must cost ≤2% of serving p50, or operators will turn it
off and fly blind.  This bench measures exactly that:

* phase ``on``: defaults (flight recorder 4096 spans, trace sample 1.0)
  PLUS an SLO target on /v1/retrieve so the burn-rate ring does real
  work per request;
* phase ``off``: ``PATHWAY_FLIGHT_RECORDER_CAPACITY=0`` +
  ``PATHWAY_TRACE_SAMPLE=0`` (the documented kill switches).

Each phase runs in its OWN subprocess (serving_bench's lesson: a live
phase-1 server skews phase 2) with ``OBS_BENCH_REPS`` (default 3)
repetitions; the banked numbers are per-phase MEDIANS of p50 over a
sequential single-client query stream against a VectorStoreServer with
the deterministic hash embedder — the LIGHTEST serving path, which
makes the measured overhead an upper bound on the fraction a real
encoder tick would show.

One JSON line (metric ``obs_overhead``) prints and appends to
``benchmarks/bench_results.jsonl``.

``--fleet`` runs the same A/B THROUGH a fleet router (replica + router
per phase child): the ON side adds the federation scrape plane and the
dispatch spans, the OFF side kills them with
``PATHWAY_FLEET_FEDERATION=0`` + the tracing switches (metric
``fleet_obs_overhead``, same ≤2% p50 acceptance).

``--fused`` isolates the fused serving tick's launch-count accounting
(ISSUE 20): both sides run the full observability stack and only
``PATHWAY_LAUNCH_ACCOUNTING`` flips, so the measured delta is the
per-dispatch counters + serving.tick span alone (metric
``fused_launch_overhead``, same ≤2% p50 acceptance).

Run: ``JAX_PLATFORMS=cpu python benchmarks/obs_overhead.py [n_docs]``
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
RESULTS = os.path.join(HERE, "bench_results.jsonl")

N_DOCS = 120
WARM_QUERIES = 40
MEASURED_QUERIES = 300


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _corpus(tmpdir: str, n: int) -> list[str]:
    texts = []
    for i in range(n):
        text = f"Benchmark document {i} about topic-{i % 7} with marker m{i}."
        (open(os.path.join(tmpdir, f"doc{i}.txt"), "w")).write(text)
        texts.append(text)
    return texts


def _phase(n_docs: int) -> dict:
    """One serving phase in THIS process: build server, warm, measure."""
    import pathway_tpu as pw
    from pathway_tpu.xpacks.llm import mocks
    from pathway_tpu.xpacks.llm.vector_store import (
        VectorStoreClient,
        VectorStoreServer,
    )

    tmpdir = tempfile.mkdtemp(prefix="obs_bench_")
    texts = _corpus(tmpdir, n_docs)
    docs = pw.io.fs.read(
        tmpdir, format="binary", mode="streaming", with_metadata=True,
        refresh_interval=1.0,
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=64))
    port = _free_port()
    vs.run_server(
        host="127.0.0.1", port=port, threaded=True, with_cache=False,
        with_scheduler=True,
    )
    client = VectorStoreClient(host="127.0.0.1", port=port)
    probe = texts[0]
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline:
        try:
            if client.query(probe, k=1):
                break
        except Exception:
            pass
        time.sleep(0.25)
    else:
        raise TimeoutError("server never became queryable")
    for i in range(WARM_QUERIES):
        client.query(texts[i % len(texts)], k=3)
    lat_ms = []
    t_start = time.monotonic()
    for i in range(MEASURED_QUERIES):
        t0 = time.monotonic()
        client.query(texts[(i * 13) % len(texts)], k=3)
        lat_ms.append((time.monotonic() - t0) * 1000.0)
    wall = time.monotonic() - t_start
    lat_ms.sort()
    import jax

    return {
        "p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
        "p99_ms": round(lat_ms[int(len(lat_ms) * 0.99) - 1], 3),
        "qps": round(MEASURED_QUERIES / wall, 1),
        "platform": jax.default_backend(),
    }


def _fleet_phase(n_docs: int) -> dict:
    """One FLEET serving phase in THIS process: replica + router, the
    query stream goes through the router's proxy surface — so the
    measured p50 includes the dispatch span, the forwarded traceparent,
    and (phase ``on``) the federation scrape plane riding the poller."""
    import pathway_tpu as pw
    from pathway_tpu.fleet.router import FleetRouter
    from pathway_tpu.xpacks.llm import mocks
    from pathway_tpu.xpacks.llm.vector_store import (
        VectorStoreClient,
        VectorStoreServer,
    )

    tmpdir = tempfile.mkdtemp(prefix="obs_bench_fleet_")
    texts = _corpus(tmpdir, n_docs)
    docs = pw.io.fs.read(
        tmpdir, format="binary", mode="streaming", with_metadata=True,
        refresh_interval=1.0,
    )
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=64))
    port = _free_port()
    vs.run_server(
        host="127.0.0.1", port=port, threaded=True, with_cache=False,
        with_scheduler=True,
    )
    router = FleetRouter(poll_interval_s=0.5)
    rport = router.start()
    router.register_replica("r0", f"http://127.0.0.1:{port}")
    client = VectorStoreClient(host="127.0.0.1", port=rport)
    probe = texts[0]
    deadline = time.monotonic() + 120.0
    while time.monotonic() < deadline:
        try:
            if client.query(probe, k=1):
                break
        except Exception:
            pass
        time.sleep(0.25)
    else:
        raise TimeoutError("fleet never became queryable")
    for i in range(WARM_QUERIES):
        client.query(texts[i % len(texts)], k=3)
    lat_ms = []
    t_start = time.monotonic()
    for i in range(MEASURED_QUERIES):
        t0 = time.monotonic()
        client.query(texts[(i * 13) % len(texts)], k=3)
        lat_ms.append((time.monotonic() - t0) * 1000.0)
    wall = time.monotonic() - t_start
    lat_ms.sort()
    import jax

    return {
        "p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
        "p99_ms": round(lat_ms[int(len(lat_ms) * 0.99) - 1], 3),
        "qps": round(MEASURED_QUERIES / wall, 1),
        "platform": jax.default_backend(),
    }


def _child(argv: list[str], env: dict, timeout: float = 600.0) -> dict:
    import subprocess

    child_env = dict(os.environ)
    child_env.update(env)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *argv],
        capture_output=True, text=True, timeout=timeout, env=child_env,
    )
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    raise RuntimeError(
        f"phase child failed (rc={proc.returncode}): {proc.stderr[-1500:]}"
    )


#: the two phase environments — the OFF side uses the documented kill
#: switches, the ON side adds an SLO target so burn-rate accounting is
#: actually exercised per request (the realistic worst case)
PHASE_ENV = {
    "on": {
        "PATHWAY_FLIGHT_RECORDER_CAPACITY": "4096",
        "PATHWAY_TRACE_SAMPLE": "1.0",
        "PATHWAY_SLO_RETRIEVE_P99_MS": "50",
        "PATHWAY_SLO_RETRIEVE_AVAIL": "0.999",
    },
    "off": {
        "PATHWAY_FLIGHT_RECORDER_CAPACITY": "0",
        "PATHWAY_TRACE_SAMPLE": "0",
        "PATHWAY_SLO_RETRIEVE_P99_MS": "",
        "PATHWAY_SLO_RETRIEVE_AVAIL": "",
    },
}

#: --fleet A/B: the ON side adds the federation scrape plane on top of
#: the full observability stack; the OFF side kills tracing AND the
#: federation (PATHWAY_FLEET_FEDERATION is its documented kill switch)
FLEET_PHASE_ENV = {
    "on": {**PHASE_ENV["on"], "PATHWAY_FLEET_FEDERATION": "1"},
    "off": {**PHASE_ENV["off"], "PATHWAY_FLEET_FEDERATION": "0"},
}

#: --fused A/B: ISOLATES the fused-serving launch-count instrumentation
#: (per-dispatch counters + the per-tick serving.tick span) — BOTH sides
#: run the full observability stack, only PATHWAY_LAUNCH_ACCOUNTING
#: flips, so the measured delta is the accounting itself and must stay
#: inside the same ≤2% serving-overhead budget
FUSED_PHASE_ENV = {
    "on": {**PHASE_ENV["on"], "PATHWAY_LAUNCH_ACCOUNTING": "1"},
    "off": {**PHASE_ENV["on"], "PATHWAY_LAUNCH_ACCOUNTING": "0"},
}


def main() -> int:
    args = sys.argv[1:]
    n_docs = next((int(a) for a in args if a.isdigit()), N_DOCS)
    if "--phase" in args:
        print(json.dumps(_phase(n_docs)))
        return 0
    if "--fleet-phase" in args:
        print(json.dumps(_fleet_phase(n_docs)))
        return 0
    fleet = "--fleet" in args
    fused = "--fused" in args
    phase_flag = "--fleet-phase" if fleet else "--phase"
    if fused:
        phase_env = FUSED_PHASE_ENV
    elif fleet:
        phase_env = FLEET_PHASE_ENV
    else:
        phase_env = PHASE_ENV
    reps = int(os.environ.get("OBS_BENCH_REPS", "3"))
    phases: dict[str, list[dict]] = {"on": [], "off": []}
    # interleave reps so slow machine drift hits both phases evenly
    for _rep in range(reps):
        for name in ("on", "off"):
            phases[name].append(
                _child([str(n_docs), phase_flag], phase_env[name])
            )
    med = {
        name: statistics.median(r["p50_ms"] for r in runs)
        for name, runs in phases.items()
    }
    med99 = {
        name: statistics.median(r["p99_ms"] for r in runs)
        for name, runs in phases.items()
    }
    overhead = med["on"] / med["off"] - 1.0
    if fused:
        metric = "fused_launch_overhead"
    elif fleet:
        metric = "fleet_obs_overhead"
    else:
        metric = "obs_overhead"
    rec = {
        "metric": metric,
        "platform": phases["on"][0]["platform"],
        "n_docs": n_docs,
        "queries": MEASURED_QUERIES,
        "reps": reps,
        "p50_on_ms": round(med["on"], 3),
        "p50_off_ms": round(med["off"], 3),
        "p99_on_ms": round(med99["on"], 3),
        "p99_off_ms": round(med99["off"], 3),
        "overhead_p50": round(overhead, 4),
        "p50_per_rep_on": [r["p50_ms"] for r in phases["on"]],
        "p50_per_rep_off": [r["p50_ms"] for r in phases["off"]],
        "meets_acceptance": overhead <= 0.02,
        "acceptance": (
            "p50 overhead <= 2% from launch-count accounting alone "
            "(PATHWAY_LAUNCH_ACCOUNTING on vs off, tracing on both sides)"
            if fused
            else "p50 overhead <= 2% with tracing+SLO+federation fully on "
            "(routed through the fleet router)"
            if fleet
            else "p50 overhead <= 2% with tracing+SLO+ledger fully on"
        ),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec))
    with open(RESULTS, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return 0 if rec["meets_acceptance"] else 1


if __name__ == "__main__":
    sys.exit(main())
