"""Serving soak: minutes of continuous churn + queries on the sharded
mesh index, watching for correctness drift, latency creep, and leaks —
plus a kill/restart crash-consistency mode (``--kill``).

Drives the product stack exactly like a deployment: streaming fs ingest →
``VectorStoreServer(mesh=8-device CPU mesh)`` → REST queries, while a
writer loop adds/re-writes/deletes files the whole time.  Asserts at the
end that the index state matches the surviving files and that query p50
did not degrade between the first and last thirds.

Run: ``JAX_PLATFORMS=cpu SOAK_SECS=180 python benchmarks/soak.py``

``--chaos`` (or ``SOAK_CHAOS=1``) additionally turns on the seeded
fault-injection harness (``pathway_tpu.testing.faults``): connector reads
fail/drop, UDF invocations fail, scheduler device steps fail/stall — at
nonzero rates for the whole run, with ``terminate_on_error=False`` so
failures land in the global error log instead of killing the run.  The
report then includes injected-fault, error-log, dead-letter, connector
restart and degraded-response counts alongside the usual metrics; the
pass criterion becomes "survived the chaos and kept answering", not
byte-exact final consistency (dropped reads are *supposed* to lose rows).
Seed: ``SOAK_SEED`` (default 17) — a failing run replays exactly.

``--kill`` runs the crash-consistency harness for the durable index
recovery plane instead: a ``VectorStoreServer`` under
``PersistenceMode.OPERATOR_PERSISTING`` is SIGKILLed at random points
mid-ingest and restarted in a loop, then a final warm restart is
asserted against a never-killed oracle run over the same corpus —
restored ``/v1/retrieve`` results must be bit-identical, the restore
must perform ZERO re-embeddings (encoder call counter flat before the
probe queries), and ``/v1/health`` must report the restore as ``ok``
with chunk/row accounting.  ``--mock`` bounds it for CI (2 kill cycles,
tiny corpus); every run appends its report to
``benchmarks/soak_results.jsonl`` and prints its seed.

With ``PATHWAY_TIER_HOT_ROWS>0`` exported, ``--kill`` runs the TIERED
index through the same harness and additionally asserts the restored
process rebuilt the exact pre-kill tier placement (hot key set + router
spec, compared by digest) — ``match_mode`` reports
``tiered+bit-identical+placement``.

``--chaos --generation`` (or ``--generation`` alone) runs the
GENERATION-plane chaos harness instead (ISSUE 18): a paged
``DecodeSession`` with its auto pump thread serves a request stream
while seeded device faults fire at nonzero rates on the launch sites —
transient ``fail`` on ``device.prefill`` / ``device.decode_step`` /
``kv.alloc`` (retried once, then contained to the launched sequences)
and ``fatal`` on ``device.decode_step`` (quarantines the KV pool and
resurrects every live row by replay re-prefill).  The pass criteria are
the containment contract itself: every request eventually completes
TOKEN-FOR-TOKEN equal to a fault-free dense oracle (contained requests
are retried, breaker sheds honor Retry-After — both are accounted, not
errors), no request fails with anything but the classified fault types
(the embedded analog of "zero non-shed client 5xx"), the pump thread is
still alive at exit, and a final fault-free probe completes to parity.
The report row (``faults_injected`` / ``replays`` / ``contained`` /
``kv_pool_rebuilds`` / ``sheds``) is appended to
``benchmarks/soak_results.jsonl``.  ``--mock`` bounds it for CI.
"""

from __future__ import annotations

import json
import os
import random
import socket
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


#: chaos-mode fault plan — deliberately nonzero everywhere the harness
#: reaches: reader failures exercise the connector supervisor's backoff
#: restarts, drops exercise at-least-once accounting, UDF failures land
#: ERROR rows in the global error log, scheduler failures trip the
#: serving breaker into lexical degraded mode (and recover)
CHAOS_RULES = {
    "connector.read": {"fail": 0.002, "drop": 0.002},
    "udf": {"fail": 0.01},
    "embedder": {"fail": 0.05},
    "scheduler.step": {"delay": 0.05, "delay_ms": 5.0},
}


def run(soak_secs: float = 180.0, chaos: bool = False) -> dict:
    import resource

    import pathway_tpu as pw
    from pathway_tpu.parallel import make_mesh
    from pathway_tpu.xpacks.llm import mocks
    from pathway_tpu.xpacks.llm.vector_store import (
        VectorStoreClient,
        VectorStoreServer,
    )

    seed = int(os.environ.get("SOAK_SEED", "17"))
    dead_letters: list = []
    if chaos:
        from pathway_tpu.testing import faults

        faults.configure(seed=seed, rules=CHAOS_RULES)
        pw.set_dead_letter_sink(lambda rec: dead_letters.append(rec))
        # the soak keeps injecting reader faults for its whole duration:
        # give the supervisor a budget to ride them out (the default of 3
        # is sized for real-world transients, not sustained chaos)
        os.environ.setdefault("PATHWAY_CONNECTOR_MAX_RESTARTS", "10000")
        os.environ.setdefault("PATHWAY_CONNECTOR_BACKOFF_S", "0.05")

    rng = random.Random(seed)
    tmp = tempfile.mkdtemp(prefix="soak-")
    live: dict[str, str] = {}

    def write_doc(name: str) -> None:
        text = f"document {name} rev {rng.randrange(1 << 30)} " + " ".join(
            f"w{rng.randrange(500)}" for _ in range(30)
        )
        with open(os.path.join(tmp, name), "w") as f:
            f.write(text)
        live[name] = text

    for i in range(40):
        write_doc(f"doc{i:03d}.txt")

    docs = pw.io.fs.read(
        tmp, format="binary", mode="streaming", with_metadata=True,
        refresh_interval=0.2,
    )
    mesh = make_mesh(8)
    vs = VectorStoreServer(docs, embedder=mocks.FakeEmbedder(dim=16), mesh=mesh)
    port = _free_port()
    vs.run_server(
        host="127.0.0.1", port=port, threaded=True, with_cache=False,
        terminate_on_error=not chaos,
    )
    client = VectorStoreClient(host="127.0.0.1", port=port)

    # wait until queryable (under chaos, injected read drops may lose a
    # few of the initial docs — that's the scenario, not a failure)
    want = 30 if chaos else 40
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        try:
            if client.get_vectorstore_statistics().get("file_count", 0) >= want:
                break
        except Exception:
            pass
        time.sleep(0.2)
    else:
        return {"metric": "serving_soak", "error": "never became queryable"}

    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    t_end = time.monotonic() + soak_secs
    lat: list[tuple[float, float]] = []  # (t, ms)
    n_mut = n_q = q_errors = n_degraded = 0
    next_name = 40
    while time.monotonic() < t_end:
        op = rng.random()
        if op < 0.3:
            write_doc(f"doc{next_name:03d}.txt")  # add
            next_name += 1
        elif op < 0.6 and live:
            write_doc(rng.choice(sorted(live)))  # rewrite in place
        elif live and len(live) > 10:
            name = rng.choice(sorted(live))
            os.unlink(os.path.join(tmp, name))  # delete
            del live[name]
        n_mut += 1
        # a few queries between mutations
        for _ in range(3):
            name, text = rng.choice(sorted(live.items()))
            t0 = time.perf_counter()
            try:
                res = client.query(text, k=1)
                lat.append((time.monotonic(), (time.perf_counter() - t0) * 1e3))
                n_q += 1
                if client.last_degraded:
                    n_degraded += 1
                # identical text must be the top hit unless the file just
                # changed under us — tolerate transient misses, count them
                if not res or res[0]["text"] != text:
                    q_errors += 1
            except Exception:
                q_errors += 1
        time.sleep(0.05)

    # settle, then final consistency: every surviving doc retrievable
    time.sleep(3.0)
    stale = 0
    for name, text in sorted(live.items()):
        try:
            res = client.query(text, k=1)
            if not res or res[0]["text"] != text:
                stale += 1
        except Exception:
            stale += 1
    stats = client.get_vectorstore_statistics()
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    third = max(len(lat) // 3, 1)
    p50_first = sorted(ms for _, ms in lat[:third])[third // 2]
    last = [ms for _, ms in lat[-third:]]
    p50_last = sorted(last)[len(last) // 2]
    out = {
        "metric": "serving_soak",
        "chaos": chaos,
        "soak_secs": round(soak_secs, 0),
        "mutations": n_mut,
        "queries": n_q,
        "transient_query_misses": q_errors,
        "degraded_responses": n_degraded,
        "final_stale_docs": stale,
        "final_live_docs": len(live),
        "server_file_count": stats.get("file_count"),
        "query_p50_ms_first_third": round(p50_first, 2),
        "query_p50_ms_last_third": round(p50_last, 2),
        "rss_growth_mb": round((rss1 - rss0) / 1024.0, 1),
    }
    if chaos:
        from pathway_tpu.internals.errors import error_stats
        from pathway_tpu.internals.health import get_health
        from pathway_tpu.testing import faults

        fstats = faults.stats()
        health = get_health().snapshot()
        breakers = {
            name: comp["state"]
            for name, comp in health["components"].items()
            if name.startswith("breaker:")
        }
        from pathway_tpu.io.streaming import connector_restart_total

        out.update(
            {
                "fault_seed": seed,
                "faults_injected": fstats["injected_total"],
                "faults_by_site": fstats.get("sites", {}),
                "error_log_counts": error_stats(),
                "dead_letters": len(dead_letters),
                "connector_restarts": connector_restart_total(),
                "breaker_states_final": breakers,
                "health_status_final": health["status"],
            }
        )
    return out


# ---------------------------------------------------------------------------
# --kill: crash-consistency harness for the durable index recovery plane
# ---------------------------------------------------------------------------

#: child process: durable VectorStoreServer (retrieve-only) over a fixed
#: corpus, writing a status file so the parent can watch ingest progress
#: and the encoder-call counter from outside.  argv: docs_dir pstore
#: status_path port dim
_KILL_CHILD_PROGRAM = r"""
import json, os, sys, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import pathway_tpu as pw
from pathway_tpu.xpacks.llm import mocks
from pathway_tpu.xpacks.llm.vector_store import VectorStoreServer

docs_dir, pstore, status_path, port, dim = sys.argv[1:6]

embed_calls = {"n": 0}


class CountingEmbedder(mocks.FakeEmbedder):
    def __wrapped__(self, input, **kwargs):
        embed_calls["n"] += 1
        return super().__wrapped__(input, **kwargs)


docs = pw.io.fs.read(docs_dir, format="binary", mode="streaming",
                     with_metadata=True, refresh_interval=0.2)
vs = VectorStoreServer(docs, embedder=CountingEmbedder(dim=int(dim)))
cfg = pw.persistence.Config(
    pw.persistence.Backend.filesystem(pstore),
    persistence_mode=pw.persistence.PersistenceMode.OPERATOR_PERSISTING)
vs.run_server(host="127.0.0.1", port=int(port), threaded=True,
              with_cache=False, aux_endpoints=False, persistence_config=cfg)

from pathway_tpu.stdlib.indexing.lowering import live_index_node

while True:
    node = live_index_node(vs.index_factory)
    status = {
        "pid": os.getpid(),
        "docs": len(node.doc_payload) if node is not None else 0,
        "embed_calls": embed_calls["n"],
        "restored_rows": getattr(node, "restored_rows", 0) if node else 0,
    }
    # tiered index (PATHWAY_TIER_HOT_ROWS>0): surface the placement
    # digest so the parent can assert the SIGKILL restore rebuilt the
    # same hot set + routing bit-for-bit
    inner = getattr(node.index, "index", None) if node is not None else None
    if inner is not None and hasattr(inner, "placement_digest"):
        status["tier_digest"] = inner.placement_digest()
        status["tier_hot_rows"] = len(inner._hot_keys)
    tmp = status_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(status, f)
    os.replace(tmp, status_path)
    time.sleep(0.1)
"""


def run_kill(mock: bool = False) -> dict:
    """Kill-at-random-point restart loop + never-killed oracle parity."""
    import shutil
    import signal  # noqa: F401 — SIGKILL via Popen.kill()
    import subprocess
    import urllib.request

    from pathway_tpu.xpacks.llm.vector_store import VectorStoreClient

    seed = int(os.environ.get("SOAK_SEED", "17"))
    print(f"[soak --kill] SOAK_SEED={seed}", flush=True)
    rng = random.Random(seed)
    kill_cycles = 2 if mock else 5
    n_docs = 12 if mock else 80
    dim = 16
    n_probes = 5

    base = tempfile.mkdtemp(prefix="soak-kill-")
    docs_dir = os.path.join(base, "docs")
    pstore = os.path.join(base, "pstore")
    oracle_pstore = os.path.join(base, "pstore-oracle")
    os.makedirs(docs_dir)
    program = os.path.join(base, "child.py")
    with open(program, "w") as f:
        f.write(_KILL_CHILD_PROGRAM)

    texts = []
    for i in range(n_docs):
        text = f"document {i:03d} " + " ".join(
            f"w{rng.randrange(2000)}" for _ in range(24)
        )
        with open(os.path.join(docs_dir, f"doc{i:03d}.txt"), "w") as fh:
            fh.write(text)
        texts.append(text)
    probes = rng.sample(texts, n_probes)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    tiered = int(os.environ.get("PATHWAY_TIER_HOT_ROWS", "0") or 0) > 0
    if tiered:
        # exhaustive cold probe for the harness: placement can legally
        # differ between the restored process (pinned by the durable
        # blob) and the never-killed oracle (insert-order fill over a
        # nondeterministic fs-stream order) — with every partition
        # probed, results are placement-independent and the comparison
        # pins the DURABILITY of tiering, while placement itself is
        # pinned restored-vs-pre-kill via the digest.  Pinned
        # unconditionally (not setdefault): a stray operator export of a
        # narrow serving probe would silently re-couple the comparison
        # to placement and fail it spuriously
        env["PATHWAY_TIER_PROBE_PARTITIONS"] = "1024"
    children: list = []

    def start_child(store: str):
        port = _free_port()
        idx = len(children)
        status_path = os.path.join(base, f"status-{idx}.json")
        # stderr to a FILE, not an undrained PIPE: JAX/absl warnings can
        # fill the ~64KB pipe buffer and block a child for the whole
        # wait_status window
        err_path = os.path.join(base, f"stderr-{idx}.log")
        err_fh = open(err_path, "wb")
        try:
            proc = subprocess.Popen(
                [sys.executable, program, docs_dir, store, status_path,
                 str(port), str(dim)],
                env=env, stdout=subprocess.DEVNULL, stderr=err_fh,
            )
        finally:
            err_fh.close()  # the child holds its own dup of the fd
        proc._err_path = err_path
        children.append(proc)
        return proc, port, status_path

    def read_status(path: str) -> dict:
        try:
            with open(path) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return {}

    def wait_status(proc, path: str, pred, timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                try:
                    with open(proc._err_path, "rb") as fh:
                        err = fh.read().decode(errors="replace")[-2000:]
                except OSError:
                    err = "<stderr unavailable>"
                raise RuntimeError(
                    f"child exited rc={proc.returncode} before becoming "
                    f"ready: {err}"
                )
            status = read_status(path)
            if status and pred(status):
                return status
            time.sleep(0.1)
        raise RuntimeError(f"timeout waiting for child status at {path}")

    def probe_results(port: int) -> list:
        client = VectorStoreClient(host="127.0.0.1", port=port, timeout=30)
        out = []
        for text in probes:
            res = client.query(text, k=3)
            # (text, dist) only: seen_at metadata is wall-clock and
            # legitimately differs between runs
            out.append([(r["text"], r["dist"]) for r in res])
        return out

    def health(port: int) -> dict:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/health", timeout=10
        ) as resp:
            return json.load(resp)

    report: dict = {
        "metric": "kill_restart_recovery",
        "seed": seed,
        "mock": mock,
        "kill_cycles": kill_cycles,
        "docs": n_docs,
    }
    try:
        # 1. kill-at-random-point loop: SIGKILL mid-startup/mid-ingest
        for cycle in range(kill_cycles):
            proc, _port, _status = start_child(pstore)
            time.sleep(rng.uniform(1.0, 6.0 if mock else 12.0))
            proc.kill()
            proc.wait()

        # 2. recovery run: restores whatever committed, ingests the rest,
        # then is killed once everything is durable
        proc, port, status_path = start_child(pstore)
        wait_status(proc, status_path, lambda s: s["docs"] >= n_docs, 150)
        # durability gate: the status file reports docs MID-step, before
        # end_of_step writes the delta chunk and the commit record — poll
        # the on-disk artifacts (commit record stable across a window)
        # instead of sleeping, or a loaded box would race the kill below
        from pathway_tpu.persistence import FilesystemKV

        kv = FilesystemKV(pstore)
        prev_rec = None
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            rec = kv.get("commit/record")
            chunks = [k for k in kv.list_keys("opstate/") if "chunk-" in k]
            if rec is not None and chunks and rec == prev_rec:
                break
            prev_rec = rec
            time.sleep(0.5)
        # tier placement as of the durable state — the restore must
        # rebuild exactly this
        pre_kill = read_status(status_path)
        if "tier_digest" in pre_kill:
            report["tier_digest_pre_kill"] = pre_kill["tier_digest"]
            report["tier_hot_rows_pre_kill"] = pre_kill.get("tier_hot_rows")
        proc.kill()
        proc.wait()

        # 3. final warm restart: everything restores from chunks — the
        # encoder counter must be FLAT until the probe queries run.
        # Wait for restored_rows too: doc_payload fills DURING
        # restore_snapshot, so a docs-only predicate can sample the
        # status file mid-restore (restored_rows/tier digest not yet
        # final)
        proc, port, status_path = start_child(pstore)
        final = wait_status(
            proc, status_path,
            lambda s: s["docs"] >= n_docs
            and s.get("restored_rows", 0) >= n_docs,
            150,
        )
        report["restore_embed_calls"] = final["embed_calls"]
        report["restored_rows"] = final["restored_rows"]
        if "tier_digest" in final:
            report["tier_digest_restored"] = final["tier_digest"]
            report["tier_hot_rows_restored"] = final.get("tier_hot_rows")
        snap = health(port)
        report["health_status"] = snap.get("status")
        report["index_restore"] = snap.get("index_restore")
        report["last_commit_age_s"] = snap.get("last_commit_age_s")
        restored_results = probe_results(port)
        proc.kill()
        proc.wait()

        # 4. never-killed oracle over the same corpus, fresh store
        proc, port, status_path = start_child(oracle_pstore)
        wait_status(proc, status_path, lambda s: s["docs"] >= n_docs, 150)
        oracle_results = probe_results(port)
        proc.kill()
        proc.wait()

        # bit-identity is the f32/bf16 contract.  At int8 the codes
        # restore bit-identical (pinned by test) but the f32 rescore
        # RING is a non-durable cache tier: the never-killed oracle
        # answers ring-exact scores for recently-written rows where the
        # restarted process answers the quantized score until rewrites
        # re-warm the ring — so the harness compares keys exactly and
        # scores within quantization tolerance there (mode reported).
        if tiered:
            # tiered serving scores EVERY candidate from the host f32
            # mirror (tier-independent scores), so restored results are
            # bit-identical to the oracle at any hot dtype — and the
            # placement itself must match the pre-kill durable state
            # exactly (digest over hot key set + router spec)
            report["match_mode"] = "tiered+bit-identical+placement"
            report["results_match_oracle"] = restored_results == oracle_results
            report["placement_match"] = (
                report.get("tier_digest_restored") is not None
                and report.get("tier_digest_restored")
                == report.get("tier_digest_pre_kill")
            )
        elif os.environ.get("PATHWAY_INDEX_DTYPE", "f32").lower() == "int8":
            # key SETS, not key order: the same score divergence the
            # tolerance admits can also swap near-tied neighbors' ranks
            report["match_mode"] = "keys+quantized-score-tolerance"

            def _rows_match(row_r, row_o):
                dr, do = dict(row_r), dict(row_o)
                return set(dr) == set(do) and all(
                    abs(dr[t] - do[t]) <= 0.02 + 1e-6 * abs(do[t])
                    for t in do
                )

            report["results_match_oracle"] = len(restored_results) == len(
                oracle_results
            ) and all(
                _rows_match(row_r, row_o)
                for row_r, row_o in zip(restored_results, oracle_results)
            )
        else:
            report["match_mode"] = "bit-identical"
            report["results_match_oracle"] = restored_results == oracle_results
        report["zero_reembed_on_restore"] = (
            final["embed_calls"] == 0 and final["restored_rows"] >= n_docs
        )
        report["ok"] = bool(
            report["results_match_oracle"]
            and report["zero_reembed_on_restore"]
            and report["health_status"] in ("ready", "degraded")
            and report.get("placement_match", True)
        )
    finally:
        for proc in children:
            if proc.poll() is None:
                proc.kill()
        shutil.rmtree(base, ignore_errors=True)

    results_path = os.path.join(HERE, "soak_results.jsonl")
    with open(results_path, "a") as fh:
        fh.write(json.dumps({**report, "ts": time.time()}) + "\n")
    return report


# ---------------------------------------------------------------------------
# --generation: chaos soak for the generation-plane containment contract
# ---------------------------------------------------------------------------

#: generation chaos plan — every launch site hot for the whole run.
#: ``fail`` exercises the retry-once-then-contain path (a containment
#: needs two consecutive hits, so contained requests are uncommon but
#: nonzero); ``fatal`` exercises quarantine + replay re-prefill.
GENERATION_CHAOS_RULES = {
    "device.prefill": {"fail": 0.15},
    "device.decode_step": {"fail": 0.10, "fatal": 0.04},
    "kv.alloc": {"fail": 0.05},
}


def run_generation(mock: bool = False) -> dict:
    """Chaos soak over the paged decode session (module docstring)."""
    import threading

    import jax.numpy as jnp

    from pathway_tpu.generation import DecodeSession
    from pathway_tpu.generation.engine import generation_status
    from pathway_tpu.models.decoder import CausalLM, DecoderConfig
    from pathway_tpu.runtime import AdmissionRefused
    from pathway_tpu.testing import faults

    seed = int(os.environ.get("SOAK_SEED", "17"))
    print(f"[soak --generation] SOAK_SEED={seed}", flush=True)
    rng = random.Random(seed)
    n_requests = 12 if mock else 48
    max_new = 8 if mock else 16
    attempts_cap = 8

    cfg = DecoderConfig(
        vocab_size=211, hidden_dim=64, num_layers=2, num_heads=4,
        mlp_dim=128, max_len=128, dtype=jnp.float32,
    )
    lm = CausalLM(cfg=cfg, seed=3)
    prompts = [
        [rng.randrange(2, cfg.vocab_size) for _ in range(rng.randrange(3, 24))]
        for _ in range(n_requests)
    ]
    # fault-free dense oracle, computed BEFORE chaos is enabled
    oracle = [lm.generate_ids([p], max_new)[0].tolist() for p in prompts]

    faults.configure(seed=seed, rules=GENERATION_CHAOS_RULES)
    fb0 = dict(generation_status()["faults"])
    s = DecodeSession(
        cfg, lm.params, auto=True, pool_tokens=4096, block_size=16,
        name=f"soak-gen-{seed}",
    )
    t0 = time.monotonic()
    sheds = contained = mismatches = unexpected = completed = 0
    try:
        pending = list(range(n_requests))
        attempts = [0] * n_requests
        wave = 4 if mock else 8  # waves, not all-at-once: every wave's
        while pending:           # prefill + decode ticks roll the dice
            batch, handles = [], {}
            for i in list(pending)[:wave]:
                attempts[i] += 1
                try:
                    handles[i] = s.submit(prompts[i], max_new_tokens=max_new)
                    batch.append(i)
                except AdmissionRefused as exc:
                    # breaker shed: honor the hint, try again next round
                    sheds += 1
                    time.sleep(min(getattr(exc, "retry_after_s", 0.2), 0.5))
            for i in batch:
                try:
                    got = handles[i].result(timeout=240)
                    if got == oracle[i]:
                        completed += 1
                        pending.remove(i)
                    else:
                        mismatches += 1
                        pending.remove(i)
                except faults.FaultInjected:
                    contained += 1  # contained launch: retryable, re-submit
                except Exception:
                    unexpected += 1  # the "non-shed client 5xx" analog
                    pending.remove(i)
            pending = [i for i in pending if attempts[i] < attempts_cap]

        pump_alive = s._pump is not None and s._pump.is_alive()
        fstats = faults.stats()  # before reset() wipes the counters
        # final fault-free probe: the session must still serve cleanly
        faults.reset()
        probe = [3, 5, 7, 9]
        probe_ok = (
            s.submit(probe, max_new_tokens=max_new).result(timeout=240)
            == lm.generate_ids([probe], max_new)[0].tolist()
        )
        fb1 = generation_status()["faults"]
    finally:
        faults.reset()
        s.close()

    threads_alive = pump_alive and threading.main_thread().is_alive()
    report = {
        "metric": "generation_chaos_soak",
        "seed": seed,
        "mock": mock,
        "requests": n_requests,
        "completed_to_parity": completed,
        "parity_mismatches": mismatches,
        "unexpected_failures": unexpected,
        "contained_retries": contained,
        "breaker_sheds": sheds,
        "faults_injected": fstats["injected_total"],
        "faults_by_site": fstats.get("sites", {}),
        "replays": fb1["replays_total"] - fb0["replays_total"],
        "contained": fb1["contained_total"] - fb0["contained_total"],
        "launch_retries": fb1["retries_total"] - fb0["retries_total"],
        "kv_pool_rebuilds": fb1["kv_pool_rebuilds_total"]
        - fb0["kv_pool_rebuilds_total"],
        "threads_alive_at_exit": threads_alive,
        "final_probe_parity": probe_ok,
        "duration_s": round(time.monotonic() - t0, 1),
    }
    report["ok"] = bool(
        completed == n_requests
        and mismatches == 0
        and unexpected == 0
        and report["faults_injected"] > 0
        and threads_alive
        and probe_ok
        # full runs must actually cover the fatal path: at least one
        # pool quarantine + replay resurrection (deterministic per seed;
        # verified for the default SOAK_SEED=17)
        and (mock or (report["kv_pool_rebuilds"] > 0 and report["replays"] > 0))
    )
    results_path = os.path.join(HERE, "soak_results.jsonl")
    with open(results_path, "a") as fh:
        fh.write(json.dumps({**report, "ts": time.time()}) + "\n")
    return report


if __name__ == "__main__":
    if "--generation" in sys.argv:
        out = run_generation(mock="--mock" in sys.argv)
        print(json.dumps(out))
        sys.exit(0 if out.get("ok") else 1)
    if "--kill" in sys.argv:
        out = run_kill(mock="--mock" in sys.argv)
        print(json.dumps(out))
        sys.exit(0 if out.get("ok") else 1)
    chaos = "--chaos" in sys.argv or os.environ.get("SOAK_CHAOS") == "1"
    out = run(float(os.environ.get("SOAK_SECS", "180")), chaos=chaos)
    print(json.dumps(out))
    if chaos:
        # chaos criteria: survived nonzero injected faults, kept answering
        # (most queries succeeded), and reported the fault accounting
        ok = (
            "error" not in out
            and out["faults_injected"] > 0
            and out["queries"] > 0
            and out["transient_query_misses"] < out["queries"]
        )
    else:
        ok = (
            "error" not in out
            and out["final_stale_docs"] == 0
            and out["server_file_count"] == out["final_live_docs"]
        )
    sys.exit(0 if ok else 1)
