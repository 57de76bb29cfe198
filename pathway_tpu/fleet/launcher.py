"""Replica process launcher: ``python -m pathway_tpu.fleet.launcher``.

One replica = one process (the health registry's deployment shape):
a :class:`~pathway_tpu.xpacks.llm.vector_store.VectorStoreServer` over
an optional corpus directory plus the fleet ingest table, running under
OPERATOR_PERSISTING against the replica's snapshot store.  A JOINING
replica pointed at a warm store bulk-restores from chunked snapshots
(PR 6) — zero re-embeds — and only then registers with the router
(the heartbeat thread gates on ``/v1/health`` readiness).

The parent-side helper :func:`spawn_replica` is what the autoscaler's
``spawn()`` and the fleet bench use.

Bench/test knobs (env):

* ``PATHWAY_FLEET_EMU_DEVICE_MS`` — emulated accelerator: every embed
  batch holds a per-process device lock and sleeps ``ms × rows``.  On a
  shared-CPU box this models "N hosts with one accelerator each" (the
  sleeps overlap across replicas, the CPU work does not), the same
  device-emulation idiom the contention bench uses for ONE device.
* ``PATHWAY_FLEET_EMBED_COUNTER_FILE`` — the embedder rewrites this
  file with its cumulative call count; the autoscale acceptance test
  pins zero-re-embed bring-up with it.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

__all__ = ["spawn_replica", "main"]


def spawn_replica(
    *,
    port: int,
    router_url: str | None = None,
    snapshot_dir: str | None = None,
    corpus_dir: str | None = None,
    name: str | None = None,
    mock_dim: int = 16,
    env: dict | None = None,
    python: str | None = None,
    chip: int | None = None,
) -> "subprocess.Popen":
    """Start a replica child process; returns the ``Popen``.  The child
    registers itself with the router once ready — the caller only needs
    to keep the handle for kill/wait.  The child runs on whatever platform
    JAX finds there; on a host with several TPU chips pass ``chip`` so
    each replica owns exactly one (``utils/chips.py``) — without it the
    first replica claims them all."""
    argv = [
        python or sys.executable,
        "-m",
        "pathway_tpu.fleet.launcher",
        "--port",
        str(port),
        "--mock-dim",
        str(mock_dim),
    ]
    if router_url:
        argv += ["--router", router_url]
    if snapshot_dir:
        argv += ["--snapshot-dir", snapshot_dir]
    if corpus_dir:
        argv += ["--corpus", corpus_dir]
    if name:
        argv += ["--name", name]
    child_env = dict(os.environ)
    if chip is not None:
        from ..utils.chips import one_chip_env

        child_env.update(one_chip_env(chip))
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    child_env["PYTHONPATH"] = (
        repo_root + os.pathsep + child_env.get("PYTHONPATH", "")
    )
    if env:
        child_env.update(env)
    return subprocess.Popen(argv, env=child_env)


def _build_embedder(dim: int):
    """FakeEmbedder + the two bench/test hooks (module docstring)."""
    from ..xpacks.llm import mocks

    emu_ms = float(os.environ.get("PATHWAY_FLEET_EMU_DEVICE_MS", "0") or 0)
    counter_file = os.environ.get("PATHWAY_FLEET_EMBED_COUNTER_FILE")
    device_lock = threading.Lock()
    calls = {"n": 0}

    class ReplicaEmbedder(mocks.FakeEmbedder):
        def __wrapped__(self, input, **kwargs):
            calls["n"] += 1
            if counter_file:
                try:
                    with open(counter_file, "w") as f:
                        f.write(str(calls["n"]))
                except OSError:
                    pass
            if emu_ms > 0:
                # the emulated accelerator: serial per replica, sleeping
                # (≈ off-CPU, like a real device) for a fixed per-ROW
                # service time — scaled by batch size so the scheduler's
                # batch coalescing can't absorb it
                rows = len(input) if isinstance(input, (list, tuple)) else 1
                with device_lock:
                    time.sleep(emu_ms * max(rows, 1) / 1000.0)
            return super().__wrapped__(input, **kwargs)

    return ReplicaEmbedder(dim=dim)


def main(argv: "list[str] | None" = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--router", default=None)
    ap.add_argument("--snapshot-dir", default=None)
    ap.add_argument("--corpus", default=None)
    ap.add_argument("--name", default=None)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--mock-dim", type=int, default=16)
    args = ap.parse_args(argv)

    import pathway_tpu as pw
    from ..xpacks.llm.vector_store import VectorStoreServer
    from . import member as member_mod

    advertise = f"http://{args.host}:{args.port}"
    member = member_mod.activate_member(
        name=args.name, advertise_url=advertise, router_url=args.router
    )

    docs = []
    if args.corpus:
        docs.append(
            pw.io.fs.read(
                args.corpus, format="binary", mode="streaming",
                with_metadata=True, refresh_interval=0.2,
            )
        )
    docs.append(member.build_ingest_table())

    vs = VectorStoreServer(*docs, embedder=_build_embedder(args.mock_dim))

    persistence_config = None
    if args.snapshot_dir:
        persistence_config = pw.persistence.Config(
            pw.persistence.Backend.filesystem(args.snapshot_dir),
            persistence_mode=pw.persistence.PersistenceMode.OPERATOR_PERSISTING,
        )

    member.start_heartbeats()
    vs.run_server(
        host=args.host,
        port=args.port,
        threaded=False,
        with_cache=False,
        # statistics/inputs are engine-routed reduce/join operators with
        # no persistent_id — OPERATOR_PERSISTING refuses them.  A fleet
        # replica's serving surface is the scheduler-routed /v1/retrieve;
        # fleet control rides raw routes, so nothing here needs them.
        aux_endpoints=False,
        persistence_config=persistence_config,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
