"""``pathway spawn`` — multi-process launcher.

reference: python/pathway/cli.py (320 LoC) — ``spawn --threads --processes``
(:60-110 setting PATHWAY_* envs + one subprocess.Popen per process) and
``spawn-from-env``.

Usage::

    python -m pathway_tpu spawn --threads 2 --processes 2 python app.py
    python -m pathway_tpu spawn-from-env python app.py   # reads PATHWAY_SPAWN_ARGS

Each spawned process gets PATHWAY_PROCESS_ID/PATHWAY_PROCESSES/
PATHWAY_THREADS/PATHWAY_FIRST_PORT; process 0 inherits stdio.  The host
plane shards sources by these (internals/config.py); the device plane
sizes its mesh from jax.device_count, not from the env.  On a host with
TPU chips, process i of N > 1 is pinned to chip i (``utils/chips.py``): a
chip belongs to one process.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

__all__ = ["main", "spawn_program"]


def checkout_repository(
    repository_url: str, branch: str | None
) -> str:
    """Clone ``repository_url`` (any git URL, incl. ``file://`` and local
    paths) into a temp dir and return its path
    (reference: cli.py:34-50 ``checkout_repository``).  If the repo
    carries a ``requirements.txt``, a private venv is built for it and
    the spawned program runs on that interpreter."""
    import tempfile

    root = tempfile.mkdtemp(prefix="pathway-spawn-")
    repo_path = os.path.join(root, "repository")
    clone = subprocess.run(
        ["git", "clone", "--quiet", repository_url, repo_path],
        capture_output=True,
        text=True,
    )
    if clone.returncode != 0:
        raise RuntimeError(f"git clone failed: {clone.stderr.strip()}")
    if branch:
        co = subprocess.run(
            ["git", "-C", repo_path, "checkout", "--quiet", branch],
            capture_output=True,
            text=True,
        )
        if co.returncode != 0:
            raise RuntimeError(f"git checkout failed: {co.stderr.strip()}")
    return repo_path


def _venv_python(repo_path: str) -> str | None:
    """Build a venv + install the repo's requirements, when present
    (reference: cli.py venv flow).  Returns the venv's python or None."""
    req = os.path.join(repo_path, "requirements.txt")
    if not os.path.exists(req):
        return None
    import venv

    venv_path = os.path.join(os.path.dirname(repo_path), "venv")
    venv.create(venv_path, with_pip=True)
    python = os.path.join(venv_path, "bin", "python")
    pip = subprocess.run(
        [python, "-m", "pip", "install", "--quiet", "-r", req],
        capture_output=True,
        text=True,
    )
    if pip.returncode != 0:
        raise RuntimeError(f"pip install failed: {pip.stderr[-500:]}")
    return python


def spawn_program(
    threads: int,
    processes: int,
    first_port: int,
    program: str,
    arguments: list[str],
    env: dict | None = None,
    repository_url: str | None = None,
    branch: str | None = None,
) -> int:
    """reference: cli.py:92-109 — N processes, shared env, wait for all;
    with ``repository_url`` the program runs from a fresh clone."""
    cwd = None
    if repository_url is not None:
        cwd = checkout_repository(repository_url, branch)
        python = _venv_python(cwd)
        if python is not None and program in ("python", sys.executable):
            program = python
    base_env = dict(env or os.environ)
    base_env.update(
        {
            "PATHWAY_THREADS": str(threads),
            "PATHWAY_PROCESSES": str(processes),
            "PATHWAY_FIRST_PORT": str(first_port),
        }
    )
    from .utils.chips import child_chip_env

    procs: list[subprocess.Popen] = []
    try:
        for pid in range(processes):
            penv = dict(base_env)
            penv["PATHWAY_PROCESS_ID"] = str(pid)
            # one process per chip: this launcher never touches JAX, and
            # each child sees its own chip (utils/chips.py)
            penv.update(child_chip_env(pid, processes, base_env))
            procs.append(
                subprocess.Popen([program, *arguments], env=penv, cwd=cwd)
            )
        exit_code = 0
        for p in procs:
            code = p.wait()
            if code:
                exit_code = code
        return exit_code
    except KeyboardInterrupt:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait()
        return 130


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="pathway", description=__doc__)
    sub = parser.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("spawn", help="run a program over N processes x M threads")
    sp.add_argument("--threads", "-t", type=int, default=1)
    sp.add_argument("--processes", "-n", type=int, default=1)
    sp.add_argument("--first-port", type=int, default=10000)
    sp.add_argument("--record", action="store_true", help="persist inputs while running")
    sp.add_argument("--record-path", default="record")
    sp.add_argument(
        "--repository-url", default=None,
        help="git URL to clone and run the program from (reference: "
        "spawn's git-repo flow; a repo requirements.txt gets a venv)",
    )
    sp.add_argument("--branch", default=None)
    sp.add_argument("program")
    sp.add_argument("arguments", nargs=argparse.REMAINDER)

    se = sub.add_parser(
        "spawn-from-env",
        help="like spawn, with arguments taken from PATHWAY_SPAWN_ARGS",
    )
    se.add_argument("program")
    se.add_argument("arguments", nargs=argparse.REMAINDER)

    args = parser.parse_args(argv)

    if args.command == "spawn":
        env = dict(os.environ)
        if args.record:
            env["PATHWAY_PERSISTENT_STORAGE"] = args.record_path
        return spawn_program(
            args.threads, args.processes, args.first_port,
            args.program, args.arguments, env,
            repository_url=args.repository_url, branch=args.branch,
        )
    if args.command == "spawn-from-env":
        spawn_args = os.environ.get("PATHWAY_SPAWN_ARGS", "").split()
        ns = parser.parse_args(["spawn", *spawn_args, args.program, *args.arguments])
        return spawn_program(
            ns.threads, ns.processes, ns.first_port, ns.program, ns.arguments,
            repository_url=ns.repository_url, branch=ns.branch,
        )
    return 2


if __name__ == "__main__":
    sys.exit(main())
