"""Shared example bootstrap: make the repo importable.  The examples run
on whatever platform JAX finds; ``JAX_PLATFORMS=cpu`` pins them to the
CPU."""

from __future__ import annotations

import pathlib
import sys


def setup(file: str) -> None:
    repo_root = pathlib.Path(file).resolve().parent.parent.parent
    sys.path.insert(0, str(repo_root))
