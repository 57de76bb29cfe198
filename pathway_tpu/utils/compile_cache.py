"""Persistent XLA compilation cache, placed from outside.

One policy, one function (:func:`enable_compile_cache`), called where
device work starts (``pw.run`` of a graph that imported jax, hence every
server start) and by ``chip_smoke.py``:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself; this
  module sets no directory at all, so whoever launched the process owns
  the placement (a chip machine that mounts a cache gets it reused).
* unset — the cache lives at :data:`CHECKOUT_CACHE_DIR`, one fixed,
  git-ignored path inside the checkout.  The path is part of the cache
  key, so it never carries a home directory, machine tag, pid or time.
* ``JAX_PLATFORMS=cpu`` (the test configuration) with the variable unset —
  nothing is persisted.  XLA:CPU artifacts bake in the compiling host's
  CPU features and the cache key does not record them, so a directory
  filled here and copied to another machine could SIGILL there; a process
  pinned to the CPU platform therefore writes none (``.chiprunignore``
  keeps the directory out of the chip tool's copy for the same reason).

Errors (an unwritable directory) propagate: a run without a cache must
not look like a run with one.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache", "cache_entry_count"]

#: <checkout>/.jax_compile_cache (this file is <checkout>/pathway_tpu/utils/)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compile_cache",
)


def enable_compile_cache() -> str | None:
    """Apply the cache policy above; returns the directory compiles
    persist to, or ``None`` when this process persists nothing.  Imports
    jax but initialises no backend; idempotent."""
    import jax

    # cache every compile: the entry count is then a function of the
    # programs run, not of how long each happened to take to build
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if (jax.config.jax_platforms or "").strip().lower() == "cpu":
        return None
    os.makedirs(CHECKOUT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def cache_entry_count(path: str | None) -> int:
    """Compiled executables persisted under ``path`` (0 for ``None`` or a
    directory that does not exist yet)."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
