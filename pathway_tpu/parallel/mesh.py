"""Device-mesh construction.

The reference scales by ``PATHWAY_THREADS × PATHWAY_PROCESSES`` timely
workers over TCP (src/engine/dataflow/config.rs:88-120).  Here the unit of
scale-out is a TPU mesh: axis ``data`` shards rows/batches (the analogue of
the reference's key-hash worker sharding), axis ``model`` shards model
weights (tensor parallelism — no reference analogue; the reference has no
on-device model at all).
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_mesh", "serving_mesh", "data_axis", "model_axis"]

data_axis = "data"
model_axis = "model"


def make_mesh(
    n_devices: int | None = None,
    *,
    model_parallel: int = 1,
    devices=None,
) -> Mesh:
    """Build a ``(data, model)`` mesh over the first ``n_devices`` devices.

    ``model_parallel`` splits off a tensor-parallel axis; the rest is data
    parallel.  ``PATHWAY_MODEL_PARALLEL`` env overrides (mirroring the
    reference's env-driven worker config, dataflow/config.rs:88).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    devices = devices[:n_devices]
    mp = int(os.environ.get("PATHWAY_MODEL_PARALLEL", model_parallel))
    if n_devices % mp != 0:
        raise ValueError(f"n_devices={n_devices} not divisible by model_parallel={mp}")
    grid = np.array(devices).reshape(n_devices // mp, mp)
    return Mesh(grid, (data_axis, model_axis))


#: cached default serving mesh, keyed by the env value that built it —
#: Mesh identity matters: the sharded search is lru-cached per mesh, so
#: every server constructed under one setting must share one object
_serving_mesh_cache: dict[str, Mesh] = {}


def serving_mesh() -> Mesh | None:
    """Process-default serving mesh from ``PATHWAY_SERVING_MESH``.

    ``N`` (an int > 1) builds a data-parallel mesh over the first N
    devices; ``all`` uses every visible device; unset/``0``/``1`` means
    single-device serving (returns ``None``).  ``VectorStoreServer`` and
    ``DocumentStore`` consult this when no explicit ``mesh=`` is passed —
    the env knob that turns a one-chip deployment into a sharded one
    without touching code.  ``PATHWAY_MODEL_PARALLEL`` composes: it
    splits the tensor-parallel axis off the same device set."""
    raw = os.environ.get("PATHWAY_SERVING_MESH", "").strip().lower()
    if not raw or raw in ("0", "1", "none", "off"):
        return None
    cached = _serving_mesh_cache.get(raw)
    if cached is not None:
        return cached
    if raw == "all":
        n: int | None = None
    else:
        try:
            n = int(raw)
        except ValueError:
            import warnings

            warnings.warn(
                f"PATHWAY_SERVING_MESH={raw!r} is not an int or 'all' — "
                "serving single-device",
                stacklevel=2,
            )
            return None
        if n <= 1:
            return None
    avail = len(jax.devices())
    if n is not None and n > avail:
        raise ValueError(
            f"PATHWAY_SERVING_MESH={n} but only {avail} device(s) are "
            "visible; serving on fewer shards than asked would silently "
            "change per-device memory and latency"
        )
    mesh = make_mesh(n)
    _serving_mesh_cache[raw] = mesh
    return mesh
