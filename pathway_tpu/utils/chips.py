"""One process per chip: the environment a launcher gives its children.

A TPU chip belongs to one process at a time.  A process that initialises
a JAX backend claims every chip it can see, so N children started with
the parent's environment all claim all chips and N-1 of them fail.  The
launchers (``pathway_tpu spawn -n N``, ``fleet.launcher.spawn_replica``)
therefore stay off JAX themselves — chips are counted from their device
nodes here — and pin each child to its own chip through libtpu's
process-bounds variables.
"""

from __future__ import annotations

import glob
import os
from typing import Mapping

__all__ = ["local_chip_count", "one_chip_env", "child_chip_env"]


def local_chip_count() -> int:
    """TPU chips attached to this host, from their device nodes (no JAX:
    a launcher that touched a backend would hold the chips itself)."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def one_chip_env(chip: int) -> dict[str, str]:
    """Variables that make a child process see exactly chip ``chip`` as a
    complete one-chip topology."""
    return {
        "TPU_VISIBLE_CHIPS": str(int(chip)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def child_chip_env(
    index: int, n_children: int, env: Mapping[str, str] | None = None
) -> dict[str, str]:
    """Chip assignment for child ``index`` of ``n_children`` started with
    environment ``env`` (default: this process's): nothing on a host
    without chips, for a single child (it owns them all), when the caller
    already placed chips through ``TPU_VISIBLE_CHIPS``, or when
    ``JAX_PLATFORMS=cpu`` keeps the children off them; else chip
    ``index``.  More children than chips is an error — two processes
    cannot share one."""
    env = os.environ if env is None else env
    chips = local_chip_count()
    if (
        chips == 0
        or n_children <= 1
        or "TPU_VISIBLE_CHIPS" in env
        or env.get("JAX_PLATFORMS") == "cpu"
    ):
        return {}
    if n_children > chips:
        raise RuntimeError(
            f"{n_children} processes on a host with {chips} TPU chip(s): a "
            "chip belongs to one process; lower --processes or set "
            "JAX_PLATFORMS=cpu for a host-only run"
        )
    return one_chip_env(index)
