#!/usr/bin/env python3
"""Read a cell's control on the chip: the plain reference put in the
program's place, computed one step of precision below what the configuration
states, judged by the same comparison that judges the program
(``run.is_correct`` over the configuration's limits).  No server is started;
the rows, weights and queries are the cell's own, at its own size.  Every
seed's line ends in ``correct``; the exit code is 1 when some seed's control
came out correct, which a control never may.

    python3 perfbench/control.py --workload retrieve-steady --seeds 11,12,13
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    _bench, cell, config, traffic = run.load_cell(args.workload, args.rehearse)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"the control is read on a TPU; JAX found {dev.platform}", file=sys.stderr)
        return 3
    check = run.load_module("checks", traffic.get("check", config["check"]))
    limits = config[check.LIMITS]
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        reading = check.control(config, traffic, seed)
        compared = {name: {"value": value, "limit": limits.get(name)}
                    for name, value in reading.items()}
        correct = run.is_correct(compared)
        passed += correct
        print("perfbench-control " + json.dumps(
            {"workload": cell["name"], "seed": seed, "device": dev.device_kind,
             "seconds": time.monotonic() - t0, **reading, "compared": compared,
             "correct": correct}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
