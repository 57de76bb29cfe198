#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one set-up, then one
short window per rate, each with its own generator process.

    python3 perfbench/sweep.py --workload retrieve-steady --seed 7 \\
        --rates 100,200,300,400,500 --seconds 15 [--out perfbench/out]

A rate is sustained when nothing failed or was refused, the generator was not
late, and the tail did not grow through the window (the p95 of the last third
is within twice that of the first third).  The cell's traffic file then gets
four fifths of the highest sustained rate, by hand: a run never searches.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import run
from stats import rank


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rate-key", default="rate_per_s")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(run.HERE, "out"))
    args = ap.parse_args()
    _bench, cell, config, traffic = run.load_cell(args.workload, args.rehearse)

    from pathway_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"sweep needs a TPU; JAX found {dev.platform}", file=sys.stderr)
        return 3
    compiles = run.CompileCounter()
    workdir = tempfile.mkdtemp(prefix="perfbench_sweep_")
    steps = []
    try:
        server = run.load_module("servers", config["server"]).start(
            config, args.seed, workdir, run.log)
        server.warm_up(traffic)
        for n, rate in enumerate(float(r) for r in args.rates.split(",")):
            w = run.run_window(server, dict(traffic, **{args.rate_key: rate}),
                               args.seed + n, args.seconds, workdir, compiles)
            recs = w["records"]
            ok = [r for r in recs if not r["failed"]]
            third = max(1, len(recs) // 3)
            key = traffic.get("latency_key", "latency_ms")
            first = [r[key] for r in recs[:third] if not r["failed"]]
            last = [r[key] for r in recs[-third:] if not r["failed"]]
            lat = [r[key] for r in ok]
            d = w["delta"]
            step = {
                "rate": rate, "attempted": len(recs), "failed": len(recs) - len(ok),
                "p50_ms": rank(lat, 50), "p95_ms": rank(lat, 95), "p99_ms": rank(lat, 99),
                "p95_first_third_ms": rank(first, 95), "p95_last_third_ms": rank(last, 95),
                "late_p95_ms": rank([r["late_ms"] for r in recs if r["late_ms"] is not None], 95),
                "closed_after_s": w["wall_s"], "compiles": w["compiles"],
                "ticks": d.get("ticks_total"), "collab_embeds": d.get("collab.embeds_total"),
                "completed": d.get("runtime.interactive.completed_total"),
                "ttft_p90_ms": rank([r["ttft_ms"] for r in ok if r.get("ttft_ms")], 90),
                "tpot_p90_ms": rank([r["tpot_ms"] for r in ok if r.get("tpot_ms")], 90),
            }
            step["sustained"] = bool(
                step["failed"] == 0 and step["late_p95_ms"] < 5.0
                and step["p95_last_third_ms"] <= 2 * step["p95_first_third_ms"])
            steps.append(step)
            print("perfbench-sweep " + json.dumps(step), flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    result = {"workload": cell["name"], "seed": args.seed, "seconds": args.seconds,
              "device": dev.device_kind, "steps": steps}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"sweep_{cell['name']}.json"), "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # noqa: BLE001 - the boundary: print it, then leave without joins
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    os._exit(code)
