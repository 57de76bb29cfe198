#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one set-up, then one
short window per rate, each with its own generator process.

    python3 perfbench/sweep.py --workload retrieve-steady --seed 7 \\
        --rates 100,200,300,400,500 --seconds 15 [--out perfbench/out]

A rate is sustained when nothing failed or was refused, the generator was not
late, and the tail did not grow through the window: by default the p95 of the
last third is within twice that of the first third; a traffic file sets its own
under ``sustained`` (``key``, ``pct``, ``parts``, ``ratio``: answers take the
p90 of ``ttft_ms``, second half within 1.25x of the first).  The cell's traffic
file then gets four fifths of the highest sustained rate, by hand: a run never
searches.
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
        if float(traffic.get("warm_s", 0)) > 0:  # shapes only the mix drives, as run.py
            run.run_window(server, traffic, args.seed ^ 0x5EED, float(traffic["warm_s"]),
                           workdir, compiles)
        for n, rate in enumerate(float(r) for r in args.rates.split(",")):
            seed = args.seed + n
            w = run.run_window(server, dict(traffic, **{args.rate_key: rate}),
                               seed, args.seconds, workdir, compiles)
            recs = w["records"]
            ok = [r for r in recs if not r["failed"]]
            key = traffic.get("latency_key", "latency_ms")
            rule = {"key": key, "pct": 95, "parts": 3, "ratio": 2.0,
                    **traffic.get("sustained", {})}
            part = max(1, len(recs) // int(rule["parts"]))
            first = [r[rule["key"]] for r in recs[:part] if not r["failed"]]
            last = [r[rule["key"]] for r in recs[-part:] if not r["failed"]]
            lat = [r[key] for r in ok]
            d = w["delta"]
            step = {
                "rate": rate, "seed": seed, "attempted": len(recs), "failed": len(recs) - len(ok),
                "p50_ms": rank(lat, 50), "p95_ms": rank(lat, 95), "p99_ms": rank(lat, 99),
                "tail_first_part_ms": rank(first, rule["pct"]),
                "tail_last_part_ms": rank(last, rule["pct"]), "rule": rule,
                "late_p95_ms": rank([r["late_ms"] for r in recs if r["late_ms"] is not None], 95),
                "closed_after_s": w["wall_s"], "compiles": w["compiles"],
                "ticks": d.get("ticks_total"), "collab_embeds": d.get("collab.embeds_total"),
                "completed": d.get("runtime.interactive.completed_total"),
                "retrieve_ticks": d.get("sched.batches_total"),
                "retrieve_ticks_of_several": d.get("sched.multi_item_batches_total"),
                "verifies": d.get('om.pathway_decode_launch_ms_count{kind="verify"}'),
                "verify_ms": d.get('om.pathway_decode_launch_ms_sum{kind="verify"}'),
                "statuses": {str(s): sum(1 for r in recs if r["status"] == s)
                             for s in sorted({r["status"] for r in recs})},
                "why_failed": sorted({str(r["answer"])[:80] for r in recs if r["failed"]})[:4],
                "decode_steps": d.get('om.pathway_decode_launch_ms_count{kind="decode_step"}'),
                "decode_step_ms": d.get('om.pathway_decode_launch_ms_sum{kind="decode_step"}'),
                "prefills": d.get('om.pathway_decode_launch_ms_count{kind="prefill"}'),
                "prefill_ms": d.get('om.pathway_decode_launch_ms_sum{kind="prefill"}'),
                "memory_peak_bytes": w["peak"],
                "ttft_p90_ms": rank([r["ttft_ms"] for r in ok if r.get("ttft_ms")], 90),
                "tpot_p90_ms": rank([r["tpot_ms"] for r in ok if r.get("tpot_ms")], 90),
            }
            step["sustained"] = bool(
                step["failed"] == 0 and step["late_p95_ms"] < 5.0
                and step["tail_last_part_ms"] <= rule["ratio"] * step["tail_first_part_ms"])
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
