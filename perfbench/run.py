#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as ``setup_s``, from the start of the process to the start of
the window): start the cell's deployment in this process, warm up every shape
its traffic uses, start the load generator in a child process that holds no
chip.  Window: the generator sends its open-loop schedule for ``--seconds``.
Afterwards: metrics from the generator's records and the program's counters
(and, with ``--trace 1``, from a profiler trace of a slice in the middle),
then the program's device state is freed and the plain reference decides
``correct``.  The last line of standard output is the result.

Everything that belongs to one configuration, traffic mix, generator, server,
check or metric is a file of its own, found by the name in BENCHMARK.json;
see README.md.  ``--rehearse`` (used only by the CPU test) runs the
configuration's tiny ``rehearse`` sizes on whatever platform there is and
still prints that platform.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str) -> None:
    print(f"[perfbench +{time.monotonic() - T_START:6.1f}s] {msg}", file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module (metric names hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def load_cell(name: str, rehearse: bool):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:  # a cell that is built but not admitted: staged.json
        with open(os.path.join(HERE, "staged.json")) as f:
            bench = json.load(f)
        cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json or perfbench/staged.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:
        config = merged(config, config.get("rehearse", {}))
        traffic = merged(traffic, traffic.get("rehearse", {}))
    return bench, cell, config, traffic


def metrics_of(bench: dict, cell: dict, group: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def is_correct(compared: dict) -> bool:
    """No number compared lies over its limit (one without a limit is only
    reported).  The program's run and the control are judged by this alone."""
    return all(c["limit"] is None or c["value"] <= c["limit"] for c in compared.values())


class CompileCounter:
    """Backend compilations seen by JAX's own monitoring, whatever compiled."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1


def trace_slice(logdir: str, wait_s: float, slice_s: float) -> None:
    import jax

    time.sleep(wait_s)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the Python tracer made the host 25x slower
    options.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=options)
    time.sleep(slice_s)
    jax.profiler.stop_trace()


def run_window(server, traffic: dict, seed: int, seconds: float, workdir: str,
               compiles: "CompileCounter", trace: bool = False) -> dict:
    """Start the cell's generator in a child process, let it send for
    ``seconds``, and return its records with the program's counters over the
    window.  ``setup_s`` is read at the moment the window opens."""
    spec = {"url": server.urls[traffic["endpoint"]], "seed": seed,
            "seconds": seconds, "traffic": traffic, "drain_s": traffic["drain_s"],
            "out": os.path.join(workdir, f"records_{time.monotonic_ns()}.jsonl"),
            "facts": server.facts()}
    spec_path = spec["out"] + ".spec.json"
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "generators", traffic["generator"] + ".py"),
         spec_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        ready = child.stdout.readline().strip()
        if ready != "READY":
            raise RuntimeError(f"the generator said {ready!r}, not READY")
        tracer, trace_dir = None, os.path.join(workdir, "trace")
        if trace:
            slice_s = min(float(traffic.get("trace_slice_s", 4.0)), seconds / 2)
            tracer = threading.Thread(
                target=trace_slice, args=(trace_dir, (seconds - slice_s) / 2, slice_s))
        import jax

        server.window_opens(traffic)
        jax.config.update("jax_log_compiles", True)  # names whatever compiles in the window
        before, compiles_before = server.counters(), compiles.n
        setup_s = time.monotonic() - T_START
        child.stdin.write("GO\n")
        child.stdin.flush()
        t_go = time.monotonic()
        if tracer is not None:
            tracer.start()
        child.wait(timeout=seconds + float(traffic["drain_s"]) + 60)
        wall_s = time.monotonic() - t_go
        if tracer is not None:
            tracer.join()
        if child.returncode != 0:
            raise RuntimeError(f"the generator exited with {child.returncode}")
        with open(spec["out"]) as f:
            records = [json.loads(line) for line in f]
        server.window_closed(traffic, records, seed)
        after, compiled = server.counters(), compiles.n - compiles_before
        jax.config.update("jax_log_compiles", False)
        server.after_window(traffic, records, seed)
    finally:
        if child.poll() is None:  # leave no process behind
            child.kill()
            child.wait()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())
    delta = {k: after[k] - before.get(k, 0) for k in after
             if isinstance(after[k], (int, float))}
    return {"records": records, "setup_s": setup_s, "wall_s": wall_s, "peak": peak,
            "delta": delta, "compiles": compiled,
            "trace_dir": trace_dir}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", help="directory that keeps the trace's planes (gzipped JSON)")
    args = ap.parse_args()
    bench, cell, config, traffic = load_cell(args.workload, args.rehearse)

    from pathway_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    import costs

    devices = jax.devices()
    dev = devices[0]
    log(f"{len(devices)} x {dev.device_kind} ({dev.platform}); compile cache {cache_dir}")
    if not args.rehearse:
        if dev.platform != "tpu" or len(devices) < cell["chips"]:
            print(f"perfbench: cell {cell['name']} needs {cell['chips']} TPU chip(s); JAX found "
                  f"{len(devices)} x {dev.device_kind} ({dev.platform})", file=sys.stderr)
            return 3
        peaks = costs.peaks(dev.device_kind)  # an unknown device is an error
    else:
        peaks = None
    compiles = CompileCounter()
    workdir = tempfile.mkdtemp(prefix="perfbench_")
    try:
        server = load_module("servers", config["server"]).start(config, args.seed, workdir, log)
        server.warm_up(traffic)
        if float(traffic.get("warm_s", 0)) > 0:
            # shapes only the mix itself drives (prefill, verify and decode
            # buckets): some seconds of it, another seed's, still set-up
            warm = run_window(server, traffic, args.seed ^ 0x5EED, float(traffic["warm_s"]),
                              workdir, compiles)
            log(f"warm traffic: {len(warm['records'])} requests, "
                f"{sum(r['failed'] for r in warm['records'])} failed, "
                f"{warm['compiles']} compiles, {warm['wall_s']:.1f}s")

        w = run_window(server, traffic, args.seed, args.seconds, workdir, compiles,
                       trace=bool(args.trace))
        records, setup_s, peak, delta = w["records"], w["setup_s"], w["peak"], w["delta"]
        window_wall_s, compiles_in_window = w["wall_s"], w["compiles"]
        trace_dir = w["trace_dir"]
        done = [r for r in records if not r["failed"]]
        late = sorted(r["late_ms"] for r in records if r["late_ms"] is not None)
        log(f"window: setup {setup_s:.1f}s, {len(records)} attempted, "
            f"{len(records) - len(done)} failed, generator closed after {window_wall_s:.1f}s")
        eyes = {
            "compiles_in_window": compiles_in_window,
            "generator_late_ms_p95": late[int(0.95 * (len(late) - 1))] if late else None,
            "generator_late_ms_max": late[-1] if late else None,
            "breaker_trips": delta.get("breaker.retrieve.trips_total"),
            "index_rebuilds": delta.get("index.rebuilds"),
            "collab_embeds": delta.get("collab.embeds_total"),
            "retrieve_ticks": delta.get("sched.batches_total"),
            "retrieve_ticks_of_several": delta.get("sched.multi_item_batches_total"),
            "statuses": {str(s): sum(1 for r in records if r["status"] == s)
                         for s in sorted({r["status"] for r in records})},
            "memory_peak_bytes": peak,
        }
        print("perfbench-eyes " + json.dumps(eyes), flush=True)

        ctx = {
            "bench": bench, "cell": cell, "config": config, "traffic": traffic,
            "seed": args.seed, "seconds": args.seconds, "records": records,
            "setup_s": setup_s, "delta": delta, "facts": server.facts(),
            "peaks": peaks, "costs": costs,
        }
        metrics: dict = {}
        device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        breakdown = None
        if args.trace:
            import trace_reduce

            planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
            if args.out:
                import gzip

                os.makedirs(args.out, exist_ok=True)
                with gzip.open(os.path.join(
                        args.out, f"planes_{cell['name']}_{args.seed}.json.gz"), "wt") as f:
                    json.dump(planes, f)
            if planes["devices"] or not args.rehearse:  # a CPU trace has no device plane
                ctx["trace"] = trace_reduce.reduce(planes)
                device["busy_s"] = ctx["trace"]["busy_s"]
                device["window_s"] = ctx["trace"]["window_s"]
                breakdown = {"device_ops": ctx["trace"]["device_ops"],
                             "idle_gaps": ctx["trace"]["idle_gaps"]}
                log(f"trace: busy {device['busy_s']:.3f}s of {device['window_s']:.3f}s; "
                    f"programs {json.dumps(ctx['trace']['programs'])}")
        group = "per_layer" if args.trace else "end_to_end"
        kind = "layer_metrics" if args.trace else "end_to_end"
        for m in metrics_of(bench, cell, group):
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = load_module(kind, m["name"]).read(ctx)
            if value is not None:  # a reader that finds nothing returns nothing
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        server.free()
        t_check = time.monotonic()
        compared = load_module("checks", traffic.get("check", config["check"])).check(ctx)
        log(f"reference check took {time.monotonic() - t_check:.1f}s")
        correct = is_correct(compared)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    result = {"correct": bool(correct), "attempted": len(records),
              "failed": len(records) - len(done), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for name, c in compared.items():
        print(f"perfbench-compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # noqa: BLE001 - the boundary: print it, then leave without joins
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # the engine's daemon threads hold no state worth a join
