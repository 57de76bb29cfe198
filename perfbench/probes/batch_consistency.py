#!/usr/bin/env python3
"""Probe: does a retrieve tick answer a query the same whatever else rides in
the tick?  Starts the cell's deployment, then hands the retrieve plane's batch
handler the same distinct queries in ticks of different sizes and compares
each query's best score with the one it gets alone.  Found in PR 25: see
PERF.md, Open questions.  In an answering deployment the plane probed is the
answerer's own (``qa._stream_retrieve_plane()``), which takes the same path.
The combine is also tried alone at every pad the 2-, 4- and 8-row launches
can carry (PR 28).

    python3 perfbench/probes/batch_consistency.py --workload retrieve-steady --seed 1001
    python3 perfbench/probes/batch_consistency.py --workload answers-steady --seed 1001 --sizes 1,2,3,4,5,6,7,8
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402
import textgen  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1001)
    ap.add_argument("--sizes", default="1,2,3,4,5,7,8,9,12,16,24,31,32,33,64,100,128,129,200,256")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    _bench, _cell, config, traffic = run.load_cell(args.workload, args.rehearse)
    from pathway_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import numpy as np

    workdir = tempfile.mkdtemp(prefix="perfbench_probe_")
    try:
        server = run.load_module("servers", config["server"]).start(
            config, args.seed, workdir, run.log)
        qa = getattr(server, "qa", None)
        plane = qa._stream_retrieve_plane() if qa is not None else server.vs._retrieve_plane
        k = int(traffic["k"])
        texts = textgen.query_texts(256, args.seed + 77, int(traffic["min_words"]),
                                    int(traffic["max_words"]))
        # the combine the cache stack makes before the search, alone: fresh rows
        # scattered into a zero batch, pad rows sent out of bounds with mode="drop"
        import jax.numpy as jnp

        for rows, fresh_rows, n in ((2, 2, 1), (2, 1, 1), (4, 4, 3), (4, 4, 2), (4, 4, 1), (4, 2, 1),
                                    (4, 2, 2), (8, 8, 5), (8, 8, 7), (8, 4, 3), (8, 2, 1),
                                    (32, 32, 9)):
            fresh = jnp.arange(1, fresh_rows + 1, dtype=jnp.float32)[:, None] * jnp.ones(
                (fresh_rows, server.inner.dim), jnp.float32)
            idx = np.full((fresh_rows,), rows, np.int32)
            idx[:n] = np.arange(n)
            got = np.asarray(jnp.asarray(np.zeros((rows, server.inner.dim), np.float32))
                             .at[jnp.asarray(idx)].set(fresh, mode="drop"))[:, 0].tolist()
            want = [float(i + 1) if i < n else 0.0 for i in range(rows)]
            print("perfbench-probe " + json.dumps(
                {"scatter_drop": [rows, fresh_rows, n], "ok": got == want, "got": got[:8]}),
                flush=True)
        for n in (int(s) for s in args.sizes.split(",")):
            tick = [f"{t} n{n}" for t in texts[:n]]  # new texts: neither cache answers
            out = plane._batch([(t, k, None) for t in tick])
            bad = []
            for i in range(min(n, 32)):
                # the witness: the encoder's host path and a one-query search
                vec = np.asarray(server.encoder.encode([tick[i]]), np.float32)
                b = [s for _key, s in server.inner.search(vec, k)[0]]
                a = [-r["dist"] for r in out[i]["results"]]
                if len(a) != len(b) or max(abs(x - y) for x, y in zip(a, b)) > 5e-3:
                    bad.append((i, a[:2], b[:2]))
            print("perfbench-probe " + json.dumps(
                {"tick": n, "compared": min(n, 32), "differ": len(bad), "first": bad[:4]}),
                flush=True)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
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
