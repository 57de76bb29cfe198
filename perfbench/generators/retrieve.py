"""Open-loop client of ``POST /v1/retrieve``.

Runs as a child process that imports neither ``jax`` nor ``pathway_tpu``: the
chip and the server's interpreter lock belong to the parent.  It sends on a
schedule drawn from the seed whether or not earlier requests have returned
(the idea of ``benchmarks/serving_bench.py --loadgen``, which was a closed
loop), times each request from when it was DUE, and records how late it was
sent.

Protocol: ``python retrieve.py <spec.json>``; prints ``READY`` when the
schedule, the texts and the connections exist, starts at the line ``GO`` on
its standard input, and writes one JSON record per request to ``spec["out"]``
when every response is in (or ``drain_s`` after the last was due).
"""

from __future__ import annotations

import http.client
import itertools
import json
import os
import sys
import threading
import time
import urllib.parse
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import textgen  # noqa: E402


def compact(body: bytes):
    """(failed, answer): the response as ``[path, dist, crc32(text)]`` rows.
    A degraded answer (an object, not a list) is a failed one."""
    try:
        rows = json.loads(body)
    except ValueError:
        return True, "not JSON"
    if not isinstance(rows, list):
        return True, "degraded" if isinstance(rows, dict) and rows.get("degraded") else "not a list"
    try:
        return False, [
            [(r["metadata"] or {}).get("path"), r["dist"],
             zlib.crc32(r["text"].encode("utf-8"))] for r in rows
        ]
    except (KeyError, TypeError, AttributeError):
        return True, "malformed rows"


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    traffic, seed, seconds = spec["traffic"], int(spec["seed"]), float(spec["seconds"])
    url = urllib.parse.urlparse(spec["url"])
    due = textgen.poisson_due_times(float(traffic["rate_per_s"]), seconds, seed)
    texts = textgen.query_texts(len(due), seed, int(traffic["min_words"]),
                                int(traffic["max_words"]))
    bodies = [json.dumps({"query": t, "k": int(traffic["k"])}).encode() for t in texts]
    records: list = [None] * len(due)
    counter = itertools.count()
    start = threading.Event()
    t0 = [0.0]

    def worker() -> None:
        conn = http.client.HTTPConnection(url.hostname, url.port, timeout=float(spec["drain_s"]))
        conn.connect()
        start.wait()
        while True:
            i = next(counter)
            if i >= len(due):
                conn.close()
                return
            at = t0[0] + due[i]
            wait = at - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            sent = time.monotonic()
            rec = {"i": i, "due_s": due[i], "late_ms": (sent - at) * 1e3}
            try:
                conn.request("POST", url.path, bodies[i],
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                body = resp.read()
                done = time.monotonic()
                rec["status"] = resp.status
                if resp.status == 200:
                    failed, answer = compact(body)
                else:
                    failed, answer = True, f"HTTP {resp.status}"
            except (OSError, http.client.HTTPException) as exc:
                done = time.monotonic()
                failed, answer = True, f"{type(exc).__name__}: {exc}"
                rec["status"] = 0
                conn.close()
                conn = http.client.HTTPConnection(url.hostname, url.port,
                                                  timeout=float(spec["drain_s"]))
            rec["latency_ms"] = (done - at) * 1e3
            rec["failed"] = failed
            rec["answer"] = answer
            records[i] = rec

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(traffic["connections"]))]
    for t in threads:
        t.start()
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 2
    t0[0] = time.monotonic() + 0.05
    start.set()
    deadline = t0[0] + seconds + float(spec["drain_s"])
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    with open(spec["out"], "w") as f:
        for i, rec in enumerate(records):
            if rec is None:  # never answered: failed, with no latency
                rec = {"i": i, "due_s": due[i], "late_ms": None, "status": 0,
                       "latency_ms": None, "failed": True, "answer": "no answer"}
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
