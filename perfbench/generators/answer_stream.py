"""Open-loop client of ``POST /v1/pw_ai_answer_stream`` (NDJSON events).

Like ``retrieve.py``: a child process without ``jax`` or ``pathway_tpu``, a
schedule and questions drawn from the seed, every request timed from when it
was DUE, its lateness recorded.  Per answer it keeps the time of the first
``token`` event and of ``done``, the token ids (the hash tokenizer decodes an
id as ``<id>``) and the CRC of each context passage.  Copied from
``chip_smoke.py`` ``_stream``: the event checks (one context line, not
degraded; ``done.response`` is the joined token pieces).
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


def read_stream(resp, at: float, max_new_tokens: int) -> dict:
    """Consume the NDJSON lines of one answer."""
    first = done_at = None
    pieces, context, done = [], None, None
    while True:
        line = resp.readline()
        if not line:
            break
        now = time.monotonic()
        try:
            ev = json.loads(line)
        except ValueError:
            return {"failed": True, "answer": "not JSON"}
        kind = ev.get("event")
        if kind == "token":
            if first is None:
                first = now
            pieces.append(ev["text"])
        elif kind == "context":
            if context is not None or ev.get("retrieval_degraded"):
                return {"failed": True, "answer": "degraded"}
            context = ev["context_docs"]
        elif kind == "done":
            done, done_at = ev, now
        else:
            return {"failed": True, "answer": f"event {kind!r}"}
    if done is None or done.get("degraded") or done.get("response") is None:
        return {"failed": True, "answer": "degraded" if done else "no done line"}
    if context is None or not pieces:
        return {"failed": True, "answer": "no context line or no token"}
    try:
        tokens = [int(p.strip("<>")) for p in done["response"].split()]
    except ValueError:
        return {"failed": True, "answer": "tokens unreadable"}
    n = len(tokens)
    return {
        "failed": False,
        "latency_ms": (done_at - at) * 1e3,
        "ttft_ms": (first - at) * 1e3,
        "tpot_ms": (done_at - first) * 1e3 / (n - 1) if n > 1 else None,
        "answer": {"tokens": tokens,
                   "context": [zlib.crc32(d.encode("utf-8")) for d in context],
                   "joined": "".join(pieces).strip() == done["response"],
                   "full": n == max_new_tokens},
    }


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    traffic, seed, seconds = spec["traffic"], int(spec["seed"]), float(spec["seconds"])
    url = urllib.parse.urlparse(spec["url"])
    due = textgen.poisson_due_times(float(traffic["rate_per_s"]), seconds, seed)
    texts = textgen.query_texts(len(due), seed, int(traffic["min_words"]),
                                int(traffic["max_words"]))
    max_new = int(traffic["max_new_tokens"])
    bodies = [json.dumps({"prompt": t, "max_new_tokens": max_new,
                          "return_context_docs": True}).encode() for t in texts]
    records: list = [None] * len(due)
    counter = itertools.count()
    start = threading.Event()
    t0 = [0.0]

    def worker() -> None:
        start.wait()
        while True:
            i = next(counter)
            if i >= len(due):
                return
            at = t0[0] + due[i]
            wait = at - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            sent = time.monotonic()
            rec = {"i": i, "due_s": due[i], "late_ms": (sent - at) * 1e3, "status": 0,
                   "latency_ms": None, "ttft_ms": None, "tpot_ms": None}
            conn = http.client.HTTPConnection(url.hostname, url.port,
                                              timeout=float(spec["drain_s"]))
            try:
                conn.request("POST", url.path, bodies[i],
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                rec["status"] = resp.status
                if resp.status == 200:
                    rec.update(read_stream(resp, at, max_new))
                else:
                    resp.read()
                    rec.update({"failed": True, "answer": f"HTTP {resp.status}"})
            except (OSError, http.client.HTTPException) as exc:
                rec.update({"failed": True, "answer": f"{type(exc).__name__}: {exc}"})
            finally:
                conn.close()
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
            if rec is None:
                rec = {"i": i, "due_s": due[i], "late_ms": None, "status": 0,
                       "latency_ms": None, "ttft_ms": None, "tpot_ms": None,
                       "failed": True, "answer": "no answer"}
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
