"""Open-loop writer of new passage files into the watched directory.

A child process without ``jax`` or ``pathway_tpu``.  On a schedule drawn from
the seed it writes passage ``first + i`` beside the directory and renames it
in (the reader never sees half a file), and records when the rename returned
(``renamed_at``, on the machine-wide monotonic clock) and how late it was.
When the live index counted each file is the parent's to see: it fills
``fresh_ms`` into these records.
"""

from __future__ import annotations

import json
import os
import sys
import time
import urllib.parse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import textgen  # noqa: E402


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    traffic, seed, seconds = spec["traffic"], int(spec["seed"]), float(spec["seconds"])
    corpus = urllib.parse.urlparse(spec["url"]).path
    first = int(spec["facts"]["next_passage"])
    corpus_seed = int(spec["facts"]["corpus_seed"])
    due = textgen.poisson_due_times(float(traffic["rate_per_s"]), seconds, seed)
    texts = [textgen.passage(first + i, corpus_seed) for i in range(len(due))]
    records = []
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 2
    t0 = time.monotonic() + 0.05
    for i, at in enumerate(due):
        wait = t0 + at - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        started = time.monotonic()
        tmp = os.path.join(os.path.dirname(corpus), f".drop_{first + i}")
        rec = {"i": i, "due_s": at, "late_ms": (started - t0 - at) * 1e3, "status": 200,
               "failed": False, "latency_ms": None, "answer": {"passage": first + i}}
        try:
            with open(tmp, "w") as f:
                f.write(texts[i])
            os.rename(tmp, os.path.join(corpus, f"passage_{first + i:07d}.txt"))
            rec["renamed_at"] = time.monotonic()
        except OSError as exc:
            rec.update({"failed": True, "status": 0, "answer": f"{type(exc).__name__}: {exc}"})
        records.append(rec)
    with open(spec["out"], "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
