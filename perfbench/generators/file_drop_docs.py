"""Open-loop writer of new document files into the watched directory.

``file_drop.py`` for documents: the same schedule, records and rename, but
document ``first + i`` holds ``words[(first + i) % len(words)]`` words, the
cycle the configuration file gives (``document_words``, handed over in the
spec's ``facts``), so every seed drops the same multiset of lengths.  The
server and the check build the same texts again from ``document``.

A child process without ``jax`` or ``pathway_tpu``.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time
import urllib.parse

VOCABULARY = 20000  # textgen's, so that words look alike in both corpora


def document(i: int, seed: int, words: list[int]) -> str:
    """Document ``i`` of the corpus of ``seed``."""
    rng = random.Random(f"{seed}:document:{i}")
    return " ".join(f"t{rng.randrange(VOCABULARY):05d}"
                    for _ in range(int(words[i % len(words)])))


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import textgen

    with open(sys.argv[1]) as f:
        spec = json.load(f)
    traffic, seed, seconds = spec["traffic"], int(spec["seed"]), float(spec["seconds"])
    corpus = urllib.parse.urlparse(spec["url"]).path
    facts = spec["facts"]
    first, corpus_seed = int(facts["next_passage"]), int(facts["corpus_seed"])
    due = textgen.poisson_due_times(float(traffic["rate_per_s"]), seconds, seed)
    texts = [document(first + i, corpus_seed, facts["document_words"]) for i in range(len(due))]
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
