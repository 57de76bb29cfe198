"""Texts and arrival schedules, from the seed alone.

Pure Python (no numpy, no jax): the load generators run in a child process
that may not touch the chip, and the checks build the same texts again.
Every seed gets the SAME multiset of lengths and of inter-arrival gaps, in
another order, so the seed never changes the amount of work.
"""

from __future__ import annotations

import math
import random

#: passage lengths in words, cycled (24/24/56/120: MS MARCO passages have a
#: mean of ~56 words; the cycle's mean is 56)
PASSAGE_WORDS = (24, 24, 56, 120)
VOCABULARY = 20000


def _word(n: int) -> str:
    return f"t{n:05d}"


def passage(i: int, seed: int) -> str:
    """Passage ``i`` of the corpus of ``seed``."""
    rng = random.Random(f"{seed}:passage:{i}")
    n = PASSAGE_WORDS[i % len(PASSAGE_WORDS)]
    return " ".join(_word(rng.randrange(VOCABULARY)) for _ in range(n))


def spread(values: list, count: int, rng: random.Random) -> list:
    """``count`` values that hold each of ``values`` equally often (to
    within one), shuffled by ``rng``."""
    out = [values[i % len(values)] for i in range(count)]
    rng.shuffle(out)
    return out


def query_texts(count: int, seed: int, min_words: int, max_words: int) -> list[str]:
    """``count`` distinct query texts; the lengths are the same multiset for
    every seed."""
    rng = random.Random(f"{seed}:queries")
    lengths = spread(list(range(min_words, max_words + 1)), count, rng)
    seen: set[str] = set()
    out = []
    for n in lengths:
        while True:
            text = " ".join(_word(rng.randrange(VOCABULARY)) for _ in range(n))
            if text not in seen:
                seen.add(text)
                out.append(text)
                break
    return out


def poisson_due_times(rate: float, seconds: float, seed: int) -> list[float]:
    """Due times (seconds from the window's start) of an open loop with
    exponential gaps of mean ``1/rate``.  The gaps are the ``n`` mid-point
    quantiles of the exponential distribution, shuffled by the seed: every
    seed sends ``n = round(rate * seconds)`` requests over the same span
    with the same set of gaps."""
    n = max(1, round(rate * seconds))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    random.Random(f"{seed}:arrivals").shuffle(gaps)
    scale = seconds / sum(gaps)  # the quantile sum is n/rate to ~1/n
    t, out = 0.0, []
    for g in gaps:
        out.append(t)
        t += g * scale
    return out
