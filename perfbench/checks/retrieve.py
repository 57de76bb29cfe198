"""What decides ``correct`` in a retrieve cell.

Every answer of the window is held to the guarantees the configuration
states (exact comparisons, limit 0): it came, it is not degraded, it has
``k`` rows in order, and each row carries the text that belongs to its id.
A sample of answers drawn from the seed (the longest query in it) is then
compared with the plain reference: the float32 encoder of ``minilm.py`` and
a float32 ``highest``-precision scan over the rows as the harness made them
from the seed, made again here block by block.

Two numbers come out of the sample:

``score_gap``       the largest |served score - reference score of that row|
``rank_shortfall``  the widest gap by which the reference score of a served
                    row lies below the reference's k-th best score

``control_answers`` is the control: the same reference computed one step
of precision below what the configuration states (float8 products in the
encoder and on the wire, bfloat16 rows and products in the scan).
"""

from __future__ import annotations

import random
import re
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np

import seeded
import textgen
from checks import minilm

#: the group of the configuration file that holds this check's limits
LIMITS = "limits"

_PASSAGE = re.compile(r"passage_(\d+)\.txt$")


def encoder_kwargs(config: dict) -> dict:
    e = config["encoder"]
    return dict(vocab_size=e["vocab_size"], max_length=e["max_seq_length"],
                heads=e["num_attention_heads"], eps=e["layer_norm_eps"])


def row_id(path) -> int | None:
    """Row number of an answer's ``metadata.path``: prefilled row ``s`` is
    ``s``; ingested passage ``i`` is ``rows + i``."""
    if not isinstance(path, str):
        return None
    if path.startswith("prefill/"):
        return int(path[len("prefill/"):])
    m = _PASSAGE.search(path)
    return None if m is None else -1 - int(m.group(1))


class Rows:
    """The index's rows as the harness made them, in blocks."""

    def __init__(self, config: dict, seed: int, params, n_passages: int,
                 lowered: bool = False):
        self.rows = int(config["rows"])
        self.dim = int(config["index"]["dim"])
        self.block = int(config["index"]["prefill_block_rows"])
        self.key = seeded.key_of(seed, 1)
        passages = [textgen.passage(i, seed) for i in range(n_passages)]
        self.passages = jnp.asarray(minilm.encode(
            params, passages, lowered=lowered, **encoder_kwargs(config)))
        self.lowered = lowered

    def blocks(self):
        """(first row number, [n, dim] unit rows); ingested passages come
        last, numbered from ``rows``."""
        for b in range(self.rows // self.block):
            raw = seeded.row_block(self.key, b, rows=self.block, dim=self.dim)
            unit = raw / jnp.maximum(jnp.linalg.norm(raw, axis=1, keepdims=True), 1e-30)
            yield b * self.block, unit
        yield self.rows, self.passages


def _global_id(i: int, rows: int) -> int:
    """Answers number passages -1-i; the scan numbers them rows+i."""
    return rows - 1 - i if i < 0 else i


def scan(q, rows: Rows, k: int, wanted: list[list[int]]):
    """Top-``k`` reference (scores, row numbers) of each query over every
    row, and the reference scores of the ``wanted`` rows of each query."""
    q = jnp.asarray(q)
    nq = q.shape[0]
    best_s = np.full((nq, k), -np.inf, np.float32)
    best_i = np.full((nq, k), -1, np.int64)
    got = [np.full((len(w),), np.nan, np.float32) for w in wanted]
    flat = [(qi, j, _global_id(r, rows.rows)) for qi, w in enumerate(wanted)
            for j, r in enumerate(w)]
    dtype = jnp.bfloat16 if rows.lowered else jnp.float32
    precision = "default" if rows.lowered else "highest"
    for first, unit in rows.blocks():
        with jax.default_matmul_precision(precision):
            s = jnp.dot(q.astype(dtype), unit.astype(dtype).T,
                        preferred_element_type=jnp.float32)
        top_s, top_i = jax.lax.top_k(s, min(k, unit.shape[0]))
        cand_s = np.concatenate([best_s, np.asarray(top_s)], axis=1)
        cand_i = np.concatenate([best_i, np.asarray(top_i) + first], axis=1)
        order = np.argsort(-cand_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(cand_s, order, axis=1)
        best_i = np.take_along_axis(cand_i, order, axis=1)
        here = [(qi, j, g - first) for qi, j, g in flat
                if first <= g < first + unit.shape[0]]
        if here:
            vals = np.asarray(s[np.asarray([h[0] for h in here]),
                                np.asarray([h[2] for h in here])])
            for (qi, j, _), v in zip(here, vals):
                got[qi][j] = v
    return best_s, best_i, got


def structural(records: list[dict], config: dict, traffic: dict, seed: int) -> dict:
    """Exact comparisons over every answer of the window."""
    k = int(traffic["k"])
    n_texts = int(config["payload_texts"])
    crc_cache: dict[int, int] = {}

    def crc_of(i: int) -> int:
        if i not in crc_cache:
            crc_cache[i] = zlib.crc32(textgen.passage(i, seed).encode("utf-8"))
        return crc_cache[i]

    missing = degraded = wrong = 0
    for rec in records:
        if rec["failed"]:
            if rec["answer"] == "degraded":
                degraded += 1
            elif rec["status"] in (0, 200):  # never came, or came unreadable
                missing += 1
            continue  # a refusal (503) is late or failed, not wrong
        rows = rec["answer"]
        dists = [r[1] for r in rows]
        ok = len(rows) == k and all(a <= b for a, b in zip(dists, dists[1:]))
        ids = []
        for path, _dist, crc in rows:
            rid = row_id(path)
            ids.append(rid)
            if rid is None:
                ok = False
            elif crc != crc_of(rid % n_texts if rid >= 0 else -1 - rid):
                ok = False
        if len(set(ids)) != len(ids):
            ok = False
        wrong += not ok
    return {"answers_missing": missing, "answers_degraded": degraded,
            "answers_malformed": wrong}


def pick_sample(records: list[dict], length_of, count: int, seed: int) -> list[int]:
    """``count`` answered requests drawn from the seed, the longest
    (by ``length_of(i)``) among them."""
    good = [r["i"] for r in records if not r["failed"]]
    if not good:
        return []
    longest = max(good, key=lambda i: (length_of(i), -i))
    rest = [i for i in good if i != longest]
    random.Random(f"{seed}:check").shuffle(rest)
    return [longest] + rest[: max(0, count - 1)]


def compare(answers: list[list[tuple[int, float]]], q_texts: list[str],
            config: dict, seed: int, params, n_passages: int, k: int) -> dict:
    """``answers[q]`` = (row number as ``row_id`` gives it, score) per row."""
    rows = Rows(config, seed, params, n_passages)
    q = minilm.encode(params, q_texts, **encoder_kwargs(config))
    wanted = [[r for r, _ in a] for a in answers]
    best_s, _best_i, got = scan(q, rows, k, wanted)
    gaps, shortfalls = [], []
    for qi, a in enumerate(answers):
        served = np.asarray([s for _, s in a], np.float32)
        if np.isnan(got[qi]).any() or len(a) == 0:
            return {"score_gap": 1e30, "rank_shortfall": 1e30}
        gaps.append(float(np.max(np.abs(served - got[qi]))))
        shortfalls.append(max(0.0, float(best_s[qi, len(a) - 1] - got[qi].min())))
    worst = int(np.argmax(gaps))
    print(f"perfbench-check worst query {worst} {q_texts[worst][:48]!r}: gap {gaps[worst]:.3g}, "
          f"shortfall {shortfalls[worst]:.3g}; served {answers[worst][:3]} reference scores "
          f"{got[worst][:3].tolist()} reference best {best_s[worst][:3].tolist()} "
          f"{_best_i[worst][:3].tolist()}; {sum(g > 0.01 for g in gaps)} of {len(gaps)} "
          f"queries over 0.01; median gap {float(np.median(gaps)):.3g}", file=sys.stderr)
    return {"score_gap": max(gaps), "rank_shortfall": max(shortfalls)}


def control_answers(q_texts: list[str], config: dict, seed: int, params,
                    n_passages: int, k: int) -> list[list[tuple[int, float]]]:
    """The reference in the program's place, one step of precision down."""
    rows = Rows(config, seed, params, n_passages, lowered=True)
    q = minilm.encode(params, q_texts, lowered=True, **encoder_kwargs(config))
    q = np.asarray(jnp.asarray(q).astype(jnp.float8_e4m3fn).astype(jnp.float32))
    best_s, best_i, _ = scan(q, rows, k, [[] for _ in q_texts])
    out = []
    for qi in range(len(q_texts)):
        out.append([(int(i) if i < rows.rows else -1 - (int(i) - rows.rows), float(s))
                    for s, i in zip(best_s[qi], best_i[qi])])
    return out


def check(ctx: dict) -> dict:
    """Numbers compared, each beside its limit."""
    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    records = ctx["records"]
    texts = textgen.query_texts(len(records), seed, int(traffic["min_words"]),
                                int(traffic["max_words"]))
    params = seeded.encoder_params(config, seed)
    n_passages = int(config["ingested_passages"])
    limits = config[LIMITS]
    out = structural(records, config, traffic, seed)
    picks = pick_sample(records, lambda i: len(texts[i].split()),
                        int(traffic["check_sample"]), seed)
    by_i = {r["i"]: r for r in records}
    answers = [[(row_id(p), -float(d)) for p, d, _ in by_i[i]["answer"]] for i in picks]
    if out["answers_malformed"] == 0 and picks:
        out.update(compare(answers, [texts[i] for i in picks], config, seed,
                           params, n_passages, int(traffic["k"])))
    else:
        out.update({"score_gap": 1e30, "rank_shortfall": 1e30})
    out["answers_compared"] = len(picks)
    return {name: {"value": value, "limit": limits.get(name)}
            for name, value in out.items()}


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The control's readings for one seed: the lowered reference answers
    ``check_sample`` of the cell's queries and is compared like the program."""
    n = int(traffic["check_sample"])
    texts = textgen.query_texts(n, seed, int(traffic["min_words"]), int(traffic["max_words"]))
    params = seeded.encoder_params(config, seed)
    n_passages, k = int(config["ingested_passages"]), int(traffic["k"])
    answers = control_answers(texts, config, seed, params, n_passages, k)
    return compare(answers, texts, config, seed, params, n_passages, k)
