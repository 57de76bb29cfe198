"""What decides ``correct`` in a live-ingest cell whose embedder is the
configuration's ``embedder`` group (a language model: ``checks/laguna.py``).

The exact counts are ``checks/ingest.py``'s (limit 0): every file dropped in
the window was counted by the live index within the drain limit, the index
grew by exactly as many rows as files were dropped, and each document of the
sample, asked for by its own text once the window had closed, came first,
once.  The sample's answers are then compared with the plain reference as a
retrieve cell's are (``checks/retrieve.py`` ``scan``):

``score_gap``         the largest |served score - reference score of that row|
``rank_shortfall``    the widest gap by which the reference score of a served
                      row lies below the reference's k-th best score
``score_gap_median``  the median, over the sampled answers, of each answer's
                      largest |served score - reference score|; reported only
``layer_gap``         the longest sampled document, layer by layer: each layer
                      of the PROGRAM is fed the input that the reference
                      computed at the STATED precision gives that layer
                      (``checks/laguna.py`` ``precision="stated"``), and the
                      median token's |program - reference| as a share of what
                      the layer adds to the token is taken; the largest over
                      the layers

over the prefilled rows, made again from the seed, and the documents the
reference has embedded.  The first three measure how far bfloat16 products put
the served scores from the float32 forward's.  They cannot tell the stated
precision from the one below it: a forward with the router, softmax, norms and
residual stream in bfloat16 too lies under twice as far from float32 as the
program does (PERF.md 6), so ``score_gap`` and ``rank_shortfall`` keep limits
as guards against a fault that hits a few documents and the median has none.
``layer_gap`` is what tells them apart: a layer that computes what the
configuration states differs from the stated-precision reference, fed the same
input, by the order of float32 sums alone (3e-7 on the chip), the lowered
layer by everything it rounds besides (2e-2).  It is read layer by layer, not
over the whole forward, because there a difference far below a bfloat16
rounding is lifted towards one by every rounding it meets (a sum that falls
the other side of a rounding moves the operand by 2^-9): over five layers the
program lies 4e-4 from the stated-precision forward in the served scores and
the lowered forward 3e-3, a ratio of seven where one layer gives 80,000 (PERF.md
6).  Departure from ``checks/ingest.py``, for its cost:
the reference embeds the sampled documents (each is its own query) and every
document that some sampled answer served, not every document of the run; a
document of 2,048 tokens through 256 experts a layer in float32 takes the
chip about a second, and a run drops hundreds.  A document that was neither
sampled nor served is not among the reference's candidates, so one the
program wrongly left out of an answer shows only if it is in the sample.
"""

from __future__ import annotations

import importlib
import sys

import jax.numpy as jnp
import numpy as np

import seeded
from checks import retrieve as rcheck
from generators import file_drop_docs

#: the group of the configuration file that holds this check's limits
LIMITS = "limits_ingest"
#: what is compared when there is nothing sound to compare
FAILED = {"score_gap": 1e30, "rank_shortfall": 1e30, "score_gap_median": 1e30,
          "layer_gap": 1e30}


def _modules(config: dict):
    e = config["embedder"]
    return (importlib.import_module("checks." + e["reference"]),
            importlib.import_module("encoders." + e["builder"]))


def embed(config: dict, seed: int, texts: list[str], precision: str = "float32") -> np.ndarray:
    """Unit vectors [n, dim] of ``texts`` by the plain reference computed in
    ``precision``, its weights made again from the seed."""
    reference, builder = _modules(config)
    out = reference.encode(config, texts, lambda: builder.embedding_params(config, seed),
                           lambda layer: builder.layer_params(config, seed, layer),
                           precision=precision)
    return out / np.maximum(np.linalg.norm(out, axis=1, keepdims=True), 1e-30)


class Rows:
    """What ``retrieve.scan`` scans: the prefilled rows in blocks, then one
    row per document number; a document the reference did not embed is a
    zero row, which never ranks."""

    def __init__(self, config: dict, seed: int, documents: dict[int, np.ndarray],
                 n_documents: int, lowered: bool = False):
        self.rows, self.dim = int(config["rows"]), int(config["index"]["dim"])
        self.block = int(config["index"]["prefill_block_rows"])
        self.key, self.lowered = seeded.key_of(seed, 1), lowered
        table = np.zeros((n_documents, self.dim), np.float32)
        for n, vec in documents.items():
            table[n] = vec
        self.documents = jnp.asarray(table)

    def blocks(self):
        for b in range(self.rows // self.block):
            raw = seeded.row_block(self.key, b, rows=self.block, dim=self.dim)
            yield b * self.block, raw / jnp.maximum(
                jnp.linalg.norm(raw, axis=1, keepdims=True), 1e-30)
        yield self.rows, self.documents


def layer_gap(config: dict, seed: int, text: str, block) -> float:
    """``block(layer, layer_params, x)`` [T, D] stands in the program's
    place, layer by layer, on the stated-precision reference's own states of
    ``text``: the median over the tokens of |block - reference| / |what the
    reference's layer added|, the largest over the layers."""
    reference, builder = _modules(config)
    ids = reference.tokenize(text, int(config["vocab_size"]), int(config["max_seq_length"]))
    x = builder.embedding_params(config, seed)["tok_emb"][jnp.asarray(ids)].astype(jnp.float32)
    worst, per_layer = 0.0, []
    for layer in range(int(config["num_hidden_layers"])):
        p, st = builder.layer_params(config, seed, layer), reference.layer_statics(config, layer)
        want = reference.layer_forward(p, x, st["freq"], precision="stated", **st["kw"])
        got = jnp.asarray(block(layer, p, x)).astype(jnp.float32)
        err = np.asarray(jnp.linalg.norm(got - want, axis=1)
                         / jnp.linalg.norm(want - x, axis=1))
        per_layer.append(float(np.median(err)))
        worst = max(worst, per_layer[-1])
        x = want
    print(f"perfbench-check layer_gap over {len(ids)} tokens, by layer: "
          f"{[float(f'{e:.3g}') for e in per_layer]}", file=sys.stderr)
    return worst


def program_block(config: dict):
    """The program's own layer, built as the server builds the model."""
    _reference, builder = _modules(config)
    return lambda layer, p, x: builder.program_layer(config, layer, p, x)


def lowered_block(config: dict):
    """The control's: the reference one step of precision down."""
    reference, _builder = _modules(config)

    def block(layer, p, x):
        st = reference.layer_statics(config, layer)
        return reference.layer_forward(p, x, st["freq"], precision="lowered", **st["kw"])

    return block


def compare(answers: list[list[tuple[int, float]]], asked: list[int], config: dict,
            seed: int, n_documents: int, k: int, block) -> dict:
    """``answers[q]`` = (row number as ``retrieve.row_id`` gives it, score)
    per served row of the own-text query of document ``asked[q]``;
    ``block`` is what ``layer_gap`` puts in the program's place."""
    words = config["document_words"]
    served = {-1 - r for a in answers for r, _ in a if r < 0}
    wanted_docs = sorted(set(asked) | served)
    vecs = embed(config, seed, [file_drop_docs.document(n, seed, words) for n in wanted_docs])
    documents = dict(zip(wanted_docs, vecs))
    rows = Rows(config, seed, documents, n_documents)
    q = np.stack([documents[n] for n in asked])
    best_s, best_i, got = rcheck.scan(q, rows, k, [[r for r, _ in a] for a in answers])
    gaps, shortfalls = [], []
    for qi, a in enumerate(answers):
        if np.isnan(got[qi]).any() or len(a) == 0:
            return dict(FAILED)
        gaps.append(float(np.max(np.abs(np.asarray([s for _, s in a], np.float32) - got[qi]))))
        shortfalls.append(max(0.0, float(best_s[qi, len(a) - 1] - got[qi].min())))
    worst = int(np.argmax(gaps))
    print(f"perfbench-check {len(wanted_docs)} documents embedded by the reference "
          f"({len(served - set(asked))} served beside the sample); worst query: document "
          f"{asked[worst]} ({words[asked[worst] % len(words)]} words): gap {gaps[worst]:.3g}, "
          f"shortfall {shortfalls[worst]:.3g}; served {answers[worst][:3]} reference scores "
          f"{got[worst][:3].tolist()} reference best {best_s[worst][:3].tolist()} "
          f"{best_i[worst][:3].tolist()}; median gap {float(np.median(gaps)):.3g}",
          file=sys.stderr)
    longest = max(asked, key=lambda n: (words[n % len(words)], -n))
    return {"score_gap": max(gaps), "rank_shortfall": max(shortfalls),
            "score_gap_median": float(np.median(gaps)),
            "layer_gap": layer_gap(config, seed, file_drop_docs.document(longest, seed, words),
                                   block)}


def check(ctx: dict) -> dict:
    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    records = ctx["records"]
    dropped = [r for r in records if r["status"] == 200]
    counted = [r for r in dropped if r.get("fresh_ms") is not None]
    grew = int(ctx["delta"].get("index.live_rows", 0))
    out = {
        "files_not_counted": len(records) - len(counted),
        "files_lost": max(0, len(dropped) - grew),
        "files_doubled": max(0, grew - len(dropped)),
    }
    asked = [r for r in records if isinstance(r["answer"], dict) and "own_query" in r["answer"]]
    k = int(traffic["k"])
    own_wrong = 0
    answers, numbers = [], []
    for rec in asked:
        rows = rec["answer"]["own_query"]
        n = rec["answer"]["passage"]
        ids = [rcheck.row_id(r[0]) for r in rows] if rows else []
        ok = bool(rows) and len(rows) == k and None not in ids and ids[0] == -1 - n \
            and ids.count(-1 - n) == 1
        own_wrong += not ok
        if ok:
            answers.append([(i, -float(r[1])) for i, r in zip(ids, rows)])
            numbers.append(n)
    out["own_text_not_first_once"] = own_wrong + (0 if asked else 1)
    if answers and own_wrong == 0:
        out.update(compare(answers, numbers, config, seed,
                           int(ctx["facts"]["next_passage"]), k, program_block(config)))
    else:
        out.update(FAILED)
    out["answers_compared"] = len(answers)
    limits = config[LIMITS]
    return {name: {"value": value, "limit": limits.get(name)} for name, value in out.items()}


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The lowered reference (``precision="lowered"``) in the program's place: it embeds
    ``control_documents`` documents, holds them beside the prefilled rows
    (bfloat16 rows and products, as ``retrieve.control_answers`` scans),
    answers the own text of the last ``check_sample`` of them, and is
    compared like the program."""
    n, k = int(traffic["check_sample"]), int(traffic["k"])
    n_documents = int(config["control_documents"])
    words = config["document_words"]
    low = embed(config, seed, [file_drop_docs.document(i, seed, words)
                               for i in range(n_documents)], "lowered")
    rows = Rows(config, seed, dict(enumerate(low)), n_documents, lowered=True)
    asked = list(range(n_documents - n, n_documents))
    best_s, best_i, _ = rcheck.scan(low[asked], rows, k, [[] for _ in asked])
    answers = [[(int(i) if i < rows.rows else -1 - (int(i) - rows.rows), float(s))
                for s, i in zip(best_s[qi], best_i[qi])] for qi in range(len(asked))]
    return compare(answers, asked, config, seed, n_documents, k, lowered_block(config))
