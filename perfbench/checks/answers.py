"""What decides ``correct`` in a streamed-answers cell.

Every answer of the window is held to exact comparisons (limit 0): it came,
neither it nor its context line is degraded, it streamed the tokens asked
for, ``done.response`` is the joined token pieces, every context passage is a
passage of the corpus.  A sample of answers drawn from the seed (the longest
prompt in it) is compared with the plain references:

``context_shortfall``  per sampled answer the widest gap by which the best
                       reference score of a context passage's rows lies
                       below the reference's k-th best score (the float32
                       encoder and scan of ``retrieve.py``; a text names a
                       class of rows), and of these the mean: a passage that
                       does not belong reads a tenth in its answer and a
                       hundredth in the mean, while the stated precision's
                       near-ties read ten-thousandths in one answer of a
                       few.  The widest of them swings with the one nearest
                       tie of ~100 (PERF.md 6) and is reported without a
                       limit as ``context_shortfall_widest``
``logit_gap``          the widest gap by which a served token's logit lies
                       below the reference's best at its position, in the
                       decoder's dense float32 forward run once over the
                       prompt and its served tokens (prefill, prefix
                       adoption and every paged decode step)

The decoder's reference is the file of ``checks/`` the configuration names
(``decoder.reference``: ``prompt_ids(prompt, decoder, max_new)`` and
``logits(params, ids, decoder, lowered=False)``), its weights come from the
file of ``decoders/`` it names (``decoder.builder``: ``params(config, seed)``).

``control`` reads both for the references one step of precision down.
"""

from __future__ import annotations

import importlib
import sys
import zlib

import numpy as np

import seeded
import textgen
from checks import minilm
from checks import retrieve as rcheck

#: the group of the configuration file that holds this check's limits
LIMITS = "limits"
NO_INFO = "No information found."


def prompt_of(question: str, docs: list[str]) -> str:
    """The answerer's default prompt (``prompts.prompt_qa_geometric_rag``)."""
    docs_str = "\n".join(f"Source {i + 1}: {d}" for i, d in enumerate(docs))
    return ("Use the below articles to answer the subsequent question. "
            f"If you cannot answer, reply: {NO_INFO}\n\n{docs_str}\n"
            f"Question: {question}\nAnswer:")


def decoder_of(config: dict):
    """(the decoder's plain reference, the maker of its weights)."""
    d = config["decoder"]
    return (importlib.import_module("checks." + d["reference"]),
            importlib.import_module("decoders." + d["builder"]))


def text_classes(config: dict, seed: int) -> dict[int, int]:
    """CRC of a passage text -> its number among the payload texts (ingested
    passage ``i`` has the text of payload ``i``)."""
    n = max(int(config["payload_texts"]), int(config["ingested_passages"]))
    return {zlib.crc32(textgen.passage(j, seed).encode("utf-8")): j for j in range(n)}


def class_rows(j: int, config: dict) -> list[int]:
    """Row numbers (as ``retrieve.row_id`` numbers them) that carry text j."""
    rows = list(range(j, int(config["rows"]), int(config["payload_texts"])))
    if j < int(config["ingested_passages"]):
        rows.append(-1 - j)
    return rows


def structural(records: list[dict], classes: dict[int, int], traffic: dict) -> dict:
    missing = degraded = wrong = 0
    for rec in records:
        if rec["failed"]:
            if rec["answer"] == "degraded":
                degraded += 1
            elif rec["status"] in (0, 200):
                missing += 1
            continue
        a = rec["answer"]
        ok = (a["joined"] and a["full"] and len(a["context"]) == int(traffic["k"])
              and all(c in classes for c in a["context"]))
        wrong += not ok
    return {"answers_missing": missing, "answers_degraded": degraded,
            "answers_malformed": wrong}


def context_shortfalls(questions: list[str], contexts: list[list[int]], config: dict,
                       seed: int, enc_params, k: int) -> list[float]:
    """Per question the widest gap of its context below the reference's
    k-th best score."""
    rows = rcheck.Rows(config, seed, enc_params, int(config["ingested_passages"]))
    q = minilm.encode(enc_params, questions, **rcheck.encoder_kwargs(config))
    wanted, spans = [], []
    for ctx in contexts:
        flat, span = [], []
        for j in ctx:
            members = class_rows(j, config)
            span.append((len(flat), len(flat) + len(members)))
            flat.extend(members)
        wanted.append(flat)
        spans.append(span)
    best_s, _best_i, got = rcheck.scan(q, rows, k, wanted)
    return [max([0.0] + [float(best_s[qi, k - 1] - np.nanmax(got[qi][lo:hi])) for lo, hi in span])
            for qi, span in enumerate(spans)]


def logit_gaps(prompts: list[list[int]], tokens: list[list[int]], config: dict,
               dec_params, control: bool = False) -> list[float]:
    """Per answer, the widest gap below the reference's best logit: of the
    served tokens, or (control) of the tokens the lowered reference puts
    first at the same positions."""
    out, d = [], config["decoder"]
    reference, _maker = decoder_of(config)
    for ids, toks in zip(prompts, tokens):
        seq = ids + toks[:-1]
        ref = np.asarray(reference.logits(dec_params, seq, d))[len(ids) - 1:]
        if control:
            low = np.asarray(reference.logits(dec_params, seq, d, lowered=True))[len(ids) - 1:]
            toks = low.argmax(axis=1).tolist()
        out.append(float(np.max(ref.max(axis=1) - ref[np.arange(len(toks)), toks])))
    return out


def prompt_ids_of(ctx: dict) -> dict:
    """Request number -> the ids of its prompt as the answerer assembles it
    (the default template over the context that was served, cut from the
    left to leave room for the answer), for every well-formed answer.
    Assembled once a run and kept in ``ctx["prompt_ids"]``: the cost readers
    of a traced run and the check read the same."""
    if "prompt_ids" not in ctx:
        config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
        d, records = config["decoder"], ctx["records"]
        texts = textgen.query_texts(len(records), seed, int(traffic["min_words"]),
                                    int(traffic["max_words"]))
        classes = text_classes(config, seed)
        reference, _maker = decoder_of(config)
        max_new = int(traffic["max_new_tokens"])
        out = {}
        for r in records:
            if r["failed"] or not all(c in classes for c in r["answer"]["context"]):
                continue
            docs = [textgen.passage(classes[c], seed) for c in r["answer"]["context"]]
            out[r["i"]] = reference.prompt_ids(prompt_of(texts[r["i"]], docs), d, max_new)
        ctx["prompt_ids"] = out
    return ctx["prompt_ids"]


def prompt_lengths(ctx: dict) -> list[int]:
    """Prompt tokens of every answer that came (the cost readers' context)."""
    return [len(ids) for ids in prompt_ids_of(ctx).values()]


def check(ctx: dict) -> dict:
    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    records = ctx["records"]
    texts = textgen.query_texts(len(records), seed, int(traffic["min_words"]),
                                int(traffic["max_words"]))
    classes = text_classes(config, seed)
    out = structural(records, classes, traffic)
    by_i = {r["i"]: r for r in records}
    _reference, maker = decoder_of(config)
    ids_of = prompt_ids_of(ctx)

    if out["answers_malformed"] == 0:
        picks = rcheck.pick_sample(records, lambda i: len(ids_of[i]),
                                   int(traffic["check_sample"]), seed)
    else:
        picks = []
    if picks:
        contexts = [[classes[c] for c in by_i[i]["answer"]["context"]] for i in picks]
        short = context_shortfalls(
            [texts[i] for i in picks], contexts, config, seed,
            seeded.encoder_params(config, seed), int(traffic["k"]))
        out["context_shortfall"] = float(np.mean(short))
        out["context_shortfall_widest"] = max(short)
        prompts = [ids_of[i] for i in picks]
        gaps = logit_gaps(prompts, [by_i[i]["answer"]["tokens"] for i in picks], config,
                          maker.params(config, seed))
        worst = int(np.argmax(gaps))
        print(f"perfbench-check answers: prompt tokens {sorted(len(p) for p in prompts)}; "
              f"worst logit gap {gaps[worst]:.3g} in answer {picks[worst]}; median "
              f"{float(np.median(gaps)):.3g}", file=sys.stderr)
        out["logit_gap"] = max(gaps)
    else:
        out.update({"context_shortfall": 1e30, "logit_gap": 1e30})
    out["answers_compared"] = len(picks)
    limits = config[LIMITS]
    return {name: {"value": value, "limit": limits.get(name)} for name, value in out.items()}


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The control's readings for one seed: the lowered references answer
    ``check_sample`` of the cell's questions."""
    n, k = int(traffic["check_sample"]), int(traffic["k"])
    d = config["decoder"]
    texts = textgen.query_texts(n, seed, int(traffic["min_words"]), int(traffic["max_words"]))
    enc = seeded.encoder_params(config, seed)
    n_passages = int(config["ingested_passages"])
    served = rcheck.control_answers(texts, config, seed, enc, n_passages, k)
    n_texts = int(config["payload_texts"])
    contexts = [[(-1 - r) if r < 0 else r % n_texts for r, _s in a] for a in served]
    short = context_shortfalls(texts, contexts, config, seed, enc, k)
    reference, maker = decoder_of(config)
    dec = maker.params(config, seed)
    max_new = int(traffic["max_new_tokens"])
    prompts = [reference.prompt_ids(prompt_of(t, [textgen.passage(j, seed) for j in c]),
                                    d, max_new)
               for t, c in zip(texts, contexts)]
    # as many positions as an answer has tokens: the prompt's last max_new,
    # where the lowered reference's first choice is read against the
    # float32 reference (the control need not decode)
    gaps = []
    for ids in prompts:
        ref = np.asarray(reference.logits(dec, ids, d))[-max_new:]
        low = np.asarray(reference.logits(dec, ids, d, lowered=True))[-max_new:]
        picked = low.argmax(axis=1)
        gaps.append(float(np.max(ref.max(axis=1) - ref[np.arange(len(picked)), picked])))
    return {"context_shortfall": float(np.mean(short)), "context_shortfall_widest": max(short),
            "logit_gap": max(gaps), "logit_gap_min_over_answers": min(gaps)}
