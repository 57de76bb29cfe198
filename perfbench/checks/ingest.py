"""What decides ``correct`` in a live-ingest cell.

Exact comparisons (limit 0): every file dropped in the window was counted by
the live index within the drain limit, the index grew by exactly as many rows
as files were dropped (none lost, none doubled), and each file of a sample
drawn from the seed, asked for by its own text once the window had closed,
came first, once, with its own text.  The sample's answers are then compared
with the plain reference exactly as a retrieve cell's are (``retrieve.py``:
``score_gap``, ``rank_shortfall``), over the prefilled rows, the passages of
set-up and every file dropped since, embedded again in float32.
"""

from __future__ import annotations

import seeded
import textgen
from checks import retrieve as rcheck

#: the group of the configuration file that holds this check's limits
LIMITS = "limits_ingest"


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
    answers, texts = [], []
    for rec in asked:
        rows = rec["answer"]["own_query"]
        n = rec["answer"]["passage"]
        ids = [rcheck.row_id(r[0]) for r in rows] if rows else []
        ok = bool(rows) and len(rows) == k and None not in ids and ids[0] == -1 - n \
            and ids.count(-1 - n) == 1
        own_wrong += not ok
        if ok:
            answers.append([(i, -float(r[1])) for i, r in zip(ids, rows)])
            texts.append(textgen.passage(n, seed))
    out["own_text_not_first_once"] = own_wrong + (0 if asked else 1)
    if answers and own_wrong == 0:
        n_passages = int(ctx["facts"]["next_passage"])
        out.update(rcheck.compare(answers, texts, config, seed,
                                  seeded.encoder_params(config, seed), n_passages, k))
    else:
        out.update({"score_gap": 1e30, "rank_shortfall": 1e30})
    out["answers_compared"] = len(answers)
    limits = config[LIMITS]
    return {name: {"value": value, "limit": limits.get(name)} for name, value in out.items()}


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The control is the retrieve check's: the lowered reference answers the
    own-text queries of ``check_sample`` passages."""
    n, k = int(traffic["check_sample"]), int(traffic["k"])
    n_passages = int(config["ingested_passages"])
    texts = [textgen.passage(i, seed) for i in range(n_passages - n, n_passages)]
    params = seeded.encoder_params(config, seed)
    answers = rcheck.control_answers(texts, config, seed, params, n_passages, k)
    return rcheck.compare(answers, texts, config, seed, params, n_passages, k)
