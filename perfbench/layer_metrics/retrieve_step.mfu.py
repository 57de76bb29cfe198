"""The whole retrieve step's share of the chip's peak FLOP/s over the window:
(encoder FLOPs of the real tokens of every answered query + 2 x rows x dim
for scoring it against every live row) / (window seconds x peak bf16
FLOP/s).  Counted from shapes by ``costs.py`` whatever implements the step;
a memory-bound scan on a 197 TFLOP/s chip reads well under 2%."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    e, facts, traffic = ctx["config"]["encoder"], ctx["facts"], ctx["traffic"]
    answered = sum(1 for r in ctx["records"] if not r["failed"])
    if not answered:
        return None
    words = (int(traffic["min_words"]) + int(traffic["max_words"])) / 2
    tokens = words + 2  # [CLS] and [SEP]
    flops = answered * (
        ctx["costs"].encoder_flops(tokens, tokens, hidden=e["hidden_size"],
                                   layers=e["num_hidden_layers"],
                                   ffn=e["intermediate_size"])
        + ctx["costs"].search_flops(1, facts["live_rows"], facts["dim"]))
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
