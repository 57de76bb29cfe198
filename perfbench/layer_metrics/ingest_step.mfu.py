"""The whole ingest step's share of the chip's peak FLOP/s over the window:
encoder forward FLOPs of the real tokens of every file made queryable
(``costs.encoder_flops``; a passage of n words is n + 2 tokens) / (window
seconds x peak bf16 FLOP/s).  A document a tick reads a few thousandths of
a percent; the number is here to bound a later claim."""


def read(ctx):
    if ctx["peaks"] is None:
        return None
    import textgen

    e = ctx["config"]["encoder"]
    flops = 0
    for r in ctx["records"]:
        if r["failed"]:
            continue
        tokens = textgen.PASSAGE_WORDS[r["answer"]["passage"] % len(textgen.PASSAGE_WORDS)] + 2
        flops += ctx["costs"].encoder_flops(tokens, tokens, hidden=e["hidden_size"],
                                            layers=e["num_hidden_layers"],
                                            ffn=e["intermediate_size"])
    if not flops:
        return None
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
