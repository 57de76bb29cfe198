"""The whole ingest step's share of the chip's peak FLOP/s over the window:
forward FLOPs of the real tokens of every file made queryable
(``costs_lfm2.forward_flops``: the parameters a token is multiplied by, the
conv layers' projections and taps, four routed experts a sparse layer among
them, and the full layers' causal pairs; a document of n words is n + 2
tokens) / (window seconds x peak bf16 FLOP/s).  Nothing off the chip, or where
the deployment states no such encoder."""

import costs_lfm2


def read(ctx):
    sizes, words = ctx["facts"].get("encoder"), ctx["facts"].get("document_words")
    if ctx["peaks"] is None or not sizes or "conv_taps" not in sizes or not words:
        return None
    flops = sum(costs_lfm2.forward_flops(words[r["answer"]["passage"] % len(words)] + 2, sizes)
                for r in ctx["records"] if not r["failed"])
    if not flops:
        return None
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
