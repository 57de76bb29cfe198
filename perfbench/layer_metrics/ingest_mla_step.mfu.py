"""The whole ingest step's share of the chip's peak FLOP/s over the window:
forward FLOPs of the real tokens of every file made queryable
(``costs_joyai.forward_flops``: the parameters a token is multiplied by, eight
routed experts and the shared one a sparse layer among them, and latent
attention's causal pairs in the prefill form, 192 + 128 multiply-adds a pair
and head; a document of n words is n + 2 tokens) / (window seconds x peak bf16
FLOP/s).  Nothing off the chip, or where the deployment states no such
encoder."""

import costs_joyai


def read(ctx):
    sizes, words = ctx["facts"].get("encoder"), ctx["facts"].get("document_words")
    if ctx["peaks"] is None or not sizes or "kv_rank" not in sizes or not words:
        return None
    flops = sum(costs_joyai.forward_flops(words[r["answer"]["passage"] % len(words)] + 2, sizes)
                for r in ctx["records"] if not r["failed"])
    if not flops:
        return None
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
