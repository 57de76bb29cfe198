"""The whole ingest step's share of the chip's peak FLOP/s over the window:
forward FLOPs of the real tokens of every file made queryable
(``costs_kimi_linear.forward_flops``: the parameters a token is multiplied by
on this chip, the routed experts held here of its eight and the shared one a
routed layer among them, MLA's causal pairs, the KDA layers' scans; a
document of n words is n + 2 tokens) / (window seconds x peak bf16 FLOP/s).
Nothing off the chip, or where the deployment states no such encoder."""

import costs_kimi_linear


def read(ctx):
    sizes, words = ctx["facts"].get("encoder"), ctx["facts"].get("document_words")
    if ctx["peaks"] is None or not sizes or "kda_heads" not in sizes or not words:
        return None
    flops = sum(costs_kimi_linear.forward_flops(
        words[r["answer"]["passage"] % len(words)] + 2, sizes)
        for r in ctx["records"] if not r["failed"])
    if not flops:
        return None
    return 100.0 * flops / (ctx["seconds"] * ctx["peaks"]["bf16_flops_per_s"])
