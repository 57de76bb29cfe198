"""Routed (token, expert) pairs over experts that got a token, summed over
the routed layers of every launch of the window (``pathway_moe_routed_tokens_total``
/ ``pathway_moe_experts_touched_total``): how full an expert's tile of the
grouped product is.  A lone document of 96 tokens reads 3.1, one of 2,048
reads 64."""


def read(ctx):
    d = ctx["delta"]
    touched = d.get("moe.experts_touched_total", 0)
    return d["moe.routed_tokens_total"] / touched if touched else None
