"""Imbalance of the routing: the fullest expert's pairs over the mean pairs
an expert, a routed layer of a launch, summed over the window: the sum of
each layer-launch's fullest expert (``pathway_moe_max_expert_tokens_sum``)
x experts / ``pathway_moe_routed_tokens_total``.  1.0 is a perfectly even
router."""


def read(ctx):
    d = ctx["delta"]
    routed = d.get("moe.routed_tokens_total", 0)
    if not routed:
        return None
    return d["moe.max_expert_tokens_sum"] * ctx["facts"]["encoder"]["experts"] / routed
