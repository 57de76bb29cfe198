"""Sequences advanced per decode-step launch: tokens generated over the
window / ``decode_step`` launches (``pathway_decode_tokens_total`` less one
prefill token per answer, over ``pathway_decode_launch_ms_count``)."""


def read(ctx):
    d = ctx["delta"]
    steps = d.get('om.pathway_decode_launch_ms_count{kind="decode_step"}', 0)
    if not steps:
        return None
    answered = sum(1 for r in ctx["records"] if not r["failed"])
    return max(0.0, d.get("om.pathway_decode_tokens_total", 0) - answered) / steps
