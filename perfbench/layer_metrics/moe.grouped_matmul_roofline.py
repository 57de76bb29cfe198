"""The grouped matrix product's share of its roofline, from the device trace:
the least time the chip could take for the products of the forward's launches
in the traced slice, over the device time of the grouped product's
operations in it (``facts["grouped_matmul_ops"]``: the names a trace shows,
``ragged-dot*`` for ``jax.lax.ragged_dot``).

The least time of one launch is the larger of FLOPs over the peak and bytes
over the bandwidth (``costs_laguna``: the routed pairs through their expert's
three matrices; the weights of the experts that got a token, once, and the
pairs' rows), from the launch's own counters (``pathway_moe_*``), taken as
the window's mean launch and multiplied by the launches in the slice.  A lone
document is bound by bytes: 96 tokens touch 244 of 256 experts and multiply
each by three rows.  Nothing without a trace, the counters, or an operation
of that name among the slice's ten longest."""

import costs_laguna
import trace_reduce


def read(ctx):
    trace, facts, d = ctx.get("trace"), ctx["facts"], ctx["delta"]
    names = tuple(facts.get("grouped_matmul_ops", ()))
    launched = d.get("moe.launches_total", 0)
    if not trace or ctx["peaks"] is None or not names or not launched:
        return None
    seconds = sum(s for op, s in trace["device_ops"] if op.startswith(names))
    _, launches = trace_reduce.program_time(trace, facts.get("encoder_programs", ()))
    if not seconds or not launches:
        return None
    sizes = facts["encoder"]
    pairs = d["moe.routed_tokens_total"] / launched
    touched = d["moe.experts_touched_total"] / launched
    least = costs_laguna.least_seconds(
        costs_laguna.grouped_matmul_flops(pairs, sizes),
        costs_laguna.grouped_matmul_least_bytes(touched, pairs, sizes), ctx["peaks"])
    return 100.0 * least * launches / seconds
