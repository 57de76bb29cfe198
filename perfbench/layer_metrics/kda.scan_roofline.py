"""The delta rule's scan's share of its roofline, from the device trace: the
least time the chip could take for the scans of the forward's launches in the
traced slice, over the device time of the scan's operations in it
(``facts["kda_scan_ops"]``: the kernel's name, ``pw_kda_scan``).

The least time of one launch is, a KDA layer, the larger of FLOPs over the
peak and bytes over the bandwidth (``costs_kimi_linear``: the chunked form's
products, each counted once though the kernel takes them at float32
precision; ``q``, ``k``, ``v``, the decay and ``beta`` read and ``o`` written
once in float32) at the token slots of the window's mean launch
(``pathway_mla_bucket_tokens_total`` / ``pathway_mla_launches_total``: the
scan walks a launch's whole bucket, padding among it, whose share
``mla.padding_share`` reads; a bucket that is no whole number of chunks
would be walked to its last chunk's end, so the bucket never counts more
than the kernel visits), times the KDA layers, times the launches in the
slice.  Both counts are what the kernel cannot do without, so the share
cannot pass 100%.  Nothing without a trace, the counters, or an operation of
that name among the slice's ten longest."""

import costs_kimi_linear
import trace_reduce


def read(ctx):
    trace, facts, d = ctx.get("trace"), ctx["facts"], ctx["delta"]
    names = tuple(facts.get("kda_scan_ops", ()))
    launched = d.get("mla.launches_total", 0)
    if not trace or ctx["peaks"] is None or not names or not launched:
        return None
    seconds = sum(s for op, s in trace["device_ops"] if op.startswith(names))
    _, launches = trace_reduce.program_time(trace, facts.get("encoder_programs", ()))
    if not seconds or not launches:
        return None
    sizes = facts["encoder"]
    slots = d["mla.bucket_tokens_total"] / launched
    layers = sum(1 for kind in sizes["layer_types"] if kind == "kda")
    least = layers * costs_kimi_linear.least_seconds(
        costs_kimi_linear.scan_flops(slots, sizes),
        costs_kimi_linear.scan_least_bytes(slots, sizes), ctx["peaks"])
    return 100.0 * least * launches / seconds
