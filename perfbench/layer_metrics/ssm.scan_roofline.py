"""The state-space scan's share of its roofline, from the device trace: the
least time the chip could take for the scans of the forward's launches in the
traced slice, over the device time of the scan's operations in it
(``facts["ssm_scan_ops"]``: the kernel's name, ``pw_ssd_scan``).

The least time of one launch is, a layer, the larger of FLOPs over the peak
and bytes over the bandwidth (``costs_falcon_h1``: the chunked form's
products at the configuration's chunk; ``x``, ``dt``, ``B``, ``C`` read and
``y`` written once in float32) for the REAL tokens of the window's mean
launch (``pathway_ssm_tokens_total`` / ``pathway_ssm_launches_total``: what
the kernel spends on padding counts against it), times the layers, times the
launches in the slice.  It is bound by bytes, and the peak is the bfloat16
one where the scan multiplies in float32.  Nothing without a trace, the
counters, or an operation of that name among the slice's ten longest."""

import costs_falcon_h1
import trace_reduce


def read(ctx):
    trace, facts, d = ctx.get("trace"), ctx["facts"], ctx["delta"]
    names = tuple(facts.get("ssm_scan_ops", ()))
    launched = d.get("ssm.launches_total", 0)
    if not trace or ctx["peaks"] is None or not names or not launched:
        return None
    seconds = sum(s for op, s in trace["device_ops"] if op.startswith(names))
    _, launches = trace_reduce.program_time(trace, facts.get("encoder_programs", ()))
    if not seconds or not launches:
        return None
    sizes = facts["encoder"]
    tokens = d["ssm.tokens_total"] / launched
    least = sizes["layers"] * costs_falcon_h1.least_seconds(
        costs_falcon_h1.scan_flops(tokens, sizes),
        costs_falcon_h1.scan_least_bytes(tokens, sizes), ctx["peaks"])
    return 100.0 * least * launches / seconds
