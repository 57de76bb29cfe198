"""The decode step's share of the HBM roofline, from the device trace: the
least bytes one step must read (``costs.decode_step_bytes``: the decoder's
matrices once, plus the cached keys and values of the live rows) over the
chip's peak bandwidth, divided by the device time of one step launch in the
traced slice.  A step is a launch of the single-token or of the multi-token
step program (``facts["decoder_programs"]``: ``decode_step``, ``verify``); the
second reads the same matrices and the same cache once for up to 16 tokens a
row, so its least bytes are the same.  Bound by memory: a step of 8 rows
multiplies 124M parameters by 8 to 128 vectors, 2 to 32 GFLOP (0.01 to 0.16 ms
of the MXU) against 0.35 GB (0.43 ms of HBM).

Live positions a step: rows a step (``pathway_decode_batch_rows`` of both
kinds over the window) x (mean prompt of the answers that came, as
``checks/answers.py`` assembles it from the served contexts, once a run, + half
the answer).  Nothing without a trace, a step launch in it, or the counters."""

import trace_reduce
from checks import answers

KINDS = ("decode_step", "verify")


def read(ctx):
    trace, facts, d = ctx.get("trace"), ctx.get("facts", {}), ctx["delta"]
    programs = facts.get("decoder_programs", {})
    if not trace or ctx["peaks"] is None:
        return None
    seconds, launches = trace_reduce.program_time(
        trace, [n for k in KINDS for n in programs.get(k, ())])
    steps = sum(d.get(f'om.pathway_decode_batch_rows_count{{kind="{k}"}}', 0) for k in KINDS)
    answered = any(not r["failed"] for r in ctx["records"])
    if not seconds or not launches or not steps or not answered:
        return None
    rows = sum(d.get(f'om.pathway_decode_batch_rows_sum{{kind="{k}"}}', 0) for k in KINDS) / steps
    prompts = answers.prompt_lengths(ctx)
    context = sum(prompts) / len(prompts) + int(ctx["traffic"]["max_new_tokens"]) / 2
    sizes = facts["decoder"]
    least = ctx["costs"].decode_step_bytes(sizes["matrix_params"],
                                           sizes["kv_values_per_token"], rows * context)
    return 100.0 * (least / ctx["peaks"]["hbm_bytes_per_s"]) / (seconds / launches)
