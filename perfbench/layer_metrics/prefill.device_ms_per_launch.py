"""Device time of one prefill launch (one or more prompts packed) in the
traced slice: seconds over launches of the decode session's prefill programs
as the deployment names them (``facts["decoder_programs"]["prefill"]``).
Nothing when the trace holds none of them."""

import trace_reduce

KINDS = ('prefill',)


def read(ctx):
    trace = ctx.get("trace")
    programs = ctx.get("facts", {}).get("decoder_programs", {})
    if not trace:
        return None
    seconds, launches = trace_reduce.program_time(
        trace, [n for k in KINDS for n in programs.get(k, ())])
    return 1e3 * seconds / launches if launches else None
