"""Device time of one launch of the decode session that advances rows, in the
traced slice: seconds over launches of its single-token and multi-token step
programs as the deployment names them (``facts["decoder_programs"]``,
``decode_step`` and ``verify``; ``trace_reduce.reduce``: ``programs`` /
``launches``).  Nothing when the trace holds none of them."""

import trace_reduce

KINDS = ('decode_step', 'verify')


def read(ctx):
    trace = ctx.get("trace")
    programs = ctx.get("facts", {}).get("decoder_programs", {})
    if not trace:
        return None
    seconds, launches = trace_reduce.program_time(
        trace, [n for k in KINDS for n in programs.get(k, ())])
    return 1e3 * seconds / launches if launches else None
