"""Device time of one launch of the language-model embedder's forward in the
traced slice: seconds over launches of the programs the deployment names
(``facts["encoder_programs"]``: ``jit_pw_moe_embedder_forward`` and its
``_ragged`` twin).  Nothing when the program names none, or the trace holds
no launch of them."""

import trace_reduce


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    seconds, launches = trace_reduce.program_time(
        trace, ctx["facts"].get("encoder_programs", ()))
    return 1e3 * seconds / launches if launches else None
