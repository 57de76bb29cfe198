"""Device time of one launch of the sentence encoder's forward in the traced
slice: seconds over launches of the programs ``jit_pw_encoder_forward`` and
``jit_pw_encoder_forward_ragged`` (``trace_reduce.reduce``: ``programs`` /
``launches``).  Nothing when the trace holds no program of that name."""

PREFIX = "jit_pw_encoder_forward"


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    seconds = sum(s for name, s in trace["programs"].items() if name.startswith(PREFIX))
    launches = sum(n for name, n in trace["launches"].items() if name.startswith(PREFIX))
    return 1e3 * seconds / launches if launches else None
