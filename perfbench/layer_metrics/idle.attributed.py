"""Share of the traced slice's idle device time that the trace can put down
to a place in the program: of the seconds in ``idle_gaps``
(``trace_reduce.reduce``: each gap under the host span that covers most of
it), those whose label holds one of the program's spans (``pw.``) or one of
its named threads (``[pw-``).  At most 100 by construction.  Nothing when
no gap carries either: a program without spans in the trace is not read as
0% attributed."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    total = sum(s for _label, s in trace["idle_gaps"])
    ours = sum(s for label, s in trace["idle_gaps"] if "pw." in label or "[pw-" in label)
    return 100.0 * ours / total if total and ours else None
