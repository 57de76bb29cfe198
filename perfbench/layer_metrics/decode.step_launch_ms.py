"""Mean host-clock time of one ``decode_step`` launch of the decode engine
(``pathway_decode_launch_ms{kind="decode_step"}`` sum / count over the window):
the time of a launch seen from outside, not a kernel time."""


def read(ctx):
    d = ctx["delta"]
    n = d.get('om.pathway_decode_launch_ms_count{kind="decode_step"}', 0)
    return d['om.pathway_decode_launch_ms_sum{kind="decode_step"}'] / n if n else None
