"""Mean host-clock time of one ``prefill`` launch of the decode engine
(``pathway_decode_launch_ms{kind="prefill"}`` sum / count over the window):
the time of a launch seen from outside, not a kernel time."""


def read(ctx):
    d = ctx["delta"]
    n = d.get('om.pathway_decode_launch_ms_count{kind="prefill"}', 0)
    return d['om.pathway_decode_launch_ms_sum{kind="prefill"}'] / n if n else None
