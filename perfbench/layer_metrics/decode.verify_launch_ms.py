"""Mean host-clock time of one ``verify`` launch of the decode engine
(``pathway_decode_launch_ms{kind="verify"}`` sum / count over the window): the
multi-token launch that ingests the tail of every prompt that adopted a
resident prefix block, 16 tokens a tick, and advances the decoding rows of the
same tick by one token.  A time seen from outside, not a kernel time.  Nothing
when none ran."""


def read(ctx):
    d = ctx["delta"]
    n = d.get('om.pathway_decode_launch_ms_count{kind="verify"}', 0)
    return d['om.pathway_decode_launch_ms_sum{kind="verify"}'] / n if n else None
