"""90th percentile over ALL answers of the window, from the time a question
was due to its first ``token`` event (nearest rank).  A failed, refused or
degraded answer counts at the generator's drain limit.  p90, not p95: at a
few answers a second a window holds 100-200 answers."""


import stats


def read(ctx):
    return stats.tail(ctx["records"], "ttft_ms", 90, float(ctx["traffic"]["drain_s"]) * 1e3)
