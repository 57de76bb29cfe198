"""95th percentile over ALL requests of the window, from the time a request
was due to the last byte of its response (nearest rank).  A request that
failed, was refused or answered degraded has no latency of its own and
counts at the generator's drain limit, so failures lengthen the tail."""


import stats


def read(ctx):
    return stats.tail(ctx["records"], "latency_ms", 95, float(ctx["traffic"]["drain_s"]) * 1e3)
