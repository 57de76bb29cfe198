"""90th percentile over ALL answers of the window of (time of ``done`` less
time of the first token) / (tokens - 1), nearest rank.  A failed answer
counts at the generator's drain limit spread over the tokens asked for."""


import stats


def read(ctx):
    asked = max(1, int(ctx["traffic"]["max_new_tokens"]) - 1)
    return stats.tail(ctx["records"], "tpot_ms", 90,
                      float(ctx["traffic"]["drain_s"]) * 1e3 / asked)
