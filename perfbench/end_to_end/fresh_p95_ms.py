"""95th percentile over ALL files dropped in the window, from the file's
rename into the watched directory to the first moment the live index counts
it (polled every ``poll_ms``; files are matched to counts in rename order),
nearest rank.  A file never counted within the drain limit counts at it."""


import stats


def read(ctx):
    return stats.tail(ctx["records"], "fresh_ms", 95, float(ctx["traffic"]["drain_s"]) * 1e3)
