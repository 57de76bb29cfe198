"""Median over all files of the window of the time from rename to the first
moment the live index counts the file: the steadier companion of
``fresh_p95_ms`` (whose runs on one seed read up to 7% apart on the chip)."""


import stats


def read(ctx):
    return stats.rank([r["fresh_ms"] for r in ctx["records"]
                       if not r["failed"] and r.get("fresh_ms") is not None], 50)
