"""Files a scan hands the engine at once: rows the live index gained over
the window (``index.live_rows``) over the scans that emitted something
(``stage.connector.scan.count``).  The files of one scan enter under one
engine timestamp and become queryable together, so each waits for the
whole batch: this times the engine's cost of a document is the queueing
that one refresh adds."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.connector.scan.count", 0)
    return d.get("index.live_rows", 0) / n if n else None
