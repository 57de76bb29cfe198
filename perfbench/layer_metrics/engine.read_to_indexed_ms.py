"""Mean time from the connector's read of a file to the moment the index
node applied the engine timestamp that carries it, per source and engine
timestamp (``FreshnessTracker.note_indexed``: the earliest read of the
timestamp's rows; ``stage="ingest.read_to_indexed"`` sum / count over the
window).  ``ingest.fresh_p50_ms`` less this is the wait from rename to
read: the refresh sleep and the scan's own backlog."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.ingest.read_to_indexed.count", 0)
    return d["stage.ingest.read_to_indexed.sum"] / n if n else None
