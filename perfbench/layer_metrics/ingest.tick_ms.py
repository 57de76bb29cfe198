"""Mean of one segment of a batch's way from the connector's read to the
index (ISSUE 38): the first tick that runs the index flush's embed calls
beginning to the last one ending (before the futures of its calls resolve).  Observed once per indexed engine timestamp and
connector (``FreshnessTracker.note_indexed``), as
``engine.read_to_indexed_ms`` is, so the seven ``ingest.*_ms`` add up to it;
``stage="ingest.tick"`` sum / count over the window, nothing where the program
has no such stage."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.ingest.tick.count", 0)
    return d["stage.ingest.tick.sum"] / n if n else None
