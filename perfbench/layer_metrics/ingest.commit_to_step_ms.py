"""Mean of one segment of a batch's way from the connector's read to the
index (ISSUE 38): that commit to the driver's ``engine.step(t)`` beginning: the driver's
wake, drain, snapshot write and connector bookkeeping; a step still running
for an earlier timestamp shows here.  Observed once per indexed engine timestamp and
connector (``FreshnessTracker.note_indexed``), as
``engine.read_to_indexed_ms`` is, so the seven ``ingest.*_ms`` add up to it;
``stage="ingest.commit_to_step"`` sum / count over the window, nothing where the program
has no such stage."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.ingest.commit_to_step.count", 0)
    return d["stage.ingest.commit_to_step.sum"] / n if n else None
