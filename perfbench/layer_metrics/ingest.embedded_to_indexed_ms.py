"""Mean of one segment of a batch's way from the connector's read to the
index (ISSUE 38): ``index.doc_data`` ending to ``FreshnessTracker.note_indexed``: the
apply and the snapshot bookkeeping.  Observed once per indexed engine timestamp and
connector (``FreshnessTracker.note_indexed``), as
``engine.read_to_indexed_ms`` is, so the seven ``ingest.*_ms`` add up to it;
``stage="ingest.embedded_to_indexed"`` sum / count over the window, nothing where the program
has no such stage."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.ingest.embedded_to_indexed.count", 0)
    return d["stage.ingest.embedded_to_indexed.sum"] / n if n else None
