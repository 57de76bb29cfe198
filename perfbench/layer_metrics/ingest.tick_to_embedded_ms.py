"""Mean of one segment of a batch's way from the connector's read to the
index (ISSUE 38): the last tick ending to ``index.doc_data`` ending: futures, the
persistent loop, back to the engine thread.  Observed once per indexed engine timestamp and
connector (``FreshnessTracker.note_indexed``), as
``engine.read_to_indexed_ms`` is, so the seven ``ingest.*_ms`` add up to it;
``stage="ingest.tick_to_embedded"`` sum / count over the window, nothing where the program
has no such stage."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.ingest.tick_to_embedded.count", 0)
    return d["stage.ingest.tick_to_embedded.sum"] / n if n else None
