"""Mean of one segment of a batch's way from the connector's read to the
index (ISSUE 38): ``index.doc_data`` beginning to the first tick that runs the flush's
embed calls beginning: row evaluation, the calls' way to
``DeviceTickRuntime`` and the admission window (the whole of
``index.doc_data`` for an embedder that rides no tick).  Observed once per indexed engine timestamp and
connector (``FreshnessTracker.note_indexed``), as
``engine.read_to_indexed_ms`` is, so the seven ``ingest.*_ms`` add up to it;
``stage="ingest.index_to_tick"`` sum / count over the window, nothing where the program
has no such stage."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.ingest.index_to_tick.count", 0)
    return d["stage.ingest.index_to_tick.sum"] / n if n else None
