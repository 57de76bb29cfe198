"""Mean of one segment of a batch's way from the connector's read to the
index (ISSUE 38): the first row of the batch pushed (``ConnectorSubject._push``) to the
connector's ``commit()`` of it: in ``pw.io.fs``, the pass's files read after
the first.  Observed once per indexed engine timestamp and
connector (``FreshnessTracker.note_indexed``), as
``engine.read_to_indexed_ms`` is, so the seven ``ingest.*_ms`` add up to it;
``stage="ingest.read_to_commit"`` sum / count over the window, nothing where the program
has no such stage."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.ingest.read_to_commit.count", 0)
    return d["stage.ingest.read_to_commit.sum"] / n if n else None
