"""Mean of one segment of a batch's way from the connector's read to the
index (ISSUE 38): the step beginning to the index node's ``index.doc_data`` beginning:
every regular operator of the step, since the index node is ``late``
(the step's ``flush:<node>`` spans under the batch's trace id split it).  Observed once per indexed engine timestamp and
connector (``FreshnessTracker.note_indexed``), as
``engine.read_to_indexed_ms`` is, so the seven ``ingest.*_ms`` add up to it;
``stage="ingest.step_to_index"`` sum / count over the window, nothing where the program
has no such stage."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.ingest.step_to_index.count", 0)
    return d["stage.ingest.step_to_index.sum"] / n if n else None
