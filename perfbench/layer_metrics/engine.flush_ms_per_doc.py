"""Engine time per document: the sum of every operator flush of the window
(``internals/engine.py`` ``_flush_node`` under ``flush:<node>``,
``stage="engine.flush"``) over the rows the live index gained.  The index
node's flush evaluates the index data expression row by row
(``index.doc_data``): for a vector index that is the embedder, so this
holds each document's wait for its device tick and the tick itself, then
``index.apply``.  The engine thread's whole cost of a document."""


def read(ctx):
    d = ctx["delta"]
    docs = d.get("index.live_rows", 0)
    if not docs or not d.get("stage.engine.flush.count", 0):
        return None
    return d["stage.engine.flush.sum"] / docs
