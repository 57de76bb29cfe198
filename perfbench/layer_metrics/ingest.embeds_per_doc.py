"""Encoder rows per file made queryable in the window (1.0 on a graph with
one server; PR 21 saw 3.0 with two servers on the graph)."""


def read(ctx):
    d = ctx["delta"]
    made = d.get("index.live_rows", 0)
    return d.get("ingest.docs_total", 0) / made if made else None
