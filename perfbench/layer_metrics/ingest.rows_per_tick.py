"""Encoder rows per device tick while files are ingested: rows through the
encoder (``ingest_stats()["docs_total"]``) over ``ticks_total`` of the tick
runtime, difference over the window.  1.0 means one document a tick."""


def read(ctx):
    d = ctx["delta"]
    ticks = d.get("ticks_total", 0)
    return d.get("ingest.docs_total", 0) / ticks if ticks else None
