"""Documents a launch of the language-model embedder's forward while files
are ingested: documents through the index (``ingest_stats()["docs_total"]``)
over launches of the forward (``pathway_moe_launches_total``), difference
over the window.  1.0 means every document reads the experts again; a flush
whose documents share one packed launch reads its size.  Nothing when the
program counts no such launches."""


def read(ctx):
    d = ctx["delta"]
    launches = d.get("moe.launches_total", 0)
    return d.get("ingest.docs_total", 0) / launches if launches else None
