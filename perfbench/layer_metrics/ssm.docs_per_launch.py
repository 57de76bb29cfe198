"""Documents a launch of the hybrid embedder's forward while files are
ingested: documents through the index (``ingest_stats()["docs_total"]``) over
launches of the forward (``pathway_ssm_launches_total``), difference over the
window.  A flush whose documents share one packed launch reads its size.
Nothing when the program counts no such launches."""


def read(ctx):
    d = ctx["delta"]
    launches = d.get("ssm.launches_total", 0)
    return d.get("ingest.docs_total", 0) / launches if launches else None
