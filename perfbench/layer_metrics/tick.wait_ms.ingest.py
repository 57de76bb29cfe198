"""Mean wait of an engine-plane embed call for its device tick while files
are ingested (the tick runtime's ``llm_rerank`` class, which carries the
ingest path's encoder calls: ``wait_ms_sum / wait_ms_count``), difference
over the window."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("runtime.llm_rerank.wait_ms_count", 0)
    return d["runtime.llm_rerank.wait_ms_sum"] / n if n else None
