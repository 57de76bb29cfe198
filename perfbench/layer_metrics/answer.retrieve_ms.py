"""Host-clock time of an answer's retrieval inside its retrieve tick: the
``embed`` and ``search`` stages (``pathway_request_stage_ms{stage=}``, which
every request of a tick records at the tick's own times: the query encoder's
launch, then the scan with the wait for both), sums of the two over the
window divided by the requests that recorded them.  Nothing when neither
stage was recorded."""


def read(ctx):
    d = ctx["delta"]
    n = max(d.get("stage.embed.count", 0), d.get("stage.search.count", 0))
    total = d.get("stage.embed.sum", 0.0) + d.get("stage.search.sum", 0.0)
    return total / n if n and total else None
