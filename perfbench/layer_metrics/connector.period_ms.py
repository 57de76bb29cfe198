"""Mean time from the start of one listing pass of the watched path to the
start of the next: the period a new file waits in, half of it on average
and nearly all of it at p95 (``io/fs`` ``_FsSubject.run`` observes it at
the start of every listing pass but a run's first;
``pathway_request_stage_ms{stage="connector.period"}`` sum / count over the
window, an observation without a span).  It reads ``refresh_interval`` +
``connector.scan_ms`` where the verify rounds run beside the listing loop,
and nothing on a program that does not observe it."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.connector.period.count", 0)
    return d["stage.connector.period.sum"] / n if n else None
