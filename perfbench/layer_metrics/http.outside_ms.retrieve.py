"""What a request spends outside the scheduler's stages: the clients' mean
latency (from due time) less the means of the server's ``queue_wait``,
``embed`` and ``search`` stages: HTTP parsing, JSON of ten passages, the
event loop's hand-offs, and the generator's own lateness."""


def read(ctx):
    d = ctx["delta"]
    lat = [r["latency_ms"] for r in ctx["records"] if not r["failed"]]
    if not lat:
        return None
    inside = 0.0
    for stage in ("queue_wait", "embed", "search"):
        n = d.get(f"stage.{stage}.count", 0)
        if not n:
            return None
        inside += d[f"stage.{stage}.sum"] / n
    return sum(lat) / len(lat) - inside
