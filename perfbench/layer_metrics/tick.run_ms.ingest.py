"""Mean time of one device tick from composed to finished while files are
ingested (``DeviceTickRuntime._run_tick`` under ``tick:runtime``,
``stage="tick.run"`` sum / count over the window): the batch function with
tokenize, launch and the wait for the result, and the runtime's own
bookkeeping around it."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.tick.run.count", 0)
    return d["stage.tick.run.sum"] / n if n else None
