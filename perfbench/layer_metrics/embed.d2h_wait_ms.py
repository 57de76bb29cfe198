"""Mean time the host waits for an encode call's results and copies them
back (the ``np.asarray`` loop of ``bucketed_dispatch`` under
``embed.d2h_wait``; ``stage="embed.d2h_wait"`` sum / count over the
window).  Holds the device's own time for the forward."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.embed.d2h_wait.count", 0)
    return d["stage.embed.d2h_wait.sum"] / n if n else None
