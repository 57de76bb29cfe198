"""Mean host time to hand one encode call's chunks to the device: host to
device copies of ids and mask and the dispatch of the jitted forward, until
the last launch call returns (``models/encoder.py`` under ``embed.launch``;
``stage="embed.launch"`` sum / count over the window)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.embed.launch.count", 0)
    return d["stage.embed.launch.sum"] / n if n else None
