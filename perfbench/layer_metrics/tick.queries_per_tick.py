"""Queries completed per device tick: INTERACTIVE ``completed_total`` over
``ticks_total``, difference over the window."""


def read(ctx):
    d = ctx["delta"]
    ticks = d.get("ticks_total", 0)
    return d.get("runtime.interactive.completed_total", 0) / ticks if ticks else None
