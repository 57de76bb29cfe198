"""Mean wait of an INTERACTIVE work item for its tick (runtime/executor.py
``stats()["classes"]["interactive"]`` ``wait_ms_sum / wait_ms_count``),
difference over the window."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("runtime.interactive.wait_ms_count", 0)
    return d["runtime.interactive.wait_ms_sum"] / n if n else None
