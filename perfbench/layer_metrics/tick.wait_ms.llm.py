"""Mean wait of a decode tick (a prefill, a decode step or a verify launch of
the decode session) for the device in the tick runtime: the ``generate``
class, which carries the LLM's launches (``wait_ms_sum / wait_ms_count`` of
``pathway_runtime_*``), difference over the window.  Nothing when the class
ran nothing."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("runtime.generate.wait_ms_count", 0)
    return d["runtime.generate.wait_ms_sum"] / n if n else None
