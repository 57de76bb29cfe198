"""Mean host-clock time of the ``search`` stage of a retrieve tick
(``pathway_request_stage_ms{stage="search"}`` sum / count over the window).
The ``embed`` stage returns a device array, so this stage absorbs the
encoder's device time as well as the scan's: a host-clock time of a stage
seen from outside, not a kernel time."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("stage.search.count", 0)
    return d["stage.search.sum"] / n if n else None
