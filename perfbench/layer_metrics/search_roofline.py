"""The search program's share of the HBM roofline, from the device trace:
the least bytes a brute-force tick must read (``costs.search_bytes``: every
slot of the row matrix once) times the launches seen in the traced slice,
over the chip's peak bandwidth, divided by the device time of those
launches.  Bound by memory: at 256 queries a tick the products would take
3 ms of the MXU against 5.9 ms of HBM."""


def read(ctx):
    trace, facts = ctx.get("trace"), ctx["facts"]
    if trace is None or ctx["peaks"] is None:
        return None
    seconds = launches = 0.0
    for program, s in trace["programs"].items():
        if any(tag in program for tag in facts["search_programs"]):
            seconds += s
            launches += trace["launches"][program]
    if not seconds:
        return None
    least = ctx["costs"].search_bytes(facts["capacity"], facts["dim"], facts["itemsize"])
    return 100.0 * (least * launches / ctx["peaks"]["hbm_bytes_per_s"]) / seconds
