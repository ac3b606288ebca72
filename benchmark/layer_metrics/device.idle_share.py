"""Share of the traced span in which no operation ran, on the device
that was idle most (a cell on several chips reports its worst)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"] or not trace["window_s"]:
        return None
    busy = min(d["busy_s"] for d in trace["devices"][:ctx["chips"]])
    return 100.0 * (1.0 - busy / trace["window_s"])
