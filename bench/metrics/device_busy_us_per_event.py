"""Device busy microseconds per simulated event over the traced block: the
union of the intervals in which an operation ran on the device."""


def read(ctx):
    if ctx.get("busy_s") is None:
        return None
    return ctx["busy_s"] / ctx["traced_events"] * 1e6
