"""Share of the traced seconds in which no operation ran on the device:
1 - union of the device-op intervals of the trace over the traced time."""


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["busy_s"] or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
