"""Share of the profiled stretch in which no operation ran on the device:
1 - (union of device-operation intervals / stretch), in percent."""


def read(ctx):
    t = ctx.trace
    if t is None or t["busy_s"] is None or not t["window_s"]:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
