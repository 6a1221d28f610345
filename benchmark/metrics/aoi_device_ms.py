"""Device time per tick: the summed durations of every device operation
in the profiled stretch (``trace.reduce``'s ``op_s``), over its ticks."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["op_s"] or not ctx.traced_ticks:
        return None
    return t["op_s"] / ctx.traced_ticks * 1e3
