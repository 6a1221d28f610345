"""Triples the single-chip extraction delivered per tick: the program's
registry counter ``aoi.triples`` (summed over the ticks whose events fit
the triples cap; an overflowing tick adds nothing) over the window's
ticks.  Telemetry is on for the whole window of a traced run, so the
counter holds the window's total."""


def read(ctx):
    from goworld_tpu import telemetry

    n = telemetry.snapshot().get("aoi.triples")
    if n is None or not ctx.ticks:
        return None
    return n / ctx.ticks
