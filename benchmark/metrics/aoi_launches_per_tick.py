"""Device program launches per tick: the program's registry counter
``aoi.launches`` (every launch ``ops/dispatch_count.record`` sees) over
the window's ticks.  Telemetry is on for the whole window of a traced
run, so the counter holds the window's total."""


def read(ctx):
    from goworld_tpu import telemetry

    n = telemetry.snapshot().get("aoi.launches")
    if not n or not ctx.ticks:
        return None
    return n / ctx.ticks
