"""Host time per position-sync record: the window's seconds in the
program's span ``sync.collect`` over the records its registry counter
``sync.records`` counted, in microseconds.  Telemetry is on for the whole
window of a traced run, so the counter holds the window's total."""


def read(ctx):
    from goworld_tpu import telemetry

    n = telemetry.snapshot().get("sync.records")
    s = sum(b - a for name, a, b in ctx.spans if name == "sync.collect")
    if not n or not s:
        return None
    return s / n * 1e6
