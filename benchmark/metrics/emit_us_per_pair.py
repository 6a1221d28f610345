"""Host time of event replay per pair replayed: the window's seconds in
the program's span ``aoi.emit`` outside ``aoi.flush`` (as
``aoi_emit_ms`` reads them) over the pairs its registry counters
``aoi.replay_columns`` and ``aoi.replay_hooked`` counted, in
microseconds.  Telemetry is on for the whole window of a traced run, so
the counters hold the window's totals.  None where the program has no
such counters or replayed no pair."""


def read(ctx):
    from goworld_tpu import telemetry

    snap = telemetry.snapshot()
    n = (snap.get("aoi.replay_columns") or 0) + (
        snap.get("aoi.replay_hooked") or 0)
    ms = ctx.span_ms_per_tick("aoi.emit", "aoi.flush")
    if not n or ms is None:
        return None
    return ms * ctx.ticks / n * 1e3
