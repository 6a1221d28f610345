"""Share of the replayed enter/leave pairs that event replay delivered
as columns (client observers with default AOI hooks) rather than
through per-pair hooks: the program's registry counter
``aoi.replay_columns`` over it plus ``aoi.replay_hooked``, in percent.
None where the program has no such counters or replayed no pair."""


def read(ctx):
    from goworld_tpu import telemetry

    snap = telemetry.snapshot()
    cols = snap.get("aoi.replay_columns") or 0
    n = cols + (snap.get("aoi.replay_hooked") or 0)
    if not n:
        return None
    return cols / n * 100
