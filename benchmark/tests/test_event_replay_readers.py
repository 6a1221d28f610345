"""The event-replay readers on a synthetic context: ``emit_us_per_pair``
and ``emit_column_share`` from the program's registry counters
``aoi.replay_columns`` / ``aoi.replay_hooked`` and its ``aoi.emit`` spans,
and None where the counters are zero or absent.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import os

import pytest

from benchmark import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = os.path.join(REPO, "benchmark", "metrics")


@pytest.mark.parametrize("counters,per_pair,share", [
    ({"aoi.replay_columns": 3000.0, "aoi.replay_hooked": 1000.0}, 1.5, 75.0),
    ({"aoi.replay_columns": 0.0, "aoi.replay_hooked": 500.0}, 12.0, 0.0),
    ({"aoi.replay_columns": 0.0, "aoi.replay_hooked": 0.0}, None, None),
    ({}, None, None),  # a program without the counters
], ids=["both", "hooked_only", "zero", "absent"])
def test_event_replay_readers(monkeypatch, counters, per_pair, share):
    """emit_us_per_pair: ``aoi.emit`` seconds outside ``aoi.flush`` over
    the replayed pairs, in us; emit_column_share: the column pairs' share,
    in %; both None where no pair was counted."""
    from goworld_tpu import telemetry

    monkeypatch.setattr(telemetry, "snapshot", lambda: dict(counters))
    # two ticks: 2 ms and 4 ms of replay; the 1 ms emit inside the flush
    # is another depth of the name and is left out
    ctx = run.Context([("aoi.emit", 0.0, 0.002), ("aoi.emit", 1.0, 1.004),
                       ("aoi.flush", 2.0, 2.01), ("aoi.emit", 2.001, 2.002)],
                      2, None, 0)
    got = run.per_layer_reader(METRICS, "emit_us_per_pair")(ctx)
    assert got == (None if per_pair is None else pytest.approx(per_pair))
    got = run.per_layer_reader(METRICS, "emit_column_share")(ctx)
    assert got == (None if share is None else pytest.approx(share))
