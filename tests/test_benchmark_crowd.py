"""The benchmark's crowd cell (``hotspot100k.crowd``) on the CPU: found
by name from its files, its mix delivering the reference's stream at a
tiny size, and the readers of its two AOI-engine metrics reading what the
program counted.

    JAX_PLATFORMS=cpu python -m pytest tests/test_benchmark_crowd.py -q
"""

from __future__ import annotations

import json
import os
import time

import pytest

from benchmark import reference as R
from benchmark import run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 977  # past 32 signed bits
# the crowd's spread at a CPU size: 1000 entities, 90% in 1% of the map
ZIPF = {"spaces": 1, "entities_per_space": 1000, "capacity": 1024,
        "world": 6000.0, "radius": 100.0,
        "spread": {"kind": "hotspot", "hot_share": 0.9, "hot_side": 0.1},
        "engine": {"aoi_backend": "tpu"}}


def _crowd_mix():
    with open(os.path.join(REPO, "benchmark", "traffic", "crowd.json")) as f:
        return json.load(f)


def test_crowd_cell_loads_from_its_files():
    found = run.load_cell("hotspot100k.crowd")
    cfg, mix = found["config"], found["mix"]
    assert found["cell"]["chips"] == 1
    assert (cfg["spaces"], cfg["entities_per_space"], cfg["capacity"],
            cfg["world"], cfg["radius"]) == (1, 100000, 131072, 60000.0,
                                             100.0)
    assert cfg["spread"] == ZIPF["spread"]
    assert cfg["engine"] == {"aoi_backend": "tpu"}
    assert cfg["reduced"] == []
    assert mix == {"loop": "closed", "player_share": 0.0, "gates": 0,
                   "move_share": 1.0, "step": 5.0, "watchers_per_space": 1,
                   "warmup_ticks": 24}
    assert {m["name"] for m in found["end_to_end"]} == {
        "moves_per_s", "event_ms_p95", "setup_s"}
    names = {m["name"] for m in found["per_layer"]}
    assert {"aoi_triples_per_tick", "aoi_scratch_mb", "aoi_device_ms",
            "device_idle_share", "aoi_harvest_ms"} <= names
    # the metrics of the players' path stay with the cell that has them
    assert not names & {"ingest_ms", "tick_sync_ms", "sync_us_per_record"}
    for name in names:
        run.per_layer_reader(found["metrics_dir"], name)


def test_crowd_mix_delivers_the_reference_stream(tmp_path):
    """The crowd's mix on a 1000-entity hotspot, through the harness: the
    events every checked tick delivered equal the plain reference's."""
    b = tmp_path / "benchmark"
    for d in ("configs", "traffic", "metrics"):
        (b / d).mkdir(parents=True)
    (b / "configs" / "zipf.json").write_text(json.dumps(ZIPF))
    (b / "traffic" / "crowd.json").write_text(json.dumps(_crowd_mix()))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "zipf", "file": "benchmark/configs/zipf.json"}],
        "workloads": [{"name": "zipf.crowd", "config": "zipf",
                       "traffic": "crowd", "chips": 1}],
        "end_to_end": [{"name": "moves_per_s", "unit": "moves/s"}],
        "per_layer": []}))
    found = run.load_cell("zipf.crowd", root=str(tmp_path))
    out = run.run_cell(found, SEED, 1.0, False, time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["checks"]["event_pairs_wrong"]["value"] == 0
    assert out["checks"]["sync_records_wrong"]["value"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0


def test_aoi_engine_readers_on_a_crowd_shard():
    """``aoi_triples_per_tick`` reads the triples the device delivered,
    which for an all-moving crowd are every enter and leave pair of the
    space; ``aoi_scratch_mb`` the bucket's pooled scratch set (on top of
    what engines of earlier tests still hold)."""
    import gc

    from benchmark.harness import Shard
    from goworld_tpu import telemetry

    ctx = run.Context([], 3, None, 0)
    mdir = os.path.join(REPO, "benchmark", "metrics")
    gc.collect()
    held = run.per_layer_reader(mdir, "aoi_scratch_mb")(ctx) or 0.0
    shard = Shard(ZIPF, _crowd_mix(), SEED)
    shard.tick(moving=False)
    shard.tick()
    reg = telemetry.registry()
    triples = reg.counter("aoi.triples")
    saved, triples.value = triples.value, 0.0  # the reader reads totals
    owed = 0
    telemetry.enable(clock=time.perf_counter)
    try:
        for _ in range(ctx.ticks):
            rec = shard.tick(keep=True)
            enter, leave = R.events(rec.prev_pos[0], rec.pos[0],
                                    shard.radius[0], rec.movers[0])
            owed += len(enter) + len(leave)
        per_tick = run.per_layer_reader(mdir, "aoi_triples_per_tick")(ctx)
        scratch = run.per_layer_reader(mdir, "aoi_scratch_mb")(ctx)
        counted = triples.value
    finally:
        telemetry.disable()
        triples.value = saved
    assert owed > 0
    assert counted == owed
    assert per_tick == pytest.approx(owed / ctx.ticks)
    bucket = shard.scenes[0]._aoi_handle.bucket
    assert scratch - held == pytest.approx(bucket.scratch_bytes() / 2**20)
    assert bucket.scratch_bytes() > 0
