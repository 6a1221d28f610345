"""The harness on the CPU at tiny sizes: cells found by name from files
alone, the delivered stream against the plain reference, the tail
arithmetic, the refusal of a CPU run, the trace reduction, and the
comparison's control and planted faults, each of which must come out as
not correct.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from benchmark import reference as R
from benchmark import run
from benchmark import trace as T
from benchmark.harness import TickRecord

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SEED = 2**31 + 977  # past 32 signed bits, as the driver's are

UNIFORM = {"spaces": 2, "entities_per_space": 300, "capacity": 384,
           "world": 600.0, "radius": 100.0, "spread": {"kind": "uniform"},
           "engine": {"aoi_backend": "tpu"}}
ZIPF = {"spaces": 1, "entities_per_space": 1000, "capacity": 1024,
        "world": 6000.0, "radius": 100.0,
        "spread": {"kind": "hotspot", "hot_share": 0.9, "hot_side": 0.1},
        "engine": {"aoi_backend": "tpu"}}
VARIABLE = dict(UNIFORM, radius={"kind": "uniform", "low": 50.0,
                                 "high": 150.0})
MIXED = {"player_share": 1 / 3, "gates": 4, "move_share": 1.0,
         "step": 5.0, "watchers_per_space": 0}
SPARSE = {"player_share": 0.0, "gates": 0, "move_share": 0.1, "step": 5.0,
          "watchers_per_space": 1}


def tree(tmp_path, cells, per_layer=(), metric_files=None):
    """A checkout-shaped tree of data files only: BENCHMARK.json, one file
    per configuration and mix, and the metric files given."""
    b = tmp_path / "benchmark"
    for d in ("configs", "traffic", "metrics"):
        (b / d).mkdir(parents=True, exist_ok=True)
    bench = {"configs": [], "workloads": [], "per_layer": list(per_layer),
             "end_to_end": [
                 {"name": "moves_per_s", "unit": "moves/s"},
                 {"name": "event_ms_p95", "unit": "ms"},
                 {"name": "setup_s", "unit": "s"}]}
    for name, cfg, mix_name, mix in cells:
        cname, _ = name.split(".", 1)
        (b / "configs" / f"{cname}.json").write_text(json.dumps(cfg))
        (b / "traffic" / f"{mix_name}.json").write_text(json.dumps(mix))
        if cname not in {c["name"] for c in bench["configs"]}:
            bench["configs"].append(
                {"name": cname, "file": f"benchmark/configs/{cname}.json"})
        bench["workloads"].append({"name": name, "config": cname,
                                   "traffic": mix_name, "chips": 1})
    for fname, text in (metric_files or {}).items():
        (b / "metrics" / fname).write_text(text)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def run_tiny(root, cell, seconds=1.0, **kw):
    found = run.load_cell(cell, root=root)
    return run.run_cell(found, SEED, seconds, False, time.perf_counter(),
                        **kw)


# -- cells from data files alone -------------------------------------------


def test_cell_from_new_files_loads_and_runs(tmp_path):
    """A configuration, a mix and two per-layer metrics (a span entry and
    a reader of its own), all new files: found by name and run, with no
    existing file edited."""
    root = tree(
        tmp_path, [("tinyworld.drift", UNIFORM, "drift",
                    dict(MIXED, move_share=0.5))],
        per_layer=[{"name": "tick_ms", "unit": "ms"},
                   {"name": "ticks_seen", "unit": "ticks",
                    "workloads": ["tinyworld.drift"]},
                   {"name": "elsewhere", "unit": "ms",
                    "workloads": ["other.cell"]}],
        metric_files={"tick_ms.json": '{"span": "bench.tick"}',
                      "ticks_seen.py": "def read(ctx):\n"
                                       "    return ctx.ticks\n"})
    found = run.load_cell("tinyworld.drift", root=root)
    assert found["mix"]["move_share"] == 0.5
    assert [m["name"] for m in found["per_layer"]] == ["tick_ms",
                                                       "ticks_seen"]
    out = run_tiny(root, "tinyworld.drift")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"moves_per_s", "event_ms_p95",
                                   "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    ctx = run.Context([("bench.tick", 0.0, 0.004), ("bench.tick", 1.0,
                                                    1.006)], 2, None, 0)
    d = found["metrics_dir"]
    assert run.per_layer_reader(d, "tick_ms")(ctx) == pytest.approx(5.0)
    assert run.per_layer_reader(d, "ticks_seen")(ctx) == 2


@pytest.mark.parametrize("cfg,mix", [(UNIFORM, MIXED), (ZIPF, SPARSE),
                                     (VARIABLE, MIXED)],
                         ids=["uniform", "zipf", "variable_radius"])
def test_delivered_stream_equals_reference(tmp_path, cfg, mix):
    root = tree(tmp_path, [("w.m", cfg, "m", mix)])
    out = run_tiny(root, "w.m")
    assert out["correct"], out["checks"]
    assert out["checks"]["event_pairs_wrong"]["value"] == 0


def test_reference_matches_brute_force():
    rng = np.random.default_rng(5)
    pos = rng.uniform(0, 400, (500, 2)).astype(np.float32)
    r = rng.uniform(30, 90, 500).astype(np.float32)
    dx = np.abs(pos[None, :, 0] - pos[:, None, 0])
    dz = np.abs(pos[None, :, 1] - pos[:, None, 1])
    m = (dx <= r[:, None]) & (dz <= r[:, None])
    np.fill_diagonal(m, False)
    i, j = np.nonzero(m)
    want = np.sort(i.astype(np.int64) * 500 + j)
    assert np.array_equal(R.seen_pairs(pos[:, 0], pos[:, 1], r), want)
    touch = rng.random(500) < 0.1
    sub = want[touch[want // 500] | touch[want % 500]]
    assert np.array_equal(R.seen_pairs(pos[:, 0], pos[:, 1], r, touch), sub)


# -- tails -------------------------------------------------------------------


def _tick(start, end, moves, records):
    t = TickRecord()
    t.start, t.end, t.moves, t.records = start, end, moves, records
    return t


def test_tail_arithmetic_with_a_planted_stall():
    """19 ticks of 100 ms and one stall of 2 s: the stall holds 1/20 of
    the moves, so the 95th percentile of all moves sits at its edge."""
    ticks, t = [], 0.0
    for k in range(20):
        dur = 2.0 if k == 7 else 0.1
        ticks.append(_tick(t, t + dur, 100, 50))
        t += dur
    assert 100.0 <= run.event_p95(ticks, 0) <= 2000.0
    # a move waits for the next tick's end: the stall lands on tick 6's
    # and tick 7's moves, 2/19 of them
    assert run.event_p95(ticks, 1) == pytest.approx(2100.0)
    # a stall that carries most of the moves sets the tail
    ticks[7].moves = 2000
    assert run.event_p95(ticks, 0) == pytest.approx(2000.0)
    calm = [_tick(k * 0.1, k * 0.1 + 0.1, 100, 50) for k in range(20)]
    assert run.event_p95(calm, 0) == pytest.approx(100.0)


# -- platform ----------------------------------------------------------------


def test_cpu_run_exits_nonzero_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "uniform8x10k.mixed", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert p.stdout.strip() == ""


# -- the trace reduction -----------------------------------------------------


def test_trace_reduction_on_a_recorded_tpu_trace():
    """tools/capture_test_trace.py: three ticks of a matmul program, each
    followed by 20 ms inside a ``host.idle`` span."""
    red = T.reduce(os.path.join(HERE, "data", "small_tpu.xplane.pb"))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["op_s"] + 1e-9
    assert red["busy_s"] < red["window_s"]
    idle = red["window_s"] - red["busy_s"]
    assert idle >= 0.06
    names = [n for n, _s in red["gaps"]]
    assert names.count("host.idle") >= 3
    assert all(s > 0 for _n, s in red["gaps"])
    assert red["ops"] and all(s > 0 for _n, s in red["ops"])


def test_peaks_table():
    v5e = T.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and "Google" in v5e["source"]
    with pytest.raises(KeyError, match="not in the peaks table"):
        T.peaks("TPU v99")


# -- the control and the planted faults ---------------------------------------


@pytest.fixture
def mixed_root(tmp_path):
    return tree(tmp_path, [("w.m", UNIFORM, "m", MIXED)])


def test_control_at_bfloat16_fails(mixed_root):
    out = run_tiny(mixed_root, "w.m", control="bfloat16")
    assert not out["correct"]
    assert out["checks"]["event_pairs_wrong"]["value"] > 0


def _state_unchanged(shard):
    # the AOI step returns its state unchanged: nothing is delivered
    shard.rt.aoi.flush = lambda: None


def _half_batch(shard):
    # half of each batch left out: every other gate batch and half the NPCs
    ingest, n = shard.ingest.ingest, {"k": 0}

    def half_ingest(pkt):
        n["k"] += 1
        return ingest(pkt) if n["k"] % 2 else 0

    shard.ingest = types.SimpleNamespace(ingest=half_ingest)
    for scene in shard.scenes:
        move = scene.move_entities
        scene.move_entities = (lambda s, x, z, move=move:
                               move(s[::2], x[::2], z[::2]))


def _event_altered(shard):
    take = shard.rt.aoi.take_events

    def altered(h):
        enter, leave = take(h)
        if len(enter):
            enter = enter.copy()
            enter[0, 1] = enter[0, 0]  # observer made to see itself
        return enter, leave

    shard.rt.aoi.take_events = altered


def _record_altered(shard):
    drain = shard.rt.drain_sync

    def altered():
        out = drain()
        if out:
            c, g, e, x, y, z, yaw = out[0]
            out[0] = (c, g, e, x + 1.0, y, z, yaw)
        return out

    shard.rt.drain_sync = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _event_altered, _record_altered],
                         ids=["state_unchanged", "half_batch",
                              "event_altered", "record_altered"])
def test_planted_fault_is_not_correct(mixed_root, fault):
    out = run_tiny(mixed_root, "w.m", fault=fault)
    assert not out["correct"], out["checks"]
