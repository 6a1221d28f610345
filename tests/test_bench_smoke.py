"""bench.py import/compile smoke test.

bench.py only ever ran as a script on the TPU host, so pure syntax-level
regressions (the round-5 advisor found a mis-indented dict key) and
config-matrix drift were invisible to the test suite.  Importing is
enough to compile every function body's bytecode; the matrix assertions
pin the measurement-window contract for the fixed-order grid configs.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _load_bench():
    if "bench" in sys.modules:
        return sys.modules["bench"]
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_bench_imports_without_jax_side_effects():
    bench = _load_bench()
    assert callable(bench.main)
    assert bench.GRID_RESORT_K >= 1


def test_config_matrix_well_formed():
    bench = _load_bench()
    cfgs = bench.config_matrix()
    names = [c.name for c in cfgs]
    assert len(names) == len(set(names)), "duplicate config names"
    for c in cfgs:
        if getattr(c, "kernel", None) == "grid":
            # the grid drain must span at least one full re-sort period,
            # otherwise the amortized resort/K term is pure extrapolation
            assert c.ticks >= bench.GRID_RESORT_K, (
                f"{c.name}: ticks={c.ticks} < GRID_RESORT_K="
                f"{bench.GRID_RESORT_K}")


def test_bench_engine_records_span_phase_breakdown():
    """Engine records carry the telemetry-sourced per-phase breakdown
    (the acceptance contract: {stage, kernel, diff, fetch, decode,
    fanout, emit} plus the split-phase scheduler's {dispatch, harvest}
    pair and the
    whole-tick span) even on the native-calculator path, where the
    scheduler phases are zero (CPU buckets dispatch-and-complete inline)."""
    bench = _load_bench()
    cfg = bench.Config("enginetiny", 1, 256, 600.0, 80.0, n_active=100,
                       ticks=3, reps=1)
    rec = bench.bench_engine(cfg, "cpp")
    assert set(rec["phase_ms"]) == {"stage", "kernel", "diff", "fetch",
                                    "decode", "fanout", "emit", "dispatch",
                                    "harvest"}
    assert all(v >= 0.0 for v in rec["phase_ms"].values())
    assert rec["span_tick_ms"] >= 0.0

    from goworld_tpu import telemetry
    assert not telemetry.enabled(), "bench must disable telemetry after"
