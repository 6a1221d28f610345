"""One-dispatch fused device pipeline (``ops/aoi_fused``,
``Runtime(aoi_fused=True)``).

The contract under test (docs/perf.md "Fused dispatch"):

* a fused steady-state tick compiles the whole per-bucket pipeline --
  delta scatter -> neighbor kernel -> diff/classify -> triple extraction
  -- into ONE jitted, donated program, and its event stream is bit-exact
  vs the unfused path and the CPU oracle across tiers +/- cross_tick
  +/- interest stacks; the classic encoded stream (``emit="host"``) has
  no fused program and falls through to the unfused path, bit-exact;
* device dispatches per steady-state tick == 1 for the fused
  single-chip bucket (counted through ``ops.dispatch_count``; unfused
  pays 2: scatter + step).  The mesh and row-sharded tiers launch one
  shard_map program per tick too -- one launch fanning out per-chip --
  asserted in scripts/fused_smoke.py, documented here;
* any ``aoi.*`` seam firing inside the fused attempt demotes that one
  tick to the unfused path -- counted in ``aoi.fused_demotions``,
  republished same-tick, bit-exact;
* telemetry: the "aoi.kernel" span brackets the fused enqueue and the
  ``aoi.fused_dispatches`` / ``aoi.fused_demotions`` counters surface
  through the engine stats (docs/observability.md).
"""

import numpy as np
import pytest

from goworld_tpu import faults, telemetry
from goworld_tpu.engine.aoi import AOIEngine
from goworld_tpu.ops import dispatch_count as DC
from goworld_tpu.telemetry import trace

from test_aoi_delta import _pad, _scene, _sparse_step
from test_flush_sched import (CAPS, _assert_multi_same, _drain_trailing,
                              _drive_multi, _mesh_or_skip)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


def _engines(variants: dict, **common):
    """cpu oracle + one tpu engine per named kwargs dict."""
    engines = {"cpu": AOIEngine(default_backend="cpu")}
    for name, kw in variants.items():
        engines[name] = AOIEngine(default_backend="tpu", **common, **kw)
    handles = {k: [e.create_space(c) for c in CAPS]
               for k, e in engines.items()}
    return engines, handles


# -- parity: fused vs unfused vs oracle --------------------------------------

@pytest.mark.parametrize("host_emit", [False, True])
def test_fused_parity_single_chip(host_emit):
    """Fused single-chip is bit-exact vs unfused and the oracle.  On the
    triples path the fused program actually runs; the classic encoded
    stream (``emit="host"``) has none, so every tick falls through to
    the unfused flow -- silently, not as a demotion."""
    engines, handles = _engines(
        {"fused": {"fused": True}, "plain": {}},
        emit="host" if host_emit else "auto")
    out = _drive_multi(engines, handles, 8)
    _assert_multi_same(out)
    st = handles["fused"][0].bucket.stats
    if host_emit:
        assert st["fused_dispatches"] == 0, "host emit has no fused program"
    else:
        assert st["fused_dispatches"] > 0, "fused path never taken"
    assert st["fused_demotions"] == 0


@pytest.mark.parametrize("host_emit", [False, True])
def test_fused_cross_tick_parity(host_emit):
    """fused composes with the one-tick deferral: fused+cross_tick is
    the oracle shifted exactly one tick, like unfused cross_tick, on
    both extraction formats."""
    engines, handles = _engines(
        {"fxt": {"fused": True, "cross_tick": True}},
        emit="host" if host_emit else "auto")
    out = _drive_multi(engines, handles, 8)
    _drain_trailing(engines, handles, out, ("fxt",))
    _assert_multi_same(out, shift=1, keys=("fxt",))
    n_fused = handles["fxt"][0].bucket.stats["fused_dispatches"]
    assert (n_fused == 0) if host_emit else (n_fused > 0)


def test_fused_mesh_parity():
    mesh = _mesh_or_skip()
    engines, handles = _engines({"fused": {"fused": True}}, mesh=mesh)
    assert type(handles["fused"][0].bucket).__name__ == "_MeshTPUBucket"
    out = _drive_multi(engines, handles, 6)
    _assert_multi_same(out)
    st = handles["fused"][0].bucket.stats
    assert st["fused_dispatches"] > 0, "mesh fused path never taken"
    assert st["fused_demotions"] == 0


def test_fused_rowshard_parity():
    mesh = _mesh_or_skip()
    cap = 2048
    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "fused": AOIEngine(default_backend="tpu", mesh=mesh,
                           rowshard_min_capacity=cap, fused=True),
    }
    handles = {k: e.create_space(cap) for k, e in engines.items()}
    assert type(handles["fused"].bucket).__name__ == "_RowShardTPUBucket"
    rng, xs, zs, rr, act = _scene(13, cap, 300)
    for _t in range(4):
        _sparse_step(rng, xs, zs)
        ref = None
        for k, e in engines.items():
            e.submit(handles[k], _pad(xs, cap), _pad(zs, cap),
                     _pad(rr, cap), act.copy())
            e.flush()
            ev = e.take_events(handles[k])
            if k == "cpu":
                ref = ev
            else:
                for pa, pb in zip(ref, ev):
                    np.testing.assert_array_equal(pa, pb)
    st = handles["fused"].bucket.stats
    assert st["fused_dispatches"] > 0, "rowshard fused path never taken"
    assert st["fused_demotions"] == 0


def test_fused_interest_parity():
    """Interest stacks compose above the bucket: a fused engine with a
    team+tier stack attached delivers the same stream as an unfused one
    (the stack consumes the submitted host columns; the fused bucket
    keeps the radius state underneath)."""
    from test_interest import _policies

    cap = 128
    engines = {
        "plain": AOIEngine(default_backend="tpu"),
        "fused": AOIEngine(default_backend="tpu", fused=True),
    }
    handles, stacks = {}, {}
    for k, e in engines.items():
        handles[k] = e.create_space(cap)
        stacks[k] = e.attach_interest(handles[k], _policies("team+tier"))
    # sparse movement (not test_interest._walk, which moves every entity
    # -- an oversized delta falls back to the unfused path by design)
    rng, xs, zs, rr, act = _scene(5, cap, cap)
    team = (np.uint32(1) << rng.integers(0, 4, cap)).astype(np.uint32)
    vis = np.full(cap, 0xF, np.uint32)
    for _t in range(6):
        _sparse_step(rng, xs, zs)
        ref = None
        for k, e in engines.items():
            e.submit(handles[k], _pad(xs, cap), _pad(zs, cap),
                     _pad(rr, cap), act.copy())
            stacks[k].submit(_pad(xs, cap), _pad(zs, cap), _pad(rr, cap),
                             act.copy(), team, vis)
            e.flush()
            ev = e.take_events(handles[k])
            if ref is None:
                ref = ev
            else:
                for pa, pb in zip(ref, ev):
                    np.testing.assert_array_equal(pa, pb)
    assert handles["fused"].bucket.stats["fused_dispatches"] > 0


# -- the acceptance meter: one device dispatch per steady tick ---------------

def test_fused_one_dispatch_per_steady_tick():
    """THE point of the PR: once warm, a fused single-chip bucket ticks
    in exactly one device step launch (unfused: two -- scatter + step),
    plus the harvest's eager triples slice on a tick with events (one
    more launch, both paths).  Counted at the launch sites via
    ops.dispatch_count; D2H fetches are not launches.  Non-deferred
    mode: the deferral (pipeline/cross_tick) slices at dispatch instead.
    Per-chip counts for mesh/rowshard (also 1 fused / 2 unfused, the
    single launch fanning out under shard_map) are asserted by
    scripts/fused_smoke.py against 8 virtual devices."""
    cap = 256
    engines = {
        "fused": AOIEngine(default_backend="tpu", fused=True),
        "plain": AOIEngine(default_backend="tpu"),
    }
    handles = {k: e.create_space(cap) for k, e in engines.items()}
    rng, xs, zs, rr, act = _scene(3, cap, 180)
    steady = {k: [] for k in engines}
    sliced = {k: [] for k in engines}
    for t in range(6):
        _sparse_step(rng, xs, zs)
        for k, e in engines.items():
            e.submit(handles[k], _pad(xs, cap), _pad(zs, cap),
                     _pad(rr, cap), act.copy())
            DC.reset()
            e.flush()
            en, lv = e.take_events(handles[k])
            if t >= 2:  # warm: past first-tick full restage + compiles
                steady[k].append(DC.read())
                sliced[k].append(int(len(en) + len(lv) > 0))
    assert sliced["fused"] == sliced["plain"] and any(sliced["fused"])
    assert steady["fused"] == [1 + s for s in sliced["fused"]], \
        f"fused steady ticks took {steady['fused']} dispatches, want 1 " \
        f"+ the triples slice"
    assert steady["plain"] == [2 + s for s in sliced["plain"]], \
        f"unfused baseline moved: {steady['plain']} (expected 2 + slice)"
    assert handles["fused"].bucket.stats["fused_dispatches"] >= 4


# -- demotion: a seam inside the fused attempt falls back, bit-exact ---------

@pytest.mark.parametrize("seam", ["aoi.kernel", "aoi.delta"])
def test_fused_demotion_republishes_same_tick(seam):
    """A fault firing inside the fused attempt demotes THAT tick to the
    unfused path: counted in fused_demotions, events delivered the same
    tick, stream bit-exact vs the oracle throughout."""
    engines, handles = _engines({"fused": {"fused": True}})
    faults.install(f"{seam}:fail@4")
    out = _drive_multi(engines, handles, 8)
    _assert_multi_same(out)
    demos = sum(h.bucket.stats["fused_demotions"] for h in handles["fused"])
    assert demos >= 1, f"forced {seam} fault did not demote"


def test_fused_demotion_under_fault_plan():
    """Same contract on the triples path under a multi-seam plan (the
    soak's shape): parity holds and every fired seam either demoted the
    fused attempt or hit the shared recovery path."""
    engines, handles = _engines({"fused": {"fused": True}})
    faults.install("seed=3;aoi.kernel:fail@3;aoi.delta:oom@5")
    out = _drive_multi(engines, handles, 8)
    _assert_multi_same(out)
    demos = sum(h.bucket.stats["fused_demotions"] for h in handles["fused"])
    assert demos >= 1


# -- telemetry: the aoi.kernel span + counters -------------------------------

def test_fused_span_and_counters():
    """The fused enqueue is the tick's "aoi.kernel" span (the removed
    "aoi.fused" lap covered the same extent) and the fused_dispatches /
    fused_demotions counters land in the engine stats."""
    engines, handles = _engines({"fused": {"fused": True}})
    telemetry.enable()
    trace.reset()
    try:
        _drive_multi(engines, handles, 4)
        names = {nm for nm, *_ in trace.spans()}
    finally:
        telemetry.disable()
    assert "aoi.fused" not in names
    assert "aoi.kernel" in names
    st = handles["fused"][0].bucket.stats
    assert st["fused_dispatches"] > 0
    assert st["fused_demotions"] == 0
