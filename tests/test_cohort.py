"""Space-stacked cohorts (``ops/aoi_cohort``, ``engine/aoi_cohort``,
``AOIEngine(cohort=...)``, docs/perf.md "Space-stacked cohorts").

The contract under test:

* cohort routing: small device-eligible spaces of DIFFERENT capacities
  round up to a pow2 ladder shape and stack into ONE shared bucket, so
  one fused launch ticks the whole cohort -- event streams bit-exact
  vs ``cohort="solo"`` (one exclusive bucket per space, the per-space
  baseline) and vs the CPU oracle;
* the device-dispatch pin: N stacked spaces cost O(1) dispatches per
  steady tick where solo pays O(N), and steady-state recompiles are 0
  after warmup (``dispatch_count.record_key``);
* the ``aoi.cohort`` fault seam: ANY kind fired at the cohort's
  dispatch demotes the whole cohort to per-space solo buckets -- same
  tick, bit-exact, counted in ``aoi.cohort_demotions`` /
  ``aoi.cohort_demoted_spaces`` -- and :meth:`AOIEngine.recohort`
  re-arms by stacking the demoted spaces back;
* live membership: ``cohort_join`` / ``cohort_leave`` move a space
  between its cohort and a solo bucket mid-walk with zero dropped
  ticks and an event stream bit-exact vs a never-cohorted oracle
  (spans "aoi.cohort.join" / "aoi.cohort.leave" / "aoi.cohort.demote";
  gauges ``aoi.cohorts`` / ``aoi.cohort_spaces``, counters
  ``aoi.cohort_joins`` / ``aoi.cohort_leaves`` /
  ``aoi.cohort_dispatches``);
* the planner: ``CohortPlanner`` re-buckets stacked vs solo membership
  from per-bucket load samples under a churn budget, and doubles as
  the demotion re-arm loop.
"""

import numpy as np
import pytest

from goworld_tpu import faults, telemetry
from goworld_tpu.engine.aoi import AOIEngine
from goworld_tpu.engine.placement import CohortPlanner
from goworld_tpu.ops import aoi_cohort as AC
from goworld_tpu.ops import dispatch_count as DC
from goworld_tpu.telemetry import trace

from test_aoi_delta import _pad, _scene, _sparse_step

CAPS = (140, 200, 256, 300)  # mixed capacities; first three share rung 256


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


def _engines(**cohort_kwargs):
    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "cohort": AOIEngine(default_backend="tpu", cohort="auto",
                            **cohort_kwargs),
        "solo": AOIEngine(default_backend="tpu", cohort="solo",
                          **cohort_kwargs),
    }
    handles = {k: [e.create_space(c) for c in CAPS]
               for k, e in engines.items()}
    return engines, handles


def _drive(engines, handles, ticks, seed=11, n=110):
    """One identical sparse walk per space, submitted to every engine;
    out[key][tick] = [(enter, leave) per space]."""
    scenes = [list(_scene(seed + i, cap, n)) for i, cap in enumerate(CAPS)]
    out = {k: [] for k in engines}
    for _t in range(ticks):
        for (rng, xs, zs, _rr, _act) in scenes:
            _sparse_step(rng, xs, zs)
        for k, e in engines.items():
            for (rng, xs, zs, rr, act), h in zip(scenes, handles[k]):
                cap = h.capacity
                e.submit(h, _pad(xs, cap), _pad(zs, cap), _pad(rr, cap),
                         _pad(act, cap))
            e.flush()
            out[k].append([e.take_events(h) for h in handles[k]])
    return out


def _assert_same(out, ref="cpu", keys=None):
    for k in (keys if keys is not None else [x for x in out if x != ref]):
        for t in range(len(out[ref])):
            for si in range(len(CAPS)):
                re_, rl = out[ref][t][si]
                pe, pl = out[k][t][si]
                np.testing.assert_array_equal(
                    re_, pe, err_msg=f"{k} space {si} enter tick {t}")
                np.testing.assert_array_equal(
                    rl, pl, err_msg=f"{k} space {si} leave tick {t}")


# -- routing & the shape ladder ----------------------------------------------

def test_cohort_routing_stacks_mixed_capacities():
    """Three spaces with different requested capacities share rung 256 of
    the ladder; the fourth rounds to 1024.  Solo mode mints one exclusive
    bucket per space at the same shapes."""
    engines, handles = _engines()
    coh = engines["cohort"]
    assert sorted(coh._buckets) == [("tpu-cohort", 256),
                                    ("tpu-cohort", 1024)]
    assert [h.capacity for h in handles["cohort"]] == [256, 256, 256, 1024]
    solo = engines["solo"]
    assert len(solo._buckets) == len(CAPS)
    assert all(getattr(b, "cohort_solo", False)
               for b in solo._buckets.values())


def test_cohort_ladder_validation():
    with pytest.raises(ValueError):
        AC.validate_ladder(())
    with pytest.raises(ValueError):
        AC.validate_ladder((300,))  # not pow2
    with pytest.raises(ValueError):
        AC.validate_ladder((64,))  # not a LANE multiple
    with pytest.raises(ValueError):
        AC.validate_ladder((1024, 256))  # not ascending
    assert AC.cohort_shape(200) == 256
    assert AC.cohort_shape(4096) == 4096
    assert AC.cohort_shape(4097) is None
    with pytest.raises(ValueError):
        AOIEngine(default_backend="tpu", cohort="bogus")


def test_cohort_past_ladder_keeps_classic_routing():
    """A space beyond the ladder ceiling falls through to capacity
    routing -- it must not silently join a cohort."""
    eng = AOIEngine(default_backend="tpu", cohort="auto",
                    cohort_ladder=(256,))
    h = eng.create_space(512)
    assert not getattr(h.bucket, "cohort", False)
    assert ("tpu", 512) in eng._buckets


# -- parity: cohort vs solo vs oracle ----------------------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_cohort_parity(fused):
    """The stacked cohort's event streams are bit-exact vs the per-space
    solo baseline and the CPU oracle, fused or not."""
    engines, handles = _engines(fused=fused)
    out = _drive(engines, handles, 8)
    _assert_same(out)


def test_cohort_parity_host_emit():
    """The same parity on the classic encoded stream (``emit="host"``):
    the stacked bucket's chunk caps are shared by every member space."""
    engines, handles = _engines(emit="host")
    out = _drive(engines, handles, 6)
    _assert_same(out)


# -- the dispatch & recompile pins -------------------------------------------

def test_cohort_one_dispatch_per_tick_vs_solo():
    """Steady state: the 256-rung cohort (3 spaces) ticks on ONE fused
    device program where solo pays one per space -- and neither path
    compiles anything new after warmup."""
    engines, handles = _engines(fused=True)
    del engines["cpu"], handles["cpu"]
    _drive(engines, handles, 3)  # warmup: full upload + first deltas
    counts, slices = {}, {}
    for k, e in engines.items():
        DC.reset()
        DC.reset_keys()
        out = _drive({k: e}, {k: handles[k]}, 4)
        counts[k] = DC.read()
        assert DC.new_keys() == 0, \
            f"{k}: steady-state recompiles must be 0"
        # a bucket whose tick has events slices its triples: one launch
        slices[k] = sum(
            len({id(h.bucket) for h, (en, lv) in zip(handles[k], tick)
                 if len(en) or len(lv)})
            for tick in out[k])
    # cohort: one fused launch per bucket (2 buckets: rungs 256 + 1024);
    # solo: one per space (4) -- the dispatch ratio the bench pins
    assert slices["cohort"] > 0, slices
    assert counts["cohort"] == 2 * 4 + slices["cohort"], (counts, slices)
    assert counts["solo"] == len(CAPS) * 4 + slices["solo"], \
        (counts, slices)
    coh = engines["cohort"]._buckets[("tpu-cohort", 256)]
    assert coh.stats["cohort_dispatches"] >= 7
    assert coh.stats["cohort_demotions"] == 0


# -- the aoi.cohort fault seam ------------------------------------------------

@pytest.mark.parametrize("kind", ["fail", "oom", "reset"])
def test_cohort_fault_demotes_same_tick_bit_exact(kind):
    """Any aoi.cohort kind fired at dispatch demotes the whole cohort to
    per-space solo buckets, republishing the SAME tick bit-exactly --
    the stream never skips a beat vs the oracle."""
    # two cohort buckets (rungs 256 + 1024) probe the seam once per flush
    # in sorted order: @3x2 fires both probes of tick 2
    faults.install(f"aoi.cohort:{kind}@3x2")
    engines, handles = _engines()
    out = _drive(engines, handles, 8)
    _assert_same(out)
    coh = engines["cohort"]
    assert not any(isinstance(k, tuple) and k[0] == "tpu-cohort"
                   for k in coh._buckets), "demoted cohorts are torn down"
    assert coh.cohort_stats["cohort_demoted_spaces"] == len(CAPS)
    stats = {}
    for b in coh._buckets.values():
        for sk, v in b.stats.items():
            stats[sk] = stats.get(sk, 0) + v
    # solo replacements carry no cohort counters; the demotion count
    # surfaces via telemetry collected below
    samples = {s.name: s.value for s in coh._telemetry_collect()}
    assert samples["aoi.cohorts"] == 0
    assert samples["aoi.cohort_spaces"] == 0
    assert samples["aoi.cohort_demoted_spaces"] == len(CAPS)


def test_cohort_demotion_sequential_flush_mode():
    """flush_sched=False runs the demoted solo buckets' whole flush inline
    before the next bucket -- same-tick, bit-exact there too."""
    faults.install("aoi.cohort:fail@3x2")
    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "cohort": AOIEngine(default_backend="tpu", cohort="auto",
                            flush_sched=False),
        "solo": AOIEngine(default_backend="tpu", cohort="solo",
                          flush_sched=False),
    }
    handles = {k: [e.create_space(c) for c in CAPS]
               for k, e in engines.items()}
    out = _drive(engines, handles, 6)
    _assert_same(out)
    assert engines["cohort"].cohort_stats["cohort_demoted_spaces"] \
        == len(CAPS)


def test_recohort_rearms_after_demotion():
    """recohort() stacks demoted-solo spaces back into cohort buckets and
    the re-armed cohort keeps serving bit-exact ticks (and a fresh fault
    plan can demote it again: the seam is counted + re-armable)."""
    faults.install("aoi.cohort:fail@3x2")
    engines, handles = _engines()
    out = _drive(engines, handles, 4)
    faults.clear()
    coh = engines["cohort"]
    assert coh.recohort() == len(CAPS)
    assert sorted(coh._buckets) == [("tpu-cohort", 256),
                                    ("tpu-cohort", 1024)]
    out2 = _drive(engines, handles, 4)
    _assert_same(out)
    _assert_same(out2)
    # round two: a fresh plan fires at the fresh buckets
    faults.install("aoi.cohort:fail@1x2")
    out3 = _drive(engines, handles, 3)
    _assert_same(out3)
    assert coh.cohort_stats["cohort_demoted_spaces"] == 2 * len(CAPS)


# -- live join/leave ----------------------------------------------------------

def test_cohort_join_leave_under_load():
    """A space leaves its cohort mid-walk and rejoins later: zero dropped
    ticks, event stream bit-exact vs the never-cohorted oracle, spans and
    counters emitted."""
    engines, handles = _engines()
    coh, hs = engines["cohort"], handles["cohort"]
    telemetry.enable()
    trace.reset()
    try:
        out = _drive(engines, handles, 3)
        coh.cohort_leave(hs[0])
        assert getattr(hs[0].bucket, "cohort_solo", False)
        mid = _drive(engines, handles, 3)
        coh.cohort_join(hs[0])
        assert getattr(hs[0].bucket, "cohort", False)
        late = _drive(engines, handles, 3)
        names = [nm for nm, *_ in trace.spans()]
    finally:
        telemetry.disable()
    for k in out:
        out[k].extend(mid[k])
        out[k].extend(late[k])
    _assert_same(out)
    assert "aoi.cohort.leave" in names and "aoi.cohort.join" in names
    assert coh.cohort_stats == {"cohort_joins": 1, "cohort_leaves": 1,
                                "cohort_demoted_spaces": 0}
    samples = {s.name: s.value for s in coh._telemetry_collect()}
    assert samples["aoi.cohort_joins"] == 1
    assert samples["aoi.cohort_leaves"] == 1
    assert samples["aoi.cohorts"] == 2
    assert samples["aoi.cohort_spaces"] == len(CAPS)


def test_cohort_demote_span_and_staged_carry():
    """Demotion mid-flush emits the "aoi.cohort.demote" span, and a tick
    staged-but-undispatched at the fault rides onto the solo buckets (the
    same-tick republish contract, visible via the span + parity above)."""
    faults.install("aoi.cohort:fail@2")
    engines, handles = _engines()
    telemetry.enable()
    trace.reset()
    try:
        out = _drive(engines, handles, 3)
        names = [nm for nm, *_ in trace.spans()]
    finally:
        telemetry.disable()
    _assert_same(out)
    assert "aoi.cohort.demote" in names


def test_grow_space_from_cohort_crosses_rungs():
    """Growing a cohort-stacked space lands it on the next rung (or past
    the ladder), interest state carried -- growth emits no events."""
    engines, handles = _engines()
    out = _drive(engines, handles, 3)
    _assert_same(out)
    coh, hs = engines["cohort"], handles["cohort"]
    nh = coh.grow_space(hs[0], 512)
    assert nh.capacity == 1024  # 512 rounds up to the next rung
    assert getattr(nh.bucket, "cohort", False)
    handles["cohort"][0] = nh
    # the oracle and solo spaces grow too so the walk stays comparable
    handles["cpu"][0] = engines["cpu"].grow_space(handles["cpu"][0], 512)
    handles["solo"][0] = engines["solo"].grow_space(handles["solo"][0], 512)
    out2 = _drive(engines, handles, 3)
    _assert_same(out2)


# -- the planner ---------------------------------------------------------------

def test_cohort_planner_rejoins_demoted_spaces():
    """auto mode: light solo spaces (here: fault-demoted ones) fold back
    into their ladder cohorts within the churn budget."""
    faults.install("aoi.cohort:fail@1x2")
    engines, handles = _engines()
    coh = engines["cohort"]
    planner = CohortPlanner(coh, mode="auto", hot_ms=1e9,
                            churn_budget=2, cooldown_ticks=0)
    _drive(engines, handles, 3)
    faults.clear()
    assert coh.cohort_stats["cohort_demoted_spaces"] == len(CAPS)
    for _ in range(4):  # budget 2/window: demoted spaces rejoin in waves
        planner.step()
        _drive(engines, handles, 1)
    assert coh.cohort_stats["cohort_joins"] == len(CAPS)
    assert sorted(coh._buckets) == [("tpu-cohort", 256),
                                    ("tpu-cohort", 1024)]
    out = _drive(engines, handles, 3)
    _assert_same(out)


def test_cohort_planner_sheds_hot_cohort_member():
    """A cohort hotter than hot_ms sheds one member per window (budget-
    bounded), and static mode never moves anything."""
    engines, handles = _engines()
    coh = engines["cohort"]
    _drive(engines, handles, 2)
    static = CohortPlanner(coh, mode="static", hot_ms=0.0)
    static.step()
    assert coh.cohort_stats["cohort_leaves"] == 0
    planner = CohortPlanner(coh, mode="auto", hot_ms=0.0,
                            churn_budget=1, cooldown_ticks=0)
    _drive(engines, handles, 1)  # give the planner's window a sample
    planner.step()
    assert coh.cohort_stats["cohort_leaves"] == 1
    out = _drive(engines, handles, 3)
    _assert_same(out)
    with pytest.raises(ValueError):
        CohortPlanner(coh, mode="bogus")


def test_runtime_cohort_knobs():
    """Runtime(aoi_cohort=...) builds the planner and routes spaces
    through the cohort tier end to end."""
    from goworld_tpu.engine.runtime import Runtime

    rt = Runtime(aoi_backend="tpu", aoi_cohort=True,
                 aoi_cohort_planner="auto")
    assert isinstance(rt.cohort_planner, CohortPlanner)
    h = rt.aoi.create_space(200)
    assert getattr(h.bucket, "cohort", False)
    for _ in range(3):
        rt.tick()
    rt2 = Runtime(aoi_backend="tpu")
    assert rt2.cohort_planner is None
