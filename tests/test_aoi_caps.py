"""Capped extraction under skew and faults, on every device tier.

The single-chip bucket compacts each tick's changes into a capped
triples buffer; the mesh and row-sharded buckets into a counted chunk
stream.  A tick past its cap is never truncated: it is recovered from
the full change grid the same tick, counted in ``decode_overflow``, and
the cap grows so the next tick compacts on the device again; the
windowed decay (``_TriCapDecay`` / ``_CapDecay``) shrinks it back once
the storm has passed.  Covered here, bit-exact against the CPU oracle:

* a clustered crowd (spread -> one-cluster storm -> dispersal) on the
  single chip, with the cap settling back after the storm;
* a cap too small for any tick on the mesh and row-sharded tiers, with
  and without an ``aoi.fetch`` oom or ``aoi.scalars`` poison on that
  tick;
* a tiny triples cap and decay floor that overflow and re-arm;
* ``aoi.fetch`` oom / partial and ``aoi.scalars`` poison on single-chip
  ticks past their triples cap.
"""

import numpy as np
import pytest

from goworld_tpu import faults
from goworld_tpu.engine.aoi import AOIEngine, _TriCapDecay
from test_aoi_parity import random_walk_scenario


@pytest.fixture(autouse=True)
def _clear_faults():
    faults.clear()
    yield
    faults.clear()


def make_mesh(n=8):
    from goworld_tpu.parallel import SpaceMesh, multichip_devices

    devs = multichip_devices(n)
    if len(devs) < n:
        pytest.skip(f"need {n} devices")
    return SpaceMesh(devs)


def cpu_oracle(frames, cap):
    eng = AOIEngine(default_backend="cpu")
    h = eng.create_space(cap)
    out = []
    for fr in frames:
        eng.submit(h, *fr)
        eng.flush()
        out.append(eng.take_events(h))
    return out


def assert_stream_parity(out, oracle, name):
    for t, ((e, l), (ce, cl)) in enumerate(zip(out, oracle)):
        np.testing.assert_array_equal(e, ce, err_msg=f"{name} enter t={t}")
        np.testing.assert_array_equal(l, cl, err_msg=f"{name} leave t={t}")


# -- clustered-crowd skew on the single chip ----------------------------------


def clustered_frames(cap, n, ticks, world=2000.0, seed=23):
    """Spread -> one-cluster storm -> dispersal (the bench's skew)."""
    rng = np.random.default_rng(seed)
    x0 = rng.uniform(0, world, cap).astype(np.float32)
    z0 = rng.uniform(0, world, cap).astype(np.float32)
    tx = world / 2 + rng.uniform(-40, 40, cap)
    tz = world / 2 + rng.uniform(-40, 40, cap)
    r = np.full(cap, 100.0, np.float32)
    act = np.zeros(cap, bool)
    act[:n] = True
    frames = []
    for t in range(ticks):
        f = 1.0 if 2 <= t < ticks - 1 else 0.0
        x = np.clip(x0 * (1 - f) + tx * f + rng.uniform(-2, 2, cap),
                    0, world).astype(np.float32)
        z = np.clip(z0 * (1 - f) + tz * f + rng.uniform(-2, 2, cap),
                    0, world).astype(np.float32)
        frames.append((x, z, r, act))
    return frames


def test_clustered_skew_single_chip_cap_settles():
    """The mass enter and the dispersal overflow the triples cap: each is
    recovered from the full grid (counted) and grows the cap.  Quiet
    ticks after the storm decay the cap back to its floor, and the
    bucket reports steady -- bit-exact throughout."""
    cap, n = 1024, 800
    frames = clustered_frames(cap, n, 5)
    frames += [frames[-1]] * 24  # the dispersed crowd stands still
    oracle = cpu_oracle(frames, cap)
    eng = AOIEngine(default_backend="tpu")
    h = eng.create_space(cap)
    b = h.bucket
    floor = b._max_triples
    out, peak = [], floor
    for fr in frames:
        eng.submit(h, *fr)
        eng.flush()
        out.append(eng.take_events(h))
        peak = max(peak, b._max_triples)
    assert_stream_parity(out, oracle, "capped")
    assert b.stats["decode_overflow"] >= 1
    assert peak > floor, "the storm never grew the cap"
    assert b._max_triples == floor, "the cap did not settle after the storm"
    assert b._steady


# -- a cap too small for any tick on the mesh and row-sharded tiers -----------


def _forced_overflow_tier(plan=None, rowshard=False, cap=1024, n=500):
    """Mesh / row-shard engine with _max_chunks=1: the first real tick
    takes the counted full-grid recovery and grows the caps; with
    ``plan`` a fault lands on that tick too.  Returns the final stats,
    whether the caps grew, and ``decode_overflow`` after each tick
    (counted per chip)."""
    if plan is not None:
        faults.install(plan)
    kw = {"rowshard_min_capacity": cap} if rowshard else {}
    eng = AOIEngine(default_backend="tpu", mesh=make_mesh(8), **kw)
    h = eng.create_space(cap)
    if rowshard:
        from goworld_tpu.engine.aoi_rowshard import _RowShardTPUBucket

        assert isinstance(h.bucket, _RowShardTPUBucket)
    h.bucket._max_chunks = 1
    h.bucket._step_cache.clear()
    rng = np.random.default_rng(3)
    x = rng.uniform(0, 600, cap).astype(np.float32)
    z = rng.uniform(0, 600, cap).astype(np.float32)
    r = np.full(cap, 80, np.float32)
    act = np.zeros(cap, bool)
    act[:n] = True
    oracle = AOIEngine(default_backend="cpu")
    oh = oracle.create_space(cap)
    outs, oouts, overflows = [], [], []
    for _t in range(4):
        x = np.clip(x + rng.uniform(-25, 25, cap), 0, 600) \
            .astype(np.float32)
        z = np.clip(z + rng.uniform(-25, 25, cap), 0, 600) \
            .astype(np.float32)
        eng.submit(h, x, z, r, act)
        oracle.submit(oh, x, z, r, act)
        eng.flush(); oracle.flush()
        outs.append(eng.take_events(h))
        oouts.append(oracle.take_events(oh))
        overflows.append(h.bucket.stats["decode_overflow"])
    assert_stream_parity(outs, oouts, "rowshard" if rowshard else "mesh")
    st = dict(h.bucket.stats)
    grown = h.bucket._max_chunks > 1
    faults.clear()
    return st, grown, overflows


@pytest.mark.parametrize("rowshard", [False, True],
                         ids=["mesh", "rowshard"])
def test_forced_overflow_multichip(rowshard):
    """Per-chip overflow on the mesh / row-shard tier: the tick is
    recovered from the full grid, counted in decode_overflow, and the
    caps grow so later ticks compact on the device again."""
    st, grown, overflows = _forced_overflow_tier(rowshard=rowshard)
    assert overflows[0] >= 1 and grown
    assert overflows[-1] == overflows[0], \
        f"the grown caps did not fit the later ticks: {overflows}"


@pytest.mark.parametrize("rowshard", [False, True],
                         ids=["mesh", "rowshard"])
def test_forced_overflow_faults_multichip(rowshard):
    """aoi.fetch oom and aoi.scalars poison on the overflowing tick of
    the mesh / row-shard tier: the fault is recovered (rebuild /
    poisoned-scalar full-grid read), the next tick overflows and grows
    the caps, and events stay bit-exact."""
    plan = faults.FaultPlan()
    plan.add("aoi.fetch", "oom", at=1)
    st, grown, _ = _forced_overflow_tier(plan=plan, rowshard=rowshard)
    assert st["rebuilds"] >= 1
    assert st["decode_overflow"] >= 1 and grown
    plan = faults.FaultPlan()
    plan.add("aoi.scalars", "poison", at=1)
    st, grown, _ = _forced_overflow_tier(plan=plan, rowshard=rowshard)
    assert st["poisoned"] >= 1
    assert st["decode_overflow"] >= 1 and grown


# -- a tiny triples cap on the single chip ------------------------------------


def test_tiny_triples_cap_overflows_and_rearms():
    """A cap and decay floor of 4 overflow on the first tick (counted,
    republished bit-exactly the same tick); the cap grows, and the
    decay window then refits it to the walk's own peak -- above the
    tiny floor, below the storm-sized growth."""
    cap = 256
    frames = list(random_walk_scenario(7, cap, 220, 20))
    oracle = cpu_oracle(frames, cap)
    eng = AOIEngine(default_backend="tpu")
    h = eng.create_space(cap)
    b = h.bucket
    b._max_triples = 4
    b._tri = _TriCapDecay(floor=4)
    out, peak = [], 4
    for fr in frames:
        eng.submit(h, *fr)
        eng.flush()
        out.append(eng.take_events(h))
        peak = max(peak, b._max_triples)
    assert_stream_parity(out, oracle, "tiny cap")
    assert b.stats["decode_overflow"] >= 1
    assert 4 < b._max_triples < peak, (b._max_triples, peak)


# -- fault seams on single-chip ticks past their triples cap -----------------


def _seamed_walk(plan):
    """A walk whose every tick is past a pinned triples cap of 4 (each
    is recovered from the full grid), under ``plan``."""
    cap = 256
    frames = list(random_walk_scenario(9, cap, 220, 5))
    oracle = cpu_oracle(frames, cap)
    faults.install(plan)
    eng = AOIEngine(default_backend="tpu")
    h = eng.create_space(cap)
    out = []
    for fr in frames:
        h.bucket._max_triples = 4
        eng.submit(h, *fr)
        eng.flush()
        out.append(eng.take_events(h))
    faults.clear()
    assert_stream_parity(out, oracle, "seamed")
    return dict(h.bucket.stats)


def test_fetch_oom_past_triples_cap_rebuilds():
    """aoi.fetch:oom on a tick past its cap: the harvest rebuilds from
    the host shadows and republishes the SAME tick bit-exactly."""
    plan = faults.FaultPlan()
    plan.add("aoi.fetch", "oom", at=3)
    st = _seamed_walk(plan)
    assert st["rebuilds"] >= 1 and st["host_ticks"] >= 1
    assert st["decode_overflow"] >= 1
    assert st["calc_level"] == 0  # a transfer OOM must not demote calc


def test_fetch_partial_past_triples_cap_is_absorbed():
    """aoi.fetch:partial is no device fault: the harvest of a tick past
    its cap goes on through the counted full-grid recovery, nothing is
    rebuilt, and the stream stays bit-exact."""
    plan = faults.FaultPlan()
    plan.add("aoi.fetch", "partial", at=2)
    st = _seamed_walk(plan)
    assert st["rebuilds"] == 0 and st["host_ticks"] == 0
    assert st["decode_overflow"] == 5


def test_scalars_poison_past_triples_cap():
    """aoi.scalars:poison corrupts the triple count of a tick past its
    cap; validation catches it and the tick is read from the full grid
    -- counted in poisoned, not in decode_overflow, still bit-exact."""
    plan = faults.FaultPlan()
    plan.add("aoi.scalars", "poison", at=3)
    st = _seamed_walk(plan)
    assert st["poisoned"] >= 1
    assert st["decode_overflow"] == 4
    assert st["calc_level"] == 0 and st["rebuilds"] == 0
