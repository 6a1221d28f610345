"""A dense crowd on the single-chip triples path (docs/perf.md emit paths).

A hot square holding most of a space's entities, all moving: the mass
enter is far larger than any steady tick.  The triples cap grows to the
ceiling the bucket's grid allows (``_tri_ceiling``), so only the mass
enter takes the counted full-grid recovery; every steady tick compacts on
the device, bit-exact with the CPU oracle, and ``aoi.triples`` counts
what it delivered.  The scratch pool keeps only sets of the current caps,
and ``aoi.scratch_bytes`` reports what it holds.
"""

import numpy as np
import pytest

from goworld_tpu import telemetry
from goworld_tpu.engine import aoi as A
from goworld_tpu.engine.aoi import AOIEngine
from goworld_tpu.ops import aoi_emit as AE

CAP, N, TICKS = 512, 480, 12


def _crowd(seed, cap=CAP, n=N, ticks=TICKS, world=2000.0, step=5.0):
    """A mass-enter frame, then ``ticks`` frames of everyone moving; 90%
    of the entities start in the central square of a tenth of the side."""
    rng = np.random.default_rng(seed)
    hot = rng.random(n) < 0.9
    lo, hi = 0.45 * world, 0.55 * world

    def spread():
        return np.where(hot, rng.uniform(lo, hi, n),
                        rng.uniform(0, world, n)).astype(np.float32)

    x, z = spread(), spread()
    act = np.zeros(cap, bool)
    act[:n] = True
    r = np.where(act, np.float32(100.0), np.float32(0.0))

    def pad(a):
        return np.concatenate([a, np.zeros(cap - n, np.float32)])

    frames = [(pad(x), pad(z), r, act.copy())]
    for _ in range(ticks):
        x = np.clip(x + rng.uniform(-step, step, n), 0, world).astype(
            np.float32)
        z = np.clip(z + rng.uniform(-step, step, n), 0, world).astype(
            np.float32)
        frames.append((pad(x), pad(z), r, act.copy()))
    return frames


def _tri_modes():
    return ("native", "vector") if AE.available() else ("vector",)


def _scratch_gauge(eng):
    return next(s.value for s in eng._telemetry_collect()
                if s.name == "aoi.scratch_bytes")


def test_tri_ceiling_follows_the_grid():
    # the uniform cell's grid and anything smaller keep 2^18, so its step
    # compiles with the caps it always had
    assert A._tri_ceiling(8 * 16384 * 512) == 1 << 18
    assert A._tri_ceiling(1 * 1024 * 32) == 1 << 18
    # one 131072-entity space: a dense crowd's ~0.6M events a tick, with
    # the decay's 1.5x margin, fit
    assert A._tri_ceiling(1 * 131072 * 4096) >= 1 << 20
    assert A._tri_ceiling(1 * 131072 * 4096) >= 583_012 * 3 // 2


@pytest.mark.parametrize("mode", _tri_modes())
def test_crowd_overflows_only_at_the_mass_enter(monkeypatch, mode):
    frames = _crowd(3)
    oracle = AOIEngine(default_backend="cpu")
    oh = oracle.create_space(CAP)
    want = []
    for f in frames:
        oracle.submit(oh, *f)
        oracle.flush()
        want.append(oracle.take_events(oh))
    steady = max(len(e) + len(l) for e, l in want[1:])
    mass = len(want[0][0]) + len(want[0][1])
    ceiling = 1 << (steady * 3 // 2 - 1).bit_length()
    assert steady * 3 // 2 <= ceiling < mass
    monkeypatch.setattr(A, "_tri_ceiling", lambda words: ceiling)

    eng = AOIEngine(default_backend="tpu", emit=mode)
    h = eng.create_space(CAP)
    b = h.bucket
    b._max_triples = 1024  # the mass enter has to grow the cap
    telemetry.enable()
    try:
        for t, f in enumerate(frames):
            before = A._TRIPLES.value
            eng.submit(h, *f)
            eng.flush()
            got = eng.take_events(h)
            for g, w, kind in zip(got, want[t], ("enter", "leave")):
                np.testing.assert_array_equal(g, w, err_msg=f"{kind} {t}")
            assert b._max_triples <= ceiling
            if t == 0:
                assert b._max_triples == ceiling, "cap did not grow"
                assert A._TRIPLES.value == before, "an overflow counted"
            else:
                assert A._TRIPLES.value - before == (len(want[t][0])
                                                     + len(want[t][1]))
        overflows = [s for s in telemetry.trace.spans()
                     if s[0] == "aoi.overflow"]
    finally:
        telemetry.disable()
    assert b.stats["decode_overflow"] == 1
    assert len(overflows) == 1
    assert b.stats["emit_path"] == AE.EMIT_LEVEL[mode]


@pytest.mark.parametrize("pipeline", [False, True],
                         ids=["serial", "pipelined"])
def test_scratch_pool_drops_sets_the_caps_left(pipeline):
    frames = _crowd(5, ticks=14)
    eng = AOIEngine(default_backend="tpu", pipeline=pipeline)
    h = eng.create_space(CAP)
    b = h.bucket
    b._max_triples = 1024            # grows at the mass enter
    b._tri = A._TriCapDecay(floor=256)  # then decays after 8 flushes
    keys = {b._scratch_key(1)}
    for f in frames + frames[-1:] * 2:
        eng.submit(h, *f)
        eng.flush()
        assert all(k == b._scratch_key(k[0]) for k in b._scratch), (
            f"a stale set is pooled: {list(b._scratch)} at cap "
            f"{b._max_triples}")
        assert len(b._scratch) <= 1
        assert _scratch_gauge(eng) == sum(
            a.nbytes for s in b._scratch.values() for a in s)
        keys.add(b._scratch_key(1))
    assert len(keys) >= 3, f"the cap neither grew nor decayed: {keys}"
    assert _scratch_gauge(eng) > 0
