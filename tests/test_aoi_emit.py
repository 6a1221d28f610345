"""Emit-path seam tests (docs/perf.md emit paths, docs/robustness.md).

The contract: every ``aoi_emit`` mode -- ``native`` (C++ fan-out),
``vector`` (NumPy sort), ``host`` (the original word-stream decode, the
oracle) -- delivers a byte-identical enter/leave stream on every tier,
through pipelining, the split-phase flush scheduler, -0.0 positions,
unsubscribed slots, slot reuse, triple-cap overflow (a counted fallback,
never a silent truncation), and an injected ``aoi.emit`` fault (local
demotion to host, same tick, bit-exact).
"""

import numpy as np
import pytest

from goworld_tpu import faults
from goworld_tpu.engine.aoi import AOIEngine
from goworld_tpu.ops import aoi_emit as AE
from goworld_tpu.ops import events as EV

MODES = ("native", "vector", "host")


def _drive(eng, h, walks, pad_cap):
    """Submit each (x, z, r, act) frame to one space; per-tick events."""
    out = []
    for x, z, r, act in walks:
        eng.submit(h, x, z, r, act)
        eng.flush()
        out.append(eng.take_events(h))
    return out


def _walk(seed, cap, n, ticks, world=600.0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0, world, n).astype(np.float32)
    z = rng.uniform(0, world, n).astype(np.float32)
    r = rng.uniform(60, 120, n).astype(np.float32)
    act = np.zeros(cap, bool)
    act[:n] = True

    def pad(a):
        o = np.zeros(cap, a.dtype)
        o[:n] = a
        return o

    frames = []
    for _ in range(ticks):
        x = np.clip(x + rng.uniform(-15, 15, n).astype(np.float32), 0, world)
        z = np.clip(z + rng.uniform(-15, 15, n).astype(np.float32), 0, world)
        frames.append((pad(x), pad(z), pad(r), act.copy()))
    return frames


def _assert_stream_equal(got, want, label):
    for t, ((ge, gl), (we, wl)) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(ge, we,
                                      err_msg=f"{label}: enter tick {t}")
        np.testing.assert_array_equal(gl, wl,
                                      err_msg=f"{label}: leave tick {t}")


def _modes():
    # native degrades to vector without the toolchain -- asserting parity
    # on a silently-degraded "native" run would test vector twice
    return MODES if AE.available() else ("vector", "host")


# ---------------------------------------------------------------- resolution

def test_mode_resolution_and_validation():
    assert AE.resolve_mode("auto") in ("native", "vector")
    assert AE.resolve_mode("host") == "host"
    if not AE.available():
        assert AE.resolve_mode("native") == "vector"
    with pytest.raises(ValueError):
        AE.resolve_mode("bogus")
    with pytest.raises(ValueError):
        AOIEngine(default_backend="tpu", emit="bogus")


# ------------------------------------------------------- single-chip parity

@pytest.mark.parametrize("pipeline,flush_sched",
                         [(False, True), (True, True), (False, False)])
def test_single_chip_mode_parity(pipeline, flush_sched):
    """All modes byte-identical to the CPU oracle, with and without the
    flush pipeline and the split-phase scheduler (two buckets so the
    scheduler has cross-bucket work)."""
    cap, n, ticks = 256, 180, 3
    frames = [_walk(5, cap, n, ticks), _walk(6, cap, n - 30, ticks)]
    runs = {}
    for mode in _modes() + ("cpu",):
        if mode == "cpu":
            eng = AOIEngine(default_backend="cpu")
        else:
            eng = AOIEngine(default_backend="tpu", pipeline=pipeline,
                            flush_sched=flush_sched, emit=mode)
        hs = [eng.create_space(cap), eng.create_space(cap)]
        out = []
        for t in range(ticks):
            for h, fr in zip(hs, frames):
                eng.submit(h, *fr[t])
            eng.flush()
            out.append([eng.take_events(h) for h in hs])
        if mode != "cpu" and pipeline:
            eng.flush()  # trailing drain: the pipe runs one tick late
            out.append([eng.take_events(h) for h in hs])
            out = out[1:]
        runs[mode] = out
    for mode in _modes():
        for t, (got, want) in enumerate(zip(runs[mode], runs["cpu"])):
            for s, ((ge, gl), (we, wl)) in enumerate(zip(got, want)):
                np.testing.assert_array_equal(
                    ge, we, err_msg=f"{mode}: enter t={t} space={s}")
                np.testing.assert_array_equal(
                    gl, wl, err_msg=f"{mode}: leave t={t} space={s}")


def test_negative_zero_positions_parity():
    """-0.0 == 0.0 in the predicate but their bit patterns differ -- the
    triples decode must deliver the same events as the host oracle."""
    cap, n = 128, 24
    x = np.zeros(cap, np.float32)
    x[:n:2] = -0.0
    x[1:n:2] = 0.0
    r = np.zeros(cap, np.float32)
    r[:n] = 10.0
    act = np.zeros(cap, bool)
    act[:n] = True
    x2 = x.copy()
    x2[:n // 2] = 500.0  # second tick: half walk away -> leave events
    frames = [(x, x, r, act), (x2, x2, r, act)]
    runs = {}
    for mode in _modes() + ("cpu",):
        eng = (AOIEngine(default_backend="cpu") if mode == "cpu"
               else AOIEngine(default_backend="tpu", emit=mode))
        h = eng.create_space(cap)
        runs[mode] = _drive(eng, h, frames, cap)
    for mode in _modes():
        _assert_stream_equal(runs[mode], runs["cpu"], mode)


def test_unsubscribe_and_slot_reuse_tri_path():
    """The triples path's all-unsubscribed branch publishes nothing, a
    re-subscribed slot replays nothing stale, and a released slot's reuse
    sees no ghost events."""
    cap, n = 128, 8
    x = np.zeros(cap, np.float32)
    r = np.full(cap, 10, np.float32)
    act = np.zeros(cap, bool)
    act[:n] = True
    for mode in _modes():
        eng = AOIEngine(default_backend="tpu", emit=mode)
        h1 = eng.create_space(cap)
        eng.submit(h1, x, x, r, act)
        eng.flush()
        e, l = eng.take_events(h1)
        assert len(e) == n * (n - 1), mode
        eng.set_subscribed(h1, False)
        eng.submit(h1, x, x, r, act)
        eng.flush()
        e, l = eng.take_events(h1)
        assert len(e) == 0 and len(l) == 0, f"{mode}: unsubscribed events"
        eng.set_subscribed(h1, True)
        eng.submit(h1, x, x, r, act)
        eng.flush()
        e, l = eng.take_events(h1)
        assert len(e) == 0 and len(l) == 0, f"{mode}: stale replay"
        eng.release_space(h1)
        h2 = eng.create_space(cap)
        assert h2.slot == h1.slot
        eng.submit(h2, x, x, r, np.zeros(cap, bool))
        eng.flush()
        e, l = eng.take_events(h2)
        assert len(e) == 0 and len(l) == 0, f"{mode}: ghost events on reuse"


# --------------------------------------------------------- multi-chip tiers

def _make_mesh(n=8):
    from goworld_tpu.parallel import SpaceMesh, multichip_devices

    devs = multichip_devices(n)
    if len(devs) < n:
        pytest.skip(f"need {n} devices")
    return SpaceMesh(devs)


@pytest.mark.parametrize("mode", ("native", "vector"))
def test_mesh_tier_mode_parity(mode):
    """Mesh bucket: the emit layer expands the per-chip word streams
    (native C++ word fan-out vs the host expansion) bit-identically."""
    if mode == "native" and not AE.available():
        pytest.skip("libgwemit unavailable")
    mesh = _make_mesh(8)
    eng = AOIEngine(default_backend="tpu", mesh=mesh, emit=mode)
    oracle = AOIEngine(default_backend="cpu")
    cap, n, spaces, ticks = 1024, 300, 8, 2
    frames = [_walk(30 + s, cap, n, ticks, world=2000.0)
              for s in range(spaces)]
    hs = [eng.create_space(cap) for _ in range(spaces)]
    ohs = [oracle.create_space(cap) for _ in range(spaces)]
    for t in range(ticks):
        for e, hh in ((eng, hs), (oracle, ohs)):
            for h, fr in zip(hh, frames):
                e.submit(h, *fr[t])
            e.flush()
        for s, (h, oh) in enumerate(zip(hs, ohs)):
            ge, gl = eng.take_events(h)
            we, wl = oracle.take_events(oh)
            np.testing.assert_array_equal(
                ge, we, err_msg=f"{mode}: enter t={t} space={s}")
            np.testing.assert_array_equal(
                gl, wl, err_msg=f"{mode}: leave t={t} space={s}")


@pytest.mark.parametrize("mode", ("native", "vector"))
def test_rowshard_tier_mode_parity(mode):
    """Row-sharded bucket: per-chip decoded words ride the same emit
    layer; events bit-identical to the oracle."""
    if mode == "native" and not AE.available():
        pytest.skip("libgwemit unavailable")
    mesh = _make_mesh(8)
    eng = AOIEngine(default_backend="tpu", mesh=mesh,
                    rowshard_min_capacity=1024, emit=mode)
    oracle = AOIEngine(default_backend="cpu")
    cap, n, ticks = 1024, 400, 2
    from goworld_tpu.engine.aoi_rowshard import _RowShardTPUBucket

    h = eng.create_space(cap)
    assert isinstance(h.bucket, _RowShardTPUBucket)
    oh = oracle.create_space(cap)
    for t, fr in enumerate(_walk(41, cap, n, ticks, world=1500.0)):
        for e, hh in ((eng, h), (oracle, oh)):
            e.submit(hh, *fr)
            e.flush()
        ge, gl = eng.take_events(h)
        we, wl = oracle.take_events(oh)
        np.testing.assert_array_equal(ge, we,
                                      err_msg=f"{mode}: enter t={t}")
        np.testing.assert_array_equal(gl, wl,
                                      err_msg=f"{mode}: leave t={t}")


# ------------------------------------------------- overflow counted fallback

def test_tri_overflow_counted_fallback_parity():
    """Shrinking the triple cap forces the counted full-diff fallback:
    events stay bit-identical, ``decode_overflow`` counts every overflowed
    tick, and the cap grows so later ticks return to the compact path."""
    cap, n, ticks = 256, 180, 3
    frames = _walk(7, cap, n, ticks)
    oracle = AOIEngine(default_backend="cpu")
    oh = oracle.create_space(cap)
    want = _drive(oracle, oh, frames, cap)
    for mode in [m for m in _modes() if m != "host"]:
        eng = AOIEngine(default_backend="tpu", emit=mode)
        h = eng.create_space(cap)
        b = h.bucket
        b._max_triples = 4  # any real tick overflows
        got = _drive(eng, h, frames, cap)
        _assert_stream_equal(got, want, mode)
        assert b.stats["decode_overflow"] >= 1, mode
        assert b._max_triples > 4, f"{mode}: cap never grew"
        assert b.stats["emit_path"] == AE.EMIT_LEVEL[mode], \
            f"{mode}: overflow must not demote the emit path"


def test_pairs_overflow_host_regression():
    """Classic word-stream path (emit=host): a per-chunk cap overflow falls
    back to the full-diff recovery built from the already-fetched words --
    counted in ``decode_overflow``, events bit-identical."""
    cap, n, ticks = 256, 220, 3
    frames = _walk(9, cap, n, ticks)
    oracle = AOIEngine(default_backend="cpu")
    want = _drive(oracle, oracle.create_space(cap), frames, cap)
    eng = AOIEngine(default_backend="tpu", emit="host")
    h = eng.create_space(cap)
    h.bucket._kcap = 4
    got = _drive(eng, h, frames, cap)
    _assert_stream_equal(got, want, "host/kcap4")
    assert h.bucket.stats["decode_overflow"] >= 1


# -------------------------------------------------------- fault-seam demotion

def test_emit_fault_demotes_to_host_bit_exact():
    """An ``aoi.emit`` fault is handled locally: the faulted tick's events
    republish through the host decode bit-exactly, the bucket sticks to
    host (``emit_path`` level 2), and ``reset_emit_path`` re-arms."""
    cap, n, ticks = 256, 180, 3
    frames = _walk(13, cap, n, ticks)
    oracle = AOIEngine(default_backend="cpu")
    want = _drive(oracle, oracle.create_space(cap), frames, cap)
    for mode in [m for m in _modes() if m != "host"]:
        faults.install("aoi.emit:fail@1")
        try:
            eng = AOIEngine(default_backend="tpu", emit=mode)
            h = eng.create_space(cap)
            got = _drive(eng, h, frames, cap)
        finally:
            faults.clear()
        _assert_stream_equal(got, want, f"{mode}+fault")
        b = h.bucket
        assert b._emit == "host" and b.stats["emit_path"] == 2, mode
        b.reset_emit_path()
        assert b._emit == mode
        assert b.stats["emit_path"] == AE.EMIT_LEVEL[mode]


# ------------------------------------------------------------ unit: fan-out

def test_fanout_triples_vector_matches_host_expansion():
    """fanout_triples (both backends) == expand_classified_host on the
    word-equivalent of the same triples."""
    rng = np.random.default_rng(2)
    cap = 256
    n = 500
    obs = rng.integers(0, 4 * cap, n)
    j = rng.integers(0, cap, n)
    key = obs * cap + j
    _, keep = np.unique(key, return_index=True)  # unique (obs, j) pairs
    tri = np.stack([obs[keep], j[keep],
                    rng.integers(0, 2, len(keep))], 1).astype(np.int32)
    ve, vl = AE.fanout_triples(tri, cap, native=False)
    chg_vals, ent_vals, gidx = EV.triples_to_words(tri, cap)
    we, wl = EV.expand_classified_host(chg_vals, ent_vals, gidx, cap, 4)
    np.testing.assert_array_equal(ve, we)
    np.testing.assert_array_equal(vl, wl)
    if AE.available():
        ne, nl = AE.fanout_triples(tri, cap, native=True)
        np.testing.assert_array_equal(ne, we)
        np.testing.assert_array_equal(nl, wl)
        xe, xl = AE.expand_words_native(chg_vals, ent_vals, gidx, cap)
        np.testing.assert_array_equal(xe, we)
        np.testing.assert_array_equal(xl, wl)


@pytest.mark.parametrize("max_triples", [4096, 256], ids=["fits", "capped"])
def test_extract_triples_matches_loop_reference(max_triples):
    """Device triple compaction vs a plain loop: the rows are every set
    bit of the change grid in (word, bit) order, (observer, observed,
    kind), -1-filled -- and the first ``max_triples`` of them when the
    tick overflows (the caller then falls back on ``count``)."""
    import jax.numpy as jnp

    from goworld_tpu.ops.aoi_predicate import words_per_row

    rng = np.random.default_rng(5)
    s, c = 2, 256
    w = words_per_row(c)
    chg = np.where(rng.random((s, c, w)) < 0.05,
                   rng.integers(1, 2**32, (s, c, w), dtype=np.uint64),
                   0).astype(np.uint32)
    new = rng.integers(0, 2**32, (s, c, w), dtype=np.uint64).astype(np.uint32)
    ref = []
    flat_c, flat_n = chg.reshape(-1), new.reshape(-1)
    for g in np.nonzero(flat_c)[0]:
        for k in range(32):
            if (int(flat_c[g]) >> k) & 1:
                ref.append((g // w, k * w + g % w, (int(flat_n[g]) >> k) & 1))
    tri, count = EV.extract_triples(jnp.asarray(chg), jnp.asarray(new), c,
                                    max_triples)
    tri = np.asarray(tri)
    assert int(count) == len(ref)
    n = min(len(ref), max_triples)
    assert tri[:n].tolist() == [list(r) for r in ref[:n]]
    assert (tri[n:] == -1).all()
