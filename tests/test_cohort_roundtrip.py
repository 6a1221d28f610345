"""Property-style round trip for cohort planes (``ops/aoi_cohort``).

The stacking contract (satellite of the space-stacked megabatch PR):
packing N spaces into shared ``[S, shape]`` cohort planes and unpacking
them preserves every slot's x/z/r/act/sub -- and the packed interest
words -- BIT-exactly, across:

* mixed per-space capacities padded up to the ladder shape;
* ``pad_snapshot`` growth between ladder rungs (pow2 planar repack and
  the dense fallback both);
* slot release + reuse inside a live cohort bucket;
* one crowded member past the cohort bucket's shared triples cap beside
  quiet ones (counted full-grid recovery, the cap grows, events stay
  bit-exact).

Positions are compared by BIT PATTERN (``view(uint32)``), never float
equality -- the delta-staging discipline (NaN payloads, -0.0 vs 0.0).
"""

import numpy as np
import pytest

from goworld_tpu.engine.aoi import AOIEngine
from goworld_tpu.ops import aoi_cohort as AC
from goworld_tpu.ops import aoi_predicate as P


def _snap(rng, cap, n=None, weird_floats=True):
    """A random migration snapshot at ``cap`` in the engine's
    _build_snapshot wire format (packet rows all-zero, cols = entity
    indices)."""
    n = int(rng.integers(1, cap)) if n is None else n
    cols = np.sort(rng.choice(cap, n, replace=False)).astype(np.int64)
    x = rng.uniform(-500, 500, n).astype(np.float32)
    z = rng.uniform(-500, 500, n).astype(np.float32)
    if weird_floats and n >= 3:
        x[0] = np.float32(-0.0)  # bit pattern 0x80000000 must survive
        z[1] = np.frombuffer(
            np.uint32(0x7FC0_0001).tobytes(), np.float32)[0]  # NaN payload
    r = np.zeros(cap, np.float32)
    r[cols] = rng.uniform(10, 120, n).astype(np.float32)
    act = np.zeros(cap, bool)
    act[cols] = rng.random(n) < 0.9
    m = np.zeros((cap, cap), bool)
    live = cols[act[cols]]
    if len(live) > 1:
        a = rng.choice(live, len(live) // 2, replace=False)
        b = rng.choice(live, len(live) // 2, replace=False)
        m[a, b] = True
        m[b, a] = True
    np.fill_diagonal(m, False)
    from goworld_tpu.ops import aoi_stage as AS

    pkt = tuple(np.ascontiguousarray(v) for v in AS.pad_packet(
        np.zeros(n, np.int64), cols, x, z))
    return {"capacity": cap, "packet": pkt, "r": r, "act": act,
            "sub": bool(rng.random() < 0.8), "words": P.pack_rows(m)}


def _dense_xz(snap, shape):
    x = np.zeros(shape, np.float32)
    z = np.zeros(shape, np.float32)
    _rows, cols, xv, zv = snap["packet"]
    x[cols] = xv
    z[cols] = zv
    return x, z


def _assert_snap_equal(a, b, cap, msg=""):
    ax, az = _dense_xz(a, cap)
    bx, bz = _dense_xz(b, cap)
    np.testing.assert_array_equal(ax.view(np.uint32), bx.view(np.uint32),
                                  err_msg=f"{msg} x bits")
    np.testing.assert_array_equal(az.view(np.uint32), bz.view(np.uint32),
                                  err_msg=f"{msg} z bits")
    np.testing.assert_array_equal(a["r"], b["r"], err_msg=f"{msg} r")
    np.testing.assert_array_equal(a["act"], b["act"], err_msg=f"{msg} act")
    assert a["sub"] == b["sub"], msg
    np.testing.assert_array_equal(
        P.unpack_rows(a["words"], cap), P.unpack_rows(b["words"], cap),
        err_msg=f"{msg} words")


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_stack_unstack_round_trip(seed):
    """N mixed-capacity snapshots -> planes at the ladder shape -> back:
    every slot bit-exact, padded tails all-zero/inactive."""
    rng = np.random.default_rng(seed)
    caps = [int(rng.choice((128, 256, 384, 512, 1024)))
            for _ in range(int(rng.integers(2, 7)))]
    shape = max(AC.cohort_shape(c) for c in caps)
    snaps = [_snap(rng, c) for c in caps]
    planes = stacked = AC.stack_spaces(snaps, shape)
    # padded tails carry nothing: inactive, zero radius, zero words
    for s, cap in enumerate(caps):
        assert not stacked["act"][s, cap:].any()
        assert not stacked["r"][s, cap:].any()
        assert not stacked["words"][s, cap:].any()
    back = AC.unstack_spaces(planes, caps)
    for i, (snap, rt) in enumerate(zip(snaps, back)):
        _assert_snap_equal(snap, rt, caps[i], msg=f"space {i} (seed {seed})")


@pytest.mark.parametrize("cap,shape", [(256, 1024), (384, 1024),
                                       (128, 256), (256, 4096)])
def test_pad_snapshot_rungs_lossless(cap, shape):
    """pad_snapshot between rungs (pow2 planar repack AND the dense
    non-pow2-ratio fallback) never loses a bit; shrinking raises."""
    rng = np.random.default_rng(cap + shape)
    snap = _snap(rng, cap)
    padded = AC.pad_snapshot(snap, shape)
    assert padded["capacity"] == shape
    m0 = P.unpack_rows(snap["words"], cap)
    m1 = P.unpack_rows(padded["words"], shape)
    np.testing.assert_array_equal(m1[:cap, :cap], m0)
    assert not m1[cap:].any() and not m1[:, cap:].any()
    np.testing.assert_array_equal(padded["r"][:cap], snap["r"])
    assert not padded["act"][cap:].any()
    with pytest.raises(ValueError):
        AC.pad_snapshot(padded, cap)


def test_round_trip_through_live_cohort_bucket():
    """import_snapshot -> export_snapshot through a live cohort bucket is
    the identity at the ladder shape, including after slot release +
    reuse (a recycled slot starts clean, then carries the new space)."""
    rng = np.random.default_rng(5)
    eng = AOIEngine(default_backend="tpu", cohort="auto")
    hs = [eng.create_space(200) for _ in range(3)]
    bucket = hs[0].bucket
    snaps = [AC.pad_snapshot(_snap(rng, 128), 256) for _ in hs]
    for h, s in zip(hs, snaps):
        bucket.import_snapshot(h.slot, s)
    for h, s in zip(hs, snaps):
        _assert_snap_equal(s, bucket.export_snapshot(h.slot), 256,
                           msg=f"slot {h.slot}")
    # slot reuse: release the middle space, a new one takes its slot
    freed = hs[1].slot
    eng.release_space(hs[1])
    nh = eng.create_space(240)
    assert nh.bucket is bucket and nh.slot == freed
    ns = AC.pad_snapshot(_snap(rng, 128), 256)
    bucket.import_snapshot(nh.slot, ns)
    _assert_snap_equal(ns, bucket.export_snapshot(nh.slot), 256,
                       msg="reused slot")
    # the neighbors were untouched by the reuse
    for h, s in ((hs[0], snaps[0]), (hs[2], snaps[2])):
        _assert_snap_equal(s, bucket.export_snapshot(h.slot), 256,
                           msg=f"neighbor slot {h.slot}")


def test_round_trip_survives_grow():
    """grow_space across a rung boundary repacks the carried words
    losslessly: the grown space's interest matrix equals the original in
    its top-left corner, zero elsewhere."""
    rng = np.random.default_rng(9)
    eng = AOIEngine(default_backend="tpu", cohort="auto")
    h = eng.create_space(256)
    snap = _snap(rng, 256)
    h.bucket.import_snapshot(h.slot, snap)
    m0 = P.unpack_rows(snap["words"], 256)
    nh = eng.grow_space(h, 512)  # rounds up to rung 1024
    assert nh.capacity == 1024
    m1 = P.unpack_rows(nh.bucket.get_prev(nh.slot), 1024)
    np.testing.assert_array_equal(m1[:256, :256], m0)
    assert not m1[256:].any() and not m1[:, 256:].any()


def test_cohort_crowded_member_past_shared_triples_cap():
    """The triples cap is bucket-wide: one crowded space beside quiet
    ones drives the shared cohort bucket past a tiny cap.  The tick takes
    the counted full-grid recovery, the cap grows, and every member's
    stream stays bit-exact vs the oracle and the solo baseline."""
    from test_aoi_delta import _pad, _scene, _sparse_step

    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "cohort": AOIEngine(default_backend="tpu", cohort="auto"),
        "solo": AOIEngine(default_backend="tpu", cohort="solo"),
    }
    # one crowded space (dense interest) + two nearly-empty ones
    loads = [(256, 220), (256, 4), (200, 6)]
    handles = {k: [e.create_space(c) for c, _n in loads]
               for k, e in engines.items()}
    bucket = handles["cohort"][0].bucket
    assert all(h.bucket is bucket for h in handles["cohort"]), \
        "one shared bucket"
    bucket._max_triples = 64  # the crowded member alone overflows it
    scenes = [list(_scene(21 + i, cap, n))
              for i, (cap, n) in enumerate(loads)]
    out = {k: [] for k in engines}
    for _t in range(6):
        for (rng, xs, zs, _rr, _act) in scenes:
            _sparse_step(rng, xs, zs)
        for k, e in engines.items():
            for (rng, xs, zs, rr, act), h in zip(scenes, handles[k]):
                cap = h.capacity
                e.submit(h, _pad(xs, cap), _pad(zs, cap), _pad(rr, cap),
                         _pad(act, cap))
            e.flush()
            out[k].append([e.take_events(h) for h in handles[k]])
    for k in ("cohort", "solo"):
        for t in range(6):
            for si in range(len(loads)):
                re_, rl = out["cpu"][t][si]
                pe, pl = out[k][t][si]
                np.testing.assert_array_equal(re_, pe)
                np.testing.assert_array_equal(rl, pl)
    assert bucket.stats["decode_overflow"] >= 1
    assert bucket._max_triples > 64, "the shared cap never grew"
    assert all(h.bucket.stats["decode_overflow"] == 0
               for h in handles["solo"][1:]), "quiet solo spaces overflowed"
