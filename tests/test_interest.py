"""Composable interest-policy subsystem (goworld_tpu/interest/).

The contract under test (docs/perf.md "Interest policies & tiered
rates"):

* the fused device step -- radius AND team mask AND tier cadence AND
  line of sight -- is BIT-EXACT against the composed CPU oracle
  (interest/oracle.py) for every policy combination, standalone and
  behind the engine seam across the bucket tiers with the classic
  encoded stream (``emit="host"``) and the cross-tick scheduler on or
  off;
* stacks with different tier periods agree bit-exactly on coinciding
  full-cadence boundary ticks, with strictly fewer line-of-sight
  samples for the larger period (``interest.los_pair_evals`` -- the
  device work tiered rates save);
* the ``aoi.interest`` fault seam (poisoned mask / stale tier / corrupt
  distance field -- any fired kind) demotes the stack STICKY to the
  radius-only oracle path, counted in ``interest.demotions``, and
  ``PolicyStack.reset_interest`` re-arms it deterministically -- the
  under-fire stream is bit-exact against a manually demoted host twin;
* policy state survives live migration (the handle is re-pointed in
  place; the stack rides it), checkpoint restore (``export_payload``
  rides the pad_packet snapshot; ``attach_interest`` auto-imports the
  restored payload), and capacity growth (planar word repack, no
  spurious events);
* the ECS ``team``/``vis`` columns default to mutual visibility
  (team=1, vis=all-ones) and ``Space.set_aoi_team`` filters live
  entities' interest sets through the normal tick path.

Telemetry pinned here (docs/observability.md): ``interest.steps``,
``interest.full_evals``, ``interest.demotions``, ``interest.host_steps``,
``interest.los_pair_evals``, and the ``aoi.interest`` flush span.
"""

from __future__ import annotations

import numpy as np
import pytest

from goworld_tpu import faults, telemetry
from goworld_tpu.engine.aoi import AOIEngine
from goworld_tpu.engine.checkpoint import (CheckpointController,
                                           _open_backends)
from goworld_tpu.engine.entity import Entity
from goworld_tpu.engine.placement import PlacementController
from goworld_tpu.engine.runtime import Runtime
from goworld_tpu.engine.space import Space
from goworld_tpu.engine.vector import Vector3
from goworld_tpu.interest import (DistanceField, InterestPolicy,
                                  LineOfSightPolicy, PolicyStack,
                                  TeamVisibilityPolicy, TieredRatePolicy)
from goworld_tpu.ops import aoi_predicate as P

CAP = 128        # standalone-stack tests
ENGINE_CAP = 256  # engine-seam tests (row-shard floor on a 2-chip mesh)
N_TICKS = 9      # two full tier periods + change


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    faults.clear()


def _field():
    return DistanceField.from_boxes(
        [(20.0, 20.0, 45.0, 60.0), (-60.0, -10.0, -30.0, 10.0)],
        (-100.0, -100.0), (200.0, 200.0), cell=5.0)


def _policies(combo: str, period: int = 4):
    ps = []
    if "team" in combo:
        ps.append(TeamVisibilityPolicy())
    if "tier" in combo:
        ps.append(TieredRatePolicy(period=period))
    if "los" in combo:
        ps.append(LineOfSightPolicy(_field(), depth=2))
    return ps


def _walk(seed, cap, n):
    """Deterministic random walk with faction columns: positions move,
    team/vis stay (live team edits get their own runtime test)."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(-90.0, 90.0, cap).astype(np.float32)
    z = rng.uniform(-90.0, 90.0, cap).astype(np.float32)
    r = rng.uniform(10.0, 30.0, cap).astype(np.float32)
    act = np.ones(cap, bool)
    team = (np.uint32(1) << rng.integers(0, 4, cap)).astype(np.uint32)
    # most observers see every faction; a few see only faction 0
    vis = np.where(rng.random(cap) < 0.75, 0xFFFFFFFF, 0b1) \
        .astype(np.uint32)
    for _ in range(n):
        x = (x + rng.uniform(-4.0, 4.0, cap)).astype(np.float32)
        z = (z + rng.uniform(-4.0, 4.0, cap)).astype(np.float32)
        yield x.copy(), z.copy(), r, act, team, vis


def _step_both(stacks, frame):
    for s in stacks:
        s.submit(*frame)
        s.step()


# -- device/host stack parity, every policy combination ----------------------

COMBOS = ["team", "tier", "los", "team+tier", "tier+los", "team+tier+los"]


@pytest.mark.parametrize("combo", COMBOS)
def test_stack_device_host_parity(combo):
    dev = PolicyStack(CAP, _policies(combo), mode="device")
    host = PolicyStack(CAP, _policies(combo), mode="host")
    total = 0
    for frame in _walk(7, CAP, N_TICKS):
        _step_both((dev, host), frame)
        de, dl = dev.take_events()
        he, hl = host.take_events()
        assert np.array_equal(de, he), f"{combo}: enter diff diverged"
        assert np.array_equal(dl, hl), f"{combo}: leave diff diverged"
        assert np.array_equal(dev.words, host.words)
        assert np.array_equal(dev.near, host.near)
        total += de.shape[0] + dl.shape[0]
    assert total > 0, "degenerate walk: no events"
    assert dev.stats["steps"] == N_TICKS
    assert dev.stats["demotions"] == 0 and dev.stats["host_steps"] == 0


# -- the engine seam: attach_interest owns the event stream ------------------
#
# The stack evaluates from the submitted host columns, so it is bucket-
# independent by construction; what each tier row verifies is the ENGINE
# integration -- flush stepping the stack after harvest, take_events
# discarding the bucket diff in favor of the stack's, the base bucket
# still carrying radius state underneath.  Fresh mesh/rowshard engines
# re-JIT (~12s each on the CPU backend), so tier-1 keeps one row per
# tier and spreads the +/-host-emit +/-cross_tick axes across them; the
# full cross-product is tier-2 (@slow).

TIER1_ENGINE = [
    ("cpu", False, False),
    ("cpu", True, True),
    ("tpu", True, False),
    ("tpu", False, True),
    ("mesh", False, False),
    ("rowshard", True, True),
]
SLOW_ENGINE = [
    (t, p, c)
    for t in ("cpu", "tpu", "mesh", "rowshard")
    for p in (False, True)
    for c in (False, True)
    if (t, p, c) not in TIER1_ENGINE
]


def _engine_parity(tier, host_emit, cross_tick, cap=ENGINE_CAP):
    mesh = 2 if tier in ("mesh", "rowshard") else None
    eng = AOIEngine("cpu", mesh=mesh, cross_tick=cross_tick,
                    emit="host" if host_emit else "auto")
    h = eng._create_handle(cap, tier)
    stack = eng.attach_interest(h, _policies("team+tier+los"))
    assert AOIEngine.interest_stack(h) is stack
    ref = PolicyStack(cap, _policies("team+tier+los"), mode="host")
    got, want = ([], []), ([], [])
    for x, z, r, act, team, vis in _walk(3, cap, N_TICKS):
        eng.submit(h, x, z, r, act)
        stack.submit(x, z, r, act, team, vis)
        eng.flush()
        e, lv = eng.take_events(h)
        got[0].append(np.asarray(e)), got[1].append(np.asarray(lv))
        ref.submit(x, z, r, act, team, vis)
        ref.step()
        re_, rl = ref.take_events()
        want[0].append(re_), want[1].append(rl)
    while eng.has_pending():  # trailing cross-tick/pipeline flushes
        eng.flush()
        e, lv = eng.take_events(h)
        got[0].append(np.asarray(e)), got[1].append(np.asarray(lv))
    for side, name in ((0, "enter"), (1, "leave")):
        a = np.concatenate(got[side])
        b = np.concatenate(want[side])
        assert np.array_equal(a, b), \
            f"{tier} host_emit={host_emit} xtick={cross_tick}: " \
            f"{name} diverged"
    assert np.array_equal(stack.words, ref.words)
    assert sum(len(v) for v in want[0]) > 0, "degenerate walk: no events"
    assert stack.stats["demotions"] == 0


@pytest.mark.parametrize(
    "tier,host_emit,cross_tick", TIER1_ENGINE,
    ids=[f"{t}{'+host' if p else ''}{'+xtick' if c else ''}"
         for t, p, c in TIER1_ENGINE])
def test_engine_stack_parity(tier, host_emit, cross_tick):
    _engine_parity(tier, host_emit, cross_tick)


@pytest.mark.slow
@pytest.mark.parametrize(
    "tier,host_emit,cross_tick", SLOW_ENGINE,
    ids=[f"{t}{'+host' if p else ''}{'+xtick' if c else ''}"
         for t, p, c in SLOW_ENGINE])
def test_engine_stack_parity_sweep(tier, host_emit, cross_tick):
    _engine_parity(tier, host_emit, cross_tick)


# -- tiered rates: bit-exact on boundary ticks, cheaper in between -----------

def test_period_boundary_bitexact_and_cheaper():
    """K=4 and K=1 stacks agree bit-exactly after every step where
    ``t % 4 == 0`` (both just ran a full eval -- the bench CRC
    invariant), and the K=4 stack samples the distance field strictly
    less: full evals only on cadence, zero LOS samples in between."""
    s4 = PolicyStack(CAP, _policies("team+tier+los", period=4),
                     mode="device")
    s1 = PolicyStack(CAP, _policies("team+tier+los", period=1),
                     mode="device")
    for t, frame in enumerate(_walk(11, CAP, N_TICKS)):
        _step_both((s4, s1), frame)
        if t % 4 == 0:
            assert np.array_equal(s4.words, s1.words), \
                f"K-boundary diverged @ {t}"
            assert np.array_equal(s4.near, s1.near)
    assert s4.stats["full_evals"] == 3      # steps 0, 4, 8
    assert s1.stats["full_evals"] == N_TICKS
    assert 0 < s4.stats["los_pair_evals"] < s1.stats["los_pair_evals"]


# -- degradation: the aoi.interest seam + reset_interest re-arm --------------

def _drive_stack(stack, frames, demote_at=None, reset_at=None):
    es, ls = [], []
    for t, frame in enumerate(frames):
        if t == demote_at:
            stack.force_demote()
        if t == reset_at:
            stack.reset_interest()
        stack.submit(*frame)
        stack.step()
        e, lv = stack.take_events()
        es.append(e), ls.append(lv)
    return np.concatenate(es), np.concatenate(ls)


@pytest.mark.parametrize("kind", ["poison", "fail", "reset"])
def test_interest_seam_demotes_and_rearms(kind):
    """Any fired kind on ``aoi.interest`` -- poisoned mask (returned
    spec), plain fail (raised InjectedFault), connection reset -- must
    demote sticky to the radius-only oracle path; the under-fire stream
    is bit-exact against a host twin demoted/re-armed by hand at the
    same ticks."""
    frames = list(_walk(13, CAP, N_TICKS))
    faults.install(f"aoi.interest:{kind}@3")  # 3rd stack step demotes
    dev = PolicyStack(CAP, _policies("team+tier+los"), mode="device")
    e, lv = _drive_stack(dev, frames, reset_at=6)
    assert faults.plan().fired, "seam never fired"
    faults.clear()
    twin = PolicyStack(CAP, _policies("team+tier+los"), mode="host")
    te, tl = _drive_stack(twin, frames, demote_at=2, reset_at=6)
    assert np.array_equal(e, te), f"{kind}: enter stream diverged"
    assert np.array_equal(lv, tl), f"{kind}: leave stream diverged"
    for s in (dev, twin):
        assert s.stats["demotions"] == 1
        assert s.stats["resets"] == 1
        assert s.stats["demoted_steps"] == 4  # steps 2..5
        assert not s.demoted  # re-armed
    assert np.array_equal(dev.words, twin.words)
    assert np.array_equal(dev.near, twin.near)


def test_corrupt_distance_field_demotes():
    """A genuinely non-finite grid (however it got that way) is
    indistinguishable from the injected kind: same sticky demotion, no
    crash, and the radius-only path keeps delivering."""
    los = LineOfSightPolicy(_field(), depth=2)
    stack = PolicyStack(CAP, [TieredRatePolicy(), los], mode="device")
    frames = list(_walk(17, CAP, 4))
    stack.submit(*frames[0])
    stack.step()
    assert stack.stats["demotions"] == 0
    los.field.grid[3, 3] = np.nan  # corrupt in place
    for fr in frames[1:]:
        stack.submit(*fr)
        stack.step()
    assert stack.demoted and stack.stats["demotions"] == 1
    assert stack.stats["demoted_steps"] == 3
    assert not stack.near_rows().any()  # radius-only path has no tiers


def test_device_fault_single_step_fallback(monkeypatch):
    """A device fault inside the fused step is NOT a demotion: that one
    step re-evaluates on the CPU oracle (``interest.host_steps``) and
    the device path resumes -- stream stays bit-exact throughout."""
    from goworld_tpu.interest import device as D

    frames = list(_walk(19, CAP, 6))
    real = D.eval_step
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            raise faults.DeviceOOM("aoi.interest", 1)
        return real(*a, **kw)

    monkeypatch.setattr(D, "eval_step", flaky)
    dev = PolicyStack(CAP, _policies("team+tier+los"), mode="device")
    e, lv = _drive_stack(dev, frames)
    monkeypatch.setattr(D, "eval_step", real)
    host = PolicyStack(CAP, _policies("team+tier+los"), mode="host")
    he, hl = _drive_stack(host, frames)
    assert np.array_equal(e, he) and np.array_equal(lv, hl)
    assert dev.stats["host_steps"] == 1
    assert dev.stats["demotions"] == 0 and not dev.demoted


def test_interest_telemetry_counters_registered():
    """The module counters exist under their documented names -- the
    registry hands back the same instruments docs/observability.md
    catalogs."""
    from goworld_tpu.interest import policy as pol

    reg = telemetry.registry()
    assert pol._STEPS is reg.counter("interest.steps")
    assert pol._FULL_EVALS is reg.counter("interest.full_evals")
    assert pol._DEMOTIONS is reg.counter("interest.demotions")
    assert pol._HOST_STEPS is reg.counter("interest.host_steps")
    assert pol._LOS_EVALS is reg.counter("interest.los_pair_evals")


# -- runtime integration: team columns + live set_aoi_team -------------------

class _Watcher(Entity):
    use_aoi = True


class _Hooked(_Watcher):
    """Overridden hooks -> nonplain: takes the replayed-event path
    (materialized interest sets) instead of on-demand derivation."""

    def on_enter_aoi(self, other):
        pass


class _Arena(Space):
    pass


def _rt(**kw):
    rt = Runtime(aoi_backend="cpu", **kw)
    rt.entities.register(_Watcher)
    rt.entities.register(_Hooked)
    rt.entities.register(_Arena)
    return rt


def test_team_mask_runtime_roundtrip():
    rt = _rt()
    sp = rt.entities.create_space("_Arena", kind=1)
    sp.enable_aoi(20.0)
    sp.enable_interest(TeamVisibilityPolicy())
    # a is plain (interest derived on demand from the stack's words);
    # b is hooked (interest materialized from the stack's event diff) --
    # both read the POLICY state, not the bucket's base predicate
    a = rt.entities.create("_Watcher", space=sp, pos=Vector3(0, 0, 0))
    b = rt.entities.create("_Hooked", space=sp, pos=Vector3(5, 0, 5))
    # ECS defaults: team=1, vis=all-ones -- mutually visible
    assert sp._cols.team[a.aoi_slot] == 1
    assert sp._cols.vis[a.aoi_slot] == 0xFFFFFFFF
    rt.tick()
    assert b in a.neighbors() and a in b.interested_in
    # b can only see faction bit 0; a moves to faction bit 1
    sp.set_aoi_team(a, team=0b10)
    sp.set_aoi_team(b, team=0b01, vis=0b01)
    rt.tick()
    assert b in a.neighbors()         # a's vis mask still passes everyone
    assert a not in b.interested_in   # vis[b] & team[a] == 0
    # a rejoins faction 0: visibility restores through the normal diff
    sp.set_aoi_team(a, team=0b01)
    rt.tick()
    assert a in b.interested_in


def test_tiered_runtime_near_rows():
    rt = _rt()
    sp = rt.entities.create_space("_Arena", kind=1)
    sp.enable_aoi(40.0)
    sp.enable_interest(TieredRatePolicy(period=4))
    a = rt.entities.create("_Watcher", space=sp, pos=Vector3(0, 0, 0))
    b = rt.entities.create("_Watcher", space=sp, pos=Vector3(5, 0, 0))
    far = rt.entities.create("_Watcher", space=sp, pos=Vector3(35, 0, 0))
    rt.tick()
    stack = sp.interest_stack
    near = stack.near_rows()
    assert near[a.aoi_slot] and near[b.aoi_slot]  # within r*near_frac
    assert not near[far.aoi_slot]                 # interested, not near
    assert far in a.neighbors()


# -- migration carries the stack ---------------------------------------------

def _mig_run(src, tgt=None, mig_at=-1, cap=ENGINE_CAP, n=10):
    eng = AOIEngine("cpu", mesh=2)
    pc = PlacementController(eng)
    h = eng._create_handle(cap, src)
    stack = eng.attach_interest(h, _policies("team+tier+los"))
    es, ls = [], []
    for t, (x, z, r, act, team, vis) in enumerate(_walk(7, cap, n)):
        if t == mig_at:
            pc.migrate(h, tgt)
        eng.submit(h, x, z, r, act)
        stack.submit(x, z, r, act, team, vis)
        eng.flush()
        e, lv = eng.take_events(h)
        es.append(np.asarray(e)), ls.append(np.asarray(lv))
    while eng.has_pending():
        eng.flush()
        e, lv = eng.take_events(h)
        es.append(np.asarray(e)), ls.append(np.asarray(lv))
    return np.concatenate(es), np.concatenate(ls), eng, h


def test_migration_carries_stack():
    """A live migration re-points the handle in place; the stack (and
    its stream) must come along bit-exactly -- the base bucket keeps
    carrying radius state through the cover/swap underneath."""
    re_, rl, _eng, _h = _mig_run("cpu")
    e, lv, eng, h = _mig_run("cpu", "tpu", mig_at=4)
    assert np.array_equal(e, re_), "enter stream diverged across migration"
    assert np.array_equal(lv, rl), "leave stream diverged across migration"
    assert eng.migration_stats["migrations"] == 1
    stack = AOIEngine.interest_stack(h)
    assert stack is not None and stack.stats["demotions"] == 0


# -- checkpoint restore of the interest payload ------------------------------

def test_checkpoint_restores_interest(tmp_path):
    """The stack payload rides the per-space snapshot records; a restore
    stashes it on the new handle and ``attach_interest`` auto-imports it
    -- the restored stack continues bit-exactly from the restore tick."""
    PRE, POST = 6, 6
    eng = AOIEngine("cpu")
    store, kv = _open_backends(str(tmp_path / "ck"))
    ctl = CheckpointController(eng, store, kv, mode="continuous")
    h = eng._create_handle(CAP, "cpu")
    stack = eng.attach_interest(h, _policies("team+tier+los"))
    ctl.track("s", h)
    frames = list(_walk(5, CAP, PRE + POST))
    for t in range(PRE):
        x, z, r, act, team, vis = frames[t]
        eng.submit(h, x, z, r, act)
        stack.submit(x, z, r, act, team, vis)
        eng.flush()
        ctl.step(t + 1)
    assert ctl.drain(), "writer did not drain"
    eng.take_events(h)  # pre-restore stream: deliver and discard

    rest = CheckpointController(eng, store, kv, mode="off")
    res = rest.restore_into(eng, "s", tier="cpu")
    assert res is not None, "no consistent checkpoint chain"
    h2, tick, _epoch = res
    assert tick == PRE
    assert getattr(h2, "_interest_snapshot", None) is not None
    stack2 = eng.attach_interest(h2, _policies("team+tier+los"))
    assert getattr(h2, "_interest_snapshot", None) is None  # consumed
    assert stack2.step_count == stack.step_count
    assert stack2._cfg.key() == stack._cfg.key()
    assert np.array_equal(stack2._field.grid, stack._field.grid)
    assert np.array_equal(stack2.words, stack.words)
    assert np.array_equal(stack2.near, stack.near)

    for t in range(PRE, PRE + POST):
        x, z, r, act, team, vis = frames[t]
        for hh, st in ((h, stack), (h2, stack2)):
            eng.submit(hh, x, z, r, act)
            st.submit(x, z, r, act, team, vis)
        eng.flush()
        oe, ol = (np.asarray(a) for a in eng.take_events(h))
        re_, rl = (np.asarray(a) for a in eng.take_events(h2))
        assert np.array_equal(oe, re_), f"post-restore enter diverged @ {t}"
        assert np.array_equal(ol, rl), f"post-restore leave diverged @ {t}"
    ctl.close()
    rest.close()
    store.close()
    kv.close()


# -- growth carries the stack ------------------------------------------------

def test_grow_space_carries_stack():
    eng = AOIEngine("cpu")
    h = eng._create_handle(CAP, "cpu")
    stack = eng.attach_interest(h, _policies("team+tier"))
    frames = list(_walk(9, CAP, 3))
    for x, z, r, act, team, vis in frames:
        eng.submit(h, x, z, r, act)
        stack.submit(x, z, r, act, team, vis)
        eng.flush()
        eng.take_events(h)
    m_before = P.unpack_rows(stack.final, CAP)
    assert m_before.any(), "degenerate walk: no interest state to carry"
    nh = eng.grow_space(h, CAP * 2)
    assert AOIEngine.interest_stack(nh) is stack
    assert AOIEngine.interest_stack(h) is None
    assert stack.capacity == CAP * 2
    m_after = P.unpack_rows(stack.final, CAP * 2)
    assert np.array_equal(m_after[:CAP, :CAP], m_before)
    assert not m_after[CAP:].any() and not m_after[:, CAP:].any()
    # growth itself must emit nothing: same positions, padded inactive
    x, z, r, act, team, vis = frames[-1]

    def pad(a, fill=0):
        return np.concatenate([a, np.full(CAP, fill, a.dtype)])

    eng.submit(nh, pad(x), pad(z), pad(r), pad(act, False))
    stack.submit(pad(x), pad(z), pad(r), pad(act, False),
                 pad(team), pad(vis))
    eng.flush()
    e, lv = eng.take_events(nh)
    assert np.asarray(e).size == 0 and np.asarray(lv).size == 0


# -- distance fields ---------------------------------------------------------

def test_distance_field_bake_and_roundtrip():
    f = _field()
    assert f.validate()
    # (30, 40) is inside the first box -> negative; (-90, -90) is open
    ix, iz = int((30.0 + 100.0) / 5.0), int((40.0 + 100.0) / 5.0)
    assert f.grid[iz, ix] < 0.0
    ix, iz = int((-90.0 + 100.0) / 5.0), int((-90.0 + 100.0) / 5.0)
    assert f.grid[iz, ix] > 0.0
    st = f.export_state()
    f2 = DistanceField.import_state(st)
    assert np.array_equal(f2.grid, f.grid) and f2.key() == f.key()
    # msgpack round-trips tuples as lists; import must not care
    st2 = {"origin": list(st["origin"]), "cell": st["cell"],
           "shape": list(st["shape"]), "grid": st["grid"]}
    f3 = DistanceField.import_state(st2)
    assert f3.key() == f.key()
    g = f.grid.copy()
    g[0, 0] = np.inf
    assert not DistanceField(float(f.origin_x), float(f.origin_z),
                             float(f.cell), g).validate()


# -- constructor validation --------------------------------------------------

def test_policy_validation_errors():
    with pytest.raises(ValueError):
        TieredRatePolicy(near_frac=0.0)
    with pytest.raises(ValueError):
        TieredRatePolicy(hysteresis=0.5)
    with pytest.raises(ValueError):
        TieredRatePolicy(period=0)
    with pytest.raises(TypeError):
        LineOfSightPolicy("not a field")
    with pytest.raises(ValueError):
        LineOfSightPolicy(_field(), depth=5)
    with pytest.raises(ValueError):
        DistanceField(0.0, 0.0, -1.0, np.zeros((4, 4), np.float32))
    with pytest.raises(ValueError):
        PolicyStack(CAP, [])
    with pytest.raises(ValueError):
        PolicyStack(CAP, [TieredRatePolicy(), TieredRatePolicy()])
    with pytest.raises(ValueError):
        PolicyStack(CAP, [TieredRatePolicy()], mode="gpu")

    class Rogue(InterestPolicy):
        name = "rogue-unregistered"

    with pytest.raises(ValueError):
        PolicyStack(CAP, [Rogue()])
    from goworld_tpu.interest import register

    class Nameless(InterestPolicy):
        pass

    with pytest.raises(ValueError):
        register(Nameless)

    class Dup(InterestPolicy):
        name = "team_mask"

    with pytest.raises(ValueError):
        register(Dup)
