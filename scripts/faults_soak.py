#!/usr/bin/env python
"""Randomized fault-plan soak: N seedable plans over every declared seam.

Each round derives a fresh :class:`~goworld_tpu.faults.FaultPlan` from
``base_seed + round``: every AOI seam gets one spec with a kind drawn
from its legal menu at an ``@auto`` occurrence (sha256 of (seed, seam) --
stable across processes), then a TPU-path engine (capped-triples
extraction) walks a seeded random world next to an UNINJECTED CPU
oracle.  The contract under test is the whole self-healing story at
once, seams interacting:

* bit-exact enter/leave parity on every tick, faults and all;
* zero stuck buckets -- after the plan exhausts, the operator re-arm
  (``reset_calc_chain``/``reset_emit_path``; demotion is deliberately
  sticky) plus two clean ticks puts every bucket back at
  ``calc_level == 0`` with no pending repair and parity intact;
* the fused one-launch pipeline (``aoi_fused``) demotes per-tick when a
  seam fires inside the attempt -- counted, bit-exact, self-re-engaging;
* the space-stacked cohort (``aoi.cohort`` seam) demotes the whole
  shared bucket to per-space solo buckets same-tick when its dispatch
  faults, and the operator re-arm (``recohort()``) stacks them back;
* the connection seams get the same treatment against a live socket:
  injected resets on flush/connect must still deliver every payload
  exactly once, in order, with the outage buffer drained.

Runs on the CPU backend in under a minute with the default 4 rounds.
Opt-in ci.sh step (GW_SOAK=1); ``faults_soak.py [rounds] [base_seed]``
for a longer stand-alone soak.  docs/robustness.md describes the seams.
"""

import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from goworld_tpu import faults  # noqa: E402
from goworld_tpu.engine.aoi import AOIEngine  # noqa: E402

# legal kind menu per AOI seam (what its recovery path is built to absorb
# on the single-chip tier; aoi.device reset = chip loss needs a mesh to
# evacuate onto, so the soak sticks to its transient kinds)
AOI_SEAM_KINDS = {
    "aoi.grow": ["oom", "fail"],
    "aoi.h2d": ["oom", "fail", "stall"],
    "aoi.delta": ["oom", "fail"],
    "aoi.kernel": ["oom", "fail"],
    "aoi.scalars": ["poison", "stall"],
    "aoi.fetch": ["oom", "fail", "stall"],
    "aoi.emit": ["oom", "fail"],
    "aoi.device": ["oom", "fail"],
}

# the batched ingest demotes on ANY kind (whole batch falls back to the
# per-entity apply path, bit-identically); soaked at a pinned occurrence
# inside the walk so the demotion provably fires every round
INGEST_KINDS = ["oom", "fail", "stall", "poison"]

# under the one-tick deferral (cross_tick) only dispatch-side faults keep
# per-tick delivery timing; harvest-side recovery (fetch/scalars
# regeneration, emit demotion mid-publish) CONVERGES instead of staying
# tick-exact -- tests/test_cross_tick.py pins convergence for those, so
# the cross-tick walks soak the timing-preserving menu and leave the
# convergence contract to the dedicated test
CROSS_TICK_SEAM_KINDS = {
    "aoi.grow": ["oom", "fail"],
    "aoi.h2d": ["oom", "fail", "stall"],
    "aoi.delta": ["oom", "fail"],
    "aoi.kernel": ["oom", "fail"],
    "aoi.scalars": ["stall"],
    "aoi.fetch": ["stall"],
    "aoi.device": ["oom", "fail"],
}


def build_plan(seed: int, menu=None) -> faults.FaultPlan:
    rng = np.random.default_rng(seed)
    plan = faults.FaultPlan(seed=seed)
    for seam, kinds in sorted((menu or AOI_SEAM_KINDS).items()):
        kind = kinds[int(rng.integers(len(kinds)))]
        arg = 0.001 if kind == "stall" else None
        plan.add(seam, kind, at="auto", arg=arg)
    return plan


def soak_aoi(seed: int, cap=256, n=200, ticks=10, cross_tick=False) -> dict:
    """One engine walk under a full seam plan.  ``cross_tick=True`` runs
    the triples-path bucket with the one-tick deferral on (the
    aoi_cross_tick path): the oracle comparison shifts by one tick and a
    trailing drain flush collects the last parked delivery."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(0, 600, cap).astype(np.float32)
    z = rng.uniform(0, 600, cap).astype(np.float32)
    r = rng.uniform(60, 120, cap).astype(np.float32)
    act = np.zeros(cap, bool)
    act[:n] = True

    oracle = AOIEngine(default_backend="cpu")
    oh = oracle.create_space(cap)
    plan = build_plan(seed,
                      menu=CROSS_TICK_SEAM_KINDS if cross_tick else None)
    faults.install(plan)
    try:
        eng = AOIEngine(default_backend="tpu", cross_tick=cross_tick)
        h = eng.create_space(cap)
        ev, oev = [], []
        # ticks under fire, then the operator re-arm (demotion is sticky
        # by design) and two clean ticks proving the device path is back
        for t in range(ticks + 2):
            if t == ticks:
                faults.clear()
                h.bucket.reset_calc_chain()
                h.bucket.reset_emit_path()
            x = np.clip(x + rng.uniform(-20, 20, cap), 0, 600) \
                .astype(np.float32)
            z = np.clip(z + rng.uniform(-20, 20, cap), 0, 600) \
                .astype(np.float32)
            eng.submit(h, x, z, r, act)
            oracle.submit(oh, x, z, r, act)
            eng.flush()
            oracle.flush()
            ev.append(eng.take_events(h))
            oev.append(oracle.take_events(oh))
        shift = 1 if cross_tick else 0
        if shift:
            # deferred cadence: tick 0 delivers nothing, one more flush
            # drains the parked last tick
            e0, l0 = ev[0]
            assert len(e0) == 0 and len(l0) == 0, \
                f"cross-tick tick 0 delivered seed={seed}"
            eng.flush()
            ev.append(eng.take_events(h))
        for t in range(len(oev)):
            e, l = ev[t + shift]
            ce, cl = oev[t]
            np.testing.assert_array_equal(e, ce,
                                          err_msg=f"enter t={t} seed={seed}")
            np.testing.assert_array_equal(l, cl,
                                          err_msg=f"leave t={t} seed={seed}")
        st = dict(h.bucket.stats)
        assert st["calc_level"] == 0, f"stuck bucket seed={seed}: {st}"
        return {"fired": len(plan.fired), "stats": st}
    finally:
        faults.clear()


def soak_fused(seed: int, cap=256, n=200, ticks=10) -> dict:
    """The ``aoi.fused`` round: a fused triples-path engine
    (``Runtime(aoi_fused=True)`` routing, docs/perf.md "Fused dispatch")
    walks next to the uninjected CPU oracle under seam specs PINNED at
    mid-walk occurrences (the soak_ingest idiom: provably fired every
    round).  A seam firing inside the one-launch fused attempt must
    demote exactly that tick to the unfused path -- counted in
    ``fused_demotions``, republished same-tick, bit-exact -- and the
    fused path must re-engage on its own (demotion is per-tick, not
    sticky).  Movement is SPARSE (~15%/tick): a full-world move is
    silently fused-ineligible by design (delta > ``_delta_max_frac``)
    and would soak nothing."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(0, 600, cap).astype(np.float32)
    z = rng.uniform(0, 600, cap).astype(np.float32)
    r = rng.uniform(60, 120, cap).astype(np.float32)
    act = np.zeros(cap, bool)
    act[:n] = True

    oracle = AOIEngine(default_backend="cpu")
    oh = oracle.create_space(cap)
    plan = faults.FaultPlan(seed=seed)
    for seam in ("aoi.kernel", "aoi.delta"):
        kinds = AOI_SEAM_KINDS[seam]
        plan.add(seam, kinds[int(rng.integers(len(kinds)))],
                 at=int(rng.integers(3, ticks)))
    faults.install(plan)
    try:
        eng = AOIEngine(default_backend="tpu", fused=True)
        h = eng.create_space(cap)
        for t in range(ticks + 2):
            if t == ticks:  # plan exhausted or not: operator re-arm
                faults.clear()
            move = rng.random(cap) < 0.15
            x[move] = np.clip(x[move] + rng.uniform(
                -20, 20, int(move.sum())), 0, 600).astype(np.float32)
            z[move] = np.clip(z[move] + rng.uniform(
                -20, 20, int(move.sum())), 0, 600).astype(np.float32)
            eng.submit(h, x, z, r, act)
            oracle.submit(oh, x, z, r, act)
            eng.flush()
            oracle.flush()
            e, l = eng.take_events(h)
            ce, cl = oracle.take_events(oh)
            np.testing.assert_array_equal(e, ce,
                                          err_msg=f"enter t={t} seed={seed}")
            np.testing.assert_array_equal(l, cl,
                                          err_msg=f"leave t={t} seed={seed}")
        st = dict(h.bucket.stats)
        assert st["fused_dispatches"] > 0, \
            f"fused path never engaged seed={seed}: {st}"
        assert st["fused_demotions"] >= 1, \
            f"pinned seam never demoted the fused attempt seed={seed}: {st}"
        assert st["calc_level"] == 0, f"stuck bucket seed={seed}: {st}"
        return {"fired": len(plan.fired),
                "fused": st["fused_dispatches"],
                "demoted": st["fused_demotions"]}
    finally:
        faults.clear()


def soak_cohort(seed: int, ticks=10) -> dict:
    """The ``aoi.cohort`` round: several small spaces stacked into ONE
    ladder-shaped cohort bucket (``AOIEngine(cohort="auto")``, docs/
    perf.md "Space-stacked cohorts") walk next to per-space CPU oracles
    under an ``aoi.cohort`` spec pinned mid-walk.  The seam firing on
    the shared dispatch must demote the WHOLE cohort to per-space solo
    buckets that same tick -- counted, republished, bit-exact -- and
    demotion is sticky by design: the operator re-arm is plan cleared +
    ``recohort()``, after which two clean ticks prove the spaces are
    stacked and dispatching fused again.  All capacities draw from one
    rung so the single cohort bucket's per-flush seam probe maps 1:1
    onto ticks and the pinned occurrence provably fires."""
    rng = np.random.default_rng(seed)
    caps = [int(rng.integers(1, 3)) * 128 for _ in range(4)]  # rung 256
    kind = ["oom", "fail", "reset"][int(rng.integers(3))]
    at = int(rng.integers(3, ticks))

    oracle = AOIEngine(default_backend="cpu")
    ohs = [oracle.create_space(c) for c in caps]
    plan = faults.FaultPlan(seed=seed)
    plan.add("aoi.cohort", kind, at=at)
    faults.install(plan)
    try:
        eng = AOIEngine(default_backend="tpu", cohort="auto",
                        cohort_ladder=(256,))
        hs = [eng.create_space(c) for c in caps]
        assert len({h.bucket for h in hs}) == 1, \
            f"caps {caps} did not stack on one rung seed={seed}"
        scenes = []
        for c in caps:
            x = rng.uniform(0, 600, c).astype(np.float32)
            z = rng.uniform(0, 600, c).astype(np.float32)
            r = rng.uniform(60, 120, c).astype(np.float32)
            act = np.ones(c, bool)
            scenes.append([x, z, r, act])
        for t in range(ticks + 2):
            if t == ticks:  # operator re-arm: demotion is sticky
                faults.clear()
                restacked = eng.recohort()
                assert restacked == len(caps), \
                    f"recohort moved {restacked} != {len(caps)} seed={seed}"
            for sc in scenes:
                sc[0] = np.clip(sc[0] + rng.uniform(-20, 20, len(sc[0])),
                                0, 600).astype(np.float32)
                sc[1] = np.clip(sc[1] + rng.uniform(-20, 20, len(sc[1])),
                                0, 600).astype(np.float32)
            for h, oh, sc in zip(hs, ohs, scenes):
                eng.submit(h, *sc)
                oracle.submit(oh, *sc)
            eng.flush()
            oracle.flush()
            for i, (h, oh) in enumerate(zip(hs, ohs)):
                e, l = eng.take_events(h)
                ce, cl = oracle.take_events(oh)
                np.testing.assert_array_equal(
                    e, ce, err_msg=f"enter t={t} space={i} seed={seed}")
                np.testing.assert_array_equal(
                    l, cl, err_msg=f"leave t={t} space={i} seed={seed}")
        assert len(plan.fired) == 1, \
            f"pinned aoi.cohort spec never fired seed={seed}: {plan.fired}"
        demoted = eng.cohort_stats["cohort_demoted_spaces"]
        assert demoted == len(caps), \
            f"demotion missed spaces seed={seed}: {eng.cohort_stats}"
        # after the re-arm every space is back on ONE shared cohort
        # bucket and its fused dispatch ran both clean ticks
        buckets = {h.bucket for h in hs}
        assert len(buckets) == 1, f"recohort left strays seed={seed}"
        st = dict(next(iter(buckets)).stats)
        assert getattr(next(iter(buckets)), "cohort", False), \
            f"re-armed bucket is not a cohort seed={seed}"
        assert st["cohort_dispatches"] >= 2, \
            f"cohort path never re-engaged seed={seed}: {st}"
        return {"kind": kind, "at": at, "demoted": demoted,
                "redispatched": st["cohort_dispatches"]}
    finally:
        faults.clear()


def soak_ingest(seed: int, n=48, ticks=8) -> dict:
    """Runtime-level ingest soak on a cross-tick triples-path engine: the
    batched wire->column decode walks under the timing-preserving
    engine-seam plan PLUS an ``aoi.ingest`` spec pinned inside the walk
    (so the batch demotion provably fires).  The drained sync stream
    must be bit-identical to a clean per-entity decode of the same
    wave."""
    from goworld_tpu.engine.entity import Entity, GameClient
    from goworld_tpu.engine.runtime import Runtime
    from goworld_tpu.engine.space import Space
    from goworld_tpu.engine.vector import Vector3
    from goworld_tpu.ingest import (RECORD_SIZE, SYNC_RECORD,
                                    MovementIngest, apply_per_entity)
    from goworld_tpu.netutil.packet import Packet

    class SoakScene(Space):
        pass

    class SoakWalker(Entity):
        use_aoi = True
        aoi_distance = 30.0

    def run(batched, plan):
        if plan is not None:
            faults.install(plan)
        try:
            rt = Runtime(aoi_backend="tpu", aoi_cross_tick=True,
                         aoi_tpu_min_capacity=16)
            rt.entities.register(SoakScene)
            rt.entities.register(SoakWalker)
            sc = rt.entities.create_space("SoakScene", kind=1)
            sc.enable_aoi(30.0)
            es, emap = [], {}
            for i in range(n):
                e = rt.entities.create(
                    "SoakWalker", space=sc,
                    pos=Vector3((i * 9.0) % 400, 0.0, (i * 7.0) % 400))
                e.set_client_syncing(True)
                e.set_client(GameClient(("s%05d" % i).ljust(16, "x")))
                es.append(e)
                emap[e.id] = i
            rt.tick()
            ing = MovementIngest(rt)
            rng = np.random.default_rng(seed)
            out = []
            for _t in range(ticks):
                xs = rng.uniform(0, 400, n).astype(np.float32)
                zs = rng.uniform(0, 400, n).astype(np.float32)
                yaws = rng.uniform(0, 6.28, n).astype(np.float32)
                pkt = Packet(bytearray())
                for j, e in enumerate(es):
                    pkt.append_entity_id(e.id)
                    pkt.append_f32(float(xs[j]))
                    pkt.append_f32(0.0)
                    pkt.append_f32(float(zs[j]))
                    pkt.append_f32(float(yaws[j]))
                if batched:
                    ing.ingest(pkt)
                else:
                    apply_per_entity(rt.entities, np.frombuffer(
                        pkt.read_view(n * RECORD_SIZE), dtype=SYNC_RECORD))
                rt.tick()
                out.append(sorted(
                    (emap[eid], xx, yy, zz, yw)
                    for _c, _g, eid, xx, yy, zz, yw in rt.drain_sync()))
            return out, dict(ing.stats)
        finally:
            faults.clear()

    clean, _ = run(batched=False, plan=None)
    rng = np.random.default_rng(seed + 7)
    plan = build_plan(seed, menu=CROSS_TICK_SEAM_KINDS)
    kind = INGEST_KINDS[int(rng.integers(len(INGEST_KINDS)))]
    plan.add("aoi.ingest", kind, at=int(rng.integers(2, ticks + 1)),
             arg=0.001 if kind == "stall" else None)
    faulted, st = run(batched=True, plan=plan)
    assert faulted == clean, f"ingest sync stream diverged seed={seed}"
    assert st["demoted_batches"] >= 1, \
        f"pinned aoi.ingest spec never fired seed={seed}: {st}"
    return {"kind": kind, "demoted": st["demoted_batches"],
            "per_entity_writes": st["per_entity_writes"],
            "batched": st["batched"]}


# the interest-policy stack demotes on ANY kind (the whole composition
# falls back to the radius-only oracle path, sticky until reset_interest)
INTEREST_KINDS = ["oom", "fail", "reset", "poison", "stall"]


def soak_interest(seed: int, cap=128, ticks=8) -> dict:
    """The ``aoi.interest`` seam in the randomized walk: a composed
    team+tier+LOS stack demotes sticky to the radius-only path when its
    spec fires (ANY kind), rides out the rest of the plan demoted, and
    the operator re-arm (plan cleared + ``reset_interest``) plus two
    clean ticks restores the full composition -- the whole stream
    bit-exact against a reference twin driven through the same
    demote/reset schedule on the CPU oracle."""
    from goworld_tpu.interest import (DistanceField, LineOfSightPolicy,
                                      PolicyStack, TeamVisibilityPolicy,
                                      TieredRatePolicy)

    def mk():
        field = DistanceField.from_boxes(
            [(20.0, 20.0, 45.0, 60.0), (-60.0, -10.0, -30.0, 10.0)],
            (-100.0, -100.0), (200.0, 200.0), cell=5.0)
        return [TeamVisibilityPolicy(), TieredRatePolicy(period=4),
                LineOfSightPolicy(field, depth=2)]

    rng = np.random.default_rng(seed)
    kind = INTEREST_KINDS[int(rng.integers(len(INTEREST_KINDS)))]
    at = int(rng.integers(2, ticks + 1))  # occurrence N = step index N-1
    x = rng.uniform(-90, 90, cap).astype(np.float32)
    z = rng.uniform(-90, 90, cap).astype(np.float32)
    r = rng.uniform(10, 30, cap).astype(np.float32)
    act = np.ones(cap, bool)
    team = (np.uint32(1) << rng.integers(0, 4, cap)).astype(np.uint32)
    vis = np.where(rng.random(cap) < 0.75, 0xFFFFFFFF, 0b1) \
        .astype(np.uint32)
    frames = []
    for _ in range(ticks + 2):
        x = (x + rng.uniform(-4, 4, cap)).astype(np.float32)
        z = (z + rng.uniform(-4, 4, cap)).astype(np.float32)
        frames.append((x.copy(), z.copy(), r, act, team, vis))

    plan = faults.FaultPlan(seed=seed)
    plan.add("aoi.interest", kind, at=at,
             arg=0.001 if kind == "stall" else None)
    faults.install(plan)
    try:
        dev = PolicyStack(cap, mk(), mode="device")
        ev = []
        for t, frame in enumerate(frames):
            if t == ticks:  # operator re-arm, then two clean ticks
                faults.clear()
                dev.reset_interest()
            dev.submit(*frame)
            dev.step()
            ev.append(dev.take_events())
    finally:
        faults.clear()
    twin = PolicyStack(cap, mk(), mode="host")
    for t, frame in enumerate(frames):
        if t == at - 1:
            twin.force_demote()
        if t == ticks:
            twin.reset_interest()
        twin.submit(*frame)
        twin.step()
        te, tl = twin.take_events()
        e, l = ev[t]
        np.testing.assert_array_equal(e, te,
                                      err_msg=f"enter t={t} seed={seed}")
        np.testing.assert_array_equal(l, tl,
                                      err_msg=f"leave t={t} seed={seed}")
    assert len(plan.fired) == 1, \
        f"aoi.interest spec never fired seed={seed}: {plan.fired}"
    assert dev.stats["demotions"] == 1, f"seed={seed}: {dev.stats}"
    assert dev.stats["resets"] == 1 and not dev.demoted, \
        f"re-arm failed seed={seed}: {dev.stats}"
    assert dev.stats["demoted_steps"] == ticks - (at - 1), \
        f"seed={seed}: {dev.stats}"
    assert np.array_equal(dev.words, twin.words)
    return {"kind": kind, "at": at,
            "demoted_steps": dev.stats["demoted_steps"]}


# the durable-state seams (engine/checkpoint.py): every kind each guarded
# op is built to absorb -- fail/oom/reset retry, stall rides the writer
# thread, partial/poison land torn records the restore-side CRC catches
STORE_SEAM_KINDS = {
    "store.write": ["oom", "fail", "reset", "stall", "partial", "poison"],
    "store.read": ["oom", "fail", "reset", "stall", "poison"],
    "store.manifest": ["fail", "reset", "stall", "partial"],
}


def soak_checkpoint(seed: int, cap=128, ticks=8) -> dict:
    """Checkpoint + restore under a randomized all-store-seam plan.  A
    clean walk records each tick's exported state; the same walk then
    runs with continuous checkpointing under fire, and the journal must
    still restore to a bit-exact copy of SOME recorded tick (torn/
    poisoned epochs legitimately shorten the chain -- the fallback tick
    just moves earlier; a transient read fault may need the one re-arm
    retry, the same operator story as the engine seams)."""
    import shutil
    import tempfile

    from goworld_tpu.engine.aoi import _unpack_positions
    from goworld_tpu.engine.checkpoint import (CheckpointController,
                                               _open_backends)

    rng = np.random.default_rng(seed)
    x = rng.uniform(0, 400, cap).astype(np.float32)
    z = rng.uniform(0, 400, cap).astype(np.float32)
    r = np.full(cap, 15.0, np.float32)
    act = np.ones(cap, bool)
    frames = []
    for _ in range(ticks):
        x = x + rng.uniform(-3, 3, cap).astype(np.float32)
        z = z + rng.uniform(-3, 3, cap).astype(np.float32)
        frames.append((x.copy(), z.copy()))

    plan = build_plan(seed, menu=STORE_SEAM_KINDS)
    base = tempfile.mkdtemp(prefix="gw_soak_ckpt_")
    eng = AOIEngine(default_backend="cpu")
    h = eng._create_handle(cap, "tpu")
    store, kv = _open_backends(base)
    ctl = CheckpointController(eng, store, kv, mode="continuous",
                               retry_base_s=0.0)
    ctl.track("s", h)
    by_tick = {}
    n_events = 0
    rest = None
    faults.install(plan)
    try:
        for t, (fx, fz) in enumerate(frames, 1):
            eng.submit(h, fx, fz, r, act)
            eng.flush()
            ev, lv = eng.take_events(h)
            n_events += len(ev) + len(lv)
            snap = h.bucket.export_snapshot(h.slot)
            sx, sz = _unpack_positions(snap)
            by_tick[t] = (sx, sz, snap["r"].copy(),
                          np.asarray(snap["act"], bool).copy(),
                          snap["words"].copy(), bool(snap["sub"]))
            ctl.step(t)
        assert ctl.drain(), f"ckpt writer stuck seed={seed}"
        rest = CheckpointController(eng, store, kv, mode="off",
                                    retry_base_s=0.0)
        res = rest.restore("s")
        if res is None:
            # a read-side poison can tear every chain through the base;
            # the operator re-arm (plan exhausted/cleared) + one retry
            # must heal it -- the journal itself was never corrupt
            faults.clear()
            res = rest.restore("s")
        assert res is not None, f"unrestorable journal seed={seed}"
        snap, tick, epoch = res
        assert tick in by_tick, f"restored unknown tick {tick} seed={seed}"
        rx, rz = _unpack_positions(snap)
        ex, ez, er, ea, ew, es = by_tick[tick]
        np.testing.assert_array_equal(rx, ex, err_msg=f"x seed={seed}")
        np.testing.assert_array_equal(rz, ez, err_msg=f"z seed={seed}")
        np.testing.assert_array_equal(snap["r"], er, err_msg=f"r seed={seed}")
        np.testing.assert_array_equal(np.asarray(snap["act"], bool), ea)
        np.testing.assert_array_equal(snap["words"], ew)
        assert bool(snap["sub"]) == es
        assert n_events > 0, f"degenerate walk seed={seed}"
        fired = sum(1 for f in plan.fired
                    if f["seam"].startswith("store."))
        return {"fired": fired, "restored_tick": tick, "epoch": epoch,
                "dropped": ctl.stats["dropped_epochs"],
                "torn": rest.stats["torn_records"]
                + ctl.stats["torn_records"]}
    finally:
        faults.clear()
        ctl.close(drain=False)
        if rest is not None:
            rest.close()
        store.close()
        kv.close()
        shutil.rmtree(base, ignore_errors=True)


class _Recorder:
    """A dispatcher stand-in: records every framed payload it receives."""

    def __init__(self):
        from goworld_tpu.netutil.conn import FrameParser, serve_tcp

        self.payloads: list[bytes] = []
        self._stop = threading.Event()
        self._FrameParser = FrameParser
        self.ls = serve_tcp(("127.0.0.1", 0), self._on_conn,
                            stop_event=self._stop)
        self.addr = self.ls.getsockname()

    def _on_conn(self, sock, peer):
        parser = self._FrameParser()
        while not self._stop.is_set():
            try:
                data = sock.recv(65536)
            except OSError:
                return
            if not data:
                return
            for p in parser.feed(data):
                self.payloads.append(p.payload)

    def close(self):
        self._stop.set()
        self.ls.close()


def soak_dispatcher(seed: int, n_payloads=12) -> dict:
    from goworld_tpu.dispatchercluster import DispatcherCluster
    from goworld_tpu.netutil.packet import Packet

    rng = np.random.default_rng(seed)
    plan = faults.FaultPlan(seed=seed)
    plan.add("conn.flush", "reset", at="auto")
    plan.add("disp.connect", "reset",
             at=int(rng.integers(1, 3)), count=int(rng.integers(1, 3)))
    rec = _Recorder()
    faults.install(plan)
    c = DispatcherCluster([rec.addr], on_packet=lambda i, p: None,
                          register=lambda conn: None, tag="soak",
                          backoff_base=0.05, backoff_cap=0.2).start()
    try:
        assert c.wait_connected(5.0), f"never connected seed={seed}"
        sent = [b"soak-%d-%02d" % (seed, i) for i in range(n_payloads)]
        for payload in sent:
            c.post(0, Packet(bytearray(payload)))
            c.flush_all()
            time.sleep(0.01)
        deadline = time.monotonic() + 10.0
        while len(rec.payloads) < len(sent) and time.monotonic() < deadline:
            c.flush_all()
            time.sleep(0.05)
        assert rec.payloads == sent, \
            f"delivery broke seed={seed}: {rec.payloads} != {sent}"
        st = c.status()[0]
        assert st["pending"] == 0 and st["dropped"] == 0, \
            f"stuck outage buffer seed={seed}: {st}"
        return {"fired": len(plan.fired), "replayed": st["replayed"]}
    finally:
        faults.clear()
        c.stop()
        rec.close()


def soak_host_failover(seed: int) -> dict:
    """Kill-a-host failover under fire (engine/failover.py).  The worker
    processes inherit a GW_FAULT_PLAN stalling the clu.zombie packet-loop
    seam (a brief mid-traffic park, the split-brain probe in miniature)
    and the clu.restore re-homing seam (stretching the survivor's
    recovery); the parent's plan stalls clu.kill so even the SIGKILL
    itself rides an injected seam.  The contract is unchanged from the
    clean run: merged delivered stream CRC-equal to the unkilled oracle,
    events_lost == 0, the survivor's own space untouched."""
    import shutil
    import tempfile

    from goworld_tpu.engine.failover import host_failover_scenario

    rng = np.random.default_rng(seed)
    zombie_at = int(rng.integers(5, 40))
    plan = faults.FaultPlan(seed)
    plan.add("clu.kill", "stall", at=1, arg=0.02)
    worker_plan = (f"seed={seed};clu.zombie:stall@{zombie_at}:0.03;"
                   f"clu.restore:stall@1:0.05")
    base = tempfile.mkdtemp(prefix="gw_soak_failover_")
    faults.install(plan)
    try:
        out = host_failover_scenario(
            base, cap=24, ticks=32, kill_at=16, pace_s=0.01,
            lease_ttl_s=2.0, seed=seed,
            worker_env={"GW_FAULT_PLAN": worker_plan})
        assert out["survivor_done"], f"survivor never finished seed={seed}"
        assert out["clu_stats"]["failovers"] >= 1, f"no failover seed={seed}"
        assert out["replay_parity_ok"], \
            f"replayed overlap diverged seed={seed}: {out}"
        assert out["parity_ok"], f"merged != oracle seed={seed}: {out}"
        assert out["survivor_space_ok"], \
            f"survivor space diverged seed={seed}: {out}"
        assert out["events_lost"] == 0, f"events lost seed={seed}: {out}"
        kill_fired = sum(1 for f in plan.fired if f["seam"] == "clu.kill")
        assert kill_fired == 1, f"clu.kill never fired seed={seed}"
        return {"fired": kill_fired, "zombie_at": zombie_at,
                "recover_ticks": out["ticks_to_recover"],
                "replayed": out["clu_stats"]["replayed_moves"]}
    finally:
        faults.clear()
        shutil.rmtree(base, ignore_errors=True)


def main(argv):
    rounds = int(argv[1]) if len(argv) > 1 else 4
    base_seed = int(argv[2]) if len(argv) > 2 else 1000
    for i in range(rounds):
        seed = base_seed + i
        # alternate the engine walk's cadence so every soak covers both
        # the sequential bucket and the aoi_cross_tick path
        xt = bool(i % 2)
        a = soak_aoi(seed, cross_tick=xt)
        f = soak_fused(seed)
        co = soak_cohort(seed)
        g = soak_ingest(seed)
        it = soak_interest(seed)
        c = soak_checkpoint(seed)
        d = soak_dispatcher(seed)
        hf = soak_host_failover(seed)
        print(f"round {i + 1}/{rounds} seed={seed}"
              f"{' xtick' if xt else ''}: "
              f"aoi fired={a['fired']} rebuilds={a['stats']['rebuilds']} "
              f"host_ticks={a['stats']['host_ticks']} "
              f"decode_overflow={a['stats']['decode_overflow']} | "
              f"fused n={f['fused']} demoted={f['demoted']} | "
              f"cohort {co['kind']}@{co['at']} demoted={co['demoted']} "
              f"restacked={co['redispatched']} | "
              f"ingest {g['kind']} demoted={g['demoted']} "
              f"batched={g['batched']} | "
              f"interest {it['kind']}@{it['at']} "
              f"demoted_steps={it['demoted_steps']} | "
              f"ckpt fired={c['fired']} tick={c['restored_tick']} "
              f"torn={c['torn']} | "
              f"disp fired={d['fired']} replayed={d['replayed']} | "
              f"failover zombie@{hf['zombie_at']} "
              f"recover_ticks={hf['recover_ticks']} "
              f"replayed={hf['replayed']} -- "
              f"bit-exact, no stuck buckets")
    print(f"faults_soak: OK ({rounds} rounds, all seams incl. aoi.fused "
          f"and aoi.cohort demotion, aoi.ingest, aoi.interest, store.* "
          f"and clu.* host failover, parity held)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
