"""Durable world state (engine/checkpoint.py): async snapshot-consistent
incremental checkpointing + kill-9 crash-restart recovery.

The contract under test (docs/robustness.md "Durability & crash-restart"):

* a space restored from its journal produces the IDENTICAL enter/leave
  event stream as the uncrashed oracle for >= 20 post-restore ticks,
  across the device bucket tiers (``tpu``/``mesh``/``rowshard``) and with
  the classic encoded stream (``emit="host"``) and the cross-tick
  scheduler on or off;
* the manifest is monotonic in ``(space, epoch, tick)`` and every entry's
  CRC matches its journal record;
* a real ``kill -9`` mid-run loses nothing: restore + replay merged with
  the crashed run's delivered stream equals the uncrashed oracle's,
  per-tick crc32s bit-exact, overlap ticks identical (the dispatcher
  bounded-replay argument across a process boundary);
* the ``store.write`` / ``store.read`` / ``store.manifest`` fault seams
  are deterministically injectable (GW_FAULT_PLAN grammar), self-healing
  (counted retries, re-armable), and torn/poisoned records fall back to
  the last consistent epoch -- never a crash, never a blocked tick.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from goworld_tpu import faults, telemetry
from goworld_tpu.engine.aoi import AOIEngine
from goworld_tpu.engine.checkpoint import (MANIFEST_PREFIX, RECORD_TYPE,
                                           CheckpointController,
                                           _open_backends,
                                           crash_restart_scenario)
from goworld_tpu.telemetry import trace

CAP = 256
PRE = 6     # checkpointed ticks before the simulated crash
POST = 20   # post-restore parity window (the acceptance bar)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    faults.clear()


def _frames(cap, ticks, seed=7, world=100.0, step=3.0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.0, world, cap).astype(np.float32)
    z = rng.uniform(0.0, world, cap).astype(np.float32)
    out = []
    for _ in range(ticks):
        x = x + rng.uniform(-step, step, cap).astype(np.float32)
        z = z + rng.uniform(-step, step, cap).astype(np.float32)
        out.append((x.copy(), z.copy()))
    return out


def _mk(tmp_path, eng, mode="continuous", **kw):
    store, kv = _open_backends(str(tmp_path / "ck"))
    return CheckpointController(eng, store, kv, mode=mode, **kw), store, kv


def _tick(eng, handles, frame, r, act):
    """Submit one frame to every handle, flush once, return each handle's
    (enters, leaves)."""
    x, z = frame
    for h in handles:
        eng.submit(h, x, z, r, act)
    eng.flush()
    return [tuple(np.asarray(a) for a in eng.take_events(h))
            for h in handles]


def _run_restore_parity(tmp_path, tier, host_emit, cross_tick, cap=CAP):
    """Checkpoint a space for PRE ticks, restore it into a SECOND handle
    on the same engine (same already-jitted bucket kernels), then drive
    oracle and restored space through POST identical frames and compare
    the concatenated delivered streams bit-exactly."""
    mesh = 2 if tier in ("mesh", "rowshard") else None
    eng = AOIEngine("cpu", mesh=mesh, cross_tick=cross_tick,
                    emit="host" if host_emit else "auto")
    ctl, store, kv = _mk(tmp_path, eng)
    h = eng._create_handle(cap, tier)
    ctl.track("s", h)
    frames = _frames(cap, PRE + POST)
    r = np.full(cap, 12.0, np.float32)
    act = np.ones(cap, bool)
    for t in range(PRE):
        _tick(eng, [h], frames[t], r, act)
        ctl.step(t + 1)
    assert ctl.drain(), "writer did not drain"
    # the capture's export drained any in-flight cross-tick work, leaving
    # its events pending on the oracle; they are part of the PRE-crash
    # delivered stream (already folded into the snapshot's words), so
    # deliver-and-discard them before the parity window opens
    eng.take_events(h)

    rest = CheckpointController(eng, store, kv, mode="off")
    res = rest.restore_into(eng, "s", tier=tier)
    assert res is not None, "no consistent checkpoint chain"
    h2, tick, epoch = res
    assert tick == PRE and epoch == PRE - 1
    # the capture at PRE drained any in-flight tick on the oracle too, so
    # both sides start the post window from an empty pipeline: identical
    # refill, identical delivery
    oracle, restored = ([], []), ([], [])
    for t in range(PRE, PRE + POST):
        (oe, ol), (re_, rl) = _tick(eng, [h, h2], frames[t], r, act)
        oracle[0].append(oe), oracle[1].append(ol)
        restored[0].append(re_), restored[1].append(rl)
    while eng.has_pending():
        eng.flush()
        for hh, (es, ls) in ((h, oracle), (h2, restored)):
            e, lv = eng.take_events(hh)
            es.append(np.asarray(e)), ls.append(np.asarray(lv))
    for side in (0, 1):
        a = np.concatenate([np.asarray(v).ravel() for v in oracle[side]])
        b = np.concatenate([np.asarray(v).ravel() for v in restored[side]])
        assert np.array_equal(a, b), \
            f"{tier} host_emit={host_emit} xtick={cross_tick}: " \
            "stream diverged"
    assert sum(len(v) for v in oracle[0]) > 0, "degenerate walk: no events"
    ctl.close()
    rest.close()
    store.close()
    kv.close()


# tier-1 covers every tier and every +/-host-emit +/-cross_tick axis; the
# remaining mesh/rowshard single-flag combos ride the @slow sweep (each
# fresh mesh/rowshard engine re-jits its kernels on the CPU backend)
TIER1_COMBOS = [
    ("tpu", False, False),
    ("tpu", True, False),
    ("tpu", False, True),
    ("tpu", True, True),
    ("mesh", True, True),
    ("rowshard", True, True),
]
# The other multi-chip combos cost ~45 s of wall on the virtual CPU mesh;
# the full tier x flag matrix stays pinned under -m slow.
SLOW_COMBOS = [
    ("mesh", False, False),
    ("mesh", True, False),
    ("mesh", False, True),
    ("rowshard", False, False),
    ("rowshard", True, False),
    ("rowshard", False, True),
]


@pytest.mark.parametrize(
    "tier,host_emit,cross_tick", TIER1_COMBOS,
    ids=[f"{t}{'+host' if p else ''}{'+xtick' if c else ''}"
         for t, p, c in TIER1_COMBOS])
def test_restore_parity(tmp_path, tier, host_emit, cross_tick):
    _run_restore_parity(tmp_path, tier, host_emit, cross_tick)


@pytest.mark.slow
@pytest.mark.parametrize(
    "tier,host_emit,cross_tick", SLOW_COMBOS,
    ids=[f"{t}{'+host' if p else ''}{'+xtick' if c else ''}"
         for t, p, c in SLOW_COMBOS])
def test_restore_parity_slow(tmp_path, tier, host_emit, cross_tick):
    _run_restore_parity(tmp_path, tier, host_emit, cross_tick)


# -- the journal itself ------------------------------------------------------

def _drive(ctl, eng, h, frames, start=0):
    n = len(frames[0][0])  # frame length, <= the handle's (rounded) capacity
    r = np.full(n, 12.0, np.float32)
    act = np.ones(n, bool)
    for t, frame in enumerate(frames, start + 1):
        _tick(eng, [h], frame, r, act)
        ctl.step(t)


def test_manifest_monotonic_and_crc_consistent(tmp_path):
    """One manifest entry per durable epoch, epochs strictly increasing,
    ticks non-decreasing, and every entry's CRC matching its record."""
    import json
    import zlib

    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    _drive(ctl, eng, h, _frames(64, 10))
    assert ctl.drain()
    rows = kv.find(f"{MANIFEST_PREFIX}s/", f"{MANIFEST_PREFIX}s/~")
    assert len(rows) == ctl.stats["records_written"] >= 2
    entries = [json.loads(v) for _k, v in rows]
    epochs = [e["epoch"] for e in entries]
    ticks = [e["tick"] for e in entries]
    assert epochs == sorted(epochs) and len(set(epochs)) == len(epochs)
    assert ticks == sorted(ticks)
    assert entries[0]["kind"] == "base"
    for ent in entries:
        rec = store.read(RECORD_TYPE, f"s.{ent['epoch']:08d}")
        assert rec is not None
        assert zlib.crc32(rec["blob"]) & 0xFFFFFFFF == ent["crc"] == rec["crc"]
    ctl.close()


def test_incremental_records_are_deltas(tmp_path):
    """After the base, a mostly-idle space journals deltas a fraction of
    the base's size; a fully-idle tick journals nothing at all."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng)
    h = eng._create_handle(128, "tpu")
    ctl.track("s", h)
    frames = _frames(128, 4)
    _drive(ctl, eng, h, frames)
    # re-submit the last frame: nothing changed -> capture skips entirely
    r = np.full(128, 12.0, np.float32)
    act = np.ones(128, bool)
    _tick(eng, [h], frames[-1], r, act)
    ctl.step(5)
    assert ctl.drain()
    assert ctl.stats["bases"] == 1
    assert ctl.stats["deltas"] == 3
    assert ctl.stats["skipped_empty"] == 1
    base = store.read(RECORD_TYPE, "s.00000000")
    delta = store.read(RECORD_TYPE, "s.00000001")
    assert len(delta["blob"]) < len(base["blob"])
    ctl.close()


def test_pad_packet_page_granular():
    from goworld_tpu.ops import aoi_stage as AS

    def mk(k):
        i = np.arange(k, dtype=np.int32)
        return i, i, i.astype(np.float32), i.astype(np.float32)

    # mid-size packets round to whole pages (<= one page of waste)...
    assert len(AS.pad_packet(*mk(130), page_granular=True)[0]) == 192
    assert len(AS.pad_packet(*mk(130))[0]) == 256  # pow2 default
    # ...tiny and huge packets take the pow2 ladder either way
    assert len(AS.pad_packet(*mk(30), page_granular=True)[0]) == 64
    assert len(AS.pad_packet(*mk(513), page_granular=True)[0]) == 1024
    # padding repeats the last entry (idempotent under the set scatter)
    rows, cols, xv, zv = AS.pad_packet(*mk(130), page_granular=True)
    assert (rows[130:] == 129).all() and (xv[130:] == 129.0).all()


def test_pos_packet_journal_bytes_pinned():
    """A journal's position packets keep their bytes: the page-granular
    padding (packet sizes and the repeated tail) is part of the record
    format, so journals already written stay restorable."""
    import hashlib

    from goworld_tpu.engine.checkpoint import _pos_packet

    digest = hashlib.sha256()
    sizes = []
    for k in (1, 30, 64, 65, 130, 512, 513):
        cols = np.arange(k, dtype=np.int64) * 3
        x = np.linspace(-1.0, 1.0, k).astype(np.float32)
        z = np.linspace(5.0, -5.0, k).astype(np.float32)
        pkt = _pos_packet(cols, x, z)
        sizes.append(pkt["n"])
        for key in ("rows", "cols", "xv", "zv"):
            digest.update(pkt[key])
    assert sizes == [64, 64, 64, 128, 192, 512, 1024]
    assert digest.hexdigest() == ("ae950d436a7cd02d2ac77ca520c5f4fc"
                                  "613cfdffb23983612115dff4566316fa")
    empty = np.zeros(0, np.int64)
    assert _pos_packet(empty, empty.astype(np.float32),
                       empty.astype(np.float32)) is None


def test_full_every_bounds_the_chain(tmp_path):
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng, full_every=3)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    _drive(ctl, eng, h, _frames(64, 9))
    assert ctl.drain()
    assert ctl.stats["bases"] >= 2  # the chain was re-based at least once
    ctl.close()


def test_grow_space_forces_fresh_base(tmp_path):
    """Growth re-homes the slot under a NEW handle; re-tracking it must
    restart the chain from a base (the packed layout changed)."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    _drive(ctl, eng, h, _frames(64, 2))
    h2 = eng.grow_space(h, 2 * h.capacity)
    ctl.track("s", h2)
    big = h2.capacity
    r = np.full(big, 12.0, np.float32)
    act = np.ones(big, bool)
    _tick(eng, [h2], _frames(big, 1, seed=9)[0], r, act)
    ctl.step(3)
    assert ctl.drain()
    assert ctl.stats["bases"] == 2
    res = CheckpointController(eng, store, kv, mode="off") \
        .restore("s")
    assert res is not None
    snap, _tick_, epoch = res
    assert snap["capacity"] == big and epoch == 2  # monotonic across growth
    ctl.close()


# -- kill -9 crash-restart ---------------------------------------------------

def test_kill9_crash_restart_recovery(tmp_path):
    """A real SIGKILL mid-run: restore + replay merged with the crashed
    run's journal equals the uncrashed oracle per-tick, crc-exact, with
    overlap ticks identical -- events_lost == 0, structurally."""
    out = crash_restart_scenario(str(tmp_path), cap=96, world=120.0,
                                 ticks=18, kill_at=12, tier="cpu",
                                 mode="continuous", interval=2)
    assert out["crash_rc"] == -signal.SIGKILL
    assert out["oracle_rc"] == 0 and out["resume_rc"] == 0
    assert 0 <= out["restored_tick"] <= out["kill_tick"]
    assert out["replay_parity_ok"], "overlap ticks diverged (exactly-once)"
    assert out["parity_ok"], "merged stream != oracle stream"
    assert out["events_lost"] == 0
    assert out["oracle_events"] > 0
    assert out["ticks_to_recover"] >= 0


def test_driver_fault_plan_via_env(tmp_path):
    """GW_FAULT_PLAN reaches the subprocess driver through the
    environment: store.write faults fire (deterministically, counted) and
    the journal still lands complete -- the seams self-heal."""
    j = str(tmp_path / "j.journal")
    env = dict(os.environ)
    env["GW_FAULT_PLAN"] = "store.write:fail@2x2;store.manifest:fail@3"
    rc = subprocess.run(
        [sys.executable, "-m", "goworld_tpu.engine.checkpoint",
         "--dir", str(tmp_path / "ck"), "--journal", j, "--ticks", "6",
         "--cap", "64", "--world", "80", "--tier", "cpu",
         "--mode", "continuous", "--seed", "5"],
        env=env, capture_output=True, text=True).returncode
    assert rc == 0, "driver crashed under injected store faults"
    eng = AOIEngine("cpu")
    store, kv = _open_backends(str(tmp_path / "ck"))
    res = CheckpointController(eng, store, kv, mode="off").restore("bench")
    assert res is not None, "no consistent chain despite self-healing"


# -- store.* fault seams -----------------------------------------------------

def test_store_write_fail_retries_and_lands(tmp_path):
    """fail/oom/reset on the journal write: counted retries with backoff,
    the record still lands, the tick never sees the fault."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng, retry_base_s=0.0)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    faults.install("store.write:fail@1x2")
    _drive(ctl, eng, h, _frames(64, 3))
    assert ctl.drain()
    faults.clear()
    assert ctl.stats["write_retries"] == 2
    assert ctl.stats["dropped_epochs"] == 0
    assert ctl.stats["records_written"] == 3
    res = CheckpointController(eng, store, kv, mode="off").restore("s")
    assert res is not None and res[2] == 2
    ctl.close()


def test_store_write_retry_budget_drops_epoch_and_rebase(tmp_path):
    """A write that NEVER succeeds drops that epoch (counted) and forces
    the next capture to a fresh base -- the chain self-heals and restore
    still finds a consistent state."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng, retry_base_s=0.0, max_retries=2)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    r = np.full(64, 12.0, np.float32)
    act = np.ones(64, bool)
    faults.install("store.write:fail@2x2")  # epoch 1's both attempts fail
    # drain per tick so the writer's force_base verdict lands before the
    # next capture (the race a real deployment absorbs with a re-base)
    for t, frame in enumerate(_frames(64, 3), 1):
        _tick(eng, [h], frame, r, act)
        ctl.step(t)
        assert ctl.drain()
    faults.clear()
    assert ctl.stats["dropped_epochs"] == 1
    assert ctl.stats["bases"] == 2  # initial + forced re-base
    res = CheckpointController(eng, store, kv, mode="off").restore("s")
    assert res is not None and res[2] == 2  # the re-based epoch wins
    ctl.close()


def test_store_write_partial_torn_record_falls_back(tmp_path):
    """partial on store.write lands a TORN record (what a mid-write
    SIGKILL leaves): the manifest entry exists but the CRC cannot match,
    so restore falls back to the last consistent epoch below it."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    faults.install("store.write:partial@3:0.5")  # epoch 2 lands torn
    _drive(ctl, eng, h, _frames(64, 4))
    assert ctl.drain()
    faults.clear()
    rest = CheckpointController(eng, store, kv, mode="off")
    res = rest.restore("s")
    assert res is not None
    assert res[2] == 1  # epochs 2 and 3 both chain through the torn one
    assert rest.stats["torn_records"] >= 1
    ctl.close()


def test_store_write_poison_detected_by_crc(tmp_path):
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    faults.install("store.write:poison@2")  # epoch 1's blob corrupted
    _drive(ctl, eng, h, _frames(64, 3))
    assert ctl.drain()
    faults.clear()
    rest = CheckpointController(eng, store, kv, mode="off")
    res = rest.restore("s")
    assert res is not None and res[2] == 0  # only the base survives
    assert rest.stats["torn_records"] >= 1
    ctl.close()


def test_store_read_faults_at_restore(tmp_path):
    """read-side fail retries (counted); read-side poison falls back to
    an earlier consistent epoch -- and a re-armed plan (x2) heals."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng, retry_base_s=0.0)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    _drive(ctl, eng, h, _frames(64, 4))
    assert ctl.drain()
    ctl.close()
    rest = CheckpointController(eng, store, kv, mode="off",
                                retry_base_s=0.0)
    faults.install("store.read:fail@1x2")
    res = rest.restore("s")
    faults.clear()
    assert res is not None and res[2] == 3  # healed: newest epoch intact
    assert rest.stats["read_retries"] == 2
    rest2 = CheckpointController(eng, store, kv, mode="off")
    faults.install("store.read:poison@1")
    res2 = rest2.restore("s")
    faults.clear()
    assert res2 is not None and res2[2] == 2  # newest read poisoned -> back
    assert rest2.stats["torn_records"] >= 1


def test_store_manifest_partial_entry_skipped(tmp_path):
    """partial on the manifest put leaves an unparseable value: restore
    skips it (counted torn) and lands on the epoch below."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    faults.install("store.manifest:partial@4:0.3")  # epoch 3's entry torn
    _drive(ctl, eng, h, _frames(64, 4))
    assert ctl.drain()
    faults.clear()
    rest = CheckpointController(eng, store, kv, mode="off")
    res = rest.restore("s")
    assert res is not None and res[2] == 2
    assert rest.stats["torn_records"] >= 1
    ctl.close()


def test_store_stall_absorbed_by_writer(tmp_path):
    """stall on store.write sleeps on the WRITER thread; the capture side
    stays non-blocking and everything still lands."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    faults.install("store.write:stall@1:0.01")
    _drive(ctl, eng, h, _frames(64, 2))
    assert ctl.drain()
    fired = [f for f in faults.plan().fired if f["seam"] == "store.write"]
    faults.clear()
    assert fired, "stall spec never fired"
    assert ctl.stats["records_written"] == 2
    ctl.close()


def test_backlog_full_drops_and_rebases(tmp_path):
    """A saturated writer queue drops captures (counted, gauge-visible)
    instead of blocking the tick, and the next enqueued capture re-bases
    the chain so restore stays consistent."""
    eng = AOIEngine("cpu")
    ctl, store, kv = _mk(tmp_path, eng, queue_max=1, retry_base_s=0.0)
    h = eng._create_handle(64, "tpu")
    ctl.track("s", h)
    frames = _frames(64, 7)
    r = np.full(64, 12.0, np.float32)
    act = np.ones(64, bool)
    faults.install("store.write:stall@1x4:0.05")  # wedge the writer
    for t in range(6):
        _tick(eng, [h], frames[t], r, act)
        ctl.step(t + 1)
    assert ctl.drain(timeout=10.0)
    faults.clear()
    assert ctl.stats["backlog_drops"] >= 1
    # the post-drop capture restarted the chain from a fresh base
    _tick(eng, [h], frames[6], r, act)
    ctl.step(7)
    assert ctl.drain()
    assert ctl.stats["bases"] >= 2
    res = CheckpointController(eng, store, kv, mode="off").restore("s")
    assert res is not None
    ctl.close()


# -- telemetry catalog -------------------------------------------------------

CKPT_SPANS = ("ckpt.snapshot", "ckpt.delta", "ckpt.flush", "ckpt.restore")
CKPT_METRICS = ("ckpt.bytes", "ckpt.records", "ckpt.epochs", "ckpt.retries",
                "ckpt.torn", "ckpt.backlog", "ckpt.lag_ticks")


def test_ckpt_telemetry_catalog(tmp_path):
    """Every ckpt.* span fires on a checkpoint+restore cycle and every
    ckpt.* instrument moves -- the names here are the docs/observability.md
    catalog rows."""
    from goworld_tpu.engine import checkpoint as ck

    telemetry.enable()
    trace.reset()
    try:
        eng = AOIEngine("cpu")
        ctl, store, kv = _mk(tmp_path, eng)
        h = eng._create_handle(64, "tpu")
        ctl.track("s", h)
        _drive(ctl, eng, h, _frames(64, 3))
        assert ctl.drain()
        rest = CheckpointController(eng, store, kv, mode="off")
        assert rest.restore("s") is not None
        names = {s[0] for s in trace.spans()}
        for span in CKPT_SPANS:
            assert span in names, f"span {span} never fired"
        assert ck._BYTES.value > 0          # ckpt.bytes
        assert ck._RECORDS.value >= 3       # ckpt.records
        assert ck._EPOCHS.value >= 3        # ckpt.epochs
        ctl.close()
    finally:
        telemetry.disable()


# -- runtime / config wiring -------------------------------------------------

def test_runtime_checkpoint_wiring(tmp_path):
    """Runtime(aoi_checkpoint=...) arms the controller, tracks live AOI
    spaces each tick, and the journaled state restores."""
    from goworld_tpu.engine.entity import Entity
    from goworld_tpu.engine.runtime import Runtime
    from goworld_tpu.engine.space import Space
    from goworld_tpu.engine.vector import Vector3

    class CkptScene(Space):
        pass

    class CkptWalker(Entity):
        use_aoi = True
        aoi_distance = 30.0

    rt = Runtime(aoi_checkpoint="interval", aoi_checkpoint_interval=2,
                 aoi_checkpoint_dir=str(tmp_path))
    rt.entities.register(CkptScene)
    rt.entities.register(CkptWalker)
    sp = rt.entities.create_space("CkptScene", kind=1)
    sp.enable_aoi(30.0)
    rng = np.random.default_rng(3)
    es = [rt.entities.create(
        "CkptWalker", space=sp,
        pos=Vector3(rng.uniform(0, 40), 0.0, rng.uniform(0, 40)))
        for _ in range(8)]
    for _t in range(6):
        for e in es:
            e.set_position(Vector3(e.position.x + 1.0, 0, e.position.z))
        rt.tick()
    assert rt.checkpoint.drain()
    assert rt.checkpoint.stats["records_written"] >= 1
    res = rt.checkpoint.restore(sp.id)
    assert res is not None
    snap, tick, _epoch = res
    assert tick in (2, 4, 6) and snap["act"].sum() == 8
    rt.checkpoint.close()


def test_runtime_checkpoint_requires_backends():
    from goworld_tpu.engine.runtime import Runtime

    with pytest.raises(ValueError, match="aoi_checkpoint"):
        Runtime(aoi_checkpoint="interval")


def test_game_config_checkpoint_knobs():
    from goworld_tpu import config

    cfg = config.loads(
        "[deployment]\ndispatchers = 1\ngames = 1\ngates = 1\n"
        "[game_common]\naoi_checkpoint = continuous\n"
        "aoi_checkpoint_interval = 8\n"
        "[dispatcher1]\n[game1]\n[gate1]\n")
    g = cfg.games[1]
    assert g.aoi_checkpoint == "continuous"
    assert g.aoi_checkpoint_interval == 8


def test_game_service_attach_checkpoints(tmp_path):
    """GameService builds the journal/manifest from the [storage]/[kvdb]
    config and arms the runtime controller (off -> None)."""
    from goworld_tpu import config
    from goworld_tpu.components.game.service import GameService

    ini = ("[deployment]\ndispatchers = 1\ngames = 1\ngates = 1\n"
           "[game_common]\naoi_checkpoint = interval\n"
           "[dispatcher1]\n[game1]\n[gate1]\n")
    cfg = config.loads(ini)
    svc = GameService(1, cfg, freeze_dir=str(tmp_path))
    ctl = svc.attach_checkpoints(str(tmp_path))
    assert ctl is not None and ctl is svc.rt.checkpoint
    assert ctl.mode == "interval"
    ctl.close()
    cfg_off = config.loads(ini.replace("interval", "off"))
    svc_off = GameService(1, cfg_off, freeze_dir=str(tmp_path))
    assert svc_off.attach_checkpoints(str(tmp_path)) is None
