"""The sync phase's columnar collect (engine/space.py, engine/sync.py)
against the per-record walk it replaced.

Random worlds on the cpu and tpu (CPU-emulated) backends, serial and
pipelined: players move through MovementIngest batches and set_position,
NPCs through move_entities; clients are bound, dropped and given to other
entities; entities leave spaces and are destroyed between ticks and inside
an on_enter_aoi hook, which also re-enters one at a new slot.  Every tick, right before the sync phase and after
the tick, each space's watch index must equal its rebuild from the
interest sets, and the drained records must equal, as a multiset, what the
old per-record walk (kept below as the reference) builds from the same
state.  The game's packing is held to the old per-record packet loop.
"""

from __future__ import annotations

import random
from collections import Counter

import numpy as np
import pytest

from goworld_tpu import telemetry
from goworld_tpu.components.game.service import GameService
from goworld_tpu.engine.entity import (SYNC_NEIGHBORS, SYNC_OWN, Entity,
                                       GameClient)
from goworld_tpu.engine.runtime import Runtime
from goworld_tpu.engine.space import Space
from goworld_tpu.engine.sync import SyncBatch
from goworld_tpu.engine.vector import Vector3
from goworld_tpu.ingest import MovementIngest
from goworld_tpu.netutil import Packet
from goworld_tpu.proto.connection import GWConnection

RADIUS = 40.0
SIDE = 200.0


class Scene(Space):
    pass


class Mob(Entity):  # an NPC: plain, moved by move_entities
    use_aoi = True
    aoi_distance = RADIUS


class Hero(Entity):  # takes clients
    use_aoi = True
    aoi_distance = RADIUS


class Hooked(Entity):  # non-plain: on enter, mid-replay, destroys a doomed
    use_aoi = True      # entity, re-enters another at a new slot, and
    aoi_distance = RADIUS  # binds a client to a third

    def on_enter_aoi(self, other):
        rt = self.manager.runtime
        while rt.hops:
            v = rt.hops.pop()
            if not v.destroyed and v.space is not None:
                sp = v.space
                sp.leave_entity(v)
                sp.enter_entity(v, Vector3(v.position.x, 0.0,
                                           v.position.z))
                break
        while rt.doomed:
            v = rt.doomed.pop()
            if not v.destroyed:
                v.destroy()
                break
        while rt.unbound:
            v = rt.unbound.pop()
            if not v.destroyed and v.client is None:
                v.set_client(GameClient(("h%d" % v.aoi_slot).ljust(16, "x"),
                                        1))
                break


class Ghost(Entity):  # no AOI slot: its records take the per-entity walk
    use_aoi = False


# -- the reference: the per-record walk the columnar collect replaced ------

def walk_records(rt) -> list[tuple]:
    """The records the old sync phase built from the current state: fold
    the watched slots' column flags into each entity's flags, then one
    record per flagged entity's own client and per client in its
    interested_by."""
    flags: dict = {}
    for sp in rt._col_sync_spaces:
        col = sp._cols.sync[: sp._slot_watermark]
        for s in np.flatnonzero(col).tolist():
            e = sp._slot_np[s]
            if e is not None and not e.destroyed:
                flags[e] = flags.get(e, 0) | int(col[s])
    for e in rt._dirty_entities:
        if not e.destroyed and e._sync_flags:
            flags[e] = flags.get(e, 0) | e._sync_flags
    out = []
    for e, f in flags.items():
        if not (e.client is not None
                or (f & SYNC_NEIGHBORS and e._watcher_clients > 0)):
            continue
        x, y, z = e.position.to_tuple()
        if f & SYNC_OWN and e.client is not None:
            out.append((e.client.client_id, e.client.gate_id, e.id,
                        x, y, z, e.yaw))
        if f & SYNC_NEIGHBORS and e._watcher_clients > 0:
            for other in e.interested_by:
                if other.client is not None:
                    out.append((other.client.client_id, other.client.gate_id,
                                e.id, x, y, z, e.yaw))
    return out


def watch_from_sets(sp) -> np.ndarray:
    """The watch index rebuilt from the interest sets (the debug check)."""
    codes = set()
    for t in sp._slot_entity:
        if t is None:
            continue
        for o in t.interested_by:
            if o.client is not None and o.space is sp and o.aoi_slot >= 0:
                codes.add(t.aoi_slot << 32 | o.aoi_slot)
    return np.array(sorted(codes), np.int64)


def assert_index(rt):
    for sp in rt.entities.spaces.values():
        if sp.aoi_enabled:
            np.testing.assert_array_equal(sp._watch, watch_from_sets(sp))


def pack_by_loop(records) -> dict[int, bytes]:
    """The game's old per-record packing (GWConnection.append_sync_record)."""
    out: dict[int, Packet] = {}
    for cid, gate, eid, x, y, z, yaw in records:
        p = out.setdefault(gate, Packet(bytearray()))
        p.append_client_id(cid)
        p.append_entity_id(eid)
        for v in (x, y, z, yaw):
            p.append_f32(v)
    return {g: bytes(p.buf) for g, p in out.items()}


def sorted_records(raw: bytes) -> list[bytes]:
    return sorted(raw[i:i + 48] for i in range(0, len(raw), 48))


# -- the random world ------------------------------------------------------

class World:
    def __init__(self, backend, pipeline, seed):
        self.rng = random.Random(seed)
        rt = self.rt = Runtime(aoi_backend=backend, aoi_pipeline=pipeline,
                               aoi_tpu_min_capacity=16)
        rt.doomed, rt.unbound, rt.hops = [], [], []
        for cls in (Scene, Mob, Hero, Hooked, Ghost):
            rt.entities.register(cls)
        self.spaces = []
        for _ in range(2):
            sp = rt.entities.create_space("Scene", kind=1)
            sp.enable_aoi(RADIUS)
            self.spaces.append(sp)
        self.ingest = MovementIngest(rt)
        self.n_clients = 0
        for sp in self.spaces:
            for kind, n in (("Mob", 24), ("Hero", 10), ("Hooked", 4)):
                for _ in range(n):
                    self.spawn(kind, sp)
        self.ghost = rt.entities.create("Ghost", space=self.spaces[0],
                                        pos=Vector3(1.0, 2.0, 3.0))
        self.bind(self.ghost)
        self.wanted: list[list[tuple]] = []
        real = rt._sync_phase

        def phase():
            assert_index(rt)
            self.wanted.append(walk_records(rt))
            real()

        rt._sync_phase = phase

    def pos(self):
        r = self.rng
        return Vector3(r.uniform(0, SIDE), 0.0, r.uniform(0, SIDE))

    def spawn(self, kind, sp):
        e = self.rt.entities.create(kind, space=sp, pos=self.pos())
        if kind == "Hero":
            if self.rng.random() < 0.5:
                e.set_client_syncing(True)
            if self.rng.random() < 0.7:
                self.bind(e)
        elif kind == "Hooked" and self.rng.random() < 0.5:
            self.bind(e)
        return e

    def bind(self, e):
        self.n_clients += 1
        e.set_client(GameClient(("c%d" % self.n_clients).ljust(16, "x"),
                                self.n_clients % 3))

    def live(self, *kinds):
        return [e for e in self.rt.entities.entities.values()
                if e.type_name in kinds and not e.destroyed
                and e.space is not None]

    def churn(self):
        r, rt = self.rng, self.rt
        heroes = self.live("Hero", "Hooked")
        for e in r.sample(heroes, 3):
            if e.client is None:
                self.bind(e)
            elif r.random() < 0.4:
                e.drop_client_ref()
            elif r.random() < 0.5:
                e.set_client(None)
            else:
                takers = [x for x in heroes if x.client is None]
                if takers:
                    e.give_client_to(r.choice(takers))
        victim = r.choice(self.live("Mob", "Hero"))
        if r.random() < 0.5:
            victim.destroy()
        else:  # leave one space for the other
            other = self.spaces[victim.space is self.spaces[0]]
            victim.enter_space(other.id, self.pos())
        for sp in self.spaces:
            self.spawn(r.choice(("Mob", "Hero", "Hooked")), sp)
        # destroyed, or given a client, inside an on_enter_aoi hook during
        # the next tick
        rt.doomed[:] = r.sample(self.live("Mob", "Hero"), 2)
        rt.unbound[:] = [e for e in self.live("Hero", "Hooked")
                         if e.client is None][:2]
        rt.hops[:] = [e for e in self.live("Hero") if e.client is not None][:1]

    def move(self):
        r = self.rng
        pkt = Packet(bytearray())
        for e in self.live("Hero", "Hooked"):
            u = r.random()
            if u < 0.4 and e.client_syncing:
                p = self.pos()
                pkt.append_entity_id(e.id)
                for v in (p.x, 1.5, p.z, r.uniform(-3, 3)):
                    pkt.append_f32(v)
            elif u < 0.7:
                e.set_position(self.pos())
            elif u < 0.8:
                e.set_yaw(r.uniform(-3, 3))
        if len(pkt.buf):
            self.ingest.ingest(pkt)
        for sp in self.spaces:
            mobs = [e for e in self.live("Mob") if e.space is sp]
            moving = [e for e in mobs if r.random() < 0.8]
            if moving:
                xs = np.array([r.uniform(0, SIDE) for _ in moving],
                              np.float32)
                zs = np.array([r.uniform(0, SIDE) for _ in moving],
                              np.float32)
                sp.move_entities([e.aoi_slot for e in moving], xs, zs)
        self.ghost.set_position(self.pos())

    def tick(self) -> tuple[list[tuple], list[tuple]]:
        self.rt.tick()
        assert_index(self.rt)
        got = list(self.rt.drain_sync())
        return got, self.wanted.pop()


@pytest.mark.parametrize("backend,pipeline", [
    ("cpu", False), ("tpu", False), ("tpu", True)],
    ids=["cpu", "tpu", "tpu-pipe"])
def test_columnar_collect_matches_the_walk(backend, pipeline):
    w = World(backend, pipeline, seed=7 * len(backend) + pipeline)
    total = 0
    for t in range(14):
        if t % 2:
            w.churn()
        w.move()
        got, want = w.tick()
        assert Counter(got) == Counter(want), t
        total += len(got)
    assert total > 500
    assert not (w.rt.doomed or w.rt.unbound or w.rt.hops)  # the hooks ran


def test_walked_counts_only_slotless_records():
    w = World("cpu", False, seed=5)
    w.tick()
    walked = telemetry.registry().counter("sync.walked")

    def counted_tick():
        # telemetry on for the tick alone: the moves' ingest counters stay
        # as other tests expect them
        telemetry.enable()
        try:
            v0 = walked.value
            got, want = w.tick()
            return got, want, walked.value - v0
        finally:
            telemetry.disable()

    w.move()
    got, want, n = counted_tick()
    assert Counter(got) == Counter(want)
    assert [r for r in got if r[2] == w.ghost.id] == [
        (w.ghost.client.client_id, w.ghost.client.gate_id, w.ghost.id,
         *w.ghost.position.to_tuple(), w.ghost.yaw)]  # its own client's
    assert n == 1
    w.ghost.destroy()
    w.move()
    got, want, n = counted_tick()
    assert Counter(got) == Counter(want) and len(got) > 0
    assert n == 0  # every mover holds a slot


def test_batch_reads_like_the_tuples():
    recs = [("a".ljust(16, "x"), 2, "b".ljust(16, "y"), 1.5, 0.0, -2.25, 0.5),
            ("c1", 0, "e1", 3.0, 1.0, 2.0, 0.0)]
    b = SyncBatch.from_records(recs)
    assert len(b) == 2 and list(b) == recs and b[1] == recs[1]
    b[0] = recs[1]
    assert b[0] == recs[1] and b[-1] == recs[1]
    b.add_walked([recs[0]])
    assert len(b) == 3 and list(b)[2] == recs[0]
    assert not SyncBatch() and list(SyncBatch.from_records([])) == []


def test_pack_matches_the_record_loop():
    """Per gate, the batch's packed bytes are the old append loop's records
    (compared sorted: order is not a contract); ids that are not 16 ascii
    bytes are refused as append_entity_id refuses them."""
    w = World("cpu", False, seed=11)
    for _ in range(3):
        w.move()
        w.tick()
    w.move()
    w.rt.tick()
    batch = w.rt.drain_sync()
    want = pack_by_loop(batch)
    got = batch.pack_by_gate()
    assert len(got) > 1 and got.keys() == want.keys()
    for g in want:
        assert sorted_records(got[g]) == sorted_records(want[g])
    # the game's packets: the gate's packet header, then those records
    sent = {}
    stub = type("Stub", (), {})()
    stub.rt = type("Rt", (), {"drain_sync": staticmethod(lambda: batch)})()
    stub.cluster = type("C", (), {"by_gate": staticmethod(
        lambda g: type("Conn", (), {"send": staticmethod(
            lambda p, g=g: sent.__setitem__(g, bytes(p.buf)))})())})()
    GameService._send_position_syncs(stub)
    for g, raw in sent.items():
        head = bytes(GWConnection.make_sync_on_clients_packet(g).buf)
        assert raw[:len(head)] == head
        assert sorted_records(raw[len(head):]) == sorted_records(want[g])
    with pytest.raises(ValueError):
        SyncBatch.from_records([("short", 0, "e" * 16, 0, 0, 0, 0)]
                               ).pack_by_gate()
