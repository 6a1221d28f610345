"""Event replay as columns (engine/space.py ``_replay_columns``) against
the per-pair path it replaced, which observers with AOI hooks still take.

The same seeded world runs twice: once with players of default hooks,
whose pairs replay as whole arrays, and once with a subclass that
overrides the hooks with no-ops, which sends every player pair through
``_interest``/``_uninterest`` (the oracle).  Each tick moves entities,
spawns, destroys and re-enters some (a freed slot is reused), leaves
attr deltas pending on entities that enter a client's view, and binds,
drops and hands over clients between ticks.  After every tick the two
worlds must agree on each client's outbox, the watch index, the watcher
counts, the interest views and the drained sync records.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from goworld_tpu import telemetry
from goworld_tpu.engine.entity import Entity, GameClient
from goworld_tpu.engine.runtime import Runtime
from goworld_tpu.engine.space import Space
from goworld_tpu.engine.vector import Vector3

RADIUS = 40.0
SIDE = 240.0


class Scene(Space):
    pass


class Mob(Entity):  # an NPC: plain, shows one attr to neighbours
    use_aoi = True
    aoi_distance = RADIUS
    all_client_attrs = frozenset({"hp"})


class Rock(Entity):  # plain, shows nothing
    use_aoi = True
    aoi_distance = RADIUS


class Player(Entity):  # default hooks: replayed as columns with a client
    use_aoi = True
    aoi_distance = RADIUS
    all_client_attrs = frozenset({"hp", "bag"})
    client_attrs = frozenset({"secret"})


class HookedPlayer(Player):  # the same, replayed per pair
    def on_enter_aoi(self, other):
        self.manager.runtime.hook_calls += 1

    def on_leave_aoi(self, other):
        self.manager.runtime.hook_calls += 1


class HookedPlayer2(HookedPlayer):  # a second hooked type name
    pass


def _id(prefix, n):
    return (prefix + str(n)).ljust(16, "x")


class World:
    """One runtime driven by a seeded script.  ``hooked`` picks the class
    of type "Player"; with ``split`` every other player is of type
    "HookedPlayer", whose class always has hooks."""

    def __init__(self, backend, hooked, seed, split=False):
        self.rng = random.Random(seed)
        rt = self.rt = Runtime(aoi_backend=backend, aoi_tpu_min_capacity=16)
        rt.hook_calls = 0
        rt.entities.register(Scene)
        rt.entities.register(Mob)
        rt.entities.register(Rock)
        rt.entities.register(HookedPlayer if hooked else Player,
                             type_name="Player")
        rt.entities.register(HookedPlayer2, type_name="HookedPlayer")
        self.split = split
        self.spaces = []
        for i in range(2):
            sp = rt.entities.create_space("Scene", kind=1,
                                          eid=_id("space", i))
            sp.enable_aoi(RADIUS)
            self.spaces.append(sp)
        self.n = 0
        self.clients: dict[str, GameClient] = {}
        self.order: list[str] = []  # entity ids, in creation order
        for sp in self.spaces:
            for kind, k in (("Mob", 30), ("Rock", 6), ("Player", 14)):
                for _ in range(k):
                    self.spawn(kind, sp)

    # -- the script: every choice comes from the rng and the (shared) state
    def pos(self):
        r = self.rng
        return Vector3(r.uniform(0, SIDE), 0.0, r.uniform(0, SIDE))

    def spawn(self, kind, sp, pos=None, attrs=None):
        self.n += 1
        if kind == "Player" and self.split and self.n % 2:
            kind = "HookedPlayer"
        e = self.rt.entities.create(kind, space=sp, pos=pos or self.pos(),
                                    eid=_id("e", self.n), attrs=attrs)
        self.order.append(e.id)
        if kind in ("Player", "HookedPlayer") and self.rng.random() < 0.6:
            self.bind(e)
        return e

    def bind(self, e):
        cid = _id("c", len(self.clients))
        c = self.clients[cid] = GameClient(cid, len(self.clients) % 3)
        e.set_client(c)

    def live(self, *kinds):
        ents = self.rt.entities.entities
        out = [ents[i] for i in self.order if i in ents]
        return [e for e in out if e.space is not None
                and (not kinds or e.type_name in kinds)]

    def players(self):
        return self.live("Player", "HookedPlayer")

    def churn(self):
        r = self.rng
        ps = self.players()
        for e in r.sample(ps, 4):
            if e.client is None:
                self.bind(e)
            elif r.random() < 0.3:
                e.drop_client_ref()
            elif r.random() < 0.5:
                e.set_client(None)
            else:
                takers = [x for x in ps if x.client is None]
                if takers:
                    e.give_client_to(r.choice(takers))
        victim = r.choice(self.live("Mob", "Player", "HookedPlayer"))
        if r.random() < 0.5:
            victim.destroy()
        else:  # over to the other space
            other = self.spaces[victim.space is self.spaces[0]]
            victim.enter_space(other.id, self.pos())
        # leave and re-enter the same space: a new slot, its old one cools
        hop = r.choice(self.live("Mob", "Rock"))
        sp = hop.space
        sp.leave_entity(hop)
        sp.enter_entity(hop, self.pos())
        for sp in self.spaces:
            kind = r.choice(("Mob", "Rock", "Player"))
            attrs = {"hp": r.randrange(100), "bag": [r.randrange(9)]} \
                if kind != "Rock" else None
            self.spawn(kind, sp, attrs=attrs)

    def move(self):
        r = self.rng
        held = set()
        # a client player jumps to where it saw nothing; two entities
        # there get attr deltas, pending when they enter its view
        ps = [e for e in self.players() if e.client is not None]
        if ps:
            p = r.choice(ps)
            here = p.position
            for _ in range(20):
                to = self.pos()
                if abs(to.x - here.x) + abs(to.z - here.z) > 4 * RADIUS:
                    break
            near = [e for e in self.live("Mob", "Player", "HookedPlayer")
                    if e.space is p.space and e is not p
                    and abs(e.position.x - to.x) < RADIUS / 3
                    and abs(e.position.z - to.z) < RADIUS / 3]
            p.set_position(to)
            held.add(p)
            for e in near[:2]:
                held.add(e)
                e.attrs.set("hp", r.randrange(100))
                if e.type_name != "Mob":
                    e.attrs.set("bag", [r.randrange(9), r.randrange(9)])
                    e.attrs.set("secret", r.randrange(9))
        for e in self.players():
            if e not in held and r.random() < 0.5:
                e.set_position(self.pos())
        for sp in self.spaces:
            mobs = [e for e in self.live("Mob", "Rock")
                    if e.space is sp and e not in held and r.random() < 0.7]
            if mobs:
                xs = np.array([r.uniform(0, SIDE) for _ in mobs], np.float32)
                zs = np.array([r.uniform(0, SIDE) for _ in mobs], np.float32)
                sp.move_entities([e.aoi_slot for e in mobs], xs, zs)

    # -- what the two worlds must agree on
    def state(self):
        ids = lambda es: sorted(x.id for x in es)  # noqa: E731
        ents = {}
        for e in self.live():
            watched_by = [o for o in e.interested_by if o.client is not None]
            ents[e.id] = {
                "slot": e.aoi_slot,
                "watchers": e._watcher_clients,
                # a clientless observer keeps an eager set only when its
                # type has hooks; with default hooks it is plain and its
                # neighbours derive from the packed words (neighbors)
                "in": ids(e.interested_in) if e.client is not None else None,
                "by": ids(watched_by),
                "neighbors": ids(e.neighbors()),
                "observers": ids(e.observers()),
            }
        return {
            "outbox": {c: list(g.outbox) for c, g in self.clients.items()},
            "watch": [sp._watch.tolist() for sp in self.spaces],
            "entities": ents,
            "sync": sorted(self.rt.drain_sync()),
        }

    def step(self, t):
        if t % 2:
            self.churn()
        self.move()
        self.rt.tick()
        out = self.state()
        for c in self.clients.values():
            c.outbox.clear()
        return out


def _creates_share_nothing(outboxes):
    seen = set()
    for box in outboxes:
        for op in box:
            if op[0] == "create_entity":
                for v in (op[4], *op[4].values()):
                    if isinstance(v, (dict, list)):
                        assert id(v) not in seen, op
                        seen.add(id(v))


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_column_replay_matches_the_per_pair_oracle(backend):
    cols, oracle = World(backend, False, 3), World(backend, True, 3)
    creates = deltas = 0
    for t in range(12):
        got, want = cols.step(t), oracle.step(t)
        assert got["outbox"] == want["outbox"], t
        assert got["watch"] == want["watch"], t
        assert got["entities"] == want["entities"], t
        assert got["sync"] == want["sync"], t
        boxes = [b for b in got["outbox"].values()]
        _creates_share_nothing(boxes)
        ops = [op[0] for b in boxes for op in b]
        creates += ops.count("create_entity")
        deltas += ops.count("attr_delta")
    assert creates > 200 and deltas > 10
    # the column world ran no hook; the oracle's players ran them all
    assert cols.rt.hook_calls == 0 and oracle.rt.hook_calls > creates


def _by_entity(box):
    """A client's ops, per entity they concern, in order: the order the
    contract keeps where hooked and column observers share a space."""
    out: dict = {}
    for op in box:
        out.setdefault(op[1] if op[0] == "attr_delta" else op[2],
                       []).append(op)
    return out


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_hooked_and_column_observers_in_one_space(backend):
    mixed = World(backend, False, 8, split=True)
    oracle = World(backend, True, 8, split=True)
    assert {type(e) for e in mixed.players()} == {Player, HookedPlayer2}
    for t in range(10):
        got, want = mixed.step(t), oracle.step(t)
        for c in want["outbox"]:
            g, w = got["outbox"][c], want["outbox"][c]
            assert sorted(map(repr, g)) == sorted(map(repr, w)), (t, c)
            assert _by_entity(g) == _by_entity(w), (t, c)
            # create/destroy ops keep their exact order
            assert ([op for op in g if op[0] != "attr_delta"]
                    == [op for op in w if op[0] != "attr_delta"]), (t, c)
        assert got["watch"] == want["watch"], t
        assert got["entities"] == want["entities"], t
        assert got["sync"] == want["sync"], t


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_replay_counters_split_by_path(backend):
    w = World(backend, False, 5, split=True)
    w.step(0)
    reg = telemetry.registry()
    cols, hooked = (reg.counter("aoi.replay_columns"),
                    reg.counter("aoi.replay_hooked"))
    for t in range(1, 4):
        w.move()  # no churn: every op of the tick comes from replay
        c0, h0, calls0 = cols.value, hooked.value, w.rt.hook_calls
        telemetry.enable()
        try:
            w.rt.tick()
        finally:
            telemetry.disable()
        column_ops = sum(
            1 for e in w.players() if e.type_name == "Player"
            and e.client is not None
            for op in e.client.outbox if op[0] != "attr_delta")
        assert cols.value - c0 == column_ops > 0, t
        assert hooked.value - h0 == w.rt.hook_calls - calls0 > 0, t
        w.state()
        for c in w.clients.values():
            c.outbox.clear()


def test_move_entities_flags_watched_slots_from_the_columns():
    """``move_entities`` picks the slots to flag from the ``watchers`` and
    ``client`` columns: a watched NPC and a player are flagged, an
    unwatched NPC is not, and the column counts what the watch index
    holds."""
    w = World("cpu", False, 2)
    w.step(0)
    for sp in w.spaces:
        counts = np.bincount(sp._watch >> 32, minlength=sp._cap)
        np.testing.assert_array_equal(sp._cols.watchers, counts)
    sp = w.spaces[0]
    mobs = [e for e in w.live("Mob") if e.space is sp]
    watched = [e for e in mobs if e._watcher_clients > 0]
    alone = [e for e in mobs if e._watcher_clients == 0]
    player = next(e for e in w.players()
                  if e.space is sp and e.client is not None)
    assert watched and alone
    w.rt._dirty_entities.clear()
    movers = [watched[0], alone[0], player]
    for e in movers:
        e._sync_flags = 0
    sp.move_entities([e.aoi_slot for e in movers],
                     np.array([e.position.x for e in movers], np.float32),
                     np.array([e.position.z for e in movers], np.float32))
    assert w.rt._dirty_entities == {watched[0], player}
    assert alone[0]._sync_flags == 0
