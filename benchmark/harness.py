"""One in-process game shard, driven closed loop on the served path.

Each tick, in this order (the path live traffic takes through a game):

1. the gates' batches of 32-byte sync records for the moving players go
   through ``MovementIngest.ingest``;
2. the moving NPCs go through ``Space.move_entities``;
3. ``Runtime.tick()`` runs;
4. the position-sync records (``Runtime.drain_sync``) and the players'
   ``GameClient`` outboxes are drained.

The next tick's batch is built only when the tick has returned.  The
harness keeps its own spans around the four steps, on
``time.perf_counter`` (the program's span clock, enabled so in
``enable_spans``), and reads nothing of the program but its public entry
points, the enter/leave pairs at ``AOIEngine.take_events`` (the event
seam ``Space.dispatch_aoi_events`` calls) and, for the fallback check, the
buckets' ``stats`` under ``rt.aoi``.
"""

from __future__ import annotations

import time

import numpy as np

from . import generator as G

# bucket stats whose rise means the AOI computation left the chip
FALLBACK_STATS = ("calc_level", "host_ticks", "fallbacks", "rebuilds")
# counted on an earlier line, not failures
COUNTED_STATS = ("decode_overflow", "page_spills", "poisoned")


class Span:
    """The harness's own span list: (name, t0, t1) on perf_counter, also
    written into the profiler's trace while a TraceAnnotation factory is
    set (traced runs)."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.annotate = None

    def __call__(self, name):
        return _SpanCtx(self, name)


class _SpanCtx:
    __slots__ = ("owner", "name", "t0", "annot")

    def __init__(self, owner, name):
        self.owner, self.name = owner, name

    def __enter__(self):
        f = self.owner.annotate
        self.annot = f(self.name) if f is not None else None
        if self.annot is not None:
            self.annot.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.annot is not None:
            self.annot.__exit__(None, None, None)
        self.owner.spans.append((self.name, self.t0, t1))
        return False


class TickRecord:
    __slots__ = ("start", "end", "moves", "records", "ops", "events",
                 "sync", "creates", "movers", "pos", "prev_pos")

    def __init__(self):
        self.events = None   # per space (enter, leave) slot pairs, if kept
        self.sync = None     # drained sync records, if kept
        self.creates = None  # (client id, entity id, position) of the
        #                      create ops drained, if kept


class Shard:
    """The world of one cell: a Runtime with its spaces, entities and
    players, the cell's traffic, and the event seam's capture."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from goworld_tpu.engine.entity import Entity, GameClient
        from goworld_tpu.engine.runtime import Runtime
        from goworld_tpu.engine.space import Space
        from goworld_tpu.engine.vector import Vector3
        from goworld_tpu.ingest.movement import MovementIngest

        self.cfg, self.mix = cfg, mix
        self.traffic = tr = G.Traffic(cfg, mix, seed)
        self.gate = G.GateBatches(tr)
        self.span = Span()
        self.radius = tr.radius
        shard = self

        class BenchScene(Space):
            def dispatch_aoi_events(self):
                shard._current = self.bench_index
                try:
                    super().dispatch_aoi_events()
                finally:
                    shard._current = None

        class BenchNPC(Entity):
            use_aoi = True

            def on_created(self):
                # runs before the entity enters its space: its own radius
                self.aoi_distance = shard._next_radius

        class BenchPlayer(BenchNPC):
            pass

        class BenchWatcher(BenchNPC):
            # an enter hook keeps its space subscribed to the event stream
            def on_enter_aoi(self, other):
                pass

        rt = Runtime(**cfg["engine"])
        self.rt = rt
        for cls in (BenchScene, BenchNPC, BenchPlayer, BenchWatcher):
            rt.entities.register(cls)
        self.ingest = MovementIngest(rt)
        self._current = None
        self._keep = False
        self._caught: list = [None] * tr.spaces
        take = rt.aoi.take_events

        def take_events(h):
            ev = take(h)
            if self._keep and self._current is not None:
                self._caught[self._current] = (ev[0].copy(), ev[1].copy())
            return ev

        rt.aoi.take_events = take_events
        self._dirty_clients: list = []
        kinds = ("BenchNPC", "BenchPlayer", "BenchWatcher")
        self.scenes, self.slots = [], []
        self.index_of_slot = []
        self.client_index: dict[str, tuple[int, int]] = {}
        self.entity_index: dict[str, tuple[int, int]] = {}
        for s in range(tr.spaces):
            scene = rt.entities.create_space("BenchScene", kind=1)
            scene.bench_index = s
            scene.enable_aoi(float(tr.radius[s].max()),
                             capacity=int(cfg["capacity"]))
            slots = np.empty(tr.n, np.int64)
            for i in range(tr.n):
                role = int(tr.roles[s, i])
                eid = G.entity_id(s, i)
                self._next_radius = float(tr.radius[s, i])
                e = rt.entities.create(
                    kinds[role], space=scene, eid=eid,
                    pos=Vector3(float(tr.pos[s, i, 0]), 0.0,
                                float(tr.pos[s, i, 1])))
                slots[i] = e.aoi_slot
                self.entity_index[eid] = (s, i)
                if role == 1:
                    cid = G.client_id(s, i)
                    e.set_client_syncing(True)
                    e.set_client(GameClient(
                        cid, int(self.gate.gate[len(self.client_index)]),
                        on_dirty=self._dirty_clients.append))
                    self.client_index[cid] = (s, i)
            inv = np.full(int(slots.max()) + 1, -1, np.int64)
            inv[slots] = np.arange(tr.n)
            if (inv < 0).any() or len(inv) != tr.n:
                raise RuntimeError(f"space {s}: slots are not 0..n-1")
            self.scenes.append(scene)
            self.slots.append(slots)
            self.index_of_slot.append(inv)
        self.n_players = int((tr.roles == 1).sum())
        self.errors = 0

        def on_error(e):
            import traceback

            self.errors += 1
            traceback.print_exception(type(e), e, e.__traceback__)

        rt.on_error = on_error

    # -- one tick -----------------------------------------------------------
    def tick(self, moving: bool = True, keep: bool = False) -> TickRecord:
        """Run one tick; ``keep`` keeps its delivered events and sync
        records and the positions before and after it (for the check)."""
        from goworld_tpu.netutil.packet import Packet

        tr, span, rec = self.traffic, self.span, TickRecord()
        prev = tr.pos
        if moving:
            movers = tr.step()
            batches = self.gate.batches(tr.pos, movers)
            npc = movers & (tr.roles != 1)
        else:
            movers = np.zeros((tr.spaces, tr.n), bool)
            batches, npc = [], movers
        rec.moves = int(movers.sum())
        self._keep = keep
        self._caught = [None] * tr.spaces
        rec.start = time.perf_counter()
        with span("bench.ingest"):
            for buf in batches:
                if buf:
                    self.ingest.ingest(Packet(bytearray(buf)))
        with span("bench.npc_move"):
            for s, scene in enumerate(self.scenes):
                idx = np.nonzero(npc[s])[0]
                if len(idx):
                    scene.move_entities(self.slots[s][idx],
                                        tr.pos[s, idx, 0],
                                        tr.pos[s, idx, 1])
        with span("bench.tick"):
            self.rt.tick()
        with span("bench.drain"):
            sync = self.rt.drain_sync()
            ops, creates = 0, []
            for c in self._dirty_clients:
                ops += len(c.outbox)
                if keep:
                    creates += [(c.client_id, op[2], op[5])
                                for op in c.outbox
                                if op[0] == "create_entity"]
                c.outbox.clear()
            self._dirty_clients.clear()
        rec.end = time.perf_counter()
        self._keep = False
        rec.records, rec.ops = len(sync), ops
        if keep:
            empty = np.empty((0, 2), np.int32)
            rec.events = [c if c is not None else (empty, empty)
                          for c in self._caught]
            rec.sync, rec.movers, rec.pos = sync, movers, tr.pos
            rec.prev_pos, rec.creates = prev, creates
        return rec

    def bucket_stats(self) -> dict:
        out = {}
        for b in self.rt.aoi._buckets.values():
            for k in FALLBACK_STATS + COUNTED_STATS:
                out[k] = out.get(k, 0) + getattr(b, "stats", {}).get(k, 0)
        return out
