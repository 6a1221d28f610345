"""Space: a shard of the world holding co-located entities.

A Space is itself an entity (reference: Space.go:14 ``__space__``); it owns
the per-space AOI arrays and its handle into the process AOIEngine.  All
entities in a space are co-located on one game process (and their AOI rows on
one chip) -- this is the framework's unit of sharding.

Batched AOI protocol per tick (north-star hot loop; reference equivalent:
Space.go:188-261 enter/leave/move -> go-aoi callbacks):

    * ``enter_entity``/``leave_entity``/``move_entity`` update the packed
      per-slot arrays (x, z, radius, active) incrementally -- O(1) each;
    * the runtime's tick calls ``submit_aoi`` then ``AOIEngine.flush`` then
      ``dispatch_aoi_events``, which replays enter/leave pairs (sorted,
      deterministic): a client observer with default hooks as whole arrays
      into its client's outbox and the watch index, an observer with AOI
      hooks through Entity._interest/_uninterest.

The nil space (reference: Space.go:127-140) is a kindless space with AOI
disabled where entities live when not in a real space.
"""

from __future__ import annotations

import itertools
from collections import deque
from itertools import compress, repeat, starmap
from operator import attrgetter, not_

import numpy as np

from .. import telemetry
from .ecs import ColumnStore
from .entity import _SLOT, SYNC_NEIGHBORS, SYNC_OWN, Entity
from .vector import Vector3

SPACE_TYPE_NAME = "__space__"
_MIN_CAPACITY = 128
# watch-index codes: target slot << _SHIFT | watching observer slot
_SHIFT = 32
_LOW = (1 << _SHIFT) - 1
_OUTBOX = attrgetter("outbox")
# the pairs event replay delivered, by path, counted once per batch
_REPLAY_COLUMNS = telemetry.counter(
    "aoi.replay_columns", "enter/leave pairs event replay delivered as "
    "columns (client observers with default AOI hooks)")
_REPLAY_HOOKED = telemetry.counter(
    "aoi.replay_hooked", "enter/leave pairs event replay delivered through "
    "per-pair AOI hooks")


class _Replay:
    """One per-pair event replay in progress (``Space._replay``): its
    pairs, their entities, and how many were replayed / folded into the
    watch index."""

    __slots__ = ("pairs", "obs", "tgt", "enter", "done", "applied", "moved")

    def __init__(self, pairs, obs, tgt, enter):
        self.pairs, self.obs, self.tgt, self.enter = pairs, obs, tgt, enter
        self.done = self.applied = 0
        # an entity left this space during the replay, so a pair's
        # entities may no longer sit at the pair's slots
        self.moved = False


def _fresh(v):
    """A copy of a plain attrs snapshot that shares no dict or list."""
    if isinstance(v, dict):
        return {k: _fresh(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_fresh(x) for x in v]
    return v


class Space(Entity):
    # spaces are never AOI members themselves
    use_aoi = False

    def __init__(self):
        super().__init__()
        self.kind = 0
        self.entities: set[Entity] = set()
        self._aoi_handle = None
        self._aoi_default_dist = 0.0
        # columnar ECS store (engine/ecs.py): the hot per-slot attributes
        # (x/z/r/act/nonplain + the y/yaw/sync/client/id host companions)
        # as capacity-sized arrays grown by doubling.  Entities hold VIEWS
        # into these columns while slotted (Entity.position); submit_aoi
        # hands the calculator the columns themselves, so the flush()
        # delta diff reads them directly -- no per-entity walk anywhere
        self._cap = 0
        self._cols = ColumnStore()
        self._slot_entity: list[Entity | None] = []
        # numpy object-array mirror of _slot_entity: event replay fancy-
        # indexes whole pair columns at C speed instead of per-pair list
        # lookups (dispatch_aoi_events)
        self._slot_np = np.empty(0, object)
        self._free_slots: list[int] = []
        # two-stage cooling for freed slots: a pipelined calculator's events
        # for a slot freed during tick T are dispatched at T and only
        # DELIVERED at T+1's AOI phase, so the slot must stay unallocatable
        # through the end of T+1 -- not just this tick's phase (timers and
        # user code between ticks allocate too).  recycle_aoi_slots advances
        # cooling -> cooling2 -> free at the end of each AOI phase.
        self._free_cooling: list[int] = []
        self._free_cooling2: list[int] = []
        self._slot_watermark = 0
        self._aoi_dirty = False
        # event-stream subscription last applied to the calculator: a space
        # with no nonplain entity opts out (set_subscribed) so device
        # backends skip its extraction/fetch/decode entirely
        self._aoi_subscribed = True
        # the watch index: every (watching observer, target) slot pair with
        # the observer interested in the target and observer.client set,
        # as sorted int64 codes target << 32 | observer, with the count of
        # each target's pairs in the ``watchers`` column.  For an observer
        # with default AOI hooks it is the only record of the pair.  Event
        # replay keeps it with whole-array operations (_replay_columns,
        # _settle_watch); the sites that bind a client or free a slot keep
        # it directly (_rewatch, leave_entity).  collect_sync reads it: no
        # per-record walk builds sync records
        self._watch = np.empty(0, np.int64)
        self._rp: _Replay | None = None

    @property
    def is_space(self) -> bool:
        return True

    @property
    def is_nil(self) -> bool:
        return self.kind == 0

    # legacy accessors for the packed arrays -- the columns ARE the
    # arrays now (ColumnStore); kept so calculators, tests and tools that
    # index `space._x[slot]` keep working against the live column
    @property
    def _x(self) -> np.ndarray:
        return self._cols.x

    @property
    def _z(self) -> np.ndarray:
        return self._cols.z

    @property
    def _r(self) -> np.ndarray:
        return self._cols.r

    @property
    def _act(self) -> np.ndarray:
        return self._cols.act

    @property
    def _nonplain(self) -> np.ndarray:
        return self._cols.nonplain

    def on_space_init(self):  # user hook (reference ISpace)
        pass

    def on_entity_enter_space(self, e: Entity):
        pass

    def on_entity_leave_space(self, e: Entity):
        pass

    # -- AOI management ----------------------------------------------------
    def enable_aoi(self, default_dist: float, backend: str | None = None,
                   capacity: int | None = None):
        """Turn on interest management for this space (reference:
        EnableAOI, Space.go:91-107).  Must be called before entities enter.

        ``capacity`` pre-sizes the space: population grows capacity on
        demand anyway, but an expected-oversized space (>= the row-shard
        threshold) should pre-size so it lands on the row-sharded
        calculator directly instead of repacking through every doubling."""
        if self._aoi_handle is not None:
            raise RuntimeError("AOI already enabled")
        if self.entities:
            raise RuntimeError("enable AOI before entities enter the space")
        self._aoi_default_dist = float(default_dist)
        self._ensure_capacity(max(_MIN_CAPACITY, int(capacity or 0)))
        self._aoi_handle = self._runtime().aoi.create_space(self._cap, backend)

    @property
    def aoi_enabled(self) -> bool:
        return self._aoi_handle is not None

    def enable_interest(self, *policies, mode: str | None = None):
        """Attach a composable interest-policy stack to this space
        (goworld_tpu/interest/): team/faction visibility, tiered update
        rates, line-of-sight occlusion -- fused into one device pass and
        composed with the base radius predicate.  Requires ``enable_aoi``
        first; like it, must run before entities enter (the stack's
        previous-step state starts empty).  Returns the PolicyStack."""
        if self._aoi_handle is None:
            raise RuntimeError("enable_aoi before enable_interest")
        if self.entities:
            raise RuntimeError(
                "enable interest policies before entities enter the space")
        return self._runtime().aoi.attach_interest(
            self._aoi_handle, policies, mode=mode)

    @property
    def interest_stack(self):
        """The attached PolicyStack, or None (radius-only space)."""
        h = self._aoi_handle
        return None if h is None else getattr(h, "_policy_stack", None)

    def set_aoi_team(self, e: Entity, team: int, vis: int | None = None):
        """Set an entity's faction columns (team_mask policy semantics:
        observer A sees B iff ``vis[A] & team[B] != 0``).  ``team`` is
        B-side (what bitmask the entity presents), ``vis`` is A-side
        (which team bits the entity can see); ``vis=None`` keeps the
        current visibility mask.  Entities enter with team=1,
        vis=0xFFFFFFFF -- mutually visible until told otherwise."""
        if e.space is not self or e.aoi_slot < 0:
            raise ValueError(f"{e} holds no AOI slot in this space")
        cols = self._cols
        cols.team[e.aoi_slot] = np.uint32(team)
        if vis is not None:
            cols.vis[e.aoi_slot] = np.uint32(vis)
        self._aoi_dirty = True

    def _ensure_capacity(self, n: int):
        if n <= self._cap:
            return
        new_cap = max(_MIN_CAPACITY, self._cap or _MIN_CAPACITY)
        while new_cap < n:
            new_cap *= 2
        self._cols.ensure_capacity(new_cap)
        self._slot_entity.extend([None] * (new_cap - len(self._slot_entity)))
        slot_np = np.empty(new_cap, object)
        slot_np[: len(self._slot_np)] = self._slot_np
        self._slot_np = slot_np
        old_cap = self._cap
        self._cap = new_cap
        if self._aoi_handle is not None and old_cap:
            self._aoi_handle = self._runtime().aoi.grow_space(
                self._aoi_handle, new_cap
            )
            # the fresh bucket slot defaults to subscribed; reset the cached
            # flag so the next submit re-applies an unsubscription (an
            # all-plain space must not silently resume event extraction)
            self._aoi_subscribed = True

    # -- membership --------------------------------------------------------
    def enter_entity(self, e: Entity, pos: Vector3, is_restore: bool = False):
        """Reference: Space.enter, Space.go:188-226.  ``is_restore``
        re-establishes membership after freeze-restore WITHOUT firing the
        user enter hooks (reference: restore re-enters quietly,
        EntityManager.go:591-652 -- a restore reconstructs state, it is not
        a new enter; hooks like the demo's spawn-monsters-per-player must
        not re-fire)."""
        if e.space is not None:
            raise ValueError(f"{e} already in a space")
        e.space = self
        e.position = pos
        self.entities.add(e)
        if self._aoi_handle is not None and e.use_aoi:
            if self._free_slots:
                slot = self._free_slots.pop()
            else:
                slot = self._next_slot()
            e.aoi_slot = slot
            self._slot_entity[slot] = e
            self._slot_np[slot] = e
            cols = self._cols
            cols.nonplain[slot] = not e._plain_aoi
            cols.x[slot] = pos.x
            cols.y[slot] = pos.y
            cols.z[slot] = pos.z
            cols.yaw[slot] = e._yaw
            cols.r[slot] = (
                e.aoi_distance if e.aoi_distance > 0 else self._aoi_default_dist
            )
            cols.act[slot] = True
            # faction defaults: on one team, sees everyone -- a space with
            # a team_mask policy behaves exactly radius-like until
            # set_aoi_team says otherwise
            cols.team[slot] = np.uint32(1)
            cols.vis[slot] = np.uint32(0xFFFFFFFF)
            cols.sync[slot] = 0
            cols.put_id("eid", slot, e.id)
            cols.hooked[slot] = not e._default_hooks
            cols.deltas[slot] = bool(e._attr_deltas)
            cols.shown[slot] = bool(e.all_client_attrs)
            cols.tname[slot] = e.type_name
            cols.ident[slot] = e.id
            self._set_client_cols(slot, e.client)
            self._aoi_dirty = True
        if not is_restore:
            self.on_entity_enter_space(e)
            e.on_enter_space()

    def _next_slot(self) -> int:
        if self._slot_watermark >= self._cap:
            self._ensure_capacity(self._cap + 1)
        slot = self._slot_watermark
        self._slot_watermark += 1
        return slot

    def leave_entity(self, e: Entity):
        """Reference: Space.leave, Space.go:228-251."""
        if e.space is not self:
            return
        if e.aoi_slot >= 0:
            slot = e.aoi_slot
            cols = self._cols
            # detach the entity's position/yaw views: snapshot the column
            # values back into the f64 Vector3 the views fall through to
            # (batched moves and ingest write columns only, so the
            # snapshot may be the ONLY up-to-date copy)
            p = e._pos
            p.x = float(cols.x[slot])
            p.y = float(cols.y[slot])
            p.z = float(cols.z[slot])
            e._yaw = float(cols.yaw[slot])
            # fold what a replay in progress did so far (it places later
            # pairs anew), then drop every watch pair of the slot: its
            # interests are severed below, from what the pairs recorded --
            # the clients watching it, and what its own client watches
            self._settle_watch()
            if self._rp is not None:
                self._rp.moved = True
            watchers = cols.gclient[self._watching(slot)].tolist()
            seen = e._interest_record()
            w = self._watch
            if len(w):
                self._watch_keep(((w >> _SHIFT) != slot)
                                 & ((w & _LOW) != slot))
            cols.clear_slot(slot)
            self._slot_entity[slot] = None
            self._slot_np[slot] = None
            self._free_cooling.append(slot)
            e.aoi_slot = -1
            self._aoi_dirty = True
            # erase the slot from the calculator's previous-tick state: the
            # interests are severed synchronously below, so the batched diff
            # must not re-emit them (and a reused slot must start clean)
            self._runtime().aoi.clear_entity(self._aoi_handle, slot)
            # departure events must fire this tick; sever interests now so
            # callbacks and client destroys are immediate and deterministic
            # (by slot).  Hooks run per pair; a client with default hooks
            # gets its destroy ops alone
            if e._default_hooks:
                if e.client is not None:
                    for other in seen:
                        e.client.destroy_entity(other)
            else:
                for other in seen:
                    e._uninterest(other)
            for c in watchers:
                c.destroy_entity(e)
            for other in sorted(e._ints_by, key=_SLOT):
                other._uninterest(e)
        self.entities.discard(e)
        e.space = None
        self.on_entity_leave_space(e)
        e.on_leave_space(self)

    def move_entities(self, slots, xs, zs, ys=None, yaws=None):
        """Batched position update: one call moves many entities (reference
        analog: the gate->game client-sync path decodes a flat array of
        positions and applies them in one pass, GameService.go:398-410).
        All position/yaw writes are vectorized column writes (entities
        VIEW the columns -- engine/ecs.py -- so nothing per-entity needs
        updating); sync bookkeeping runs just for entities some client can
        actually see.  This is the device-cadence movement path: at 64k
        entities it costs ~20 ms where per-entity set_position costs
        ~100 ms.  (The fully-batched wire path, goworld_tpu/ingest/,
        replaces even the bookkeeping loop with a sync-column write.)

        With ``ys``/``yaws`` (the client-sync ingest,
        sync_entities_from_client) height and yaw update too."""
        slots = np.asarray(slots, np.int64)
        cols = self._cols
        cols.x[slots] = xs
        cols.z[slots] = zs
        if ys is not None:
            cols.y[slots] = ys
            cols.yaw[slots] = yaws
        self._aoi_dirty = True
        se = self._slot_np
        # sync bookkeeping for the slots some client watches or owns
        # (client-driven entities get no owner echo -- same rule as
        # set_position: correcting the owner fights client-side
        # prediction; server-driven ones do).  The columns pick the slots;
        # the loop is inlined, not a helper: a per-entity call costs ~5 ms
        # at 64k on the device-cadence path.
        self._settle_watch()
        seen = slots[(cols.watchers[slots] > 0) | cols.client[slots]]
        for s in seen.tolist():
            e = se[s]
            if e is None:
                continue
            e._sync_flags |= 2 if e.client_syncing else 3
            ds = e._dirty_set
            if ds is not None:
                ds.add(e)

    def sync_entities_from_client(self, slots, xs, ys, zs, yaws):
        """Batched client-driven position/yaw sync: the gate->game sync
        packet decodes into flat arrays and applies in one pass (reference:
        GameService.go:398-410 decodes the flat sync array;
        Entity.go:1221-1267 batches the outbound half).  Semantically one
        ``sync_position_yaw_from_client`` per entry; shares move_entities'
        apply loop -- the sync-flag policy there already reduces to
        SYNC_NEIGHBORS-only for client-syncing entities (no owner echo:
        correcting the owner fights client-side prediction)."""
        self.move_entities(slots, xs, zs, ys=ys, yaws=yaws)

    def move_entity(self, e: Entity, pos: Vector3):
        """Reference: Space.move, Space.go:253-261.  (Entity.set_position
        inlines this; other callers use it directly.)  The position
        assignment writes the columns and marks AOI dirty when slotted
        (Entity.position setter)."""
        e.position = pos

    # -- per-tick AOI ------------------------------------------------------
    def recycle_aoi_slots(self):
        """Advance the two-stage cooling pipeline (see ``_free_cooling``).
        Called at the END of each AOI phase, after event delivery: a slot
        freed during tick T becomes allocatable only after T+1's delivery
        of the events dispatched while it was live."""
        if self._free_cooling2:
            self._free_slots.extend(self._free_cooling2)
            self._free_cooling2.clear()
        if self._free_cooling:
            self._free_cooling2.extend(self._free_cooling)
            self._free_cooling.clear()

    def submit_aoi(self) -> bool:
        """Stage this tick's arrays if anything changed; returns staged?"""
        if self._aoi_handle is None or not self._aoi_dirty:
            return False
        aoi = self._runtime().aoi
        stack = getattr(self._aoi_handle, "_policy_stack", None)
        # subscription tracks "does anyone consume events?": pairs whose
        # observer is plain are dropped at delivery anyway, so an all-plain
        # space needs no event stream at all -- the calculator skips its
        # extraction/fetch/decode and interest state derives on demand.
        # With an interest stack attached the BUCKET's stream is never
        # consumed at all (the stack owns take_events), so the bucket
        # unsubscribes outright while still carrying the base state.
        cols = self._cols
        sub = (stack is None
               and bool(cols.nonplain[: self._slot_watermark].any()))
        if sub != self._aoi_subscribed:
            self._aoi_subscribed = sub
            aoi.set_subscribed(self._aoi_handle, sub)
        # the columns ARE the staged arrays: flush()'s delta diff
        # (engine/aoi._stage_inputs) reads them directly against the host
        # shadows -- wire/logic writes land here vectorized and nothing
        # walks entities between a move and the H2D packet
        aoi.submit(self._aoi_handle, cols.x, cols.z, cols.r, cols.act)
        if stack is not None:
            stack.submit(cols.x, cols.z, cols.r, cols.act,
                         cols.team, cols.vis)
        self._aoi_dirty = False
        return True

    def take_column_sync(self) -> np.ndarray:
        """The pending column sync flags (set vectorized by the batched
        ingest path, goworld_tpu/ingest/), one per slot up to the
        watermark, cleared in the column."""
        sf = self._cols.sync[: self._slot_watermark]
        f = sf.copy()
        sf[:] = 0
        return f

    def collect_sync(self, f: np.ndarray):
        """This tick's position-sync records of the space as columns --
        ``(cid, gate, eid, x, y, z, yaw)``, see engine/sync.py -- or None.
        ``f`` holds each slot's SYNC_* flags: an own-client record for
        every SYNC_OWN slot whose entity has a client, and one record per
        watch-index pair whose target is flagged SYNC_NEIGHBORS."""
        cols = self._cols
        tgt = np.flatnonzero((f & SYNC_OWN).astype(bool)
                             & cols.client[: len(f)])
        obs = tgt
        w = self._watch
        if len(w):
            t = w >> _SHIFT
            sel = (f[t] & SYNC_NEIGHBORS) != 0
            if not sel.all():
                t, w = t[sel], w[sel]
            if len(t):
                tgt = np.concatenate((tgt, t))
                obs = np.concatenate((obs, w & _LOW))
        if not len(tgt):
            return None
        return (cols.cid[obs], cols.gate[obs], cols.eid[tgt], cols.x[tgt],
                cols.y[tgt], cols.z[tgt], cols.yaw[tgt])

    def dispatch_aoi_events(self):
        """Replay batched enter/leave pairs to their observers.

        A pair whose OBSERVER has no client and default AOI hooks
        (``_plain_aoi``) is pure interest bookkeeping the calculator's
        packed words already hold -- dropped with one vectorized mask.  The
        rest split by observer, on the slot's ``hooked`` column: a client
        observer with default hooks is replayed as whole arrays
        (``_replay_columns``: create/destroy ops, the watch index), an
        observer whose type overrides the hooks through
        ``_interest``/``_uninterest`` per pair (``_replay``).  Leaves go
        first -- a slot reused within one tick (leave+enter) must destroy
        before re-creating on clients -- and within each batch the columns
        go first: they run no user code."""
        if self._aoi_handle is None:
            return
        enter, leave = self._runtime().aoi.take_events(self._aoi_handle)
        cols = self._cols
        nonplain, hooked = cols.nonplain, cols.hooked
        batches = []
        for pairs, is_enter in ((leave, False), (enter, True)):
            if len(pairs):
                pairs = pairs[nonplain[pairs[:, 0]]]
                h = hooked[pairs[:, 0]]
                batches.append((pairs[~h], pairs[h], is_enter))
        for by_cols, by_hooks, is_enter in batches:
            if len(by_cols):
                _REPLAY_COLUMNS.inc(self._replay_columns(by_cols, is_enter))
            if len(by_hooks):
                _REPLAY_HOOKED.inc(self._replay(by_hooks, is_enter))

    def _replay_columns(self, pairs: np.ndarray, enter: bool) -> int:
        """Replay (observer, target) slot pairs of client observers with
        default hooks as whole arrays; returns how many were live.  Each
        client's outbox gets the ops the per-pair path would push, in its
        order: one create (``enter``) or destroy op per pair, built from
        the target's slot columns, and a target with pending attr deltas
        flushes them to its audience from before the batch where its first
        pair stands.  Then the pairs fold into the watch index."""
        cols = self._cols
        o = pairs[:, 0].astype(np.int64)
        t = pairs[:, 1].astype(np.int64)
        live = cols.act[o] & cols.act[t] & cols.client[o]
        if not live.all():
            o, t = o[live], t[live]
        n = len(o)
        if not n:
            return 0
        # the op tuples come out of zip in C: ("create_entity", type_name,
        # id, False, attrs, (x, y, z), yaw) with a fresh {} per op, or
        # ("destroy_entity", type_name, id)
        tn, ids = cols.tname[t].tolist(), cols.ident[t].tolist()
        if enter:
            ops = list(zip(
                repeat("create_entity", n), tn, ids, repeat(False, n),
                starmap(dict, repeat((), n)),
                zip(cols.x[t].tolist(), cols.y[t].tolist(),
                    cols.z[t].tolist()),
                cols.yaw[t].tolist()))
            if cols.shown[t].any():
                self._attach_attrs(ops, t)
            cuts = [0]
            first = np.flatnonzero(cols.deltas[t])
            if len(first):
                # each pending target's first pair: its flush stands there
                first = first[np.unique(t[first], return_index=True)[1]]
                cuts = sorted({0, *first.tolist()})
        else:
            ops = list(zip(repeat("destroy_entity", n), tn, ids))
            cuts = [0]
        cuts.append(n)
        se = self._slot_np
        for a, b in zip(cuts, cuts[1:]):
            if enter and cols.deltas[t[a]]:
                se[t[a]]._flush_attr_deltas()
            self._hand_out(o[a:b], ops[a:b])
        codes = (t << _SHIFT) | o
        if enter:
            self._watch_add(codes)
        else:
            self._watch_remove(codes)
        return n

    def _attach_attrs(self, ops: list, t: np.ndarray):
        """Give the create ops of targets whose type shows attrs to
        neighbours (``shown``) the snapshot the per-pair path would take,
        each op a copy of its own (no op shares a mutable object)."""
        se = self._slot_np
        snaps: dict = {}
        for i in np.flatnonzero(self._cols.shown[t]).tolist():
            s = int(t[i])
            snap = snaps.get(s)
            if snap is None:
                snap = snaps[s] = se[s].client_visible_attrs(to_owner=False)
            op = ops[i]
            ops[i] = op[:4] + (_fresh(snap),) + op[5:]

    def _hand_out(self, o: np.ndarray, ops: list):
        """Extend each observer's client outbox with its ops, in order (a
        stable sort groups them by observer)."""
        if len(o) > 1 and (o[1:] < o[:-1]).any():
            order = np.argsort(o, kind="stable")
            o, ops = o[order], [ops[i] for i in order.tolist()]
        starts = np.flatnonzero(np.r_[True, o[1:] != o[:-1]])
        clients = self._cols.gclient[o[starts]].tolist()
        boxes = list(map(_OUTBOX, clients))
        # GameClient._push's rule: on_dirty on the first op after a drain
        # (each observer has a client of its own, so no client repeats)
        for c in compress(clients, map(not_, boxes)):
            if c.on_dirty is not None:
                c.on_dirty(c)
        ends = starts[1:].tolist() + [len(o)]
        deque(map(list.extend, boxes,
                  map(ops.__getitem__, map(slice, starts.tolist(), ends))),
              maxlen=0)

    def _replay(self, pairs: np.ndarray, enter: bool) -> int:
        """Replay (observer, target) slot pairs of live slots through
        ``_interest`` (``enter``) or ``_uninterest``, then fold them into
        the watch index as one array operation; returns how many were
        live.  Hooks may change clients and slots mid-replay: a client
        binding recomputes its observer's pairs from its eager set, which
        already holds the pairs replayed so far, and a slot freed
        mid-replay first folds them (``_settle_watch``)."""
        act = self._cols.act
        pairs = pairs[act[pairs[:, 0]] & act[pairs[:, 1]]]
        if not len(pairs):
            return 0
        se = self._slot_np
        obs, tgt = se[pairs[:, 0]], se[pairs[:, 1]]
        rp = self._rp = _Replay(pairs, obs, tgt, enter)
        try:
            for n, a, b in zip(itertools.count(1), obs, tgt):
                rp.done = n
                if enter:
                    a._interest(b)
                else:
                    a._uninterest(b)
        finally:
            self._settle_watch()
            self._rp = None
        return len(pairs)

    # -- watch index -------------------------------------------------------
    def _settle_watch(self):
        """Fold the pairs a per-pair replay in progress has replayed since
        the last fold into the watch index (a no-op outside one)."""
        rp = self._rp
        if rp is None or rp.done <= rp.applied:
            return
        lo, hi = rp.applied, rp.done
        rp.applied = hi
        if rp.moved:
            # place each pair's entities where they are now, if still here
            o, t = [], []
            for a, b in zip(rp.obs[lo:hi], rp.tgt[lo:hi]):
                if (a.space is self and b.space is self
                        and a.aoi_slot >= 0 and b.aoi_slot >= 0):
                    o.append(a.aoi_slot)
                    t.append(b.aoi_slot)
            o, t = np.array(o, np.int64), np.array(t, np.int64)
        else:
            o = rp.pairs[lo:hi, 0].astype(np.int64)
            t = rp.pairs[lo:hi, 1].astype(np.int64)
        m = self._cols.client[o]
        codes = (t[m] << _SHIFT) | o[m]
        if rp.enter:
            self._watch_add(codes)
        else:
            self._watch_remove(codes)

    def _count_watchers(self, codes: np.ndarray, sign: int):
        """Add ``sign`` to the ``watchers`` column of each sorted code's
        target, once per code."""
        t = codes >> _SHIFT
        starts = np.flatnonzero(np.r_[True, t[1:] != t[:-1]])
        self._cols.watchers[t[starts]] += sign * np.diff(
            np.r_[starts, len(t)]).astype(np.int32)

    def _watch_add(self, codes: np.ndarray):
        if not len(codes):
            return
        codes = np.unique(codes)
        w = self._watch
        pos = np.searchsorted(w, codes)
        if len(w):
            new = w[np.minimum(pos, len(w) - 1)] != codes
            codes, pos = codes[new], pos[new]
        if len(codes):
            self._count_watchers(codes, 1)
            self._watch = np.insert(w, pos, codes)

    def _watch_remove(self, codes: np.ndarray):
        w = self._watch
        if not len(codes) or not len(w):
            return
        codes = np.unique(codes)
        pos = np.searchsorted(w, codes)
        ok = pos < len(w)
        pos = pos[ok][w[pos[ok]] == codes[ok]]
        if len(pos):
            self._count_watchers(w[pos], -1)
            self._watch = np.delete(w, pos)

    def _watch_keep(self, keep: np.ndarray):
        """Keep the watch pairs under the mask ``keep``, counting the
        others out of their targets' ``watchers``."""
        w = self._watch
        gone = w[~keep]
        if len(gone):
            self._count_watchers(gone, -1)
            self._watch = w[keep]

    def _watching(self, slot: int) -> np.ndarray:
        """Slots of the client observers with default hooks that watch
        the slot (observers with AOI hooks keep eager records instead)."""
        w = self._watch
        lo, hi = np.searchsorted(w, (slot << _SHIFT, (slot + 1) << _SHIFT))
        o = w[lo:hi] & _LOW
        return o[~self._cols.hooked[o]]

    def _watched_by(self, slot: int) -> list[Entity]:
        """The entities the slot's client watches, by slot."""
        w = self._watch
        return self._slot_np[w[(w & _LOW) == slot] >> _SHIFT].tolist()

    def _set_client_cols(self, slot: int, client):
        cols = self._cols
        cols.client[slot] = client is not None
        cols.gclient[slot] = client
        if client is not None:
            cols.put_id("cid", slot, client.client_id)
            cols.gate[slot] = client.gate_id

    def _rewatch(self, e: Entity, targets=()):
        """Set the watch pairs whose observer is ``e`` (slotted here) to
        ``targets`` if it has a client, else to none."""
        slot = e.aoi_slot
        w = self._watch
        if len(w):
            self._watch_keep((w & _LOW) != slot)
        if e.client is not None:
            t = [x.aoi_slot for x in targets
                 if x.space is self and x.aoi_slot >= 0]
            self._watch_add((np.array(t, np.int64) << _SHIFT) | slot)

    # -- lazy interest derivation ------------------------------------------
    def derive_interests(self, slot: int) -> list[Entity]:
        """Entities the slot's entity is interested in, derived from the
        calculator's packed interest words (post-last-flush state).  This is
        how PLAIN entities -- no client, default hooks -- answer
        ``neighbors()`` without any per-event host bookkeeping: the
        authoritative interest state never leaves the packed representation
        until someone actually asks."""
        h = self._aoi_handle
        if h is None or slot < 0:
            return []
        stack = getattr(h, "_policy_stack", None)
        if stack is not None:
            # policy space: the stack's post-step words ARE the interest
            # state (the bucket's base words ignore team/tier/los)
            row = stack.words[slot]
        else:
            derive = getattr(h.bucket, "derive_row", None)
            if derive is not None:
                # row-sharded oversized space: fetch ONE observer's words
                # [W] (16 KB) instead of materializing the full [C, W]
                row = derive(h.slot, slot)
            else:
                words = h.bucket.peek_words(h.slot)
                if words is None:
                    words = h.bucket.get_prev(h.slot)
                row = words[slot]
        w_per = row.shape[0]
        sn = self._slot_np
        out = []
        for w in np.nonzero(row)[0]:
            bits = int(row[w])
            while bits:
                k = (bits & -bits).bit_length() - 1
                bits &= bits - 1
                e = sn[k * w_per + w]  # planar layout: j = k*W + w
                if e is not None:
                    out.append(e)
        return out

    def derive_observers(self, slot: int) -> list[Entity]:
        """Entities interested IN the slot's entity (the packed column)."""
        h = self._aoi_handle
        if h is None or slot < 0:
            return []
        stack = getattr(h, "_policy_stack", None)
        derive = getattr(h.bucket, "derive_col", None)
        if stack is None and derive is not None:
            rows = derive(h.slot, slot)
        else:
            if stack is not None:
                words = stack.words
            else:
                words = h.bucket.peek_words(h.slot)
                if words is None:
                    words = h.bucket.get_prev(h.slot)
            from ..ops import aoi_predicate as AP

            w, b = AP.word_bit_for_column(slot, self._cap)
            rows = np.nonzero(words[:, w] & (np.uint32(1) << np.uint32(b)))[0]
        sn = self._slot_np
        return [sn[i] for i in rows if sn[i] is not None]

    # -- destroy -----------------------------------------------------------
    def _destroy_impl(self, is_migrate: bool):
        for e in list(self.entities):
            e.destroy()
        if self._aoi_handle is not None:
            self._runtime().aoi.release_space(self._aoi_handle)
            self._aoi_handle = None
        super()._destroy_impl(is_migrate)
