"""The game runtime: single logic thread + batched tick phases.

Mirrors the reference's game main loop (GameService.serveRoutine,
/root/reference/components/game/GameService.go:88-192): one thread runs all
entity logic; each tick fires timers, executes the batched AOI pass, flushes
client-bound traffic, and drains the post queue.  Networking components wrap
this object (components/game); tests drive it directly.

Tick phases (order matters and is part of the engine contract):

  1. timers        -- user logic (AI moves, scheduled callbacks);
  2. AOI           -- submit dirty spaces, one batched TPU step per bucket,
                      replay enter/leave events through entity hooks;
  3. sync          -- collect position/yaw records for every entity flagged
                      dirty (reference: CollectEntitySyncInfos,
                      Entity.go:1221-1267), as columns per space from its
                      watch index (engine/space.py, engine/sync.py), and
                      flush attr deltas;
  4. post          -- callbacks queued by workers/IO during the tick.
"""

from __future__ import annotations

import os
import time
from typing import Callable

import numpy as np

from .. import faults, telemetry
from ..telemetry import trace as _trace
from ..utils.crontab import Crontab
from .aoi import AOIEngine
from .entity import SYNC_NEIGHBORS, SYNC_OWN, Entity
from .manager import EntityManager
from .placement import PlacementController
from .post import PostQueue
from .sync import SyncBatch
from .timers import TimerQueue

# whole-tick latency histogram (pow2 buckets -> p50/p99 at /debug/metrics);
# a no-op while telemetry is disabled
_TICK_SECONDS = telemetry.histogram(
    "tick.seconds", "whole-tick wall time (timers+aoi+sync+post)")
# position-sync records the sync phase appended (one per mover and
# watching client), counted once per tick
_SYNC_RECORDS = telemetry.counter(
    "sync.records", "position-sync records collected by the sync phase")
# the records of them built one at a time: slotless entities (no AOI slot,
# so no columns); 0 where every mover holds a slot
_SYNC_WALKED = telemetry.counter(
    "sync.walked", "position-sync records built by the per-entity walk")

# SLO gate: a tick over this budget trips the flight recorder (0 = off).
# Env-configured -- the budget is an ops knob, not an engine parameter.
try:
    _TICK_BUDGET_MS = float(os.environ.get("GW_TICK_BUDGET_MS", "0") or 0)
except ValueError:
    _TICK_BUDGET_MS = 0.0


class Runtime:
    def __init__(
        self,
        aoi_backend: str = "cpu",
        now: Callable[[], float] = time.monotonic,
        on_error: Callable[[BaseException], None] | None = None,
        aoi_mesh=None,
        aoi_pipeline: bool = False,
        aoi_delta_staging: bool = True,
        aoi_tpu_min_capacity: int = 4096,
        aoi_rowshard_min_capacity: int = 65536,
        aoi_flush_sched: bool = True,
        aoi_emit: str = "auto",
        aoi_cross_tick: bool = False,
        aoi_fused: bool = False,
        aoi_interest: str = "device",
        aoi_placement: str = "static",
        aoi_migration_threshold_ms: float = 5.0,
        aoi_migration_cooldown: int = 64,
        aoi_cohort=False,
        aoi_cohort_ladder=None,
        aoi_cohort_planner: str = "static",
        aoi_cohort_hot_ms: float = 8.0,
        aoi_cohort_churn_budget: int = 2,
        aoi_cohort_cooldown: int = 32,
        aoi_checkpoint: str = "off",
        aoi_checkpoint_interval: int = 16,
        aoi_checkpoint_dir: str | None = None,
        aoi_checkpoint_store=None,
        aoi_checkpoint_kvdb=None,
        fault_plan: "faults.FaultPlan | str | None" = None,
        telemetry_on: bool = False,
    ):
        # Install BEFORE AOIEngine construction: buckets decide at __init__
        # whether to keep eager host mirrors (faults.active()).
        if fault_plan is not None:
            faults.install(fault_plan)
        # The injectable clock doubles as the span clock (docs/
        # observability.md): enabling telemetry through the Runtime routes
        # every span timestamp through ``now``, so tests drive tracing
        # deterministically.  False leaves process-global state untouched
        # (another component may have enabled it already).
        if telemetry_on:
            telemetry.enable(clock=now)
        self.now = now
        self.on_error = on_error or self._default_on_error
        self.timers = TimerQueue(now)
        self.post = PostQueue()
        self.crontab = Crontab()
        self.aoi = AOIEngine(default_backend=aoi_backend, mesh=aoi_mesh,
                             pipeline=aoi_pipeline,
                             delta_staging=aoi_delta_staging,
                             tpu_min_capacity=aoi_tpu_min_capacity,
                             rowshard_min_capacity=aoi_rowshard_min_capacity,
                             flush_sched=aoi_flush_sched, emit=aoi_emit,
                             cross_tick=aoi_cross_tick,
                             fused=aoi_fused,
                             interest_mode=aoi_interest,
                             cohort=aoi_cohort,
                             cohort_ladder=aoi_cohort_ladder)
        # telemetry-driven placement (engine/placement.py): "static" keeps
        # spaces where capacity routing put them (migrate() stays available
        # as the operator entry point); "auto" re-homes hot/idle spaces
        # between tiers live, one at a time, from per-bucket load scores
        self.placement = PlacementController(
            self.aoi, mode=aoi_placement,
            threshold_ms=aoi_migration_threshold_ms,
            cooldown_ticks=aoi_migration_cooldown)
        # cohort membership planner (engine/placement.py CohortPlanner):
        # only meaningful with aoi_cohort on; "auto" re-buckets stacked
        # vs solo spaces live from the same load scores, under a churn
        # budget, and doubles as the aoi.cohort demotion re-arm loop
        self.cohort_planner = None
        if aoi_cohort:
            from .placement import CohortPlanner

            self.cohort_planner = CohortPlanner(
                self.aoi, mode=aoi_cohort_planner,
                hot_ms=aoi_cohort_hot_ms,
                churn_budget=aoi_cohort_churn_budget,
                cooldown_ticks=aoi_cohort_cooldown)
        # durable world state (engine/checkpoint.py): "off" costs nothing;
        # "interval"/"continuous" stream per-space incremental checkpoints
        # off the hot path.  Backends come pre-built (aoi_checkpoint_store/
        # _kvdb -- the GameService path, via storage/kvdb config) or are
        # filesystem defaults under aoi_checkpoint_dir
        self.checkpoint = None
        if aoi_checkpoint != "off":
            if aoi_checkpoint_store is None or aoi_checkpoint_kvdb is None:
                if aoi_checkpoint_dir is None:
                    raise ValueError(
                        "aoi_checkpoint=%r needs aoi_checkpoint_dir or "
                        "pre-built store+kvdb backends" % aoi_checkpoint)
                from .checkpoint import _open_backends
                aoi_checkpoint_store, aoi_checkpoint_kvdb = \
                    _open_backends(aoi_checkpoint_dir)
            self.arm_checkpoints(aoi_checkpoint_store, aoi_checkpoint_kvdb,
                                 mode=aoi_checkpoint,
                                 interval=aoi_checkpoint_interval)
        self.entities = EntityManager(self)
        self.tick_count = 0
        # entities with pending sync flags / attr deltas / quiet countdowns;
        # the sync phase walks ONLY these (reference scans every entity each
        # tick -- Entity.go:1221-1267 -- which compiled Go affords)
        self._dirty_entities: set[Entity] = set()
        # spaces whose sync COLUMN holds pending flags (vectorized ingest
        # writes -- engine/ecs.py), read at the head of the sync phase.  A
        # dict used as an ordered set
        self._col_sync_spaces: dict = {}
        # position sync records collected since the last drain_sync, as
        # columns; iterates as (client_id, gate_id, entity_id, x, y, z, yaw)
        self.sync_out = SyncBatch()
        # optional hooks set by the hosting component (GameService): called
        # when entities register/unregister so the dispatcher directory stays
        # current (reference: MT_NOTIFY_CREATE_ENTITY/DESTROY)
        self.on_entity_registered = None
        self.on_entity_unregistered = None
        # set by GameService when clustered; entities reach cluster ops
        # (enter_space migration, remote calls) through it
        self.game = None

    def arm_checkpoints(self, store, manifest, mode: str = "interval",
                        interval: int = 16, **kw):
        """Attach (or replace) the checkpoint controller post-construction
        -- the GameService path, after storage/kvdb backends exist."""
        from .checkpoint import CheckpointController

        if self.checkpoint is not None:
            self.checkpoint.close()
        self.checkpoint = CheckpointController(
            self.aoi, store, manifest, mode=mode, interval=interval, **kw)
        return self.checkpoint

    def _default_on_error(self, e: BaseException):
        import traceback

        traceback.print_exception(type(e), e, e.__traceback__)

    # -- the tick ----------------------------------------------------------
    def tick(self):
        self.tick_count += 1
        _trace.mark_tick(self.tick_count)
        _t0 = _trace.t()
        _wall0 = time.perf_counter() if _TICK_BUDGET_MS > 0 else 0.0
        with _trace.span("tick.timers"):
            self.timers.tick(self.on_error)
            self.crontab.maybe_check()
        with _trace.span("tick.aoi"):
            self._aoi_phase()
        with _trace.span("tick.sync"):
            self._sync_phase()
        with _trace.span("tick.post"):
            self.post.tick(self.on_error)
        # placement decisions AFTER the tick's phases: scores reflect the
        # flush that just ran, and a migration started here snapshots
        # between ticks (no partially-staged state)
        self.placement.step()
        if self.cohort_planner is not None:
            # same between-tick discipline as placement: join/leave move
            # snapshots only after this tick's events are delivered
            self.cohort_planner.step()
        # checkpoint capture AFTER placement: events for this tick are
        # delivered, migrations are settled, so the export is snapshot-
        # consistent; the expensive half runs on the background writer
        if self.checkpoint is not None:
            self.checkpoint.sync_tracked({
                sid: sp._aoi_handle
                for sid, sp in self.entities.spaces.items()
                if sp._aoi_handle is not None})
            self.checkpoint.step(self.tick_count)
        _TICK_SECONDS.observe(_trace.lap("tick", _t0))
        if _TICK_BUDGET_MS > 0:
            _dur_ms = (time.perf_counter() - _wall0) * 1000.0
            if _dur_ms > _TICK_BUDGET_MS:
                from ..telemetry import flight as _flight

                _flight.slo_breach(self.tick_count, _dur_ms,
                                   _TICK_BUDGET_MS)

    def _aoi_phase(self):
        spaces = list(self.entities.spaces.values())
        staged = False
        for sp in spaces:
            staged = sp.submit_aoi() or staged
        # a pipelined bucket may hold an inflight tick even when nothing new
        # is staged (trailing flush); events can land on any AOI space, not
        # just the ones staged this tick
        if staged or self.aoi.has_pending():
            # the flush span nests aoi.dispatch + aoi.harvest (the split-
            # phase scheduler, docs/perf.md): dispatch of EVERY bucket
            # precedes the first blocking fetch
            with _trace.span("aoi.flush"):
                self.aoi.flush()
            with _trace.span("aoi.emit"):
                for sp in spaces:
                    sp.dispatch_aoi_events()
        # slots freed last tick become reusable only NOW, after event
        # delivery: with a pipelined calculator, events replayed this phase
        # may reference a slot freed last tick, and recycling before the
        # replay would let an entity created inside an on_leave_aoi hook
        # take the slot and inherit the dead occupant's pending enter pairs
        for sp in spaces:
            sp.recycle_aoi_slots()

    def _sync_phase(self):
        """Collect position sync + flush attr deltas for DIRTY entities only
        (entities self-register via Entity._mark_dirty; idle entities cost
        nothing per tick).  The dirty set object is STABLE -- entities cache
        a reference to it (Entity._dirty_set) -- so it is drained in place,
        never swapped.

        Records are built per space as columns (Space.collect_sync): each
        slotted mover's flags -- from the space's sync column (batched
        ingest) and from the entity's ``_sync_flags`` (per-entity moves)
        -- land in one per-slot vector, and the space's watch index names
        the watching clients.  Only slotless entities, which have no
        columns, still build their records one at a time."""
        css = self._col_sync_spaces
        ds = self._dirty_entities
        if not css and not ds:
            return
        flags: dict = {}  # space -> pending flags per slot
        if css:
            with _trace.span("sync.columns"):
                for sp in css:
                    flags[sp] = sp.take_column_sync()
                css.clear()
        with _trace.span("sync.collect"):
            folds: dict = {}  # space -> ([slots], [flags]) of dirty movers
            walked: list[tuple] = []
            dirty = list(ds)
            ds.clear()
            for e in dirty:
                if e.destroyed:
                    continue
                f = e._sync_flags
                if f:
                    e._sync_flags = 0
                    slot = e.aoi_slot
                    if slot >= 0:
                        fold = folds.get(e.space)
                        if fold is None:
                            fold = folds[e.space] = ([], [])
                        fold[0].append(slot)
                        fold[1].append(f)
                    elif (e.client is not None or
                            (f & SYNC_NEIGHBORS and e._watcher_clients > 0)):
                        self._walk_sync(e, f, walked)
                if e._attr_deltas:
                    e._flush_attr_deltas()
            out = self.sync_out
            n0 = len(out)
            for sp in sorted(flags.keys() | folds.keys(),
                             key=lambda sp: sp.id):
                f = flags.get(sp)
                if f is None:
                    f = np.zeros(sp._slot_watermark, np.uint8)
                fold = folds.get(sp)
                if fold is not None:
                    f[fold[0]] |= np.array(fold[1], np.uint8)
                part = sp.collect_sync(f)
                if part is not None:
                    out.add(part)
            out.add_walked(walked)
        # once per tick, never per record: the counts the span divides
        _SYNC_RECORDS.inc(len(out) - n0)
        _SYNC_WALKED.inc(len(walked))

    @staticmethod
    def _walk_sync(e: Entity, flags: int, out: list):
        """The records of one slotless entity (reference record layout:
        proto.go:135-139)."""
        x, y, z = e.position.to_tuple()
        if flags & SYNC_OWN and e.client is not None:
            out.append(
                (e.client.client_id, e.client.gate_id, e.id, x, y, z, e.yaw)
            )
        if flags & SYNC_NEIGHBORS and e._watcher_clients > 0:
            for other in e.interested_by:
                if other.client is not None:
                    out.append(
                        (
                            other.client.client_id,
                            other.client.gate_id,
                            e.id,
                            x,
                            y,
                            z,
                            e.yaw,
                        )
                    )

    def drain_sync(self) -> SyncBatch:
        out = self.sync_out
        self.sync_out = SyncBatch()
        return out
