"""Mesh-sharded TPU AOI bucket: the engine's multi-chip production path.

Round 2 proved space sharding at the ops level only
(parallel/mesh.make_sharded_aoi_step); this module puts the ENGINE on the
mesh: a ``_Bucket`` implementation whose slots (spaces) are placed across a
``SpaceMesh`` so every space's [C] rows live wholly on one chip and the
per-tick step needs **zero cross-chip collectives** -- the reference's
defining scaling property (all of a space's work stays on its shard,
/root/reference/engine/entity/EntityManager.go:429-442 local-call fast path)
delivered by the framework itself, not just the kernel.

Per flush, ONE jitted dispatch runs under ``shard_map``:

    per chip:  fused Pallas AOI step (emit="chg")
               -> chunk-compacted diff extraction (ops/events.extract_chunks)
               -> wire encode (ops/events.encode_row_stream)

Each chip compacts and encodes its OWN spaces' events; the host decodes the
per-chip streams with the same overflow contract as the single-chip bucket
(engine/aoi._TPUBucket) and falls back to that chip's raw diff grids when a
cap is exceeded.  Event pairs are bit-identical to every other backend
(tests/test_aoi_mesh.py drives this against the CPU oracle).

``pipeline=True`` double-buffers the flush exactly like the single-chip
bucket (SURVEY §7 hard part (d)): ``flush()`` dispatches tick T and then
harvests tick T-1, whose scalars + optimistically sized stream slices were
issued ``copy_to_host_async`` at T-1's dispatch -- the D2H rides under the
whole host tick between flushes and events arrive ONE TICK LATE.  Slot
release epochs drop a dead space's in-flight events and mirror traffic; all
large outputs ride DONATED per-capacity scratch buffers (two sets alternate
naturally with the one-deep pipeline).

Differences from the single-chip bucket (deliberate):

  * ALL slots step every flush (no ``slot_idx`` gather): a gather across the
    sharded leading axis would be a cross-chip collective.  Unstaged slots
    re-step their cached previous inputs -- identical inputs produce a zero
    diff, so they emit nothing and their interest words are rewritten
    unchanged.  Fresh slots (never staged) carry ``active=False`` and empty
    prev, so they also emit nothing.  ``clear_entity`` marks the departed
    entity inactive in the cached inputs too, so a cleared-but-unstaged slot
    stays silent exactly like the single-chip bucket.
  * A slot whose prev words were seeded via ``set_prev`` (capacity growth,
    freeze-restore) MUST be staged before the next flush -- stepping cached
    zero inputs against carried state would emit a mass-leave.  The engine's
    callers guarantee this (growth and restore both mark the space AOI-dirty
    the same tick); ``flush`` raises if the contract is broken rather than
    corrupt interest state.

Maintenance never round-trips the full interest state: resets and clears
scatter on device in ONE dispatch (donated, sharding pinned), ``set_prev``
ships one slot's [C, W] words, ``get_prev`` fetches one slot's.  The only
full-array host copy left is capacity growth (rare, amortized by doubling);
``full_roundtrips`` counts it so tests can pin the steady state to zero.
"""

from __future__ import annotations

import time

import numpy as np

from .. import faults
from ..telemetry import trace as _T
from ..ops import aoi_emit as AE
from ..ops import aoi_predicate as P
from ..ops import dispatch_count as DC
from ..ops import events as EV
from .aoi import (_Bucket, _CapDecay, _build_snapshot, _device_fault,
                  _emit_expand, _kernelish_fault, _packed_predicate,
                  _split_rows, _unpack_positions)

_LANES = 128


class _MeshTPUBucket(_Bucket):
    """Device-mesh-resident interest state [S, C, W], spaces sharded over
    the mesh's 'space' axis; one fused shard_map dispatch per flush."""

    def __init__(self, capacity: int, mesh, pipeline: bool = False,
                 delta_staging: bool = True, emit: str = "vector",
                 cross_tick: bool = False, fused: bool = False):
        super().__init__(capacity)
        # fused steady tick (ops/aoi_fused contract, per chip): the
        # packet scatter folds INTO the sharded step, so a steady tick
        # is ONE program launch (vs scatter + step); see _dispatch_fused
        self.fused = bool(fused)
        import jax  # noqa: F401  (fail fast if jax is unavailable)

        # emit path for the harvested word streams (docs/perf.md emit
        # paths): "native" hands bit expansion + sort to libgwemit; on the
        # multi-chip tiers "vector" and "host" are both the numpy
        # expand_classified_host (the split only diverges single-chip).
        # _emit_requested re-arms after a seam demotion (reset_emit_path).
        self._emit = emit
        self._emit_requested = emit

        self.mesh = mesh  # parallel.SpaceMesh
        self.n_dev = mesh.n_devices
        self.pipeline = pipeline
        # cross_tick composes with pipeline idempotently: either flag (or
        # both) defers delivery by exactly one tick (see _TPUBucket._defer)
        self.cross_tick = bool(cross_tick)
        self.delta_staging = delta_staging
        self.s_max = 0
        self.prev = None  # [S, C, W] uint32, sharded over axis 0
        # host-side staged inputs, persistent: unstaged slots re-submit their
        # previous values (zero diff)
        self._hx = np.zeros((0, capacity), np.float32)
        self._hz = np.zeros((0, capacity), np.float32)
        self._hr = np.zeros((0, capacity), np.float32)
        self._hact = np.zeros((0, capacity), bool)
        # per-slot event-stream subscription (True = extract events); an
        # all-plain space opts out and its changes never enter the stream
        self._hsub = np.ones(0, bool)
        self._unsub: set[int] = set()
        # mirror rows gone stale because their slot's changes were masked
        # while unsubscribed; refreshed from device on the next peek
        self._mirror_stale: set[int] = set()
        self._pending_reset: set[int] = set()
        self._pending_clear: list[tuple[int, int]] = []
        # slots seeded via set_prev that have not been staged since (see
        # module docstring)
        self._seeded_unstaged: set[int] = set()
        # per-chip extraction caps (static shapes; grow on overflow, decay
        # via the shared _CapDecay window so a mass-enter storm stops
        # pessimizing later flushes)
        self._max_chunks = 1024
        self._kcap = 8
        self._max_gaps = 2048
        self._max_exc = 8192
        self._caps = _CapDecay(nd_floor=1024)
        self._step_cache: dict[tuple, object] = {}
        self._maint_cache: dict[tuple, object] = {}
        # donated scratch sets keyed by the static caps; the pipeline holds
        # one in flight, the pool holds the other
        self._scratch: dict[tuple, tuple] = {}
        # device copies of rarely-changing staged arrays (radius, active),
        # re-uploaded only when values change
        self._h2d_cache: dict[str, tuple] = {}
        # delta staging: persistent device-resident sharded x/z copies,
        # bitwise-identical to the _hx/_hz shadows; steady flushes ship a
        # replicated sparse packet each chip scatters into its own row
        # block (no collectives).  _xz_stale = the device copies diverged
        # (grow/reset/clear, r/act/sub change) -> full restage fallback.
        self._dx = None
        self._dz = None
        self._xz_stale = True
        self._delta_max_frac = 0.25
        # fault tolerance (see engine/aoi._TPUBucket and docs/robustness.md):
        # under an active plan the mirror is kept eagerly from slot 0 so a
        # device loss always has a durable copy to rebuild from
        self._ft = faults.active()
        self._need_rebuild = False
        # chip-loss failover: True after a DeviceLost recovery -- the
        # engine rebuilds every live slot onto a fresh bucket at the end
        # of the current flush (docs/robustness.md)
        self._evacuating = False
        self._calc_level = 0  # 0 = platform default, 1 = dense, 2 = oracle
        self._fault_phase = "stage"
        self._cur_slots: list[int] = []
        self.stats = {"h2d_bytes": 0, "delta_flushes": 0, "full_flushes": 0,
                      "rebuilds": 0, "fallbacks": 0, "host_ticks": 0,
                      "poisoned": 0, "calc_level": 0, "decode_overflow": 0,
                      "fused_dispatches": 0, "fused_demotions": 0,
                      "emit_path": AE.EMIT_LEVEL[emit]}
        # pipelined tick awaiting harvest
        self._inflight = None
        # split-phase flush (docs/perf.md): dispatch() parks what harvest()
        # must do (see _TPUBucket._sched for the grammar)
        self._sched: tuple | None = None
        # per-slot release epoch: a harvest must not publish events (or XOR
        # mirror traffic) for a slot released after its dispatch
        self._slot_epoch: dict[int, int] = {}
        # lazily enabled host mirror of the interest words (see
        # _TPUBucket.peek_words).  Resets apply to it immediately (they only
        # follow release+reacquire, and the harvest XOR is epoch-guarded);
        # clears DEFER past an in-flight tick's stream -- that stream was
        # dispatched with the entity still active, so applying the clear
        # first would let the XOR re-plant the removed bits (same ordering
        # rule as _TPUBucket._mirror_apply)
        self._mirror: np.ndarray | None = None
        self._mirror_ops: list[tuple] = []
        # growth is the only remaining full-array host round-trip; steady
        # state (flushes, clears, set/get_prev) must keep this at zero
        self.full_roundtrips = 0
        # optimistic per-chip prefetch sizes (rows, escapes, exceptions)
        self._pred = (256, 64, 256)
        self.perf = {"stage_s": 0.0, "fetch_s": 0.0, "decode_s": 0.0,
                     "emit_s": 0.0}

    @property
    def _defer(self) -> bool:
        """One-tick event deferral in effect (pipeline OR cross_tick --
        see aoi._TPUBucket._defer for the composition contract)."""
        return self.pipeline or self.cross_tick

    @property
    def _steady(self) -> bool:
        """No cap recompile pending (see aoi._CapDecay)."""
        return self._caps.steady

    # -- slot management ---------------------------------------------------
    def _grow_to(self, n_slots: int) -> None:  # gwlint: allow[host-sync] -- growth copy drains old buffers once per capacity doubling
        if n_slots <= self.s_max:
            return
        self.drain()
        new_s = max(self.n_dev, self.s_max)
        while new_s < n_slots:
            new_s *= 2
        for name in ("_hx", "_hz", "_hr"):
            arr = getattr(self, name)
            grown = np.zeros((new_s, self.capacity), np.float32)
            grown[: arr.shape[0]] = arr
            setattr(self, name, grown)
        hact = np.zeros((new_s, self.capacity), bool)
        hact[: self._hact.shape[0]] = self._hact
        self._hact = hact
        hsub = np.ones(new_s, bool)
        hsub[: self._hsub.shape[0]] = self._hsub
        self._hsub = hsub
        # device prev: host round-trip (growth is rare; doubling amortizes)
        prev_h = np.zeros((new_s, self.capacity, self.W), np.uint32)
        if self.prev is not None and self.s_max > 0:
            prev_h[: self.s_max] = np.asarray(self.prev)
            self.full_roundtrips += 1
        if self._need_rebuild or self._calc_level >= 2:
            # device copy is already down: the mirror below is the durable
            # copy and grows host-side; the next rebuild uploads it grown
            self.prev = None
        else:
            try:
                faults.check("aoi.grow")
                self.prev = self.mesh.device_put(prev_h)
            except Exception as e:
                if not _device_fault(e):
                    raise
                from ..utils import gwlog

                gwlog.logger("gw.aoi").warning(
                    "mesh AOI bucket grow to %d slots failed on device "
                    "(%s); keeping the host copy, rebuild at next flush", new_s, e)
                self.stats["rebuilds"] += 1
                if self._mirror is None:
                    self._mirror = prev_h  # the growth copy becomes durable
                self.prev = None
                self._need_rebuild = True
        if self._mirror is not None:
            if self._mirror.shape[0] != new_s:
                grown = np.zeros((new_s, self.capacity, self.W), np.uint32)
                grown[: self._mirror.shape[0]] = self._mirror
                self._mirror = grown
        elif self._ft:
            # prev_h already holds the pre-growth words (zeros for fresh
            # slots): it IS the durable copy under a fault plan
            self._mirror = prev_h
        self.s_max = new_s
        self._h2d_cache.clear()
        self._dx = self._dz = None
        self._xz_stale = True
        self._scratch.clear()

    def _reset_slot(self, slot: int) -> None:
        self._pending_reset.add(slot)
        # a reused slot's cached inputs are stale; clear them so it steps
        # inert until its space stages real arrays
        self._hx[slot] = 0.0
        self._hz[slot] = 0.0
        self._hr[slot] = 0.0
        self._hact[slot] = False
        self._xz_stale = True  # device x/z diverged from the shadow
        self._seeded_unstaged.discard(slot)
        self._unsub.discard(slot)  # subscription is per-occupant; default on
        self._hsub[slot] = True
        self._mirror_stale.discard(slot)  # mirror row reset to truth below
        if self._mirror is not None:
            self._mirror[slot] = 0

    def release_slot(self, slot: int) -> None:
        self._slot_epoch[slot] = self._slot_epoch.get(slot, 0) + 1
        # a slot seeded via set_prev but released before ever being staged
        # must not trip the seeded-but-unstaged check at the next flush --
        # it is dead, not mis-staged
        self._seeded_unstaged.discard(slot)
        super().release_slot(slot)

    def set_subscribed(self, slot: int, flag: bool) -> None:
        if flag:
            self._unsub.discard(slot)
        else:
            self._unsub.add(slot)
        if slot < self._hsub.shape[0] and self._hsub[slot] != flag:
            self._hsub[slot] = flag
            self._xz_stale = True  # sub change: full-restage fallback

    def peek_words(self, slot: int) -> np.ndarray:  # gwlint: allow[host-sync] -- parity/debug accessor, off the tick path
        if self._mirror is None:
            self.flush()
            self.drain()
            # writable C-contiguous copy is load-bearing: see
            # _TPUBucket.peek_words
            self._mirror = (np.zeros((self.s_max, self.capacity, self.W),
                                     np.uint32)
                            if self.prev is None
                            else np.array(self.prev, np.uint32, copy=True,
                                          order="C"))
            if self.prev is not None:
                self.full_roundtrips += 1  # one-time mirror seed
        elif slot in self._mirror_stale:
            # changes were masked while unsubscribed: refresh this slot's
            # rows from device truth (one [C, W] slice, on demand)
            self.flush()
            self.drain()
            if self.prev is not None:
                self._mirror[slot] = np.asarray(self.prev[slot])
            else:
                # device down (rebuild pending / oracle mode): the slot's
                # prev equals the predicate of its last staged inputs
                self._mirror[slot] = _packed_predicate(
                    self._hx[slot], self._hz[slot], self._hr[slot],
                    self._hact[slot])
            self._mirror_stale.discard(slot)
        return self._mirror[slot]

    # -- state carry-over (growth / freeze-restore) ------------------------
    def get_prev(self, slot: int) -> np.ndarray:  # gwlint: allow[host-sync] -- parity/debug accessor, off the tick path
        self.flush()
        self.drain()
        if self.prev is None:  # device down: the mirror IS the state
            self._ensure_mirror()
            return np.array(self._mirror[slot], copy=True)
        return np.asarray(self.prev[slot])

    def set_prev(self, slot: int, words: np.ndarray) -> None:
        self.flush()
        self.drain()
        self._pending_reset.discard(slot)
        words = np.ascontiguousarray(words, np.uint32)
        if self.prev is not None:
            self.prev = self._set_slot_fn()(self.prev,
                                            np.int32(slot),
                                            words)
        else:  # device down: seed the durable copy; rebuild uploads it
            self._ensure_mirror()
        self._seeded_unstaged.add(slot)
        self._mirror_stale.discard(slot)  # mirror row set to truth below
        if self._mirror is not None:
            self._mirror[slot] = words

    def clear_entity(self, slot: int, entity_slot: int) -> None:
        self._pending_clear.append((slot, entity_slot))
        # keep the cached inputs consistent with what the space will stage
        # (the departed entity is inactive), so an unstaged re-step of this
        # slot cannot re-derive the cleared pairs
        if slot < self._hact.shape[0]:
            self._hact[slot, entity_slot] = False
            self._xz_stale = True  # act change: full-restage fallback
        if self._mirror is not None:
            if self._inflight is not None:
                self._mirror_ops.append(
                    (slot, entity_slot, self._slot_epoch.get(slot, 0)))
            else:
                self._mirror_clear(slot, entity_slot)

    def _mirror_clear(self, slot: int, entity_slot: int) -> None:
        self._mirror[slot, entity_slot, :] = 0
        w, b = P.word_bit_for_column(entity_slot, self.capacity)
        self._mirror[slot, :, w] &= np.uint32(
            ~(np.uint32(1) << np.uint32(b)) & 0xFFFFFFFF)

    # -- live migration & chip-loss failover (docs/robustness.md) ----------

    def _mark_evacuating(self) -> None:
        """The mesh shard holding this bucket is LOST (faults.DeviceLost):
        never touch the device again.  Host-oracle mode keeps the bucket
        serving bit-exact ticks from (mirror, shadows) until the engine
        rebuilds its spaces onto a fresh bucket at the end of the flush."""
        self._evacuating = True
        self._calc_level = 2
        self.stats["calc_level"] = 2
        self._need_rebuild = False  # there is no device to rebuild onto

    def export_snapshot(self, slot: int) -> dict:  # gwlint: allow[host-sync] -- migration snapshot, off the steady tick path
        """Live-migration wire image of one slot (see
        _TPUBucket.export_snapshot; drains the pipeline first so the
        delivered stream and the snapshot agree)."""
        self.drain()
        return _build_snapshot(
            self.capacity, self._hx[slot], self._hz[slot], self._hr[slot],
            self._hact[slot], bool(self._hsub[slot]), self.get_prev(slot))

    def import_snapshot(self, slot: int, snap: dict) -> None:  # gwlint: allow[host-sync] -- migration replay, off the steady tick path
        """Replay a migration snapshot onto this slot (see
        _TPUBucket.import_snapshot).  set_prev marks the slot
        seeded-but-unstaged: the space MUST stage before the next flush
        (the migration cover and the evacuation re-point both guarantee a
        submit every tick)."""
        if snap["capacity"] != self.capacity:
            raise ValueError(
                f"snapshot capacity {snap['capacity']} != bucket "
                f"capacity {self.capacity}")
        x, z = _unpack_positions(snap)
        self._hx[slot] = x
        self._hz[slot] = z
        self._hr[slot] = snap["r"]
        self._hact[slot] = snap["act"]
        self.set_subscribed(slot, snap["sub"])
        self._xz_stale = True  # device x/z copies diverged: full restage
        self._h2d_cache.clear()
        self.set_prev(slot, snap["words"])

    def evacuate(self) -> dict[int, dict]:
        """Snapshot every occupied slot for rebuild on surviving devices
        (the engine drives this after a DeviceLost recovery marked the
        bucket evacuating)."""
        live = sorted(set(range(self.n_slots)) - set(self._free))
        return {slot: self.export_snapshot(slot) for slot in live}

    # -- jitted helpers (sharding pinned, no host round-trips) -------------
    def _set_slot_fn(self):
        fn = self._maint_cache.get("set_slot")
        if fn is None:
            import functools

            import jax

            @functools.partial(jax.jit, donate_argnums=(0,),
                               out_shardings=self.mesh.sharding())
            def aoi_mesh_set_rows(prev, slot, words):
                return prev.at[slot].set(words)

            self._maint_cache["set_slot"] = fn = aoi_mesh_set_rows
        return fn

    def _maintenance_fn(self):
        """One donated device scatter applies all pending slot resets, row
        clears, and (pre-combined per (slot, word)) column masks."""
        fn = self._maint_cache.get("maint")
        if fn is None:
            import functools

            import jax

            @functools.partial(jax.jit, donate_argnums=(0,),
                               out_shardings=self.mesh.sharding())
            def aoi_mesh_clear(prev, reset_slots, row_slots, row_ents,
                               col_slots, col_words, col_masks):
                # mode="drop": padding uses out-of-bounds indices as true
                # no-ops.  The col pass MUST pad out of bounds too: an
                # in-bounds fill that collides with a real (slot, word)
                # entry would scatter the pre-masked gathered value over
                # the real clear (duplicate scatter indices, last write
                # wins) -- caught by the cap-4096 storm test.
                prev = prev.at[reset_slots].set(0, mode="drop")
                prev = prev.at[row_slots, row_ents, :].set(0, mode="drop")
                cols = prev.at[col_slots, :, col_words].get(
                    mode="fill", fill_value=0) & col_masks[:, None]
                return prev.at[col_slots, :, col_words].set(cols,
                                                            mode="drop")

            self._maint_cache["maint"] = fn = aoi_mesh_clear
        return fn

    def _apply_maintenance(self) -> None:
        if not self._pending_reset and not self._pending_clear:
            return
        import jax.numpy as jnp

        c = self.capacity
        noop = self.s_max  # out-of-bounds: dropped by the scatter

        def pad(seq, fill):  # pad to a power of two with no-op entries
            if not seq:
                seq = [fill]
            n = 1
            while n < len(seq):
                n *= 2
            return seq + [fill] * (n - len(seq))

        resets = sorted(self._pending_reset)
        self._pending_reset.clear()
        col_mask: dict[tuple[int, int], int] = {}
        rows = []
        for slot, e in self._pending_clear:
            w, b = P.word_bit_for_column(e, c)
            key = (slot, w)
            col_mask[key] = col_mask.get(key, 0xFFFFFFFF) & (
                ~(1 << b) & 0xFFFFFFFF)
            rows.append((slot, e))
        self._pending_clear.clear()
        cols = [(s, w, m) for (s, w), m in col_mask.items()]
        resets = pad(resets, noop)
        rows = pad(rows, (noop, 0))
        # the col fill must not collide with any real (slot, word) pair --
        # an out-of-bounds word index is dropped by the scatter
        cols = pad(cols, (0, self.W, 0xFFFFFFFF))
        DC.record()
        self.prev = self._maintenance_fn()(
            self.prev,
            jnp.asarray(resets, jnp.int32),
            jnp.asarray([s for s, _ in rows], jnp.int32),
            jnp.asarray([e for _, e in rows], jnp.int32),
            jnp.asarray([s for s, _, _ in cols], jnp.int32),
            jnp.asarray([w for _, w, _ in cols], jnp.int32),
            jnp.asarray([m for _, _, m in cols], jnp.uint32),
        )

    def _delta_fn(self, npk: int):
        """Jitted donated per-shard scatter of one replicated (rows, cols,
        xv, zv) packet into the sharded device x/z: each chip localizes the
        row indices to its own block and drops the rest
        (ops/aoi_stage.delta_scatter) -- no cross-chip collectives.  Keyed
        by padded packet length AND s_max (the closure bakes the block
        size)."""
        key = ("delta", npk, self.s_max)
        fn = self._maint_cache.get(key)
        if fn is None:
            import jax
            from jax.sharding import PartitionSpec as PS

            from ..ops.aoi_stage import delta_scatter

            s_local = self.s_max // self.n_dev
            axis = self.mesh.axis

            def _local(dx, dz, rows, cols, xv, zv):
                lo = jax.lax.axis_index(axis) * s_local
                return delta_scatter(dx, dz, rows, cols, xv, zv,
                                     row_lo=lo, n_rows=s_local)

            spec, rep = PS(axis), PS()
            local = jax.shard_map(_local, mesh=self.mesh.mesh,
                              in_specs=(spec, spec, rep, rep, rep, rep),
                              out_specs=(spec, spec), check_vma=False)
            self._maint_cache[key] = fn = jax.jit(
                local, donate_argnums=(0, 1))
        return fn

    def _stage_xz(self, sl, old_x, old_z, old_r, old_act) -> None:
        """Bring the device-resident sharded x/z up to date with the host
        shadow: a sparse replicated packet on the steady path, a full
        sharded re-upload on the fallbacks (grow/reset/clear, r/act/sub
        change, changed fraction above _delta_max_frac, or delta staging
        disabled).  Bit-pattern diff: see _TPUBucket._stage_inputs."""
        from ..ops import aoi_stage as AS

        new_x, new_z = self._hx[sl], self._hz[sl]
        diff = (new_x.view(np.uint32) != old_x.view(np.uint32)) \
            | (new_z.view(np.uint32) != old_z.view(np.uint32))
        n_changed = np.count_nonzero(diff)  # host numpy scalar
        if not (np.array_equal(self._hr[sl], old_r)
                and np.array_equal(self._hact[sl], old_act)):
            self._xz_stale = True  # r/act change: full-restage fallback
        if (self.delta_staging and not self._xz_stale
                and self._dx is not None
                and n_changed <= self._delta_max_frac * max(diff.size, 1)):
            if n_changed:
                faults.check("aoi.delta")
                rows, cols = np.nonzero(diff)
                pkt = AS.pad_packet(sl[rows], cols, new_x[rows, cols],
                                    new_z[rows, cols])
                DC.record()
                self._dx, self._dz = self._delta_fn(len(pkt[0]))(
                    self._dx, self._dz, *pkt)
                self.stats["h2d_bytes"] += AS.packet_nbytes(*pkt)
            self.stats["delta_flushes"] += 1
            return
        faults.check("aoi.h2d")
        self._dx = self.mesh.device_put(self._hx)
        self._dz = self.mesh.device_put(self._hz)
        self.stats["h2d_bytes"] += self._hx.nbytes + self._hz.nbytes
        self._xz_stale = False
        self.stats["full_flushes"] += 1

    def _h2d(self, role: str, arr: np.ndarray):
        cached = self._h2d_cache.get(role)
        if cached is not None and cached[0].shape == arr.shape and \
                np.array_equal(cached[0], arr):
            return cached[1]
        faults.check("aoi.h2d")
        dev = self.mesh.device_put(arr)
        self._h2d_cache[role] = (arr.copy(), dev)
        self.stats["h2d_bytes"] += arr.nbytes
        return dev

    # -- the fused dispatch ------------------------------------------------
    def _sharded_step(self, npk: int | None = None):
        """Build (or reuse) the jitted shard_map flush for the current
        static config (s_max, caps).  All large outputs ride DONATED scratch
        buffers (see engine/aoi._fused_bucket_step for why).

        ``npk`` (fused mode, ops/aoi_fused contract): fold the delta
        scatter of one replicated packet of that padded length INTO the
        program -- each chip localizes the row indices to its own block
        and drops the rest, then steps from the freshly scattered x/z --
        so the steady tick is ONE launch instead of scatter + step.  The
        sharded x/z ride as donated inputs and come back as two extra
        outputs."""
        key = (self.s_max, self._max_chunks, self._kcap, self._max_gaps,
               self._max_exc, self._calc_level, npk)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        if len(self._step_cache) > 4:
            self._step_cache.clear()
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as PS

        from ..ops.aoi_dense import aoi_step_chg
        from ..ops.aoi_stage import delta_scatter

        # calculator fallback chain level 1: force the fused dense path
        # even where the platform default would pick Pallas
        platform = "cpu" if self._calc_level >= 1 else self.mesh.platform
        mc, kcap = self._max_chunks, self._kcap
        mg, mx = self._max_gaps, self._max_exc
        s_local = self.s_max // self.n_dev
        axis = self.mesh.axis
        fused = npk is not None

        def _body(prev, chg_buf, vals_buf, nv_buf, lane_buf, csel_buf,
                  x, z, r, act, sub):
            # platform routing (pallas on TPU, fused dense elsewhere --
            # interpret-mode Pallas walks its grid step-by-step in Python,
            # ~49 s/flush at cap 16384) lives in ops/aoi_dense.aoi_step_chg
            new, chg = aoi_step_chg(x, z, r, act, prev, platform=platform)
            # subscription mask: all-plain spaces contribute nothing to the
            # event stream (see engine/aoi._fused_bucket_step); ``new`` is
            # unmasked -- prev stays authoritative
            chg = jnp.where(sub[:, None, None], chg, jnp.uint32(0))
            vals, nv, lane, csel, ccnt, nd, mcc = EV.extract_chunks(
                chg, mc, kcap, aux=new, lanes=_LANES)
            (rowb, bitpos, woff, base_row, n_esc, esc_rows, exc_gidx,
             exc_chg, exc_new, exc_n) = EV.encode_row_stream(
                vals, nv, lane, csel, ccnt, w=_LANES, max_gaps=mg,
                max_exc=mx)
            scalars = jnp.stack([nd, mcc, base_row, n_esc, exc_n])
            chg_buf = chg_buf.at[:].set(chg)
            vals_buf = vals_buf.at[:].set(vals)
            nv_buf = nv_buf.at[:].set(nv)
            lane_buf = lane_buf.at[:].set(lane)
            csel_buf = csel_buf.at[:].set(csel)
            return (new, chg_buf, vals_buf, nv_buf, lane_buf, csel_buf,
                    rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
                    exc_new, scalars[None])

        spec, rep = PS(self.mesh.axis), PS()
        if fused:
            def _local(prev, chg_buf, vals_buf, nv_buf, lane_buf,
                       csel_buf, dx, dz, rows, cols, xv, zv, r, act,
                       sub):
                lo = jax.lax.axis_index(axis) * s_local
                dx, dz = delta_scatter(dx, dz, rows, cols, xv, zv,
                                       row_lo=lo, n_rows=s_local)
                out = _body(prev, chg_buf, vals_buf, nv_buf, lane_buf,
                            csel_buf, dx, dz, r, act, sub)
                return out + (dx, dz)

            local = jax.shard_map(
                _local,
                mesh=self.mesh.mesh,
                in_specs=(spec,) * 8 + (rep,) * 4 + (spec,) * 3,
                out_specs=(spec,) * 16,
                check_vma=False,
            )
            fn = jax.jit(local, donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
        else:
            local = jax.shard_map(
                _body,
                mesh=self.mesh.mesh,
                in_specs=(spec,) * 11,
                out_specs=(spec,) * 14,
                check_vma=False,
            )
            fn = jax.jit(local, donate_argnums=(0, 1, 2, 3, 4, 5))
        self._step_cache[key] = fn
        return fn

    def _get_scratch(self):
        """Donated buffers for one dispatch: (chg [S,C,W], vals/nv [D*mc,k],
        lane [D*mc,k], csel [D*mc]); sharded over the mesh."""
        import jax.numpy as jnp

        key = (self.s_max, self._max_chunks, self._kcap)
        sc = self._scratch.pop(key, None)
        if sc is not None:
            return key, sc
        while len(self._scratch) >= 2:
            self._scratch.pop(next(iter(self._scratch)))
        put = self.mesh.device_put
        mc, kcap = self._max_chunks, self._kcap
        n = self.n_dev * mc
        sc = (
            put(np.zeros((self.s_max, self.capacity, self.W), np.uint32)),
            put(np.zeros((n, kcap), np.uint32)),
            put(np.zeros((n, kcap), np.uint32)),
            put(np.full((n, kcap), -1, np.int32)),
            put(np.zeros(n, np.int32)),
        )
        return key, sc

    def flush(self) -> None:
        """Monolithic flush = dispatch immediately followed by harvest (the
        forced-sequential baseline; see _TPUBucket.flush)."""
        self.dispatch()
        self.harvest()

    def dispatch(self) -> None:
        """Phase 1 of the split flush: maintenance + pack + H2D enqueue +
        sharded-kernel enqueue, never blocking on device values (gwlint
        flush-phase rule); parks the harvest work in ``_sched``."""
        if self._sched is not None:
            self.harvest()  # gwlint: allow[flush-phase] -- re-entrant flush drains the prior dispatch first
        if (not self._staged and not self._pending_reset
                and not self._pending_clear):
            if self._inflight is not None:
                self._sched = ("inflight",)
            return
        if self._calc_level >= 2:
            # calculator fallback chain bottom: host-oracle mode -- the
            # device is out of the loop; maintenance already reached the
            # mirror when issued, and the host compute defers to harvest
            # so it overlaps other buckets' device work
            self._pending_reset.clear()
            self._pending_clear.clear()
            if not self._staged:
                if self._inflight is not None:
                    self._sched = ("inflight",)
                return
            slots = self._restage_shadows()
            if self._seeded_unstaged:
                raise RuntimeError(
                    "mesh AOI bucket: slots %r carry seeded interest state "
                    "but were not staged before flush -- stepping them would "
                    "emit a spurious mass-leave (stage the space first)"
                    % sorted(self._seeded_unstaged))
            self._sched = ("oracle", slots)
            return
        try:
            self._dispatch_device()
        except Exception as e:
            if not _device_fault(e):
                raise
            self._recover(e)
            if isinstance(e, faults.DeviceLost):
                self._mark_evacuating()

    def harvest(self) -> None:
        """Phase 2 of the split flush: the blocking fetch + decode of what
        :meth:`dispatch` parked (see _TPUBucket.harvest)."""
        sched, self._sched = self._sched, None
        if sched is None:
            return
        if sched[0] == "oracle":
            if self._inflight is not None:
                self._harvest()  # deliver T-1 before parking T (cadence)
            self._host_tick(sched[1])
            return
        rec = self._inflight if sched[0] == "inflight" else sched[1]
        if rec is None:
            return
        self._fault_phase = "harvest"
        try:
            if sched[0] == "inflight":
                self._harvest()
            else:
                self._harvest(rec)
        except Exception as e:
            if not _device_fault(e):
                raise
            self._recover_harvest(e, rec)

    def _dispatch_device(self) -> None:  # gwlint: allow[host-sync] -- pre-dispatch overflow peek reads an async-fetched host-local scalar
        t0 = time.perf_counter()
        _ts = _T.t()
        self._fault_phase = "stage"
        # device health probe: kind ``reset`` = the chip is LOST
        # (faults.DeviceLost; dispatch()'s handler marks the bucket
        # evacuating after the standard host-side recovery)
        faults.check("aoi.device")
        if self._defer and self._inflight is not None \
                and not self._inflight.get("all_unsub") \
                and not self._inflight.get("host"):
            # peek the inflight tick's scalars (async-fetched at its
            # dispatch, host-local by now): a ROW overflow recovery reads
            # the NEW interest words, i.e. self.prev -- which maintenance
            # below mutates (a clear would flip that tick's enters for the
            # cleared entity to leaves) and the next dispatch donates.
            # Harvest BEFORE both in that rare case; the pipeline stalls
            # one tick instead of misclassifying or reading freed memory.
            # (an all-unsub tick cannot overflow: its stream is empty)
            nd_mcc = np.asarray(self._inflight["scalars"])[:, :2]  # gwlint: allow[flush-phase] -- async-fetched at T-1's dispatch, host-local by now
            mc_i, kcap_i = self._inflight["caps"][:2]
            if (nd_mcc[:, 0] > mc_i).any() or (nd_mcc[:, 1] > kcap_i).any():
                self._harvest()  # gwlint: allow[flush-phase] -- rare overflow: stall one tick rather than read donated memory
        self._rebuild_device()
        self._apply_maintenance()
        if not self._staged:
            # maintenance-only tick: a pending pipelined tick still
            # delivers -- at harvest time
            if self._inflight is not None:
                self._sched = ("inflight",)
            return

        staged_slots = sorted(self._staged)
        # np.array (not asarray): packs a host python list, no device sync
        sl = np.array(staged_slots, np.intp)
        # save the previously staged rows (fancy index -> compact copies)
        # before overwriting: _stage_xz diffs the new tick against them
        old_x, old_z = self._hx[sl], self._hz[sl]
        old_r, old_act = self._hr[sl], self._hact[sl]
        self._restage_shadows()
        self._cur_slots = staged_slots  # recovery needs them once _staged is gone
        if self._seeded_unstaged:
            raise RuntimeError(
                "mesh AOI bucket: slots %r carry seeded interest state but "
                "were not staged before flush -- stepping them would emit a "
                "spurious mass-leave (stage the space first)"
                % sorted(self._seeded_unstaged))

        if self._mirror is not None and self._unsub:
            self._mirror_stale.update(
                s for s in staged_slots if s in self._unsub)
        key, scratch = self._get_scratch()
        if self.fused and self._dispatch_fused(staged_slots, sl, key,
                                               scratch, old_x, old_z,
                                               old_r, old_act, t0, _ts):
            return
        self._stage_xz(sl, old_x, old_z, old_r, old_act)
        _T.lap("aoi.stage", _ts)
        _tk = _T.t()
        self._fault_phase = "kernel"
        faults.check("aoi.kernel")
        DC.record()
        out = self._sharded_step()(
            self.prev, *scratch, self._dx, self._dz,
            self._h2d("r", self._hr), self._h2d("act", self._hact),
            self._h2d("sub", self._hsub))
        (new, chg, g_vals, g_nv, g_lane, g_csel, rowb, bitpos,
         woff, esc_rows, exc_gidx, exc_chg, exc_new, scalars) = out
        _T.lap("aoi.kernel", _tk)
        self.prev = new  # the step's new words ARE next tick's prev
        # every staged slot unsubscribed (and unstaged slots re-step
        # identical inputs -> zero diff): the stream is empty by
        # construction, so the harvest needs NO fetch -- not even scalars
        # (every synchronous wait is a device round trip)
        all_unsub = bool(self._unsub) and all(s in self._unsub
                                              for s in staged_slots)
        if not all_unsub:
            scalars.copy_to_host_async()
        rec = {
            "slots": staged_slots,
            "epochs": {s: self._slot_epoch.get(s, 0)
                       for s in range(self.s_max)},
            "key": key, "caps": (self._max_chunks, self._kcap,
                                 self._max_gaps, self._max_exc),
            "scratch": (chg, g_vals, g_nv, g_lane, g_csel),
            "streams": (rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
                        exc_new),
            "scalars": scalars,
            "all_unsub": all_unsub,
            "prefetch": None,
        }
        if self._defer and not all_unsub:
            # optimistic per-chip prefetch at recently observed stream
            # sizes; the harvest refetches exact slices on a misfit (an
            # all-unsubscribed tick's stream is empty by construction --
            # skip the prefetch outright, the per-chip nd==0 early-out
            # never fetches)
            mc = self._max_chunks
            ndp = min(mc, self._pred[0])
            escp = min(self._max_gaps, self._pred[1])
            excp = min(self._max_exc, self._pred[2])
            slices = []
            for d in range(self.n_dev):
                slices.append((
                    rowb[d * mc:d * mc + ndp],
                    bitpos[d * mc:d * mc + ndp],
                    woff[d * mc:d * mc + ndp],
                    esc_rows[d * self._max_gaps:d * self._max_gaps + escp],
                    exc_gidx[d * self._max_exc:d * self._max_exc + excp],
                    exc_chg[d * self._max_exc:d * self._max_exc + excp],
                    exc_new[d * self._max_exc:d * self._max_exc + excp],
                ))
                for a in slices[-1]:
                    a.copy_to_host_async()
            rec["prefetch"] = (ndp, escp, excp, slices)
        prev_rec, self._inflight = self._inflight, rec
        self.perf["stage_s"] += time.perf_counter() - t0
        if self._defer:
            if prev_rec is not None:
                self._sched = ("rec", prev_rec)
        else:
            self._sched = ("inflight",)

    def _dispatch_fused(self, staged_slots, sl, key, scratch, old_x,
                        old_z, old_r, old_act, t0, _ts) -> bool:
        """Attempt the per-chip fused tick (ops/aoi_fused contract): the
        packet scatter folds into :meth:`_sharded_step`, making a steady
        tick ONE program launch instead of delta-scatter + step.  Returns
        True when dispatched fused; False falls through to the unfused
        flow -- silently when the tick is simply not a steady delta tick
        (stale x/z, r/act change, oversized diff), counted in
        ``fused_demotions`` when an ``aoi.delta``/``aoi.kernel`` seam
        fault fired in the attempt (the occurrence is consumed, so the
        unfused flow runs clean in the same call -- same-tick,
        bit-exact)."""
        if (not self.delta_staging or self._xz_stale
                or self._dx is None or self._need_rebuild):
            return False
        new_x, new_z = self._hx[sl], self._hz[sl]
        if not (np.array_equal(self._hr[sl], old_r)
                and np.array_equal(self._hact[sl], old_act)):
            return False  # r/act moved: full-restage tick, unfused
        diff = (new_x.view(np.uint32) != old_x.view(np.uint32)) \
            | (new_z.view(np.uint32) != old_z.view(np.uint32))
        n_changed = np.count_nonzero(diff)
        if n_changed > self._delta_max_frac * max(diff.size, 1):
            return False  # mass movement: full restage beats the scatter
        try:
            if n_changed:
                faults.check("aoi.delta")
            self._fault_phase = "kernel"
            faults.check("aoi.kernel")
        except Exception as e:
            if not _device_fault(e):
                raise
            self.stats["fused_demotions"] += 1
            self._fault_phase = "stage"
            return False
        from ..ops import aoi_stage as AS

        if n_changed:
            rows, cols = np.nonzero(diff)
            pkt = AS.pad_packet(sl[rows], cols, new_x[rows, cols],
                                new_z[rows, cols])
            self.stats["h2d_bytes"] += AS.packet_nbytes(*pkt)
        else:
            zi = np.zeros(0, np.int32)
            zf = np.zeros(0, np.float32)
            pkt = (zi, zi, zf, zf)  # zero movers: in-program no-op scatter
        self.stats["delta_flushes"] += 1
        _T.lap("aoi.stage", _ts)
        _tk = _T.t()
        DC.record()
        out = self._sharded_step(len(pkt[0]))(
            self.prev, *scratch, self._dx, self._dz, *pkt,
            self._h2d("r", self._hr), self._h2d("act", self._hact),
            self._h2d("sub", self._hsub))
        (new, chg, g_vals, g_nv, g_lane, g_csel, rowb, bitpos,
         woff, esc_rows, exc_gidx, exc_chg, exc_new, scalars,
         self._dx, self._dz) = out
        _T.lap("aoi.kernel", _tk)
        self.prev = new
        all_unsub = bool(self._unsub) and all(s in self._unsub
                                              for s in staged_slots)
        if not all_unsub:
            scalars.copy_to_host_async()
        rec = {
            "slots": staged_slots,
            "epochs": {s: self._slot_epoch.get(s, 0)
                       for s in range(self.s_max)},
            "key": key, "caps": (self._max_chunks, self._kcap,
                                 self._max_gaps, self._max_exc),
            "scratch": (chg, g_vals, g_nv, g_lane, g_csel),
            "streams": (rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
                        exc_new),
            "scalars": scalars,
            "all_unsub": all_unsub,
            "prefetch": None,
        }
        if self._defer and not all_unsub:
            mc = self._max_chunks
            ndp = min(mc, self._pred[0])
            escp = min(self._max_gaps, self._pred[1])
            excp = min(self._max_exc, self._pred[2])
            slices = []
            for d in range(self.n_dev):
                slices.append((
                    rowb[d * mc:d * mc + ndp],
                    bitpos[d * mc:d * mc + ndp],
                    woff[d * mc:d * mc + ndp],
                    esc_rows[d * self._max_gaps:d * self._max_gaps + escp],
                    exc_gidx[d * self._max_exc:d * self._max_exc + excp],
                    exc_chg[d * self._max_exc:d * self._max_exc + excp],
                    exc_new[d * self._max_exc:d * self._max_exc + excp],
                ))
                for a in slices[-1]:
                    a.copy_to_host_async()
            rec["prefetch"] = (ndp, escp, excp, slices)
        self.stats["fused_dispatches"] += 1
        prev_rec, self._inflight = self._inflight, rec
        self.perf["stage_s"] += time.perf_counter() - t0
        if self._defer:
            if prev_rec is not None:
                self._sched = ("rec", prev_rec)
        else:
            self._sched = ("inflight",)
        return True

    def drain(self) -> None:
        self.harvest()
        if self._inflight is not None:
            self._harvest()

    # -- fault recovery (see engine/aoi._TPUBucket and docs/robustness.md):
    # the durable copies are the host shadows plus the mirror; on a device
    # fault the in-flight tick delivers first (its buffers predate the
    # fault), the faulted tick recomputes host-side from (mirror, shadows)
    # -- bit-exact with the sharded step because every backend evaluates
    # the same packed predicate and np.nonzero's ascending flat order
    # matches the per-chip chunk extraction after the chip-offset shift --
    # and all device state drops for a mirror re-upload at the next flush.

    def _restage_shadows(self) -> list[int]:
        """Copy staged tick inputs into the persistent host shadows (pure
        host work; shared by the device path and fault recovery)."""
        slots = sorted(self._staged)
        for slot in slots:
            sx, sz, sr, sa = self._staged[slot]
            n = len(sx)
            self._hx[slot, :n] = sx
            self._hz[slot, :n] = sz
            self._hr[slot, :n] = sr
            self._hact[slot] = False
            self._hact[slot, :n] = sa
            self._seeded_unstaged.discard(slot)
        self._staged.clear()
        return slots

    def _rebuild_device(self) -> None:
        """Re-upload the packed interest state from the durable host mirror
        after a device loss (deferred to flush so a dead mesh is retried at
        tick cadence, not in the failure handler)."""
        if not self._need_rebuild:
            return
        self._need_rebuild = False
        self.prev = self.mesh.device_put(self._mirror)
        self.stats["h2d_bytes"] += self._mirror.nbytes
        self.full_roundtrips += 1

    def reset_calc_chain(self) -> None:
        """Re-arm the device calculator after fallback (operator action --
        demotion is sticky so a flapping device cannot oscillate)."""
        self._calc_level = 0
        self.stats["calc_level"] = 0
        if self.prev is None and self.s_max:
            self._ensure_mirror()
            self._need_rebuild = True

    def _ensure_mirror(self) -> None:  # gwlint: allow[host-sync] -- fault-recovery path, not the steady tick
        """Make the host mirror exist (see _TPUBucket._ensure_mirror)."""
        if self._mirror is not None:
            return
        try:
            self._mirror = (
                np.zeros((self.s_max, self.capacity, self.W), np.uint32)
                if self.prev is None
                else np.array(self.prev, np.uint32, copy=True, order="C"))
            if self.prev is not None:
                self.full_roundtrips += 1
        except Exception:
            from ..utils import gwlog

            gwlog.logger("gw.aoi").warning(
                "mesh prev unreadable during recovery; rebuilding the "
                "mirror from the input shadows (derived state of cleared/"
                "seeded slots may lag until their next stage)")
            m = np.empty((self.s_max, self.capacity, self.W), np.uint32)
            for s in range(self.s_max):
                m[s] = _packed_predicate(self._hx[s], self._hz[s],
                                         self._hr[s], self._hact[s])
            self._mirror = m

    def _refresh_stale_rows(self) -> None:
        """Recompute mirror rows that went stale while unsubscribed (see
        _TPUBucket._refresh_stale_rows for the exactness contract)."""
        for s in sorted(self._mirror_stale):
            self._mirror[s] = _packed_predicate(
                self._hx[s], self._hz[s], self._hr[s], self._hact[s])
        self._mirror_stale.clear()

    def _recover(self, e: BaseException) -> None:  # gwlint: allow[flush-phase] -- fault recovery: the device is gone, host sync is the point
        """Device fault mid-flush: deliver the inflight tick, recompute the
        faulted tick host-side (bit-exact), drop all device state."""
        from ..utils import gwlog

        self.stats["rebuilds"] += 1
        if self._fault_phase == "kernel" and self._calc_level < 2:
            # the calculator itself failed: demote one level down the
            # chain (pallas -> dense -> host oracle)
            self._calc_level += 1
            self.stats["fallbacks"] += 1
            self.stats["calc_level"] = self._calc_level
        gwlog.logger("gw.aoi").warning(
            "mesh AOI bucket (cap %d) device fault during %s: %s -- "
            "recovering tick on host (calc level %d)",
            self.capacity, self._fault_phase, e, self._calc_level)
        # 1. the tick dispatched LAST flush finished before this fault; its
        # buffers are intact, so it delivers on its normal schedule
        if self._inflight is not None:
            try:
                self._harvest()
            except Exception as he:  # the device died mid-harvest too
                gwlog.logger("gw.aoi").warning(
                    "inflight tick unharvestable during recovery (%s); "
                    "its events are lost", he)
                self._inflight = None
        # 2. make the durable copy exist, and land any maintenance that
        # never reached the device (resets/clears already hit the mirror
        # when they were issued, so the re-apply is idempotent)
        self._ensure_mirror()
        for s in sorted(self._pending_reset):
            self._mirror[s] = 0
        for s, ent in self._pending_clear:
            self._mirror_clear(s, ent)
        self._pending_reset.clear()
        self._pending_clear.clear()
        # 3. the faulted tick's inputs are (or now land) in the shadows
        slots = self._restage_shadows() if self._staged else self._cur_slots
        self._cur_slots = []
        # 4. device state is gone; the next flush rebuilds from the mirror
        self.prev = None
        self._dx = self._dz = None
        self._xz_stale = True
        self._h2d_cache.clear()
        self._scratch.clear()
        self._need_rebuild = self._calc_level < 2
        # 5. compute the faulted tick on the host (staged slots only:
        # unstaged slots re-step identical inputs -> zero diff by the
        # module contract, so they emit nothing either way)
        if slots:
            self._host_tick(slots)

    def _recover_harvest(self, e: BaseException, rec: dict) -> None:  # gwlint: allow[flush-phase] -- fault recovery: the device is gone, host sync is the point
        """Device fault surfacing at HARVEST time (see
        _TPUBucket._recover_harvest for the full contract): the mirror
        still predates the faulted record's XOR and the shadows hold the
        newest staged inputs, so one host predicate pass regenerates the
        lost events as a coalesced diff, published immediately."""
        from ..utils import gwlog

        self.stats["rebuilds"] += 1
        if _kernelish_fault(e) and self._calc_level < 2:
            self._calc_level += 1
            self.stats["fallbacks"] += 1
            self.stats["calc_level"] = self._calc_level
        gwlog.logger("gw.aoi").warning(
            "mesh AOI bucket (cap %d) device fault during harvest: %s -- "
            "regenerating the tick's events on host (calc level %d)",
            self.capacity, e, self._calc_level)
        if rec.get("host"):  # defensive: a synthetic record never faults
            chg_vals, ent_vals, gidx, s_n = rec["payload"]
            self._publish(rec["slots"], rec["epochs"], chg_vals, ent_vals,
                          gidx, s_n)
            rec_slots: list[int] = []
        else:
            rec_slots = rec["slots"]
        newest, self._inflight = self._inflight, None
        host_rec = None
        if newest is not None:
            if newest.get("host"):
                host_rec = newest
            else:
                rec_slots = sorted(set(rec_slots) | set(newest["slots"]))
        self._ensure_mirror()
        # deferred mirror maintenance (behind the now-lost stream XOR) plus
        # device-queue maintenance that never reached prev: land everything
        # on the mirror (idempotent)
        if self._mirror_ops:
            ops, self._mirror_ops = self._mirror_ops, []
            for op in ops:
                if self._slot_epoch.get(op[0], 0) == op[-1]:
                    self._mirror_clear(op[0], op[1])
        for s in sorted(self._pending_reset):
            self._mirror[s] = 0
        for s, ent in self._pending_clear:
            self._mirror_clear(s, ent)
        self._pending_reset.clear()
        self._pending_clear.clear()
        if self._staged:  # defensive: inputs staged between the phases
            rec_slots = sorted(set(rec_slots) | set(self._restage_shadows()))
        self._cur_slots = []
        self.prev = None
        self._dx = self._dz = None
        self._xz_stale = True
        self._h2d_cache.clear()
        self._scratch.clear()
        self._need_rebuild = self._calc_level < 2
        if rec_slots:
            self._host_tick(rec_slots, publish_now=True)
        self._inflight = host_rec

    def _host_tick(self, slots: list[int], publish_now: bool = False) -> None:
        """One bucket tick on the host from the durable copies, bit-exact
        with the sharded step (see _TPUBucket._host_tick; ``publish_now``
        skips the pipelined parking for harvest-time recovery)."""
        c, W = self.capacity, self.W
        s_n = len(slots)
        self.stats["host_ticks"] += 1
        _th = _T.t()
        self._refresh_stale_rows()
        sl = np.array(slots, np.intp)
        sub = self._hsub[sl]
        new = np.empty((s_n, c, W), np.uint32)
        for i, s in enumerate(slots):
            new[i] = _packed_predicate(self._hx[s], self._hz[s],
                                       self._hr[s], self._hact[s])
        chg = new ^ self._mirror[sl]
        chg[~sub] = 0
        flat = chg.reshape(-1)
        gidx = np.nonzero(flat)[0]
        chg_vals = flat[gidx]
        ent_vals = chg_vals & new.reshape(-1)[gidx]
        self._mirror[sl] = new
        epochs = [self._slot_epoch.get(s, 0) for s in slots]
        if self._defer and not publish_now:
            # deferred cadence (pipeline/cross_tick): events deliver one
            # tick late, so the recovered tick parks as a synthetic
            # inflight record
            self._inflight = {"host": True, "slots": slots,
                              "epochs": epochs,
                              "payload": (chg_vals, ent_vals, gidx, s_n)}
        else:
            self._publish(slots, epochs, chg_vals, ent_vals, gidx, s_n)
        _T.lap("aoi.host_tick", _th)

    def _apply_deferred_mirror_ops(self) -> None:
        """Clears issued after a tick's dispatch apply now, AFTER its
        stream; the epoch tag drops ops whose slot was released since (a
        reacquired slot may carry freshly seeded set_prev words)."""
        if self._mirror is None or not self._mirror_ops:
            return
        ops, self._mirror_ops = self._mirror_ops, []
        for slot, ent, ep in ops:
            if self._slot_epoch.get(slot, 0) == ep:
                self._mirror_clear(slot, ent)

    def _publish(self, slots, epochs, chg_vals, ent_vals, gidx,
                 s_n: int) -> None:
        """Expand a compact-layout classified stream into per-slot events
        (host-recovery ticks; the device harvest keys by global slot)."""
        pe, pl = _emit_expand(self, chg_vals, ent_vals, gidx, s_n)
        ent_rows = _split_rows(pe)
        lv_rows = _split_rows(pl)
        empty = np.empty((0, 2), np.int32)
        for row, (slot, epoch) in enumerate(zip(slots, epochs)):
            if self._slot_epoch.get(slot, 0) != epoch:
                continue  # released since the tick: events of a dead space
            e = ent_rows.get(row, empty)
            l = lv_rows.get(row, empty)
            pend = self._events.get(slot)
            if pend is not None:
                e = np.concatenate([pend[0], e])
                l = np.concatenate([pend[1], l])
            self._events[slot] = (e, l)

    def _harvest(self, rec=None) -> None:  # gwlint: allow[host-sync] -- THE per-tick drain point: harvests kernel outputs once per flush
        if rec is None:
            rec, self._inflight = self._inflight, None
        if rec.get("host"):
            # synthetic record parked by fault recovery / oracle mode: the
            # events were computed host-side at its tick; only the
            # pipelined one-tick-late delivery remained
            chg_vals, ent_vals, gidx, s_n = rec["payload"]
            self._publish(rec["slots"], rec["epochs"], chg_vals, ent_vals,
                          gidx, s_n)
            self._apply_deferred_mirror_ops()
            return
        c = self.capacity
        mc, kcap, mg, mx = rec["caps"]
        s_local = self.s_max // self.n_dev
        chunk_base = s_local * c * self.W // _LANES  # chunks per chip
        (chg, g_vals, g_nv, g_lane, g_csel) = rec["scratch"]
        (rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
         exc_new) = rec["streams"]
        faults.check("aoi.fetch")  # stallable: a delayed host sync
        t0 = time.perf_counter()
        _tf = _T.t()
        poisoned = False
        if rec.get("all_unsub"):
            scal_h = np.zeros((self.n_dev, 5), np.int64)
        else:
            scal_h = faults.filter("aoi.scalars",
                                   np.asarray(rec["scalars"]))  # [n_dev, 5]
            nw = s_local * c * self.W  # words per chip
            if not ((scal_h >= 0).all()
                    and (scal_h[:, 0] <= chunk_base).all()
                    and (scal_h[:, 1] <= _LANES).all()
                    and (scal_h[:, 2] <= chunk_base).all()
                    and (scal_h[:, 3] <= nw).all()
                    and (scal_h[:, 4] <= nw).all()):
                # garbage control scalars: distrust the encoded streams
                # wholesale and recover every chip from its raw diff grid
                # (without growing any caps off corrupted values)
                from ..utils import gwlog

                self.stats["poisoned"] += 1
                gwlog.logger("gw.aoi").warning(
                    "mesh AOI control scalars failed validation (%r); "
                    "recovering the tick from the raw diff grids",
                    scal_h.tolist())
                poisoned = True
        self.perf["fetch_s"] += time.perf_counter() - t0
        _T.lap("aoi.fetch", _tf)
        pf = rec["prefetch"]
        all_c, all_e, all_g = [], [], []
        grew = False
        peak = [0, 0, 0]  # per-chip maxima of (nd, n_esc, exc_n) this tick
        peak_mcc = 0
        for d in range(self.n_dev):
            if poisoned:
                t0 = time.perf_counter()
                _tf = _T.t()
                lo = d * s_local
                chg_h = np.asarray(chg[lo:lo + s_local]).reshape(-1)
                gidx = np.nonzero(chg_h)[0]
                chg_vals = chg_h[gidx]
                if self._defer and self._mirror is not None:
                    # prev was donated to the NEXT dispatch already; the
                    # pre-XOR mirror still holds this tick's old words, so
                    # new = old ^ chg reconstructs the enter/leave split
                    base = self._mirror[lo:lo + s_local].reshape(-1)[gidx]
                    ent_vals = chg_vals & (base ^ chg_vals)
                else:
                    new_h = np.asarray(
                        self.prev[lo:lo + s_local]).reshape(-1)
                    ent_vals = chg_vals & new_h[gidx]
                self.perf["fetch_s"] += time.perf_counter() - t0
                _T.lap("aoi.fetch", _tf)
                all_c.append(chg_vals)
                all_e.append(ent_vals)
                all_g.append(np.asarray(gidx, np.int64)
                             + d * chunk_base * _LANES)
                continue
            nd, mcc, base_row, n_esc, exc_n = (int(v) for v in scal_h[d])
            if nd == 0 and exc_n == 0:
                continue
            t0 = time.perf_counter()
            _tf = _T.t()
            if nd > mc or mcc > kcap:
                # this chip's stream is incomplete.  self.prev still
                # holds this tick's NEW words -- flush() harvests an
                # overflowing tick BEFORE the next dispatch donates prev
                # (see the scalar peek there), so the read is safe.
                # Recover it from the raw diff grid and grow the caps for
                # the next flush.
                lo = d * s_local
                self._max_chunks = max(self._max_chunks, 2 * nd)
                self._kcap = min(max(self._kcap, 2 * mcc), _LANES)
                self.stats["decode_overflow"] += 1
                grew = True
                chg_h = np.asarray(chg[lo:lo + s_local]).reshape(-1)
                new_h = np.asarray(self.prev[lo:lo + s_local]).reshape(-1)
                gidx = np.nonzero(chg_h)[0]
                chg_vals = chg_h[gidx]
                ent_vals = chg_vals & new_h[gidx]
                self.perf["fetch_s"] += time.perf_counter() - t0
                _T.lap("aoi.fetch", _tf)
            elif n_esc > mg or exc_n > mx:
                # encode overflow: rebuild from the kept chunk grids (the
                # compact recovery source, bounded by mc rows) and grow
                # the encode caps for the next flush
                self._max_gaps = max(mg, 2 * n_esc)
                self._max_exc = max(mx, 2 * exc_n)
                self.stats["decode_overflow"] += 1
                grew = True
                lo = d * mc
                vh = np.asarray(g_vals[lo:lo + mc])
                nh = np.asarray(g_nv[lo:lo + mc])
                lh = np.asarray(g_lane[lo:lo + mc])
                ch = np.asarray(g_csel[lo:lo + mc])
                valid = lh >= 0
                chg_vals = vh[valid]
                ent_vals = chg_vals & nh[valid]
                gidx = (ch[:, None].astype(np.int64) * _LANES + lh)[valid]
                self.perf["fetch_s"] += time.perf_counter() - t0
                _T.lap("aoi.fetch", _tf)
            else:
                if pf is not None and pf[0] >= nd and pf[1] >= n_esc \
                        and pf[2] >= exc_n:
                    hb = [np.asarray(a) for a in pf[3][d]]
                else:
                    nds = max(nd, 1)
                    hb = [np.asarray(a) for a in (
                        rowb[d * mc:d * mc + nds],
                        bitpos[d * mc:d * mc + nds],
                        woff[d * mc:d * mc + nds],
                        esc_rows[d * mg:d * mg + max(n_esc, 1)],
                        exc_gidx[d * mx:d * mx + max(exc_n, 1)],
                        exc_chg[d * mx:d * mx + max(exc_n, 1)],
                        exc_new[d * mx:d * mx + max(exc_n, 1)])]
                self.perf["fetch_s"] += time.perf_counter() - t0
                _T.lap("aoi.fetch", _tf)
                t0 = time.perf_counter()
                _td = _T.t()
                chg_vals, ent_vals, gidx = EV.decode_row_stream(
                    hb[0], hb[1], hb[2].astype(np.uint16), base_row, nd,
                    _LANES, hb[3], hb[4], hb[5], hb[6])
                self.perf["decode_s"] += time.perf_counter() - t0
                _T.lap("aoi.diff", _td)
            peak = [max(peak[0], nd), max(peak[1], n_esc),
                    max(peak[2], exc_n)]
            peak_mcc = max(peak_mcc, mcc)
            # chip-local flat word index -> global
            all_c.append(chg_vals)
            all_e.append(ent_vals)
            all_g.append(np.asarray(gidx, np.int64) + d * chunk_base * _LANES)
        if grew:
            self._step_cache.clear()  # static caps changed
            self._scratch.clear()
            self._caps.reset_after_growth()
        elif not poisoned:  # poisoned peaks are zeros, not observations
            shrink = self._caps.observe(peak[0], peak_mcc,
                                        self._max_chunks, self._kcap)
            if shrink is not None:
                self._max_chunks, self._kcap = shrink
                self._step_cache.clear()
                self._scratch.clear()
        # refit the next dispatch's optimistic prefetch to THIS tick's
        # per-chip peaks (fresh, not a running max: prefetch sizes must
        # decay after a storm or every later tick ships storm-sized slices)
        self._pred = (
            max(256, min(mc, -(-(peak[0] * 5 // 4) // 128) * 128)),
            max(64, -(-(peak[1] + 1) * 3 // 2 // 64) * 64),
            max(256, -(-(peak[2] + 1) * 5 // 4 // 256) * 256),
        )
        t0 = time.perf_counter()
        _td = _T.t()
        epochs = rec["epochs"]
        live = np.fromiter(
            (self._slot_epoch.get(s, 0) == epochs.get(s, 0)
             for s in range(self.s_max)), bool, self.s_max)
        if self._mirror is not None and all_g:
            gx = np.concatenate(all_g)
            if len(gx):
                cv = np.concatenate(all_c)
                # epoch guard: a slot released since dispatch had its mirror
                # reset at re-acquire; the dead stream must not XOR back in
                keep = live[gx // (c * self.W)]
                if self._mirror_stale:
                    # a re-subscribed slot's stream must not XOR onto its
                    # stale mirror base; the row refreshes from device on
                    # the next peek instead
                    stale = np.zeros(self.s_max, bool)
                    stale[list(self._mirror_stale)] = True
                    keep &= ~stale[gx // (c * self.W)]
                if not keep.all():
                    gx, cv = gx[keep], cv[keep]
                self._mirror.reshape(-1)[gx] ^= cv
        # clears issued after this tick's dispatch apply now, AFTER its
        # stream (see _apply_deferred_mirror_ops)
        self._apply_deferred_mirror_ops()
        self.perf["decode_s"] += time.perf_counter() - t0
        _T.lap("aoi.diff", _td)
        t0 = time.perf_counter()
        _te = _T.t()
        empty = np.empty((0, 2), np.int32)
        if all_c:
            # fan-out through the bucket's emit path (C++ bit expansion
            # when emit="native"; bit-exact either way)
            pe, pl = _emit_expand(
                self, np.concatenate(all_c), np.concatenate(all_e),
                np.concatenate(all_g), self.s_max)
        else:
            pe = pl = np.empty((0, 3), np.int32)
        ent_rows = _split_rows(pe)
        lv_rows = _split_rows(pl)
        for slot in rec["slots"]:
            if not live[slot]:
                continue  # released since dispatch: events of a dead space
            e = ent_rows.get(slot, empty)
            l = lv_rows.get(slot, empty)
            pend = self._events.get(slot)
            if pend is not None:
                # mid-dispatch harvest with undelivered prior events:
                # append, never clobber (see _TPUBucket._harvest)
                e = np.concatenate([pend[0], e])
                l = np.concatenate([pend[1], l])
            self._events[slot] = (e, l)
        # the harvested scratch returns to the pool for reuse -- but only
        # while its shape key is still current: after a grow/shrink cleared
        # the pool, a stale-keyed set can never match _get_scratch again and
        # would pin a full [S,C,W] chg buffer in device memory indefinitely
        if rec["key"] == (self.s_max, self._max_chunks, self._kcap):
            self._scratch.setdefault(rec["key"], rec["scratch"])
        self.perf["emit_s"] += time.perf_counter() - t0
        _T.lap("aoi.fanout", _te)
