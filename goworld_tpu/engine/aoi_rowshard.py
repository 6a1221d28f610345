"""Observer-row-sharded AOI for ONE oversized space (the zipf100k answer).

The mesh bucket (engine/aoi_mesh) shards SPACES over chips -- a single space
is chip-local by design, so a space too hot for one chip's real-time budget
(BASELINE's zipf100k: 100k entities, ONE space, 161-165 ms device tick vs the
100 ms cadence in round 4) had no scaling story.  This bucket shards WITHIN
the space: chip d owns interest rows [d*C/n, (d+1)*C/n) -- its block of
observers -- evaluated against ALL C candidates.  Work and interest-state
memory split n_dev ways; candidates (x, z, active) are replicated at H2D
(~1 MB/tick at C=131072), and every chip's diff extraction is chip-local, so
the tick uses ZERO inter-chip collectives, exactly like the slot-sharded
bucket.

The reference's answer to an oversized space is capacity-capping and
splitting (/root/reference/examples/unity_demo/SpaceService.go:91-109) plus a
pluggable-AOI seam meant to scale (/root/reference/engine/entity/Space.go:106;
see ROADMAP.md for the scaling north-star); this supersedes both: one logical
space, n chips, bit-exact events.

Design notes:
  * One bucket instance per space (``exclusive``): the engine keys it
    uniquely and drops it at release -- at C=131072 the packed state is
    2 GB mesh-wide; slot reuse machinery would just pin it.
  * The kernel runs in RECTANGULAR mode (ops/aoi_pallas ``cols=``/
    ``row_ids=``): each chip's [C/n] observer block against the replicated
    [C] candidate arrays, prev block [C/n, W].  Global observer ids ride
    ``row_ids`` so self-exclusion holds across blocks.
  * Events: per-chip chunk extraction + wire encode, identical machinery to
    the mesh bucket; a chip's global flat word index is just offset by
    d * (C/n) * W, and expansion runs with n_spaces=1.
  * Flush is synchronous (dispatch + harvest in one call): events arrive
    same-tick like the CPU oracle.  ``pipeline`` is accepted for engine
    symmetry; stream D2H still overlaps via async copies inside the flush.
  * No host mirror: at this size a [C, W] mirror is the whole interest
    state.  ``derive_row``/``derive_col`` fetch one observer's row [W]
    (16 KB) or one column's word across rows [C] on demand --
    Space.derive_interests/derive_observers prefer them when present.
  * Subscription (set_subscribed False) masks the whole space's change
    stream on device: an all-plain 100k NPC space pays kernel time only,
    no fetch, no decode.
"""

from __future__ import annotations

import time

import numpy as np

from .. import faults
from ..telemetry import trace as _T
from ..ops import aoi_predicate as P
from ..ops import dispatch_count as DC
from ..ops import events as EV
from ..ops import aoi_emit as AE
from .aoi import (_Bucket, _CapDecay, _build_snapshot, _device_fault,
                  _emit_expand, _kernelish_fault, _packed_predicate,
                  _paged_absorb_chip, _unpack_positions)

_LANES = 128


class _RowShardTPUBucket(_Bucket):
    """ONE space, interest rows sharded over the mesh's 'space' axis."""

    exclusive = True  # engine: one bucket per space, dropped at release

    def __init__(self, capacity: int, mesh, pipeline: bool = False,
                 delta_staging: bool = True, emit: str = "vector",
                 paged: bool = False, cross_tick: bool = False,
                 fused: bool = False):
        super().__init__(capacity)
        # fused steady tick (ops/aoi_fused contract, per chip): both
        # packet scatters (sharded block + replicated candidates) fold
        # INTO the rectangular step, so a steady tick is ONE program
        # launch (vs scatter + step); see _dispatch_fused
        self.fused = bool(fused)
        import jax  # noqa: F401  (fail fast if jax is unavailable)

        # paged overflow absorber (docs/perf.md, paged storage): a chip
        # whose encoded stream overflows its caps is recovered through
        # the device-side page allocator (used pages + spilled bins D2H)
        # instead of growing the caps (a recompile) and fetching its full
        # diff grid; counted in page_spills, never decode_overflow
        self.paged = bool(paged)
        self._n_pages = 0
        self._page_free = None
        self._pages = None  # _PageDecay, lazily sized at first absorb

        # emit path for the harvested word streams (docs/perf.md emit
        # paths; see _MeshTPUBucket -- "vector" and "host" coincide here)
        self._emit = emit
        self._emit_requested = emit

        self.mesh = mesh
        self.n_dev = mesh.n_devices
        if capacity % (self.n_dev * 128):
            raise ValueError(
                f"row-sharded capacity {capacity} must be a multiple of "
                f"n_dev*128 = {self.n_dev * 128}")
        self.c_local = capacity // self.n_dev
        self.pipeline = pipeline  # accepted for symmetry; flush is sync
        self.cross_tick = bool(cross_tick)  # likewise: never deferred here
        self.prev = None  # [C, W] uint32, rows sharded over the mesh
        # persistent staged inputs [C]; unstaged flushes step nothing
        self._hx = np.zeros(capacity, np.float32)
        self._hz = np.zeros(capacity, np.float32)
        self._hr = np.zeros(capacity, np.float32)
        self._hact = np.zeros(capacity, bool)
        self._pending_clear: list[int] = []
        self._subscribed = True
        # per-chip extraction caps (static shapes, grow on overflow, decay
        # via the shared window)
        self._max_chunks = 4096
        self._kcap = 8
        self._max_gaps = 2048
        self._max_exc = 16384
        self._caps = _CapDecay(nd_floor=4096)
        self._step_cache: dict[tuple, object] = {}
        self._maint_cache: dict[tuple, object] = {}
        self._scratch: dict[tuple, tuple] = {}
        self._h2d_cache: dict[str, tuple] = {}
        # delta staging: persistent device copies of x/z -- one SHARDED
        # block pair (observer rows) and one REPLICATED candidate pair --
        # bitwise-identical to the _hx/_hz shadows.  Steady flushes ship
        # one replicated (cols, x, z) packet; each chip scatters its own
        # column block plus its replicated copy (no collectives).
        self.delta_staging = delta_staging
        self._dxs = self._dzs = None  # sharded [C]
        self._dxr = self._dzr = None  # replicated [C]
        self._xz_stale = True
        self._delta_max_frac = 0.25
        # fault tolerance (docs/robustness.md): NO standing mirror at this
        # size -- the durable copies are the input shadows (prev equals
        # their predicate except between set_prev and the next step, which
        # _seed_prev covers under an active plan) plus _host_prev, the
        # recovered state carried host-side while the device is down
        self._ft = faults.active()
        # chip-loss failover: True after a DeviceLost recovery -- the
        # engine rebuilds the space onto a fresh bucket at the end of the
        # current flush (docs/robustness.md)
        self._evacuating = False
        self._calc_level = 0  # 0 = platform default, 1 = dense, 2 = oracle
        self._fault_phase = "stage"
        self._seed_prev: np.ndarray | None = None
        self._host_prev: np.ndarray | None = None
        self._cur_old: tuple | None = None
        self._tick_inflight = False  # restage done, events not yet harvested
        # split-phase flush (docs/perf.md): dispatch() parks what harvest()
        # must do (see _TPUBucket._sched for the grammar); this bucket is
        # not pipelined, so the parked record is always the CURRENT tick's
        self._sched: tuple | None = None
        self.stats = {"h2d_bytes": 0, "delta_flushes": 0, "full_flushes": 0,
                      "rebuilds": 0, "fallbacks": 0, "host_ticks": 0,
                      "poisoned": 0, "calc_level": 0, "decode_overflow": 0,
                      "page_spills": 0, "page_occupancy": 0.0,
                      "fused_dispatches": 0, "fused_demotions": 0,
                      "emit_path": AE.EMIT_LEVEL[emit]}
        self._pred = (512, 64, 256)
        self.full_roundtrips = 0
        self.perf = {"stage_s": 0.0, "fetch_s": 0.0, "decode_s": 0.0,
                     "emit_s": 0.0}

    @property
    def _steady(self) -> bool:
        return self._caps.steady

    # -- slot management (exactly one) --------------------------------------
    def acquire_slot(self) -> int:
        if self.n_slots:
            raise RuntimeError("row-sharded bucket holds exactly one space")
        return super().acquire_slot()

    def _grow_to(self, n_slots: int) -> None:
        pass  # single slot; device state allocates lazily at first flush

    def _reset_slot(self, slot: int) -> None:
        pass  # fresh bucket per space: nothing to reset

    def set_subscribed(self, slot: int, flag: bool) -> None:
        if self._subscribed != bool(flag):
            self._xz_stale = True  # sub change: full-restage fallback
        self._subscribed = bool(flag)

    # -- device programs ----------------------------------------------------
    def _replicated(self, arr):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as PS

        return jax.device_put(arr, NamedSharding(self.mesh.mesh, PS()))

    def _h2d(self, role: str, arr: np.ndarray, replicated: bool = False):
        cached = self._h2d_cache.get(role)
        if cached is not None and cached[0].shape == arr.shape and \
                np.array_equal(cached[0], arr):
            return cached[1]
        faults.check("aoi.h2d")
        dev = self._replicated(arr) if replicated else self.mesh.device_put(arr)
        self._h2d_cache[role] = (arr.copy(), dev)
        self.stats["h2d_bytes"] += arr.nbytes
        return dev

    def _delta_fn(self, npk: int):
        """Jitted donated per-shard scatter of one replicated (cols, x, z)
        packet into BOTH device x/z copies: the sharded observer blocks
        (column indices localized per chip, out-of-block entries dropped)
        and the replicated candidate copies (every chip applies the whole
        packet) -- no cross-chip collectives either way."""
        key = ("delta", npk)
        fn = self._maint_cache.get(key)
        if fn is None:
            import jax
            from jax.sharding import PartitionSpec as PS

            from ..ops.aoi_stage import delta_scatter_1d

            cl = self.c_local
            axis = self.mesh.axis

            def _local(xs, zs, xr, zr, cols, xv, zv):
                lo = jax.lax.axis_index(axis) * cl
                xs, zs = delta_scatter_1d(xs, zs, cols, xv, zv,
                                          col_lo=lo, n_cols=cl)
                xr, zr = delta_scatter_1d(xr, zr, cols, xv, zv)
                return xs, zs, xr, zr

            spec, rep = PS(axis), PS()
            local = jax.shard_map(_local, mesh=self.mesh.mesh,
                              in_specs=(spec, spec, rep, rep, rep, rep, rep),
                              out_specs=(spec, spec, rep, rep),
                              check_vma=False)
            self._maint_cache[key] = fn = jax.jit(
                local, donate_argnums=(0, 1, 2, 3))
        return fn

    def _stage_xz(self, old_x, old_z, old_r, old_act) -> None:
        """Bring the device-resident x/z copies (sharded + replicated) up
        to date with the host shadow: sparse packet on the steady path,
        full re-upload on the fallbacks (clear_entity, r/act/sub change,
        changed fraction above _delta_max_frac, or delta staging
        disabled).  Bit-pattern diff: see _TPUBucket._stage_inputs."""
        from ..ops import aoi_stage as AS

        diff = (self._hx.view(np.uint32) != old_x.view(np.uint32)) \
            | (self._hz.view(np.uint32) != old_z.view(np.uint32))
        n_changed = np.count_nonzero(diff)  # host numpy scalar
        if not (np.array_equal(self._hr, old_r)
                and np.array_equal(self._hact, old_act)):
            self._xz_stale = True  # r/act change: full-restage fallback
        if (self.delta_staging and not self._xz_stale
                and self._dxs is not None
                and n_changed <= self._delta_max_frac * diff.size):
            if n_changed:
                faults.check("aoi.delta")
                cols = np.nonzero(diff)[0]
                _, cols, xv, zv = AS.pad_packet(cols, cols, self._hx[cols],
                                                self._hz[cols],
                                                page_granular=self.paged)
                DC.record()
                self._dxs, self._dzs, self._dxr, self._dzr = \
                    self._delta_fn(len(cols))(
                        self._dxs, self._dzs, self._dxr, self._dzr,
                        cols, xv, zv)
                self.stats["h2d_bytes"] += \
                    cols.nbytes + xv.nbytes + zv.nbytes
            self.stats["delta_flushes"] += 1
            return
        faults.check("aoi.h2d")
        put = self.mesh.device_put
        self._dxs, self._dzs = put(self._hx), put(self._hz)
        self._dxr = self._replicated(self._hx)
        self._dzr = self._replicated(self._hz)
        self.stats["h2d_bytes"] += 2 * (self._hx.nbytes + self._hz.nbytes)
        self._xz_stale = False
        self.stats["full_flushes"] += 1

    def _ensure_prev(self):
        if self.prev is None:
            faults.check("aoi.grow")  # the lazy state allocation seam
            src = (self._host_prev if self._host_prev is not None
                   else np.zeros((self.capacity, self.W), np.uint32))
            self.prev = self.mesh.device_put(np.ascontiguousarray(src))
            if self._host_prev is not None:  # rebuild after device loss
                self.stats["h2d_bytes"] += src.nbytes
                self._host_prev = None

    def _sharded_step(self, npk: int | None = None):
        """Jitted shard_map rectangular step for the current static caps.

        ``npk`` (fused mode, ops/aoi_fused contract): fold the delta
        scatter of one replicated (cols, xv, zv) packet of that padded
        length into the program -- each chip scatters its own column
        block plus its replicated candidate copy, then steps from the
        freshly scattered x/z -- so a steady tick is ONE launch instead
        of scatter + step.  The four device x/z copies ride as donated
        inputs and come back as extra outputs."""
        key = (self._max_chunks, self._kcap, self._max_gaps, self._max_exc,
               self._calc_level, npk)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        if len(self._step_cache) > 4:
            self._step_cache.clear()
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as PS

        from ..ops.aoi_dense import aoi_step_chg
        from ..ops.aoi_stage import delta_scatter_1d

        # calculator fallback chain level 1: force the fused dense path
        platform = "cpu" if self._calc_level >= 1 else self.mesh.platform
        mc, kcap = self._max_chunks, self._kcap
        mg, mx = self._max_gaps, self._max_exc
        cl = self.c_local
        axis = self.mesh.axis
        fused = npk is not None

        def _body(prev_blk, chg_buf, vals_buf, nv_buf, lane_buf, csel_buf,
                  xs, zs, rs, acts, x_all, z_all, act_all, sub):
            lo = jax.lax.axis_index(axis) * cl
            rid = (lo + jnp.arange(cl, dtype=jnp.int32))[None]
            # platform routing lives in ops/aoi_dense.aoi_step_chg
            new, chg = aoi_step_chg(
                xs[None], zs[None], rs[None], acts[None], prev_blk[None],
                cols=(x_all[None], z_all[None], act_all[None]),
                row_ids=rid, platform=platform)
            new, chg = new[0], chg[0]
            # subscription mask (see engine/aoi._fused_bucket_step): ``new``
            # stays unmasked -- prev is authoritative
            chg = jnp.where(sub, chg, jnp.uint32(0))
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

        spec = PS(self.mesh.axis)
        rep = PS()
        if fused:
            def _local(prev_blk, chg_buf, vals_buf, nv_buf, lane_buf,
                       csel_buf, xs, zs, rs, acts, xr, zr, act_all, sub,
                       cols, xv, zv):
                lo = jax.lax.axis_index(axis) * cl
                xs, zs = delta_scatter_1d(xs, zs, cols, xv, zv,
                                          col_lo=lo, n_cols=cl)
                xr, zr = delta_scatter_1d(xr, zr, cols, xv, zv)
                out = _body(prev_blk, chg_buf, vals_buf, nv_buf, lane_buf,
                            csel_buf, xs, zs, rs, acts, xr, zr, act_all,
                            sub)
                return out + (xs, zs, xr, zr)

            local = jax.shard_map(
                _local,
                mesh=self.mesh.mesh,
                in_specs=(spec,) * 10 + (rep,) * 7,
                out_specs=(spec,) * 16 + (rep, rep),
                check_vma=False,
            )
            fn = jax.jit(local,
                         donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7, 10, 11))
        else:
            local = jax.shard_map(
                _body,
                mesh=self.mesh.mesh,
                in_specs=(spec,) * 10 + (rep, rep, rep, rep),
                out_specs=(spec,) * 14,
                check_vma=False,
            )
            fn = jax.jit(local, donate_argnums=(0, 1, 2, 3, 4, 5))
        self._step_cache[key] = fn
        return fn

    def _maintenance_fn(self):
        key = True
        fn = self._maint_cache.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as PS

        cl = self.c_local
        axis = self.mesh.axis
        W = self.W

        def _local(prev_blk, rows, col_w, col_m):
            # row clears: global row -> local.  Out-of-block rows must map
            # to an index >= cl (mode="drop"); a bare ``rows - lo`` would
            # go NEGATIVE for earlier chips' rows and .at[] wraps negative
            # indices numpy-style BEFORE the mode applies -- clearing the
            # wrong row on every other chip.
            lo = jax.lax.axis_index(axis) * cl
            in_blk = (rows >= lo) & (rows < lo + cl)
            lr = jnp.where(in_blk, rows - lo, cl)
            prev_blk = prev_blk.at[lr].set(jnp.uint32(0), mode="drop")
            # column clears: AND the mask into word col_w of EVERY row
            # (col_w == W pads are dropped)
            cur = prev_blk.at[:, col_w].get(mode="fill", fill_value=0)
            prev_blk = prev_blk.at[:, col_w].set(cur & col_m, mode="drop")
            return prev_blk

        spec = PS(self.mesh.axis)
        rep = PS()
        local = jax.shard_map(
            _local, mesh=self.mesh.mesh,
            in_specs=(spec, rep, rep, rep), out_specs=spec,
            check_vma=False)
        fn = jax.jit(local, donate_argnums=(0,))
        self._maint_cache[key] = fn
        return fn

    # -- maintenance --------------------------------------------------------
    def clear_entity(self, slot: int, entity_slot: int) -> None:
        self._pending_clear.append(entity_slot)
        # keep the cached inputs consistent (departed entity inactive) so an
        # unstaged re-step cannot re-derive the cleared pairs
        self._hx[entity_slot] = 0.0
        self._hz[entity_slot] = 0.0
        self._hr[entity_slot] = 0.0
        self._hact[entity_slot] = False
        self._xz_stale = True  # device x/z diverged from the shadow
        self._h2d_cache.pop("act", None)
        self._h2d_cache.pop("r", None)

    def _apply_maintenance(self) -> None:
        if not self._pending_clear or self.prev is None:
            if self._pending_clear and self._host_prev is not None:
                # device down after a recovery: the maintenance scatter
                # lands on the host copy _ensure_prev will re-upload
                for ent in set(self._pending_clear):
                    self._host_prev[ent] = 0
                    w, b = P.word_bit_for_column(ent, self.capacity)
                    self._host_prev[:, w] &= np.uint32(
                        ~(np.uint32(1) << np.uint32(b)) & 0xFFFFFFFF)
            self._pending_clear.clear()
            return
        import jax.numpy as jnp

        ents = sorted(set(self._pending_clear))
        self._pending_clear.clear()
        col_mask: dict[int, int] = {}
        for e in ents:
            w, b = P.word_bit_for_column(e, self.capacity)
            col_mask[w] = col_mask.get(w, 0xFFFFFFFF) & (~(1 << b)
                                                         & 0xFFFFFFFF)
        cols = sorted(col_mask.items())

        def pad(seq, fill):
            if not seq:
                seq = [fill]
            n = 1
            while n < len(seq):
                n *= 2
            return seq + [fill] * (n - len(seq))

        rows = pad(ents, self.capacity)        # OOB row -> dropped
        cols = pad(cols, (self.W, 0xFFFFFFFF))  # OOB word -> dropped
        DC.record()
        self.prev = self._maintenance_fn()(
            self.prev,
            jnp.asarray(rows, jnp.int32),
            jnp.asarray([w for w, _ in cols], jnp.int32),
            jnp.asarray([m for _, m in cols], jnp.uint32),
        )

    # -- the flush ----------------------------------------------------------
    def _get_scratch(self):
        key = (self._max_chunks, self._kcap)
        sc = self._scratch.pop(key, None)
        if sc is not None:
            return key, sc
        while len(self._scratch) >= 2:
            self._scratch.pop(next(iter(self._scratch)))
        put = self.mesh.device_put
        mc, kcap = self._max_chunks, self._kcap
        n = self.n_dev * mc
        sc = (
            put(np.zeros((self.capacity, self.W), np.uint32)),
            put(np.zeros((n, kcap), np.uint32)),
            put(np.zeros((n, kcap), np.uint32)),
            put(np.full((n, kcap), -1, np.int32)),
            put(np.zeros(n, np.int32)),
        )
        return key, sc

    def flush(self) -> None:
        """Monolithic flush = dispatch immediately followed by harvest (the
        forced-sequential baseline; see _TPUBucket.flush).  Events always
        arrive same-tick -- this bucket is never pipelined across ticks."""
        self.dispatch()
        self.harvest()

    def dispatch(self) -> None:
        """Phase 1 of the split flush: maintenance + restage + H2D enqueue
        + rectangular-kernel enqueue, never blocking on device values
        (gwlint flush-phase rule); parks the harvest work in ``_sched``."""
        if self._sched is not None:
            self.harvest()  # gwlint: allow[flush-phase] -- re-entrant flush drains the prior dispatch first
        if self._calc_level >= 2:
            # calculator fallback chain bottom: host-oracle mode; the host
            # compute defers to harvest so it overlaps other buckets
            self._dispatch_oracle()
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
        """Phase 2 of the split flush: the blocking per-chip fetch + decode
        of what :meth:`dispatch` enqueued.  ``_tick_inflight`` (and a live
        set_prev seed) stay armed until the events actually land, so a
        fault surfacing at the fetch recovers bit-exactly from the pre-tick
        durable state (_cur_old / _seed_prev)."""
        sched, self._sched = self._sched, None
        if sched is None:
            return
        if sched[0] == "oracle":
            self._host_tick(sched[1])
            return
        self._fault_phase = "harvest"
        try:
            self._harvest(sched[1])
        except Exception as e:
            if not _device_fault(e):
                raise
            self._recover(e)
            return
        # the tick delivered: prev == predicate(shadows) again, so a
        # set_prev seed is no longer the recovery base
        self._seed_prev = None
        self._tick_inflight = False

    def _restage_shadows(self) -> None:
        """Pop the staged tick into the persistent shadows, keeping the
        pre-tick values in _cur_old (the _stage_xz diff base, and the
        durable old state for fault recovery)."""
        (sx, sz, sr, sa) = self._staged.pop(0)
        n = len(sx)
        self._cur_old = (self._hx.copy(), self._hz.copy(),
                         self._hr.copy(), self._hact.copy())
        self._hx[:n] = sx
        self._hz[:n] = sz
        self._hr[:n] = sr
        self._hact[:] = False
        self._hact[:n] = sa
        self._staged.clear()

    def _dispatch_device(self) -> None:
        self._fault_phase = "stage"
        # device health probe: kind ``reset`` = the chip is LOST
        # (faults.DeviceLost; dispatch()'s handler marks the bucket
        # evacuating after the standard host-side recovery)
        faults.check("aoi.device")
        self._apply_maintenance()
        if not self._staged:
            return
        t0 = time.perf_counter()
        _ts = _T.t()
        self._restage_shadows()
        self._tick_inflight = True  # a restaged tick awaits its events
        old_x, old_z, old_r, old_act = self._cur_old
        self._ensure_prev()
        key, scratch = self._get_scratch()
        if self.fused and self._dispatch_fused(key, scratch, old_x, old_z,
                                               old_r, old_act, t0, _ts):
            return
        self._stage_xz(old_x, old_z, old_r, old_act)
        # np.array (not asarray): a host python bool, no device sync here
        sub = self._h2d("sub", np.array(self._subscribed), replicated=True)
        _T.lap("aoi.stage", _ts)
        _tk = _T.t()
        self._fault_phase = "kernel"
        faults.check("aoi.kernel")
        DC.record()
        out = self._sharded_step()(
            self.prev, *scratch,
            self._dxs, self._dzs,
            self._h2d("r", self._hr), self._h2d("act", self._hact),
            self._dxr, self._dzr,
            self._h2d("act_all", self._hact, replicated=True),
            sub)
        (new, chg, g_vals, g_nv, g_lane, g_csel, rowb, bitpos, woff,
         esc_rows, exc_gidx, exc_chg, exc_new, scalars) = out
        _T.lap("aoi.kernel", _tk)
        self.prev = new
        scalars.copy_to_host_async()
        # optimistic async prefetch of the streams at recent sizes -- the
        # copies ride the wire while jax finishes the dispatch; exact slices
        # refetch on a misfit
        pf = None
        if self._subscribed:
            mc = self._max_chunks
            ndp = min(mc, self._pred[0])
            escp = min(self._max_gaps, self._pred[1])
            excp = min(self._max_exc, self._pred[2])
            slices = []
            for d in range(self.n_dev):
                sl = (rowb[d * mc:d * mc + ndp],
                      bitpos[d * mc:d * mc + ndp],
                      woff[d * mc:d * mc + ndp],
                      esc_rows[d * self._max_gaps:
                               d * self._max_gaps + escp],
                      exc_gidx[d * self._max_exc:d * self._max_exc + excp],
                      exc_chg[d * self._max_exc:d * self._max_exc + excp],
                      exc_new[d * self._max_exc:d * self._max_exc + excp])
                for a in sl:
                    a.copy_to_host_async()
                slices.append(sl)
            pf = (ndp, escp, excp, slices)
        self.perf["stage_s"] += time.perf_counter() - t0
        # everything above is enqueue-only; the blocking fetch + decode
        # happen in harvest() (split-phase flush) -- _tick_inflight and any
        # set_prev seed stay armed until the events actually land
        self._sched = ("rec", {
            "caps": (self._max_chunks, self._kcap, self._max_gaps,
                     self._max_exc),
            "key": key,
            "scratch": (chg, g_vals, g_nv, g_lane, g_csel),
            "streams": (rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
                        exc_new),
            "scalars": scalars, "prefetch": pf})

    def _dispatch_fused(self, key, scratch, old_x, old_z, old_r, old_act,
                        t0, _ts) -> bool:
        """One-launch steady tick (ops/aoi_fused contract, per chip): the
        packet scatter of all four device x/z copies folds into the
        rectangular step program, so a steady tick is one enqueue per
        chip instead of scatter + step.  Returns False -- silently on an
        ineligible tick (full restage pending, r/act change, oversized
        delta), counted in ``fused_demotions`` on a seam demotion -- and
        _dispatch_device continues down the unfused path in the same
        call, bit-exact."""
        if (not self.delta_staging or self._xz_stale
                or self._dxs is None):
            return False
        if not (np.array_equal(self._hr, old_r)
                and np.array_equal(self._hact, old_act)):
            return False  # r/act change: unfused full-restage fallback
        diff = (self._hx.view(np.uint32) != old_x.view(np.uint32)) \
            | (self._hz.view(np.uint32) != old_z.view(np.uint32))
        n_changed = np.count_nonzero(diff)  # host numpy scalar
        if n_changed > self._delta_max_frac * diff.size:
            return False
        # the unfused path's staging + kernel seams, checked up front --
        # BEFORE any device mutation -- so a seam firing mid-"program"
        # demotes cleanly: the unfused retry re-runs from the exact same
        # pre-tick device state
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
            cols = np.nonzero(diff)[0]
            _, cols, xv, zv = AS.pad_packet(cols, cols, self._hx[cols],
                                            self._hz[cols],
                                            page_granular=self.paged)
            self.stats["h2d_bytes"] += cols.nbytes + xv.nbytes + zv.nbytes
        else:
            # zero movers: a shape-(0,) packet keeps the scatter an
            # in-program no-op under its own (bounded) compile key
            cols = np.zeros(0, np.int32)
            xv = zv = np.zeros(0, np.float32)
        self.stats["delta_flushes"] += 1
        sub = self._h2d("sub", np.array(self._subscribed), replicated=True)
        _T.lap("aoi.stage", _ts)
        _tk = _T.t()
        DC.record()
        out = self._sharded_step(len(cols))(
            self.prev, *scratch,
            self._dxs, self._dzs,
            self._h2d("r", self._hr), self._h2d("act", self._hact),
            self._dxr, self._dzr,
            self._h2d("act_all", self._hact, replicated=True),
            sub, cols, xv, zv)
        (new, chg, g_vals, g_nv, g_lane, g_csel, rowb, bitpos, woff,
         esc_rows, exc_gidx, exc_chg, exc_new, scalars,
         self._dxs, self._dzs, self._dxr, self._dzr) = out
        _T.lap("aoi.kernel", _tk)
        _T.lap("aoi.fused", _tk)
        self.prev = new
        scalars.copy_to_host_async()
        pf = None
        if self._subscribed:
            mc = self._max_chunks
            ndp = min(mc, self._pred[0])
            escp = min(self._max_gaps, self._pred[1])
            excp = min(self._max_exc, self._pred[2])
            slices = []
            for d in range(self.n_dev):
                sl = (rowb[d * mc:d * mc + ndp],
                      bitpos[d * mc:d * mc + ndp],
                      woff[d * mc:d * mc + ndp],
                      esc_rows[d * self._max_gaps:
                               d * self._max_gaps + escp],
                      exc_gidx[d * self._max_exc:d * self._max_exc + excp],
                      exc_chg[d * self._max_exc:d * self._max_exc + excp],
                      exc_new[d * self._max_exc:d * self._max_exc + excp])
                for a in sl:
                    a.copy_to_host_async()
                slices.append(sl)
            pf = (ndp, escp, excp, slices)
        self.stats["fused_dispatches"] += 1
        self.perf["stage_s"] += time.perf_counter() - t0
        self._sched = ("rec", {
            "caps": (self._max_chunks, self._kcap, self._max_gaps,
                     self._max_exc),
            "key": key,
            "scratch": (chg, g_vals, g_nv, g_lane, g_csel),
            "streams": (rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
                        exc_new),
            "scalars": scalars, "prefetch": pf})
        return True

    def _harvest(self, rec) -> None:  # gwlint: allow[host-sync] -- THE per-tick drain point: harvests kernel outputs once per flush
        c = self.capacity
        cl = self.c_local
        mc, kcap, mg, mx = rec["caps"]
        chunk_base = cl * self.W // _LANES  # chunks per chip
        (chg, g_vals, g_nv, g_lane, g_csel) = rec["scratch"]
        (rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
         exc_new) = rec["streams"]
        faults.check("aoi.fetch")  # stallable: a delayed host sync
        t0 = time.perf_counter()
        _tf = _T.t()
        scal_h = faults.filter("aoi.scalars",
                               np.asarray(rec["scalars"]))  # [n_dev, 5]
        poisoned = False
        nw = cl * self.W  # words per chip
        if not ((scal_h >= 0).all()
                and (scal_h[:, 0] <= chunk_base).all()
                and (scal_h[:, 1] <= _LANES).all()
                and (scal_h[:, 2] <= chunk_base).all()
                and (scal_h[:, 3] <= nw).all()
                and (scal_h[:, 4] <= nw).all()):
            # garbage control scalars: distrust the encoded streams and
            # recover every chip from its raw diff grid (no cap growth off
            # corrupted values).  No other dispatch intervenes between the
            # phases (one bucket per space), so self.prev still holds THIS
            # tick's new words
            from ..utils import gwlog

            self.stats["poisoned"] += 1
            gwlog.logger("gw.aoi").warning(
                "row-shard AOI control scalars failed validation (%r); "
                "recovering the tick from the raw diff grids",
                scal_h.tolist())
            poisoned = True
        self.perf["fetch_s"] += time.perf_counter() - t0
        _T.lap("aoi.fetch", _tf)
        pf = rec["prefetch"]
        all_c, all_e, all_g = [], [], []
        grew = False
        peak = [0, 0, 0]
        peak_mcc = 0
        for d in range(self.n_dev):
            if poisoned:
                t0 = time.perf_counter()
                _tf = _T.t()
                lo = d * cl
                chg_h = np.asarray(chg[lo:lo + cl]).reshape(-1)
                gidx = np.nonzero(chg_h)[0]
                chg_vals = chg_h[gidx]
                new_h = np.asarray(self.prev[lo:lo + cl]).reshape(-1)
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
                # incomplete stream: recover from this chip's raw diff grid
                lo = d * cl
                if self.paged:
                    # paged absorber: compact the kept grids into pages
                    # on device and fetch only the used prefix -- no cap
                    # growth, no recompile, decode_overflow stays 0
                    chg_vals, ent_vals, gidx = _paged_absorb_chip(
                        self, chg[lo:lo + cl], self.prev[lo:lo + cl],
                        self.W)
                    self.perf["fetch_s"] += time.perf_counter() - t0
                    _T.lap("aoi.fetch", _tf)
                else:
                    self._max_chunks = max(self._max_chunks, 2 * nd)
                    self._kcap = min(max(self._kcap, 2 * mcc), _LANES)
                    self.stats["decode_overflow"] += 1
                    grew = True
                    chg_h = np.asarray(chg[lo:lo + cl]).reshape(-1)
                    new_h = np.asarray(self.prev[lo:lo + cl]).reshape(-1)
                    gidx = np.nonzero(chg_h)[0]
                    chg_vals = chg_h[gidx]
                    ent_vals = chg_vals & new_h[gidx]
                    self.perf["fetch_s"] += time.perf_counter() - t0
                    _T.lap("aoi.fetch", _tf)
            elif n_esc > mg or exc_n > mx:
                # encode overflow: rebuild from the kept chunk grids.  In
                # paged mode this is a counted spill (the chunk grids ARE
                # the compact recovery source, bounded by mc rows), with
                # no cap growth so the compile key never churns.
                if self.paged:
                    self.stats["page_spills"] += 1
                else:
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
            all_c.append(chg_vals)
            all_e.append(ent_vals)
            all_g.append(np.asarray(gidx, np.int64) + d * chunk_base * _LANES)
        if grew:
            self._step_cache.clear()
            self._scratch.clear()
            self._caps.reset_after_growth()
        elif not poisoned:  # poisoned peaks are zeros, not observations
            shrink = self._caps.observe(peak[0], peak_mcc,
                                        self._max_chunks, self._kcap)
            if shrink is not None:
                self._max_chunks, self._kcap = shrink
                self._step_cache.clear()
                self._scratch.clear()
        self._pred = (
            max(512, min(mc, -(-(peak[0] * 5 // 4) // 128) * 128)),
            max(64, -(-(peak[1] + 1) * 3 // 2 // 64) * 64),
            max(256, -(-(peak[2] + 1) * 5 // 4 // 256) * 256),
        )
        t0 = time.perf_counter()
        _te = _T.t()
        empty = np.empty((0, 2), np.int32)
        if all_c:
            # fan-out through the bucket's emit path (C++ bit expansion
            # when emit="native"; bit-exact either way)
            pe, pl = _emit_expand(
                self, np.concatenate(all_c), np.concatenate(all_e),
                np.concatenate(all_g), 1)
            e = pe[:, 1:] if len(pe) else empty
            l = pl[:, 1:] if len(pl) else empty
        else:
            e = l = empty
        pend = self._events.get(0)
        if pend is not None:
            e = np.concatenate([pend[0], e])
            l = np.concatenate([pend[1], l])
        self._events[0] = (e, l)
        if rec["key"] == (self._max_chunks, self._kcap):
            self._scratch.setdefault(rec["key"], rec["scratch"])
        self.perf["emit_s"] += time.perf_counter() - t0
        _T.lap("aoi.emit", _te)

    # -- fault recovery (docs/robustness.md): no standing mirror at this
    # size, so the durable old state is reconstructed on demand -- the
    # set_prev seed if one is live (kept under an active plan), else the
    # predicate of the pre-tick shadows (exact: prev always equals the
    # predicate of the last stepped inputs, and clear_entity keeps the
    # shadows consistent).  The recovered tick publishes same-tick (this
    # bucket's flush is synchronous) and _host_prev carries the state until
    # _ensure_prev re-uploads it.

    def reset_calc_chain(self) -> None:
        """Re-arm the device calculator after fallback (operator action --
        demotion is sticky so a flapping device cannot oscillate)."""
        self._calc_level = 0
        self.stats["calc_level"] = 0
        # prev rebuilds lazily from _host_prev at the next _ensure_prev

    def _old_prev_host(self) -> np.ndarray:
        """The pre-tick interest words, reconstructed host-side."""
        if self._seed_prev is not None:
            old = self._seed_prev.copy()
        elif self._cur_old is not None:
            ox, oz, orr, oact = self._cur_old
            old = _packed_predicate(ox, oz, orr, oact)
        else:
            old = np.zeros((self.capacity, self.W), np.uint32)
        # land any clears still queued for the device (idempotent: the
        # predicate of shadows already excludes cleared entities)
        for ent in set(self._pending_clear):
            old[ent] = 0
            w, b = P.word_bit_for_column(ent, self.capacity)
            old[:, w] &= np.uint32(~(np.uint32(1) << np.uint32(b))
                                   & 0xFFFFFFFF)
        self._pending_clear.clear()
        return old

    def _recover(self, e: BaseException) -> None:  # gwlint: allow[flush-phase] -- fault recovery: the device is gone, host sync is the point
        """Device fault mid-flush: recompute the faulted tick host-side
        (bit-exact) and drop all device state."""
        from ..utils import gwlog

        self.stats["rebuilds"] += 1
        # kernel-phase faults demote outright; at harvest time the seam
        # cannot tell a kernel error from a transfer fault (async dispatch:
        # both surface at the blocking fetch), so the decision keys off the
        # exception class (_kernelish_fault)
        if (self._fault_phase == "kernel"
                or (self._fault_phase == "harvest" and _kernelish_fault(e))) \
                and self._calc_level < 2:
            self._calc_level += 1
            self.stats["fallbacks"] += 1
            self.stats["calc_level"] = self._calc_level
        gwlog.logger("gw.aoi").warning(
            "row-shard AOI bucket (cap %d) device fault during %s: %s -- "
            "recovering tick on host (calc level %d)",
            self.capacity, self._fault_phase, e, self._calc_level)
        # _dispatch_device restages BEFORE the device seams, so at fault time
        # the tick may already live in the shadows (_tick_inflight) rather
        # than in _staged -- both mean "a tick's events must be recovered"
        inflight = self._tick_inflight
        staged = inflight or bool(self._staged)
        if staged:
            if not inflight:
                self._restage_shadows()
            old_prev = self._old_prev_host()
        else:
            # maintenance-only flush: nothing stepped, so there are no
            # events to recover -- only the state survives.  The current
            # shadows ARE the last stepped inputs; _old_prev_host derives
            # the pre-fault words from them (or the set_prev seed) and
            # lands any queued clears
            self._cur_old = (self._hx, self._hz, self._hr, self._hact)
            old_prev = self._old_prev_host()
        # drop device state; _ensure_prev re-uploads _host_prev next flush
        self.prev = None
        self._dxs = self._dzs = self._dxr = self._dzr = None
        self._xz_stale = True
        self._h2d_cache.clear()
        self._scratch.clear()
        self._page_free = None  # device-resident free list died with it
        if staged:
            self._host_tick(old_prev)
        else:
            self._host_prev = old_prev
            self._seed_prev = None
            self._cur_old = None
        self._tick_inflight = False

    def _host_tick(self, old_prev: np.ndarray) -> None:
        """One tick on the host from the durable copies, bit-exact with the
        sharded step: the global flat word order equals the per-chip
        extraction order after the chip-offset shift."""
        self.stats["host_ticks"] += 1
        _th = _T.t()
        new = _packed_predicate(self._hx, self._hz, self._hr, self._hact)
        empty = np.empty((0, 2), np.int32)
        if self._subscribed:
            chg = new ^ old_prev
            flat = chg.reshape(-1)
            gidx = np.nonzero(flat)[0]
            chg_vals = flat[gidx]
            ent_vals = chg_vals & new.reshape(-1)[gidx]
            pe, pl = _emit_expand(self, chg_vals, ent_vals, gidx, 1)
            e = pe[:, 1:] if len(pe) else empty
            l = pl[:, 1:] if len(pl) else empty
        else:
            e = l = empty
        pend = self._events.get(0)
        if pend is not None:
            e = np.concatenate([pend[0], e])
            l = np.concatenate([pend[1], l])
        self._events[0] = (e, l)
        self._host_prev = new
        self._seed_prev = None
        self._cur_old = None
        _T.lap("aoi.host_tick", _th)

    def _dispatch_oracle(self) -> None:
        """Level-2 fallback dispatch: the device is out of the loop
        entirely; _host_prev is the authoritative state.  Maintenance and
        restaging run now, the host compute parks for harvest() so it
        overlaps other buckets' device work under the scheduler."""
        if self._host_prev is None:
            self._host_prev = np.zeros((self.capacity, self.W), np.uint32)
        if self._pending_clear:
            # the device maintenance scatter, applied to the host copy
            for ent in set(self._pending_clear):
                self._host_prev[ent] = 0
                w, b = P.word_bit_for_column(ent, self.capacity)
                self._host_prev[:, w] &= np.uint32(
                    ~(np.uint32(1) << np.uint32(b)) & 0xFFFFFFFF)
            self._pending_clear.clear()
        if not self._staged:
            return
        self._restage_shadows()
        old_prev = self._seed_prev if self._seed_prev is not None \
            else self._host_prev
        self._sched = ("oracle", old_prev)

    # -- state carry / lazy derivation --------------------------------------
    def get_prev(self, slot: int) -> np.ndarray:  # gwlint: allow[host-sync] -- parity/debug accessor, off the tick path
        self.flush()
        if self.prev is None:
            if self._host_prev is not None:  # device down: host copy rules
                return np.array(self._host_prev, copy=True)
            return np.zeros((self.capacity, self.W), np.uint32)
        self.full_roundtrips += 1
        return np.asarray(self.prev)

    def set_prev(self, slot: int, words: np.ndarray) -> None:
        self.flush()
        words = np.ascontiguousarray(words, np.uint32)
        if self._calc_level >= 2 or self.prev is None:
            # device down: the words land host-side; _ensure_prev uploads
            # them if the calculator chain re-arms
            self._host_prev = words.copy()
            self._seed_prev = None
            return
        self.full_roundtrips += 1
        if self._ft:
            # the seed is the ONLY durable copy of carried-in state until
            # the next step (prev != predicate(shadows) in between); keep
            # it host-side while a fault plan is active
            self._seed_prev = words.copy()
        self.prev = self.mesh.device_put(words)

    # -- live migration & chip-loss failover (docs/robustness.md) ----------

    def _mark_evacuating(self) -> None:
        """The shard's devices are LOST (faults.DeviceLost): never touch
        them again.  Host-oracle mode keeps the bucket serving bit-exact
        ticks from (_host_prev, shadows) until the engine rebuilds the
        space onto a fresh bucket at the end of the current flush."""
        self._evacuating = True
        self._calc_level = 2
        self.stats["calc_level"] = 2

    def export_snapshot(self, slot: int) -> dict:  # gwlint: allow[host-sync] -- migration snapshot, off the steady tick path
        """Live-migration wire image of THE slot: the 1-D input shadows as
        a delta-staging packet + the previous-tick interest words (see
        _TPUBucket.export_snapshot; this bucket's flush is synchronous, so
        there is no pipeline to drain)."""
        return _build_snapshot(self.capacity, self._hx, self._hz, self._hr,
                               self._hact, self._subscribed,
                               self.get_prev(slot))

    def import_snapshot(self, slot: int, snap: dict) -> None:  # gwlint: allow[host-sync] -- migration replay, off the steady tick path
        """Replay a migration snapshot onto this bucket (see
        _TPUBucket.import_snapshot; shadows here are 1-D, one space)."""
        if snap["capacity"] != self.capacity:
            raise ValueError(
                f"snapshot capacity {snap['capacity']} != bucket "
                f"capacity {self.capacity}")
        x, z = _unpack_positions(snap)
        self._hx[:] = x
        self._hz[:] = z
        self._hr[:] = snap["r"]
        self._hact[:] = snap["act"]
        self.set_subscribed(slot, snap["sub"])
        self._xz_stale = True  # device x/z copies diverged: full restage
        self._h2d_cache.clear()
        self.set_prev(slot, snap["words"])
        if self._ft:
            # set_prev parked the words host-side (device state is lazy)
            # and dropped the seed; under an active plan the seed is the
            # exact recovery base for a fault on the first post-import
            # tick (prev != predicate(shadows) until that tick lands)
            self._seed_prev = np.ascontiguousarray(snap["words"], np.uint32)

    def evacuate(self) -> dict[int, dict]:
        """Snapshot the (single) occupied slot for rebuild on surviving
        devices (the engine drives this after a DeviceLost recovery
        marked the bucket evacuating)."""
        live = sorted(set(range(self.n_slots)) - set(self._free))
        return {slot: self.export_snapshot(slot) for slot in live}

    def peek_words(self, slot: int):
        return None  # no host mirror at this size; use derive_row/derive_col

    def derive_row(self, slot: int, entity_slot: int) -> np.ndarray:  # gwlint: allow[host-sync] -- parity/debug accessor, off the tick path
        """One observer's interest words [W] -- a 16 KB on-demand fetch."""
        self.flush()
        if self.prev is None:
            if self._host_prev is not None:  # device down: host copy rules
                return np.array(self._host_prev[entity_slot], copy=True)
            return np.zeros(self.W, np.uint32)
        return np.asarray(self.prev[entity_slot])

    def derive_col(self, slot: int, entity_slot: int) -> np.ndarray:  # gwlint: allow[host-sync] -- parity/debug accessor, off the tick path
        """Row indices of observers interested in ``entity_slot`` (the
        packed column), from one [C] word-column fetch."""
        self.flush()
        w, b = P.word_bit_for_column(entity_slot, self.capacity)
        if self.prev is None:
            if self._host_prev is None:
                return np.empty(0, np.int64)
            colw = self._host_prev[:, w]  # device down: host copy rules
        else:
            colw = np.asarray(self.prev[:, w])
        return np.nonzero(colw & (np.uint32(1) << np.uint32(b)))[0]
