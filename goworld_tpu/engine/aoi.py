"""The AOI-calculator seam: where Spaces meet the TPU.

Reference seam being re-designed (not ported): the reference plugs an
``aoi.AOIManager{Enter,Leave,Moved}`` into each Space
(/root/reference/engine/entity/Space.go:33,105,211,243,259) and receives
synchronous OnEnterAOI/OnLeaveAOI callbacks per mutation
(Entity.go:227-233).  Here the same contract is delivered *batched per tick*:

    1. each Space stages its per-tick arrays (x, z, radius, active);
    2. the game loop calls ``AOIEngine.flush()`` once per tick;
    3. the engine executes one batched step per (backend, capacity) bucket --
       on TPU that is ONE pallas kernel launch for every space of that
       capacity on the chip -- and returns per-space enter/leave event pairs
       in deterministic (observer, observed) order.

Spaces shard over chips with no cross-chip collectives: a bucket's arrays are
sharded over the mesh 'space' axis (see goworld_tpu.parallel.mesh); every
space's [C] rows live wholly on one chip.

Backends:
  * ``cpu`` -- the Python XZ-sweep oracle (the parity oracle);
  * ``cpp`` -- the native C++ sweep (ops/aoi_native, reference role: the
    compiled go-aoi XZList) -- the production host-CPU calculator;
  * ``tpu`` -- persistent device-resident interest state per bucket, pallas
    fused kernel, two-stage device event extraction.

All produce bit-identical events (tests/test_aoi_engine.py,
tests/test_aoi_native.py).
"""

from __future__ import annotations

import time
import weakref
from dataclasses import dataclass

import numpy as np

from .. import faults, telemetry
from ..ops import aoi_cohort as AC
from ..ops import aoi_emit as AE
from ..ops import aoi_fused as AF
from ..ops import aoi_pages as PG
from ..ops import aoi_predicate as P
from ..ops import aoi_stage as AS
from ..ops import dispatch_count as DC
from ..ops.aoi_oracle import CPUAOIOracle
from ..telemetry import trace as _T
from ..telemetry.metrics import Sample
from ..ops import events as EV

# A space handle is stable for the space's lifetime; slots inside a bucket are
# reused after release.

_fused_impl = None  # built lazily: jax must not load in cpu-only processes
_fused_tri_impl = None
_fused_paged_impl = None
_clear_impl = None


def _batched_clear(prev_all, row_slots, row_ents, col_slots, col_words,
                   col_masks):
    """Erase departed entities' rows and columns in ONE device dispatch.

    A migration storm of k entities used to cost 2k sequential ``.at[].set``
    dispatches before the kernel even ran; this scatters all row clears and
    all (pre-combined per (slot, word)) column masks at once.  Callers pad
    the index arrays by repeating a real entry -- both operations are
    idempotent -- so compilation is per padded size, not per k.
    """
    global _clear_impl
    if _clear_impl is None:
        import functools

        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def impl(prev_all, row_slots, row_ents, col_slots, col_words,
                 col_masks):
            prev_all = prev_all.at[row_slots, row_ents, :].set(0)
            cols = prev_all[col_slots, :, col_words] & col_masks[:, None]
            prev_all = prev_all.at[col_slots, :, col_words].set(cols)
            return prev_all

        _clear_impl = impl
    return _clear_impl(prev_all, row_slots, row_ents, col_slots, col_words,
                       col_masks)


_LANES = 128
_MAX_GAPS = 2048    # escaped chunk-index deltas per flush
_MAX_EXC = 32768    # exception triples (tail + multi-bit words) per flush
# triples-path extraction cap ceiling: the [max_triples, 32] bit matrix
# inside extract_triples is the shape driver (~32 MB of int32 at 2^18), so
# growth stops here and larger ticks permanently take the counted
# full-grid fallback (decode_overflow)
_TRI_MAX = 1 << 18


def _device_fault(e: BaseException) -> bool:
    """Classify an exception as a device-side fault the bucket should
    recover from (vs a logic bug that must propagate).  Injected faults are
    explicit; real jax runtime errors are matched by type name (no jaxlib
    import) and by the canonical XLA status prefixes."""
    if isinstance(e, faults.InjectedFault):
        return True
    if type(e).__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
        return True
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "ALLOCATION" in msg.upper()


def _kernelish_fault(e: BaseException) -> bool:
    """Sub-classify a device fault that surfaced at HARVEST time.  Under
    async dispatch a kernel failure only materializes at the blocking
    fetch, where the seam cannot tell it from a transfer fault -- so the
    calculator-demotion decision keys off the exception itself: an
    injected KernelFailure (or a non-OOM XLA runtime error) demotes the
    calc chain one level, a DeviceOOM/RESOURCE_EXHAUSTED only rebuilds."""
    if isinstance(e, faults.KernelFailure):
        return True
    if isinstance(e, faults.InjectedFault):
        return False
    return type(e).__name__ in ("XlaRuntimeError", "JaxRuntimeError") \
        and "RESOURCE_EXHAUSTED" not in str(e)


def _packed_predicate(x, z, r, act, block: int = 2048) -> np.ndarray:  # gwlint: allow[host-sync] -- pure host numpy on the durable copies (recovery path), never device values
    """Host recomputation of one slot's packed interest words [C, W] --
    bit-exact with every device backend (all evaluate the same f32
    predicate; ops/aoi_predicate).  Blocked over observer rows so the
    boolean matrix never materializes at O(C^2) bytes (17 GB at the
    row-sharded C=131072)."""
    c = x.shape[0]
    out = np.empty((c, P.words_per_row(c)), np.uint32)
    xx = np.asarray(x, np.float32)
    zz = np.asarray(z, np.float32)
    rr = np.asarray(r, np.float32)
    aa = np.asarray(act, bool)
    for lo in range(0, c, block):
        hi = min(lo + block, c)
        dx = np.abs(xx[None, :] - xx[lo:hi, None])
        dz = np.abs(zz[None, :] - zz[lo:hi, None])
        rad = rr[lo:hi, None]
        m = (dx <= rad) & (dz <= rad)
        m &= aa[lo:hi, None] & aa[None, :]
        idx = np.arange(lo, hi)
        m[idx - lo, idx] = False  # self-interest excluded, like the kernel
        out[lo:hi] = P.pack_rows(m)
    return out


def _split_rows(tri: np.ndarray) -> dict[int, np.ndarray]:
    """(space_row, i, j) triples -> {space_row: (i, j) pairs}."""
    out: dict[int, np.ndarray] = {}
    if len(tri):
        for s in np.unique(tri[:, 0]).tolist():
            out[s] = tri[tri[:, 0] == s][:, 1:]
    return out


def _build_snapshot(capacity: int, x, z, r, act, sub: bool,
                    words: np.ndarray) -> dict:
    """One space's live-migration wire image (docs/robustness.md).

    Positions travel as a delta-staging packet (ops/aoi_stage.pad_packet --
    PR 2's H2D wire format doubles as the migration serialization), rows all
    zero because the importer scatters into its own slot row; the pow2
    padding duplicates the last entry, which an assignment scatter absorbs
    idempotently.  ``words`` is the previous-tick packed interest state --
    the only other durable truth a tier needs to resume bit-exactly.
    Pending events are NOT part of the snapshot: the migration swap and the
    evacuation path carry them explicitly (delivery, not state)."""
    from ..ops import aoi_stage as AS

    # Snapshot export runs between ticks (a migration/evacuation event,
    # not the flush hot path); the inputs are host shadows already, so
    # asarray only normalizes dtype and pad_packet is numpy-in/numpy-out.
    x = np.asarray(x, np.float32)  # gwlint: allow[host-sync] -- host shadow
    z = np.asarray(z, np.float32)  # gwlint: allow[host-sync] -- host shadow
    nz = np.nonzero((x.view(np.uint32) != 0) | (z.view(np.uint32) != 0))[0]
    pkt = None
    if len(nz):
        pkt = tuple(np.asarray(a) for a in AS.pad_packet(  # gwlint: allow[host-sync] -- migration-time packet build
            np.zeros(len(nz), np.int64), nz, x[nz], z[nz]))
    return {"capacity": capacity, "packet": pkt,
            "r": np.array(r, np.float32, copy=True),
            "act": np.array(act, bool, copy=True),
            "sub": bool(sub),
            "words": np.array(words, np.uint32, copy=True)}


def _unpack_positions(snap: dict) -> tuple[np.ndarray, np.ndarray]:
    """Scatter a snapshot's packet back into dense [C] x/z arrays."""
    c = snap["capacity"]
    x = np.zeros(c, np.float32)
    z = np.zeros(c, np.float32)
    if snap["packet"] is not None:
        _rows, cols, xv, zv = snap["packet"]
        x[cols] = xv
        z[cols] = zv
    return x, z


def _demote_emit(bucket, e: BaseException) -> None:
    """``aoi.emit`` seam fault: the faulted tick's events fall back to the
    host decode (pure numpy on arrays the harvest already fetched, so the
    fallback is bit-exact), and the bucket sticks to the host emit path for
    every later tick (docs/robustness.md emit fallback chain;
    ``reset_emit_path`` re-arms)."""
    from ..utils import gwlog

    bucket._emit = "host"
    bucket.stats["emit_path"] = AE.EMIT_LEVEL["host"]
    gwlog.logger("gw.aoi").warning(
        "AOI bucket (cap %d) emit fan-out fault: %s -- demoting to the "
        "host decode emit path", bucket.capacity, e)


def _emit_expand(bucket, chg_vals, ent_vals, gidx, s_n: int):
    """Classified word stream -> sorted (enter, leave) triples through the
    bucket's emit path (docs/perf.md emit paths): C++ bit expansion when
    the bucket runs emit="native", the numpy host expansion otherwise (for
    word streams "vector" IS the host expansion -- the vector/native split
    only diverges on the single-chip triples path).  The native attempt
    sits behind the ``aoi.emit`` fault seam; any failure is handled HERE --
    never propagated to harvest's device-fault recovery -- by demoting the
    bucket and expanding the same stream on host, bit-exactly.
    Harvest-phase numpy on already-fetched arrays throughout (the gwlint
    flush-phase rule walks emit helpers)."""
    if bucket._emit == "native" and len(chg_vals):
        try:
            faults.check("aoi.emit")
            return AE.expand_words_native(chg_vals, ent_vals, gidx,
                                          bucket.capacity)
        except Exception as e:
            if not (_device_fault(e) or isinstance(e, RuntimeError)):
                raise
            _demote_emit(bucket, e)
    return EV.expand_classified_host(chg_vals, ent_vals, gidx,
                                     bucket.capacity, s_n)


def _fused_bucket_step(prev_all, *args):
    """One device program per bucket flush: gather staged slots' previous
    words, run the fused AOI kernel, scatter the new words back, compact the
    diff with the chunk extraction (ops/events.py extract_chunks -- no
    per-element gathers; the NEW words ride the same chunk gather so
    enter/leave classification is free), and wire-encode the result
    (~5 B/dirty chunk + 12 B/exception) so the host fetch is the encoded
    stream, not raw grids.  A single dispatch instead of six (dispatch
    latency is per tick on the production path).

    ``args`` = (new_buf, chg_buf, vals_buf, nv_buf, lane_buf, csel_buf,
    slot_idx, x_all, z_all, r_all, act_all, sub_all, max_chunks, kcap,
    max_gaps, max_exc) where x_all/z_all/r_all/act_all are the bucket's
    persistent DEVICE-RESIDENT [s_max, C] staged inputs (sub_all [s_max]);
    the staged slots' rows are gathered by ``slot_idx`` inside the program,
    so a delta-staged tick never re-ships unchanged inputs (see
    ops/aoi_stage.py and _TPUBucket.flush).  ``chg``/``new`` and the raw
    grids are kept for cap-overflow recovery -- ``prev_all`` is donated, so
    the diff would otherwise be unrecoverable -- and ALL large outputs ride
    DONATED scratch buffers: a freshly allocated device array costs an
    allocation per dispatch even when never fetched, while donated
    in-place buffers are free.
    """
    global _fused_impl
    if _fused_impl is None:
        import functools

        import jax
        import jax.numpy as jnp

        from ..ops.aoi_dense import aoi_step_chg

        @functools.partial(
            jax.jit,
            static_argnames=("max_chunks", "kcap", "max_gaps", "max_exc",
                             "platform"),
            donate_argnums=(0, 1, 2, 3, 4, 5, 6))
        def impl(prev_all, new_buf, chg_buf, vals_buf, nv_buf, lane_buf,
                 csel_buf, slot_idx, x_all, z_all, r_all, act_all, sub_all,
                 max_chunks, kcap, max_gaps, max_exc, platform=None):
            prev_rows = prev_all[slot_idx]
            x = x_all[slot_idx]
            z = z_all[slot_idx]
            r = r_all[slot_idx]
            act = act_all[slot_idx]
            sub = sub_all[slot_idx]
            # platform routing (pallas on TPU, fused dense elsewhere) lives
            # in ONE place: ops/aoi_dense.aoi_step_chg.  ``platform`` is the
            # calculator fallback chain's override: a bucket demoted off the
            # pallas path after a kernel failure forces the dense route
            # (bit-identical results; docs/robustness.md)
            new, chg = aoi_step_chg(x, z, r, act, prev_rows,
                                    platform=platform)
            prev_all = prev_all.at[slot_idx].set(new)
            # subscription mask: slots with no event consumers (all-plain
            # spaces -- their interest state lives in the packed words,
            # derived on demand) contribute NOTHING to the change stream,
            # so the fetch/decode cost scales with subscribed slots only.
            # ``new`` above is unmasked: prev_all must stay authoritative.
            chg = jnp.where(sub[:, None, None], chg, jnp.uint32(0))
            vals, nv, lane, csel, ccnt, nd, mcc = EV.extract_chunks(
                chg, max_chunks, kcap, aux=new, lanes=_LANES)
            enc = EV.encode_row_stream(vals, nv, lane, csel, ccnt,
                                       w=_LANES, max_gaps=max_gaps,
                                       max_exc=max_exc)
            (rowb, bitpos, woff, base_row, n_esc, esc_rows,
             exc_gidx, exc_chg, exc_new, exc_n) = enc
            scalars = jnp.stack([nd, mcc, base_row, n_esc, exc_n])
            new_buf = new_buf.at[:].set(new)
            chg_buf = chg_buf.at[:].set(chg)
            vals_buf = vals_buf.at[:].set(vals)
            nv_buf = nv_buf.at[:].set(nv)
            lane_buf = lane_buf.at[:].set(lane)
            csel_buf = csel_buf.at[:].set(csel)
            return (prev_all, new_buf, chg_buf, vals_buf, nv_buf, lane_buf,
                    csel_buf, rowb, bitpos, woff, esc_rows, exc_gidx,
                    exc_chg, exc_new, scalars)

        _fused_impl = impl
    return _fused_impl(prev_all, *args)


def _fused_bucket_step_tri(prev_all, *args):
    """Triples-mode bucket flush (docs/perf.md emit paths): same gather /
    fused kernel / scatter prologue as :func:`_fused_bucket_step`, but the
    diff compacts straight into fixed-capacity (observer, observed, kind)
    triples ON DEVICE (ops/events.py extract_triples) -- harvest then
    fetches the compact triple buffer plus ONE count scalar, and the host
    never unpacks a word again on the steady path.  The raw ``new``/``chg``
    grids still ride donated scratch for the counted-overflow and
    poisoned-scalar recoveries (prev_all is donated, so the diff would
    otherwise be unrecoverable).

    ``args`` = (new_buf, chg_buf, tri_buf, slot_idx, x_all, z_all, r_all,
    act_all, sub_all, max_triples, platform).
    """
    global _fused_tri_impl
    if _fused_tri_impl is None:
        import functools

        import jax
        import jax.numpy as jnp

        from ..ops.aoi_dense import aoi_step_chg

        @functools.partial(
            jax.jit,
            static_argnames=("max_triples", "platform"),
            donate_argnums=(0, 1, 2, 3))
        def impl(prev_all, new_buf, chg_buf, tri_buf, slot_idx, x_all,
                 z_all, r_all, act_all, sub_all, max_triples,
                 platform=None):
            prev_rows = prev_all[slot_idx]
            x = x_all[slot_idx]
            z = z_all[slot_idx]
            r = r_all[slot_idx]
            act = act_all[slot_idx]
            sub = sub_all[slot_idx]
            new, chg = aoi_step_chg(x, z, r, act, prev_rows,
                                    platform=platform)
            prev_all = prev_all.at[slot_idx].set(new)
            chg = jnp.where(sub[:, None, None], chg, jnp.uint32(0))
            tri, count = EV.extract_triples(chg, new, chg.shape[1],
                                            max_triples)
            new_buf = new_buf.at[:].set(new)
            chg_buf = chg_buf.at[:].set(chg)
            tri_buf = tri_buf.at[:].set(tri)
            return (prev_all, new_buf, chg_buf, tri_buf,
                    count.reshape(1))

        _fused_tri_impl = impl
    return _fused_tri_impl(prev_all, *args)


def _fused_bucket_step_paged(prev_all, *args):
    """Paged-mode bucket flush (docs/perf.md paged storage, ROADMAP #2):
    same gather / fused kernel / scatter prologue as
    :func:`_fused_bucket_step`, but the diff compacts into page-granular
    word entries through the on-device allocator (ops/aoi_pages): each
    allocation bin's nonzero change words land on pages drawn from the
    shared free list, so a dense hotspot borrows pages sparse bins never
    needed and NO global per-tick cap exists -- bins the pool cannot
    serve are reported in ``spill_bins`` for the counted spill-to-host
    fallback instead of truncating anything.  Harvest fetches the used
    page prefix, the page table, and one scalar vector.  The raw
    ``new``/``chg`` grids still ride donated scratch for the spill and
    poisoned-scalar recoveries.

    ``args`` = (new_buf, chg_buf, pg_buf, pc_buf, pn_buf, free, slot_idx,
    x_all, z_all, r_all, act_all, sub_all, page_words, bin_words,
    max_spill, platform).
    """
    global _fused_paged_impl
    if _fused_paged_impl is None:
        import functools

        import jax
        import jax.numpy as jnp

        from ..ops.aoi_dense import aoi_step_chg

        @functools.partial(
            jax.jit,
            static_argnames=("page_words", "bin_words", "max_spill",
                             "platform"),
            donate_argnums=(0, 1, 2, 3, 4, 5, 6))
        def impl(prev_all, new_buf, chg_buf, pg_buf, pc_buf, pn_buf,
                 free, slot_idx, x_all, z_all, r_all, act_all, sub_all,
                 page_words, bin_words, max_spill, platform=None):
            prev_rows = prev_all[slot_idx]
            x = x_all[slot_idx]
            z = z_all[slot_idx]
            r = r_all[slot_idx]
            act = act_all[slot_idx]
            sub = sub_all[slot_idx]
            new, chg = aoi_step_chg(x, z, r, act, prev_rows,
                                    platform=platform)
            prev_all = prev_all.at[slot_idx].set(new)
            chg = jnp.where(sub[:, None, None], chg, jnp.uint32(0))
            (pg, pc, pn, page_tab, free_next, spill_bins,
             scalars) = PG.allocate_pages(chg, new, free, page_words,
                                          bin_words, max_spill)
            new_buf = new_buf.at[:].set(new)
            chg_buf = chg_buf.at[:].set(chg)
            pg_buf = pg_buf.at[:].set(pg)
            pc_buf = pc_buf.at[:].set(pc)
            pn_buf = pn_buf.at[:].set(pn)
            return (prev_all, new_buf, chg_buf, pg_buf, pc_buf, pn_buf,
                    page_tab, free_next, spill_bins, scalars)

        _fused_paged_impl = impl
    return _fused_paged_impl(prev_all, *args)


class _CapDecay:
    """Windowed decay of adaptive extraction caps, shared by the TPU
    buckets (single-chip and mesh).  Growth on overflow is the owner's
    job; this tracks window peaks and proposes shrinks on a SHORT doubling
    window -- a one-off mass tick (space fill, restore storm) must not
    pessimize hundreds of later flushes with storm-sized extraction grids.
    ``steady`` turns True once a window check passes with nothing to
    change, i.e. the static compile key is final; benchmarks warm up until
    then."""

    def __init__(self, nd_floor: int):
        self.nd_floor = nd_floor
        self.peak_nd = 0
        self.peak_mcc = 0
        self.flushes = 0
        self.refit_at = 8
        self.steady = False

    def reset_after_growth(self) -> None:
        """The storm that grew the caps must not anchor the next window's
        peak, or the post-storm shrink waits a full window."""
        self.peak_nd = self.peak_mcc = 0
        self.flushes = 0
        self.refit_at = 8
        self.steady = False

    def observe(self, nd: int, mcc: int, cur_nd: int,
                cur_k: int) -> tuple[int, int] | None:
        """Track one flush's peaks; at the window boundary return the
        shrunk ``(max_chunks, kcap)`` to adopt, or None."""
        self.peak_nd = max(self.peak_nd, nd)
        self.peak_mcc = max(self.peak_mcc, mcc)
        self.flushes += 1
        if self.flushes < self.refit_at:
            return None
        fit_nd = max(self.nd_floor, -(-self.peak_nd * 3 // 2 // 512) * 512)
        fit_k = min(max(8, 1 << (self.peak_mcc * 2 - 1).bit_length()),
                    _LANES)
        self.peak_nd = self.peak_mcc = 0
        self.flushes = 0
        self.refit_at = min(self.refit_at * 2, 128)
        if fit_nd < cur_nd or fit_k < cur_k:
            self.steady = False  # one more clean window confirms
            return min(cur_nd, fit_nd), min(cur_k, fit_k)
        self.steady = True
        return None


class _TriCapDecay:
    """Windowed decay of the triples-path extraction cap (the exact
    _CapDecay story for ``max_triples``: growth on overflow is the owner's
    job, this proposes post-storm shrinks on a doubling window and reports
    ``steady`` once the static compile key is final)."""

    def __init__(self, floor: int):
        self.floor = floor
        self.peak = 0
        self.flushes = 0
        self.refit_at = 8
        self.steady = False

    def reset_after_growth(self) -> None:
        self.peak = 0
        self.flushes = 0
        self.refit_at = 8
        self.steady = False

    def observe(self, count: int, cur: int) -> int | None:
        """Track one flush's triple count; at the window boundary return
        the shrunk cap to adopt, or None."""
        self.peak = max(self.peak, count)
        self.flushes += 1
        if self.flushes < self.refit_at:
            return None
        fit = max(self.floor,
                  1 << (max(self.peak * 3 // 2, 1) - 1).bit_length())
        self.peak = 0
        self.flushes = 0
        self.refit_at = min(self.refit_at * 2, 128)
        if fit < cur:
            self.steady = False  # one more clean window confirms
            return fit
        self.steady = True
        return None


class _PageDecay:
    """Windowed decay of the paged pool size (the exact _TriCapDecay
    story for ``n_pages``: growth on spill is the owner's job -- bounded
    by ops/aoi_pages.pool_ceiling, past which the pool can never spill --
    and this proposes post-storm shrinks on a doubling window, reporting
    ``steady`` once the static compile key is final)."""

    def __init__(self, floor: int):
        self.floor = floor
        self.peak = 0
        self.flushes = 0
        self.refit_at = 8
        self.steady = False

    def reset_after_growth(self) -> None:
        self.peak = 0
        self.flushes = 0
        self.refit_at = 8
        self.steady = False

    def observe(self, n_used: int, cur: int) -> int | None:
        """Track one flush's used-page peak; at the window boundary
        return the shrunk pool size to adopt, or None."""
        self.peak = max(self.peak, n_used)
        self.flushes += 1
        if self.flushes < self.refit_at:
            return None
        fit = max(self.floor,
                  1 << (max(self.peak * 3 // 2, 1) - 1).bit_length())
        self.peak = 0
        self.flushes = 0
        self.refit_at = min(self.refit_at * 2, 128)
        if fit < cur:
            self.steady = False  # one more clean window confirms
            return fit
        self.steady = True
        return None


def _paged_absorb_chip(bk, chg_dev, new_dev, W: int):  # gwlint: allow[host-sync] -- counted overflow absorber: fetches used pages + spilled bins instead of a chip's full diff grid
    """Absorb one chip's decode overflow through the paged pool
    (docs/perf.md, paged storage): instead of growing the stream caps (a
    recompile) and fetching the chip's FULL diff grid, compact the kept
    change/new grids into pages ON DEVICE (ops/aoi_pages) and fetch only
    the used prefix -- plus any spilled bins host-side, as a counted
    graceful degradation.  Shares the bucket's persistent free list /
    pool-decay state (``_page_free``/``_n_pages``/``_pages``) across
    chips and ticks; the ``aoi.pages`` seam crosses once per absorbed
    chip (oom/fail/partial = whole-grid spill + pool re-arm; poison =
    page-table corruption caught by validation -> whole-grid spill +
    free-list reinit -- the multi-chip pool is transient per-harvest, so
    reinit IS the rebuild).

    Returns ``(chg_vals, ent_vals, gidx)`` with chip-LOCAL flat word
    indices (the caller offsets by its chip base), bit-exact with the
    raw-grid recovery it replaces.
    """
    from ..utils import gwlog
    import jax.numpy as jnp

    nw = int(np.prod(chg_dev.shape))
    bw = PG.bin_words_for(W)
    if bk._pages is None:
        bk._pages = _PageDecay(floor=PG.pool_floor(nw))
    want = max(bk._n_pages, bk._pages.floor)
    if bk._page_free is None or int(bk._page_free.shape[0]) != want:
        bk._n_pages = want
        bk._page_free = jnp.arange(want, dtype=jnp.int32)
    n_pages = bk._n_pages

    def _whole_grid():  # gwlint: allow[host-sync] -- counted whole-grid spill drains on purpose
        # counted spill: the raw-grid fallback the capped path used
        bk.stats["page_spills"] += 1
        chg_h = np.asarray(chg_dev).reshape(-1)
        new_h = np.asarray(new_dev).reshape(-1)
        gidx = np.nonzero(chg_h)[0]
        chg_vals = chg_h[gidx]
        return chg_vals, chg_vals & new_h[gidx], np.asarray(gidx, np.int64)

    try:
        spec = faults.check("aoi.pages")
    except Exception as e:  # noqa: BLE001 -- seam-injected device faults
        if not _device_fault(e):
            raise
        gwlog.logger("gw.aoi").warning(
            "AOI page pool unusable for this chip (%s); spilling its "
            "whole grid to host and re-arming the pool", e)
        bk._page_free = None
        bk._pages.reset_after_growth()
        return _whole_grid()
    if spec is not None and spec.kind == "partial":
        gwlog.logger("gw.aoi").warning(
            "AOI page allocation reported partial for this chip; "
            "spilling its whole grid to host and re-arming the pool")
        bk._page_free = None
        bk._pages.reset_after_growth()
        return _whole_grid()
    _tp = _T.t()
    (pg, pc, pn, tab, free_next, sb, scal) = PG.paged_extract(
        chg_dev.reshape(-1), new_dev.reshape(-1), bk._page_free,
        page_words=PG.PAGE_WORDS, bin_words=bw, max_spill=PG.MAX_SPILL)
    bk._page_free = free_next
    scal_h = np.asarray(scal)
    n_used, n_spill = int(scal_h[0]), int(scal_h[1])
    n_bins = -(-nw // bw)
    tab_h = np.asarray(tab)
    if spec is not None and spec.kind == "poison":
        # seam-injected allocator corruption: trash the fetched table so
        # validation must catch it (docs/robustness.md, aoi.pages)
        tab_h = np.full_like(tab_h, np.iinfo(np.int32).min)
    bad_scal = not (0 <= n_used <= n_pages and 0 <= n_spill <= n_bins)
    if bad_scal or not PG.validate_page_table(
            tab_h, 0 if bad_scal else n_used, n_pages):
        bk.stats["poisoned"] += 1
        gwlog.logger("gw.aoi").warning(
            "AOI page table failed validation during overflow absorb "
            "(n_used=%d, n_pages=%d); spilling the chip's whole grid and "
            "reinitialising the free list", n_used, n_pages)
        bk._page_free = None
        bk._pages.reset_after_growth()
        out = _whole_grid()
        _T.lap("aoi.pages", _tp)
        return out
    pg_h = np.asarray(pg[:max(n_used, 1)])[:n_used]
    pc_h = np.asarray(pc[:max(n_used, 1)])[:n_used]
    pn_h = np.asarray(pn[:max(n_used, 1)])[:n_used]
    gidx, chg_vals, new_vals = PG.decode_pages(pg_h, pc_h, pn_h)
    if n_spill:
        # hotter than the pool: counted spill for the offending bins +
        # pool growth so the NEXT storm tick absorbs fully page-side
        bk.stats["page_spills"] += n_spill
        sgi, sc, sn = PG.spill_stream(
            chg_dev.reshape(-1), new_dev.reshape(-1), np.asarray(sb),
            bw, nw)
        gidx = np.concatenate([np.asarray(gidx, np.int64), sgi])
        chg_vals = np.concatenate([chg_vals, sc])
        new_vals = np.concatenate([new_vals, sn])
        grown = min(PG.pool_ceiling(nw, bw), max(n_pages * 2, 64))
        if grown > n_pages:
            bk._n_pages = grown
            bk._page_free = None
        bk._pages.reset_after_growth()
    else:
        shrink = bk._pages.observe(n_used, n_pages)
        if shrink is not None:
            bk._n_pages = shrink
            bk._page_free = None
    bk.stats["page_occupancy"] = n_used / max(n_pages, 1)
    _T.lap("aoi.pages", _tp)
    return chg_vals, chg_vals & new_vals, np.asarray(gidx, np.int64)


@dataclass(eq=False)  # identity hash: handles live in a WeakSet registry
class SpaceAOIHandle:
    backend: str        # resolved (cpu | cpp | tpu)
    capacity: int
    bucket: "_Bucket"
    slot: int
    released: bool = False
    # the backend as REQUESTED (may be "auto"); growth re-resolves it, so
    # a space that grows past the routing threshold moves to the tpu bucket
    requested: str = ""


class AOIEngine:
    """Per-process registry of AOI state, bucketed by (backend, capacity).

    ``mesh`` (a :class:`goworld_tpu.parallel.SpaceMesh`, or an int device
    count) shards every tpu bucket's spaces over the mesh's 'space' axis --
    the engine-level multi-chip path (see engine/aoi_mesh).  Without it, tpu
    buckets are single-device."""

    _next_telemetry_id = 0

    def __init__(self, default_backend: str = "cpu",
                 oracle_algorithm: str = "sweep", mesh=None,
                 pipeline: bool = False, delta_staging: bool = True,
                 tpu_min_capacity: int = 4096,
                 rowshard_min_capacity: int = 65536,
                 flush_sched: bool = True, emit: str = "auto",
                 paged: bool = False, cross_tick: bool = False,
                 interest_mode: str = "device", fused: bool = False,
                 cohort=False, cohort_ladder=None):
        self.default_backend = default_backend
        # space-stacked cohorts (ROADMAP #2, ops/aoi_cohort, docs/perf.md
        # "Space-stacked cohorts"): "auto"/True stacks small device-eligible
        # spaces into shared ladder-shaped _CohortTPUBucket planes so ONE
        # launch ticks the whole cohort; "solo" forces one exclusive bucket
        # per space -- the O(spaces)-dispatches baseline the engine_multispace
        # bench A/Bs against (and the demotion target of the aoi.cohort
        # seam); False keeps classic (backend, capacity) pooling.  Cohorts
        # are a single-chip tier: a mesh engine keeps its mesh routing.
        if cohort is True:
            cohort = "auto"
        if cohort not in (False, "auto", "solo"):
            raise ValueError(
                f"aoi_cohort must be False|True|'auto'|'solo', got "
                f"{cohort!r}")
        self.cohort = cohort
        self.cohort_ladder = AC.validate_ladder(
            cohort_ladder if cohort_ladder is not None else AC.DEFAULT_LADDER)
        self._cohort_serial = 0
        self.cohort_stats = {"cohort_joins": 0, "cohort_leaves": 0,
                             "cohort_demoted_spaces": 0}
        # fused steady tick (ops/aoi_fused, ROADMAP #3): each device
        # bucket compiles its steady-state tick into ONE jitted program
        # (one enqueue + one D2H fetch); unfused stays the A/B baseline
        # and the per-tick demotion target for any aoi.* seam fault
        self.fused = bool(fused)
        # interest-policy stacks (goworld_tpu/interest/): where attached
        # stacks evaluate -- "device" = the fused jitted step, "host" =
        # the CPU oracle (the bit-exact perf baseline bench_engine_interest
        # A/Bs against).  Validated here, consumed by attach_interest.
        if interest_mode not in ("device", "host"):
            raise ValueError(
                f"interest_mode must be device|host, got {interest_mode!r}")
        self.interest_mode = interest_mode
        # cross-tick pipelining (docs/perf.md): tick T+1's dispatch (pack
        # + H2D + kernel enqueue on the double-buffered device state) runs
        # while tick T harvests -- the device bucket parks each dispatched
        # record one flush and delivers it at the next, buying near-100%
        # device occupancy for ONE TICK of documented event latency.  The
        # deferral is exactly the ``pipeline`` bucket contract, asserted
        # engine-wide: cross_tick composes idempotently with pipeline
        # (either flag defers; both together still defer exactly one
        # tick), and the stream is bit-exact modulo the shift.  The
        # row-sharded tier accepts the flag but stays synchronous (its
        # flush is already a collective barrier -- see aoi_rowshard).
        self.cross_tick = bool(cross_tick)
        # paged ragged event storage (docs/perf.md paged storage): the
        # device buckets compact their change stream into fixed-size pages
        # drawn from a shared on-device free list instead of a global
        # per-tick cap, retiring the decode_overflow failure class for
        # skewed (clustered-crowd) distributions.  Off by default while
        # the capped layouts remain the tuned production path; bench.py's
        # clustered_crowd config A/Bs the two.
        self.paged = bool(paged)
        # event emit fan-out path for the device buckets (docs/perf.md):
        # "auto" = fastest available (native when libgwemit builds, else
        # vector), "host" = the original per-word host decode kept as the
        # bit-exact oracle.  Validated here (fail fast at construction) but
        # RESOLVED lazily at the first tpu bucket -- resolution may shell
        # out to make, which a cpu-only engine must never pay.
        if emit != "auto" and emit not in AE.EMIT_MODES:
            raise ValueError(
                f"aoi_emit must be one of {('auto',) + AE.EMIT_MODES}, "
                f"got {emit!r}")
        self.emit = emit
        self._emit_resolved: str | None = None
        # sparse delta staging of device-resident tick inputs (see
        # _TPUBucket._stage_inputs); False = full-restage baseline, kept
        # for perf A/B in bench.py
        self.delta_staging = delta_staging
        # split-phase flush scheduler (docs/perf.md): True = issue-all-
        # then-harvest across buckets; False = the forced-sequential
        # baseline (each bucket dispatches AND harvests before the next
        # starts), kept for perf A/B and parity tests
        self.flush_sched = flush_sched
        self.oracle_algorithm = oracle_algorithm
        # "auto" routing threshold: spaces below it go to the native host
        # calculator (a tiny space is dispatch-bound on an accelerator;
        # the native sweep finishes in microseconds), larger ones to the
        # tpu bucket where the batched kernel wins
        self.tpu_min_capacity = tpu_min_capacity
        # oversized-single-space threshold: with a mesh, a space at or above
        # this capacity shards its interest ROWS over the chips (each chip
        # owns C/n observers vs all C candidates -- engine/aoi_rowshard)
        # instead of living whole on one chip.  The zipf100k scaling answer.
        self.rowshard_min_capacity = rowshard_min_capacity
        self._rowshard_serial = 0
        if isinstance(mesh, int):
            from ..parallel import SpaceMesh, multichip_devices

            mesh = SpaceMesh(multichip_devices(mesh))
        self.mesh = mesh
        # double-buffered tpu flush: events arrive one tick late, D2H
        # overlaps the host tick (SURVEY §7(d); see _TPUBucket docstring --
        # the mesh bucket implements the same contract per chip)
        self.pipeline = pipeline
        self._buckets: dict[tuple[str, int], _Bucket] = {}
        # live handle registry (weak: a dropped Space must not pin its
        # slot); the chip-loss evacuation path re-points these in place so
        # Spaces survive their bucket dying (docs/robustness.md)
        self._handles: "weakref.WeakSet[SpaceAOIHandle]" = weakref.WeakSet()
        # in-flight live migrations (engine/placement.py _Migration
        # objects); flush() drives their per-flush double-cover compare
        self._migrations: list = []
        self.migration_stats = {"migrations": 0, "evacuations": 0,
                                "migration_rollbacks": 0,
                                "migration_ms": 0.0}
        # unified telemetry: the per-bucket stats/perf dicts surface at
        # /debug/metrics under aoi.* dotted names.  Registered weakly so
        # the registry never keeps a dead engine (and its device state)
        # alive; the label tells concurrent engines apart.
        self._telemetry_id = AOIEngine._next_telemetry_id
        AOIEngine._next_telemetry_id += 1
        telemetry.register_collector(self._telemetry_collect, weak=True)
        if default_backend in ("tpu", "auto"):
            # fail FAST at process boot, not on the first space's first
            # tick: a game configured for tpu whose jax backend is broken
            # would otherwise come up "healthy" and swallow an error per
            # tick forever.  JAX drops to the CPU by itself when the TPU
            # cannot start (absent, or held by another process); the
            # kernel would then run interpreted under a "tpu" name, so
            # that is an error too unless the process pinned the CPU.
            # The probe targets the engine's compute platform: the mesh's
            # devices when there is one, else the default backend.
            import jax

            try:
                if self.mesh is not None:
                    dev = next(iter(self.mesh.mesh.devices.flat))
                    jax.device_put(np.zeros(8, np.float32),  # gwlint: allow[host-sync] -- one-time boot probe at engine init, not per-tick
                                   dev).block_until_ready()
                    platform = self.mesh.platform
                else:
                    import jax.numpy as jnp

                    jnp.zeros(8).block_until_ready()  # gwlint: allow[host-sync] -- one-time boot probe at engine init, not per-tick
                    platform = jax.default_backend()
            except RuntimeError as e:
                raise RuntimeError(
                    f"aoi_backend={default_backend}: JAX could not start "
                    f"its device ({e}).  A TPU chip belongs to one "
                    "process: run one tpu game per chip.") from e
            from ..chip import require_tpu

            require_tpu(platform, f"aoi_backend={default_backend}")

    def create_space(self, capacity: int, backend: str | None = None) -> SpaceAOIHandle:
        requested = backend or self.default_backend
        capacity = P.round_capacity(capacity)
        if self.cohort and self.mesh is None \
                and requested in ("tpu", "auto"):
            # cohort routing (docs/perf.md "Space-stacked cohorts"): a
            # device-eligible space inside the ladder range rounds UP to
            # its pow2 ladder shape -- "auto" stacks it into the shared
            # cohort bucket at that shape (one launch per cohort, not per
            # space), "solo" pins it to an exclusive per-space bucket
            # (the O(spaces) baseline / demotion target).  Spaces past
            # the ladder ceiling keep the classic routing below.
            shape = AC.cohort_shape(capacity, self.cohort_ladder)
            if shape is not None:
                if self.cohort == "solo":
                    h = self._solo_handle(shape)
                else:
                    bucket = self._cohort_bucket(shape)
                    slot = bucket.acquire_slot()
                    h = SpaceAOIHandle("tpu", shape, bucket, slot)
                    self._handles.add(h)
                h.requested = requested
                return h
        backend = requested
        if backend == "auto":
            # capacity routing: tiny spaces are dispatch-bound on an
            # accelerator (the native sweep finishes them in microseconds);
            # large ones belong on the batched kernel
            backend = ("tpu" if capacity >= self.tpu_min_capacity
                       else "cpp")
        rowshard = (backend == "tpu" and self.mesh is not None
                    and capacity >= self.rowshard_min_capacity
                    and capacity % (self.mesh.n_devices * 128) == 0)
        key = (backend, capacity)
        bucket = None if rowshard else self._buckets.get(key)
        if bucket is None:
            if backend == "cpu":
                bucket = _CPUBucket(capacity, self.oracle_algorithm)
            elif backend == "cpp":
                from ..ops import aoi_native

                if aoi_native.available():
                    # "auto" = grid candidate binning when the layout
                    # supports it, sweep otherwise (bit-exact either way);
                    # the production host calculator should always take the
                    # cheaper enumeration
                    bucket = _CPUBucket(capacity, "auto",
                                        oracle_cls=aoi_native.NativeAOIOracle)
                else:
                    # LOUD fallback (results are bit-identical, only slower)
                    from ..utils import gwlog

                    gwlog.logger("gw.aoi").warning(
                        "libgwaoi.so unavailable (no C++ toolchain?); "
                        "aoi_backend=cpp falling back to the python oracle"
                    )
                    bucket = _CPUBucket(capacity, self.oracle_algorithm)
            elif backend == "tpu":
                if rowshard:
                    # oversized single space: shard its interest rows over
                    # the mesh; one EXCLUSIVE bucket per space (at C=131072
                    # the packed state is 2 GB mesh-wide -- released with
                    # the space, never pooled)
                    from .aoi_rowshard import _RowShardTPUBucket

                    bucket = _RowShardTPUBucket(
                        capacity, self.mesh, pipeline=self.pipeline,
                        cross_tick=self.cross_tick,
                        delta_staging=self.delta_staging,
                        emit=self._resolve_emit(), paged=self.paged,
                        fused=self.fused)
                    self._rowshard_serial += 1
                    key = (f"tpu-rowshard-{self._rowshard_serial}", capacity)
                elif self.mesh is not None:
                    from .aoi_mesh import _MeshTPUBucket

                    bucket = _MeshTPUBucket(
                        capacity, self.mesh, pipeline=self.pipeline,
                        cross_tick=self.cross_tick,
                        delta_staging=self.delta_staging,
                        emit=self._resolve_emit(), paged=self.paged,
                        fused=self.fused)
                else:
                    bucket = _TPUBucket(capacity, pipeline=self.pipeline,
                                        cross_tick=self.cross_tick,
                                        delta_staging=self.delta_staging,
                                        emit=self._resolve_emit(),
                                        paged=self.paged,
                                        fused=self.fused)
            else:
                raise ValueError(f"unknown AOI backend {backend!r}")
            self._buckets[key] = bucket
        slot = bucket.acquire_slot()
        h = SpaceAOIHandle(backend, capacity, bucket, slot,
                           requested=requested)
        self._handles.add(h)
        return h

    def _create_handle(self, capacity: int, tier: str) -> SpaceAOIHandle:
        """Acquire a slot on an EXPLICIT bucket tier (``cpu`` | ``cpp`` |
        ``tpu`` | ``mesh`` | ``rowshard``) -- the placement controller's
        entry point: capacity routing is create_space's job, but a
        migration target chosen by scoring must land exactly where the
        controller said.  ``tier="tpu"`` means the single-chip bucket even
        on a mesh engine (keyed ``tpu-single`` so it never collides with
        the mesh bucket at the same capacity)."""
        capacity = P.round_capacity(capacity)
        if tier in ("cpu", "cpp"):
            return self.create_space(capacity, tier)
        if tier == "rowshard":
            if self.mesh is None or capacity % (self.mesh.n_devices * 128):
                raise ValueError(
                    f"capacity {capacity} cannot row-shard on this engine")
            from .aoi_rowshard import _RowShardTPUBucket

            bucket = _RowShardTPUBucket(
                capacity, self.mesh, pipeline=self.pipeline,
                cross_tick=self.cross_tick,
                delta_staging=self.delta_staging, emit=self._resolve_emit(),
                paged=self.paged, fused=self.fused)
            self._rowshard_serial += 1
            self._buckets[(f"tpu-rowshard-{self._rowshard_serial}",
                           capacity)] = bucket
        elif tier == "mesh":
            if self.mesh is None:
                raise ValueError("tier='mesh' requires a mesh engine")
            key = ("tpu", capacity)
            bucket = self._buckets.get(key)
            if bucket is None:
                from .aoi_mesh import _MeshTPUBucket

                bucket = _MeshTPUBucket(
                    capacity, self.mesh, pipeline=self.pipeline,
                    cross_tick=self.cross_tick,
                    delta_staging=self.delta_staging,
                    emit=self._resolve_emit(), paged=self.paged,
                    fused=self.fused)
                self._buckets[key] = bucket
        elif tier == "tpu":
            key = (("tpu-single", capacity) if self.mesh is not None
                   else ("tpu", capacity))
            bucket = self._buckets.get(key)
            if bucket is None:
                bucket = _TPUBucket(capacity, pipeline=self.pipeline,
                                    cross_tick=self.cross_tick,
                                    delta_staging=self.delta_staging,
                                    emit=self._resolve_emit(),
                                    paged=self.paged,
                                    fused=self.fused)
                self._buckets[key] = bucket
        else:
            raise ValueError(f"unknown placement tier {tier!r}")
        slot = bucket.acquire_slot()
        h = SpaceAOIHandle("tpu", capacity, bucket, slot, requested="tpu")
        self._handles.add(h)
        return h

    def _resolve_emit(self) -> str:
        """Resolve the requested emit mode once (an explicit/auto "native"
        probes -- and on first use builds -- libgwemit; degrading to
        "vector" when the toolchain is absent must not flap per bucket)."""
        if self._emit_resolved is None:
            self._emit_resolved = AE.resolve_mode(self.emit)
        return self._emit_resolved

    # -- space-stacked cohorts (docs/perf.md "Space-stacked cohorts") -----

    def _cohort_bucket(self, shape: int):
        """Get-or-create the shared cohort bucket at a ladder shape.  One
        bucket per shape: membership churn re-buckets spaces between
        ladder rungs, never mints new shapes, so the jit key set -- and
        therefore recompiles -- stays pinned after warmup."""
        key = ("tpu-cohort", shape)
        bucket = self._buckets.get(key)
        if bucket is None:
            from .aoi_cohort import _CohortTPUBucket

            bucket = _CohortTPUBucket(
                shape, pipeline=self.pipeline, cross_tick=self.cross_tick,
                delta_staging=self.delta_staging, emit=self._resolve_emit(),
                paged=self.paged, fused=self.fused)
            self._buckets[key] = bucket
        return bucket

    def _solo_bucket(self, capacity: int):
        """One EXCLUSIVE single-space device bucket: the per-space
        baseline (``cohort="solo"``) and the ``aoi.cohort`` demotion
        target.  ``exclusive`` frees it with its space (release_space);
        ``cohort_solo`` marks it for :meth:`recohort` and maps its tier
        back to ``tpu`` under chip-loss evacuation."""
        self._cohort_serial += 1
        bucket = _TPUBucket(capacity, pipeline=self.pipeline,
                            cross_tick=self.cross_tick,
                            delta_staging=self.delta_staging,
                            emit=self._resolve_emit(), paged=self.paged,
                            fused=self.fused)
        bucket.exclusive = True
        bucket.cohort_solo = True
        self._buckets[(f"tpu-solo-{self._cohort_serial}", capacity)] = bucket
        return bucket

    def _solo_handle(self, capacity: int) -> SpaceAOIHandle:
        bucket = self._solo_bucket(capacity)
        slot = bucket.acquire_slot()
        h = SpaceAOIHandle("tpu", capacity, bucket, slot, requested="tpu")
        self._handles.add(h)
        return h

    def _restack_handle(self, h: SpaceAOIHandle, bucket, shape: int) -> None:
        """Move one live space onto ``bucket`` (capacity ``shape`` >= the
        space's) through the snapshot seam -- the join/leave primitive.
        Runs between flushes; undelivered events and a staged-but-
        undispatched tick are carried, so nothing drops or doubles.
        Snapshot padding is bit-exact: the grown tail is inactive and the
        predicate never reports inactive slots."""
        mig = getattr(h, "_migration", None)
        if mig is not None:
            mig.abort("space re-stacked mid-cover")
        old_bucket, old_slot = h.bucket, h.slot
        snap = AC.pad_snapshot(old_bucket.export_snapshot(old_slot), shape)
        staged = old_bucket._staged.pop(old_slot, None)
        slot = bucket.acquire_slot()
        bucket.import_snapshot(slot, snap)
        pending = old_bucket._events.pop(old_slot, None)
        if pending is not None:
            bucket._events[slot] = pending
        if staged is not None:
            bucket.stage(slot, staged)
        old_bucket.release_slot(old_slot)
        if getattr(old_bucket, "exclusive", False):
            for k, b in list(self._buckets.items()):
                if b is old_bucket:
                    del self._buckets[k]
        stack = getattr(h, "_policy_stack", None)
        if stack is not None and shape != h.capacity:
            stack.grow(shape)
        h.bucket, h.slot = bucket, slot
        h.capacity, h.backend = shape, "tpu"

    def cohort_join(self, h: SpaceAOIHandle) -> SpaceAOIHandle:
        """Stack a live space into the shared cohort bucket at its ladder
        shape (planner stack decision, or re-arming after a demotion).
        In place: the handle object survives, re-pointed."""
        if h.released:
            raise ValueError("space AOI handle already released")
        if self.mesh is not None:
            raise ValueError("cohorts are a single-chip tier")
        shape = AC.cohort_shape(h.capacity, self.cohort_ladder)
        if shape is None:
            raise ValueError(
                f"capacity {h.capacity} is past the cohort ladder "
                f"{self.cohort_ladder}")
        bucket = self._cohort_bucket(shape)
        if h.bucket is bucket:
            return h
        with _T.span("aoi.cohort.join"):
            self._restack_handle(h, bucket, shape)
        self.cohort_stats["cohort_joins"] += 1
        return h

    def cohort_leave(self, h: SpaceAOIHandle) -> SpaceAOIHandle:
        """Un-stack a live space onto its own solo bucket (planner
        keep-solo decision: e.g. one hot space must not gate its cohort's
        shared launch).  In place, like :meth:`cohort_join`."""
        if h.released:
            raise ValueError("space AOI handle already released")
        if not getattr(h.bucket, "cohort", False):
            return h
        with _T.span("aoi.cohort.leave"):
            self._restack_handle(h, self._solo_bucket(h.capacity),
                                 h.capacity)
        self.cohort_stats["cohort_leaves"] += 1
        return h

    def recohort(self) -> int:
        """Re-arm after ``aoi.cohort`` demotions: stack every space now
        sitting on a demoted/planner solo bucket back into its cohort.
        Returns the number of spaces moved.  (The fault seam stays
        one-shot per cohort bucket instance -- a fresh bucket probes the
        seam fresh, so a re-armed plan can fire again.)"""
        moved = 0
        for h in list(self._handles):
            if h.released or not getattr(h.bucket, "cohort_solo", False):
                continue
            self.cohort_join(h)
            moved += 1
        return moved

    def _demote_cohort(self, bucket) -> list:
        """The ``aoi.cohort`` seam fired at this bucket's dispatch (its
        shared program is suspect; nothing was staged to the device this
        tick): rebuild every member space onto its own solo bucket NOW,
        re-staging this tick's inputs, and return the fresh buckets still
        undispatched so flush() runs them under whichever phase
        discipline is active -- the republish is same-tick and bit-exact.
        """
        t0 = time.perf_counter()
        new_buckets: list = []
        with _T.span("aoi.cohort.demote"):
            for m in [m for m in self._migrations
                      if m.h.bucket is bucket or m.t.bucket is bucket]:
                m.abort("cohort demoting to per-space dispatch")
            staged = dict(bucket._staged)
            bucket._staged.clear()
            snaps = bucket.evacuate()
            for k, b in list(self._buckets.items()):
                if b is bucket:
                    del self._buckets[k]
            owners = {h.slot: h for h in self._handles
                      if h.bucket is bucket and not h.released}
            for slot in sorted(snaps):
                h = owners.get(slot)
                if h is None:
                    continue  # no live Space behind the slot
                nb = self._solo_bucket(h.capacity)
                ns = nb.acquire_slot()
                nb.import_snapshot(ns, snaps[slot])
                pending = bucket._events.pop(slot, None)
                if pending is not None:
                    nb._events[ns] = pending
                tick = staged.get(slot)
                if tick is not None:
                    nb.stage(ns, tick)
                h.bucket, h.slot = nb, ns
                self.cohort_stats["cohort_demoted_spaces"] += 1
                new_buckets.append(nb)
        self.migration_stats["migration_ms"] += (
            time.perf_counter() - t0) * 1e3
        return new_buckets

    def release_space(self, h: SpaceAOIHandle) -> None:
        mig = getattr(h, "_migration", None)
        if mig is not None:
            # a space released mid-cover rolls its migration back first --
            # the target slot must not outlive the space
            mig.abort("space released mid-cover")
        if not h.released:
            h.bucket.release_slot(h.slot)
            h.released = True
            if getattr(h.bucket, "exclusive", False):
                # per-space bucket (row-sharded): drop it so its device
                # state frees with the space
                for k, b in list(self._buckets.items()):
                    if b is h.bucket:
                        del self._buckets[k]

    def submit(self, h: SpaceAOIHandle, x, z, radius, active) -> None:
        """Stage one space's tick inputs (numpy arrays of length <= capacity)."""
        if h.released:
            raise ValueError("space AOI handle already released")
        mig = getattr(h, "_migration", None)
        if mig is not None:
            # double-cover: the migration target computes the same ticks
            # from the same inputs until CRC parity confirms the replay
            mig.on_submit(x, z, radius, active)
        h.bucket.stage(h.slot, (x, z, radius, active))

    def flush(self) -> None:
        """Execute all staged steps (one batched kernel per bucket); results
        are then available per space via :meth:`take_events` (one tick late
        when pipelined).

        Split-phase scheduler (docs/perf.md): dispatch EVERY bucket first
        (host pack + delta diff + H2D enqueue + kernel enqueue, never
        blocking on device values), then harvest in dispatch order -- so
        every bucket's kernel is in flight before the first fetch blocks,
        and bucket N+1's device work overlaps bucket N's host decode.
        Buckets iterate in sorted key order so dispatch/harvest order --
        and therefore the fired order of fault-seam occurrences -- is
        independent of space-creation interleaving.  ``flush_sched=False``
        forces the sequential baseline: each bucket dispatches AND
        harvests before the next starts."""
        for m in list(self._migrations):
            m.on_flush_begin()
        buckets = [self._buckets[k] for k in sorted(self._buckets)]
        if not self.flush_sched:
            for bucket in buckets:
                bucket.dispatch()
                if getattr(bucket, "_cohort_demote", False):
                    # aoi.cohort fired at dispatch (before any staging
                    # mutation): rebuild per-space and republish the SAME
                    # tick through the fresh solo buckets
                    for nb in self._demote_cohort(bucket):
                        nb.flush()
                    continue  # the torn-down cohort has nothing to harvest
                bucket.harvest()
        else:
            with _T.span("aoi.dispatch"):
                for bucket in buckets:
                    bucket.dispatch()
                demoting = [b for b in buckets
                            if getattr(b, "_cohort_demote", False)]
                if demoting:
                    for b in demoting:
                        for nb in self._demote_cohort(b):
                            nb.dispatch()
                    # re-list: demoted cohorts are gone, their solo
                    # replacements (already dispatched) must harvest
                    buckets = [self._buckets[k]
                               for k in sorted(self._buckets)]
            with _T.span("aoi.harvest"):
                for bucket in buckets:
                    bucket.harvest()
        if self._migrations:
            # double-cover verification: compare the event deltas both
            # homes produced this flush; swap/abort decisions happen here
            with _T.span("aoi.migrate.cover"):
                for m in list(self._migrations):
                    m.on_flush_end()
        evacuating = [k for k, b in self._buckets.items()
                      if getattr(b, "_evacuating", False)]
        for key in sorted(evacuating):
            self._evacuate_bucket(key)
        # interest-policy stacks evaluate LAST, after bucket harvest (and
        # after any evacuation re-pointed their handles): each staged
        # stack runs one fused step and accumulates its enter/leave diff
        # for take_events.  Stacks are per-space independent, so the
        # iteration order cannot affect results.
        staged = [h for h in self._handles
                  if getattr(h, "_policy_stack", None) is not None
                  and h._policy_stack.has_pending]
        if staged:
            with _T.span("aoi.interest"):
                for h in staged:
                    h._policy_stack.step()

    # -- chip-loss failover (docs/robustness.md) --------------------------

    @staticmethod
    def _tier_of(bucket) -> str:
        """Placement tier of a live bucket (the _create_handle vocabulary)."""
        if getattr(bucket, "cohort", False) \
                or getattr(bucket, "cohort_solo", False):
            # cohort + demoted-solo buckets are single-chip device tiers;
            # chip-loss evacuation re-homes their spaces onto the shared
            # tpu bucket at the same (ladder) capacity -- still stacked
            return "tpu"
        if getattr(bucket, "exclusive", False):
            return "rowshard"
        name = type(bucket).__name__
        if name == "_MeshTPUBucket":
            return "mesh"
        if name == "_TPUBucket":
            return "tpu"
        return ("cpu" if getattr(bucket, "_oracle_cls", None) is CPUAOIOracle
                else "cpp")

    def _evacuate_bucket(self, key) -> None:
        """The bucket's chip is LOST (``aoi.device`` seam, kind ``reset``
        -> faults.DeviceLost).  Its in-flight tick was already recovered
        host-side from (mirror, shadows) by the tier's ``_recover`` -- the
        bucket's host state IS the truth -- so rebuild every live space
        onto a fresh bucket of the same tier (a surviving device) through
        the snapshot/import machinery, carry undelivered events, and
        re-point the handles in place: no restart, no dropped tick, no
        lost or duplicated enter/leave events."""
        bucket = self._buckets[key]
        t0 = time.perf_counter()
        with _T.span("aoi.evacuate"):
            for m in [m for m in self._migrations
                      if m.h.bucket is bucket or m.t.bucket is bucket]:
                m.abort("bucket evacuating after device loss")
            tier = self._tier_of(bucket)
            snaps = bucket.evacuate()
            del self._buckets[key]
            owners = {h.slot: h for h in self._handles
                      if h.bucket is bucket and not h.released}
            for slot in sorted(snaps):
                h = owners.get(slot)
                if h is None:
                    continue  # no live Space behind the slot: nothing to save
                nh = self._create_handle(h.capacity, tier)
                nh.bucket.import_snapshot(nh.slot, snaps[slot])
                pending = bucket._events.pop(slot, None)
                if pending is not None:
                    nh.bucket._events[nh.slot] = pending
                # atomic ownership swap: the Space's handle object never
                # changes, it just points at the new home
                h.bucket, h.slot = nh.bucket, nh.slot
                nh.released = True  # shell handle; h owns the slot now
        self.migration_stats["evacuations"] += 1
        self.migration_stats["migration_ms"] += (
            time.perf_counter() - t0) * 1e3

    def has_pending(self) -> bool:
        """True when a pipelined bucket holds a dispatched-but-unharvested
        tick (the runtime must keep flushing until it drains)."""
        return any(
            getattr(self._buckets[k], "_inflight", None) is not None
            for k in sorted(self._buckets)
        )

    def _telemetry_collect(self):
        """Registry collector: bucket stats/perf summed across this
        engine's buckets (docs/observability.md metric catalog).
        ``calc_level`` reports the WORST bucket -- any demoted calculator
        should page, however many healthy ones sit next to it."""
        lbl = {"engine": str(self._telemetry_id)}
        stats: dict[str, float] = {}
        perf: dict[str, float] = {}
        calc_level = 0
        emit_path = 0
        page_occ = 0.0
        for b in (self._buckets[k] for k in sorted(self._buckets)):
            for k, v in getattr(b, "stats", {}).items():
                if k == "calc_level":
                    calc_level = max(calc_level, v)
                elif k == "emit_path":
                    # like calc_level: the WORST bucket -- one demoted emit
                    # path should page even among healthy neighbors
                    emit_path = max(emit_path, v)
                elif k == "page_occupancy":
                    # gauge, not a counter: the FULLEST pool -- the bucket
                    # closest to spilling is the one capacity planning
                    # must see
                    page_occ = max(page_occ, v)
                else:
                    stats[k] = stats.get(k, 0) + v
            for k, v in getattr(b, "perf", {}).items():
                perf[k] = perf.get(k, 0.0) + v
        cohorts = sum(1 for b in self._buckets.values()
                      if getattr(b, "cohort", False))
        cohort_spaces = sum(1 for h in self._handles
                            if not h.released
                            and getattr(h.bucket, "cohort", False))
        out = [Sample("aoi.buckets", "gauge", len(self._buckets), lbl,
                      "live AOI buckets in this engine"),
               Sample("aoi.cohorts", "gauge", cohorts, lbl,
                      "live cohort buckets (space-stacked planes)"),
               Sample("aoi.cohort_spaces", "gauge", cohort_spaces, lbl,
                      "spaces currently stacked into cohort buckets"),
               Sample("aoi.calc_level", "gauge", calc_level, lbl,
                      "worst calculator fallback level "
                      "(0=pallas 1=dense 2=host oracle)"),
               Sample("aoi.emit_path", "gauge", emit_path, lbl,
                      "worst emit-path fallback level "
                      "(0=native 1=vector 2=host decode)"),
               Sample("aoi.page_occupancy", "gauge", page_occ, lbl,
                      "fullest page pool at last harvest "
                      "(used/total pages; paged buckets only)")]
        for k in sorted(stats):
            out.append(Sample("aoi." + k, "counter", stats[k], lbl,
                              "summed per-bucket AOI stat"))
        for k in sorted(perf):
            out.append(Sample("aoi." + k.replace("_s", "_seconds"), "counter",
                              perf[k], lbl,
                              "cumulative per-phase flush time"))
        ms = self.migration_stats
        out.append(Sample("aoi.migrations", "counter", ms["migrations"], lbl,
                          "completed live space migrations"))
        out.append(Sample("aoi.evacuations", "counter", ms["evacuations"],
                          lbl, "bucket evacuations after chip loss"))
        out.append(Sample("aoi.migration_rollbacks", "counter",
                          ms["migration_rollbacks"], lbl,
                          "migrations aborted back to their source bucket"))
        out.append(Sample("aoi.migration_ms", "counter",
                          ms["migration_ms"], lbl,
                          "cumulative migration/evacuation wall time (ms)"))
        cs = self.cohort_stats
        out.append(Sample("aoi.cohort_joins", "counter", cs["cohort_joins"],
                          lbl, "spaces stacked into a cohort live"))
        out.append(Sample("aoi.cohort_leaves", "counter",
                          cs["cohort_leaves"], lbl,
                          "spaces un-stacked onto solo buckets"))
        out.append(Sample("aoi.cohort_demoted_spaces", "counter",
                          cs["cohort_demoted_spaces"], lbl,
                          "spaces rebuilt per-space by aoi.cohort "
                          "demotions"))
        return out

    def attach_interest(self, h: SpaceAOIHandle, policies,
                        mode: str | None = None):
        """Attach a composable interest-policy stack to a space
        (goworld_tpu/interest/): from here on the stack's fused step --
        radius AND team mask AND tier cadence AND line of sight -- owns
        the space's event stream (:meth:`take_events` returns the
        stack's diff), while the base bucket keeps carrying the radius
        state through migration/checkpoint/growth untouched.  A restore
        snapshot stashed on the handle (``_interest_snapshot``, set by
        checkpoint.restore_into) is imported automatically so policy
        state rides the pad_packet payload format end to end."""
        from ..interest import PolicyStack

        if getattr(h, "_policy_stack", None) is not None:
            raise ValueError("space already has an interest stack")
        stack = PolicyStack(h.capacity, policies,
                            mode=mode or self.interest_mode)
        snap = getattr(h, "_interest_snapshot", None)
        if snap is not None:
            stack.import_payload(snap)
            h._interest_snapshot = None
        h._policy_stack = stack
        return stack

    @staticmethod
    def interest_stack(h: SpaceAOIHandle):
        """The space's PolicyStack, or None (plain radius-only space)."""
        return getattr(h, "_policy_stack", None)

    def take_events(self, h: SpaceAOIHandle):
        """(enter_pairs, leave_pairs) for this space from the last flush."""
        stack = getattr(h, "_policy_stack", None)
        if stack is not None:
            # the stack owns the stream: drop the bucket's base-predicate
            # diff (the bucket still computes/carries base state -- that
            # is what migration double-cover and checkpoints verify)
            h.bucket.take_events(h.slot)
            return stack.take_events()
        return h.bucket.take_events(h.slot)

    def set_subscribed(self, h: SpaceAOIHandle, flag: bool) -> None:
        """Opt a space in/out of the per-tick event stream (see
        _Bucket.set_subscribed).  Spaces whose entities are all plain opt
        out: device backends then skip their extraction/fetch/decode
        entirely and their interest state is derived on demand."""
        mig = getattr(h, "_migration", None)
        if mig is not None:  # keep the double-cover target in lockstep
            mig.t.bucket.set_subscribed(mig.t.slot, flag)
        h.bucket.set_subscribed(h.slot, flag)

    def clear_entity(self, h: SpaceAOIHandle, entity_slot: int) -> None:
        """Erase one entity's row and column from the space's previous-tick
        interest state.  Called when an entity leaves the space: the runtime
        severs its interest pairs synchronously (departure events must fire
        the same tick), so the calculator must not re-emit them as diffs --
        and a reused slot must start clean."""
        mig = getattr(h, "_migration", None)
        if mig is not None:  # keep the double-cover target in lockstep
            mig.t.bucket.clear_entity(mig.t.slot, entity_slot)
        h.bucket.clear_entity(h.slot, entity_slot)
        stack = getattr(h, "_policy_stack", None)
        if stack is not None:
            stack.clear_entity(entity_slot)

    def grow_space(self, h: SpaceAOIHandle, new_capacity: int) -> SpaceAOIHandle:
        """Move a space to a larger-capacity bucket, carrying its interest
        state so the growth itself emits no enter/leave events.

        The packed layout depends on capacity (planar: bit positions shuffle
        when W changes), so the carry-over repacks via the boolean matrix.
        Growth is rare (capacity doubles), so the host-side repack is fine.
        """
        new_capacity = P.round_capacity(new_capacity)
        if new_capacity <= h.capacity:
            raise ValueError("grow_space requires a larger capacity")
        mig = getattr(h, "_migration", None)
        if mig is not None:
            # growth changes the packed layout mid-cover; roll the
            # migration back (zero loss) and let the controller retry
            mig.abort("space grown mid-cover")
        nh = self.create_space(new_capacity, h.requested or h.backend)
        # cohort routing may round the new home UP to its ladder shape;
        # repack to the capacity the new bucket actually allocates
        target = nh.capacity
        old_words = h.bucket.get_prev(h.slot)
        ratio = target // h.capacity
        if target == h.capacity * ratio and ratio & (ratio - 1) == 0:
            # power-of-two growth (every Space growth: capacity doubles):
            # packed word-level column remap, no dense matrix -- the dense
            # path is O(C^2) host BYTES, 17 GB at C=131072 (the oversized
            # capacities the row-sharded calculator serves)
            cap = h.capacity
            words = old_words
            while cap < target:
                words = P.repack_columns_double(words, cap)
                cap *= 2
            packed = np.zeros((target, words.shape[1]), np.uint32)
            packed[: h.capacity] = words
        else:
            m = P.unpack_rows(old_words, h.capacity)
            grown = np.zeros((target, target), bool)
            grown[: h.capacity, : h.capacity] = m
            packed = P.pack_rows(grown)
        nh.bucket.set_prev(nh.slot, packed)
        # carry undelivered events: growth can happen between flush() and
        # dispatch_aoi_events() (e.g. an on_enter_aoi hook spawns entities);
        # dropping them would permanently desync interest sets
        pending = h.bucket._events.pop(h.slot, None)
        if pending is not None:
            nh.bucket._events[nh.slot] = pending
        stack = getattr(h, "_policy_stack", None)
        if stack is not None:
            # the interest stack grows with the space: same planar column
            # remap as the base carry above, then it rides the NEW handle
            stack.grow(target)
            nh._policy_stack = stack
            h._policy_stack = None
        self.release_space(h)
        return nh


class _Bucket:
    """Slot-managed batch of spaces sharing a backend and capacity."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.W = P.words_per_row(capacity)
        self.n_slots = 0
        self._free: list[int] = []
        self._staged: dict[int, tuple] = {}
        self._events: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    def acquire_slot(self) -> int:
        if self._free:
            slot = self._free.pop()
        else:
            slot = self.n_slots
            self.n_slots += 1
            self._grow_to(self.n_slots)
        self._reset_slot(slot)
        return slot

    def release_slot(self, slot: int) -> None:
        self._free.append(slot)
        self._staged.pop(slot, None)
        self._events.pop(slot, None)

    def stage(self, slot: int, staged: tuple) -> None:
        self._staged[slot] = staged

    def take_events(self, slot: int):
        return self._events.pop(slot, (np.empty((0, 2), np.int32),) * 2)

    def set_subscribed(self, slot: int, flag: bool) -> None:
        """Event-stream subscription.  A slot whose space has no event
        consumers (all entities plain: no client, default hooks) may opt out
        of the per-tick event stream entirely -- its interest state stays in
        the packed device words, derived on demand (Space.derive_interests).
        Default: subscribed.  Host backends ignore this (their events are a
        free by-product of the sweep); device backends skip the extraction,
        fetch, and decode for opted-out slots."""

    def reset_emit_path(self) -> None:
        """Re-arm the configured emit path after an ``aoi.emit`` demotion
        (operator action, like reset_calc_chain -- demotion is sticky so a
        flapping native layer cannot oscillate).  No-op for host buckets,
        which have no emit seam."""
        req = getattr(self, "_emit_requested", None)
        if req is not None:
            self._emit = req
            self.stats["emit_path"] = AE.EMIT_LEVEL[req]

    # subclass API
    def _grow_to(self, n_slots: int) -> None:
        raise NotImplementedError

    def _reset_slot(self, slot: int) -> None:
        raise NotImplementedError

    def flush(self) -> None:
        raise NotImplementedError

    def dispatch(self) -> None:
        """Phase 1 of the split flush (docs/perf.md): enqueue this tick's
        device work without blocking on device values.  Host-only buckets
        dispatch-and-complete inline -- the default delegates to
        :meth:`flush` -- so their harvest is a no-op.  Device buckets
        override both phases."""
        self.flush()

    def harvest(self) -> None:
        """Phase 2 of the split flush: fetch + decode whatever
        :meth:`dispatch` enqueued (no-op for inline buckets)."""

    def drain(self) -> None:
        """Deliver any pipelined tick still in flight (no-op by default)."""

    def peek_words(self, slot: int) -> np.ndarray | None:
        """Current interest words [C, W] for a slot WITHOUT forcing a device
        round trip -- the backing store for lazily derived interest sets
        (Space.derive_interests).  None when no cheap host copy exists yet
        (the caller then falls back to :meth:`get_prev`)."""
        return None

    def get_prev(self, slot: int) -> np.ndarray:
        """Previous-tick interest words [C, W] for state carry-over."""
        raise NotImplementedError

    def set_prev(self, slot: int, words: np.ndarray) -> None:
        raise NotImplementedError

    def clear_entity(self, slot: int, entity_slot: int) -> None:
        raise NotImplementedError


class _CPUBucket(_Bucket):
    """Host-side bucket; ``oracle_cls`` picks the python sweep oracle (the
    parity reference) or the native C++ sweep (ops.aoi_native, the
    production host calculator -- reference role: go-aoi XZList)."""

    def __init__(self, capacity: int, algorithm: str,
                 oracle_cls=CPUAOIOracle):
        super().__init__(capacity)
        self.algorithm = algorithm
        self._oracle_cls = oracle_cls
        self._oracles: list = []
        # last flushed inputs per slot (REFERENCES, not copies -- the host
        # hot path must not pay per-tick array copies; export_snapshot
        # copies on demand).  The migration snapshot's position packet is
        # built from these.
        self._last: dict[int, tuple] = {}
        # phase-attribution counters (seconds, cumulative; bench_engine
        # reads deltas) -- a perf_counter pair per flush, noise-level cost
        self.perf = {"calc_s": 0.0}

    def _grow_to(self, n_slots: int) -> None:
        while len(self._oracles) < n_slots:
            self._oracles.append(
                self._oracle_cls(self.capacity, self.algorithm)
            )

    def _reset_slot(self, slot: int) -> None:
        self._oracles[slot].reset()
        self._last.pop(slot, None)

    def flush(self) -> None:
        t0 = time.perf_counter()
        _ts = _T.t()
        for slot, (x, z, r, act) in self._staged.items():
            self._events[slot] = self._oracles[slot].step(x, z, r, act)
            self._last[slot] = (x, z, r, act)
        self._staged.clear()
        _T.lap("aoi.kernel", _ts)
        self.perf["calc_s"] += time.perf_counter() - t0

    def export_snapshot(self, slot: int) -> dict:
        """Live-migration wire image of one slot (docs/robustness.md): the
        last flushed inputs as a delta-staging packet + the previous-tick
        interest words.  Inputs are the staged REFERENCES -- callers
        migrate between ticks, after flush and before the next submit, so
        the arrays still hold the flushed values."""
        last = self._last.get(slot)
        if last is None:
            c = self.capacity
            last = (np.zeros(c, np.float32), np.zeros(c, np.float32),
                    np.zeros(c, np.float32), np.zeros(c, bool))
        x, z, r, act = last
        xx = np.zeros(self.capacity, np.float32)
        zz = np.zeros(self.capacity, np.float32)
        rr = np.zeros(self.capacity, np.float32)
        aa = np.zeros(self.capacity, bool)
        n = len(x)
        xx[:n], zz[:n], rr[:n], aa[:n] = x, z, r, act
        return _build_snapshot(self.capacity, xx, zz, rr, aa, True,
                               self._oracles[slot].prev_words)

    def import_snapshot(self, slot: int, snap: dict) -> None:
        """Replay a migration snapshot onto this slot: reconstruct the
        input arrays from the packet (so a later re-export round-trips)
        and seed the oracle's previous-tick words."""
        if snap["capacity"] != self.capacity:
            raise ValueError(
                f"snapshot capacity {snap['capacity']} != bucket "
                f"capacity {self.capacity}")
        x, z = _unpack_positions(snap)
        self._last[slot] = (x, z, snap["r"].copy(), snap["act"].copy())
        self.set_prev(slot, snap["words"])

    def peek_words(self, slot: int) -> np.ndarray:
        return self._oracles[slot].prev_words

    def get_prev(self, slot: int) -> np.ndarray:
        return self._oracles[slot].prev_words.copy()

    def set_prev(self, slot: int, words: np.ndarray) -> None:  # gwlint: allow[host-sync] -- CPU-backend bucket: state is already host-resident
        self._oracles[slot].prev_words = np.asarray(words, np.uint32).copy()

    def clear_entity(self, slot: int, entity_slot: int) -> None:
        pw = self._oracles[slot].prev_words
        pw[entity_slot, :] = 0
        w, b = P.word_bit_for_column(entity_slot, self.capacity)
        pw[:, w] &= np.uint32(~(np.uint32(1) << np.uint32(b)) & 0xFFFFFFFF)


class _TPUBucket(_Bucket):
    """Device-resident interest state [S, C, W]; one fused kernel per flush.

    S (slot count) grows by doubling; interest state is preserved across
    growth by zero-padding new slots.  Unstaged slots step with their previous
    inputs absent -- their rows are marked inactive so they emit leave events
    only if they had interests and were explicitly reset (slot reuse), never
    spontaneously: a space that skips a tick simply re-submits nothing and its
    previous words are carried forward untouched (active=False would wipe
    them, so unstaged slots are skipped via a host-side mask and their
    prev rows rewritten unchanged).

    ``pipeline=True`` double-buffers the flush (SURVEY §7 hard part (d)):
    ``flush()`` dispatches tick T's device step and then harvests tick T-1's
    results -- whose scalar+stream D2H transfers were issued asynchronously
    at T-1's dispatch with optimistically sized slices, so the wire time
    overlaps the whole host tick between the two flushes.  Events are
    therefore delivered ONE TICK LATE (the documented latency/throughput
    trade; parity is bit-exact modulo the shift -- tests/test_aoi_engine.py
    test_pipelined_flush_parity).  ``drain()`` harvests a pending tick
    without dispatching a new one (shutdown, state carry-over, tests).

    ``cross_tick=True`` (the engine's ``aoi_cross_tick``) requests the
    SAME one-tick deferral as the scheduler-level contract: tick T+1's
    pack + H2D + kernel enqueue overlaps tick T's harvest because the
    dispatched record parks one flush before delivering.  It composes
    idempotently with ``pipeline`` -- either flag (or both) defers by
    exactly one tick, so every flag combination stays bit-exact modulo
    the same single shift (tests/test_cross_tick.py).  Fault recovery is
    unchanged: a fault during T's harvest cannot corrupt T+1's already-
    dispatched state because _recover/_recover_harvest rebuild from the
    columnar host shadows and re-park synthetic host records
    (docs/robustness.md).
    """

    def __init__(self, capacity: int, pipeline: bool = False,
                 delta_staging: bool = True, emit: str = "vector",
                 paged: bool = False, cross_tick: bool = False,
                 fused: bool = False):
        super().__init__(capacity)
        self.pipeline = pipeline
        self.cross_tick = bool(cross_tick)
        self.delta_staging = delta_staging
        # fused steady tick (docs/perf.md "Fused tick", ROADMAP #3): when
        # eligible, the whole dispatch compiles into ONE program
        # (ops/aoi_fused: scatter + kernel + diff + extraction/paging),
        # so the steady cost is one enqueue + one D2H fetch.  Unfused is
        # the A/B baseline and the demotion target: an aoi.* seam firing
        # in the fused attempt falls through to the unfused flow in the
        # same call, counted in fused_demotions, bit-exact same-tick.
        self.fused = bool(fused)
        # paged ragged storage (docs/perf.md paged storage): the change
        # stream compacts into fixed-size pages from an on-device free
        # list (ops/aoi_pages) instead of the capped triples/chunk
        # buffers -- no global per-tick cap, so decode_overflow cannot
        # fire; bins the pool cannot serve spill to host (counted in
        # page_spills, republished same-tick bit-exact) and re-arm the
        # pool through _PageDecay
        self.paged = bool(paged)
        self._n_pages = 0           # pool size; sized at first dispatch
        self._page_free = None      # device free list [n_pages] int32
        self._pages: _PageDecay | None = None
        self._pred_pages = 64       # optimistic page prefetch (pipeline)
        # emit fan-out path (docs/perf.md): "native"/"vector" run the
        # device-resident triples decode (_fused_bucket_step_tri) and fan
        # out through ops/aoi_emit; "host" keeps the classic encoded-stream
        # fetch + host decode as the bit-exact oracle.  _emit_requested is
        # what reset_emit_path re-arms after an aoi.emit demotion.
        self._emit = emit
        self._emit_requested = emit
        self._inflight = None  # pending dispatch awaiting harvest
        # split-phase flush (docs/perf.md): dispatch() parks what harvest()
        # must do here -- ("inflight",) = drain the inflight record,
        # ("rec", rec) = harvest a specific record, ("oracle", slots) =
        # level-2 host compute deferred past the other buckets' dispatches
        self._sched: tuple | None = None
        # per-slot release epoch: a pipelined harvest must NOT publish
        # events for a slot released (and possibly reused) after its
        # dispatch -- the new occupant would replay the dead space's pairs
        self._slot_epoch: dict[int, int] = {}
        # mirror maintenance ops (clears/resets) issued while a dispatched
        # tick is still in flight: they postdate that tick's change stream,
        # so they must apply AFTER its XOR at harvest, not immediately --
        # else the XOR re-plants bits the clear just removed
        self._mirror_ops: list[tuple] = []
        import jax.numpy as jnp

        self._jnp = jnp
        self.s_max = 0
        self.prev = None  # [S, C, W] uint32 device array
        self._pending_reset: set[int] = set()
        self._pending_clear: list[tuple[int, int]] = []  # (slot, entity_slot)
        # adaptive extraction caps; a tick that exceeds them is recovered
        # host-side from the full diff and the caps grow for the next tick;
        # _CapDecay shrinks them back toward the steady state
        self._max_chunks = 4096
        self._kcap = 8
        self._caps = _CapDecay(nd_floor=4096)
        # triples-path extraction cap (native/vector emit): grows on a
        # counted overflow up to _TRI_MAX, decays back via _tri
        self._max_triples = 16384
        self._tri = _TriCapDecay(floor=16384)
        # optimistic triple-buffer prefetch rows for the pipelined path
        self._pred_tri = 2048
        # donated scratch buffers, keyed (s_n, mc, kcap); replaced by each
        # flush's returns (same device memory, in-place)
        self._scratch: dict[tuple, tuple] = {}
        # encode-side caps (instance attrs so overflow tests can shrink them)
        self._max_gaps = _MAX_GAPS
        self._max_exc = _MAX_EXC
        # optimistic prefetch sizes for the pipelined path (rows, escapes,
        # exceptions) -- refit to each harvested tick
        self._pred = (512, 64, 256)
        # host mirror of the interest words, enabled lazily on the first
        # peek_words (lazy interest-set derivation): one device fetch to
        # seed, then one vectorized XOR of each harvested tick's change
        # stream -- no per-tick fetches
        self._mirror: np.ndarray | None = None
        # slots opted OUT of the event stream (set_subscribed(False)):
        # their changes are masked out of the extraction on device, so
        # their mirror rows go stale -- tracked in _mirror_stale and
        # refreshed from device on the next peek of that slot
        self._unsub: set[int] = set()
        self._mirror_stale: set[int] = set()
        # delta staging (the _h2d role cache grown into full device
        # residency): persistent HOST SHADOWS of the staged inputs
        # [s_max, C] (+ sub [s_max]) and matching DEVICE copies in _dev.
        # The shadow and the device copy are kept BITWISE identical --
        # flush() diffs newly staged values against the shadow (uint32 bit
        # patterns, so NaN payloads and -0.0/0.0 cannot silently diverge)
        # and ships only a compact (row, col, x, z) packet
        # (ops/aoi_stage.py); _dev_stale names the roles whose device copy
        # no longer matches the shadow and must be fully re-uploaded
        # (grow/reset, r/act/sub change -- the full-restage fallbacks).
        self._hx = np.zeros((0, capacity), np.float32)
        self._hz = np.zeros((0, capacity), np.float32)
        self._hr = np.zeros((0, capacity), np.float32)
        self._hact = np.zeros((0, capacity), bool)
        self._hsub = np.ones(0, bool)
        self._dev: dict[str, object] = {}
        self._dev_stale: set[str] = {"xz", "ra", "sub"}
        # delta path bails to a full restage past this changed fraction:
        # scatter cost grows with the packet while the full upload is flat
        self._delta_max_frac = 0.25
        # -- fault tolerance (docs/robustness.md) ------------------------
        # With a fault plan active the mirror is kept EAGERLY from slot 0:
        # it is the durable copy of the interest state the rebuild path
        # re-uploads after a device loss.  (Without a plan it stays lazy --
        # no behavior change for fault-free runs; a real device fault then
        # recovers via a best-effort prev fetch / shadow recompute.)
        self._ft = faults.active()
        self._need_rebuild = False   # device prev dropped; re-upload next flush
        # chip-loss failover: True after a DeviceLost recovery -- the
        # engine rebuilds every live slot onto a fresh bucket at the end
        # of the current flush (docs/robustness.md)
        self._evacuating = False
        # calculator fallback chain: 0 = platform default (pallas on TPU),
        # 1 = dense formulation, 2 = host oracle (device never touched).
        # Each kernel-phase fault demotes one level; reset_calc_chain()
        # re-arms the device path.
        self._calc_level = 0
        self._fault_phase = "stage"
        self._cur_slots: list[int] = []
        # H2D attribution (bench artifact): cumulative wire bytes actually
        # shipped and how often the sparse-packet path won.  The fault
        # counters ride along: rebuilds = device-state drops recovered from
        # the durable copy, fallbacks = calculator demotions, host_ticks =
        # ticks computed by the host oracle (recovery or level-2 mode),
        # poisoned = control-scalar corruptions caught by validation.
        # emit-path additions: decode_overflow = ticks whose compact decode
        # overflowed its cap and fell back to a counted full recovery;
        # emit_path = the fan-out level actually in use (0=native 1=vector
        # 2=host decode), surfaced like calc_level as a max gauge.
        # paged-path additions: page_spills = bins (or whole ticks) the
        # page pool could not serve, re-read from the kept change grid and
        # republished same-tick (counted, never silent); page_occupancy =
        # used/total pages at the last harvest (gauge, worst bucket wins)
        # fused-path additions: fused_dispatches = steady ticks that ran
        # as one program, fused_demotions = fused attempts a seam fault
        # demoted to the unfused flow (same call, bit-exact)
        self.stats = {"h2d_bytes": 0, "delta_flushes": 0, "full_flushes": 0,
                      "rebuilds": 0, "fallbacks": 0, "host_ticks": 0,
                      "poisoned": 0, "calc_level": 0,
                      "decode_overflow": 0,
                      "page_spills": 0, "page_occupancy": 0.0,
                      "fused_dispatches": 0, "fused_demotions": 0,
                      "emit_path": AE.EMIT_LEVEL[emit]}
        # phase-attribution counters (seconds, cumulative): stage = host
        # pack + H2D enqueue + dispatch, fetch = synchronous D2H waits,
        # decode = stream decode + mirror upkeep, emit = event fan-out +
        # publish (triples path; the classic host path lumps expansion
        # into decode_s as before).  bench_engine reads deltas to
        # attribute engine ms/tick between host logic, wire, and decode.
        self.perf = {"stage_s": 0.0, "fetch_s": 0.0, "decode_s": 0.0,
                     "emit_s": 0.0}

    @property
    def _defer(self) -> bool:
        """One-tick event deferral in effect.  ``pipeline`` and
        ``cross_tick`` request the SAME deferral mechanics (park the
        dispatched record one flush, prefetch its D2H async), so either
        flag -- or both -- shifts delivery by exactly one tick and the
        parity contract stays a single shift for every combination."""
        return self.pipeline or self.cross_tick

    @property
    def _steady(self) -> bool:
        """No cap recompile pending (see _CapDecay/_TriCapDecay/_PageDecay;
        benchmarks read this)."""
        if self.paged:
            return self._pages is not None and self._pages.steady
        if self._emit != "host":
            return self._tri.steady
        return self._caps.steady

    def _grow_to(self, n_slots: int) -> None:
        jnp = self._jnp
        if n_slots <= self.s_max:
            return
        new_s = max(1, self.s_max)
        while new_s < n_slots:
            new_s *= 2
        if self._need_rebuild or self._calc_level >= 2:
            # device copy is already down: the mirror below is the durable
            # copy and grows host-side; the next rebuild uploads it grown
            self.prev = None
        else:
            try:
                faults.check("aoi.grow")
                new_prev = jnp.zeros((new_s, self.capacity, self.W),
                                     jnp.uint32)
                if self.prev is not None and self.s_max > 0:
                    new_prev = new_prev.at[: self.s_max].set(self.prev)
                self.prev = new_prev
            except Exception as e:
                if not _device_fault(e):
                    raise
                # allocation of the GROWN state failed; the old prev is
                # intact, so the durable copy seeds exactly, then grows
                # host-side with the rest of this method
                self._ensure_mirror()
                self.stats["rebuilds"] += 1
                self.prev = None
                self._need_rebuild = True
                from ..utils import gwlog

                gwlog.logger("gw.aoi").warning(
                    "bucket grow to %d slots hit a device fault (%s); "
                    "state held in the host mirror until the next flush "
                    "rebuilds", new_s, e)
        if self._mirror is not None:
            grown = np.zeros((new_s, self.capacity, self.W), np.uint32)
            grown[: self._mirror.shape[0]] = self._mirror
            self._mirror = grown
        elif self._ft:
            # fault-tolerant mode keeps the durable copy from the start
            # (a fresh bucket's interest state is all-zero, so no fetch)
            self._mirror = np.zeros((new_s, self.capacity, self.W),
                                    np.uint32)
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
        # device copies are the old shape: full restage on the next flush
        self._dev.clear()
        self._dev_stale = {"xz", "ra", "sub"}
        self.s_max = new_s

    def _reset_slot(self, slot: int) -> None:
        self._pending_reset.add(slot)
        self._unsub.discard(slot)  # subscription is per-occupant; default on
        # the shadow must match what the next flush stages for this slot
        # (zeros until the new occupant stages); the device copies now
        # diverge -> full restage (the ISSUE's grow/reset fallback)
        self._hx[slot] = 0.0
        self._hz[slot] = 0.0
        self._hr[slot] = 0.0
        self._hact[slot] = False
        self._hsub[slot] = True
        self._dev_stale.update(("xz", "ra", "sub"))
        self._mirror_stale.discard(slot)  # mirror row is reset to truth below
        if self._mirror is not None:
            # immediate even with a tick in flight: the harvest XOR is
            # epoch-guarded, so a dead epoch's stream can no longer re-plant
            # bits over this reset, and derivations between now and the next
            # flush must already see the slot empty
            self._mirror_apply_now(("reset", slot))

    def set_subscribed(self, slot: int, flag: bool) -> None:
        if flag:
            self._unsub.discard(slot)
        else:
            self._unsub.add(slot)
        if slot < self._hsub.shape[0] and self._hsub[slot] != flag:
            self._hsub[slot] = flag
            self._dev_stale.add("sub")

    def peek_words(self, slot: int) -> np.ndarray:  # gwlint: allow[host-sync] -- parity/debug accessor, off the tick path
        """Host mirror of the slot's interest words.  First call seeds the
        mirror with one device fetch (after draining any pipelined tick so
        mirror and delivered events agree); afterwards each harvest keeps it
        current with a vectorized XOR of the decoded change stream."""
        if self._mirror is None:
            self.drain()
            # explicit copy=True + order="C" are BOTH load-bearing: a fetched
            # device array can carry the TPU's tiled strides (a non-C mirror
            # would make the harvest's reshape-XOR write to a silent copy),
            # and on the cpu backend np.asarray is a zero-copy READ-ONLY
            # view (the XOR would raise)
            self._mirror = (np.zeros((self.s_max, self.capacity, self.W),
                                     np.uint32)
                            if self.prev is None
                            else np.array(self.prev, np.uint32, copy=True,
                                          order="C"))
        elif slot in self._mirror_stale:
            # the slot's changes were masked out of the stream while it was
            # unsubscribed: refresh its rows from the device truth (one
            # [C, W] slice fetch, on demand -- the whole point is that quiet
            # plain spaces never pay this unless someone actually asks).
            # flush() first so pending maintenance (resets/clears) reaches
            # prev before the read; drain() so the refreshed row and the
            # delivered events agree.
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

    def flush(self) -> None:
        """Monolithic flush = dispatch immediately followed by harvest (the
        forced-sequential baseline; AOIEngine's scheduler calls the phases
        directly to overlap buckets -- docs/perf.md)."""
        self.dispatch()
        self.harvest()

    def dispatch(self) -> None:
        """Phase 1: drain maintenance, pack + diff + H2D-enqueue this tick's
        inputs and enqueue the jitted kernel -- never blocking on device
        values (gwlint flush-phase rule).  What remains to be fetched is
        parked in ``_sched`` for :meth:`harvest`."""
        if self._sched is not None:
            # re-entrant flush (get_prev/peek_words mid-scheduler): complete
            # the previous phase pair before dispatching anew
            self.harvest()  # gwlint: allow[flush-phase] -- re-entrant flush drains the prior dispatch first
        if not self._staged and not self._pending_reset and not self._pending_clear:
            # pipelined: a tick with nothing new still delivers the pending
            # tick's events (trailing flush)
            if self._inflight is not None:
                self._sched = ("inflight",)
            return
        if self._calc_level >= 2:
            # calculator fallback chain bottom: host-oracle mode -- the
            # device is out of the loop; maintenance already reached the
            # mirror (its device queues just drain) and the host compute
            # itself defers to harvest so it overlaps other buckets'
            # device work under the scheduler
            self._pending_reset.clear()
            self._pending_clear.clear()
            if not self._staged:
                if self._inflight is not None:
                    self._sched = ("inflight",)
                return
            self._sched = ("oracle", self._restage_shadows())
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
        """Phase 2: block on whatever :meth:`dispatch` parked -- the D2H
        fetch + decode of the encoded event stream (or the deferred host
        oracle tick).  A device fault surfacing here (async dispatch:
        kernel errors materialize at the blocking fetch) recovers via
        :meth:`_recover_harvest`."""
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

    def _dispatch_device(self) -> None:
        import jax.numpy as jnp

        c = self.capacity
        self._fault_phase = "stage"
        # device health probe: kind ``reset`` = the chip is LOST
        # (faults.DeviceLost) -- recovery must land on a different device,
        # so dispatch()'s handler marks the bucket evacuating after the
        # standard host-side tick recovery
        faults.check("aoi.device")
        self._rebuild_device()
        if self._pending_reset:
            idx = jnp.asarray(sorted(self._pending_reset), jnp.int32)
            DC.record()
            self.prev = self.prev.at[idx].set(jnp.uint32(0))
            self._pending_reset.clear()
        if self._pending_clear:
            # combine repeated (slot, word) column masks host-side so the
            # scatter indices are unique, then apply everything in ONE
            # dispatch (k clears used to cost 2k round trips)
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

            def pad(seq):  # repeat the last entry up to a power of two
                n = 1
                while n < len(seq):
                    n *= 2
                return seq + [seq[-1]] * (n - len(seq))

            rows = pad(rows)
            cols = pad(cols)
            DC.record()
            self.prev = _batched_clear(
                self.prev,
                jnp.asarray([s for s, _ in rows], jnp.int32),
                jnp.asarray([e for _, e in rows], jnp.int32),
                jnp.asarray([s for s, _, _ in cols], jnp.int32),
                jnp.asarray([w for _, w, _ in cols], jnp.int32),
                jnp.asarray([m for _, _, m in cols], jnp.uint32),
            )
        if not self._staged:
            # maintenance-only tick: nothing dispatched, but a pending
            # pipelined tick still delivers -- at harvest time
            if self._inflight is not None:
                self._sched = ("inflight",)
            return

        t_stage0 = time.perf_counter()
        _ts = _T.t()
        slots = sorted(self._staged)
        s_n = len(slots)
        sl = np.array(slots, np.intp)
        # restage into the persistent host shadow; the previously staged
        # values are saved first (fancy index -> compact copies) so
        # _stage_inputs can diff the new tick against them
        old_x, old_z = self._hx[sl], self._hz[sl]
        old_r, old_act = self._hr[sl], self._hact[sl]
        self._restage_shadows()
        self._cur_slots = slots  # recovery needs them once _staged is gone

        slot_idx = jnp.asarray(slots, jnp.int32)
        tri_mode = self._emit != "host" and not self.paged
        if self.paged:
            # paged path (docs/perf.md paged storage): the change stream
            # compacts into pages from the device-resident free list; the
            # scratch key uses mc=-2 as the paged namespace.  The pool is
            # (re)sized here: first dispatch seeds the floor, spills grow
            # it (bounded by pool_ceiling), _PageDecay shrinks it back --
            # a size change just reinitializes the free list.
            nw = s_n * c * self.W
            bw = PG.bin_words_for(self.W)
            if self._pages is None:
                self._pages = _PageDecay(floor=PG.pool_floor(nw))
            # the decay's floor (not a recomputed one) sizes the first
            # pool, so tests can preset a tiny _PageDecay to force spills
            want = max(self._n_pages, self._pages.floor)
            if self._page_free is None or want != self._n_pages \
                    or self._page_free.shape[0] != want:
                self._n_pages = want
                self._page_free = jnp.arange(want, dtype=jnp.int32)
            key = (s_n, -2, self._n_pages)
        elif tri_mode:
            # triples path (docs/perf.md emit paths): the decode happens ON
            # DEVICE; harvest fetches [count, 3] triples + one scalar.  The
            # scratch key uses mc=-1 as the tri namespace (classic mc >= 512)
            mt = self._max_triples
            key = (s_n, -1, mt)
        else:
            n_chunks_total = s_n * c * self.W // _LANES
            mc = min(self._max_chunks, max(n_chunks_total, 512))
            key = (s_n, mc, self._kcap)
        scratch = self._scratch.pop(key, None)
        if scratch is None:
            # keep a few shape variants so alternating staged-slot counts
            # still reuse donated memory; evict oldest beyond that.  The
            # pipeline holds one extra set in flight, so the pool plus the
            # inflight record double-buffer naturally.
            while len(self._scratch) >= 4:
                self._scratch.pop(next(iter(self._scratch)))
            if self.paged:
                scratch = (
                    jnp.zeros((s_n, c, self.W), jnp.uint32),
                    jnp.zeros((s_n, c, self.W), jnp.uint32),
                    jnp.full((self._n_pages, PG.PAGE_WORDS), -1,
                             jnp.int32),
                    jnp.zeros((self._n_pages, PG.PAGE_WORDS), jnp.uint32),
                    jnp.zeros((self._n_pages, PG.PAGE_WORDS), jnp.uint32),
                )
            elif tri_mode:
                scratch = (
                    jnp.zeros((s_n, c, self.W), jnp.uint32),
                    jnp.zeros((s_n, c, self.W), jnp.uint32),
                    jnp.full((mt, 3), -1, jnp.int32),
                )
            else:
                scratch = (
                    jnp.zeros((s_n, c, self.W), jnp.uint32),
                    jnp.zeros((s_n, c, self.W), jnp.uint32),
                    jnp.zeros((mc, self._kcap), jnp.uint32),
                    jnp.zeros((mc, self._kcap), jnp.uint32),
                    jnp.full((mc, self._kcap), -1, jnp.int32),
                    jnp.zeros(mc, jnp.int32),
                )
        sub = self._hsub[sl]
        if self._mirror is not None and not sub.all():
            self._mirror_stale.update(s for s in slots if s in self._unsub)
        if self.fused and self._dispatch_fused(
                slots, sl, slot_idx, key, scratch, sub, old_x, old_z,
                old_r, old_act, tri_mode, t_stage0, _ts):
            return
        self._stage_inputs(sl, old_x, old_z, old_r, old_act)
        _T.lap("aoi.stage", _ts)
        _tk = _T.t()
        self._fault_phase = "kernel"
        faults.check("aoi.kernel")
        all_unsub = not sub.any()
        if self.paged:
            DC.record()
            out = _fused_bucket_step_paged(
                self.prev, *scratch, self._page_free, slot_idx,
                self._dev["x"], self._dev["z"], self._dev["r"],
                self._dev["act"], self._dev["sub"],
                PG.PAGE_WORDS, bw, PG.MAX_SPILL,
                "cpu" if self._calc_level >= 1 else None
            )
            (self.prev, new, chg, pg, pc, pn, page_tab, self._page_free,
             spill_bins, scalars) = out
            _T.lap("aoi.kernel", _tk)
            if not all_unsub:
                scalars.copy_to_host_async()
                page_tab.copy_to_host_async()
                spill_bins.copy_to_host_async()
            rec = {
                "mode": "paged",
                "slots": slots, "s_n": s_n, "key": key,
                "n_pages": self._n_pages, "bin_words": bw,
                "epochs": [self._slot_epoch.get(s, 0) for s in slots],
                "scratch": (new, chg, pg, pc, pn),
                "page_tab": page_tab,
                "spill_bins": spill_bins,
                "scalars": scalars,
                "all_unsub": all_unsub,
                "prefetch": None,
            }
            if self._defer and not all_unsub:
                # optimistic page prefetch: the used prefix rides the wire
                # while the host runs the next tick; harvest refetches on
                # a misfit
                ndp = min(self._n_pages, self._pred_pages)
                sl_pg = (pg[:ndp], pc[:ndp], pn[:ndp])
                for a in sl_pg:
                    a.copy_to_host_async()
                rec["prefetch"] = (ndp, sl_pg)
            prev_rec, self._inflight = self._inflight, rec
            self.perf["stage_s"] += time.perf_counter() - t_stage0
            if self._defer:
                if prev_rec is not None:
                    self._sched = ("rec", prev_rec)
            else:
                self._sched = ("inflight",)
            return
        if tri_mode:
            DC.record()
            out = _fused_bucket_step_tri(
                self.prev, *scratch, slot_idx, self._dev["x"],
                self._dev["z"], self._dev["r"], self._dev["act"],
                self._dev["sub"], mt,
                "cpu" if self._calc_level >= 1 else None
            )
            (self.prev, new, chg, tri, scalars) = out
            _T.lap("aoi.kernel", _tk)
            if not all_unsub:
                scalars.copy_to_host_async()
            rec = {
                "mode": "tri",
                "slots": slots, "s_n": s_n, "key": key, "mt": mt,
                "epochs": [self._slot_epoch.get(s, 0) for s in slots],
                "scratch": (new, chg, tri),
                "scalars": scalars,
                "all_unsub": all_unsub,
                "prefetch": None,
            }
            if self._defer and not all_unsub:
                # optimistic triple prefetch: D2H rides the wire while the
                # host runs the next tick; harvest refetches on a misfit
                ndp = min(mt, self._pred_tri)
                sl_tri = tri[:ndp]
                sl_tri.copy_to_host_async()
                rec["prefetch"] = (ndp, sl_tri)
            prev_rec, self._inflight = self._inflight, rec
            self.perf["stage_s"] += time.perf_counter() - t_stage0
            if self._defer:
                if prev_rec is not None:
                    self._sched = ("rec", prev_rec)
            else:
                self._sched = ("inflight",)
            return
        DC.record()
        out = _fused_bucket_step(
            self.prev, *scratch, slot_idx, self._dev["x"], self._dev["z"],
            self._dev["r"], self._dev["act"], self._dev["sub"],
            mc, self._kcap, self._max_gaps, self._max_exc,
            "cpu" if self._calc_level >= 1 else None
        )
        (self.prev, new, chg, g_vals, g_nv, g_lane, g_csel,
         rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg, exc_new,
         scalars) = out
        _T.lap("aoi.kernel", _tk)
        if not all_unsub:
            scalars.copy_to_host_async()
        rec = {
            "slots": slots, "s_n": s_n, "key": key, "mc": mc,
            "kcap": self._kcap,
            "epochs": [self._slot_epoch.get(s, 0) for s in slots],
            "scratch": (new, chg, g_vals, g_nv, g_lane, g_csel),
            "streams": (rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
                        exc_new),
            "scalars": scalars,
            # every staged slot unsubscribed: the stream is empty BY
            # CONSTRUCTION (chg masked on device), so the harvest needs no
            # fetch at all -- not even the scalars (every synchronous wait
            # is a device round trip)
            "all_unsub": all_unsub,
            "prefetch": None,
        }
        if self._defer and not all_unsub:
            # optimistic prefetch at the recent ticks' observed stream sizes:
            # the D2H rides the wire while the host runs the next tick's
            # logic; the harvest refetches exact slices on a misfit (rare --
            # sizes move slowly in steady state).  An all-unsubscribed tick
            # skips it outright: its stream is empty by construction and the
            # harvest's nd==0 early-out never fetches.
            ndp = min(mc, self._pred[0])
            escp = min(self._max_gaps, self._pred[1])
            excp = min(self._max_exc, self._pred[2])
            slices = (rowb[:ndp], bitpos[:ndp], woff[:ndp],
                      esc_rows[:escp], exc_gidx[:excp], exc_chg[:excp],
                      exc_new[:excp])
            for a in slices:
                a.copy_to_host_async()
            rec["prefetch"] = (ndp, escp, excp, slices)
        prev_rec, self._inflight = self._inflight, rec
        self.perf["stage_s"] += time.perf_counter() - t_stage0
        if self._defer:
            # tick T dispatched; T-1's record (whose D2H was prefetched at
            # its own dispatch) harvests in phase 2
            if prev_rec is not None:
                self._sched = ("rec", prev_rec)
        else:
            self._sched = ("inflight",)

    def _dispatch_fused(self, slots, sl, slot_idx, key, scratch, sub,
                        old_x, old_z, old_r, old_act, tri_mode,
                        t_stage0, _ts) -> bool:
        """Attempt the ONE-DISPATCH fused tick (ops/aoi_fused, ROADMAP
        #3): packet scatter + kernel + diff + extraction/paging as a
        single jitted program, so the steady tick is one enqueue + one
        D2H fetch.  Returns True when the tick was dispatched fused
        (the caller's unfused flow is skipped), False to fall through.

        Two distinct False paths, by design:

        * ineligible -- the tick is not a steady delta tick (stale
          device roles, r/act changed, diff too large, classic host-emit
          mode, device down): silent fall-through, the unfused path IS
          the right program for it;
        * demoted -- an ``aoi.delta``/``aoi.kernel`` seam fault fired in
          the fused attempt: counted in ``fused_demotions`` and fall
          through BEFORE any device mutation, so the unfused flow
          (whose seam occurrence was consumed by the fused attempt)
          runs clean in the same call -- same-tick, bit-exact.
        """
        s_n = len(slots)
        if not (tri_mode or self.paged):
            return False  # classic host-emit stream has no fused program
        if (not self.delta_staging or self._dev_stale
                or self._calc_level >= 2 or self._need_rebuild):
            return False
        if any(role not in self._dev
               for role in ("x", "z", "r", "act", "sub")):
            return False
        new_x, new_z = self._hx[sl], self._hz[sl]
        if not (np.array_equal(self._hr[sl], old_r)
                and np.array_equal(self._hact[sl], old_act)):
            return False  # r/act moved: full-restage tick, unfused
        diff = (new_x.view(np.uint32) != old_x.view(np.uint32)) \
            | (new_z.view(np.uint32) != old_z.view(np.uint32))
        n_changed = np.count_nonzero(diff)
        if n_changed > self._delta_max_frac * diff.size:
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
        if n_changed:
            rows, cols = np.nonzero(diff)
            pkt = AS.pad_packet(sl[rows], cols, new_x[rows, cols],
                                new_z[rows, cols],
                                page_granular=self.paged)
            self.stats["h2d_bytes"] += AS.packet_nbytes(*pkt)
        else:
            zi = np.zeros(0, np.int32)
            zf = np.zeros(0, np.float32)
            pkt = (zi, zi, zf, zf)  # zero movers: in-program no-op scatter
        self.stats["delta_flushes"] += 1
        _T.lap("aoi.stage", _ts)
        _tk = _T.t()
        all_unsub = not sub.any()
        platform = "cpu" if self._calc_level >= 1 else None
        DC.record()
        if self.paged:
            bw = PG.bin_words_for(self.W)
            out = AF.fused_paged_step(
                self.prev, *scratch, self._page_free, self._dev["x"],
                self._dev["z"], *pkt, slot_idx, self._dev["r"],
                self._dev["act"], self._dev["sub"], PG.PAGE_WORDS, bw,
                PG.MAX_SPILL, platform)
            (self.prev, new, chg, pg, pc, pn, self._page_free, bundle,
             self._dev["x"], self._dev["z"]) = out
            _T.lap("aoi.kernel", _tk)
            _T.lap("aoi.fused", _tk)
            if not all_unsub:
                bundle.copy_to_host_async()
            rec = {
                "mode": "paged",
                "slots": slots, "s_n": s_n, "key": key,
                "n_pages": self._n_pages, "bin_words": bw,
                "epochs": [self._slot_epoch.get(s, 0) for s in slots],
                "scratch": (new, chg, pg, pc, pn),
                # one compact int32 vector replaces the page_tab /
                # spill_bins / scalars triple-fetch of the unfused
                # harvest (_harvest_paged slices it back apart)
                "bundle": bundle,
                "page_tab": None, "spill_bins": None, "scalars": None,
                "all_unsub": all_unsub,
                "prefetch": None,
            }
            if self._defer and not all_unsub:
                ndp = min(self._n_pages, self._pred_pages)
                sl_pg = (pg[:ndp], pc[:ndp], pn[:ndp])
                for a in sl_pg:
                    a.copy_to_host_async()
                rec["prefetch"] = (ndp, sl_pg)
        else:
            mt = self._max_triples
            out = AF.fused_tri_step(
                self.prev, *scratch, self._dev["x"], self._dev["z"],
                *pkt, slot_idx, self._dev["r"], self._dev["act"],
                self._dev["sub"], mt, platform)
            (self.prev, new, chg, tri, scalars,
             self._dev["x"], self._dev["z"]) = out
            _T.lap("aoi.kernel", _tk)
            _T.lap("aoi.fused", _tk)
            if not all_unsub:
                scalars.copy_to_host_async()
            rec = {
                "mode": "tri",
                "slots": slots, "s_n": s_n, "key": key, "mt": mt,
                "epochs": [self._slot_epoch.get(s, 0) for s in slots],
                "scratch": (new, chg, tri),
                "scalars": scalars,
                "all_unsub": all_unsub,
                "prefetch": None,
            }
            if self._defer and not all_unsub:
                ndp = min(mt, self._pred_tri)
                sl_tri = tri[:ndp]
                sl_tri.copy_to_host_async()
                rec["prefetch"] = (ndp, sl_tri)
        self.stats["fused_dispatches"] += 1
        prev_rec, self._inflight = self._inflight, rec
        self.perf["stage_s"] += time.perf_counter() - t_stage0
        if self._defer:
            if prev_rec is not None:
                self._sched = ("rec", prev_rec)
        else:
            self._sched = ("inflight",)
        return True

    def drain(self) -> None:
        """Harvest a pending pipelined tick without dispatching a new one
        (shutdown, state carry-over, tests)."""
        self.harvest()
        if self._inflight is not None:
            self._harvest()

    # -- fault recovery (docs/robustness.md) -----------------------------
    #
    # The durable copies are the host shadows (_hx/_hz/_hr/_hact/_hsub --
    # bitwise identical to the device inputs by the delta-staging contract)
    # plus the mirror (the XOR-maintained host copy of the packed interest
    # words).  On any device-side fault the bucket (1) delivers the tick
    # already in flight (its buffers predate the fault), (2) recomputes the
    # faulted tick on the host from (mirror, shadows) -- the host predicate
    # is bit-exact with every device backend, and np.nonzero's ascending
    # flat order matches the device chunk extraction's, so the recovered
    # event stream is bit-identical -- and (3) drops all device state; the
    # next flush re-uploads prev from the mirror and full-restages inputs.

    def _restage_shadows(self) -> list[int]:
        """Copy staged tick inputs into the persistent host shadows (pure
        host work; shared by the device path and fault recovery)."""
        slots = sorted(self._staged)
        for slot in slots:
            sx, sz, sr, sa = self._staged[slot]
            n = len(sx)
            self._hx[slot, :n] = sx
            self._hx[slot, n:] = 0.0
            self._hz[slot, :n] = sz
            self._hz[slot, n:] = 0.0
            self._hr[slot, :n] = sr
            self._hr[slot, n:] = 0.0
            self._hact[slot, :n] = sa
            self._hact[slot, n:] = False
        self._staged.clear()
        return slots

    def _rebuild_device(self) -> None:
        """Re-upload the packed interest state from the durable host mirror
        after a device loss (deferred to flush so a dead device is retried
        at tick cadence, not in the failure handler)."""
        if not self._need_rebuild:
            return
        self._need_rebuild = False
        self.prev = self._jnp.asarray(self._mirror)
        self.stats["h2d_bytes"] += self._mirror.nbytes

    def reset_calc_chain(self) -> None:
        """Re-arm the device calculator after fallback (operator action --
        demotion is sticky so a flapping device cannot oscillate)."""
        self._calc_level = 0
        self.stats["calc_level"] = 0
        if self.prev is None and self.s_max:
            self._ensure_mirror()
            self._need_rebuild = True

    def _ensure_mirror(self) -> None:  # gwlint: allow[host-sync] -- fault-recovery path, not the steady tick
        """Make the host mirror exist.  Fault-tolerant buckets keep it from
        slot 0; otherwise seed it from the still-live device prev, or -- if
        the device is truly dead -- recompute from the input shadows (exact
        for every slot whose prev equals the predicate of its last staged
        inputs; seeded-then-unstepped slots lose their seed, loudly)."""
        if self._mirror is not None:
            return
        try:
            self._mirror = (
                np.zeros((self.s_max, self.capacity, self.W), np.uint32)
                if self.prev is None
                else np.array(self.prev, np.uint32, copy=True, order="C"))
        except Exception:
            from ..utils import gwlog

            gwlog.logger("gw.aoi").warning(
                "device prev unreadable during recovery; rebuilding the "
                "mirror from the input shadows (derived state of cleared/"
                "seeded slots may lag until their next stage)")
            m = np.empty((self.s_max, self.capacity, self.W), np.uint32)
            for s in range(self.s_max):
                m[s] = _packed_predicate(self._hx[s], self._hz[s],
                                         self._hr[s], self._hact[s])
            self._mirror = m

    def _refresh_stale_rows(self) -> None:
        """Recompute mirror rows that went stale while unsubscribed: a
        slot's prev equals the predicate of its last staged inputs (its
        shadows), so the recompute is exact up to post-stage clears
        (documented limitation; resubscription resyncs)."""
        for s in sorted(self._mirror_stale):
            self._mirror[s] = _packed_predicate(
                self._hx[s], self._hz[s], self._hr[s], self._hact[s])
        self._mirror_stale.clear()

    def _recover(self, e: BaseException) -> None:  # gwlint: allow[flush-phase] -- fault recovery: the device is gone, host sync is the point
        """Device fault mid-flush: deliver the inflight tick, recompute the
        faulted tick host-side (bit-exact), drop device state."""
        from ..utils import gwlog

        self.stats["rebuilds"] += 1
        if self._fault_phase == "kernel" and self._calc_level < 2:
            # the calculator itself failed: demote one level down the
            # chain (pallas -> dense -> host oracle)
            self._calc_level += 1
            self.stats["fallbacks"] += 1
            self.stats["calc_level"] = self._calc_level
        gwlog.logger("gw.aoi").warning(
            "AOI bucket (cap %d) device fault during %s: %s -- recovering "
            "tick on host (calc level %d)",
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
        # never reached the device (idempotent re-apply otherwise)
        self._ensure_mirror()
        for s in sorted(self._pending_reset):
            self._mirror_apply_now(("reset", s))
        for s, ent in self._pending_clear:
            self._mirror_apply_now(("clear", s, ent))
        self._pending_reset.clear()
        self._pending_clear.clear()
        # 3. the faulted tick's inputs are (or now land) in the shadows
        slots = self._restage_shadows() if self._staged else self._cur_slots
        self._cur_slots = []
        # 4. device state is gone; the next flush rebuilds from the mirror
        self.prev = None
        self._dev.clear()
        self._dev_stale = {"xz", "ra", "sub"}
        self._scratch.clear()
        self._need_rebuild = self._calc_level < 2
        # 5. compute the faulted tick on the host
        if slots:
            self._host_tick(slots)

    def _recover_harvest(self, e: BaseException, rec: dict) -> None:  # gwlint: allow[flush-phase] -- fault recovery: the device is gone, host sync is the point
        """Device fault surfacing at HARVEST time (split-phase flush: the
        blocking fetch is where async kernel/transfer errors materialize).
        The faulted record's stream is unrecoverable from the device, but
        the durable copies bracket it exactly: the mirror still holds the
        state BEFORE the record's tick (its XOR never applied) and the
        shadows hold the newest staged inputs -- so one host predicate pass
        regenerates the lost events as a single coalesced diff, published
        immediately in place of the record's due delivery (bit-exact for
        the non-pipelined path; pipelined, the faulted tick and the one
        dispatched after it coalesce -- docs/robustness.md)."""
        from ..utils import gwlog

        self.stats["rebuilds"] += 1
        if _kernelish_fault(e) and self._calc_level < 2:
            self._calc_level += 1
            self.stats["fallbacks"] += 1
            self.stats["calc_level"] = self._calc_level
        gwlog.logger("gw.aoi").warning(
            "AOI bucket (cap %d) device fault during harvest: %s -- "
            "regenerating the tick's events on host (calc level %d)",
            self.capacity, e, self._calc_level)
        # a host-synthetic record cannot fault here (its harvest never
        # touches the device), but stay defensive: its events and mirror
        # effects are already final, so just re-publish its payload
        if rec.get("host"):
            chg_vals, ent_vals, gidx, s_n = rec["payload"]
            self._publish(rec["slots"], rec["epochs"], chg_vals, ent_vals,
                          gidx, s_n)
            rec_slots: list[int] = []
        else:
            rec_slots = rec["slots"]
        # the record dispatched AFTER the faulted one (pipelined) is on the
        # same dead device; fold its slots into the recompute.  A synthetic
        # inflight stays parked -- its mirror effects already landed and
        # its delivery schedule is unchanged.
        newest, self._inflight = self._inflight, None
        host_rec = None
        if newest is not None:
            if newest.get("host"):
                host_rec = newest
            else:
                rec_slots = sorted(set(rec_slots) | set(newest["slots"]))
        self._ensure_mirror()
        # mirror maintenance that was deferred behind the (now lost) stream
        # XOR, plus device-queue maintenance that never reached prev: land
        # everything on the mirror (idempotent)
        if self._mirror_ops:
            ops, self._mirror_ops = self._mirror_ops, []
            for op in ops:
                if self._slot_epoch.get(op[1], 0) == op[-1]:
                    self._mirror_apply_now(op[:-1])
        for s in sorted(self._pending_reset):
            self._mirror_apply_now(("reset", s))
        for s, ent in self._pending_clear:
            self._mirror_apply_now(("clear", s, ent))
        self._pending_reset.clear()
        self._pending_clear.clear()
        if self._staged:  # defensive: inputs staged between the phases
            rec_slots = sorted(set(rec_slots) | set(self._restage_shadows()))
        self._cur_slots = []
        # device state is gone; the next dispatch rebuilds from the mirror
        self.prev = None
        self._dev.clear()
        self._dev_stale = {"xz", "ra", "sub"}
        self._scratch.clear()
        self._page_free = None  # paged free list reinits at next dispatch
        self._need_rebuild = self._calc_level < 2
        if rec_slots:
            self._host_tick(rec_slots, publish_now=True)
        self._inflight = host_rec

    def _host_tick(self, slots: list[int], publish_now: bool = False) -> None:
        """One bucket tick on the host from the durable copies, bit-exact
        with the device step: new = predicate(shadows) per staged slot,
        chg = new XOR mirror (masked for unsubscribed slots), and the
        event stream in np.nonzero's ascending flat order -- exactly the
        device chunk-extraction order (the cap-overflow recovery path in
        _harvest decodes the same way).  ``publish_now`` skips the
        pipelined one-tick-late parking: harvest-time recovery substitutes
        this tick for the faulted record's due delivery."""
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
            # deferred cadence (pipeline/cross_tick): events are delivered
            # one tick late, so a recovered tick parks as a synthetic
            # inflight record and publishes at the NEXT flush, exactly like
            # a device tick
            self._inflight = {"host": True, "slots": slots,
                              "epochs": epochs,
                              "payload": (chg_vals, ent_vals, gidx, s_n)}
        else:
            self._publish(slots, epochs, chg_vals, ent_vals, gidx, s_n)
        _T.lap("aoi.host_tick", _th)

    def _harvest(self, rec=None) -> None:  # gwlint: allow[host-sync] -- THE per-tick drain point: harvests kernel outputs once per flush
        """Fetch + decode one dispatched tick's event stream and publish its
        per-slot events.  ``rec=None`` harvests (and clears) the inflight
        record."""
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
        if rec.get("mode") == "paged":
            self._harvest_paged(rec)
            return
        if rec.get("mode") == "tri":
            self._harvest_tri(rec)
            return
        slots, s_n, mc = rec["slots"], rec["s_n"], rec["mc"]
        kcap = rec["kcap"]
        c = self.capacity
        (new, chg, g_vals, g_nv, g_lane, g_csel) = rec["scratch"]
        (rowb, bitpos, woff, esc_rows, exc_gidx, exc_chg,
         exc_new) = rec["streams"]
        # ONE tiny fetch for all control scalars (each synchronous fetch
        # pays a device round trip); under the pipeline it was issued async
        # at dispatch and is local by now
        faults.check("aoi.fetch")  # stallable: a delayed host sync
        t_f0 = time.perf_counter()
        _tf = _T.t()
        poisoned = False
        if rec.get("all_unsub"):
            nd = mcc = base_row = n_esc = exc_n = 0
        else:
            raw = faults.filter("aoi.scalars", np.asarray(rec["scalars"]))
            nd, mcc, base_row, n_esc, exc_n = (int(v) for v in raw)
            nw = s_n * c * self.W
            if not (0 <= nd <= nw // _LANES and 0 <= mcc <= _LANES
                    and 0 <= n_esc <= nw and 0 <= exc_n <= nw
                    and 0 <= base_row <= nw // _LANES):
                # garbage control scalars (a kernel writing NaN-adjacent
                # junk): distrust the encoded stream wholesale and recover
                # this tick from the raw diff grids riding the same record
                from ..utils import gwlog

                self.stats["poisoned"] += 1
                gwlog.logger("gw.aoi").warning(
                    "AOI control scalars failed validation "
                    "(nd=%d mcc=%d base=%d esc=%d exc=%d); recovering the "
                    "tick from the raw diff grids", nd, mcc, base_row,
                    n_esc, exc_n)
                poisoned = True
                nd = mcc = base_row = n_esc = exc_n = 0
        shrink = (None if poisoned else
                  self._caps.observe(nd, mcc, self._max_chunks, self._kcap))
        if shrink is not None:
            self._max_chunks, self._kcap = shrink
        if poisoned:
            # full-diff recovery (same shape as the cap-overflow branch,
            # without growing the caps off corrupted values)
            chg_h = np.asarray(chg).reshape(-1)
            new_h = np.asarray(new).reshape(-1)
            gidx = np.nonzero(chg_h)[0]
            chg_vals = chg_h[gidx]
            ent_vals = chg_vals & new_h[gidx]
            self.perf["fetch_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.fetch", _tf)
        elif nd == 0 and exc_n == 0:
            # quiet tick (or every staged slot unsubscribed): the stream is
            # empty by construction -- the scalars above are the ONLY fetch
            chg_vals = np.empty(0, np.uint32)
            ent_vals = np.empty(0, np.uint32)
            gidx = np.empty(0, np.int64)
            self.perf["fetch_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.fetch", _tf)
        elif nd > mc or mcc > kcap:
            # caps exceeded: recover this tick from the full diff, then grow
            # the caps so the next tick extracts on device again
            self.stats["decode_overflow"] += 1
            self._max_chunks = max(self._max_chunks, 2 * nd)
            # a chunk holds at most _LANES nonzero words
            self._kcap = min(max(self._kcap, 2 * mcc), _LANES)
            self._caps.reset_after_growth()
            chg_h = np.asarray(chg).reshape(-1)
            new_h = np.asarray(new).reshape(-1)
            gidx = np.nonzero(chg_h)[0]
            chg_vals = chg_h[gidx]
            ent_vals = chg_vals & new_h[gidx]
            self.perf["fetch_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.fetch", _tf)
        elif n_esc > self._max_gaps or exc_n > self._max_exc:
            # encode overflow (pathological churn): rebuild from the raw
            # grids kept on device
            self.stats["decode_overflow"] += 1
            ndp = min(mc, -(-max(nd, 1) // 512) * 512)
            slices = (g_vals[:ndp], g_nv[:ndp], g_lane[:ndp], g_csel[:ndp])
            for a in slices:
                a.copy_to_host_async()
            vh, nh, lh, ch = (np.asarray(a) for a in slices)
            valid = lh >= 0
            chg_vals = vh[valid]
            ent_vals = chg_vals & nh[valid]
            gidx = (ch[:, None].astype(np.int64) * _LANES + lh)[valid]
            self.perf["fetch_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.fetch", _tf)
        else:
            # the common path fetches the ENCODED stream: ~5 B per dirty
            # chunk + 12 B per exception, overlapped slice transfers
            pf = rec["prefetch"]
            if pf is not None and pf[0] >= nd and pf[1] >= n_esc \
                    and pf[2] >= exc_n:
                hb = [np.asarray(a) for a in pf[3]]
            else:
                ndp = min(mc, -(-max(nd, 1) // 128) * 128)
                escp = min(self._max_gaps, -(-max(n_esc, 1) // 64) * 64)
                excp = min(self._max_exc, -(-max(exc_n, 1) // 256) * 256)
                slices = (rowb[:ndp], bitpos[:ndp], woff[:ndp],
                          esc_rows[:escp], exc_gidx[:excp], exc_chg[:excp],
                          exc_new[:excp])
                for a in slices:
                    a.copy_to_host_async()
                hb = [np.asarray(a) for a in slices]
            self.perf["fetch_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.fetch", _tf)
            t_f0 = time.perf_counter()
            _td = _T.t()
            chg_vals, ent_vals, gidx = EV.decode_row_stream(
                hb[0], hb[1], hb[2].astype(np.uint16), base_row, nd,
                _LANES, hb[3], hb[4], hb[5], hb[6])
            self.perf["decode_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.diff", _td)
        t_f0 = time.perf_counter()
        _td = _T.t()
        # refit the next dispatch's optimistic prefetch to this tick
        self._pred = (
            max(512, -(-nd * 5 // 4 // 128) * 128),
            max(64, -(-(n_esc + 1) * 3 // 2 // 64) * 64),
            max(256, -(-(exc_n + 1) * 5 // 4 // 256) * 256),
        )
        self._mirror_xor_stream(slots, rec["epochs"], gidx, chg_vals)
        # the harvested scratch set returns to the pool for reuse
        self._scratch.setdefault(rec["key"], rec["scratch"])
        self._publish(slots, rec["epochs"], chg_vals, ent_vals, gidx, s_n)
        self.perf["decode_s"] += time.perf_counter() - t_f0
        _T.lap("aoi.diff", _td)

    def _mirror_xor_stream(self, slots, epochs, gidx, chg_vals) -> None:  # gwlint: allow[host-sync] -- harvest-phase mirror upkeep on already-fetched host arrays
        """Apply one harvested word stream to the host mirror (then run the
        deferred maintenance ops that postdate it)."""
        if self._mirror is None:
            return
        if len(gidx):
            # stream entries are whole words with unique indices, so one
            # fancy-index XOR applies the tick exactly.  Rows whose slot
            # was released since this tick's dispatch are skipped -- the
            # same epoch guard that drops the dead space's events; a
            # reused slot's mirror was already reset at re-acquire and
            # must not have the dead stream XORed back in.
            wps = self.capacity * self.W
            gidx = np.asarray(gidx, np.int64)
            rows = gidx // wps
            cur = np.fromiter(
                (self._slot_epoch.get(s, 0) for s in slots),
                np.int64, len(slots))
            keep = cur[rows] == np.asarray(epochs, np.int64)[rows]
            if self._mirror_stale:
                # a re-subscribed slot's stream must not XOR onto its
                # stale mirror base; the row refreshes from device on
                # the next peek instead
                stale = np.fromiter(
                    (s in self._mirror_stale for s in slots),
                    bool, len(slots))
                keep &= ~stale[rows]
            g, v = (gidx, chg_vals) if keep.all() else (gidx[keep],
                                                        chg_vals[keep])
            srows = np.asarray(slots, np.int64)[g // wps]
            self._mirror.reshape(self.s_max, wps)[srows, g % wps] ^= v
        self._apply_deferred_mirror_ops()

    def _mirror_xor_triples(self, slots, epochs, tri) -> None:  # gwlint: allow[host-sync] -- harvest-phase mirror upkeep on already-fetched host arrays
        """Apply a tick's triples to the host mirror.  Each triple flips one
        unique (row, bit), so a scatter-XOR of single-bit masks applies the
        tick exactly; the epoch/stale guards mirror _mirror_xor_stream."""
        c = self.capacity
        obs = tri[:, 0].astype(np.int64)
        rows = obs // c
        cur = np.fromiter(
            (self._slot_epoch.get(s, 0) for s in slots),
            np.int64, len(slots))
        keep = cur[rows] == np.asarray(epochs, np.int64)[rows]
        if self._mirror_stale:
            stale = np.fromiter(
                (s in self._mirror_stale for s in slots),
                bool, len(slots))
            keep &= ~stale[rows]
        if not keep.all():
            obs, rows, tri = obs[keep], rows[keep], tri[keep]
        j = tri[:, 1].astype(np.int64)
        srows = np.asarray(slots, np.int64)[rows]
        # planar layout: column j lives at word j % W, bit j // W
        gw = (srows * c + obs % c) * self.W + j % self.W
        bit = (j // self.W).astype(np.uint32)
        np.bitwise_xor.at(self._mirror.reshape(-1), gw, np.uint32(1) << bit)

    def _grow_pool(self, nw: int, bw: int, full: bool = False) -> None:
        """Spill re-arm (the growth half of the _PageDecay contract,
        mirroring the tri/chunk cap growth): double the pool, bounded by
        pool_ceiling -- a pool at the ceiling can NEVER spill (full word
        coverage plus per-bin rounding) -- and reinitialize the free list
        at the next dispatch.  ``full`` jumps straight to the ceiling: a
        WHOLE-TICK spill (> MAX_SPILL bins) is an unambiguous undersize
        signal, and doubling through a sustained storm would spill every
        tick of it; _PageDecay shrinks the pool back afterwards."""
        ceil_p = PG.pool_ceiling(nw, bw)
        grown = ceil_p if full else min(ceil_p, max(self._n_pages * 2, 64))
        if grown > self._n_pages:
            self._n_pages = grown
            self._page_free = None
        if self._pages is not None:
            self._pages.reset_after_growth()

    def _harvest_paged(self, rec) -> None:  # gwlint: allow[host-sync] -- paged-path drain point: fetches the used page prefix once per flush
        """Harvest one paged tick: fetch the used page prefix + page table
        + scalars, validate the allocator's page table, merge any spilled
        bins' words re-read from the kept change grid, XOR the mirror, and
        publish (docs/perf.md paged storage; docs/robustness.md spill
        chain).  Degradation ladder: spilled bins re-read host-side
        (counted in page_spills, same-tick bit-exact); pool exhaustion
        injected through the ``aoi.pages`` seam (oom/fail/partial) forces
        a counted whole-tick spill from the raw grids and re-arms the
        pool; a corrupt page table (``aoi.pages`` poison, or real
        allocator rot) re-raises as RESOURCE_EXHAUSTED to ride
        :meth:`_recover_harvest`'s rebuild-from-host-shadows."""
        slots, s_n = rec["slots"], rec["s_n"]
        n_pages, bw = rec["n_pages"], rec["bin_words"]
        c = self.capacity
        (new, chg, pg, pc, pn) = rec["scratch"]
        nw = s_n * c * self.W
        faults.check("aoi.fetch")  # stallable: a delayed host sync
        t_f0 = time.perf_counter()
        _tf = _T.t()
        poisoned = False
        n_used = n_spill = 0
        page_spec = page_fault = None
        bun_h = None
        if not rec.get("all_unsub"):
            if rec.get("bundle") is not None:
                # fused tick: scalars + page_tab + spill_bins ride ONE
                # int32 bundle -- a single blocking fetch replaces the
                # unfused path's three (ops/aoi_fused)
                bun_h = np.asarray(rec["bundle"])
                raw = faults.filter("aoi.scalars", bun_h[:4])
            else:
                raw = faults.filter("aoi.scalars",
                                    np.asarray(rec["scalars"]))
            n_used, n_spill, nz_fit, nz_total = (int(v) for v in raw)
            n_bins = -(-nw // bw)
            if not (0 <= n_used <= n_pages and 0 <= n_spill <= n_bins
                    and 0 <= nz_fit <= nw and 0 <= nz_total <= nw):
                from ..utils import gwlog

                self.stats["poisoned"] += 1
                gwlog.logger("gw.aoi").warning(
                    "AOI page scalars failed validation (used=%d spill=%d "
                    "fit=%d total=%d); recovering the tick from the raw "
                    "diff grids", n_used, n_spill, nz_fit, nz_total)
                poisoned = True
                n_used = n_spill = 0
            # the aoi.pages seam (docs/robustness.md): oom/fail = pool
            # exhaustion, partial = untrustworthy allocation -- all three
            # force the counted whole-tick spill below; poison corrupts
            # the fetched page table (validated further down)
            try:
                page_spec = faults.check("aoi.pages")
            except Exception as pe:
                if not _device_fault(pe):
                    raise
                page_fault = pe
            if page_spec is not None and page_spec.kind == "partial":
                page_fault = page_spec
        shrink = (None if poisoned or n_spill or page_fault is not None
                  else self._pages.observe(n_used, n_pages))
        if shrink is not None and shrink < self._n_pages:
            self._n_pages = shrink
            self._page_free = None  # reinit at the shrunk size
        if poisoned or page_fault is not None or n_spill > PG.MAX_SPILL:
            # whole-tick spill: the page stream is untrustworthy (poisoned
            # scalars), the allocator faulted (aoi.pages oom/fail/partial),
            # or more bins spilled than the reporting vector holds --
            # recover this tick from the raw diff grids riding the same
            # record (bit-exact; np.nonzero's ascending flat order matches
            # the device extraction's), then re-arm the pool
            if not poisoned:
                from ..utils import gwlog

                self.stats["page_spills"] += 1
                gwlog.logger("gw.aoi").warning(
                    "AOI page pool unusable this tick (%s); spilling the "
                    "whole tick to host and re-arming the pool",
                    page_fault if page_fault is not None
                    else f"{n_spill} bins spilled > {PG.MAX_SPILL}")
                # organic mass-spill = the pool is way undersized: jump to
                # the ceiling.  A fault-caused spill says nothing about
                # size, so it only doubles.
                self._grow_pool(nw, bw, full=page_fault is None)
            chg_h = np.asarray(chg).reshape(-1)
            new_h = np.asarray(new).reshape(-1)
            gidx = np.nonzero(chg_h)[0]
            chg_vals = chg_h[gidx]
            ent_vals = chg_vals & new_h[gidx]
            self.perf["fetch_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.fetch", _tf)
            t_f0 = time.perf_counter()
            _td = _T.t()
            self._mirror_xor_stream(slots, rec["epochs"], gidx, chg_vals)
            self._scratch.setdefault(rec["key"], rec["scratch"])
            self._publish(slots, rec["epochs"], chg_vals, ent_vals, gidx,
                          s_n)
            self.perf["decode_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.diff", _td)
            return
        if n_used == 0:
            pg_h = np.empty((0, PG.PAGE_WORDS), np.int32)
            pc_h = pn_h = np.empty((0, PG.PAGE_WORDS), np.uint32)
        else:
            pf = rec["prefetch"]
            if pf is not None and pf[0] >= n_used:
                pg_h, pc_h, pn_h = (np.asarray(a)[:n_used] for a in pf[1])
            else:
                ndp = min(n_pages, -(-max(n_used, 1) // 16) * 16)
                slices = (pg[:ndp], pc[:ndp], pn[:ndp])
                for a in slices:
                    a.copy_to_host_async()
                pg_h, pc_h, pn_h = (np.asarray(a)[:n_used] for a in slices)
        self.perf["fetch_s"] += time.perf_counter() - t_f0
        _T.lap("aoi.fetch", _tf)
        # refit the next dispatch's optimistic page prefetch to this tick
        self._pred_pages = max(
            64, min(self._n_pages, -(-n_used * 5 // 4 // 16) * 16))
        t_f0 = time.perf_counter()
        _tp = _T.t()
        if n_used:
            # page-table integrity: the table is the allocator's word of
            # which logical pages back this tick; a duplicate, out-of-range
            # or truncated id means the free list itself is corrupt -- not
            # a per-tick cap problem -- so the ONLY safe recovery is the
            # full device-state rebuild from the host shadows
            tab_h = (bun_h[4:4 + n_pages] if bun_h is not None
                     else np.asarray(rec["page_tab"]))
            if page_spec is not None and page_spec.kind == "poison":
                tab_h = np.full_like(tab_h, np.iinfo(np.int32).min)
            if not PG.validate_page_table(tab_h, n_used, n_pages):
                self.stats["poisoned"] += 1
                self._page_free = None  # rebuilt (arange) at next dispatch
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: aoi.pages page table failed "
                    f"validation (n_used={n_used}, n_pages={n_pages}) -- "
                    "allocator free list corrupt")
        gidx, chg_vals, new_vals = PG.decode_pages(pg_h, pc_h, pn_h)
        gidx = gidx.astype(np.int64)
        if n_spill:
            # counted graceful degradation: the pool served every bin it
            # could; the spilled bins' words are re-read from the kept
            # change grid (small per-bin D2H slices), merged unsorted --
            # the mirror XOR is order-independent over unique words and
            # both emit paths sort before expansion -- and the pool grows
            # for the next tick (decay shrinks it back post-storm)
            self.stats["page_spills"] += n_spill
            sb = (bun_h[4 + n_pages:] if bun_h is not None
                  else np.asarray(rec["spill_bins"]))
            sg, sc, sn2 = PG.spill_stream(chg.reshape(-1), new.reshape(-1),
                                          sb, bw, nw)
            gidx = np.concatenate([gidx, sg])
            chg_vals = np.concatenate([chg_vals, sc])
            new_vals = np.concatenate([new_vals, sn2])
            self._grow_pool(nw, bw)
        ent_vals = chg_vals & new_vals
        self.stats["page_occupancy"] = (n_used / n_pages) if n_pages else 0.0
        _T.lap("aoi.pages", _tp)
        _td = _T.t()
        self._mirror_xor_stream(slots, rec["epochs"], gidx, chg_vals)
        self._scratch.setdefault(rec["key"], rec["scratch"])
        self._publish(slots, rec["epochs"], chg_vals, ent_vals, gidx, s_n)
        self.perf["decode_s"] += time.perf_counter() - t_f0
        _T.lap("aoi.diff", _td)

    def _harvest_tri(self, rec) -> None:  # gwlint: allow[host-sync] -- triples-path drain point: fetches the compact triple buffer once per flush
        """Harvest one tri-mode tick: fetch the compact (observer, observed,
        kind) triples + count scalar, XOR the mirror, and fan the pairs out
        through the native/vector emit layer (docs/perf.md emit paths)."""
        slots, s_n, mt = rec["slots"], rec["s_n"], rec["mt"]
        c = self.capacity
        (new, chg, tri) = rec["scratch"]
        faults.check("aoi.fetch")  # stallable: a delayed host sync
        t_f0 = time.perf_counter()
        _tf = _T.t()
        poisoned = False
        if rec.get("all_unsub"):
            count = 0
        else:
            raw = faults.filter("aoi.scalars", np.asarray(rec["scalars"]))
            count = int(raw[0])
            if not 0 <= count <= s_n * c * c:
                from ..utils import gwlog

                self.stats["poisoned"] += 1
                gwlog.logger("gw.aoi").warning(
                    "AOI triple count failed validation (count=%d); "
                    "recovering the tick from the raw diff grids", count)
                poisoned = True
        shrink = (None if poisoned or count > mt else
                  self._tri.observe(count, self._max_triples))
        if shrink is not None:
            self._max_triples = shrink
        if poisoned or count > mt:
            # triple-capacity overflow (or corrupt count): the compact
            # buffer is truncated, so recover this tick from the raw diff
            # grids riding the same record, then grow the cap so the next
            # tick compacts on device again (counted, never silent --
            # docs/robustness.md)
            if not poisoned:
                self.stats["decode_overflow"] += 1
                if self._max_triples < _TRI_MAX:
                    self._max_triples = min(
                        _TRI_MAX, 1 << (2 * count - 1).bit_length())
                self._tri.reset_after_growth()
            chg_h = np.asarray(chg).reshape(-1)
            new_h = np.asarray(new).reshape(-1)
            gidx = np.nonzero(chg_h)[0]
            chg_vals = chg_h[gidx]
            ent_vals = chg_vals & new_h[gidx]
            self.perf["fetch_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.fetch", _tf)
            t_f0 = time.perf_counter()
            _td = _T.t()
            self._mirror_xor_stream(slots, rec["epochs"], gidx, chg_vals)
            self._scratch.setdefault(rec["key"], rec["scratch"])
            self._publish(slots, rec["epochs"], chg_vals, ent_vals, gidx,
                          s_n)
            self.perf["decode_s"] += time.perf_counter() - t_f0
            _T.lap("aoi.diff", _td)
            return
        if count == 0:
            tri_h = np.empty((0, 3), np.int32)
        else:
            pf = rec["prefetch"]
            if pf is not None and pf[0] >= count:
                tri_h = np.asarray(pf[1])[:count]
            else:
                ndp = min(mt, -(-count // 256) * 256)
                sl_tri = tri[:ndp]
                sl_tri.copy_to_host_async()
                tri_h = np.asarray(sl_tri)[:count]
        self.perf["fetch_s"] += time.perf_counter() - t_f0
        _T.lap("aoi.fetch", _tf)
        # refit the next dispatch's optimistic prefetch to this tick
        self._pred_tri = max(
            2048, min(self._max_triples, -(-count * 5 // 4 // 256) * 256))
        t_f0 = time.perf_counter()
        _td = _T.t()
        if self._mirror is not None:
            if len(tri_h):
                self._mirror_xor_triples(slots, rec["epochs"], tri_h)
            self._apply_deferred_mirror_ops()
        self._scratch.setdefault(rec["key"], rec["scratch"])
        self.perf["decode_s"] += time.perf_counter() - t_f0
        _T.lap("aoi.decode", _td)
        t_f0 = time.perf_counter()
        _te = _T.t()
        try:
            faults.check("aoi.emit")
            pe, pl = AE.fanout_triples(tri_h, c,
                                       native=(self._emit == "native"))
        except Exception as e:
            if not (_device_fault(e) or isinstance(e, RuntimeError)):
                raise
            # emit seam tripped (or the native layer rejected the buffer):
            # demote sticky to host decode and publish this tick through
            # the oracle path -- bit-exact, mirror untouched (_publish
            # never XORs)
            _demote_emit(self, e)
            chg_vals, ent_vals, gidx = EV.triples_to_words(tri_h, c)
            self._publish(slots, rec["epochs"], chg_vals, ent_vals, gidx,
                          s_n)
        else:
            self._publish_pairs(slots, rec["epochs"], _split_rows(pe),
                                _split_rows(pl))
        self.perf["emit_s"] += time.perf_counter() - t_f0
        _T.lap("aoi.emit", _te)

    def _apply_deferred_mirror_ops(self) -> None:
        """Clears issued after a tick's dispatch apply now, AFTER its
        stream (see _mirror_apply).  Applied directly: the NEXT tick may
        already be in flight, and re-deferring would postpone them forever.
        The epoch tag drops ops whose slot was released since queueing -- a
        reacquired slot may carry freshly seeded words (set_prev) the dead
        occupant's clear must not touch."""
        if not self._mirror_ops:
            return
        ops, self._mirror_ops = self._mirror_ops, []
        for op in ops:
            if self._slot_epoch.get(op[1], 0) == op[-1]:
                self._mirror_apply_now(op[:-1])

    def _publish(self, slots, epochs, chg_vals, ent_vals, gidx,
                 s_n: int) -> None:
        """Expand a classified change stream into per-slot (enter, leave)
        pair arrays and merge them into the deliverable events (shared by
        the device harvest and the host-recovery tick).  The expansion runs
        through the bucket's emit path (native C++ when emit="native", host
        numpy otherwise) -- identical order either way."""
        pe, pl = _emit_expand(self, chg_vals, ent_vals, gidx, s_n)
        self._publish_pairs(slots, epochs, _split_rows(pe), _split_rows(pl))

    def _publish_pairs(self, slots, epochs, ent_rows, lv_rows) -> None:
        """Merge per-space-row (enter, leave) pair dicts into the
        deliverable events, under the slot-epoch liveness guard."""
        empty = np.empty((0, 2), np.int32)
        for row, (slot, epoch) in enumerate(zip(slots, epochs)):
            if self._slot_epoch.get(slot, 0) != epoch:
                # slot released (and possibly reused) since this tick was
                # dispatched: its events belong to a dead space
                continue
            e = ent_rows.get(row, empty)
            l = lv_rows.get(row, empty)
            pend = self._events.get(slot)
            if pend is not None:
                # a mid-dispatch harvest (grow_space inside an AOI hook
                # calls get_prev -> flush) can land while another space's
                # prior-tick events are still undelivered: APPEND, never
                # clobber -- replay order stays oldest-first
                e = np.concatenate([pend[0], e])
                l = np.concatenate([pend[1], l])
            self._events[slot] = (e, l)

    def release_slot(self, slot: int) -> None:
        self._slot_epoch[slot] = self._slot_epoch.get(slot, 0) + 1
        super().release_slot(slot)

    def clear_entity(self, slot: int, entity_slot: int) -> None:
        self._pending_clear.append((slot, entity_slot))
        self._mirror_apply(("clear", slot, entity_slot))

    def _mirror_apply(self, op: tuple) -> None:
        """Apply (or defer) one mirror maintenance op.  With a tick in
        flight the op postdates that tick's stream, so it queues (tagged
        with the slot's current epoch) and runs after the harvest XOR;
        otherwise it applies immediately so derivations before the next
        flush already see it."""
        if self._mirror is None:
            return
        if self._inflight is not None:
            self._mirror_ops.append(op + (self._slot_epoch.get(op[1], 0),))
            return
        self._mirror_apply_now(op)

    def _mirror_apply_now(self, op: tuple) -> None:
        if op[0] == "reset":
            self._mirror[op[1]] = 0
        else:
            _slot, e = op[1], op[2]
            self._mirror[_slot, e, :] = 0
            w, b = P.word_bit_for_column(e, self.capacity)
            self._mirror[_slot, :, w] &= np.uint32(
                ~(np.uint32(1) << np.uint32(b)) & 0xFFFFFFFF)

    def _stage_inputs(self, sl, old_x, old_z, old_r, old_act) -> None:
        """Bring the device-resident staged inputs up to date with the host
        shadow.  The steady path ships a sparse (row, col, x, z) packet
        applied by a donated scatter (ops/aoi_stage.py); the fallbacks ship
        full role arrays through _h2d: after grow/reset, when r/act/sub
        changed, when the changed fraction exceeds _delta_max_frac, or when
        delta staging is disabled (the bench's full-restage baseline).

        The diff compares float BIT PATTERNS: device copies must stay
        byte-identical to the shadow or delta-staged ticks would diverge
        from full-staged ones (the bit-exactness contract)."""
        from ..ops import aoi_stage as AS

        new_x, new_z = self._hx[sl], self._hz[sl]
        diff = (new_x.view(np.uint32) != old_x.view(np.uint32)) \
            | (new_z.view(np.uint32) != old_z.view(np.uint32))
        n_changed = np.count_nonzero(diff)  # host numpy scalar
        if not (np.array_equal(self._hr[sl], old_r)
                and np.array_equal(self._hact[sl], old_act)):
            self._dev_stale.add("ra")
            self._dev_stale.add("xz")  # r/act change: full-restage fallback
        stale = self._dev_stale
        if (self.delta_staging and not stale
                and n_changed <= self._delta_max_frac * diff.size):
            if n_changed:
                faults.check("aoi.delta")
                rows, cols = np.nonzero(diff)
                pkt = AS.pad_packet(sl[rows], cols, new_x[rows, cols],
                                    new_z[rows, cols],
                                    page_granular=self.paged)
                self._dev["x"], self._dev["z"] = AS.apply_packet(
                    self._dev["x"], self._dev["z"], *pkt)
                self.stats["h2d_bytes"] += AS.packet_nbytes(*pkt)
            self.stats["delta_flushes"] += 1
            return
        if (not self.delta_staging or "xz" in stale or n_changed
                or "x" not in self._dev):
            self._dev["x"] = self._h2d("x", self._hx)
            self._dev["z"] = self._h2d("z", self._hz)
        if "ra" in stale or "r" not in self._dev:
            self._dev["r"] = self._h2d("r", self._hr)
            self._dev["act"] = self._h2d("act", self._hact)
        if "sub" in stale or "sub" not in self._dev:
            self._dev["sub"] = self._h2d("sub", self._hsub)
        stale.clear()
        self.stats["full_flushes"] += 1

    def _h2d(self, role: str, arr: np.ndarray):
        """Full upload of one shadow-backed role array -- THE seam every
        full-array staged-input H2D rides (gwlint h2d-staging); its sparse
        sibling is the delta packet in _stage_inputs."""
        import jax.numpy as jnp

        faults.check("aoi.h2d")
        self.stats["h2d_bytes"] += arr.nbytes
        return jnp.asarray(arr)

    def get_prev(self, slot: int) -> np.ndarray:  # gwlint: allow[host-sync] -- parity/debug accessor, off the tick path
        self.flush()  # apply pending resets/steps before reading
        if self.prev is None:  # device down: the mirror IS the state
            self._ensure_mirror()
            return np.array(self._mirror[slot], copy=True)
        return np.asarray(self.prev[slot])

    def set_prev(self, slot: int, words: np.ndarray) -> None:  # gwlint: allow[host-sync] -- parity/debug accessor, off the tick path
        self.flush()
        self._pending_reset.discard(slot)
        w = np.asarray(words, np.uint32)
        if self.prev is not None:
            self.prev = self.prev.at[slot].set(self._jnp.asarray(w))
        else:  # device down: seed the durable copy; rebuild uploads it
            self._ensure_mirror()
        self._mirror_stale.discard(slot)  # mirror row set to truth below
        if self._mirror is not None:
            self._mirror[slot] = w

    # -- live migration & chip-loss failover (docs/robustness.md) --------

    def _mark_evacuating(self) -> None:
        """The device is LOST (faults.DeviceLost): never touch it again.
        Host-oracle mode (calc level 2) keeps the bucket serving bit-exact
        ticks from (mirror, shadows) until the engine rebuilds its spaces
        onto a fresh bucket at the end of the current flush."""
        self._evacuating = True
        self._calc_level = 2
        self.stats["calc_level"] = 2
        self._need_rebuild = False  # there is no device to rebuild onto

    def export_snapshot(self, slot: int) -> dict:  # gwlint: allow[host-sync] -- migration snapshot, off the steady tick path
        """Live-migration wire image of one slot: the input shadows as a
        delta-staging packet + the previous-tick interest words.  Drains
        any pipelined in-flight tick first so the delivered event stream
        and the snapshot agree (double-cover alignment)."""
        self.drain()
        return _build_snapshot(
            self.capacity, self._hx[slot], self._hz[slot], self._hr[slot],
            self._hact[slot], bool(self._hsub[slot]), self.get_prev(slot))

    def import_snapshot(self, slot: int, snap: dict) -> None:  # gwlint: allow[host-sync] -- migration replay, off the steady tick path
        """Replay a migration snapshot onto this slot: scatter the packet
        into the input shadows (device copies invalidated -> the next
        flush full-restages) and seed prev from the words.  Bit-exact with
        the source tier: shadows are the durable truth everywhere (the
        delta-staging contract)."""
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
        self._dev_stale.update(("xz", "ra", "sub"))
        self.set_prev(slot, snap["words"])

    def evacuate(self) -> dict[int, dict]:
        """Snapshot every occupied slot for rebuild on a surviving device
        (the engine drives this after a DeviceLost recovery marked the
        bucket evacuating)."""
        live = sorted(set(range(self.n_slots)) - set(self._free))
        return {slot: self.export_snapshot(slot) for slot in live}

