"""Block-culled AOI kernel for large capacities.

The dense kernel (ops/aoi_pallas) evaluates all C^2 pairs per space per
tick -- 17G pair-tests at the BASELINE `million` config (64 x 16384).  This
module is the windowed-work answer (the reference's XZList/TowerAOI idea,
/root/reference/engine/entity/Space.go:105-115, rebuilt TPU-style):

  1. per space, order entities by x (``argsort`` + gathers -- the order
     only needs to make index-contiguous GROUPS spatially compact, not be
     perfectly sorted, so nearly-sorted inputs work identically);
  2. compute per row-block reach bounds ``[min(x-r), max(x+r)]`` and per
     column-group position bounds ``[min x, max x]`` from the actual data;
  3. a planewise Pallas kernel runs the same exact predicate + slice-pack
     as the dense kernel, but each (row-block, column-group, bit-plane)
     grid step first consults a precomputed SMEM cull flag and skips ALL
     mask/pack compute for spatially disjoint blocks (``pl.when``) --
     compute drops to the overlap fraction while outputs stay dense packed
     words.

Bounds are widened by an absolute f32-safety margin so the cull can only
ever ADMIT extra blocks, never drop a true pair; every admitted pair is
then re-checked by the exact f32 predicate, so the words are bit-identical
to the dense kernel's (tests/test_aoi_grid.py proves it against both the
dense kernel and the CPU oracle through the permutation).

The words come out in SORTED index space together with the permutation;
callers either translate sparse events through the permutation or, like
bench.py's device-cadence pipeline, avoid the translation entirely by
recomputing the previous tick's words under the CURRENT order (positions
are a pure function input) and diffing in sorted space.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .aoi_predicate import WORD_BITS, words_per_row
from ..chip import interpret_for

_INF = float("inf")


def _mask_block(x_row, z_row, r_row, xc, zc, *, ti, col_off, bi):
    """xc/zc are [1, cb] column slices (already loaded); rows come as refs."""
    cb = xc.shape[-1]
    xr = x_row[0, 0].reshape(ti, 1)
    zr = z_row[0, 0].reshape(ti, 1)
    rr = r_row[0, 0].reshape(ti, 1)
    row_ids = bi * ti + jax.lax.broadcasted_iota(jnp.int32, (ti, 1), 0)
    col_ids = col_off + jax.lax.broadcasted_iota(jnp.int32, (ti, cb), 1)
    m = (jnp.abs(xc - xr) <= rr) & (jnp.abs(zc - zr) <= rr)
    return m & (row_ids != col_ids)


def _accumulate_culled_plane(need, x_row, z_row, r_row, x_col, z_col, out,
                             *, ti, w, wb):
    """One grid step of the planewise slice-pack with whole-step SMEM
    culling -- the shared body of both culled kernels.

    Grid (S, C//ti, w//wb, 32): step (si, bi, wo, k) computes bit plane k
    over words [wo*wb, (wo+1)*wb); the out block accumulates across the
    innermost plane dim (k==0 initializes, so skipped revisits stay
    sound), and the whole step's mask+pack is predicated on the SMEM cull
    flag.  Structure notes from measurement on v5e: whole-step ``pl.when``
    predication actually skips the work, whereas per-plane ``pl.when``
    inside one step lowers to predicated full execution, and a dynamic
    fori_loop over a packed plane list costs ~100 us/step in Mosaic
    overheads -- both lose the cull's win.  The remaining per-step cost of
    this 4-dim structure is amortized by large row blocks (block_rows).
    """
    bi = pl.program_id(1)
    wo = pl.program_id(2)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        out[0] = jnp.zeros_like(out[0])

    @pl.when(need[0, 0, wo, k] != 0)
    def _compute():
        off = k * w + wo * wb
        xc = x_col[0, 0].reshape(1, wb)
        zc = z_col[0, 0].reshape(1, wb)
        m32 = _mask_block(
            x_row, z_row, r_row, xc, zc, ti=ti, col_off=off, bi=bi,
        ).astype(jnp.int32)
        kbit = jax.lax.shift_left(jnp.int32(1), k)
        partu = jax.lax.bitcast_convert_type(m32 * kbit, jnp.uint32)
        out[0] = out[0] | partu


def _culled_kernel(need, x_row, z_row, r_row, x_col, z_col, out, *, ti, w,
                   wb):
    _accumulate_culled_plane(need, x_row, z_row, r_row, x_col, z_col, out,
                             ti=ti, w=w, wb=wb)


def _culled_step_kernel(need, x_row, z_row, r_row, x_col, z_col, prev,
                        new_out, chg_out, *, ti, w, wb):
    """The ``_culled_kernel`` structure fused with the prev-words diff.

    ``new`` accumulates across the innermost plane dim exactly as in
    ``_culled_kernel``; ``chg = new ^ prev`` is rewritten from the running
    accumulator every step (unconditionally -- a VMEM write is cheap and
    both out blocks land in HBM once per revisit window), so the last
    plane's value is the true diff even when that plane's step is culled.
    """
    _accumulate_culled_plane(need, x_row, z_row, r_row, x_col, z_col,
                             new_out, ti=ti, w=w, wb=wb)
    chg_out[0] = new_out[0] ^ prev[0]


def _legal_blocks(c, w, block_rows, col_words, interpret):
    ti = min(block_rows, c)
    if ti != c:
        ti = (ti // 128) * 128
        if ti == 0 or c % ti != 0:
            ti = c
    wb = col_words or min(w, 512)
    while w % wb:
        wb //= 2
    if interpret is None:
        interpret = interpret_for()
    if not interpret and wb < 128:
        # Mosaic lane rule: the column/out blocks ride the lane dim, so the
        # word window must be >= 128 -- i.e. this kernel needs W >= 128
        # (C >= 4096).  Below that the dense kernel is the right tool
        # anyway (the whole space fits a handful of blocks).
        raise ValueError(
            f"culled kernel needs col_words >= 128 on TPU (got wb={wb} "
            f"at C={c}); use ops.aoi_pallas.aoi_step_pallas below C=4096")
    return ti, wb, interpret


def _cull_table(x, radius, active, x_eff, r_eff, *, s, c, ti, wb):
    """need[si, bi, wo, k] (int32) + culled fraction (f32 scalar).

    Row block bi reaches x in [min(x-r), max(x+r)]; column group (wo, k)
    covers entities [k*w + wo*wb, k*w + (wo+1)*wb) and spans [min x, max x].
    Bounds are widened by an absolute f32-safety margin so the cull can
    only ever ADMIT extra blocks (every admitted pair is re-checked by the
    exact predicate); empty blocks drop via the +-inf folds.
    """
    w = words_per_row(c)
    n_bi = c // ti
    n_wo = w // wb
    # conservative f32 margin: bounds may round, the predicate is exact, so
    # the window only needs to be a hair wider than any rounding error
    margin = jnp.float32(1e-3) + jnp.float32(1e-5) * (
        jnp.max(jnp.where(active, jnp.abs(x), 0.0)) + jnp.max(radius))
    xr_blocks = x_eff.reshape(s, n_bi, ti)
    rr_blocks = r_eff.reshape(s, n_bi, ti)
    fin = jnp.isfinite(xr_blocks)
    row_lo = jnp.min(jnp.where(fin, xr_blocks - rr_blocks, jnp.float32(_INF)),
                     axis=2) - margin
    row_hi = jnp.max(jnp.where(fin, xr_blocks + rr_blocks,
                               jnp.float32(-_INF)), axis=2) + margin
    # reshape to [s, 32, n_wo, wb] puts k before wo
    xc = x_eff.reshape(s, WORD_BITS, n_wo, wb)
    finc = jnp.isfinite(xc)
    col_lo = jnp.min(jnp.where(finc, xc, jnp.float32(_INF)), axis=3)
    col_hi = jnp.max(jnp.where(finc, xc, jnp.float32(-_INF)), axis=3)
    need = ((col_lo[:, None, :, :] <= row_hi[:, :, None, None])
            & (col_hi[:, None, :, :] >= row_lo[:, :, None, None]))
    need = jnp.swapaxes(need, 2, 3).astype(jnp.int32)  # -> [s, bi, wo, k]
    culled_frac = 1.0 - jnp.mean(need.astype(jnp.float32))
    return need, culled_frac


def _culled_specs(c, w, ti, wb, n_wo):
    row_spec = pl.BlockSpec(
        (1, 1, ti), lambda si, bi, wo, k: (si, 0, bi))
    col_spec = pl.BlockSpec(
        (1, 1, wb), lambda si, bi, wo, k: (si, 0, k * (w // wb) + wo))
    out_spec = pl.BlockSpec(
        (1, ti, wb), lambda si, bi, wo, k: (si, bi, wo))
    # SMEM blocks must keep the LAST TWO dims whole (Mosaic: divisible by
    # (8, 128) or equal to the array dims), so the block spans all of
    # (n_wo, 32) and the kernel indexes (wo, k) dynamically
    need_spec = pl.BlockSpec(
        (1, 1, n_wo, WORD_BITS), lambda si, bi, wo, k: (si, bi, 0, 0),
        memory_space=pltpu.SMEM)
    return row_spec, col_spec, out_spec, need_spec


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "col_words", "interpret"))
def aoi_words_culled(x, z, radius, active, *, block_rows=128, col_words=0,
                     interpret=None):
    """Packed interest words for the CURRENT positions, with block culling.

    Args: x, z, radius [S, C] f32; active [S, C] bool -- in the CALLER's
    index order, which should be spatially compact per 128-index group
    (use :func:`sort_spaces` first).  Returns ``(words [S, C, W] u32,
    culled_frac f32 scalar)`` where culled_frac is the fraction of grid
    blocks skipped (the work saved; 0 on pathological layouts).

    No prev/diff input: this computes absolute words.  Diffing strategies
    are the caller's (see module docstring).  Bit-exact with
    ``aoi_step_pallas(... prev=0)[0]`` on identical inputs.
    """
    s, c = x.shape
    w = words_per_row(c)
    ti, wb, interpret = _legal_blocks(c, w, block_rows, col_words, interpret)

    x_eff = jnp.where(active, x, jnp.float32(_INF))
    z_eff = jnp.where(active, z, jnp.float32(_INF))
    r_eff = jnp.where(active, radius, jnp.float32(-1.0))
    need, culled_frac = _cull_table(x, radius, active, x_eff, r_eff,
                                    s=s, c=c, ti=ti, wb=wb)

    x3 = x_eff.reshape(s, 1, c)
    z3 = z_eff.reshape(s, 1, c)
    r3 = r_eff.reshape(s, 1, c)
    row_spec, col_spec, out_spec, need_spec = _culled_specs(
        c, w, ti, wb, w // wb)
    words = pl.pallas_call(
        functools.partial(_culled_kernel, ti=ti, w=w, wb=wb),
        grid=(s, c // ti, w // wb, WORD_BITS),
        in_specs=[need_spec, row_spec, row_spec, row_spec, col_spec,
                  col_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((s, c, w), jnp.uint32),
        interpret=interpret,
    )(need, x3, z3, r3, x3, z3)
    return words, culled_frac


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "col_words", "interpret"))
def aoi_step_culled(x, z, radius, active, prev_words, *, block_rows=512,
                    col_words=0, interpret=None):
    """One culled tick with the diff fused: ``(new, chg, culled_frac)``.

    ``prev_words`` must be packed in the SAME index order as the inputs --
    i.e. the caller keeps one x-sorted order FIXED across ticks and carries
    the previous tick's words in it (re-sorting periodically by recomputing
    the old words under the new order; see bench.py's fixed-order grid
    pipeline).  Bit-exact with ``aoi_step_pallas(..., emit="chg")`` on
    identical inputs; the cull only skips pair blocks whose widened x-reach
    windows are disjoint, and the ``new`` accumulator plus unconditional
    ``chg`` rewrite keep skipped blocks sound (zero bits / pure prev).

    Default ``block_rows=512``: the 4-dim grid pays a fixed per-step cost,
    and at (wo, k) granularity the step count is 8x the dense kernel's --
    512-row blocks cut it 4x for a modest cull-width loss (measured on
    v5e: see CHANGES_r05.md, fixed-order culled kernel).
    """
    s, c = x.shape
    w = words_per_row(c)
    ti, wb, interpret = _legal_blocks(c, w, block_rows, col_words, interpret)

    x_eff = jnp.where(active, x, jnp.float32(_INF))
    z_eff = jnp.where(active, z, jnp.float32(_INF))
    r_eff = jnp.where(active, radius, jnp.float32(-1.0))
    need, culled_frac = _cull_table(x, radius, active, x_eff, r_eff,
                                    s=s, c=c, ti=ti, wb=wb)

    x3 = x_eff.reshape(s, 1, c)
    z3 = z_eff.reshape(s, 1, c)
    r3 = r_eff.reshape(s, 1, c)
    row_spec, col_spec, out_spec, need_spec = _culled_specs(
        c, w, ti, wb, w // wb)
    out_shape = jax.ShapeDtypeStruct((s, c, w), jnp.uint32)
    new, chg = pl.pallas_call(
        functools.partial(_culled_step_kernel, ti=ti, w=w, wb=wb),
        grid=(s, c // ti, w // wb, WORD_BITS),
        in_specs=[need_spec, row_spec, row_spec, row_spec, col_spec,
                  col_spec, out_spec],
        out_specs=(out_spec, out_spec),
        out_shape=(out_shape, out_shape),
        interpret=interpret,
    )(need, x3, z3, r3, x3, z3, prev_words)
    return new, chg, culled_frac


def sort_spaces(x, z, radius, active):
    """Order each space's entities by x (inactive entries sink to the end
    via the +inf fold).  Returns (xs, zs, rs, acts, perm) -- perm maps
    sorted index -> original index.

    NOTE: device-side argsort measured ~150 ms per [8, 16384] call on this
    chip -- do NOT call this per tick.  Sort once (host-side is fine) to
    establish a spatially compact slot order and let it go stale: the cull
    bounds come from the actual per-block data, so a drifted order only
    widens the windows, never breaks exactness
    (tests/test_aoi_grid.py::test_nearly_sorted_order_still_exact)."""
    x_eff = jnp.where(active, x, jnp.float32(_INF))
    perm = jnp.argsort(x_eff, axis=1)
    take = lambda a: jnp.take_along_axis(a, perm, axis=1)
    return take(x), take(z), take(radius), take(active), perm
