"""Pallas TPU kernel for the fused AOI visibility pass.

Fuses predicate evaluation -> bit packing -> XOR diff for a batch of spaces,
never materializing the [C, C] boolean interest matrix in HBM: each grid step
produces packed uint32 words directly in VMEM.  This is the hot op of the
framework (reference hot path: /root/reference/engine/entity/Space.go:253-261
``aoiMgr.Moved`` + Entity.go:1221-1267 sync collection, batched per tick).

Layout (see aoi_predicate): planar packed words [C, W], W = C/32, where bit k
of word [i, w] is the interest of entity i in entity j = k*W + w.  The kernel
computes the full [TI, C] mask block on the VPU, then packs it one of two
ways:

  * ``W % 128 == 0`` (large capacities -- the hot sizes): pure-VPU
    "slice-pack": word block w gathers bit k from the STATIC lane slice
    ``mask[:, k*W:(k+1)*W]``, so packing is 32 shift-OR ops over 128-aligned
    static slices.  No MXU, no per-step constants -- measured 1.6x faster
    than the matmul pack at C=8192 on v5e (and exactly equal output).
  * otherwise (small capacities, where static lane slices would break the
    128-alignment rule): pack on the MXU as ``words = mask @ P`` with the
    constant banded matrix ``P[j, ws] = 2^(j//W)`` iff ``j % W == ws``,
    split into four byte planes (weights <= 128, partial sums <= 255 --
    exact in f32) recombined with integer shifts.

Both shapes avoid the two Mosaic limits that rule out direct formulations:
dynamic lane-dim slices must be 128-aligned, and 2D->3D vector reshapes are
unsupported.

Active handling is folded into the inputs by the wrapper so the kernel has no
mask operand:
  * inactive observer  -> radius = -1   (nothing satisfies |d| <= -1)
  * inactive observed  -> position = +inf (|inf - x| = inf/nan, never <= r)
Both transformations are exact w.r.t. the predicate -- parity with the CPU
oracle is preserved bit-for-bit (verified in tests/test_aoi_pallas.py).

Grid: (S, C // TI) -- spaces x row blocks, both parallel.  Per step the kernel
reads a [TI] row slice of x/z/r, the full [C] column arrays, and the [TI, W]
previous-words block; it writes new/enter/leave [TI, W] blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .aoi_predicate import WORD_BITS, words_per_row
from ..chip import interpret_for

_INF = float("inf")


def _mask_block(x_row, z_row, r_row, rid_row, x_col, z_col, *, ti,
                col_off=0):
    cb = x_col.shape[-1]
    xr = x_row[0, 0].reshape(ti, 1)
    zr = z_row[0, 0].reshape(ti, 1)
    rr = r_row[0, 0].reshape(ti, 1)
    xc = x_col[0, 0].reshape(1, cb)
    zc = z_col[0, 0].reshape(1, cb)
    # GLOBAL observer ids ride an input array (not the grid position): in
    # rectangular mode (observer-row-sharded space) this block's rows are a
    # slice of a larger space, so self-exclusion needs the global id
    row_ids = rid_row[0, 0].reshape(ti, 1)
    col_ids = col_off + jax.lax.broadcasted_iota(jnp.int32, (ti, cb), 1)
    m = (jnp.abs(xc - xr) <= rr) & (jnp.abs(zc - zr) <= rr)
    return m & (row_ids != col_ids)


def _write_diff(acc, prev, *outs):
    accu = jax.lax.bitcast_convert_type(acc, jnp.uint32)
    pw = prev[0]
    if len(outs) == 3:  # (new, enter, leave)
        new_out, ent_out, lv_out = outs
        new_out[0] = accu
        ent_out[0] = accu & ~pw
        lv_out[0] = pw & ~accu
    else:  # (new, changed): changed = xor; enter = chg & new, leave = chg & ~new
        new_out, chg_out = outs
        new_out[0] = accu
        chg_out[0] = accu ^ pw


def _aoi_kernel_slicepack(x_row, z_row, r_row, rid_row, x_col, z_col,
                          prev, *outs, ti, w, planes):
    """Pure-VPU pack with column blocking.

    Grid (S, C//ti, n_cb): this step sees the column slice
    ``[ci*planes*w, (ci+1)*planes*w)``, which in the planar packed layout is
    exactly bit planes ``[ci*planes, (ci+1)*planes)`` of every word -- so a
    column block contributes whole bit planes and the ``new`` output block
    (revisited across the innermost grid dim, Pallas keeps it resident in
    VMEM) doubles as the cross-block accumulator.  Diff outputs are written
    from the running accumulator; the last ci step's values are what lands
    in HBM.  With n_cb == 1 this degenerates to the original single-pass
    slice-pack (planes == 32).
    """
    ci = pl.program_id(2)
    m32 = _mask_block(
        x_row, z_row, r_row, rid_row, x_col, z_col, ti=ti,
        col_off=ci * planes * w
    ).astype(jnp.int32)
    part = jnp.zeros((ti, w), jnp.int32)
    for kk in range(planes):
        # dynamic bit plane ci*planes + kk: shift via scalar multiply
        kbit = jax.lax.shift_left(jnp.int32(1), ci * planes + kk)
        part = part | (m32[:, kk * w:(kk + 1) * w] * kbit)
    partu = jax.lax.bitcast_convert_type(part, jnp.uint32)
    new_out = outs[0]
    if planes == WORD_BITS:  # single column pass: no revisit read needed
        acc = partu
    else:
        acc = jnp.where(ci == 0, partu, new_out[0] | partu)
    pw = prev[0]
    new_out[0] = acc
    if len(outs) == 3:
        outs[1][0] = acc & ~pw
        outs[2][0] = pw & ~acc
    else:
        outs[1][0] = acc ^ pw


def _aoi_kernel_planewise(x_row, z_row, r_row, rid_row, x_col, z_col,
                          prev, *outs, ti, w, wb):
    """Slice-pack for very wide rows (w >= 2048, C >= 64k).

    Grid (S, C//ti, w//wb, 32): one step computes ONE bit plane k over the
    word range [wo*wb, (wo+1)*wb) -- its column slice is the contiguous
    [k*w + wo*wb, k*w + (wo+1)*wb).  Keeping every block [ti, wb] bounds
    VMEM at large C where the 3-dim scheme's [ti, w] blocks blow the scoped
    limit (measured: 20.2 MB > 16 MB at C=131072).  The ``new`` output block
    is revisited across the innermost (plane) dim and accumulates.
    """
    wo = pl.program_id(2)
    k = pl.program_id(3)
    m32 = _mask_block(
        x_row, z_row, r_row, rid_row, x_col, z_col, ti=ti,
        col_off=k * w + wo * wb
    ).astype(jnp.int32)
    kbit = jax.lax.shift_left(jnp.int32(1), k)
    partu = jax.lax.bitcast_convert_type(m32 * kbit, jnp.uint32)
    new_out = outs[0]
    acc = jnp.where(k == 0, partu, new_out[0] | partu)
    pw = prev[0]
    new_out[0] = acc
    if len(outs) == 3:
        outs[1][0] = acc & ~pw
        outs[2][0] = pw & ~acc
    else:
        outs[1][0] = acc ^ pw


def _aoi_kernel(x_row, z_row, r_row, rid_row, x_col, z_col, prev, *outs,
                ti, w):
    c = WORD_BITS * w
    m = _mask_block(x_row, z_row, r_row, rid_row, x_col, z_col, ti=ti)
    mf = m.astype(jnp.float32)

    # Pack on the MXU, one byte plane per matmul (see module docstring).
    j_ids = jax.lax.broadcasted_iota(jnp.int32, (c, w), 0)
    ws_ids = jax.lax.broadcasted_iota(jnp.int32, (c, w), 1)
    k_ids = j_ids // w
    hit = (j_ids % w) == ws_ids
    acc = jnp.zeros((ti, w), jnp.int32)
    for b in range(4):
        band = hit & (k_ids >= 8 * b) & (k_ids < 8 * (b + 1))
        pb = jnp.where(band, jnp.exp2((k_ids - 8 * b).astype(jnp.float32)),
                       jnp.float32(0.0))
        byte = jax.lax.dot(mf, pb, preferred_element_type=jnp.float32)
        acc = acc | (byte.astype(jnp.int32) << (8 * b))
    _write_diff(acc, prev, *outs)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret", "emit"))
def aoi_step_pallas(x, z, radius, active, prev_words, *, block_rows=128,
                    interpret=None, emit="entlv", cols=None, row_ids=None):
    """Batched AOI tick on TPU.

    Args: x, z, radius [S, C] f32; active [S, C] bool; prev_words [S, C, W]
    uint32.  With ``emit="entlv"`` (default) returns (new_words, enter_words,
    leave_words); with ``emit="chg"`` returns (new_words, changed_words) where
    ``changed = new ^ prev`` -- one fewer [S, C, W] HBM write per tick, and
    enter/leave recover exactly as ``chg & new`` / ``chg & ~new``.
    Bit-exact with :func:`aoi_dense.aoi_step_dense` and the CPU oracle.

    RECTANGULAR mode (observer-row-sharded oversized spaces): with
    ``cols=(x_col, z_col, active_col)`` [S, C_cols] the row arrays are a
    BLOCK of observers evaluated against all C_cols candidates;
    ``prev_words`` is then [S, C_rows, W(C_cols)] and ``row_ids``
    [S, C_rows] int32 must carry the observers' GLOBAL column ids (for
    self-exclusion).  Each device of a row-sharded mesh calls this with its
    row block -- no collectives, candidates are replicated at H2D.
    """
    s, c_rows = x.shape
    if cols is None:
        x_c, z_c, act_c = x, z, active
        c = c_rows
    else:
        x_c, z_c, act_c = cols
        c = x_c.shape[-1]
    w = words_per_row(c)
    # Legalize the row-block hint: the row slice rides the lane dim, so a
    # partial block must be a 128-multiple that divides C_rows; else full.
    ti = min(block_rows, c_rows)
    if ti != c_rows:
        ti = (ti // 128) * 128
        if ti == 0 or c_rows % ti != 0:
            ti = c_rows
    if interpret is None:
        interpret = interpret_for()

    # Fold activity into coordinates/radius (exact; see module docstring).
    # The [S, 1, C] layout keeps every block's trailing dims either equal to
    # the array dims or lane/sublane aligned -- the Mosaic tiling rule that a
    # 2D [S, C] layout breaks whenever S is not a multiple of 8.
    x_eff = jnp.where(active, x, jnp.float32(_INF)).reshape(s, 1, c_rows)
    r_eff = jnp.where(active, radius, jnp.float32(-1.0)).reshape(s, 1, c_rows)
    if cols is None:
        z_eff = jnp.where(active, z, jnp.float32(_INF)).reshape(s, 1, c)
        xc_eff, zc_eff = x_eff, z_eff
    else:
        z_eff = jnp.where(active, z, jnp.float32(_INF)).reshape(s, 1, c_rows)
        xc_eff = jnp.where(act_c, x_c, jnp.float32(_INF)).reshape(s, 1, c)
        zc_eff = jnp.where(act_c, z_c, jnp.float32(_INF)).reshape(s, 1, c)
    if row_ids is None:
        row_ids = jnp.broadcast_to(
            jnp.arange(c_rows, dtype=jnp.int32)[None, :], (s, c_rows))
    rid = row_ids.astype(jnp.int32).reshape(s, 1, c_rows)

    out_shape = jax.ShapeDtypeStruct((s, c_rows, w), jnp.uint32)
    n_out = 3 if emit == "entlv" else 2

    if w % 2048 == 0:
        # Very wide rows: plane-wise 4-dim grid keeps blocks [ti, wb].
        # (wb must divide w or the column BlockSpec and col_off disagree.)
        wb = 2048
        row_spec = pl.BlockSpec((1, 1, ti), lambda si, bi, wo, k: (si, 0, bi))
        col_spec = pl.BlockSpec(
            (1, 1, wb), lambda si, bi, wo, k: (si, 0, k * (w // wb) + wo))
        words_spec = pl.BlockSpec(
            (1, ti, wb), lambda si, bi, wo, k: (si, bi, wo))
        kernel = functools.partial(_aoi_kernel_planewise, ti=ti, w=w, wb=wb)
        grid = (s, c_rows // ti, w // wb, WORD_BITS)
    elif w % 128 == 0:
        # Column-blocked slice-pack: cap the mask block at [ti, 8192] so VMEM
        # stays bounded as C grows (a [128, C] mask is 64 MB at C=131072).
        # A column block covers whole bit planes (cb = planes * w), and
        # planes must divide WORD_BITS or the grid would drop the tail
        # planes -- so planes is the largest power of two <= min(32, 8192/w).
        planes = 1
        while planes < WORD_BITS and planes * 2 * w <= 8192:
            planes *= 2
        cb = planes * w
        n_cb = WORD_BITS // planes
        row_spec = pl.BlockSpec((1, 1, ti), lambda si, bi, ci: (si, 0, bi))
        col_spec = pl.BlockSpec((1, 1, cb), lambda si, bi, ci: (si, 0, ci))
        words_spec = pl.BlockSpec((1, ti, w), lambda si, bi, ci: (si, bi, 0))
        kernel = functools.partial(_aoi_kernel_slicepack, ti=ti, w=w,
                                   planes=planes)
        grid = (s, c_rows // ti, n_cb)
    else:
        row_spec = pl.BlockSpec((1, 1, ti), lambda si, bi: (si, 0, bi))
        col_spec = pl.BlockSpec((1, 1, c), lambda si, bi: (si, 0, 0))
        words_spec = pl.BlockSpec((1, ti, w), lambda si, bi: (si, bi, 0))
        kernel = functools.partial(_aoi_kernel, ti=ti, w=w)
        grid = (s, c_rows // ti)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec, row_spec, col_spec, col_spec,
                  words_spec],
        out_specs=(words_spec,) * n_out,
        out_shape=(out_shape,) * n_out,
        interpret=interpret,
    )(x_eff, z_eff, r_eff, rid, xc_eff, zc_eff, prev_words)
