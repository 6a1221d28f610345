"""Sparse delta staging for device-resident tick inputs.

The AOI buckets keep x/z (and r/act/sub) device-resident between flushes
and ship only the entries that actually changed since the last staged tick
(the GoWorld semantic is "batch per-tick position *updates*"; movement is
sparse).  The update packet is ``(rows, cols, xv, zv)`` -- flat index lists
plus the new float32 values -- applied by a donated in-place scatter, so a
steady tick's H2D traffic is O(movers), not O(S*C).

Shape discipline: jit compiles per packet LENGTH, so packets are padded to
a power of two (>= ``_MIN_PACKET``) by repeating their last entry -- the
scatter is an idempotent set, duplicate (row, col) pairs with identical
values are harmless -- keeping the compile-key set logarithmic in packet
size instead of one compile per mover count.  The checkpoint journal
(engine/checkpoint.py) serializes its position packets with
``page_granular`` padding: mid-size packets round up to a whole number
of ``_PAGE``-entry pages instead of the next power of two, bounding
padding waste to one page where pow2 wastes up to ~2x.  That padding is
part of the journal's record format, so existing journals restore only
while it stays as it is.

Bit-exactness: the buckets diff the float BIT PATTERNS (``view(uint32)``),
never float equality -- NaN payloads and -0.0 vs 0.0 would otherwise let
the device copy silently diverge from the host shadow, and the whole
contract is that a delta-staged tick is byte-identical to a full restage.
"""

from __future__ import annotations

import numpy as np

from . import dispatch_count as DC
from ..telemetry import trace as _T

_MIN_PACKET = 64
# page-granular padding (the checkpoint journal's packet format): one page
# of packet entries; packets up to _PAGE_KEYS pages round to a page
# multiple, larger ones fall back to pow2
_PAGE = 64
_PAGE_KEYS = 8

_apply_impl = None


def pad_packet(rows: np.ndarray, cols: np.ndarray, xv: np.ndarray,
               zv: np.ndarray, page_granular: bool = False):
    """Pad a (rows, cols, xv, zv) update packet to a power-of-two length
    (>= ``_MIN_PACKET``) by repeating the last entry.  Requires a non-empty
    packet (an empty delta skips the scatter entirely).

    ``page_granular=True`` (the checkpoint journal, engine/checkpoint.py
    ``_pos_packet``) rounds mid-size packets up to a whole number of
    ``_PAGE``-entry pages instead -- at most one page of repeated-entry
    waste, vs up to ~2x for pow2 -- up to ``_PAGE_KEYS`` pages; bigger
    packets use the pow2 ladder either way.  Padding never changes what the scatter writes
    (idempotent set of the repeated last entry), so both paddings stage
    bit-identical device state."""
    k = len(rows)
    if k == 0:
        raise ValueError("empty delta packet: skip the scatter instead")
    if page_granular and k <= _PAGE * _PAGE_KEYS:
        n = -(-k // _PAGE) * _PAGE
    else:
        n = _MIN_PACKET
        while n < k:
            n *= 2
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    xv = np.ascontiguousarray(xv, np.float32)
    zv = np.ascontiguousarray(zv, np.float32)
    if n != k:
        pad = n - k
        rows = np.concatenate([rows, np.broadcast_to(rows[-1:], (pad,))])
        cols = np.concatenate([cols, np.broadcast_to(cols[-1:], (pad,))])
        xv = np.concatenate([xv, np.broadcast_to(xv[-1:], (pad,))])
        zv = np.concatenate([zv, np.broadcast_to(zv[-1:], (pad,))])
    return rows, cols, xv, zv


def packet_nbytes(rows, cols, xv, zv) -> int:
    """Wire bytes of one padded packet (the bench's h2d_bytes attribution)."""
    return rows.nbytes + cols.nbytes + xv.nbytes + zv.nbytes


def delta_scatter(dx, dz, rows, cols, xv, zv, row_lo=None, n_rows=None):
    """Pure scatter of one packet into device-resident [S, C] x/z copies.

    With ``row_lo``/``n_rows`` the row indices are localized to a shard
    block first and out-of-block entries dropped -- this is the per-shard
    form used INSIDE shard_map by the mesh/rowshard buckets: the packet is
    replicated, each chip applies only its own rows, and no cross-chip
    collective is ever needed.
    """
    import jax.numpy as jnp

    if row_lo is not None:
        in_blk = (rows >= row_lo) & (rows < row_lo + n_rows)
        # out-of-block -> n_rows, an out-of-bounds index mode="drop" ignores
        rows = jnp.where(in_blk, rows - row_lo, n_rows)
    dx = dx.at[rows, cols].set(xv, mode="drop")
    dz = dz.at[rows, cols].set(zv, mode="drop")
    return dx, dz


def delta_scatter_1d(xs, zs, cols, xv, zv, col_lo=None, n_cols=None):
    """1-D form for the row-sharded bucket's single oversized space: x/z are
    [C] vectors (one sharded block copy, one replicated copy); same
    localize-and-drop contract as :func:`delta_scatter`."""
    import jax.numpy as jnp

    if col_lo is not None:
        in_blk = (cols >= col_lo) & (cols < col_lo + n_cols)
        cols = jnp.where(in_blk, cols - col_lo, n_cols)
    xs = xs.at[cols].set(xv, mode="drop")
    zs = zs.at[cols].set(zv, mode="drop")
    return xs, zs


def apply_packet(dx, dz, rows, cols, xv, zv):
    """Jitted donated single-device scatter: the persistent device x/z are
    updated in place (donation) and rebound by the caller.  Host arrays from
    :func:`pad_packet` ride the call's implicit H2D -- the only upload a
    delta-staged tick pays."""
    global _apply_impl
    if _apply_impl is None:
        import functools

        import jax

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def aoi_apply_packet(dx, dz, rows, cols, xv, zv):
            return delta_scatter(dx, dz, rows, cols, xv, zv)

        _apply_impl = aoi_apply_packet
    _th = _T.t()
    DC.record()
    DC.record_key("aoi.apply_packet", (dx.shape, rows.shape))
    out = _apply_impl(dx, dz, rows, cols, xv, zv)
    _T.lap("aoi.h2d", _th)
    return out
