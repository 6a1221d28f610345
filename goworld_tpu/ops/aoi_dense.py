"""Dense JAX backend for the AOI visibility pass (pure jnp, no Pallas).

Evaluates the exact predicate of :mod:`aoi_predicate` on [C] position arrays,
packs the interest matrix into planar uint32 words, and XOR-diffs against the
previous tick.  This is the readable reference implementation the Pallas
kernel (:mod:`aoi_pallas`) is checked against; it is also a perfectly good
execution path on its own for capacities where XLA's fusion handles the [C, C]
intermediate well.

All functions are shape-polymorphic over leading batch (space) dimensions only
via ``jax.vmap``; the core operates on a single space.

Reference seam: /root/reference/engine/entity/Space.go:253-261 (Moved ->
AOI recompute) batched per tick per the north-star design.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .aoi_predicate import WORD_BITS, words_per_row
from ..chip import interpret_for


def interest_words_dense(x, z, radius, active):
    """Predicate over all pairs, packed.  [C] f32 inputs -> [C, W] uint32."""
    c = x.shape[0]
    w = words_per_row(c)
    dx = jnp.abs(x[None, :] - x[:, None])
    dz = jnp.abs(z[None, :] - z[:, None])
    r = radius[:, None]
    m = (dx <= r) & (dz <= r)
    m &= active[:, None] & active[None, :]
    m &= ~jnp.eye(c, dtype=bool)
    planes = m.reshape(c, WORD_BITS, w).astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)[None, :, None]
    return jnp.sum(planes << shifts, axis=1, dtype=jnp.uint32)


def aoi_step_dense(x, z, radius, active, prev_words):
    """One tick: returns (new_words, enter_words, leave_words), all [C, W]."""
    new_words = interest_words_dense(x, z, radius, active)
    enter = new_words & ~prev_words
    leave = prev_words & ~new_words
    return new_words, enter, leave


aoi_step_dense_batched = jax.vmap(aoi_step_dense)  # [S, C] / [S, C, W]


def interest_words_dense_rect(x, z, radius, active, x_col, z_col, act_col,
                              row_ids):
    """Rectangular predicate (observer rows vs all candidates), packed.
    [R] observer arrays + [C] candidate arrays + [R] GLOBAL row ids ->
    [R, W(C)] uint32.  The dense mirror of aoi_pallas's ``cols=`` mode
    (observer-row-sharded oversized spaces)."""
    c = x_col.shape[0]
    w = words_per_row(c)
    r = x.shape[0]
    dx = jnp.abs(x_col[None, :] - x[:, None])
    dz = jnp.abs(z_col[None, :] - z[:, None])
    rr = radius[:, None]
    m = (dx <= rr) & (dz <= rr)
    m &= active[:, None] & act_col[None, :]
    m &= row_ids[:, None] != jnp.arange(c, dtype=row_ids.dtype)[None, :]
    planes = m.reshape(r, WORD_BITS, w).astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)[None, :, None]
    return jnp.sum(planes << shifts, axis=1, dtype=jnp.uint32)


def aoi_step_chg_dense(x, z, radius, active, prev_words, cols=None,
                       row_ids=None):
    """Batched ``emit="chg"`` step, dense formulation: the drop-in
    replacement for ``aoi_step_pallas(..., emit="chg")`` on NON-TPU
    platforms -- interpret-mode Pallas evaluates its grid step by step in
    Python (a 16k-capacity mesh flush measured ~49 s), while this compiles
    to one fused XLA CPU program.  Bit-exact with the kernel
    (tests/test_aoi_pallas.py pins square AND rect parity)."""
    if cols is None:
        new = jax.vmap(interest_words_dense)(x, z, radius, active)
    else:
        x_c, z_c, act_c = cols
        new = jax.vmap(interest_words_dense_rect)(
            x, z, radius, active, x_c, z_c, act_c, row_ids)
    return new, new ^ prev_words


def aoi_step_chg(x, z, radius, active, prev_words, cols=None, row_ids=None,
                 platform=None):
    """THE step entry for engine buckets: ``emit="chg"`` with square or
    rectangular (``cols=``/``row_ids=``) operands, routed by platform.
    On TPU -> the Pallas kernel; anywhere else -> this module's dense
    formulation (one fused XLA program -- interpret-mode Pallas walks its
    grid step-by-step in Python).  ``platform`` defaults to
    ``jax.default_backend()`` and decides the kernel's interpret mode too,
    so a compile for a described TPU from a CPU process reaches Mosaic."""
    if platform is None:
        platform = jax.default_backend()
    if platform != "tpu":
        return aoi_step_chg_dense(x, z, radius, active, prev_words,
                                  cols=cols, row_ids=row_ids)
    from .aoi_pallas import aoi_step_pallas

    return aoi_step_pallas(x, z, radius, active, prev_words, emit="chg",
                           cols=cols, row_ids=row_ids,
                           interpret=interpret_for(platform))
