"""One-dispatch fused bucket tick (ROADMAP #3: the last single-machine
bottleneck is the host/XLA boundary, not the device).

The unfused steady tick crosses Python -> XLA at least twice per bucket
(delta scatter, then the bucket step), and at r04 that host-side
overhead is the gap between 8 ms of device time and 84 ms of wall.  This module compiles the WHOLE
steady-state tick into one jitted, donated, double-buffered program:

    delta-scatter of the staged packet
      -> neighbor kernel (aoi_step_chg, pallas/dense per platform)
      -> diff mask by subscription
      -> triple extraction

so a steady tick is one enqueue plus one D2H fetch.

Composition, not duplication: the body is assembled from the ops
layer's jit-free pure functions (``aoi_stage.delta_scatter``,
``aoi_dense.aoi_step_chg``, ``events.extract_triples``) -- the same
inner functions the unfused path jits separately -- so fused vs unfused is a program-boundary
choice, never a semantics choice, and bit-exactness is by construction.

Donation discipline: the persistent interest state (``prev_all``), the
scratch output buffers and the device x/z copies are all donated and
rebound by the caller -- steady state allocates nothing.  The staged packet rides the call's implicit H2D.  An empty
packet (zero movers) passes shape-(0,) index arrays: the scatter is a
no-op and the compile key stays distinct and bounded
(``aoi_stage.pad_packet`` bounds the non-empty keys).

Fault surface: these entry points run INSIDE the bucket's fused
attempt, after the ``aoi.delta``/``aoi.kernel`` seam checks and before
any device mutation -- a seam firing demotes the tick to the unfused
path (counted in ``aoi.fused_demotions``), which then runs clean in the
same call.  Nothing here may sync with the host; the gwlint
fused-dispatch rule walks these functions and rejects
``block_until_ready``/``np.asarray``-style calls.

Impls are built lazily so importing this module never loads jax
(cpu-only processes, gwlint itself).
"""

from __future__ import annotations

from . import aoi_stage as AS
from . import dispatch_count as DC
from . import events as EV

_tri_impl = None


def fused_tri_step(prev_all, new_buf, chg_buf, tri_buf, x_all, z_all,
                   rows, cols, xv, zv, slot_idx, r_all, act_all,
                   sub_all, max_triples, platform=None):
    """Fused triples-mode tick: scatter + kernel + diff + triple
    extraction in one program.

    Returns ``(prev_all, new_buf, chg_buf, tri_buf, [count, chunks],
    x_all, z_all)`` -- the same scratch/rec shape as the unfused tri step
    plus the rebound device x/z, so the existing tri harvest decodes the
    result unchanged."""
    global _tri_impl
    if _tri_impl is None:
        import functools

        import jax
        import jax.numpy as jnp

        from .aoi_dense import aoi_step_chg

        @functools.partial(
            jax.jit,
            static_argnames=("max_triples", "platform"),
            donate_argnums=(0, 1, 2, 3, 4, 5))
        def aoi_fused_tri(prev_all, new_buf, chg_buf, tri_buf, x_all, z_all,
                          rows, cols, xv, zv, slot_idx, r_all, act_all,
                          sub_all, max_triples, platform=None):
            x_all, z_all = AS.delta_scatter(x_all, z_all, rows, cols,
                                            xv, zv)
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
            tri, count, chunks = EV.extract_triples(chg, new, chg.shape[1],
                                                    max_triples)
            new_buf = new_buf.at[:].set(new)
            chg_buf = chg_buf.at[:].set(chg)
            tri_buf = tri_buf.at[:].set(tri)
            return (prev_all, new_buf, chg_buf, tri_buf,
                    jnp.stack([count, chunks]), x_all, z_all)

        _tri_impl = aoi_fused_tri
    # compile-key meter (steady-state recompiles = 0 pins): the static
    # args + every donated shape ARE the jit cache key
    DC.record_key("aoi.fused_tri", (prev_all.shape, new_buf.shape,
                                    tri_buf.shape, rows.shape,
                                    max_triples, platform))
    return _tri_impl(prev_all, new_buf, chg_buf, tri_buf, x_all, z_all,
                     rows, cols, xv, zv, slot_idx, r_all, act_all,
                     sub_all, max_triples, platform=platform)
