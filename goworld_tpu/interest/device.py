"""The fused device step for interest-policy stacks.

One jitted function evaluates the WHOLE composition -- radius predicate,
team/faction mask, tier hysteresis, line-of-sight sampling -- and packs
the result to planar uint32 words on device, behind the same
AOI-calculator seam the base buckets use (the stack intercepts
``AOIEngine.take_events``; see interest/policy.py).  The expression tree
is ops/interest_kernels.py with ``xp=jax.numpy``: identical structure to
the CPU oracle, which is what makes the two bit-exact (the kernels
module documents the FMA/dyadic-midpoint discipline that survives XLA).

Compilation is cached per (capacity, stack config, cadence): every space
sharing a capacity and policy parameters shares one compiled step for
full ticks and one for off-cadence ticks, so a 256-space load-harness
world compiles exactly twice.  The distance-field GRID rides as an
operand (content changes never recompile); its geometry (origin, cell,
shape) is baked into the closure.

jax loads lazily here -- a host-mode (``interest_mode="host"``) engine
never imports it.
"""

from __future__ import annotations

import numpy as np

from ..ops import interest_kernels as K

_STEP_CACHE: dict = {}


def _get_step(capacity: int, cfg, full: bool):
    key = (capacity, cfg.key(), bool(full))
    fn = _STEP_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def interest_step(x, z, r, act, team, vis, prev_final_words,
                          prev_near_words, grid):
            prev_final = K.unpack_words(prev_final_words, capacity, jnp)
            prev_near = K.unpack_words(prev_near_words, capacity, jnp)
            final, near = K.step_masks(x, z, r, act, team, vis,
                                       prev_final, prev_near, cfg, full,
                                       jnp, grid=grid)
            return K.pack_bool(final, jnp), K.pack_bool(near, jnp)

        fn = jax.jit(interest_step)
        _STEP_CACHE[key] = fn
    return fn


def eval_step(x, z, r, act, team, vis, prev_final_words, prev_near_words,
              cfg, full: bool, grid=None):
    """One fused stack evaluation on device: packed
    (final_words, near_words) as host uint32 [C, W] -- bit-exact with
    interest/oracle.eval_step on the same inputs.  Raises whatever the
    device raises; the stack classifies (engine/aoi._device_fault) and
    falls back to the oracle for the step."""
    fn = _get_step(x.shape[0], cfg, full)
    fw, nw = fn(np.asarray(x, np.float32), np.asarray(z, np.float32),
                np.asarray(r, np.float32), np.asarray(act, bool),
                np.asarray(team, np.uint32), np.asarray(vis, np.uint32),
                prev_final_words, prev_near_words, grid)
    # the stack's flush runs AFTER bucket harvest (engine/aoi.flush), so
    # this fetch overlaps nothing it could have pipelined against; the
    # packed words are the step's entire output
    return (np.asarray(fw), np.asarray(nw))  # gwlint: allow[host-sync] -- the stack step's single result fetch, post-harvest
