"""Culled-vs-dense kernel tuning at the giant-C BASELINE shapes (real TPU).

Measures the MARGINAL per-pass cost (long-minus-half chained drains, each
ending in a REAL host fetch -- see microbench_extract.py: single-run
timings carry a fixed dispatch cost) of:

  * the dense kernel (``aoi_step_pallas emit="chg"``) -- the recorded path;
  * the fused culled step (``aoi_step_culled``) across block_rows values,
    in x-sorted order (the fixed-order pipeline's steady-state tick).

Run: python scripts/microbench_grid.py [million|zipf|both]
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from goworld_tpu.ops import words_per_row
from goworld_tpu.ops.aoi_grid import aoi_step_culled
from goworld_tpu.ops.aoi_pallas import aoi_step_pallas

N = 8          # full chain length (marginal = T(N) - T(N/2) over N/2)
REPS = 3
QSCALE = np.float32(1.0 / 16.0)
QMAX = 80


def make_shape(kind):
    rng = np.random.default_rng(7)
    if kind == "million":
        s, c, world, radius = 64, 16384, 11314.0, 100.0
        x = rng.uniform(0, world, (s, c)).astype(np.float32)
        z = rng.uniform(0, world, (s, c)).astype(np.float32)
    else:  # zipf100k: 90% of 100k in the central 10%-linear hot zone
        s, c, world, radius = 1, 131072, 60000.0, 100.0
        hot = rng.random((s, c)) < 0.9
        lo, hi = 0.45 * world, 0.55 * world
        x = np.where(hot, rng.uniform(lo, hi, (s, c)),
                     rng.uniform(0, world, (s, c))).astype(np.float32)
        z = np.where(hot, rng.uniform(lo, hi, (s, c)),
                     rng.uniform(0, world, (s, c))).astype(np.float32)
    act = np.zeros((s, c), bool)
    n_active = 100000 if kind == "zipf" else s * c
    per = n_active // s
    act[:, :per] = True
    r = np.full((s, c), radius, np.float32)
    # x-sorted order (the fixed-order pipeline's steady state)
    key = np.where(act, x, np.float32("inf"))
    perm = np.argsort(key, axis=1, kind="stable")
    take = lambda a: np.take_along_axis(a, perm, axis=1)
    qx = [rng.integers(-QMAX, QMAX + 1, (s, c)).astype(np.int8)
          for _ in range(N)]
    return (take(x), take(z), take(r), take(act), np.float32(world), qx)


def marginal(tick, carry0, deltas):
    """tick(carry, dq) -> (carry, fetchable) chained; marginal per call."""
    def drain(k):
        c = carry0
        t0 = time.perf_counter()
        out = None
        for i in range(k):
            c, out = tick(c, deltas[i])
        _ = np.asarray(out)    # REAL fetch: forces the chain
        return time.perf_counter() - t0
    drain(2)  # compile + warm
    tf = min(drain(N) for _ in range(REPS))
    th = min(drain(N // 2) for _ in range(REPS))
    return (tf - th) / (N - N // 2)


def bench_kind(kind):
    xh, zh, rh, acth, world, qxs = make_shape(kind)
    s, c = xh.shape
    w = words_per_row(c)
    x, z = jnp.asarray(xh), jnp.asarray(zh)
    r, act = jnp.asarray(rh), jnp.asarray(acth)
    deltas = [jnp.asarray(q) for q in qxs]
    jax.block_until_ready(deltas)
    prev0 = jnp.zeros((s, c, w), jnp.uint32)
    print(f"\n== {kind}: {s}x{c} (w={w}) ==")

    @jax.jit
    def dense_tick(carry, dq):
        xx, zz, prev = carry
        xx = jnp.clip(xx + dq.astype(jnp.float32) * QSCALE, 0.0, world)
        new, chg = aoi_step_pallas(xx, zz, r, act, prev, emit="chg")
        return (xx, zz, new), chg[0, 0, :8]

    prev1, _ = aoi_step_pallas(x, z, r, act, prev0, emit="chg")
    jax.block_until_ready(prev1)
    del prev0
    m = marginal(dense_tick, (x, z, prev1), deltas)
    print(f"  dense emit=chg:                 {m * 1e3:8.2f} ms/pass")

    for br in (512, 1024):
        for cw in (512,) if w >= 512 else (w,):
            @jax.jit
            def culled_tick(carry, dq, _br=br, _cw=cw):
                xx, zz, prev = carry
                xx = jnp.clip(xx + dq.astype(jnp.float32) * QSCALE, 0.0,
                              world)
                new, chg, frac = aoi_step_culled(
                    xx, zz, r, act, prev, block_rows=_br, col_words=_cw)
                return (xx, zz, new), jnp.concatenate(
                    [chg[0, 0, :8].astype(jnp.float32), frac[None]])

            try:
                m = marginal(culled_tick, (x, z, prev1), deltas)
                # one extra call for the reported cull fraction
                _c, out = culled_tick((x, z, prev1), deltas[0])
                frac = float(np.asarray(out)[-1])
                print(f"  culled br={br:5d} cw={cw:4d}:       "
                      f"{m * 1e3:8.2f} ms/pass   culled_frac={frac:.3f}")
            except Exception as e:  # VMEM blowups etc -- record and move on
                print(f"  culled br={br:5d} cw={cw:4d}:       FAIL "
                      f"{type(e).__name__}: {str(e)[:120]}")


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    kinds = ("million", "zipf") if which == "both" else (which,)
    for k in kinds:
        bench_kind(k)


if __name__ == "__main__":
    main()
