"""On-chip attribution of the extraction pipeline (run on the real TPU).

The timings use block_until_ready around a single chained call, which
includes one fixed dispatch+sync cost amortized over ITERS -- treat
per-iter numbers as upper bounds, and for decisions re-measure the
finalists as MARGINALS over two chain lengths (the difference cancels
every fixed cost).  Every measured run ends with a REAL host fetch
(``np.asarray`` of an output).  None of its numbers has been taken
with the current tree on a chip.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from goworld_tpu.ops import words_per_row
from goworld_tpu.ops.events import encode_row_stream, extract_chunks

ITERS = 16


def timed(name, fn, *args):
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = jax.jit(fn)(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    print(f"{name}: {best / ITERS * 1e3:.3f} ms/iter")
    return best / ITERS


def chain(body, seed):
    """Run body ITERS times with a varying data dependency."""
    def run(x):
        def step(c, _):
            r = body(x ^ c)
            return c + r, ()
        c, _ = jax.lax.scan(step, jnp.uint32(seed), None, length=ITERS)
        return c
    return run


def make_chg(s, cap, n_dirty_chunks, rng):
    """Realistic sparse diff: n dirty chunks, 1-3 words each, 1-2 bits."""
    w = words_per_row(cap)
    nwords = s * cap * w
    nc = nwords // 128
    chg = np.zeros(nwords, np.uint32)
    chunks = rng.choice(nc, n_dirty_chunks, replace=False)
    for c in chunks:
        for _ in range(rng.integers(1, 4)):
            lane = rng.integers(0, 128)
            chg[c * 128 + lane] |= np.uint32(1) << rng.integers(0, 32)
    return chg.reshape(s, cap, w), nc


def main():
    rng = np.random.default_rng(0)
    for s, cap, nd in ((8, 8192, 640), (64, 16384, 2816)):
        chg_h, nc = make_chg(s, cap, nd, rng)
        print(f"\n== {s}x{cap} (nc={nc}, dirty={nd}) ==")
        chg = jnp.asarray(chg_h)
        new = chg  # stand-in aux
        mc, kcap = 4096, 8

        flat = chg.reshape(-1, 128)

        # stage 1: popcount/dirty pass
        def s1(x):
            f = x.reshape(-1, 128)
            ccnt = jnp.sum((f != 0).astype(jnp.int32), axis=1)
            return jnp.sum(ccnt.astype(jnp.uint32))
        timed("  ccnt pass", chain(s1, 1), chg)

        # stage 2a: top_k compaction of dirty chunk ids
        def s2a(x):
            f = x.reshape(-1, 128)
            dirty = jnp.any(f != 0, axis=1)
            score = jnp.where(dirty, nc - jnp.arange(nc, dtype=jnp.int32), 0)
            _sv, cidx = jax.lax.top_k(score, mc)
            return jnp.sum(cidx.astype(jnp.uint32))
        timed("  top_k compaction", chain(s2a, 2), chg)

        # stage 2b: scatter compaction of dirty chunk ids
        def s2b(x):
            f = x.reshape(-1, 128)
            dirty = jnp.any(f != 0, axis=1)
            pos = jnp.cumsum(dirty.astype(jnp.int32)) - 1
            idx = jnp.where(dirty, pos, mc)
            csel = jnp.zeros(mc, jnp.int32).at[idx].set(
                jnp.arange(nc, dtype=jnp.int32), mode="drop")
            return jnp.sum(csel.astype(jnp.uint32))
        timed("  scatter compaction", chain(s2b, 3), chg)

        # stage 2c: hierarchical -- top_k over 128-chunk super-rows, then
        # masked-reduction compaction inside selected super-rows
        nsup = nc // 128
        msup = min(nsup, 1024)

        def s2c(x):
            f = x.reshape(-1, 128)
            dirty = jnp.any(f != 0, axis=1)          # [nc]
            sup = dirty.reshape(nsup, 128)
            scnt = jnp.sum(sup.astype(jnp.int32), axis=1)
            score = jnp.where(scnt > 0,
                              nsup - jnp.arange(nsup, dtype=jnp.int32), 0)
            _sv, sidx = jax.lax.top_k(score, msup)
            rows = jnp.take(sup, sidx, axis=0)       # [msup, 128]
            return jnp.sum(rows.astype(jnp.uint32)) + jnp.sum(
                sidx.astype(jnp.uint32))
        timed("  hier super-row topk+gather", chain(s2c, 4), chg)

        # stage 3: row gather of mc chunks
        csel_h = jnp.asarray(
            np.sort(rng.choice(nc, mc, replace=False)).astype(np.int32))

        def s3(x):
            f = x.reshape(-1, 128)
            return jnp.sum(jnp.take(f, csel_h, axis=0).astype(jnp.uint32))
        timed("  chunk row gather", chain(s3, 5), chg)

        # stage 4: the k-slot masked reductions on gathered chunks
        chunks_h = jnp.asarray(rng.integers(
            0, 2**31, (mc, 128), dtype=np.int64).astype(np.uint32))

        def s4(x):
            ch = chunks_h ^ x[: mc * 128].reshape(mc, 128)
            nz2 = ch != 0
            pos = jnp.cumsum(nz2.astype(jnp.int32), axis=1) - 1
            acc = jnp.uint32(0)
            for slot in range(kcap):
                m = nz2 & (pos == slot)
                acc = acc ^ jnp.sum(jnp.where(m, ch, jnp.uint32(0)))
            return acc
        timed("  k-slot masked reductions", chain(s4, 6), chg.reshape(-1))

        # full extract + encode for reference
        def full(x):
            vals, nv, lane, csel, ccnt, nd_, mcc = extract_chunks(
                x, mc, kcap, aux=x, lanes=128)
            enc = encode_row_stream(vals, nv, lane, csel, ccnt, w=128)
            return (jnp.sum(vals) ^ jnp.sum(enc[0].astype(jnp.uint32))
                    ^ nd_.astype(jnp.uint32))
        timed("  FULL extract+encode", chain(full, 7), chg)


if __name__ == "__main__":
    main()
