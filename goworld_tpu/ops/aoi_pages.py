"""Paged, ragged storage for the AOI change stream (ROADMAP #2).

The fixed-cap layouts (``extract_triples``'s ``max_triples``, the
mesh/rowshard chunk + escape caps) all share one failure class: a single
dense hotspot forces a *global* cap, and the tick either overflows
(counted ``decode_overflow`` + full-diff recovery) or the cap grows and
recompiles.  This module adopts the page-granular buffer discipline of
Ragged Paged Attention (PAPERS.md): the flat ``[S, C, W]`` change grid is
split into fixed *bins* (``BIN_ROWS`` entity rows each), every bin gets a
page *table* sized by its own occupancy, and pages come from one shared
device-resident free list -- dense bins borrow pages sparse bins never
needed, so skewed distributions (clustered-crowd) stop hitting any
per-tick cap at all.

Layout.  A page holds ``PAGE_WORDS`` *word entries* ``(gidx, chg_word,
new_word)`` -- exactly the stream :meth:`_publish` and the mirror XOR
consume, so decode is a validity filter, not a format conversion, and
bit-exactness is free (both emit paths sort; XOR over unique words is
order-independent).  The allocate/compact pass is one jitted scan:

1. count nonzero change words per bin; ``need = ceil(cnt / PAGE_WORDS)``
2. feasibility: bins sorted ascending by need are granted pages while
   the running total fits the pool (smallest-first maximizes the number
   of bins served device-side); the rest *spill*
3. granted bins receive consecutive page ranks; each fit word is
   scattered to ``rank * PAGE_WORDS + slot`` in the pool buffers
4. logical page ids are consumed from the head of the free list and the
   list is rolled -- the returned page table (``free[:n_used]``) is what
   the host fetches, validates, and the ``aoi.pages`` poison seam
   corrupts

Spilled bins are *counted, graceful* degradation, not data loss: the
harvest path re-reads the offending bins' word slices straight from the
kept change grid (``aoi.page_spills`` counter), merges them with the
paged stream, and republishes the same tick bit-exact -- the same
contract as the ``aoi.emit`` fallback chain (docs/robustness.md).

Everything here is pure (grids in, pool + table + scalars out); the
buckets own donation, free-list persistence, and the fault seam.
"""

from __future__ import annotations

import numpy as np

# Word entries per page.  Small enough that a half-empty page wastes
# little pool, large enough that page-table overhead stays negligible.
PAGE_WORDS = 64

# Entity rows per allocation bin: each bin covers BIN_ROWS consecutive
# rows of the [S*C, W] word grid, so a bin's page table is sized by the
# occupancy of a small neighborhood of entities (the grid-binned kernel
# in ops/aoi_grid.py makes neighborhoods spatially coherent).
BIN_ROWS = 8

# Static width of the returned spilled-bin vector.  More simultaneous
# spills than this falls back to the full-grid recovery (still counted).
MAX_SPILL = 64


def bin_words_for(words_per_row: int) -> int:
    """Flat words per allocation bin for a grid with W words per row."""
    return max(1, words_per_row) * BIN_ROWS


def pool_floor(n_words: int) -> int:
    """Starting pool size (pages): 1/8 of full coverage, at least 64.
    The decay controller grows toward :func:`pool_ceiling` on spill."""
    return max(64, n_words // PAGE_WORDS // 8)


def pool_ceiling(n_words: int, bin_words: int) -> int:
    """Pages that can never spill: full word coverage plus one page of
    ragged padding per bin (each bin wastes < 1 page to rounding)."""
    n_bins = -(-n_words // bin_words)
    return -(-n_words // PAGE_WORDS) + n_bins


def allocate_pages(chg, new, free, page_words: int, bin_words: int,
                   max_spill: int):
    """Traceable allocate/compact pass (jit-compiled by the bucket's
    fused step; :func:`paged_extract` wraps it standalone for tests).

    ``chg`` / ``new`` are uint32 grids of any shape (flattened here);
    ``free`` is the device-resident free list ``[n_pages] int32``.

    Returns ``(pool_g, pool_c, pool_n, page_tab, free_next, spill_bins,
    scalars)`` where the pools are ``[n_pages, page_words]`` rank-indexed
    staging buffers (``pool_g`` is -1 off the valid prefix), ``page_tab``
    is ``free[:n_used]`` padded with -1, ``spill_bins`` lists spilled bin
    ids ascending (-1 padded, width ``max_spill``) and ``scalars`` is
    ``[n_used, n_spill, nz_fit_words, nz_total_words] int32``.
    """
    import jax.numpy as jnp

    n_pages = free.shape[0]
    flat_c = chg.reshape(-1)
    flat_n = new.reshape(-1)
    nw = flat_c.shape[0]
    n_bins = -(-nw // bin_words)
    nwp = n_bins * bin_words
    if nwp != nw:
        flat_c = jnp.pad(flat_c, (0, nwp - nw))
        flat_n = jnp.pad(flat_n, (0, nwp - nw))

    nz = flat_c != 0
    cnt = nz.reshape(n_bins, bin_words).sum(axis=1).astype(jnp.int32)
    need = (cnt + (page_words - 1)) // page_words

    # feasibility: grant ascending by need while the pool lasts
    order = jnp.argsort(need, stable=True)
    fit_sorted = jnp.cumsum(need[order]) <= n_pages
    fit = jnp.zeros((n_bins,), bool).at[order].set(fit_sorted)
    fit = fit & (need > 0)
    spill = (need > 0) & ~fit
    n_spill = spill.sum().astype(jnp.int32)
    bin_ids = jnp.arange(n_bins, dtype=jnp.int32)
    spill_sorted = jnp.sort(jnp.where(spill, bin_ids, n_bins))[:max_spill]
    spill_bins = jnp.where(spill_sorted < n_bins, spill_sorted,
                           -1).astype(jnp.int32)

    # page-rank allocation: granted bins take consecutive rank ranges
    need_fit = jnp.where(fit, need, 0)
    rank0 = jnp.cumsum(need_fit) - need_fit          # [n_bins] excl. cumsum
    n_used = need_fit.sum().astype(jnp.int32)
    cnt_fit = jnp.where(fit, cnt, 0)
    wrank0 = jnp.cumsum(cnt_fit) - cnt_fit           # word rank at bin start
    nz_fit = nz & jnp.repeat(fit, bin_words)
    gcum = jnp.cumsum(nz_fit.astype(jnp.int32)) - 1  # global fit-word rank
    word_bin = jnp.arange(nwp, dtype=jnp.int32) // bin_words
    within = gcum - wrank0[word_bin]                 # rank inside own bin
    dst = ((rank0[word_bin] + within // page_words) * page_words
           + within % page_words)
    oob = n_pages * page_words
    dst = jnp.where(nz_fit, dst, oob)

    pool_g = jnp.full((n_pages * page_words,), -1, jnp.int32).at[dst].set(
        jnp.arange(nwp, dtype=jnp.int32), mode="drop")
    pool_c = jnp.zeros((n_pages * page_words,), jnp.uint32).at[dst].set(
        flat_c, mode="drop")
    pool_n = jnp.zeros((n_pages * page_words,), jnp.uint32).at[dst].set(
        flat_n, mode="drop")

    # logical page ids: consume the free-list head, roll the remainder
    page_tab = jnp.where(jnp.arange(n_pages, dtype=jnp.int32) < n_used,
                         free, -1).astype(jnp.int32)
    free_next = jnp.roll(free, -n_used)

    scalars = jnp.stack([n_used, n_spill,
                         cnt_fit.sum().astype(jnp.int32),
                         cnt.sum().astype(jnp.int32)])
    return (pool_g.reshape(n_pages, page_words),
            pool_c.reshape(n_pages, page_words),
            pool_n.reshape(n_pages, page_words),
            page_tab, free_next, spill_bins, scalars)


_extract_impl = None


def paged_extract(chg, new, free, page_words: int = PAGE_WORDS,
                  bin_words: int | None = None,
                  max_spill: int = MAX_SPILL):
    """Standalone jitted :func:`allocate_pages` (unit tests / oracles);
    the buckets fuse the same pass into their step instead."""
    global _extract_impl
    import jax

    if _extract_impl is None:
        import functools

        @functools.partial(
            jax.jit,
            static_argnames=("page_words", "bin_words", "max_spill"))
        def aoi_alloc_pages(chg, new, free, page_words, bin_words, max_spill):
            return allocate_pages(chg, new, free, page_words, bin_words,
                                  max_spill)

        _extract_impl = aoi_alloc_pages
    if bin_words is None:
        bin_words = bin_words_for(chg.shape[-1])
    return _extract_impl(chg, new, free, page_words=page_words,
                         bin_words=bin_words, max_spill=max_spill)


def allocate_pages_host(chg, new, free, page_words: int,  # gwlint: allow[host-sync] -- NumPy oracle
                        bin_words: int, max_spill: int):
    """NumPy oracle for :func:`allocate_pages` -- bit-identical outputs
    (same stable ascending-need grant order, same rank placement), used
    by the allocator parity tests and the host fallback paths."""
    free = np.asarray(free, np.int32)
    n_pages = free.shape[0]
    flat_c = np.asarray(chg, np.uint32).reshape(-1)
    flat_n = np.asarray(new, np.uint32).reshape(-1)
    nw = flat_c.shape[0]
    n_bins = -(-nw // bin_words)
    nwp = n_bins * bin_words
    if nwp != nw:
        flat_c = np.pad(flat_c, (0, nwp - nw))
        flat_n = np.pad(flat_n, (0, nwp - nw))

    nz = flat_c != 0
    cnt = nz.reshape(n_bins, bin_words).sum(axis=1).astype(np.int32)
    need = (cnt + (page_words - 1)) // page_words

    order = np.argsort(need, kind="stable")
    fit_sorted = np.cumsum(need[order]) <= n_pages
    fit = np.zeros((n_bins,), bool)
    fit[order] = fit_sorted
    fit &= need > 0
    spill = (need > 0) & ~fit
    n_spill = np.int32(spill.sum())
    bin_ids = np.arange(n_bins, dtype=np.int32)
    spill_sorted = np.sort(np.where(spill, bin_ids, n_bins))[:max_spill]
    spill_bins = np.where(spill_sorted < n_bins, spill_sorted,
                          -1).astype(np.int32)

    need_fit = np.where(fit, need, 0)
    rank0 = np.cumsum(need_fit) - need_fit
    n_used = np.int32(need_fit.sum())
    cnt_fit = np.where(fit, cnt, 0)
    wrank0 = np.cumsum(cnt_fit) - cnt_fit
    nz_fit = nz & np.repeat(fit, bin_words)
    gcum = np.cumsum(nz_fit.astype(np.int32)) - 1
    word_bin = np.arange(nwp, dtype=np.int32) // bin_words
    within = gcum - wrank0[word_bin]

    pool_g = np.full((n_pages * page_words,), -1, np.int32)
    pool_c = np.zeros((n_pages * page_words,), np.uint32)
    pool_n = np.zeros((n_pages * page_words,), np.uint32)
    sel = np.nonzero(nz_fit)[0]
    dst = ((rank0[word_bin[sel]] + within[sel] // page_words) * page_words
           + within[sel] % page_words)
    keep = dst < n_pages * page_words
    pool_g[dst[keep]] = sel[keep].astype(np.int32)
    pool_c[dst[keep]] = flat_c[sel[keep]]
    pool_n[dst[keep]] = flat_n[sel[keep]]

    page_tab = np.where(np.arange(n_pages, dtype=np.int32) < n_used,
                        free, -1).astype(np.int32)
    free_next = np.roll(free, -int(n_used))
    scalars = np.array([n_used, n_spill, cnt_fit.sum(), cnt.sum()],
                       np.int32)
    return (pool_g.reshape(n_pages, page_words),
            pool_c.reshape(n_pages, page_words),
            pool_n.reshape(n_pages, page_words),
            page_tab, free_next, spill_bins, scalars)


def decode_pages(pool_g, pool_c, pool_n):  # gwlint: allow[host-sync] -- host decode of fetched pages
    """Host decode of fetched pool rows -> ``(gidx, chg_vals, new_vals)``
    word stream (only valid entries; order is rank order, i.e. ascending
    flat index within each granted bin)."""
    g = np.asarray(pool_g).reshape(-1)
    ok = g >= 0
    return (g[ok],
            np.asarray(pool_c).reshape(-1)[ok],
            np.asarray(pool_n).reshape(-1)[ok])


def spill_stream(chg_flat_h, new_flat_h, spill_bins,  # gwlint: allow[host-sync] -- spill-to-host fallback
                 bin_words: int, n_words: int):
    """Re-read spilled bins' word slices from host copies of the kept
    change/new grids -> ``(gidx, chg_vals, new_vals)``.  ``chg_flat_h`` /
    ``new_flat_h`` are 1-D host arrays (full grid or per-bin slices laid
    flat); ``n_words`` clips the last ragged bin."""
    gs, cs, ns = [], [], []
    for b in np.asarray(spill_bins).reshape(-1):
        if b < 0:
            continue
        lo = int(b) * bin_words
        hi = min(lo + bin_words, n_words)
        csl = np.asarray(chg_flat_h[lo:hi])
        idx = np.nonzero(csl)[0]
        if idx.size == 0:
            continue
        gs.append((idx + lo).astype(np.int64))
        cs.append(csl[idx])
        ns.append(np.asarray(new_flat_h[lo:hi])[idx])
    if not gs:
        z = np.zeros((0,), np.int64)
        return z, z.astype(np.uint32), z.astype(np.uint32)
    return (np.concatenate(gs), np.concatenate(cs), np.concatenate(ns))


def validate_page_table(page_tab, n_used: int, n_pages: int) -> bool:  # gwlint: allow[host-sync] -- validates an already-fetched table
    """Allocator-integrity check on the fetched page table: the first
    ``n_used`` entries must be unique in-range page ids and the rest -1.
    A failure means the free list is corrupt (``aoi.pages`` poison) and
    the bucket must rebuild from host shadows."""
    t = np.asarray(page_tab).reshape(-1)
    if t.shape[0] != n_pages or not 0 <= n_used <= n_pages:
        return False
    used, rest = t[:n_used], t[n_used:]
    if rest.size and not np.all(rest == -1):
        return False
    if used.size and (used.min() < 0 or used.max() >= n_pages
                      or np.unique(used).size != used.size):
        return False
    return True
