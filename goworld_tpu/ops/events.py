"""Device-side extraction of enter/leave event pairs from packed diff words.

A batched AOI tick produces *sets* of events as packed bitmasks; the host
needs (observer, observed) index pairs to replay the entity callbacks
(onEnterAOI/onLeaveAOI -- reference /root/reference/engine/entity/Entity.go:227-233).
Shipping full [C, W] masks D2H every tick is wasteful at scale, so events are
compacted on device into fixed-capacity index lists (static shapes under jit).

``extract_pairs(words, capacity, max_events)`` returns:
  * pairs [max_events, 2] int32, (-1, -1)-filled past the real events,
    sorted lexicographically by (observer, observed) -- the deterministic
    callback replay order;
  * count: the true number of set bits (may exceed max_events; the caller
    detects overflow with count > max_events and falls back to
    :func:`pairs_overflow_host` on the ALREADY-fetched host words for that
    rare tick -- counted per bucket as ``decode_overflow``, never repaying
    the full-mask unpack).

``extract_triples(chg, new, capacity, max_triples)`` is the device-resident
decode the production buckets run (docs/perf.md emit paths): it compacts a
classified diff into fixed-capacity (observer, observed, kind) int32
triples ON DEVICE, so harvest fetches the compact triple buffer plus one
count scalar instead of word grids that still need host bit expansion.

The paged layout (:mod:`goworld_tpu.ops.aoi_pages`, docs/perf.md paged
storage) carries the same ``(gidx, chg_word, new_word)`` entries this
module's word expanders consume, just page-packed: a paged harvest may
hand the expanders an UNSORTED merge of paged and spilled-bin words --
legal because every expander here sorts on the unique per-tick key, so
the published order is identical regardless of arrival order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .aoi_predicate import WORD_BITS, words_per_row


def popcount_total(words) -> jnp.ndarray:
    """Total set bits in a packed words array (any shape)."""
    return jnp.sum(jax.lax.population_count(words), dtype=jnp.int32)


def unpack_words(words, capacity: int):
    """uint32 [N, W] -> bool [N, capacity] (planar layout)."""
    n, w = words.shape
    assert w == words_per_row(capacity)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)[None, :, None]
    planes = (words[:, None, :] >> shifts) & jnp.uint32(1)
    return planes.reshape(n, capacity).astype(bool)


def extract_pairs(words, capacity: int, max_events: int):
    """Packed diff words -> ((observer, observed) pairs, true count)."""
    m = unpack_words(words, capacity)
    count = popcount_total(words)
    i, j = jnp.nonzero(m, size=max_events, fill_value=-1)
    # jnp.nonzero on a row-major matrix is already (i, j)-lexicographic.
    return jnp.stack([i, j], axis=1).astype(jnp.int32), count


def extract_chunks(words, max_chunks: int, k: int, aux=None,
                   lanes: int = 128):
    """Chunk-compacted extraction over 128-lane windows (the fast path).

    Views the packed words as rows of 128 lanes (lane-aligned, so the
    reshape is free when W % 128 == 0) and compacts each dirty chunk's
    nonzero words into ``k`` slots via masked reductions -- ``pos ==
    slot`` selects at most one lane per chunk-row, so a sum over lanes IS
    the selection.  No per-element gathers anywhere: the only data
    movement is one contiguous row gather of the dirty chunks and one
    full-array popcount pass.  This is what makes it ~4x cheaper than the
    word-level segmented top_k at 8x8192 (whose candidate-window element
    gathers ran at ~40 M elems/s).

    Args: ``words`` any shape whose total size ``lanes`` divides;
    ``max_chunks`` static cap on dirty chunks; ``k`` static slots per
    chunk; ``aux`` optional same-shape array (e.g. NEW interest words)
    compacted at the same slots; ``lanes`` chunk width (<= 256 keeps the
    lane offset in one byte on the wire).

    Returns ``(vals [max_chunks, k] u32, aux_vals | None, lane [max_chunks,
    k] i32 (-1 fill), csel [max_chunks] i32 ascending dirty-chunk indices,
    ccnt [max_chunks] i32 true per-chunk word counts, n_dirty i32,
    max_ccnt i32)``.  Global word index of slot (c, s) = csel[c] * 128 +
    lane[c, s].  ``n_dirty > max_chunks`` or ``max_ccnt > k`` means the
    stream is incomplete (fall back); both scalars are exact regardless.
    """
    flat = words.reshape(-1, lanes)
    nc = flat.shape[0]
    nz = flat != 0
    ccnt_full = jnp.sum(nz.astype(jnp.int32), axis=1)
    dirty = ccnt_full > 0
    n_dirty = jnp.sum(dirty.astype(jnp.int32))
    max_ccnt = jnp.max(ccnt_full)
    mc = min(max_chunks, nc)
    score = jnp.where(dirty, nc - jnp.arange(nc, dtype=jnp.int32), 0)
    sv, cidx = jax.lax.top_k(score, mc)  # descending score = ascending chunks
    valid_c = sv > 0
    csel = jnp.where(valid_c, cidx, 0)
    chunks = jnp.take(flat, csel, axis=0)
    chunks = jnp.where(valid_c[:, None], chunks, jnp.uint32(0))
    if aux is not None:
        achunks = jnp.take(aux.reshape(-1, lanes), csel, axis=0)
    nz2 = chunks != 0
    pos = jnp.cumsum(nz2.astype(jnp.int32), axis=1) - 1
    lane_ids = jnp.arange(lanes, dtype=jnp.int32)[None, :]
    kk = min(k, lanes)
    vals_s, aux_s, lane_s = [], [], []
    for s in range(kk):
        m = nz2 & (pos == s)
        vals_s.append(jnp.sum(jnp.where(m, chunks, jnp.uint32(0)), axis=1))
        lane_s.append(jnp.sum(jnp.where(m, lane_ids, 0), axis=1))
        if aux is not None:
            aux_s.append(jnp.sum(
                jnp.where(m, achunks, jnp.uint32(0)), axis=1))
    vals = jnp.stack(vals_s, axis=1)
    lane = jnp.stack(lane_s, axis=1)
    aux_vals = jnp.stack(aux_s, axis=1) if aux is not None else None
    ccnt = jnp.take(ccnt_full, csel) * valid_c.astype(jnp.int32)
    slot = jnp.arange(kk, dtype=jnp.int32)[None, :]
    lane = jnp.where(slot < ccnt[:, None], lane, -1)
    if mc < max_chunks:
        pad = max_chunks - mc
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
        lane = jnp.pad(lane, ((0, pad), (0, 0)), constant_values=-1)
        if aux_vals is not None:
            aux_vals = jnp.pad(aux_vals, ((0, pad), (0, 0)))
        csel = jnp.pad(csel, (0, pad))
        ccnt = jnp.pad(ccnt, (0, pad))
    return vals, aux_vals, lane, csel, ccnt, n_dirty, max_ccnt


_ROW_SLOTS = 2  # word slots shipped inline per row; the tail rides exc


def encode_row_stream(vals, new_vals, widx, rsel, rcnt, *, w,
                      max_gaps: int = 2048, max_exc: int = 16384,
                      exc_select: str = "auto"):
    """Compress a row-extracted change stream for D2H (~1 B/row + 2-3 B per
    single-bit word).

    ``exc_select``: exception-triple selection strategy -- "flat" (one
    top_k over the [mr * k] grid), "hier" (chunk-level then element-level
    top_k; identical output, ~2x cheaper when the grid is millions of
    entries wide but the exc population is sparse), or "auto" (hier when
    mr * k > 2^20).

    Per row ONE byte: row-index delta in bits 0-5 (63 = escaped, absolute
    index in the ``esc_rows`` side list) and ``min(rcnt, 2) - 1`` in bit 6.
    Two inline word slots per row: ``bitpos`` u8 (bit position 0-4, bit 5 =
    the bit's NEW state i.e. enter; 255 = multi-bit word, shipped via exc)
    and ``woff`` (word index within the row, u8 when W <= 256 else u16).
    Everything else -- words beyond slot 2 and multi-bit words -- ships as
    absolute exception triples ``(gidx i32, chg u32, new u32)``, ascending.
    The decoder needs no positional matching for exc entries, so the slices
    shipped can be cut independently of the device caps.

    Returns ``(rowb u8 [mr], bitpos u8 [mr, 2], woff [mr, 2], base_row,
    n_esc, esc_rows i32 [max_gaps], exc_gidx i32 [max_exc],
    exc_chg u32 [max_exc], exc_new u32 [max_exc], exc_n)``.
    ``n_esc > max_gaps`` or ``exc_n > max_exc`` means the stream is
    incomplete for this tick (fall back to the kept device rows).
    Decode with :func:`decode_row_stream`.
    """
    mr, k = vals.shape
    slot = jnp.arange(k, dtype=jnp.int32)[None, :]
    valid = slot < jnp.minimum(rcnt, k)[:, None]
    has_row = rcnt > 0
    prev_r = jnp.concatenate([rsel[:1], rsel[:-1]])
    rd = rsel - prev_r
    esc = has_row & (rd >= 63)
    db = jnp.where(esc, 63, rd).astype(jnp.uint8)
    nv2 = (jnp.minimum(jnp.maximum(rcnt, 1), _ROW_SLOTS) - 1).astype(jnp.uint8)
    rowb = jnp.where(has_row, db | (nv2 << 6), 0).astype(jnp.uint8)
    n_esc = jnp.sum(esc.astype(jnp.int32))
    score_e = jnp.where(esc, mr - jnp.arange(mr, dtype=jnp.int32), 0)
    sv_e, pos_e = jax.lax.top_k(score_e, min(max_gaps, mr))
    esc_rows = jnp.where(sv_e > 0, rsel[jnp.maximum(pos_e, 0)], -1)
    if esc_rows.shape[0] < max_gaps:
        esc_rows = jnp.pad(esc_rows, (0, max_gaps - esc_rows.shape[0]),
                           constant_values=-1)

    pc = jax.lax.population_count(vals)
    ctz = jax.lax.population_count(vals ^ (vals - 1)) - 1
    enter = ((new_vals >> jnp.maximum(ctz, 0).astype(jnp.uint32)) & 1
             ).astype(jnp.int32)
    single = valid & (pc == 1)
    bp2 = jnp.where(single, ctz | (enter << 5), 255)[:, :_ROW_SLOTS]
    bitpos = bp2.astype(jnp.uint8)
    wdt = jnp.uint8 if w <= 256 else jnp.uint16
    woff = jnp.where(valid, widx, 0)[:, :_ROW_SLOTS].astype(wdt)
    base_row = rsel[0]

    exc_mask2 = valid & ((slot >= _ROW_SLOTS) | (pc > 1))  # [mr, k]
    exc_n = jnp.sum(exc_mask2.astype(jnp.int32))
    n = mr * k
    me = min(max_exc, n)
    if exc_select == "auto":
        exc_select = "hier" if n > (1 << 20) else "flat"
    if exc_select == "hier":
        # Hierarchical selection for giant grids: a flat top_k over the
        # [mr * k] score vector costs ~30 ms at 651k x 22 (zipf100k fit)
        # while the true exc population is ~34k.  Select exc-bearing
        # CHUNKS first (each contributes >= 1 entry, so chunks-with-exc
        # <= exc_n <= me and nothing in the first `me` entries can live
        # past the first `me` such chunks -- entries are chunk-major
        # ascending, so even the overflow prefix matches the flat path
        # bit for bit), then element-select inside the gathered rows.
        mrow = min(me, mr)
        row_has = jnp.any(exc_mask2, axis=1)
        rscore = jnp.where(row_has, mr - jnp.arange(mr, dtype=jnp.int32), 0)
        rsv, rpos = jax.lax.top_k(rscore, mrow)
        rsel2 = jnp.maximum(rpos, 0)
        g_vals = jnp.take(vals, rsel2, axis=0)
        g_new = jnp.take(new_vals, rsel2, axis=0)
        g_widx = jnp.take(widx, rsel2, axis=0)
        g_rsel = jnp.take(rsel, rsel2)
        g_mask = jnp.take(exc_mask2, rsel2, axis=0) & (rsv > 0)[:, None]
        n2 = mrow * k
        score = jnp.where(g_mask.reshape(-1),
                          n2 - jnp.arange(n2, dtype=jnp.int32), 0)
        sv, spos = jax.lax.top_k(score, min(me, n2))
        sel = jnp.maximum(spos, 0)
        gidx_grid = (g_rsel[:, None] * w
                     + jnp.maximum(g_widx, 0)).reshape(-1)
        exc_gidx = jnp.where(sv > 0, gidx_grid[sel], -1)
        exc_chg = jnp.where(sv > 0, g_vals.reshape(-1)[sel], 0)
        exc_new2 = jnp.where(sv > 0, g_new.reshape(-1)[sel], 0)
    else:
        exc_mask = exc_mask2.reshape(-1)
        score = jnp.where(exc_mask, n - jnp.arange(n, dtype=jnp.int32), 0)
        sv, spos = jax.lax.top_k(score, me)
        sel = jnp.maximum(spos, 0)
        gidx_grid = (rsel[:, None] * w + jnp.maximum(widx, 0)).reshape(-1)
        exc_gidx = jnp.where(sv > 0, gidx_grid[sel], -1)
        exc_chg = jnp.where(sv > 0, vals.reshape(-1)[sel], 0)
        exc_new2 = jnp.where(sv > 0, new_vals.reshape(-1)[sel], 0)
    if exc_gidx.shape[0] < max_exc:
        pad = max_exc - exc_gidx.shape[0]
        exc_gidx = jnp.pad(exc_gidx, (0, pad), constant_values=-1)
        exc_chg = jnp.pad(exc_chg, (0, pad))
        exc_new2 = jnp.pad(exc_new2, (0, pad))
    return (rowb, bitpos, woff, base_row, n_esc, esc_rows,
            exc_gidx, exc_chg, exc_new2, exc_n)


def decode_row_stream(rowb, bitpos, woff, base_row, n_dirty, w,  # gwlint: allow[host-sync] -- host-side decoder: consumes the already-drained stream
                      esc_rows, exc_gidx, exc_chg, exc_new):
    """Host-side (numpy) inverse of :func:`encode_row_stream`.

    Harvest-phase only (docs/perf.md split flush): the inputs are the
    already-drained host copies of the encoded stream -- callers run this
    from ``harvest()`` after the blocking fetch, never from ``dispatch()``
    (the flush-phase gwlint rule enforces the reachability).

    Returns ``(chg_vals u32 [K], ent_vals u32 [K], gidx i64 [K])`` --
    ent_vals are the enter-bit subsets (``chg & new``), directly consumable
    by :func:`expand_classified_host` (which sorts, so main-stream/exc
    concatenation order is fine).  The caller must pre-check its overflow
    contracts (n_dirty/row-count caps, n_esc vs the esc slice, exc_n vs the
    exc slice) before decoding.
    """
    import numpy as np

    nd = int(n_dirty)
    outs_c, outs_e, outs_g = [], [], []
    if nd > 0:
        rowb = np.asarray(rowb)[:nd]
        bitpos = np.asarray(bitpos)[:nd]
        woff = np.asarray(woff)[:nd]
        d = (rowb & 63).astype(np.int64)
        d[0] = 0
        esc_at = np.nonzero((rowb & 63) == 63)[0]
        rows = int(base_row) + np.cumsum(d)
        if len(esc_at):
            er = np.asarray(esc_rows)[:len(esc_at)].astype(np.int64)
            # reset the running index at each escape: add the correction of
            # the MOST RECENT escape at or before each row
            corr = er - rows[esc_at]
            which = np.searchsorted(esc_at, np.arange(nd), side="right") - 1
            adj = np.where(which >= 0, corr[np.maximum(which, 0)], 0)
            rows = rows + adj
        nv2 = ((rowb >> 6) & 1).astype(np.int32) + 1
        valid = np.arange(_ROW_SLOTS, dtype=np.int32)[None, :] < nv2[:, None]
        single = bitpos < 64
        m = valid & single
        bp = bitpos[m]
        outs_c.append(np.uint32(1) << (bp & 31).astype(np.uint32))
        outs_e.append(np.where(((bp >> 5) & 1) == 1, outs_c[-1], np.uint32(0)))
        outs_g.append((rows[:, None] * w + woff.astype(np.int64))[m])
    keep = np.asarray(exc_gidx) >= 0
    if keep.any():
        ec = np.asarray(exc_chg)[keep]
        en = np.asarray(exc_new)[keep]
        outs_c.append(ec)
        outs_e.append(ec & en)
        outs_g.append(np.asarray(exc_gidx)[keep].astype(np.int64))
    if not outs_c:
        z = np.empty(0, np.uint32)
        return z, z, np.empty(0, np.int64)
    return (np.concatenate(outs_c), np.concatenate(outs_e),
            np.concatenate(outs_g))


def _expand_bits(vals, flat_idx, capacity, w):
    """(word values, flat word indices) -> unsorted (s, i, j, widx) arrays.

    np.unpackbits over the little-endian byte view beats the broadcast-shift
    formulation ~3x at 85k words/tick."""
    import numpy as np

    v8 = np.ascontiguousarray(vals.astype("<u4")).view(np.uint8)
    bits = np.unpackbits(v8.reshape(-1, 4), axis=1, bitorder="little")
    widx, k = np.nonzero(bits)
    fi = flat_idx[widx]
    s = fi // (capacity * w)
    rem = fi % (capacity * w)
    i = rem // w
    word = rem % w
    j = k * w + word  # planar layout: bit k of word -> column k*W + word
    return s, i, j, widx, k


def _sorted_pairs(s, i, j, capacity):
    import numpy as np

    out = np.stack([s, i, j], axis=1).astype(np.int32)
    # single int64 sort key (int32 would wrap at capacity >= ~46k)
    key = (s.astype(np.int64) * capacity + i) * capacity + j
    return out[np.argsort(key)]


def expand_words_host(vals, flat_idx, capacity: int, n_spaces: int):  # gwlint: allow[host-sync] -- host-side expansion of the drained stream
    """Host-side expansion of extracted words into per-space sorted pairs.

    Returns int32 array [K, 3] of (space, observer, observed), sorted
    lexicographically -- the deterministic callback replay order.
    """
    import numpy as np

    w = words_per_row(capacity)
    vals = np.asarray(vals)
    flat_idx = np.asarray(flat_idx)
    keep = flat_idx >= 0
    vals, flat_idx = vals[keep], flat_idx[keep]
    if vals.size == 0:
        return np.empty((0, 3), np.int32)
    s, i, j, _, _ = _expand_bits(vals, flat_idx, capacity, w)
    return _sorted_pairs(s, i, j, capacity)


def expand_classified_host(chg_vals, ent_vals, flat_idx, capacity: int,  # gwlint: allow[host-sync,flush-phase] -- host-side expansion of the drained stream: harvest feeds it decoded values after the fetch
                           n_spaces: int):
    """One-pass expansion of a classified change stream.

    Harvest-phase only, like :func:`decode_row_stream`: the per-bucket
    ``harvest()`` feeds it decoded host values after the fetch; nothing on
    the dispatch side may reach it.

    ``chg_vals`` are the changed words, ``ent_vals`` their enter-bit subsets
    (``chg & new``, from :func:`decode_word_stream` with_enter).  Returns
    (enter_pairs [K, 3], leave_pairs [L, 3]) int32, each sorted
    lexicographically by (space, observer, observed).
    """
    import numpy as np

    w = words_per_row(capacity)
    chg_vals = np.asarray(chg_vals)
    ent_vals = np.asarray(ent_vals)
    flat_idx = np.asarray(flat_idx)
    if chg_vals.size == 0:
        e = np.empty((0, 3), np.int32)
        return e, e
    s, i, j, widx, k = _expand_bits(chg_vals, flat_idx, capacity, w)
    is_ent = ((ent_vals[widx] >> k.astype(np.uint32)) & 1).astype(bool)
    return (_sorted_pairs(s[is_ent], i[is_ent], j[is_ent], capacity),
            _sorted_pairs(s[~is_ent], i[~is_ent], j[~is_ent], capacity))


def extract_triples(chg, new, capacity: int, max_triples: int):
    """Classified diff words -> compact (observer, observed, kind) triples,
    entirely on device (docs/perf.md emit paths).

    Two-pass compaction sized by an exact popcount (NOT a silent cap):
    pass 1 compacts the nonzero WORDS of the flat change grid (there are at
    most ``count`` of them, so the same ``max_triples`` budget covers both
    passes on every non-overflow tick); pass 2 finds, for each output row,
    the surviving word and bit holding the set BIT of that rank.  When
    ``count > max_triples`` the triple buffer is incomplete and the caller
    must fall back (a counted, per-tick event -- bucket ``decode_overflow``
    stat), which is why the dropped pass-1 words never matter.

    ``chg``/``new`` are uint32 planar words of any leading shape whose flat
    word order defines the observer index: ``obs = flat_word // W`` (for
    the bucket grids [s_n, C, W] that is the global observer row
    ``s * C + i``).  ``kind`` is 1 for enter (the bit's NEW interest state),
    0 for leave.

    Returns ``(tri [max_triples, 3] int32, count i32)``.  ``tri`` rows are
    (-1, -1, -1)-filled past the real triples and UNSORTED (pass order is
    (word, bit), not (observer, observed)); the emit layer
    (:mod:`goworld_tpu.ops.aoi_emit`) owns the deterministic callback-order
    sort.
    """
    w = words_per_row(capacity)
    flat_c = chg.reshape(-1)
    flat_n = new.reshape(-1)
    count = popcount_total(chg)
    (widx,) = jnp.nonzero(flat_c != jnp.uint32(0), size=max_triples,
                          fill_value=-1)
    wsel = jnp.maximum(widx, 0)
    wvals = jnp.where(widx >= 0, flat_c[wsel], jnp.uint32(0))
    nvals = jnp.where(widx >= 0, flat_n[wsel], jnp.uint32(0))
    # pass 2: row r of the output is the bit whose rank among all set bits
    # in (word, bit) order is r: its word is found by a binary search of
    # the words' running popcount, its bit as the one with rank
    # r - (bits in earlier words) inside that word.  Gathers over
    # [max_triples] and [max_triples, 32] only.  (A nonzero over the
    # [max_triples, 32] bit matrix gives the same rows, but its prefix sum
    # over 32x the elements cost the TPU compiler ~20 s per cap size,
    # stalling a served game's tick loop.)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)[None, :]
    pc = jax.lax.population_count(wvals).astype(jnp.int32)
    end = jnp.cumsum(pc)
    r = jnp.arange(max_triples, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(end, r, side="right"),
                       max_triples - 1)
    wv = wvals[slot]
    nth = r - (end[slot] - pc[slot])
    below = jax.lax.population_count(
        wv[:, None] & ((jnp.uint32(1) << shifts) - jnp.uint32(1)))
    hit = (((wv[:, None] >> shifts) & jnp.uint32(1)) != 0) \
        & (below.astype(jnp.int32) == nth[:, None])
    k = jnp.argmax(hit, axis=1).astype(jnp.uint32)
    g = widx[slot]
    obs = g // w
    j = k.astype(jnp.int32) * w + g % w
    kind = ((nvals[slot] >> k) & jnp.uint32(1)).astype(jnp.int32)
    valid = r < end[-1]
    tri = jnp.stack([jnp.where(valid, obs, -1),
                     jnp.where(valid, j, -1),
                     jnp.where(valid, kind, -1)], axis=1).astype(jnp.int32)
    return tri, count


def triples_to_words(tri, capacity: int):  # gwlint: allow[host-sync] -- pure numpy on already-fetched triples
    """Reconstruct the classified word stream from already-fetched triples.

    The bridge back to the classic host decode: the triples-mode mirror
    XOR and the ``aoi.emit`` fault fallback both need (chg_vals, ent_vals,
    gidx) exactly as :func:`decode_row_stream` would have produced them.
    Inverse of :func:`extract_triples` up to word grouping; bit-exact by
    construction (each triple is one unique (word, bit)).

    ``tri`` must hold only VALID rows ([n, 3] int32).  Returns
    ``(chg_vals u32 [K], ent_vals u32 [K], gidx i64 [K])`` with ``gidx``
    ascending.
    """
    import numpy as np

    w = words_per_row(capacity)
    if len(tri) == 0:
        z = np.empty(0, np.uint32)
        return z, z, np.empty(0, np.int64)
    obs = tri[:, 0].astype(np.int64)
    j = tri[:, 1].astype(np.int64)
    ent = tri[:, 2] == 1
    g = obs * w + j % w
    bit = (j // w).astype(np.uint32)
    gidx = np.unique(g)
    grp = np.searchsorted(gidx, g)
    chg_vals = np.zeros(len(gidx), np.uint32)
    ent_vals = np.zeros(len(gidx), np.uint32)
    np.bitwise_or.at(chg_vals, grp, np.uint32(1) << bit)
    np.bitwise_or.at(ent_vals, grp[ent], np.uint32(1) << bit[ent])
    return chg_vals, ent_vals, gidx


def pairs_overflow_host(words, capacity: int):  # gwlint: allow[host-sync] -- overflow fallback consumes the already-fetched words
    """:func:`extract_pairs` overflow fallback on the ALREADY-fetched words.

    When ``count > max_events`` the device pair list is incomplete; the old
    fallback re-unpacked the full [capacity, capacity] mask on host (O(C^2)
    bools for what is usually a handful of extra events).  This expands
    only the NONZERO words of the host copy instead -- O(count) work -- so
    an overflow tick reuses the words it already paid to fetch.

    Returns (observer, observed) int32 [K, 2], sorted lexicographically --
    identical to the non-overflow ``extract_pairs`` ordering.
    """
    import numpy as np

    w = words_per_row(capacity)
    flat = np.ascontiguousarray(words, np.uint32).reshape(-1)
    gidx = np.nonzero(flat)[0]
    if len(gidx) == 0:
        return np.empty((0, 2), np.int32)
    # one implicit "space" of `capacity` rows: _expand_bits yields s == 0
    _, i, j, _, _ = _expand_bits(flat[gidx], gidx, capacity, w)
    out = np.stack([i, j], axis=1).astype(np.int32)
    key = i.astype(np.int64) * capacity + j
    return out[np.argsort(key)]
