"""Plain reference of what a shard must deliver: fixed-radius neighbour
sets per tick, their diff, and the position-sync records they imply.

Semantics (GoWorld's XZ-list AOI, the configuration's stated guarantee):
observer i sees entity j, i != j, when |x_j - x_i| <= r_i and
|z_j - z_i| <= r_i, both differences taken in float32 (``dtype``).  The
enter events of tick t are the pairs seen after tick t's moves and not
before, the leave events the reverse.  A moved entity's position reaches
every player that sees it after the tick.

Nothing here imports the program or reads what it computed: positions,
radii and roles come from the generator.  Pairs are coded ``i * n + j``
with i and j entity indices inside one space, and found by a grid of side
max(radius): every candidate of i lies in the 3x3 cells around it.
"""

from __future__ import annotations

import numpy as np


def _grid(x, z, side):
    cx = np.floor(x / side).astype(np.int64)
    cz = np.floor(z / side).astype(np.int64)
    key = cx * (1 << 32) + cz
    order = np.argsort(key, kind="stable")
    return cx, cz, key[order], order


def _expand(starts, ends):
    """Flattened ranges [starts[k], ends[k]) and the k of each element."""
    lens = ends - starts
    owner = np.repeat(np.arange(len(starts)), lens)
    base = np.repeat(starts - np.cumsum(lens) + lens, lens)
    return owner, base + np.arange(lens.sum())


def seen_pairs(x, z, r, touch=None, dtype=np.float32) -> np.ndarray:
    """Sorted unique codes ``i * n + j`` of every pair in which observer i
    sees j, restricted to pairs with ``touch[i] or touch[j]`` when a mask
    is given (pairs between untouched entities are left out)."""
    n = len(x)
    x = np.asarray(x).astype(dtype)
    z = np.asarray(z).astype(dtype)
    r = np.asarray(r).astype(dtype)
    side = float(np.max(r)) if n else 1.0
    cx, cz, skey, order = _grid(x.astype(np.float64), z.astype(np.float64),
                                side)
    who = np.arange(n) if touch is None else np.nonzero(touch)[0]
    out = []
    for dx in (-1, 0, 1):
        for dz in (-1, 0, 1):
            k = (cx[who] + dx) * (1 << 32) + (cz[who] + dz)
            lo = np.searchsorted(skey, k, "left")
            hi = np.searchsorted(skey, k, "right")
            own, at = _expand(lo, hi)
            a, b = who[own], order[at]
            # a as observer of b, and (for a restricted set) b of a
            for i, j in ((a, b), (b, a)) if touch is not None else ((a, b),):
                hit = ((i != j)
                       & (np.abs(x[j] - x[i]) <= r[i])
                       & (np.abs(z[j] - z[i]) <= r[i]))
                out.append(i[hit].astype(np.int64) * n + j[hit])
    return np.unique(np.concatenate(out)) if out else np.empty(0, np.int64)


def events(prev_pos, pos, r, moved, dtype=np.float32):
    """(enter, leave) codes of one space for one tick.  Only pairs with a
    moved end can change, so both sets are restricted to them."""
    before = seen_pairs(prev_pos[:, 0], prev_pos[:, 1], r, moved, dtype)
    after = seen_pairs(pos[:, 0], pos[:, 1], r, moved, dtype)
    return (np.setdiff1d(after, before, assume_unique=True),
            np.setdiff1d(before, after, assume_unique=True))


def sync_pairs(pos, r, moved, players, dtype=np.float32) -> np.ndarray:
    """Codes ``w * n + e`` of the records one space owes after a tick:
    every moved entity e to every player w that sees it."""
    n = len(r)
    seen = seen_pairs(pos[:, 0], pos[:, 1], r, moved, dtype)
    w, e = seen // n, seen % n
    return seen[players[w] & moved[e]]
