"""The comparison that decides ``correct``.

For every tick the run kept (a sample of the window's ticks drawn from the
seed), what the timed path delivered is set against the plain reference
(``reference.py``) computed from the generator's positions:

* ``event_pairs_wrong``: enter and leave pairs, per space, that the event
  seam delivered and the reference does not have, plus those it has and
  the seam did not deliver.  Exact: limit 0.
* ``sync_records_wrong``: position-sync records drained that the
  reference does not owe (or owes with another position or gate, or
  twice), plus positions it owes that reached no player: a moved entity's
  position is owed to every player that sees it after the tick, by a sync
  record or, where the player has just started to see it, by the
  client's create op (which carries the position).  Exact: limit 0.

The control (``control="bfloat16"``) puts the reference, computed one
precision below the configuration's float32 positions, in the program's
place: its stream and records are compared the same way and must fail.
"""

from __future__ import annotations

import numpy as np

from . import reference as R

LIMITS = {"event_pairs_wrong": 0, "sync_records_wrong": 0}


def _bf16():
    import ml_dtypes

    return ml_dtypes.bfloat16


def _codes(pairs: np.ndarray, inv: np.ndarray, n: int) -> np.ndarray:
    if not len(pairs):
        return np.empty(0, np.int64)
    a = inv[pairs[:, 0]]
    b = inv[pairs[:, 1]]
    bad = (a < 0) | (b < 0)
    codes = a * n + b
    # a slot no entity holds can never match: give it a code of its own
    codes[bad] = -1 - np.arange(int(bad.sum()))
    return np.sort(codes)


def _multiset_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Size of the symmetric difference of two multisets of codes."""
    ua, ca = np.unique(a, return_counts=True)
    ub, cb = np.unique(b, return_counts=True)
    allk = np.union1d(ua, ub)
    na = np.zeros(len(allk), np.int64)
    nb = np.zeros(len(allk), np.int64)
    na[np.searchsorted(allk, ua)] = ca
    nb[np.searchsorted(allk, ub)] = cb
    return int(np.abs(na - nb).sum())


def delivered_events(shard, rec) -> list[tuple[np.ndarray, np.ndarray]]:
    n = shard.traffic.n
    return [(_codes(e, inv, n), _codes(l, inv, n))
            for (e, l), inv in zip(rec.events, shard.index_of_slot)]


def delivered_sync(shard, rec):
    """Per space, codes ``w * n + e`` of the drained records and of the
    create ops carrying the entity's position after the tick, and the
    count of records whose space, position or gate is not the one owed."""
    n, pos = shard.traffic.n, rec.pos
    gate_of = {}
    for k, (s, i) in enumerate(zip(shard.gate.space, shard.gate.index)):
        gate_of[(int(s), int(i))] = int(shard.gate.gate[k])
    per = [[] for _ in range(shard.traffic.spaces)]
    created = [[] for _ in range(shard.traffic.spaces)]
    wrong = 0
    for cid, gid, eid, x, y, z, yaw in rec.sync:
        w = shard.client_index.get(cid)
        e = shard.entity_index.get(eid)
        if w is None or e is None or w[0] != e[0]:
            wrong += 1
            continue
        s, j = e
        if (x != float(pos[s, j, 0]) or z != float(pos[s, j, 1])
                or y != 0.0 or yaw != 0.0 or gid != gate_of[w]):
            wrong += 1
            continue
        per[s].append(w[1] * n + j)
    for cid, eid, (x, _y, z) in rec.creates:
        w = shard.client_index.get(cid)
        e = shard.entity_index.get(eid)
        if (w is not None and e is not None and w[0] == e[0]
                and x == float(pos[e[0], e[1], 0])
                and z == float(pos[e[0], e[1], 1])):
            created[e[0]].append(w[1] * n + e[1])
    return ([np.asarray(p, np.int64) for p in per],
            [np.asarray(c, np.int64) for c in created], wrong)


def sync_wrong(got: np.ndarray, created: np.ndarray, want: np.ndarray) -> int:
    """Records not owed or owed once but sent twice, plus owed positions
    that reached the player neither by a record nor by a create op."""
    ug, cg = np.unique(got, return_counts=True)
    extra = int((cg - 1).sum()) + int(np.setdiff1d(ug, want).size)
    missing = np.setdiff1d(np.setdiff1d(want, ug), created)
    return extra + int(missing.size)


def owed(shard, rec, shift: int, dtype=np.float32):
    """The reference's events of the kept tick and the records it owes."""
    tr = shard.traffic
    players = tr.roles == 1
    ev, sy = [], []
    for s in range(tr.spaces):
        moved = rec.movers[s]
        ev.append(R.events(rec.prev_pos[s], rec.pos[s], shard.radius[s], moved,
                           dtype))
        # with a shift, the tick's sync phase still sees the interests
        # of the positions before it
        seen_pos = rec.prev_pos[s] if shift else rec.pos[s]
        sy.append(R.sync_pairs(seen_pos, shard.radius[s], moved, players[s],
                               dtype))
    return ev, sy


def compare(shard, kept: dict, sampled: list[int], shift: int,
            control: str | None = None) -> dict:
    """Numbers compared over the sampled ticks (their keys in ``kept``;
    the events of tick t are those delivered ``shift`` ticks later)."""
    ev_wrong = sy_wrong = n_events = n_records = 0
    for t in sampled:
        rec, late = kept[t], kept[t + shift]
        want_ev, want_sy = owed(shard, rec, shift)
        if control is None:
            got_ev = delivered_events(shard, late)
            got_sy, created, bad = delivered_sync(shard, rec)
        else:
            got_ev, got_sy = owed(shard, rec, shift, dtype=_bf16())
            created = [np.empty(0, np.int64)] * len(got_sy)
            bad = 0
        sy_wrong += bad
        for (ge, gl), (we, wl) in zip(got_ev, want_ev):
            ev_wrong += _multiset_diff(ge, we) + _multiset_diff(gl, wl)
            n_events += len(we) + len(wl)
        for g, c, w in zip(got_sy, created, want_sy):
            sy_wrong += sync_wrong(g, c, w)
            n_records += len(w)
    return {"event_pairs_wrong": ev_wrong, "sync_records_wrong": sy_wrong,
            "ticks_checked": len(sampled), "events_owed": n_events,
            "records_owed": n_records}


def find_shift(shard, kept: dict, t: int) -> tuple[int | None, list[int]]:
    """Which tick delivers the events of tick ``t``'s moves: ``t`` itself
    (0) or the next (1).  Returns the shift that matches exactly (None if
    neither) and the mismatch count of each."""
    counts = []
    for shift in (0, 1):
        want, _ = owed(shard, kept[t], shift)
        got = delivered_events(shard, kept[t + shift])
        counts.append(sum(_multiset_diff(ge, we) + _multiset_diff(gl, wl)
                          for (ge, gl), (we, wl) in zip(got, want)))
    return (counts.index(0) if 0 in counts else None), counts


def verdict(numbers: dict) -> bool:
    return (numbers["ticks_checked"] > 0
            and all(numbers[k] <= v for k, v in LIMITS.items()))
