"""The one traffic generator: a configuration's world plus a mix's
parameters, made from ``--seed`` and nothing else.

A configuration (``configs/<name>.json``) fixes the world: spaces,
entities per space, capacity, world side, AOI radius, how entities are
spread.  A mix (``traffic/<name>.json``) fixes what happens each tick:
which share of the entities are players (moved through gate batches),
which share of all entities moves, how far a move goes, how many watchers
each space gets.  Adding a mix is adding a data file; this module reads
every key it needs from those two files.

Closed loop: the harness asks for tick t's moves only after tick t-1
returned.  Every seed gives the same sizes and the same counts of movers
and players; only which entity and where differ.

Copied from the program, so that later PRs may change the program but not
this yardstick: the uniform and "90% in the central 10%x10%" initial
spread of ``bench.py:make_initial`` / ``chip_smoke._initial``, the clipped
random walk of ``chip_smoke.make_walk`` (``STEP``), and the 32-byte gate
record layout of ``goworld_tpu/ingest/movement.SYNC_RECORD`` with the
round-robin gate striping of ``load/clients.GateBatcher``.
"""

from __future__ import annotations

import numpy as np

# one position-sync record as a gate coalesces it onto the wire:
# [16s entity id][f32 x][f32 y][f32 z][f32 yaw], little-endian, no padding
SYNC_RECORD = np.dtype([("eid", "S16"), ("x", "<f4"), ("y", "<f4"),
                        ("z", "<f4"), ("yaw", "<f4")])


def entity_id(space: int, i: int) -> str:
    """Deterministic 16-character entity id (the wire's fixed width)."""
    return "b%03d%012d" % (space, i)


def client_id(space: int, i: int) -> str:
    return "c%03d%012d" % (space, i)


def _rng(seed: int, stream: int) -> np.random.Generator:
    # any whole number, negative or past 64 bits, maps to one entropy word
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def initial_positions(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """[spaces, n, 2] float32 positions inside [0, world]."""
    s, n, world = cfg["spaces"], cfg["entities_per_space"], cfg["world"]
    spread = cfg["spread"]
    if spread["kind"] == "uniform":
        pos = rng.uniform(0, world, (s, n, 2))
    elif spread["kind"] == "hotspot":
        # hot_share of the entities inside the central square whose side
        # is hot_side of the world's (0.1 of the side = 1% of the area)
        hot = rng.random((s, n, 1)) < spread["hot_share"]
        lo = (0.5 - spread["hot_side"] / 2) * world
        hi = (0.5 + spread["hot_side"] / 2) * world
        pos = np.where(hot, rng.uniform(lo, hi, (s, n, 2)),
                       rng.uniform(0, world, (s, n, 2)))
    else:
        raise ValueError(f"unknown spread {spread['kind']!r}")
    return pos.astype(np.float32)


def radii(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """[spaces, n] float32 AOI radii: one number for all, or drawn
    uniformly from [low, high] per entity."""
    s, n, r = cfg["spaces"], cfg["entities_per_space"], cfg["radius"]
    if isinstance(r, dict):
        if r["kind"] != "uniform":
            raise ValueError(f"unknown radius spread {r['kind']!r}")
        return rng.uniform(r["low"], r["high"], (s, n)).astype(np.float32)
    return np.full((s, n), r, np.float32)


class Traffic:
    """Per-tick moves of one cell.  ``step()`` returns the tick's movers
    and leaves ``pos`` at the positions after it."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix = cfg, mix
        s, n = cfg["spaces"], cfg["entities_per_space"]
        self.spaces, self.n = s, n
        self.world = np.float32(cfg["world"])
        self.step_len = float(mix["step"])
        self.pos = initial_positions(cfg, _rng(seed, 0))
        self.radius = radii(cfg, _rng(seed, 4))
        self._walk = _rng(seed, 1)
        n_players = int(round(n * float(mix["player_share"])))
        n_watch = int(mix["watchers_per_space"])
        if n_players + n_watch > n:
            raise ValueError("more players and watchers than entities")
        # role per entity, the same count in every space and every seed:
        # 0 npc, 1 player, 2 watcher (an npc with an enter hook)
        roles = np.zeros((s, n), np.int8)
        pick = _rng(seed, 2)
        for sp in range(s):
            order = pick.permutation(n)
            roles[sp, order[:n_players]] = 1
            roles[sp, order[n_players:n_players + n_watch]] = 2
        self.roles = roles
        self.gates = int(mix["gates"])
        if n_players and self.gates < 1:
            raise ValueError("players need at least one gate")
        self.n_movers = int(round(n * float(mix["move_share"])))

    def movers(self) -> np.ndarray:
        """[spaces, n] bool: who moves this tick (drawn anew each tick, the
        same count in every space)."""
        if self.n_movers == self.n:
            return np.ones((self.spaces, self.n), bool)
        m = np.zeros((self.spaces, self.n), bool)
        for sp in range(self.spaces):
            m[sp, self._walk.choice(self.n, self.n_movers,
                                    replace=False)] = True
        return m

    def step(self) -> np.ndarray:
        """Advance one tick: movers walk up to ``step`` per axis, clipped
        to the world, in float32.  Returns the [spaces, n] mover mask."""
        m = self.movers()
        d = self._walk.uniform(-self.step_len, self.step_len,
                               (self.spaces, self.n, 2)).astype(np.float32)
        new = np.clip(self.pos + d, np.float32(0), self.world)
        self.pos = np.where(m[..., None], new, self.pos).astype(np.float32)
        return m


class GateBatches:
    """Per gate, the wire batch of its players' records for one tick.
    Players stripe over gates round-robin in (space, index) order."""

    def __init__(self, traffic: Traffic):
        sp, idx = np.nonzero(traffic.roles == 1)
        self.space, self.index = sp, idx
        eids = np.array([entity_id(int(a), int(b)).encode()
                         for a, b in zip(sp, idx)], "S16")
        self.gate = np.arange(len(sp)) % max(traffic.gates, 1)
        self._rows = [np.nonzero(self.gate == g)[0]
                      for g in range(traffic.gates)]
        self._rec = []
        for rows in self._rows:
            rec = np.zeros(len(rows), SYNC_RECORD)
            rec["eid"] = eids[rows]
            self._rec.append(rec)

    def batches(self, pos: np.ndarray, movers: np.ndarray) -> list[bytes]:
        """The bytes each gate sends this tick: one record per moving
        player (y and yaw stay 0)."""
        out = []
        for rows, rec in zip(self._rows, self._rec):
            s, i = self.space[rows], self.index[rows]
            keep = movers[s, i]
            r = rec[keep]
            r["x"] = pos[s[keep], i[keep], 0]
            r["z"] = pos[s[keep], i[keep], 1]
            out.append(r.tobytes())
        return out
