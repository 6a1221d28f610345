"""Position-sync records as columns.

The sync phase (``Runtime._sync_phase``) builds each space's records as
arrays (``Space.collect_sync``): one column per field of a record --
client id bytes, gate, entity id bytes, x, y, z, yaw.  A
:class:`SyncBatch` holds those parts for the ticks since the last
``Runtime.drain_sync``, together with the records of slotless entities
(no AOI slot, so no columns), which the phase still builds one at a time
as tuples.  ``pack_by_gate`` lays the columns out in the wire layout of
``MT_SYNC_POSITION_YAW_ON_CLIENTS`` (``SYNC_ON_CLIENT_RECORD``: 16-byte
client id, 16-byte entity id, x, y, z, yaw as little-endian f32;
proto/connection.py), one ``tobytes()`` per gate.

Iterating a batch yields the records as 7-tuples
``(client_id, gate_id, entity_id, x, y, z, yaw)`` with ``str`` ids, an
``int`` gate and ``float`` coordinates.  Order is not a contract (GoWorld
walks a map): per tick, space by space in id order, each space's
own-client records by entity slot, then its neighbour records by
(entity slot, watcher slot); the slotless records come last.
"""

from __future__ import annotations

import numpy as np

from .ecs import ID_BYTES


# one 48-byte record of MT_SYNC_POSITION_YAW_ON_CLIENTS
SYNC_ON_CLIENT_RECORD = np.dtype([
    ("cid", "S%d" % ID_BYTES), ("eid", "S%d" % ID_BYTES), ("x", "<f4"),
    ("y", "<f4"), ("z", "<f4"), ("yaw", "<f4")])


def _strs(a: np.ndarray) -> list:
    try:
        return a.astype(str).tolist()
    except UnicodeDecodeError:
        return [b.decode() for b in a.tolist()]


def _columns(records) -> tuple:
    """The columns of 7-tuples."""
    cids, gates, eids, xs, ys, zs, yaws = zip(*records)
    f32 = np.float32
    return (np.array([c.encode() for c in cids]), np.array(gates, np.int64),
            np.array([e.encode() for e in eids]), np.array(xs, f32),
            np.array(ys, f32), np.array(zs, f32), np.array(yaws, f32))


class SyncBatch:
    """The position-sync records drained from a Runtime, as columns."""

    __slots__ = ("_parts", "_walked", "_n")

    def __init__(self):
        # each part: (cid, gate, eid, x, y, z, yaw) arrays of one length
        self._parts: list[tuple] = []
        self._walked: list[tuple] = []
        self._n = 0

    @classmethod
    def from_records(cls, records) -> "SyncBatch":
        """A batch holding the given 7-tuples as columns."""
        b = cls()
        records = list(records)
        if records:
            b.add(_columns(records))
        return b

    def add(self, part: tuple):
        """Append one part: (cid, gate, eid, x, y, z, yaw) columns."""
        self._parts.append(part)
        self._n += len(part[0])

    def add_walked(self, records: list[tuple]):
        """Append records built one at a time (slotless entities)."""
        self._walked += records
        self._n += len(records)

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        for cid, gate, eid, x, y, z, yaw in self._parts:
            yield from zip(_strs(cid), gate.tolist(), _strs(eid), x.tolist(),
                           y.tolist(), z.tolist(), yaw.tolist())
        yield from self._walked

    def _locate(self, i: int):
        if i < 0:
            i += self._n
        if not 0 <= i < self._n:
            raise IndexError("sync record index out of range")
        for part in self._parts:
            if i < len(part[0]):
                return part, i
            i -= len(part[0])
        return None, i

    def __getitem__(self, i: int) -> tuple:
        part, i = self._locate(i)
        if part is None:
            return self._walked[i]
        cid, gate, eid, x, y, z, yaw = (c[i] for c in part)
        return (cid.decode(), int(gate), eid.decode(), float(x), float(y),
                float(z), float(yaw))

    def __setitem__(self, i: int, record: tuple):
        part, i = self._locate(i)
        if part is None:
            self._walked[i] = tuple(record)
            return
        cid, gate, eid, *xyzw = record
        for col, v in zip(part, (cid.encode(), gate, eid.encode(), *xyzw)):
            col[i] = v

    def pack_by_gate(self) -> dict[int, bytes]:
        """The wire bytes of the records, per gate id, each gate's records
        in batch order.  Raises ValueError for an id that is not 16 ascii
        bytes, as ``Packet.append_entity_id`` does."""
        parts = list(self._parts)
        if self._walked:
            parts.append(_columns(self._walked))
        if not parts:
            return {}
        cid, gate, eid, x, y, z, yaw = (np.concatenate(c) for c in zip(*parts))
        for ids in (cid, eid):
            raw = ids.view(np.uint8).reshape(len(ids), ids.itemsize)
            if (ids.itemsize != ID_BYTES or (raw[:, -1] == 0).any()
                    or (raw >= 0x80).any()):
                raise ValueError("sync record id is not %d ascii bytes"
                                 % ID_BYTES)
        rec = np.empty(len(cid), SYNC_ON_CLIENT_RECORD)
        for name, col in zip(SYNC_ON_CLIENT_RECORD.names,
                             (cid, eid, x, y, z, yaw)):
            rec[name] = col
        if (gate == gate[0]).all():
            return {int(gate[0]): rec.tobytes()}
        order = np.argsort(gate, kind="stable")
        g = gate[order]
        cuts = np.flatnonzero(g[1:] != g[:-1]) + 1
        bounds = zip(np.r_[0, cuts].tolist(), np.r_[cuts, len(g)].tolist())
        return {int(g[lo]): rec[order[lo:hi]].tobytes() for lo, hi in bounds}
