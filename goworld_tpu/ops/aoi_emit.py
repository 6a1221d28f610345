"""Event emit fan-out: device triples / decoded words -> replay-ready pairs.

The host half of the device-resident event decode (docs/perf.md emit
paths).  A tick's classified AOI diff reaches the host either as raw
(observer, observed, kind) triples (:func:`goworld_tpu.ops.events.
extract_triples`, single-chip tier) or as a decoded word stream
(mesh/rowshard tiers); this module turns both into the per-space sorted
enter/leave pair arrays the buckets publish, in one of three modes:

  * ``native`` -- ``native/libgwemit.so`` (ctypes, built on demand exactly
    like :mod:`goworld_tpu.ops.aoi_native`): partition + deterministic
    (space, observer, observed) sort + row split in C++;
  * ``vector`` -- pure-NumPy argsort fallback, used when the ``.so``
    cannot build (no toolchain);
  * ``host``   -- the original per-word host decode
    (:func:`goworld_tpu.ops.events.expand_classified_host`), kept as the
    bit-exact oracle and the ``aoi.emit`` fault seam's fallback target.

All three orders are identical by construction (one integer sort key,
unique within a tick); tests/test_aoi_emit.py pins the parity across the
bucket tiers.  Everything here is harvest-phase numpy on already-fetched
arrays -- the gwlint flush-phase rule walks this module's functions and
rejects any blocking device fetch.
"""

from __future__ import annotations

import ctypes
import threading

import numpy as np

from ..utils import native
from .aoi_predicate import words_per_row

EMIT_MODES = ("native", "vector", "host")
# stats["emit_path"] levels, mirroring stats["calc_level"]: higher = more
# demoted (native 0 -> vector 1 -> host 2)
EMIT_LEVEL = {"native": 0, "vector": 1, "host": 2}

_SO_NAME = native.so_name("libgwemit")
_lib = None
_tried = False
_build_lock = threading.Lock()


def _load():
    global _lib, _tried
    if _lib is not None:
        return _lib
    # _tried is read under the lock only: the attempt (a make run) holds the
    # lock throughout, so a thread that finds _tried set there sees its result
    with _build_lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = native.build(_SO_NAME)
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        u32p = ctypes.POINTER(ctypes.c_uint32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        lib.gwemit_fanout.restype = ctypes.c_int64
        lib.gwemit_fanout.argtypes = [
            i32p, ctypes.c_int64, ctypes.c_int32, i32p, i32p,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.gwemit_count.restype = ctypes.c_int64
        lib.gwemit_count.argtypes = [u32p, ctypes.c_int64]
        lib.gwemit_words.restype = ctypes.c_int64
        lib.gwemit_words.argtypes = [
            u32p, u32p, i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            i32p, ctypes.c_int64, i32p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _ptr(a, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def resolve_mode(requested: str | None) -> str:
    """Resolve a Runtime ``aoi_emit`` request to a concrete mode.

    ``auto`` (the default) picks the fastest available: ``native`` when
    libgwemit loads, else ``vector``.  An explicit ``native`` request also
    degrades to ``vector`` when the library is absent (no toolchain) --
    mode selection must never make an engine unconstructable.
    """
    if requested is None or requested == "auto":
        return "native" if available() else "vector"
    if requested not in EMIT_MODES:
        raise ValueError(
            f"aoi_emit must be one of {('auto',) + EMIT_MODES}, "
            f"got {requested!r}")
    if requested == "native" and not available():
        return "vector"
    return requested


def fanout_triples(tri, capacity: int, native: bool = True):
    """Raw (obs, observed, kind) triples -> sorted (enter, leave) rows.

    ``tri`` holds only VALID rows ([n, 3] int32; obs is the global observer
    row ``s * capacity + i``).  Returns (enter [K, 3], leave [L, 3]) int32
    (space, observer, observed) rows, each sorted lexicographically --
    bit-exact with :func:`goworld_tpu.ops.events.expand_classified_host`.
    ``native=False`` forces the NumPy path (the ``vector`` mode).
    """
    n = len(tri)
    if n == 0:
        e = np.empty((0, 3), np.int32)
        return e, e
    lib = _load() if native else None
    if lib is not None:
        t = np.ascontiguousarray(tri, np.int32)
        enter = np.empty((n, 3), np.int32)
        leave = np.empty((n, 3), np.int32)
        nl = ctypes.c_int64(0)
        ne = lib.gwemit_fanout(
            _ptr(t, ctypes.c_int32), n, capacity,
            _ptr(enter, ctypes.c_int32), _ptr(leave, ctypes.c_int32),
            ctypes.byref(nl),
        )
        if ne >= 0:
            return enter[:ne].copy(), leave[:nl.value].copy()
        # defensive: malformed triples -> same answer via the numpy path
    obs = tri[:, 0].astype(np.int64)
    key = obs * capacity + tri[:, 1]
    out = np.empty((n, 3), np.int32)
    out[:, 0] = obs // capacity
    out[:, 1] = obs % capacity
    out[:, 2] = tri[:, 1]
    order = np.argsort(key)  # keys unique per tick: any sort is the order
    out = out[order]
    ent = tri[order, 2] == 1
    return (np.ascontiguousarray(out[ent]),
            np.ascontiguousarray(out[~ent]))


def expand_words_native(chg_vals, ent_vals, gidx, capacity: int):
    """Classified word stream -> sorted (enter, leave) rows via C++.

    The mesh/rowshard emit path: those tiers decode per-chip wire streams
    into (chg, ent, gidx) words on host, and this hands the bit expansion +
    partition + sort to libgwemit.  Raises RuntimeError when the library
    is unavailable or rejects the input -- callers fall back to
    :func:`goworld_tpu.ops.events.expand_classified_host` (bit-exact).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("libgwemit.so unavailable")
    cv = np.ascontiguousarray(chg_vals, np.uint32)
    ev = np.ascontiguousarray(ent_vals, np.uint32)
    gi = np.ascontiguousarray(gidx, np.int64)
    n = len(cv)
    if n == 0:
        e = np.empty((0, 3), np.int32)
        return e, e
    total = lib.gwemit_count(_ptr(cv, ctypes.c_uint32), n)
    enter = np.empty((total, 3), np.int32)
    leave = np.empty((total, 3), np.int32)
    nl = ctypes.c_int64(0)
    ne = lib.gwemit_words(
        _ptr(cv, ctypes.c_uint32), _ptr(ev, ctypes.c_uint32),
        _ptr(gi, ctypes.c_int64), n, capacity, words_per_row(capacity),
        _ptr(enter, ctypes.c_int32), total,
        _ptr(leave, ctypes.c_int32), total,
        ctypes.byref(nl),
    )
    if ne < 0:
        raise RuntimeError("gwemit_words rejected the word stream")
    return enter[:ne].copy(), leave[:nl.value].copy()
