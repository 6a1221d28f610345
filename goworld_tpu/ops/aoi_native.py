"""Native (C++) XZ-sweep AOI backend.

Role equivalent of the reference's production AOI manager (go-aoi XZList --
a compiled-language sorted-coordinate sweep, /root/reference/engine/entity/
Space.go:105): the fast host-CPU calculator for spaces where a device
round-trip isn't worth it, and the native-speed CPU baseline.  Evaluates the
exact predicate of :mod:`aoi_predicate`; bit-exact with the Python oracle
and the TPU backends (tests/test_aoi_native.py).

Loads ``native/libgwaoi.so`` via ctypes, building it with make on first use
(same scheme as netutil.compress's gwlz loader).  ``available()`` reports
whether the library could be loaded; callers fall back to the Python oracle.
"""

from __future__ import annotations

import ctypes
import threading

import numpy as np

from ..utils import native
from . import aoi_predicate as P

_SO_NAME = native.so_name("libgwaoi")
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
        f32p = ctypes.POINTER(ctypes.c_float)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.gwaoi_words.restype = None
        lib.gwaoi_words.argtypes = [f32p, f32p, f32p, u8p, ctypes.c_int32,
                                    u32p, ctypes.c_int32]
        lib.gwaoi_step.restype = ctypes.c_int64
        lib.gwaoi_step.argtypes = [
            f32p, f32p, f32p, u8p, ctypes.c_int32, u32p,
            i32p, ctypes.c_int64, i32p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _ptr(a, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


_ALGOS = {"auto": 0, "sweep": 1, "grid": 2}


class NativeAOIOracle:
    """Drop-in for ops.aoi_oracle.CPUAOIOracle, backed by libgwaoi.

    ``algorithm``: "sweep" (XZList-analog windowed scan -- the reference-
    parity baseline), "grid" (uniform cell binning, the TowerAOI idea --
    wins decisively at high density), or "auto" (grid when the layout
    supports it, sweep otherwise).  All bit-exact with each other and the
    Python oracle."""

    def __init__(self, capacity: int, algorithm: str = "auto"):
        self.capacity = P.round_capacity(capacity)
        self.W = P.words_per_row(self.capacity)
        self.prev_words = np.zeros((self.capacity, self.W), np.uint32)
        self._algo = _ALGOS.get(algorithm, 0)
        self._lib = _load()
        if self._lib is None:
            raise RuntimeError(
                "libgwaoi.so unavailable (no C++ toolchain?); use the "
                "python oracle backend instead"
            )
        # event buffers grow on overflow (-1 return)
        self._cap_pairs = 4096

    def reset(self) -> None:
        self.prev_words[:] = 0

    def _padded(self, a, dtype):
        a = np.ascontiguousarray(a, dtype)
        if a.shape[0] > self.capacity:
            raise ValueError(
                f"{a.shape[0]} entities exceed capacity {self.capacity}"
            )
        if a.shape[0] < self.capacity:
            a = np.concatenate(
                [a, np.zeros(self.capacity - a.shape[0], dtype)]
            )
        return a

    def step(self, x, z, radius, active):
        """Advance one tick; returns (enter_pairs, leave_pairs) int32 [K, 2],
        each sorted lexicographically."""
        x = self._padded(x, np.float32)
        z = self._padded(z, np.float32)
        radius = self._padded(radius, np.float32)
        act = self._padded(np.asarray(active, bool), np.uint8)
        prev = np.ascontiguousarray(self.prev_words)
        while True:
            enter = np.empty((self._cap_pairs, 2), np.int32)
            leave = np.empty((self._cap_pairs, 2), np.int32)
            n_leave = ctypes.c_int64(0)
            ne = self._lib.gwaoi_step(
                _ptr(x, ctypes.c_float), _ptr(z, ctypes.c_float),
                _ptr(radius, ctypes.c_float), _ptr(act, ctypes.c_uint8),
                self.capacity, _ptr(prev, ctypes.c_uint32),
                _ptr(enter, ctypes.c_int32), self._cap_pairs,
                _ptr(leave, ctypes.c_int32), self._cap_pairs,
                ctypes.byref(n_leave), self._algo,
            )
            if ne < 0:
                self._cap_pairs *= 4
                continue
            self.prev_words = prev
            return enter[:ne].copy(), leave[: n_leave.value].copy()
