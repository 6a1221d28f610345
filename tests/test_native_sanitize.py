"""Sanitizer harness for the native C++ (the CI analog of the reference's
race-detector runs, /root/reference/covertest.sh:8-14: every package, every
commit, -race on).  Here the compiled code on the production host path --
native/gwaoi.cpp (pointer-heavy sweep/grid enumeration) and native/gwlz.cpp
(LZ codec) -- is rebuilt with ASAN+UBSAN (-fno-sanitize-recover, so ANY
finding aborts) and driven through the same python callers in a subprocess
with the sanitizer runtimes preloaded."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

_REPO = Path(__file__).resolve().parent.parent
_NATIVE = _REPO / "native"

_DRIVE = r"""
import numpy as np

from goworld_tpu.ops import aoi_native
from goworld_tpu.ops.aoi_oracle import CPUAOIOracle

assert aoi_native._SO_NAME.endswith(".san.so"), aoi_native._SO_NAME
assert aoi_native.available(), "sanitized libgwaoi failed to load"

rng = np.random.default_rng(7)
cap = 256
for algo in ("sweep", "grid", "auto"):
    o = aoi_native.NativeAOIOracle(cap, algo)
    ref = CPUAOIOracle(cap, "sweep")
    n = 200  # partial occupancy: exercises the padded tail
    x = rng.uniform(0, 300, n).astype(np.float32)
    z = rng.uniform(0, 300, n).astype(np.float32)
    r = rng.uniform(0, 60, n).astype(np.float32)  # includes r ~ 0
    act = rng.random(n) < 0.8
    for tick in range(6):
        x = np.clip(x + rng.uniform(-40, 40, n).astype(np.float32), 0, 300)
        # tie lattice every other tick: duplicate coordinates stress the
        # sweep's equal-key windows and the grid's shared-cell chains
        if tick % 2:
            x = np.round(x / 25) * 25
            z = np.round(z / 25) * 25
        act ^= rng.random(n) < 0.1
        e1, l1 = o.step(x, z, r, act)
        e2, l2 = ref.step(x, z, r, act)
        assert (e1 == e2).all() and (l1 == l2).all(), (algo, tick)
    o.reset()
    # overflow growth path: everyone inside everyone's radius
    xx = np.zeros(cap, np.float32)
    rr = np.full(cap, 1000, np.float32)
    aa = np.ones(cap, bool)
    ent, _ = o.step(xx, xx, rr, aa)
    assert len(ent) == cap * (cap - 1)

from goworld_tpu.netutil.compress import GwlzCompressor

c = GwlzCompressor()
payloads = [
    b"",
    b"a",
    b"ab" * 5000,
    bytes(rng.integers(0, 256, 70000, dtype=np.uint8)),
    bytes(rng.integers(0, 4, 70000, dtype=np.uint8)),  # compressible
    bytes(range(256)) * 3,
]
for p in payloads:
    comp = c.compress(p)
    assert c.decompress(comp) == p
print("SAN_OK")
"""


def _runtime(name):
    try:
        r = subprocess.run(["g++", f"-print-file-name={name}"],
                           capture_output=True, text=True)
    except FileNotFoundError:
        return None  # no gcc: skip, don't error
    p = r.stdout.strip()
    return p if os.path.sep in p and os.path.exists(p) else None


def test_native_under_asan_ubsan():
    if not (_NATIVE / "Makefile").exists():
        pytest.skip("native sources absent")
    asan, ubsan = _runtime("libasan.so"), _runtime("libubsan.so")
    if asan is None or ubsan is None:
        pytest.skip("sanitizer runtimes unavailable (no gcc?)")
    b = subprocess.run(["make", "-C", str(_NATIVE), "-s", "sanitize"],
                       capture_output=True, text=True, timeout=300)
    assert b.returncode == 0, b.stderr
    env = os.environ.copy()
    env["GW_SANITIZED_NATIVE"] = "1"
    # the drive is numpy+ctypes only, but importing goworld_tpu.ops pulls
    # in jax -- keep it off any accelerator
    env["JAX_PLATFORMS"] = "cpu"
    # the .so carries no runtime (gcc links it into executables only);
    # preload both.  leak detection off: the python interpreter's own
    # arenas drown the report in noise
    env["LD_PRELOAD"] = f"{asan} {ubsan}"
    env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
    env["UBSAN_OPTIONS"] = "halt_on_error=1:print_stacktrace=1"
    r = subprocess.run([sys.executable, "-c", _DRIVE], cwd=str(_REPO),
                       env=env, capture_output=True, timeout=600)
    assert r.returncode == 0, (r.stdout.decode()[-2000:]
                               + r.stderr.decode()[-4000:])
    assert b"SAN_OK" in r.stdout
