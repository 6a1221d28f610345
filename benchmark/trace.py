"""Reduction of a ``jax.profiler`` trace to the device metrics.

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData``: the device
planes (``/device:TPU:<n>``) hold one event per device operation on
their ``XLA Ops`` line; the host plane holds the host spans written as
``TraceAnnotation``s (the program's, through
``telemetry.trace.enable_jax_annotations``, and the harness's own).
Both are on the profiler's one clock.

Within the window (the host span named ``window``):

* ``busy_s``: the union of the device-operation intervals, averaged over
  the devices that ran any;
* ``op_s``: the summed durations of the device operations, counting an
  operation nested inside another (a loop's body) once, with its parent
  (per program and operation name in ``ops``);
* ``gaps``: the idle stretches between busy intervals, longest first,
  each named by the innermost host span running at its middle.

``peaks`` holds the published peaks of each chip, keyed by JAX's
``device_kind``; a kind that is not in the table is an error.
"""

from __future__ import annotations

import glob
import json
import os

import numpy as np

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in the peaks table "
                       f"{PEAKS}: add its published peaks and source")
    return table[kind]


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge [k, 2] intervals into disjoint sorted ones."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _start(ev):
    return ev[1]


def _top_level(ops: list) -> list:
    """The operations not nested inside another on the same line (a
    while loop and the operations of its body are one piece of work)."""
    out, end = [], -1
    for ev in sorted(ops, key=_start):
        if ev[2] <= end:
            continue
        out.append(ev)
        end = max(end, ev[2])
    return out


def reduce(path: str, window: str = "bench.window", top: int = 10) -> dict:
    """Device metrics of the trace at ``path`` over its ``window`` span.
    Returns None-valued fields where the trace holds no device plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dest = ops
                elif line.name == "XLA Modules":
                    dest = mods
                else:
                    continue
                for e in line.events:
                    dest.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
            devices.append((_top_level(ops), sorted(mods, key=_start)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
    wins = [(a, b) for n, a, b in host if n == window]
    if not wins:
        raise ValueError(f"no host span {window!r} in {path}")
    lo, hi = wins[0]
    window_s = (hi - lo) * 1e-9
    used = [d for d in devices
            if any(b > lo and a < hi for _n, a, b in d[0])]
    out = {"window_s": window_s, "devices": len(used), "busy_s": None,
           "op_s": None, "ops": [], "gaps": []}
    if not used:
        return out
    busy, op_s, per_op = [], 0.0, {}
    merged0 = None
    for ops, mods in used:
        iv = _clip(np.asarray([(a, b) for _n, a, b in ops], np.float64),
                   lo, hi)
        merged = _union(iv)
        if merged0 is None:
            merged0 = merged
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        starts = [a for _n, a, _b in mods]
        for name, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                op_s += (b - a) * 1e-9
                k = int(np.searchsorted(starts, a, "right")) - 1
                mod = mods[k][0] if k >= 0 and mods[k][2] >= b else "?"
                key = f"{mod}:{name.split(' = ')[0]}"
                per_op[key] = per_op.get(key, 0.0) + (b - a) * 1e-9
    out["busy_s"] = float(np.mean(busy))
    out["op_s"] = op_s / len(used)
    out["ops"] = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    # idle gaps of the first device, named by the innermost host span
    edges = np.concatenate([[lo], merged0.ravel(), [hi]]).reshape(-1, 2)
    gaps = [(a, b) for a, b in edges if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    spans = [(n, a, b) for n, a, b in host if n != window]
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        inner = [(sa, n) for n, sa, sb in spans if sa <= mid <= sb]
        # a span (TraceAnnotation) names the gap; where none is open, the
        # innermost Python frame the profiler's tracer recorded ("$...")
        marked = [x for x in inner if not x[1].startswith("$")]
        name = max(marked or inner)[1] if inner else "(no host span)"
        named.append((name, (b - a) * 1e-9))
    out["gaps"] = named
    return out
