#!/usr/bin/env python
"""CPU-only smoke test of the event decode/emit paths (docs/perf.md).

A ci.sh step (and a standalone sanity check): on a small churny walk every
``aoi_emit`` mode -- the device-resident triples decode with native C++
fan-out when libgwemit builds, the vectorized NumPy fan-out, and the
classic host word-stream decode -- must deliver a byte-identical
enter/leave stream (CRC-folded, same artifact as bench.py's
``parity_checksum``), including one forced triple-cap-overflow tick (the
counted fallback).  Ends with a span-sourced phase report
(fetch/decode/emit) so the numbers CI prints are the ones the tentpole is
judged on.
"""

import os
import sys
import zlib

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from goworld_tpu import telemetry  # noqa: E402
from goworld_tpu.engine.aoi import AOIEngine  # noqa: E402
from goworld_tpu.ops import aoi_emit as AE  # noqa: E402
from goworld_tpu.telemetry import trace as gwtrace  # noqa: E402


def run_mode(mode, frames, cap, shrink_tri=False):
    """Drive one engine through the walk; returns (crc, bucket, span_s)."""
    eng = AOIEngine(default_backend="cpu" if mode == "cpu"
                    else "tpu", emit=mode if mode != "cpu" else "auto")
    h = eng.create_space(cap)
    if shrink_tri:
        h.bucket._max_triples = 4  # force the counted overflow fallback
    telemetry.enable()
    gwtrace.reset()
    crc = 0
    for x, z, r, act in frames:
        eng.submit(h, x, z, r, act)
        eng.flush()
        e, l = eng.take_events(h)
        crc = zlib.crc32(np.ascontiguousarray(e).tobytes(), crc)
        crc = zlib.crc32(np.ascontiguousarray(l).tobytes(), crc)
    span_s = {}
    for name, _tid, s0, s1 in gwtrace.spans():
        span_s[name] = span_s.get(name, 0.0) + (s1 - s0)
    telemetry.disable()
    return crc, (h.bucket if mode != "cpu" else None), span_s


def main():
    cap, n, ticks = 256, 180, 5
    rng = np.random.default_rng(33)
    x = rng.uniform(0, 600, n).astype(np.float32)
    z = rng.uniform(0, 600, n).astype(np.float32)
    r = rng.uniform(60, 120, n).astype(np.float32)
    act = np.zeros(cap, bool)
    act[:n] = True

    def pad(a):
        o = np.zeros(cap, a.dtype)
        o[:n] = a
        return o

    frames = []
    for _ in range(ticks):
        x = np.clip(x + rng.uniform(-15, 15, n).astype(np.float32), 0, 600)
        z = np.clip(z + rng.uniform(-15, 15, n).astype(np.float32), 0, 600)
        frames.append((pad(x), pad(z), pad(r), act.copy()))

    modes = ["vector", "host"] + (["native"] if AE.available() else [])
    oracle_crc, _, _ = run_mode("cpu", frames, cap)
    phases = {}
    for mode in modes:
        crc, bucket, span_s = run_mode(mode, frames, cap)
        assert crc == oracle_crc, \
            f"{mode}: parity {crc:08x} != oracle {oracle_crc:08x}"
        assert bucket.stats["emit_path"] == AE.EMIT_LEVEL[mode], \
            f"{mode}: demoted to level {bucket.stats['emit_path']}"
        phases[mode] = {
            ph: span_s.get(nm, 0.0) / ticks * 1e3
            for ph, nm in (("fetch", "aoi.fetch"), ("decode", "aoi.decode"),
                           ("diff", "aoi.diff"), ("fanout", "aoi.fanout"))}

    # forced overflow: the counted fallback must stay bit-exact and count
    crc, bucket, _ = run_mode(modes[0], frames, cap, shrink_tri=True)
    assert crc == oracle_crc, f"overflow parity {crc:08x}"
    assert bucket.stats["decode_overflow"] >= 1, bucket.stats
    assert bucket._max_triples > 4, "triple cap never grew"

    default = AE.resolve_mode("auto")
    report = "; ".join(
        f"{m}: " + " ".join(f"{ph}={v:.2f}ms"
                            for ph, v in phases[m].items() if v)
        for m in modes)
    print(f"emit_smoke: OK -- {ticks} ticks x {len(modes)} modes bit-exact "
          f"(crc {oracle_crc:08x}), overflow fallback counted "
          f"({bucket.stats['decode_overflow']} ticks); default={default}; "
          f"phase_ms {report}")


if __name__ == "__main__":
    main()
