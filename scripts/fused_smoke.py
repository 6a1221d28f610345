#!/usr/bin/env python
"""Fused-dispatch smoke: 3-tier parity + dispatch counts + demotion.

The ci.sh gate for the one-dispatch fused pipeline (ops/aoi_fused,
``Runtime(aoi_fused=True)``; docs/perf.md "Fused dispatch"):

* every tier (single-chip, mesh, row-sharded) runs a seeded random
  world fused next to an unfused engine and the CPU oracle; enter/leave
  events must match bit-exactly every tick;
* device dispatches per steady-state tick are counted through
  ``ops.dispatch_count`` and reported per tier -- fused must reach 1
  (the whole point), unfused sits at 2 (scatter + step); the
  single-chip harvest adds its eager triples slice on a tick with
  events, one more launch on both sides;
* a forced mid-run ``aoi.kernel`` fault demotes exactly one fused tick
  to the unfused path (``aoi.fused_demotions``), which must republish
  the same events same-tick -- parity is asserted across the demotion.

Runs on the CPU backend (8 forced host devices) in well under a minute;
a real accelerator only changes the platform routing, not the contract.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from goworld_tpu import faults  # noqa: E402
from goworld_tpu.engine.aoi import AOIEngine  # noqa: E402
from goworld_tpu.ops import dispatch_count as DC  # noqa: E402

TICKS = 8
N_ENT = 200


def _scene(seed, cap, n):
    rng = np.random.default_rng(seed)
    xs = rng.uniform(0, 400, n).astype(np.float32)
    zs = rng.uniform(0, 400, n).astype(np.float32)
    rr = rng.uniform(20, 60, n).astype(np.float32)
    act = np.ones(n, bool)
    return rng, xs, zs, rr, act


def _pad(a, cap):
    out = np.zeros(cap, a.dtype)
    out[:len(a)] = a
    return out


def _drive(engines, handles, cap, seed=11, ticks=TICKS, n=N_ENT):
    """Tick a seeded world through every engine; return per-tick events
    and per-tick device dispatch counts per engine."""
    rng, xs, zs, rr, act = _scene(seed, cap, n)
    events = {k: [] for k in engines}
    counts = {k: [] for k in engines}
    for _t in range(ticks):
        move = rng.random(n) < 0.3
        xs[move] += rng.uniform(-8, 8, int(move.sum())).astype(np.float32)
        zs[move] += rng.uniform(-8, 8, int(move.sum())).astype(np.float32)
        for k, e in engines.items():
            h = handles[k]
            e.submit(h, _pad(xs, cap), _pad(zs, cap), _pad(rr, cap),
                     _pad(act, cap).astype(bool))
            DC.reset()
            e.flush()
            counts[k].append(DC.read())
            ev = e.take_events(h)
            events[k].append(tuple(np.array(p, copy=True) for p in ev))
    return events, counts


def _assert_parity(events, ref="cpu", label=""):
    for k, evs in events.items():
        if k == ref:
            continue
        for t, (a, b) in enumerate(zip(events[ref], evs)):
            for pa, pb in zip(a, b):
                np.testing.assert_array_equal(
                    pa, pb, err_msg=f"{label}/{k} tick {t}")


def _mesh(n=8):
    from goworld_tpu.parallel import SpaceMesh, multichip_devices

    devs = multichip_devices(n)
    if len(devs) < n:
        raise SystemExit(f"fused_smoke: needs {n} (virtual) devices")
    return SpaceMesh(devs)


def run_tier(name, cap, tri_slice=False, **ekw):
    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "unfused": AOIEngine(default_backend="tpu", **ekw),
        "fused": AOIEngine(default_backend="tpu", fused=True, **ekw),
    }
    handles = {k: e.create_space(cap) for k, e in engines.items()}
    events, counts = _drive(engines, handles, cap)
    _assert_parity(events, label=name)
    st = handles["fused"].bucket.stats
    steady_f, steady_u = counts["fused"][-1], counts["unfused"][-1]
    # the single-chip harvest slices the triples of a tick with events
    sliced = int(tri_slice and sum(len(p) for p in events["fused"][-1]) > 0)
    print(f"  {name:11s} parity OK | dispatches/tick steady: "
          f"fused={steady_f} unfused={steady_u} (triples slice: {sliced}) | "
          f"fused_dispatches={st['fused_dispatches']} "
          f"demotions={st['fused_demotions']}")
    assert st["fused_dispatches"] > 0, f"{name}: fused path never taken"
    assert st["fused_demotions"] == 0, f"{name}: unexpected demotion"
    assert steady_f == 1 + sliced, \
        f"{name}: fused steady tick took {steady_f} dispatches, want 1"
    assert steady_f < steady_u, \
        f"{name}: fused ({steady_f}) not below unfused ({steady_u})"
    return steady_f, steady_u


def run_demotion(cap=256):
    """A kernel seam firing INSIDE the fused attempt must demote that one
    tick to the unfused path -- counted, bit-exact, same-tick."""
    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "fused": AOIEngine(default_backend="tpu", fused=True),
    }
    handles = {k: e.create_space(cap) for k, e in engines.items()}
    faults.install("aoi.kernel:fail@4")
    try:
        events, _counts = _drive(engines, handles, cap)
    finally:
        faults.clear()
    _assert_parity(events, label="demotion")
    st = handles["fused"].bucket.stats
    print(f"  demotion    parity OK | fused_demotions="
          f"{st['fused_demotions']} (forced aoi.kernel fail)")
    assert st["fused_demotions"] >= 1, "forced fault did not demote"


def main():
    print("== fused smoke: single-chip ==")
    run_tier("single", 256, tri_slice=True)
    mesh = _mesh()
    print("== fused smoke: mesh ==")
    run_tier("mesh", 256, mesh=mesh)
    print("== fused smoke: rowshard ==")
    run_tier("rowshard", 2048, mesh=mesh, rowshard_min_capacity=2048)
    print("== fused smoke: fault demotion ==")
    run_demotion()
    print("fused smoke OK")


if __name__ == "__main__":
    main()
