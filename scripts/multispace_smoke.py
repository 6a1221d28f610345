#!/usr/bin/env python
"""Space-stacked cohort smoke: parity + dispatch pin + demotion chain.

The ci.sh gate for the space-stacked megabatch (engine/aoi_cohort,
``AOIEngine(cohort="auto")``; docs/perf.md "Space-stacked cohorts"):

* a shard of small spaces (mixed capacities on one ladder rung) runs
  stacked into ONE shared cohort bucket next to a per-space solo engine
  and the CPU oracle; every space's enter/leave stream must match
  bit-exactly every tick;
* device dispatches per steady-state tick are counted through
  ``ops.dispatch_count``: the cohort side must take 1 (the whole
  point), the solo side one per space, beside one eager triples slice
  per bucket whose tick has events -- and after warmup NEITHER side
  may mint a new step compile key (``DC.new_keys() == 0`` less the
  eager slices' ``aoi.tri_slice`` keys, one per new count bucket: the
  pow2 ladder keeps the step key set O(ladder));
* a forced ``aoi.cohort`` fault demotes the whole cohort to per-space
  solo buckets same-tick -- counted, bit-exact -- and the operator
  re-arm (``recohort()``) stacks every space back onto one bucket.

Runs on the CPU backend in well under a minute; a real accelerator only
changes the platform routing, not the contract.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from goworld_tpu import faults  # noqa: E402
from goworld_tpu.engine.aoi import AOIEngine  # noqa: E402
from goworld_tpu.ops import dispatch_count as DC  # noqa: E402

N_SPACES = 24  # 1 cohort dispatch vs 24 solo: under the 0.05x bench bar
CAPS = [128 if i % 3 else 256 for i in range(N_SPACES)]  # one rung: 256
TICKS = 8
WARMUP = 3


def _scenes(seed=11):
    rng = np.random.default_rng(seed)
    out = []
    for cap in CAPS:
        n = cap - 32
        out.append([rng.uniform(0, 400, n).astype(np.float32),
                    rng.uniform(0, 400, n).astype(np.float32),
                    rng.uniform(20, 60, n).astype(np.float32),
                    np.ones(n, bool)])
    return rng, out


def _pad(a, cap):
    out = np.zeros(cap, a.dtype)
    out[:len(a)] = a
    return out


def _drive(engines, handles, ticks=TICKS, seed=11):
    """Tick one seeded shard through every engine; return per-space
    events and the measured-window dispatch/new-key counts."""
    rng, scenes = _scenes(seed)
    events = {k: [] for k in engines}
    meters = {"slices": 0}
    for t in range(ticks):
        if t == WARMUP:
            DC.reset()
            DC.reset_keys()
        for sc in scenes:
            n = len(sc[0])
            move = rng.random(n) < 0.3
            k = int(move.sum())
            sc[0][move] += rng.uniform(-8, 8, k).astype(np.float32)
            sc[1][move] += rng.uniform(-8, 8, k).astype(np.float32)
        for k, e in engines.items():
            tick_evs = []
            for (x, z, r, act), h in zip(scenes, handles[k]):
                cap = h.capacity
                e.submit(h, _pad(x, cap), _pad(z, cap), _pad(r, cap),
                         _pad(act, cap).astype(bool))
            e.flush()
            busy = set()
            for h in handles[k]:
                ev = e.take_events(h)
                tick_evs.append(tuple(np.array(p, copy=True) for p in ev))
                if k != "cpu" and sum(len(p) for p in ev):
                    busy.add(id(h.bucket))
            events[k].append(tick_evs)
            if t >= WARMUP:
                # a bucket whose tick has events slices its triples
                meters["slices"] += len(busy)
    meters["dispatches"] = DC.read()
    meters["new_keys"] = DC.new_keys() - DC.new_keys("aoi.tri_slice")
    meters["slice_keys"] = DC.new_keys("aoi.tri_slice")
    return events, meters


def _assert_parity(events, ref="cpu", label=""):
    for k, evs in events.items():
        if k == ref:
            continue
        for t, (a, b) in enumerate(zip(events[ref], evs)):
            for si, (sa, sb) in enumerate(zip(a, b)):
                for pa, pb in zip(sa, sb):
                    np.testing.assert_array_equal(
                        pa, pb, err_msg=f"{label}/{k} tick {t} space {si}")


def run_stacked():
    """Parity + the dispatch/recompile pins, cohort vs solo vs oracle."""
    # meter each device engine in its own drive (interleaving them in
    # one drive would mix their dispatch counts), each next to a FRESH
    # CPU oracle (an oracle reused across drives would carry state)
    def _pair(name, **ekw):
        engines = {
            "cpu": AOIEngine(default_backend="cpu"),
            name: AOIEngine(default_backend="tpu", fused=True, **ekw),
        }
        handles = {k: [e.create_space(c) for c in CAPS]
                   for k, e in engines.items()}
        return engines, handles

    eng_c, h_c = _pair("cohort", cohort="auto", cohort_ladder=(256,))
    assert len({h.bucket for h in h_c["cohort"]}) == 1, \
        "shard did not stack into one cohort bucket"
    eng_s, h_s = _pair("solo", cohort="solo")
    ev_c, m_c = _drive(eng_c, h_c)
    ev_s, m_s = _drive(eng_s, h_s)
    _assert_parity(ev_c, label="stacked")
    _assert_parity(ev_s, label="stacked")
    meas = TICKS - WARMUP
    # the step launches, without the harvests' eager triples slices
    disp_c = (m_c["dispatches"] - m_c["slices"]) / meas
    disp_s = (m_s["dispatches"] - m_s["slices"]) / meas
    print(f"  stacked     parity OK | dispatches/tick: "
          f"cohort={disp_c:g} solo={disp_s:g} "
          f"(ratio {disp_c / disp_s:.4f}) | new step keys after warmup: "
          f"cohort={m_c['new_keys']} solo={m_s['new_keys']}, new triples "
          f"slice keys: cohort={m_c['slice_keys']} "
          f"solo={m_s['slice_keys']}")
    assert disp_c == 1, f"cohort steady tick took {disp_c} dispatches"
    assert disp_s == N_SPACES, \
        f"solo baseline took {disp_s}, want {N_SPACES}"
    assert disp_c <= 0.05 * disp_s, "cohort ratio above the 0.05x bar"
    assert m_c["new_keys"] == 0 and m_s["new_keys"] == 0, \
        f"steady state recompiled: {m_c['new_keys']}/{m_s['new_keys']}"


def run_demotion():
    """The aoi.cohort seam: one fault on the shared dispatch demotes the
    WHOLE cohort to per-space solo buckets same-tick (bit-exact), and
    recohort() re-stacks every space."""
    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "cohort": AOIEngine(default_backend="tpu", cohort="auto",
                            cohort_ladder=(256,)),
    }
    handles = {k: [e.create_space(c) for c in CAPS]
               for k, e in engines.items()}
    coh = engines["cohort"]
    faults.install("aoi.cohort:fail@4")
    try:
        events, _m = _drive(engines, handles)
    finally:
        faults.clear()
    _assert_parity(events, label="demotion")
    demoted = coh.cohort_stats["cohort_demoted_spaces"]
    assert demoted == N_SPACES, \
        f"demotion covered {demoted}/{N_SPACES} spaces"
    assert not any(getattr(h.bucket, "cohort", False)
                   for h in handles["cohort"]), "cohort bucket survived"
    restacked = coh.recohort()
    assert restacked == N_SPACES, f"recohort moved {restacked}"
    assert len({h.bucket for h in handles["cohort"]}) == 1, \
        "recohort left stray buckets"
    events2, _m2 = _drive(engines, handles, ticks=2, seed=12)
    _assert_parity(events2, label="recohorted")
    print(f"  demotion    parity OK | demoted_spaces={demoted} "
          f"restacked={restacked} (forced aoi.cohort fail)")


def main():
    print("== multispace smoke: stacked cohort vs solo ==")
    run_stacked()
    print("== multispace smoke: fault demotion + recohort ==")
    run_demotion()
    print("multispace smoke OK")


if __name__ == "__main__":
    main()
