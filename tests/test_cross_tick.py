"""Cross-tick pipelined scheduler (``aoi_cross_tick`` / ``cross_tick``).

The contract under test (docs/perf.md cross-tick section):

* ``cross_tick=True`` defers event delivery by EXACTLY one tick -- tick
  T+1's pack + H2D + kernel enqueue overlaps tick T's harvest -- and the
  stream is bit-identical to the sequential baseline modulo that shift;
* it composes IDEMPOTENTLY with ``pipeline``: either flag, or both,
  produce the same single-shift stream (``_defer = pipeline or
  cross_tick``);
* the parity holds with the split-phase scheduler on or off and on both
  extraction formats (capped triples, and the classic encoded stream of
  ``emit="host"``);
* the row-sharded tier stays synchronous (cross_tick accepted, ignored)
  -- a single giant space keeps zero added latency;
* a fault during tick T's harvest while T+1 is already dispatched must
  not corrupt T+1's state: recovery rebuilds from the columnar host
  shadows and the net interest state converges to the oracle.
"""

import numpy as np
import pytest

from goworld_tpu import faults
from goworld_tpu.engine.aoi import AOIEngine

from test_aoi_delta import _pad, _scene, _sparse_step
from test_flush_sched import (CAPS, _assert_multi_same, _drain_trailing,
                              _drive_multi, _mesh_or_skip)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


def _engines(variants: dict, **common):
    """cpu oracle + one tpu engine per named kwargs dict."""
    engines = {"cpu": AOIEngine(default_backend="cpu")}
    for name, kw in variants.items():
        engines[name] = AOIEngine(default_backend="tpu", **common, **kw)
    handles = {k: [e.create_space(c) for c in CAPS]
               for k, e in engines.items()}
    return engines, handles


@pytest.mark.parametrize("flush_sched", [False, True])
@pytest.mark.parametrize("host_emit", [False, True])
def test_cross_tick_shifted_parity(flush_sched, host_emit):
    """cross_tick == sequential shifted exactly one tick, with the
    split-phase scheduler toggled both ways, on the triples path and on
    the classic encoded stream (``emit="host"``)."""
    engines, handles = _engines(
        {"xt": {"cross_tick": True}, "seq": {}},
        flush_sched=flush_sched, emit="host" if host_emit else "auto")
    out = _drive_multi(engines, handles, 8)
    assert all(len(e) == 0 and len(l) == 0 for e, l in out["xt"][0]), \
        "cross-tick tick 0 delivers nothing"
    _drain_trailing(engines, handles, out, ("xt",))
    _assert_multi_same(out, shift=0, keys=("seq",))
    _assert_multi_same(out, shift=1, keys=("xt",))


def test_cross_tick_pipeline_idempotent():
    """pipeline, cross_tick, and both defer by the same single tick: the
    three deferred streams are identical to each other and to the oracle
    shifted once."""
    engines, handles = _engines({
        "xt": {"cross_tick": True},
        "pipe": {"pipeline": True},
        "both": {"pipeline": True, "cross_tick": True},
    })
    out = _drive_multi(engines, handles, 8)
    _drain_trailing(engines, handles, out, ("xt", "pipe", "both"))
    _assert_multi_same(out, shift=1, keys=("xt", "pipe", "both"))
    for k in ("pipe", "both"):
        for t, (a, b) in enumerate(zip(out["xt"], out[k])):
            for (ae, al), (be, bl) in zip(a, b):
                np.testing.assert_array_equal(ae, be, err_msg=f"{k} tick {t}")
                np.testing.assert_array_equal(al, bl, err_msg=f"{k} tick {t}")


def test_cross_tick_mesh_parity():
    mesh = _mesh_or_skip()
    engines, handles = _engines({"xt": {"cross_tick": True}}, mesh=mesh)
    assert type(handles["xt"][0].bucket).__name__ == "_MeshTPUBucket"
    out = _drive_multi(engines, handles, 6)
    _drain_trailing(engines, handles, out, ("xt",))
    _assert_multi_same(out, shift=1, keys=("xt",))


def test_cross_tick_rowshard_stays_sync():
    """The row-sharded tier accepts cross_tick and ignores it (flush is
    synchronous): zero shift, bit-exact with the oracle."""
    mesh = _mesh_or_skip()
    cap = 2048
    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "xt": AOIEngine(default_backend="tpu", mesh=mesh,
                        rowshard_min_capacity=cap, cross_tick=True),
    }
    handles = {k: e.create_space(cap) for k, e in engines.items()}
    assert type(handles["xt"].bucket).__name__ == "_RowShardTPUBucket"
    rng, xs, zs, rr, act = _scene(13, cap, 300)
    for _t in range(4):
        _sparse_step(rng, xs, zs)
        ref = pair = None
        for k, e in engines.items():
            e.submit(handles[k], _pad(xs, cap), _pad(zs, cap),
                     _pad(rr, cap), act.copy())
            e.flush()
            ev = e.take_events(handles[k])
            if k == "cpu":
                ref = ev
            else:
                pair = ev
        np.testing.assert_array_equal(ref[0], pair[0])
        np.testing.assert_array_equal(ref[1], pair[1])


def test_cross_tick_harvest_fault_converges():
    """aoi.fetch:fail fires at tick T's harvest while T+1 is already
    dispatched (the cross-tick overlap window).  Recovery coalesces the
    faulted tick with the in-flight one from the columnar host shadows;
    the net interest words converge to the oracle's -- T+1's dispatched
    state is not corrupted."""
    faults.install("aoi.fetch:fail@4")
    engines, handles = _engines({"xt": {"cross_tick": True}})
    _drive_multi(engines, handles, 8)
    for k in ("cpu", "xt"):
        for h in handles[k]:
            h.bucket.drain()
    for si in range(len(CAPS)):
        ref = handles["cpu"][si].bucket.peek_words(handles["cpu"][si].slot)
        h = handles["xt"][si]
        np.testing.assert_array_equal(
            ref, h.bucket.peek_words(h.slot),
            err_msg=f"space {si} final interest words")
    st = [h.bucket.stats for h in handles["xt"]]
    assert sum(s["host_ticks"] for s in st) >= 1, st


def test_cross_tick_dispatch_fault_parity():
    """Dispatch-time faults (h2d OOM, kernel launch failure) under
    cross_tick recover to the oracle stream, still shifted exactly one
    tick -- the deferral cadence survives recovery."""
    faults.install("seed=7;aoi.h2d:oom@3;aoi.kernel:fail@5")
    engines, handles = _engines({"xt": {"cross_tick": True}})
    out = _drive_multi(engines, handles, 8)
    _drain_trailing(engines, handles, out, ("xt",))
    _assert_multi_same(out, shift=1, keys=("xt",))
    st = [h.bucket.stats for h in handles["xt"]]
    assert sum(s["rebuilds"] for s in st) >= 1, st
