"""Unified telemetry: the observability layer never changes what it observes.

The contract under test (docs/observability.md):

* **bit-exactness** -- the enter/leave event stream of a sparse walk is
  byte-identical with telemetry enabled and disabled, on every device tier
  (single-chip, mesh, row-sharded), because spans read clocks and counters
  only;
* **disabled path** -- every instrument is a no-op (``t()`` returns 0.0,
  ``span`` is a shared singleton, counters don't move);
* **trace export** -- spans land in a bounded ring and export as Chrome
  trace-event JSON (Perfetto-loadable): "X" spans nest, "i" tick marks,
  ``last_ticks`` windows, timestamps ride the injected clock;
* **exposition** -- the registry renders Prometheus text 0.0.4 (cumulative
  pow2 buckets, ``_total`` counters, sorted labels) and stays exact under
  concurrent mutation;
* **agreement** -- ``opmon.dump()`` and the registry collector render the
  same numbers, and the canonical name catalog in docs/observability.md
  covers every name production code can emit (the ``telemetry`` gwlint
  rule enforces the converse).
"""

from __future__ import annotations

import json
import logging
import os
import re
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from goworld_tpu import telemetry
from goworld_tpu.engine.aoi import AOIEngine
from goworld_tpu.telemetry import trace
from goworld_tpu.telemetry.metrics import (HIST_BOUNDS, Registry, Sample,
                                           bucket_index)
from goworld_tpu.utils import gwlog, opmon
from test_aoi_delta import _assert_same, _drive

REPO = Path(__file__).resolve().parents[1]

# every metric, span, and op name production code can emit with a literal
# (docs/observability.md catalog; the gwlint `telemetry` rule pins
# code->docs/tests, this list pins docs->tests)
CANONICAL_NAMES = (
    # runtime tick phases + the whole-tick histogram
    "tick", "tick.seconds", "tick.timers", "tick.aoi", "tick.sync",
    "tick.post",
    # the sync phase's spans and record counter, the served game's packing
    "sync.columns", "sync.collect", "sync.records", "game.sync_pack",
    # AOI engine phase spans + engine gauges
    "aoi.flush", "aoi.emit", "aoi.h2d", "aoi.stage", "aoi.kernel",
    "aoi.fetch", "aoi.wait", "aoi.diff", "aoi.decode", "aoi.fanout",
    "aoi.host_tick", "aoi.buckets", "aoi.calc_level", "aoi.emit_path",
    "aoi.launches", "aoi.tri_chunks",
    # live migration / chip-loss failover (engine/placement.py): start
    # spans, per-flush cover/swap + evacuation spans, totals
    "aoi.migrate", "aoi.migrate.snapshot", "aoi.migrate.replay",
    "aoi.migrate.cover", "aoi.migrate.swap", "aoi.evacuate",
    "aoi.migrations", "aoi.evacuations", "aoi.migration_rollbacks",
    "aoi.migration_ms",
    # opmon op names (components + net + storage)
    "conn.flush", "gate.client_pkt", "game.outbox", "disp.route",
    "storage.op",
    # dispatchercluster link samples
    "disp.connected", "disp.attempts", "disp.backoff_s",
    "disp.next_retry_in", "disp.pending", "disp.replayed", "disp.dropped",
    # fault-injection samples
    "faults.active", "faults.occurrences", "faults.fired",
    # opmon bridge samples
    "opmon.count", "opmon.total_seconds", "opmon.peak_seconds",
    "opmon.p50_seconds", "opmon.p99_seconds",
)


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    """Telemetry state is process-global; never leak it across tests --
    neither the switch nor what the registry's counters and gauges
    counted while a test had it on (other files read their absolute
    values)."""
    from goworld_tpu.telemetry.metrics import Counter, Gauge

    reg = telemetry.registry()
    saved = {nm: m.value for nm, m in list(reg._metrics.items())
             if isinstance(m, (Counter, Gauge))}
    yield
    telemetry.disable()
    for nm, m in list(reg._metrics.items()):
        if isinstance(m, (Counter, Gauge)):
            m.value = saved.get(nm, 0.0)


# -- bit-exact parity: telemetry on vs off, per device tier ------------------


def _walk(cap=256, ticks=8, n=180, **tpu_kwargs):
    engines = {"cpu": AOIEngine(default_backend="cpu"),
               "tpu": AOIEngine(default_backend="tpu", **tpu_kwargs)}
    handles = {k: e.create_space(cap) for k, e in engines.items()}
    out, _ = _drive(engines, handles, cap, ticks, n=n)
    _assert_same(out)
    return out


def _assert_on_off_identical(off, on):
    assert len(off["tpu"]) == len(on["tpu"])
    for t, (oe, ol) in enumerate(off["tpu"]):
        ne, nl = on["tpu"][t]
        np.testing.assert_array_equal(oe, ne, err_msg=f"enter tick {t}")
        np.testing.assert_array_equal(ol, nl, err_msg=f"leave tick {t}")


def _traced_walk(**kw):
    """Run the walk with tracing live; return (events, span-name set)."""
    telemetry.enable()
    trace.reset()
    try:
        on = _walk(**kw)
        names = {nm for nm, _tid, _t0, _t1 in trace.spans()}
    finally:
        telemetry.disable()
    return on, names


def test_single_chip_parity_on_vs_off():
    """The acceptance criterion: the same sparse walk with telemetry off
    and on yields byte-identical event streams, and the traced run
    recorded the per-phase engine spans."""
    off = _walk()
    on, names = _traced_walk()
    _assert_on_off_identical(off, on)
    # the single-chip default is the triples path: its harvest segments
    # are aoi.fetch (with the aoi.wait inside), aoi.decode (mirror upkeep)
    # and aoi.fanout, not aoi.diff; no bucket-level aoi.emit is left
    assert {"aoi.stage", "aoi.kernel", "aoi.fetch", "aoi.wait",
            "aoi.decode", "aoi.fanout"} <= names, names
    assert "aoi.emit" not in names and "aoi.fused" not in names, names


def _mesh_devices():
    from goworld_tpu.parallel import multichip_devices

    devs = multichip_devices(8)
    if len(devs) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return devs


def test_mesh_parity_on_vs_off():
    from goworld_tpu.parallel import SpaceMesh

    devs = _mesh_devices()
    off = _walk(mesh=SpaceMesh(devs))
    on, names = _traced_walk(mesh=SpaceMesh(devs))
    _assert_on_off_identical(off, on)
    assert {"aoi.stage", "aoi.kernel", "aoi.fetch", "aoi.diff",
            "aoi.fanout"} <= names, names


def test_rowshard_parity_on_vs_off():
    from goworld_tpu.parallel import SpaceMesh

    devs = _mesh_devices()
    kw = dict(cap=2048, ticks=5, n=300, rowshard_min_capacity=2048)
    off = _walk(mesh=SpaceMesh(devs), **kw)
    on, names = _traced_walk(mesh=SpaceMesh(devs), **kw)
    _assert_on_off_identical(off, on)
    assert {"aoi.stage", "aoi.kernel", "aoi.fetch", "aoi.diff",
            "aoi.fanout"} <= names, names


# -- disabled path -----------------------------------------------------------


def test_disabled_instruments_are_noops():
    telemetry.disable()
    assert trace.t() == 0.0
    assert trace.lap("tick", 0.0) == 0.0
    # span() hands out the shared no-op singleton, not a fresh object
    assert trace.span("tick.aoi") is trace.span("tick.sync")
    assert trace.spans() == []
    reg = Registry(enabled=False)
    c = reg.counter("aoi.h2d_bytes")
    c.inc(5)
    g = reg.gauge("aoi.buckets")
    g.set(3)
    h = reg.histogram("tick.seconds")
    h.observe(1.0)
    assert (c.value, g.value, h.count) == (0.0, 0.0, 0)


def test_gw_telemetry_env_enables_at_import():
    code = ("from goworld_tpu import telemetry\n"
            "from goworld_tpu.telemetry import trace\n"
            "print(telemetry.enabled(), trace.enabled())\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("GW_TELEMETRY", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["False", "False"]
    r = subprocess.run([sys.executable, "-c", code],
                       env={**env, "GW_TELEMETRY": "1"}, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["True", "True"]


# -- trace export ------------------------------------------------------------


class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_chrome_trace_schema_nesting_and_windowing():
    clk = _Clock()
    telemetry.enable(clock=clk)
    trace.reset()
    for n in (1, 2):
        clk.advance(1.0)
        trace.mark_tick(n)
        t0 = trace.t()
        with trace.span("tick.aoi"):
            clk.advance(0.002)
        trace.lap("tick", t0)
    doc = trace.export_chrome_trace()

    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "goworld_tpu"
    xs = [e for e in evs if e["ph"] == "X"]
    marks = [e for e in evs if e["ph"] == "i"]
    assert [e["name"] for e in marks] == ["tick 1", "tick 2"]
    assert all(e["pid"] == os.getpid() for e in xs)
    assert all(e["tid"] == threading.get_ident() for e in xs)
    # microseconds relative to the oldest stamp (the first tick mark)
    aoi1, tick1 = xs[0], xs[1]
    assert (aoi1["name"], tick1["name"]) == ("tick.aoi", "tick")
    assert aoi1["ts"] == pytest.approx(0.0)
    assert aoi1["dur"] == pytest.approx(2000.0)
    aoi2 = xs[2]
    assert aoi2["ts"] == pytest.approx(1.002e6)
    # spans nest: each tick.aoi interval lies inside its tick span
    for aoi, tick in ((xs[0], xs[1]), (xs[2], xs[3])):
        assert tick["ts"] <= aoi["ts"]
        assert aoi["ts"] + aoi["dur"] <= tick["ts"] + tick["dur"]

    # ?ticks=1 windows to the spans of the most recent tick
    win = trace.export_chrome_trace(last_ticks=1)
    wx = [e for e in win["traceEvents"] if e["ph"] == "X"]
    wm = [e for e in win["traceEvents"] if e["ph"] == "i"]
    assert [e["name"] for e in wm] == ["tick 2"]
    assert [e["name"] for e in wx] == ["tick.aoi", "tick"]


def test_trace_ring_is_bounded():
    telemetry.enable(ring=4)
    trace.reset()
    for _ in range(10):
        trace.lap("tick", trace.t())
    assert len(trace.spans()) == 4


def test_chrome_trace_file_export(tmp_path):
    telemetry.enable(clock=_Clock())
    trace.reset()
    with trace.span("tick.aoi"):
        pass
    path = tmp_path / "trace.json"
    doc = trace.export_chrome_trace(path=str(path))
    assert json.loads(path.read_text()) == doc
    assert doc["displayTimeUnit"] == "ms"


def test_runtime_tick_records_spans_on_injected_clock():
    """Runtime(telemetry_on=True) routes span stamps through its ``now``
    seam: span durations are exactly what the fake clock says, and the
    whole-tick histogram observes them."""
    from goworld_tpu.engine.runtime import Runtime

    clk = _Clock()
    hist = telemetry.registry().histogram("tick.seconds")
    count0 = hist.count
    rt = Runtime(now=clk, telemetry_on=True)
    trace.reset()
    rt.tick()
    spans = {nm: (t0, t1) for nm, _tid, t0, t1 in trace.spans()}
    assert {"tick", "tick.timers", "tick.aoi", "tick.sync",
            "tick.post"} <= set(spans)
    t0, t1 = spans["tick"]
    assert (t0, t1) == (clk.t, clk.t)  # fake clock never advanced
    assert hist.count == count0 + 1


# -- segments: one clock read per boundary ----------------------------------


class _CountingClock(_Clock):
    """A fake clock that counts its reads and steps 1 ms per read."""

    def __init__(self):
        super().__init__()
        self.reads = 0

    def __call__(self):
        self.reads += 1
        self.t += 0.001
        return self.t


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter/exit."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))
        return False


def test_segments_share_one_stamp_per_boundary():
    """Each boundary reads the clock once; that stamp ends one segment's
    sum and span and starts the next; ``None`` sums without a span."""
    clk = _CountingClock()
    telemetry.enable(clock=clk)
    trace.reset()
    sink = {"a_s": 0.0, "b_s": 0.0}
    with trace.Segments(sink) as seg:
        seg.begin("aoi.stage", "a_s")
        seg.begin("aoi.kernel", "b_s")
        seg.begin(None, "b_s")
    assert clk.reads == 4
    spans = [(nm, t0, t1) for nm, _tid, t0, t1 in trace.spans()]
    assert [nm for nm, *_ in spans] == ["aoi.stage", "aoi.kernel"]
    assert spans[0][2] == spans[1][1]  # one stamp at the boundary
    assert sink["a_s"] == pytest.approx(0.001)
    assert sink["b_s"] == pytest.approx(0.002)
    assert trace.current_span() is None


def test_segments_feed_their_sums_while_tracing_is_off():
    telemetry.disable()
    sink = {"fetch_s": 0.0}
    with trace.Segments(sink) as seg:
        seg.begin("aoi.fetch", "fetch_s")
    assert sink["fetch_s"] > 0.0
    assert trace.spans() == []


def _annotations_balanced(names):
    """Every converted span opened and closed one annotation each, in
    nesting order, and the ring holds one span per annotation."""
    log = _FakeAnnotation.log
    depth = []
    for kind, nm in log:
        if kind == "enter":
            depth.append(nm)
        else:
            assert depth and depth[-1] == nm, log
            depth.pop()
    assert depth == [], depth
    ring = [nm for nm, *_ in trace.spans()]
    for nm in names:
        opened = sum(1 for k, n in log if k == "enter" and n == nm)
        assert opened == ring.count(nm) > 0, (nm, opened, ring.count(nm))


def test_converted_spans_open_one_annotation_each_even_on_faults():
    """With a fake annotation factory, the single-chip bucket's segments
    (aoi.stage/kernel/fetch/decode/fanout) and aoi.wait each open and
    close exactly one annotation per span, also on the tick whose
    ``aoi.fetch`` faults inside the fetch segment (recovered bit-exactly
    from the host copies), and nothing is left open."""
    from goworld_tpu import faults

    converted = ("aoi.stage", "aoi.kernel", "aoi.fetch", "aoi.wait",
                 "aoi.decode", "aoi.fanout")
    off = _walk(ticks=6)
    tr = trace.enable()
    telemetry.enable()
    tr.annot_factory = _FakeAnnotation
    _FakeAnnotation.log = []
    trace.reset()
    faults.install("aoi.fetch:fail@4")
    try:
        on = _walk(ticks=6)
        fired = [f["seam"] for f in faults.plan().snapshot()["fired"]]
    finally:
        faults.clear()
    _assert_on_off_identical(off, on)
    assert fired == ["aoi.fetch"], fired
    _annotations_balanced(converted)
    assert trace.current_span() is None


def _small_shard(seed):
    """The benchmark cell's path at a CPU size: players through gate
    batches and MovementIngest, NPCs through Space.move_entities."""
    from benchmark.harness import Shard

    cfg = {"spaces": 2, "entities_per_space": 240, "capacity": 256,
           "world": 500.0, "radius": 100.0, "spread": {"kind": "uniform"},
           "engine": {"aoi_backend": "tpu"}}
    mix = {"player_share": 1 / 3, "gates": 4, "move_share": 1.0,
           "step": 5.0, "watchers_per_space": 0}
    shard = Shard(cfg, mix, seed)
    shard.tick(moving=False)
    shard.tick()
    return shard


def _inside(spans, inner, outer):
    """Every ``inner`` span lies within some ``outer`` span."""
    outs = [(a, b) for nm, a, b in spans if nm == outer]
    ins = [(a, b) for nm, a, b in spans if nm == inner]
    assert ins, inner
    return all(any(oa <= a and b <= ob for oa, ob in outs) for a, b in ins)


def test_sync_and_harvest_spans_nest_and_count():
    """On the benchmark cell's path: sync.columns and sync.collect nest in
    tick.sync, aoi.wait in aoi.fetch in aoi.harvest, the inner fan-out is
    aoi.fanout (every aoi.emit is the runtime's, outside aoi.flush),
    ``sync.records`` counts exactly the records drained, and
    ``aoi.launches`` moves with ``ops.dispatch_count``."""
    import time

    from goworld_tpu.ops import dispatch_count as DC

    shard = _small_shard(2**31 + 11)
    reg = telemetry.registry()
    records, launches = reg.counter("sync.records"), \
        reg.counter("aoi.launches")
    telemetry.enable(clock=time.perf_counter)
    trace.reset()
    r0, l0, d0 = records.value, launches.value, DC.read()
    drained = sum(shard.tick().records for _ in range(3))
    assert records.value - r0 == drained > 0
    assert launches.value - l0 == DC.read() - d0 > 0
    spans = [(nm, a, b) for nm, _tid, a, b in trace.spans()]
    assert _inside(spans, "sync.columns", "tick.sync")
    assert _inside(spans, "sync.collect", "tick.sync")
    assert _inside(spans, "aoi.wait", "aoi.fetch")
    assert _inside(spans, "aoi.fetch", "aoi.harvest")
    assert _inside(spans, "aoi.fanout", "aoi.harvest")
    flushes = [(a, b) for nm, a, b in spans if nm == "aoi.flush"]
    assert not any(fa <= a and b <= fb for nm, a, b in spans
                   if nm == "aoi.emit" for fa, fb in flushes)


def test_tri_chunks_counts_dirty_chunks_of_the_tick():
    """``aoi.tri_chunks`` moves, over one tri-mode tick of known moves, by
    the nonzero 128-word chunks of that tick's change grid (the XOR of
    the bucket's interest words before and after it)."""
    from goworld_tpu.ops import events as EV

    cap, n = 256, 180
    rng = np.random.default_rng(3)
    eng = AOIEngine(default_backend="tpu", emit="vector")
    hs = [eng.create_space(cap) for _ in range(2)]
    act = np.zeros(cap, bool)
    act[:n] = True
    r = np.full(cap, 80.0, np.float32)
    pos = [rng.uniform(0, 600, (2, cap)).astype(np.float32) for _ in hs]
    for h, (x, z) in zip(hs, pos):
        eng.submit(h, x, z, r, act)
    eng.flush()
    b = hs[0].bucket
    assert b._emit == "vector" and b._scratch_key(2)[1] == -1  # triples
    before = np.array(b.prev, copy=True)
    chunks = telemetry.registry().counter("aoi.tri_chunks")
    telemetry.enable()
    c0 = chunks.value
    for h, (x, z) in zip(hs, pos):
        eng.submit(h, x + rng.uniform(-15, 15, cap).astype(np.float32),
                   z + rng.uniform(-15, 15, cap).astype(np.float32), r, act)
    eng.flush()
    slots = [h.slot for h in hs]
    chg = (np.asarray(b.prev) ^ before)[slots]
    want = np.count_nonzero(chg.reshape(-1, EV.CHUNK_WORDS).any(axis=1))
    assert 0 < want < chg.size // EV.CHUNK_WORDS
    assert chunks.value - c0 == want


def test_streams_and_sync_records_identical_traced_and_untraced():
    """Two shards on one seed, one traced (spans, counters and profiler
    annotations through a fake factory), one not: the same enter/leave
    pairs and the same sync records, tick by tick (records compared as a
    multiset: the dirty set's walk order follows object identity)."""
    seed = 2**31 + 29
    runs = []
    for traced in (False, True):
        shard = _small_shard(seed)
        if traced:
            tr = trace.enable()
            telemetry.enable()
            tr.annot_factory = _FakeAnnotation
        else:
            telemetry.disable()
        runs.append([shard.tick(keep=True) for _ in range(3)])
        telemetry.disable()
    for off, on in zip(*runs):
        assert sorted(off.sync) == sorted(on.sync) and len(off.sync) > 0
        for (oe, ol), (ne, nl) in zip(off.events, on.events):
            np.testing.assert_array_equal(oe, ne)
            np.testing.assert_array_equal(ol, nl)


def test_game_sync_pack_span():
    """GameService._send_position_syncs packs per gate inside the
    game.sync_pack span."""
    import types

    from goworld_tpu.components.game.service import GameService
    from goworld_tpu.engine.sync import SyncBatch

    recs = SyncBatch.from_records(
        [("c%015d" % i, i % 2, "e%015d" % i, 1.0, 0.0, 2.0, 0.5)
         for i in range(4)])
    stub = types.SimpleNamespace(
        rt=types.SimpleNamespace(drain_sync=lambda: recs),
        cluster=types.SimpleNamespace(by_gate=lambda gate_id: None))
    telemetry.enable()
    trace.reset()
    GameService._send_position_syncs(stub)
    assert [nm for nm, *_ in trace.spans()] == ["game.sync_pack"]


def test_benchmark_readers_of_the_new_metrics():
    """The per-layer readers of benchmark/metrics, from a run.Context with
    synthetic spans and a primed registry: the span entries give ms per
    tick, the counter readers divide the window's counts."""
    from benchmark import run

    mdir = str(REPO / "benchmark" / "metrics")
    spans = [("tick.sync", 0.0, 1.0), ("sync.columns", 0.0, 0.05),
             ("sync.collect", 0.05, 0.8), ("aoi.wait", 2.0, 2.6),
             ("sync.collect", 3.0, 3.7)]
    ctx = run.Context(spans, 2, None, 0)

    def read(name):
        return run.per_layer_reader(mdir, name)(ctx)

    assert read("sync_columns_ms") == pytest.approx(25.0)
    assert read("sync_collect_ms") == pytest.approx(725.0)
    assert read("aoi_wait_ms") == pytest.approx(300.0)
    reg = telemetry.registry()
    counters = {nm: reg.counter(nm) for nm in ("sync.records",
                                                "aoi.launches")}
    saved = {nm: c.value for nm, c in counters.items()}
    try:
        counters["sync.records"].value = 1_450_000.0
        counters["aoi.launches"].value = 6.0
        assert read("sync_us_per_record") == pytest.approx(1.0)
        assert read("aoi_launches_per_tick") == pytest.approx(3.0)
        # a program without the counters (or a window that counted
        # nothing) reads as no metric, never as a fault
        counters["sync.records"].value = 0.0
        counters["aoi.launches"].value = 0.0
        assert read("sync_us_per_record") is None
        assert read("aoi_launches_per_tick") is None
    finally:
        for nm, c in counters.items():
            c.value = saved[nm]


# -- metrics registry --------------------------------------------------------


def test_bucket_index_pow2_boundaries():
    assert bucket_index(0.0) == 0
    assert bucket_index(HIST_BOUNDS[0]) == 0
    for i, b in enumerate(HIST_BOUNDS):
        assert bucket_index(b) == i, b  # bounds are inclusive upper edges
        if i:
            assert bucket_index(b * 0.75) == i, b
    assert bucket_index(HIST_BOUNDS[-1] * 2) == len(HIST_BOUNDS)


def test_prometheus_text_format():
    reg = Registry(enabled=True)
    reg.counter("aoi.h2d_bytes", "bytes shipped").inc(512)
    reg.gauge("aoi.buckets").set(2)
    h = reg.histogram("tick.seconds", "tick wall time")
    for v in (1.5e-6, 0.25, 100.0):  # one per region: low, mid, overflow
        h.observe(v)
    reg.register_collector(lambda: [
        Sample("disp.pending", "gauge", 3.0, {"tag": "game1", "disp": "0"}),
        Sample("disp.replayed", "counter", 7.0, {"disp": "0"}),
    ])
    text = reg.render_prometheus()
    assert text.endswith("\n")
    lines = text.splitlines()

    assert "# TYPE gw_aoi_h2d_bytes_total counter" in lines
    assert "gw_aoi_h2d_bytes_total 512" in lines
    assert "# TYPE gw_aoi_buckets gauge" in lines
    assert "gw_aoi_buckets 2" in lines

    # histogram: one line per pow2 bound plus +Inf, cumulative counts
    bucket_lines = [ln for ln in lines
                    if ln.startswith("gw_tick_seconds_bucket")]
    assert len(bucket_lines) == len(HIST_BOUNDS) + 1
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in bucket_lines]
    assert counts == sorted(counts) and counts[-1] == 3
    assert bucket_lines[-1].startswith('gw_tick_seconds_bucket{le="+Inf"}')
    assert "gw_tick_seconds_count 3" in lines
    assert any(ln.startswith("gw_tick_seconds_sum ") for ln in lines)

    # collector samples: sorted labels, counters suffixed _total
    assert 'gw_disp_pending{disp="0",tag="game1"} 3' in lines
    assert 'gw_disp_replayed_total{disp="0"} 7' in lines


def test_registry_rejects_kind_conflicts():
    reg = Registry(enabled=True)
    reg.counter("aoi.h2d_bytes")
    with pytest.raises(TypeError):
        reg.gauge("aoi.h2d_bytes")
    # same-kind re-registration returns the same instrument
    assert reg.counter("aoi.h2d_bytes") is reg.counter("aoi.h2d_bytes")


def test_registry_thread_safety():
    reg = Registry(enabled=True)
    c = reg.counter("aoi.h2d_bytes")
    h = reg.histogram("tick.seconds")
    n_threads, n_iter = 8, 2000

    def work():
        for _ in range(n_iter):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iter
    assert c.value == total
    assert h.count == total
    assert sum(h.snapshot()["buckets"]) == total


def test_weak_collectors_die_with_their_owner():
    class Owner:
        def collect(self):
            return [Sample("aoi.buckets", "gauge", 1.0)]

    reg = Registry(enabled=True)
    o = Owner()
    reg.register_collector(o.collect, weak=True)
    assert reg.snapshot().get("aoi.buckets") == 1.0
    del o
    assert "aoi.buckets" not in reg.snapshot()


# -- opmon bridge ------------------------------------------------------------


def test_opmon_quantiles_and_registry_agreement():
    """/debug/opmon and /debug/metrics render the same _stats dict: the
    dump's p50/p99 are exactly the registry collector's, scaled to ms."""
    opmon.reset()
    for _ in range(20):
        with opmon.Operation("storage.op"):
            pass
    d = opmon.dump()["storage.op"]
    assert d["count"] == 20
    assert 0 < d["p50_ms"] <= d["p99_ms"] <= d["max_ms"] * 64  # pow2-coarse
    snap = telemetry.snapshot()
    assert snap['opmon.count{op="storage.op"}'] == 20
    assert snap['opmon.p50_seconds{op="storage.op"}'] * 1e3 == d["p50_ms"]
    assert snap['opmon.p99_seconds{op="storage.op"}'] * 1e3 == d["p99_ms"]
    assert snap['opmon.peak_seconds{op="storage.op"}'] * 1e3 == d["max_ms"]


def test_opmon_operations_land_in_trace_ring():
    telemetry.enable()
    trace.reset()
    with opmon.Operation("game.outbox"):
        pass
    assert "game.outbox" in [nm for nm, *_ in trace.spans()]


def test_faults_collector_reports_plan_state():
    from goworld_tpu import faults

    faults.clear()
    snap = telemetry.snapshot()
    assert snap["faults.active"] == 0.0
    faults.install("seed=3;conn.flush:reset@1")
    try:
        with pytest.raises(ConnectionResetError):
            faults.check("conn.flush")
        snap = telemetry.snapshot()
        assert snap["faults.active"] == 1.0
        assert snap['faults.occurrences{seam="conn.flush"}'] == 1.0
        assert snap['faults.fired{seam="conn.flush"}'] == 1.0
    finally:
        faults.clear()


def test_dispatchercluster_status_in_registry():
    from goworld_tpu.dispatchercluster import DispatcherCluster

    # two dispatcher addrs, maintain threads never started: both links
    # report down through the registry under per-cluster labels
    dc = DispatcherCluster([("127.0.0.1", 1), ("127.0.0.1", 2)],
                           on_packet=lambda i, pkt: None,
                           register=lambda conn: None, tag="game1")
    cid = dc._telemetry_id
    snap = telemetry.snapshot()
    for i in range(2):
        key = ('disp.connected{cluster="%d",disp="%d",tag="game1"}'
               % (cid, i))
        assert snap[key] == 0.0
    dc.stop()


def test_dispatchercluster_dropped_counter_in_registry():
    """Overflowing the outage buffer surfaces in the labeled registry
    counter, not just status(): drop-oldest is counted, never silent."""
    from goworld_tpu.dispatchercluster import DispatcherCluster
    from goworld_tpu.netutil.packet import Packet

    dc = DispatcherCluster([("127.0.0.1", 1)],
                           on_packet=lambda i, pkt: None,
                           register=lambda conn: None, tag="game1",
                           pending_cap=4)
    try:
        for i in range(7):  # link down: all buffer; 3 past cap drop oldest
            assert not dc.post(0, Packet(bytearray(b"p%d" % i)))
        snap = telemetry.snapshot()
        lbl = 'cluster="%d",disp="0",tag="game1"' % dc._telemetry_id
        assert snap["disp.dropped{%s}" % lbl] == 3.0
        assert snap["disp.pending{%s}" % lbl] == 4.0
    finally:
        dc.stop()


# -- structured logs ---------------------------------------------------------


def test_gwlog_json_lines_keeps_ready_tag(tmp_path):
    logf = tmp_path / "game.log"
    gwlog.setup("info", str(logf), json_lines=True)
    try:
        gwlog.announce_ready("game1", "game")
    finally:
        gwlog.setup("info")
    line = logf.read_text().strip().splitlines()[-1]
    rec = json.loads(line)
    assert sorted(rec) == ["component", "level", "msg", "ts"]
    assert rec["component"] == "gw.game1"
    assert rec["level"] == "INFO"
    # the supervisor start barrier still greps the raw line
    assert gwlog.READY_TAG in rec["msg"] and gwlog.READY_TAG in line


def test_gwlog_json_env_gate(tmp_path, monkeypatch):
    monkeypatch.setenv("GW_LOG_JSON", "1")
    logf = tmp_path / "env.log"
    gwlog.setup("info", str(logf))  # json_lines=None -> GW_LOG_JSON
    try:
        logging.getLogger("gw.gate1").info("hello")
    finally:
        gwlog.setup("info")
    rec = json.loads(logf.read_text().strip().splitlines()[-1])
    assert (rec["component"], rec["msg"]) == ("gw.gate1", "hello")


# -- the name catalog --------------------------------------------------------


def test_canonical_names_are_documented():
    """docs/observability.md lists every canonical name with dotted-word
    precision (matching the gwlint `telemetry` rule's notion of
    'documented'): 'tick' may not ride on 'tick.seconds'."""
    docs = (REPO / "docs" / "observability.md").read_text()
    missing = [nm for nm in CANONICAL_NAMES
               if not re.search(r"(?<![\w.])" + re.escape(nm) + r"(?![\w.])",
                                docs)]
    assert missing == [], missing
