"""PR 19 observability: cluster causal tracing, the flight recorder, the
federated metric view, and the bench regression gate.

Pins the wire-trailer contract (``tracectx.TRACE_WIRE`` +
``TRACE_WIRE_VERSION``: structural detection, magic confirm, version-gated
interpretation), the black-box triggers (``clu.*`` faults, the
``GW_TICK_BUDGET_MS`` SLO budget, the ``GW_FLIGHT_INTERVAL_S`` heartbeat
that survives SIGKILL, the ``GW_FLIGHT_DIR`` override), the dispatcher's
``clu.metric_sources`` federation, the always-on ``accelerator_absent``
gauge, the ``trace.hops`` / ``flight.dumps`` counters and the ``wire.hop``
merged-trace slices, and ``scripts/bench_gate.py`` in both directions
(real history passes, a synthetic regression fails).
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import struct

import pytest

from goworld_tpu import config, telemetry
from goworld_tpu.netutil.packet import Packet
from goworld_tpu.telemetry import flight, tracectx


@pytest.fixture
def clean_telemetry():
    telemetry.disable()
    tracectx.reset()
    flight.reset()
    yield
    telemetry.disable()
    tracectx.reset()
    flight.reset()


@pytest.fixture
def flight_dir(tmp_path, monkeypatch):
    """Point the recorder at a fresh dir for one test (the module keeps
    process-global first-dir-wins state)."""
    d = tmp_path / "flight"
    monkeypatch.setattr(flight, "_dir", str(d))
    monkeypatch.setattr(flight, "_component", "t1")
    flight.reset()
    yield str(d)
    flight.reset()


def _records_packet(n_records: int) -> Packet:
    p = Packet()
    p.append_bytes(b"\x00" * (32 * n_records))
    return p


# -- trace-context trailer ---------------------------------------------------


def test_trace_trailer_round_trip(clean_telemetry):
    p = _records_packet(3)
    tracectx.stamp(p, 0xABC, hop=0)
    assert p.remaining() % 32 == tracectx.TRACE_WIRE_SIZE % 32
    ctx = tracectx.try_strip(p)
    assert ctx is not None
    assert (ctx.trace_id, ctx.hop, ctx.version) == (0xABC, 0, 1)
    assert ctx.send_ns >= ctx.origin_ns > 0
    # trailer fully removed: the flat record body is intact
    assert p.remaining() == 96 and p.remaining() % 32 == 0


def test_trace_trailer_absent_leaves_packet_untouched(clean_telemetry):
    p = _records_packet(2)
    before = bytes(p.buf)
    assert tracectx.try_strip(p) is None
    assert bytes(p.buf) == before


def test_trace_trailer_bad_magic_not_stripped(clean_telemetry):
    p = _records_packet(1)
    p.append_bytes(tracectx.TRACE_WIRE.pack(1, 2, 3, 0,
                                            tracectx.TRACE_WIRE_VERSION,
                                            0xDEAD))
    before = bytes(p.buf)
    assert tracectx.try_strip(p) is None
    assert bytes(p.buf) == before


def test_trace_trailer_future_version_stripped_not_interpreted(
        clean_telemetry):
    """A newer TRACE_WIRE_VERSION is structurally removed (record parsing
    must survive a rolling restart) but its fields are never consumed --
    the versioned-consumption half of the gwlint telemetry wire rule."""
    p = _records_packet(2)
    p.append_bytes(tracectx.TRACE_WIRE.pack(
        7, 1, 2, 0, tracectx.TRACE_WIRE_VERSION + 1,
        tracectx.TRACE_WIRE_MAGIC))
    assert tracectx.try_strip(p) is None
    assert p.remaining() == 64  # stripped anyway


def test_trace_trailer_is_28_bytes_forever():
    # the structural detection (rem % stride == 28 % stride) depends on it
    assert tracectx.TRACE_WIRE_SIZE == 28
    assert tracectx.TRACE_WIRE.size == struct.calcsize("<QQQBBH")


def test_record_hop_feeds_ring_counter_and_log_context(clean_telemetry):
    telemetry.enable()
    p = _records_packet(1)
    tracectx.stamp(p, 0x55AA, hop=1)
    ctx = tracectx.try_strip(p)
    lat = tracectx.record_hop(ctx, "game.ingest")
    assert lat >= 0
    assert telemetry.snapshot().get("trace.hops", 0) >= 1
    # the thread-local id GW_LOG_JSON lines join on
    assert tracectx.current_trace_id() == "%016x" % 0x55AA
    hops = tracectx.wire_hops_by_trace()["%016x" % 0x55AA]
    assert hops[0]["where"] == "game.ingest" and hops[0]["hop"] == 1


def test_merge_traces_builds_async_rows_with_wire_hop_slices(
        clean_telemetry):
    telemetry.enable()
    for hop, where in ((0, "dispatcher.sync"), (1, "game.ingest")):
        p = _records_packet(1)
        tracectx.stamp(p, 0xF00D, hop=hop)
        tracectx.record_hop(tracectx.try_strip(p), where)
    doc = {"wireHops": tracectx.wire_hops_by_trace()}
    merged = tracectx.merge_traces([doc])
    evs = merged["traceEvents"]
    aid = "0x" + "%016x" % 0xF00D
    assert any(e["ph"] == "b" and e.get("id") == aid for e in evs)
    assert any(e["ph"] == "e" and e.get("id") == aid for e in evs)
    xs = [e for e in evs if e["ph"] == "X" and e["name"] == "wire.hop"]
    assert len(xs) == 2
    assert {e["args"]["where"] for e in xs} == {"dispatcher.sync",
                                                "game.ingest"}


# -- flight recorder ---------------------------------------------------------


def test_flight_clu_fault_triggers_dump(clean_telemetry, flight_dir):
    telemetry.enable()  # so flight.dumps counts the write
    flight.note_fault({"seam": "clu.lease", "kind": "stall"})
    dumps = glob.glob(os.path.join(flight_dir, "flight_t1_*fault_clu*"))
    assert dumps, os.listdir(flight_dir) if os.path.isdir(flight_dir) else []
    doc = flight.load(dumps[0])
    assert doc["component"] == "t1"
    assert any(f.get("seam") == "clu.lease" for f in doc["faults"])
    assert doc["reason"] == "fault:clu.lease"
    # the latest-pointer follows the newest dump
    latest = flight.load(os.path.join(flight_dir, "flight_t1_latest.json"))
    assert latest["reason"] == doc["reason"]
    assert telemetry.snapshot().get("flight.dumps", 0) >= 1


def test_flight_non_clu_fault_recorded_without_dump(clean_telemetry,
                                                    flight_dir):
    flight.note_fault({"seam": "aoi.kernel", "kind": "error"})
    assert not glob.glob(os.path.join(flight_dir, "flight_t1_0*"))
    assert any(f.get("seam") == "aoi.kernel"
               for f in flight.state()["faults"])


def test_flight_dump_renders_as_chrome_trace(clean_telemetry, flight_dir):
    flight.note("failover", game=2)
    flight.note_packet("rx", 60, 128)
    path = flight.dump("unit")
    chrome = flight.to_chrome(flight.load(path))
    cats = {e.get("cat") for e in chrome["traceEvents"]}
    assert "note" in cats and "pkt" in cats
    assert chrome["displayTimeUnit"] == "ms"


def test_flight_slo_breach_dumps_on_tick_budget(clean_telemetry, flight_dir,
                                                monkeypatch):
    """GW_TICK_BUDGET_MS is the SLO seam: a tick over budget trips
    Runtime.tick -> flight.slo_breach -> an slo:* dump."""
    from goworld_tpu.engine import runtime as rt_mod

    monkeypatch.setenv("GW_TICK_BUDGET_MS", "0.000001")
    monkeypatch.setattr(rt_mod, "_TICK_BUDGET_MS", 0.000001)
    rt = rt_mod.Runtime(aoi_backend="cpu")
    rt.tick()
    dumps = glob.glob(os.path.join(flight_dir, "flight_t1_*slo_tick*"))
    assert dumps
    doc = flight.load(dumps[0])
    assert any(n.get("kind") == "slo.tick_budget" for n in doc["notes"])


def test_flight_no_dir_costs_nothing(clean_telemetry, monkeypatch):
    monkeypatch.setattr(flight, "_dir", None)
    flight.note_fault({"seam": "clu.kill", "kind": "error"})
    assert flight.dump("unit") is None


def test_gwlog_json_carries_span_and_trace_id(clean_telemetry, tmp_path):
    """Satellite 1: a GW_LOG_JSON line emitted inside an open span, after
    a wire hop, joins on the same keys as /debug/trace -- and neither key
    leaks once tracing is reset/disabled."""
    import json as _json
    import logging

    from goworld_tpu.telemetry import trace
    from goworld_tpu.utils import gwlog

    telemetry.enable()
    p = _records_packet(1)
    tracectx.stamp(p, 0xBEEF, hop=0)
    tracectx.record_hop(tracectx.try_strip(p), "game.ingest")
    logf = tmp_path / "t.log"
    gwlog.setup("info", str(logf), json_lines=True)
    try:
        with trace.span("tick.aoi"):
            logging.getLogger("gw.game1").info("inside")
        logging.getLogger("gw.game1").info("outside")
    finally:
        gwlog.setup("info")
    inside, outside = [
        _json.loads(ln) for ln in logf.read_text().strip().splitlines()]
    assert inside["span"] == "tick.aoi"
    assert inside["trace_id"] == "%016x" % 0xBEEF
    assert "span" not in outside  # no open span on this thread
    assert outside["trace_id"] == "%016x" % 0xBEEF
    # reset + disable: the join keys must vanish, not linger
    tracectx.reset()
    assert tracectx.current_trace_id() is None


# -- federated metrics + accelerator gauge -----------------------------------


def test_dispatcher_federates_component_snapshots(clean_telemetry):
    """clu.metric_sources counts reporting components; every numeric key
    of a stored snapshot re-emits labeled by component -- one dispatcher
    scrape reads the whole cluster."""
    from goworld_tpu.components.dispatcher.service import DispatcherService

    cfg = config.loads(
        "[deployment]\ndispatchers = 1\ngames = 1\ngates = 1\n"
        "[dispatcher1]\nhost = 127.0.0.1\nport = 0\n")
    ds = DispatcherService(1, cfg)
    ds._store_metrics("game1", {"tick.count": 5.0, "junk": "str"})
    ds._store_metrics("gate1", {"net.packets_sent": 7})
    samples = ds._telemetry_collect()
    by_name = {}
    for s in samples:
        by_name.setdefault(s.name, []).append(s)
    assert by_name["clu.metric_sources"][0].value == 2.0
    [tick] = by_name["tick.count"]
    assert tick.labels["component"] == "game1" and tick.value == 5.0
    assert all(s.name != "junk" for s in samples)


def test_accelerator_absent_gauge_always_on(clean_telemetry):
    """The gauge scrapes truthfully even with telemetry disabled, and on
    the CPU-pinned test backend it must read absent."""
    assert telemetry.accelerator_absent() is True  # JAX_PLATFORMS=cpu
    assert telemetry.snapshot().get("accelerator_absent") == 1.0
    assert "gw_accelerator_absent" in telemetry.render_prometheus()


# -- bench regression gate ---------------------------------------------------


def _load_bench_gate():
    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "bench_gate.py")
    spec = importlib.util.spec_from_file_location("bench_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_record(d, run, rows):
    tail = "\n".join(json.dumps(r) for r in rows)
    with open(os.path.join(d, "BENCH_r%02d.json" % run), "w") as fh:
        json.dump({"n": run, "cmd": "bench", "rc": 0, "tail": tail}, fh)


def test_bench_gate_passes_green_history(tmp_path):
    """Swings inside each config's pinned threshold, a recovery that got
    faster and a chip-less record beside the accelerated series: a green
    history -- the gate must not cry wolf."""
    bg = _load_bench_gate()
    eng = {"config": "engine", "metric": "moves_per_s", "value": 100.0,
           "unit": "moves/s", "n_entities": 512}
    uni = {**eng, "config": "uniform", "n_entities": 65536}
    rec = {"config": "engine_restart", "metric": "ticks_to_recover",
           "value": 4.0, "unit": "ticks", "rate_kind": "recovery",
           "n_entities": 64}
    _write_record(str(tmp_path), 1, [eng, uni, rec])
    _write_record(str(tmp_path), 2, [{**eng, "value": 82.0},
                                     {**uni, "value": 93.0},
                                     {**rec, "value": 2.0},
                                     {**uni, "value": 1.0,
                                      "accelerator_absent": True}])
    pattern = os.path.join(str(tmp_path), "BENCH_r*.json")
    assert bg.main(["--records", pattern]) == 0


def test_bench_gate_fails_synthetic_regression(tmp_path, capsys):
    bg = _load_bench_gate()
    row = {"config": "engine", "metric": "moves_per_s", "value": 100.0,
           "unit": "moves/s", "n_entities": 512}
    _write_record(str(tmp_path), 1, [row])
    _write_record(str(tmp_path), 2, [{**row, "value": 40.0}])
    pattern = os.path.join(str(tmp_path), "BENCH_r*.json")
    assert bg.main(["--records", pattern]) == 1
    assert "REGRESSION engine/moves_per_s" in capsys.readouterr().out


def test_bench_gate_ignores_historical_dips_and_buckets_conditions(tmp_path):
    bg = _load_bench_gate()
    row = {"config": "engine", "metric": "moves_per_s", "value": 100.0,
           "unit": "moves/s", "n_entities": 512}
    # r1 -> r2 halves (historical dip), r2 -> r3 recovers: only the
    # latest comparison gates
    _write_record(str(tmp_path), 1, [row])
    _write_record(str(tmp_path), 2, [{**row, "value": 50.0}])
    _write_record(str(tmp_path), 3, [{**row, "value": 49.0},
                                     # condition change: never compared
                                     # against the unflagged series
                                     {**row, "value": 5.0,
                                      "accelerator_absent": True}])
    pattern = os.path.join(str(tmp_path), "BENCH_r*.json")
    assert bg.main(["--records", pattern]) == 0


def test_bench_gate_recovery_metrics_are_lower_is_better(tmp_path):
    bg = _load_bench_gate()
    row = {"config": "engine_restart", "metric": "ticks_to_recover",
           "value": 3.0, "unit": "ticks", "rate_kind": "recovery",
           "n_entities": 64}
    _write_record(str(tmp_path), 1, [row])
    _write_record(str(tmp_path), 2, [{**row, "value": 30.0}])
    pattern = os.path.join(str(tmp_path), "BENCH_r*.json")
    assert bg.main(["--records", pattern]) == 1


# -- end to end: SIGKILL a worker, read its black box ------------------------


def test_host_failover_kill9_leaves_flight_dump(tmp_path, clean_telemetry):
    """Satellite of the PR 18 drill: run the kill -9 failover scenario
    with the flight recorder's heartbeat on (GW_FLIGHT_INTERVAL_S via
    worker_env); the SIGKILLed game1 cannot trap anything, so its latest
    heartbeat dump IS the post-mortem.  Failover forensics ride along:
    the survivor still loses nothing, and the dispatcher (in-process
    here, telemetry on) serves the failover counters plus the workers'
    piggybacked snapshots in its federated exposition."""
    from goworld_tpu.engine.failover import host_failover_scenario

    telemetry.enable()
    fdir = str(tmp_path / "flight")
    res = host_failover_scenario(
        str(tmp_path), cap=16, ticks=24, kill_at=12, pace_s=0.005,
        lease_ttl_s=2.0,
        worker_env={"GW_FLIGHT_DIR": fdir, "GW_FLIGHT_INTERVAL_S": "0.1",
                    "GW_TELEMETRY": "1"})
    assert res["events_lost"] == 0, res
    assert res["parity_ok"] and res["survivor_space_ok"], res
    assert res["clu_stats"]["failovers"] >= 1
    dumps = glob.glob(os.path.join(fdir, "flight_game1_*.json"))
    assert dumps, "SIGKILLed worker left no flight dump"
    doc = flight.load(os.path.join(fdir, "flight_game1_latest.json"))
    assert doc["component"] == "game1"
    assert doc["reason"] == "interval"  # the heartbeat, not a trap
    chrome = flight.to_chrome(doc)
    assert chrome["traceEvents"], "empty post-mortem"
    # the federated /debug/metrics body the dispatcher would serve: its
    # own failover counters + the lease-renew piggybacked worker series
    prom = telemetry.render_prometheus()
    assert "gw_clu_failovers" in prom
    assert 'component="game' in prom, "no piggybacked worker snapshot"
