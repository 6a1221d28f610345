"""Deterministic fault injection + self-healing tick execution.

The contract under test (goworld_tpu/faults.py + docs/robustness.md):

* a ``FaultPlan`` fires at exact (seed, seam, occurrence) tuples -- the
  same plan replays the same faults in every run, including ``@auto``
  scheduling and the ``GW_FAULT_PLAN`` env activation;
* the TPU AOI buckets survive injected device OOM, kernel failure,
  poisoned control scalars and stalled fetches with BIT-IDENTICAL
  enter/leave streams vs an uninjected CPU oracle -- rebuilds, host
  ticks and calculator fallbacks are recorded in ``bucket.stats``;
* the network tier survives injected connection resets and partial
  writes: a reset flush keeps its batch salvageable, the dispatcher
  cluster reconnects with capped deterministic backoff and replays
  buffered traffic exactly once, in order;
* ``bench.py`` isolates per-config failures into parseable error
  records instead of voiding the whole artifact.

Seam coverage ledger (the fault-seam-coverage gwlint rule checks these
literals): aoi.grow, aoi.h2d, aoi.delta, aoi.kernel, aoi.scalars,
aoi.fetch, aoi.emit, conn.send, conn.flush, conn.recv, disp.connect,
bench.config, store.write, store.read, store.manifest.
"""

import json
import os
import pathlib
import socket
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from goworld_tpu import faults
from goworld_tpu.engine.aoi import AOIEngine

from test_aoi_delta import _assert_same, _drive, _pad, _scene, _sparse_step


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


# -- the plan itself ---------------------------------------------------------

def test_seam_catalog_stable():
    """The catalog is API: docs, gwlint and env strings name these."""
    assert set(faults.SEAMS) == {
        "aoi.grow", "aoi.h2d", "aoi.delta", "aoi.kernel", "aoi.scalars",
        "aoi.fetch", "aoi.emit", "aoi.device", "aoi.ingest",
        "aoi.interest", "aoi.cohort", "conn.send", "conn.flush", "conn.recv",
        "disp.connect", "bench.config", "store.write", "store.read",
        "store.manifest", "clu.lease", "clu.kill", "clu.zombie",
        "clu.restore"}
    assert set(faults.KINDS) == {
        "oom", "fail", "stall", "poison", "reset", "partial"}


def test_parse_grammar_roundtrip():
    plan = faults.parse("seed=7; aoi.h2d:oom@3; aoi.kernel:fail@5x2; "
                        "aoi.fetch:stall@4:0.01; conn.flush:reset@auto")
    assert plan.seed == 7
    by_seam = {s.seam: s for s in plan.specs}
    assert by_seam["aoi.h2d"].kind == "oom" and by_seam["aoi.h2d"].at == 3
    assert by_seam["aoi.kernel"].count == 2
    assert by_seam["aoi.fetch"].arg == 0.01
    auto = by_seam["conn.flush"]
    assert auto.at == faults.derive_occurrence(7, "conn.flush")
    assert 1 <= auto.at <= 8
    # stable across calls/processes: sha256, not random
    assert faults.derive_occurrence(7, "conn.flush") == auto.at
    assert faults.derive_occurrence(8, "conn.flush") != auto.at \
        or faults.derive_occurrence(8, "aoi.kernel") \
        != faults.derive_occurrence(7, "aoi.kernel")
    with pytest.raises(ValueError):
        faults.parse("not.a.seam:oom@1")
    with pytest.raises(ValueError):
        faults.parse("aoi.h2d:bogus@1")
    with pytest.raises(ValueError):
        faults.parse("aoi.h2d:oom")  # missing @at


def _fired_occurrences(text, seam, n=10):
    faults.install(text)
    hit = []
    for i in range(1, n + 1):
        try:
            faults.check(seam)
        except (faults.InjectedFault, ConnectionResetError):
            hit.append(i)
    faults.clear()
    return hit


def test_firing_is_deterministic():
    a = _fired_occurrences("aoi.h2d:oom@3", "aoi.h2d")
    b = _fired_occurrences("aoi.h2d:oom@3", "aoi.h2d")
    assert a == b == [3]
    assert _fired_occurrences("aoi.kernel:fail@5x2", "aoi.kernel") == [5, 6]
    # a plan records what it did
    faults.install("aoi.kernel:fail@1")
    with pytest.raises(faults.KernelFailure):
        faults.check("aoi.kernel")
    snap = faults.plan().snapshot()
    assert snap["fired"] == [{"seam": "aoi.kernel", "kind": "fail",
                              "occurrence": 1, "arg": None}]


def test_env_var_activates_plan():
    """GW_FAULT_PLAN is parsed at import in a fresh process."""
    code = ("import goworld_tpu.faults as f; "
            "p = f.plan(); "
            "assert p is not None and p.seed == 3, p; "
            "assert p.specs[0].seam == 'aoi.kernel'")
    env = dict(os.environ, GW_FAULT_PLAN="seed=3;aoi.kernel:fail@1")
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        cwd=str(pathlib.Path(__file__).resolve().parents[1]))
    assert r.returncode == 0, r.stderr.decode()


def test_oom_error_text_matches_real_classifier():
    """Injected OOM must be caught by the same message classifier that
    catches real jaxlib RESOURCE_EXHAUSTED errors."""
    from goworld_tpu.engine.aoi import _device_fault

    assert _device_fault(faults.DeviceOOM("aoi.h2d", 3))
    assert _device_fault(faults.KernelFailure("aoi.kernel", 5))
    assert not _device_fault(ValueError("logic bug"))


def test_runtime_installs_fault_plan():
    from goworld_tpu.engine.runtime import Runtime

    Runtime(aoi_backend="cpu", fault_plan="seed=9;aoi.kernel:fail@99")
    assert faults.active() and faults.plan().seed == 9


# -- engine: self-healing tick execution ------------------------------------

def _cpu_vs_tpu(cap=256, **tpu_kwargs):
    engines = {"cpu": AOIEngine(default_backend="cpu"),
               "tpu": AOIEngine(default_backend="tpu", **tpu_kwargs)}
    handles = {k: e.create_space(cap) for k, e in engines.items()}
    return engines, handles


def test_device_oom_and_kernel_failure_bitexact():
    """The acceptance scenario: device OOM at the 3rd upload + kernel
    failure at the 5th launch; the sparse walk's enter/leave stream stays
    bit-identical to the uninjected oracle, with the recovery recorded."""
    faults.install("seed=7;aoi.h2d:oom@3;aoi.kernel:fail@5")
    engines, handles = _cpu_vs_tpu()
    out, _ = _drive(engines, handles, 256, 8)
    _assert_same(out)
    st = handles["tpu"].bucket.stats
    assert st["rebuilds"] >= 1, st
    assert st["fallbacks"] >= 1, st
    assert st["host_ticks"] >= 1, st
    assert st["calc_level"] == 1, st  # one kernel fault: dense, not oracle
    assert faults.plan().fired, "plan must record the taken faults"


def test_kernel_fallback_chain_reaches_oracle():
    """Two consecutive kernel failures exhaust pallas -> dense and land on
    the CPU oracle; parity holds and the level is sticky."""
    faults.install("aoi.kernel:fail@2x2")
    engines, handles = _cpu_vs_tpu()
    out, _ = _drive(engines, handles, 256, 6)
    _assert_same(out)
    st = handles["tpu"].bucket.stats
    assert st["calc_level"] == 2, st
    assert st["fallbacks"] >= 2, st
    assert st["host_ticks"] >= 3, st  # oracle mode ticks on the host
    handles["tpu"].bucket.reset_calc_chain()
    assert handles["tpu"].bucket.stats["calc_level"] == 0


def test_pipelined_fault_parity_one_tick_late():
    """pipeline=True: recovery must preserve the one-tick-late cadence --
    the host-recovered tick is published at the next flush, exactly where
    the device tick would have landed."""
    faults.install("seed=5;aoi.kernel:fail@4")
    engines, handles = _cpu_vs_tpu(pipeline=True)
    out, _ = _drive(engines, handles, 256, 6)
    engines["tpu"].flush()  # trailing flush delivers the final tick
    out["tpu"].append(engines["tpu"].take_events(handles["tpu"]))
    assert len(out["tpu"][0][0]) == 0 and len(out["tpu"][0][1]) == 0
    _assert_same(out, shift=1, key="tpu")
    st = handles["tpu"].bucket.stats
    assert st["fallbacks"] >= 1 and st["host_ticks"] >= 1, st


def test_grow_oom_recovers():
    """OOM on the very first slot allocation: the bucket carries state on
    the host until a later flush rebuilds the device residency."""
    faults.install("aoi.grow:oom@1")
    engines, handles = _cpu_vs_tpu(cap=128)
    out, _ = _drive(engines, handles, 128, 4, n=60)
    _assert_same(out)
    st = handles["tpu"].bucket.stats
    assert st["rebuilds"] >= 1 or st["host_ticks"] >= 1, st


def test_delta_scatter_fault_recovers():
    faults.install("aoi.delta:oom@2")
    engines, handles = _cpu_vs_tpu()
    out, _ = _drive(engines, handles, 256, 6)
    _assert_same(out)
    assert handles["tpu"].bucket.stats["rebuilds"] >= 1


def test_poisoned_scalars_full_diff_recovery():
    """NaN/garbage control scalars must be caught by range validation and
    routed to the full-diff path -- same events, no cap growth from the
    poisoned values."""
    faults.install("aoi.scalars:poison@4")
    engines, handles = _cpu_vs_tpu()
    out, _ = _drive(engines, handles, 256, 8)
    _assert_same(out)
    st = handles["tpu"].bucket.stats
    assert st["poisoned"] >= 1, st
    assert st["calc_level"] == 0, st  # poison is not a kernel fault


def test_fetch_stall_is_transparent():
    """A stalled harvest delays, but changes no bytes."""
    faults.install("aoi.fetch:stall@2:0.001")
    engines, handles = _cpu_vs_tpu()
    out, _ = _drive(engines, handles, 256, 5)
    _assert_same(out)
    assert any(f["kind"] == "stall" for f in faults.plan().fired)


def test_harvest_phase_kernel_fault_bitexact():
    """aoi.fetch:fail -- the async-dispatch reality: a kernel error
    materializes at the harvest fetch, after dispatch() already returned
    (split-phase flush, docs/perf.md).  _recover_harvest regenerates the
    lost tick's events on the host bit-exactly and demotes the calc chain
    exactly like a launch-time failure."""
    faults.install("aoi.fetch:fail@3")
    engines, handles = _cpu_vs_tpu()
    out, _ = _drive(engines, handles, 256, 8)
    _assert_same(out)
    st = handles["tpu"].bucket.stats
    assert st["calc_level"] == 1, st
    assert st["rebuilds"] >= 1 and st["host_ticks"] >= 1, st


def test_harvest_phase_oom_keeps_calculator():
    """OOM at the harvest fetch is a memory fault, not a kernel bug: the
    bucket rebuilds device residency but stays on the pallas path."""
    faults.install("aoi.fetch:oom@3")
    engines, handles = _cpu_vs_tpu()
    out, _ = _drive(engines, handles, 256, 8)
    _assert_same(out)
    st = handles["tpu"].bucket.stats
    assert st["rebuilds"] >= 1, st
    assert st["calc_level"] == 0, st


def test_mesh_fault_parity():
    from goworld_tpu.parallel import SpaceMesh, multichip_devices

    devs = multichip_devices(8)
    if len(devs) < 8:
        pytest.skip("needs 8 (virtual) devices")
    faults.install("seed=7;aoi.h2d:oom@3;aoi.kernel:fail@5")
    engines = {
        "cpu": AOIEngine(default_backend="cpu"),
        "mesh": AOIEngine(default_backend="tpu", mesh=SpaceMesh(devs)),
    }
    handles = {k: e.create_space(256) for k, e in engines.items()}
    out, _ = _drive(engines, handles, 256, 8)
    _assert_same(out)
    st = handles["mesh"].bucket.stats
    assert st["rebuilds"] >= 1 and st["fallbacks"] >= 1, st


def test_rowshard_fault_parity():
    from goworld_tpu.parallel import SpaceMesh, multichip_devices

    devs = multichip_devices(8)
    if len(devs) < 8:
        pytest.skip("needs 8 (virtual) devices")
    faults.install("aoi.kernel:fail@2")
    cap, n, ticks = 2048, 300, 5
    eng = AOIEngine(default_backend="tpu", mesh=SpaceMesh(devs),
                    rowshard_min_capacity=2048)
    oracle = AOIEngine(default_backend="cpu")
    h, ho = eng.create_space(cap), oracle.create_space(cap)
    assert type(h.bucket).__name__ == "_RowShardTPUBucket"
    rng, xs, zs, rr, act = _scene(13, cap, n)
    for _t in range(ticks):
        _sparse_step(rng, xs, zs)
        for e, hh in ((eng, h), (oracle, ho)):
            e.submit(hh, _pad(xs, cap), _pad(zs, cap), _pad(rr, cap),
                     act.copy())
            e.flush()
        ee, el = eng.take_events(h)
        oe, ol = oracle.take_events(ho)
        np.testing.assert_array_equal(oe, ee, err_msg=f"enter tick {_t}")
        np.testing.assert_array_equal(ol, el, err_msg=f"leave tick {_t}")
    st = h.bucket.stats
    assert st["fallbacks"] >= 1 and st["host_ticks"] >= 1, st


# -- network tier ------------------------------------------------------------

def _pc_pair():
    from goworld_tpu.netutil.conn import PacketConnection

    a, b = socket.socketpair()
    return PacketConnection(a), b


def _packet(payload: bytes):
    from goworld_tpu.netutil.packet import Packet

    return Packet(bytearray(payload))


def test_conn_flush_reset_preserves_pending():
    """An injected reset fires BEFORE the batch pops: every queued payload
    stays salvageable for replay -- the exactly-once foundation."""
    faults.install("conn.flush:reset@1")
    pc, peer = _pc_pair()
    pc.send_packet(_packet(b"hello"))
    with pytest.raises(ConnectionResetError):
        pc.flush()
    assert pc.closed
    assert pc.take_pending() == [b"hello"]
    # the peer sees EOF, like a real dropped link
    peer.settimeout(2.0)
    assert peer.recv(64) == b""
    peer.close()


def test_conn_flush_on_closed_connection_keeps_batch():
    """Sends racing a dead link must not be popped into a doomed sendall."""
    pc, peer = _pc_pair()
    pc.close()
    pc.send_packet(_packet(b"raced"))
    with pytest.raises(ConnectionResetError):
        pc.flush()
    assert pc.take_pending() == [b"raced"]
    peer.close()


def test_conn_partial_write_drops_link_midframe():
    """``partial`` writes a prefix then cuts: the peer parses only the
    complete frames and then sees EOF -- its parser must not desync."""
    from goworld_tpu.netutil.conn import FrameParser

    faults.install("conn.flush:partial@1:0.5")
    pc, peer = _pc_pair()
    for i in range(3):
        pc.send_packet(_packet(b"x" * 40 + bytes([i])))
    with pytest.raises(ConnectionResetError):
        pc.flush()
    assert pc.closed
    peer.settimeout(2.0)
    chunks = []
    while True:
        data = peer.recv(65536)
        if not data:
            break
        chunks.append(data)
    pkts = FrameParser().feed(b"".join(chunks))
    assert len(pkts) < 3  # the cut really truncated the stream
    for p in pkts:
        assert p.payload[:-1] == b"x" * 40  # ...but whole frames survive
    peer.close()


def test_conn_recv_reset():
    faults.install("conn.recv:reset@1")
    pc, peer = _pc_pair()
    with pytest.raises(ConnectionResetError):
        pc.recv_packet()
    assert pc.closed
    peer.close()


def test_conn_send_reset_closes_link():
    from goworld_tpu.netutil.conn import PacketConnection
    from goworld_tpu.proto import GWConnection

    faults.install("conn.send:reset@1")
    a, b = socket.socketpair()
    gw = GWConnection(PacketConnection(a))
    with pytest.raises(ConnectionResetError):
        gw.send(_packet(b"p"))
    assert gw.pc.closed
    b.close()


# -- dispatcher cluster: backoff + replay ------------------------------------

class _Recorder:
    """A dispatcher stand-in: records every framed payload it receives."""

    def __init__(self):
        from goworld_tpu.netutil.conn import FrameParser, serve_tcp

        self.payloads: list[bytes] = []
        self.conn_count = 0
        self._stop = threading.Event()
        self._FrameParser = FrameParser
        self.ls = serve_tcp(("127.0.0.1", 0), self._on_conn,
                            stop_event=self._stop)
        self.addr = self.ls.getsockname()

    def _on_conn(self, sock, peer):
        self.conn_count += 1
        parser = self._FrameParser()
        while not self._stop.is_set():
            try:
                data = sock.recv(65536)
            except OSError:
                return
            if not data:
                return
            for p in parser.feed(data):
                self.payloads.append(p.payload)

    def close(self):
        self._stop.set()
        self.ls.close()


def _cluster(addrs, **kw):
    from goworld_tpu.dispatchercluster import DispatcherCluster

    kw.setdefault("backoff_base", 0.05)
    kw.setdefault("backoff_cap", 0.2)
    return DispatcherCluster(addrs, on_packet=lambda i, p: None,
                             register=lambda c: None, tag="test", **kw)


def test_backoff_deterministic_and_capped():
    c = _cluster([("127.0.0.1", 1)], backoff_base=0.5, backoff_cap=15.0)
    d1 = [c._backoff_delay(0, a) for a in range(1, 12)]
    d2 = [c._backoff_delay(0, a) for a in range(1, 12)]
    assert d1 == d2, "jitter must be deterministic"
    for a, d in enumerate(d1, 1):
        base = min(15.0, 0.5 * 2 ** (a - 1))
        assert 0.75 * base <= d < 1.25 * base, (a, d)
    # per-link jitter de-synchronizes reconnect storms
    assert c._backoff_delay(0, 5) != c._backoff_delay(1, 5)


def test_dispatcher_reconnect_replays_exactly_once():
    """A reset mid-stream: the cluster salvages the un-flushed batch,
    reconnects under backoff, and replays -- the dispatcher sees every
    packet exactly once, in order."""
    rec = _Recorder()
    faults.install("conn.flush:reset@3")
    c = _cluster([rec.addr]).start()
    try:
        assert c.wait_connected(5.0)
        sent = [b"pkt-%02d" % i for i in range(10)]
        for payload in sent:
            c.post(0, _packet(payload))
            c.flush_all()
            time.sleep(0.01)
        deadline = time.monotonic() + 10.0
        while len(rec.payloads) < len(sent) and time.monotonic() < deadline:
            c.flush_all()
            time.sleep(0.05)
        assert rec.payloads == sent, (rec.payloads, sent)
        assert rec.conn_count >= 2, "the reset must have forced a reconnect"
        st = c.status()[0]
        assert st["connected"] and st["replayed"] >= 1, st
        assert st["pending"] == 0 and st["dropped"] == 0, st
    finally:
        c.stop()
        rec.close()


def test_disp_connect_fault_then_recovery():
    rec = _Recorder()
    faults.install("disp.connect:reset@1x2")
    c = _cluster([rec.addr]).start()
    try:
        assert c.wait_connected(5.0)
        st = c.status()[0]
        assert st["connected"] and st["attempts"] == 0, st
        assert faults.plan().counts["disp.connect"] >= 3
    finally:
        c.stop()
        rec.close()


def test_wait_connected_respects_backoff():
    """With the next reconnect attempt far beyond the deadline,
    wait_connected gives up early instead of burning the whole timeout."""
    # a bound-but-never-listening port refuses connections immediately
    dead = socket.socket()
    dead.bind(("127.0.0.1", 0))
    addr = dead.getsockname()
    c = _cluster([addr], backoff_base=30.0, backoff_cap=60.0).start()
    try:
        t0 = time.monotonic()
        assert not c.wait_connected(5.0)
        assert time.monotonic() - t0 < 4.0, "should bail before the deadline"
        st = c.status()[0]
        assert not st["connected"] and st["attempts"] >= 1, st
        assert st["last_error"] is not None and st["backoff_s"] >= 22.5, st
    finally:
        c.stop()
        dead.close()


def test_post_buffers_while_down_and_drops_oldest():
    c = _cluster([("127.0.0.1", 1)], pending_cap=4)
    for i in range(6):
        assert not c.post(0, _packet(b"b%d" % i))
    st = c.status()[0]
    assert st["pending"] == 4 and st["dropped"] == 2, st
    assert list(c._pending[0]) == [b"b2", b"b3", b"b4", b"b5"]


# -- bench isolation ---------------------------------------------------------

def _fake_bench(monkeypatch, fail_name=None):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    import bench

    cfgs = [types.SimpleNamespace(name=n, headline=False)
            for n in ("a", "b", "c")]
    monkeypatch.setattr(bench, "config_matrix", lambda: cfgs)
    monkeypatch.setattr(bench, "CONFIGS", ["a", "b", "c"])
    monkeypatch.setattr(bench, "bench_sentinel",
                        lambda: {"metric": "sentinel"})

    def fake_run(cfg, companion=False, cpu_cached=None):
        if cfg.name == fail_name:
            raise MemoryError("RESOURCE_EXHAUSTED: out of device memory")
        return {"metric": "result", "config": cfg.name, "value": 1.0}

    monkeypatch.setattr(bench, "run_config", fake_run)
    return bench


def _bench_lines(capsys):
    out = capsys.readouterr().out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.strip()]
    return lines  # every stdout line must parse -- the artifact contract


def test_bench_one_config_oom_does_not_void_matrix(monkeypatch, capsys):
    bench = _fake_bench(monkeypatch, fail_name="b")
    bench.main()
    lines = _bench_lines(capsys)
    errs = [ln for ln in lines if ln.get("metric") == "error"]
    assert len(errs) == 1 and errs[0]["config"] == "b", errs
    assert errs[0]["rc"] == 1 and "RESOURCE_EXHAUSTED" in errs[0]["error"]
    ok = {ln["config"] for ln in lines if ln.get("metric") == "result"}
    assert ok == {"a", "c"}, "the other configs still produce real numbers"


def test_bench_config_fault_seam(monkeypatch, capsys):
    faults.install("bench.config:fail@2")
    bench = _fake_bench(monkeypatch)
    bench.main()
    lines = _bench_lines(capsys)
    errs = [ln for ln in lines if ln.get("metric") == "error"]
    assert len(errs) == 1 and errs[0]["config"] == "b", errs
    assert "injected kernel failure" in errs[0]["error"]
    ok = {ln["config"] for ln in lines if ln.get("metric") == "result"}
    assert ok == {"a", "c"}
