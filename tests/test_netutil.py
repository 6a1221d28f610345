"""Wire-layer tests (reference test model: engine/netutil/netutil_test.go's
in-process TCP echo + MsgPacker/compress roundtrips)."""

import os
import random
import threading

import pytest

from goworld_tpu.engine.ids import gen_id
from goworld_tpu.netutil import (
    FrameParser,
    JSONMsgPacker,
    MessagePackMsgPacker,
    Packet,
    PacketConnection,
    connect_tcp,
    new_compressor,
    serve_tcp,
)


def test_packet_typed_roundtrip():
    eid = gen_id()
    p = Packet.for_msgtype(42)
    p.append_u8(7)
    p.append_u32(123456)
    p.append_f32(1.5)
    p.append_bool(True)
    p.append_entity_id(eid)
    p.append_varstr("héllo")
    p.append_data({"k": [1, 2, {"n": None}]})
    p.append_args((1, "two", [3.0]))

    q = Packet(bytearray(p.payload))
    assert q.read_u16() == 42
    assert q.read_u8() == 7
    assert q.read_u32() == 123456
    assert q.read_f32() == 1.5
    assert q.read_bool() is True
    assert q.read_entity_id() == eid
    assert q.read_varstr() == "héllo"
    assert q.read_data() == {"k": [1, 2, {"n": None}]}
    assert q.read_args() == (1, "two", [3.0])
    assert q.remaining() == 0
    with pytest.raises(ValueError):
        q.read_u8()


def test_packet_bad_entity_id():
    p = Packet()
    with pytest.raises(ValueError):
        p.append_entity_id("short")


@pytest.mark.parametrize("fmt", ["none", "flate", "lzma", "lzw", "gwlz"])
def test_compressor_roundtrip(fmt):
    c = new_compressor(fmt)
    rng = random.Random(0)
    for _ in range(50):
        n = rng.randrange(0, 3000)
        data = bytes(rng.choices(range(8), k=n))
        assert c.decompress(c.compress(data)) == data


def test_gwlz_concurrent_first_use_never_falls_back(monkeypatch):
    """Connections opened by many threads at once (a bot swarm) while the
    native codec is still being built must all get gwlz: a thread that
    gave up while another built it fell back to flate, and its peer then
    read corrupt frames."""
    import sys
    import time

    from goworld_tpu.netutil import compress
    from goworld_tpu.utils import native

    build = native.build

    def slow_build(name):
        time.sleep(0.2)  # the window a concurrent make run holds open
        return build(name)

    monkeypatch.setattr(native, "build", slow_build)
    monkeypatch.setattr(compress, "_gwlz", None)
    monkeypatch.setattr(compress, "_gwlz_tried", False)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    got, start = [], threading.Barrier(16)

    def open_one():
        start.wait()
        got.append(compress.new_compressor("gwlz").name)

    try:
        threads = [threading.Thread(target=open_one) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == ["gwlz"] * 16


def test_lzw_hard_cases():
    # dictionary resets (incompressible data fills the 4096-entry table
    # fast), the KwKwK pattern, and width-boundary sizes
    c = new_compressor("lzw")
    rng = random.Random(1)
    for data in (
        bytes(rng.randrange(256) for _ in range(64 * 1024)),  # many resets
        b"ab" * 20000,                                         # KwKwK chains
        bytes(rng.choices(range(4), k=100000)),                # deep table
        b"",
        b"x",
    ):
        assert c.decompress(c.compress(data)) == data


def test_msgpackers():
    from goworld_tpu.netutil.msgpacker import PickleMsgPacker

    for packer in (MessagePackMsgPacker(), JSONMsgPacker(), PickleMsgPacker()):
        obj = {"a": 1, "b": [1.5, "x", None], "c": {"d": True}}
        assert packer.unpack(packer.pack(obj)) == obj
    # tuples become lists on the wire (documented)
    mp = MessagePackMsgPacker()
    assert mp.unpack(mp.pack((1, 2))) == [1, 2]


def test_frame_parser_handles_split_and_batched_frames():
    parser = FrameParser()
    import struct

    frames = bytearray()
    payloads = [os.urandom(10), os.urandom(700), b"", os.urandom(3)]
    comp = new_compressor("gwlz")
    for pl in payloads:
        if len(pl) >= 512:
            z = comp.compress(pl)
            frames += struct.pack("<I", len(z) | 0x80000000) + z
        else:
            frames += struct.pack("<I", len(pl)) + pl
    # feed in awkward chunk sizes
    got = []
    for i in range(0, len(frames), 7):
        got.extend(parser.feed(bytes(frames[i : i + 7])))
    assert [g.payload for g in got] == payloads


def test_tcp_echo_roundtrip_with_compression():
    """Echo server: every received packet is sent back verbatim."""
    stop = threading.Event()

    def on_conn(sock, peer):
        pc = PacketConnection(sock)
        while True:
            pkt = pc.recv_packet()
            if pkt is None:
                return
            pc.send_packet(pkt)
            pc.flush()

    ls = serve_tcp(("127.0.0.1", 0), on_conn, stop_event=stop)
    port = ls.getsockname()[1]
    try:
        pc = PacketConnection(connect_tcp(("127.0.0.1", port)))
        bigdata = {"arr": list(range(2000)), "s": "x" * 2000}
        for payload_obj in ({"small": 1}, bigdata):
            p = Packet.for_msgtype(7)
            p.append_data(payload_obj)
            pc.send_packet(p)
        pc.flush()  # both packets in one write; big one compressed
        r1 = pc.recv_packet()
        r2 = pc.recv_packet()
        assert r1.read_u16() == 7 and r1.read_data() == {"small": 1}
        assert r2.read_u16() == 7 and r2.read_data() == bigdata
        pc.close()
    finally:
        stop.set()
        ls.close()
