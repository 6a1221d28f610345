#!/usr/bin/env python
"""CPU-only smoke test of the unified telemetry layer, end to end.

A ci.sh step (and a standalone sanity check): boot a Runtime with
telemetry on, tick a small scene, then validate the whole observability
surface the way an operator would use it -- scrape /debug/metrics
(Prometheus text), pull /debug/trace (Chrome trace-event JSON) and check
it is Perfetto-loadable, and confirm the engine phase spans that bench.py
aggregates into phase_ms are all present.  docs/observability.md
describes the surface under test.
"""

import json
import os
import sys
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from goworld_tpu import telemetry  # noqa: E402
from goworld_tpu.engine.entity import Entity  # noqa: E402
from goworld_tpu.engine.runtime import Runtime  # noqa: E402
from goworld_tpu.engine.space import Space  # noqa: E402
from goworld_tpu.engine.vector import Vector3  # noqa: E402
from goworld_tpu.telemetry import trace  # noqa: E402
from goworld_tpu.utils import binutil  # noqa: E402


class Scene(Space):
    pass


class Walker(Entity):
    use_aoi = True
    aoi_distance = 80.0


def main():
    n, ticks = 120, 6
    rt = Runtime(aoi_backend="tpu", telemetry_on=True)
    trace.reset()
    rt.entities.register(Scene)
    rt.entities.register(Walker)
    scene = rt.entities.create_space("Scene")
    scene.enable_aoi(80.0)

    rng = np.random.default_rng(11)
    walkers = [
        rt.entities.create("Walker", space=scene,
                           pos=Vector3(rng.uniform(0, 600), 0.0,
                                       rng.uniform(0, 600)))
        for _ in range(n)
    ]
    for _ in range(ticks):
        for w in walkers[:: 10]:
            p = w.position
            w.set_position(Vector3(p.x + float(rng.uniform(-15, 15)), 0.0,
                                   p.z + float(rng.uniform(-15, 15))))
        rt.tick()

    # 1. the engine phase spans bench.py turns into phase_ms are recorded
    # (the default triples emit path records aoi.decode; the classic
    # word-stream path records aoi.diff instead -- docs/observability.md)
    names = {nm for nm, _tid, _t0, _t1 in trace.spans()}
    for want in ("tick", "tick.aoi", "tick.sync", "sync.collect",
                 "aoi.flush", "aoi.stage", "aoi.kernel", "aoi.fetch",
                 "aoi.fanout", "aoi.emit"):
        assert want in names, f"span {want!r} missing from {sorted(names)}"
    assert "aoi.decode" in names or "aoi.diff" in names, \
        f"neither decode span present in {sorted(names)}"

    # 2. scrape the endpoints like Prometheus / Perfetto would
    srv = binutil.setup_http_server(0)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/metrics", timeout=5) as r:
            assert r.status == 200
            ctype = r.headers["Content-Type"]
            assert ctype.startswith("text/plain; version=0.0.4"), ctype
            text = r.read().decode()
        assert "gw_tick_seconds_count %d" % ticks in text, text[:400]
        assert "# TYPE gw_tick_seconds histogram" in text

        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/trace?ticks=3",
                timeout=5) as r:
            assert r.status == 200
            doc = json.loads(r.read())
    finally:
        srv.shutdown()

    # 3. the trace document is schema-valid Chrome trace-event JSON
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    marks = [e for e in evs if e["ph"] == "i"]
    assert len(marks) == 3, "?ticks=3 must window to 3 tick marks"
    assert xs, "no spans in the windowed trace"
    for e in xs:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0 and "tid" in e

    telemetry.disable()
    print("telemetry smoke: OK -- %d spans, %d trace events, %d byte scrape"
          % (len(names), len(evs), len(text)))


if __name__ == "__main__":
    main()
