#!/usr/bin/env python
"""Proof that the served AOI path runs on a TPU, through the normal entry
points, and agrees with the C++ calculator.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # one host with four chips

One chip, in this order (a chip belongs to one process at a time, so this
process stays off JAX until the cluster's game has released it):

1. build: ``make -C native``, so every native library comes from the
   committed sources;
2. cluster: 1 dispatcher, 1 game with ``aoi_backend = tpu`` and 1 gate,
   started by ``goworld_tpu.cli``, serving ``examples/unity_demo`` to
   strict bots; the game's ``/debug/metrics`` must show the accelerator
   and AOI calculator level 0;
3. engine: ``Runtime(aoi_backend="tpu", aoi_pipeline=True)`` at BASELINE's
   "8 Spaces x 10k entities, uniform density" against
   ``Runtime(aoi_backend="cpp")`` on the same walk: the enter/leave
   streams must be bit-identical (one tick later on the pipelined side)
   and no fallback may have fired.

Four chips: the space-sharded mesh (16 spaces x 10k, 4 per chip) and the
row-sharded Zipf hotspot (BASELINE's 100k entities, 90% in 1% of the map),
in one ``Runtime(aoi_mesh=4)``, against the C++ calculator, with state on
all four chips.

Every phase either passes or ends the run with a nonzero exit.  The last
line of a passing run is one JSON object naming the device.  Times printed
on the way are host-clock information, not device metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
# run directory of the cluster phase (gitignored, like all run output)
RUN_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# BASELINE "8 Spaces x 10k entities, uniform density", with bench.py's
# headline world and AOI radius
UNIFORM = dict(n_per=10_000, cap=16384, world=4000.0)
# BASELINE "Zipfian crowd hotspot: 100k entities, 90% in 1% of map", with
# bench.py's zipf100k world; its capacity is above the row-shard threshold
HOTSPOT = dict(n_per=100_000, cap=131072, world=60000.0)
RADIUS = 100.0
STEP = 5.0          # max move per axis per tick
BULK_TICKS = 8      # movement through Space.move_entities
BOTS, BOT_SECONDS = 100, 20.0
SEED = 0

# bucket stats that count a fallback away from the compiled kernel.
# decode_overflow also counts the designed growth of the adaptive event
# caps: the mass-enter tick overflows the starting caps, is recovered
# exactly from the full diff and grows them.  A pipelined bucket has
# already dispatched the next tick at the old caps when it harvests the
# mass enter, so that tick may overflow too.  No later tick may.
FALLBACK_STATS = ("calc_level", "fallbacks", "rebuilds", "host_ticks",
                  "poisoned", "decode_overflow")


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def say(msg):
    print(msg, flush=True)


# -- 1. build ---------------------------------------------------------------


def build_native():
    t0 = time.perf_counter()
    r = subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                       capture_output=True, text=True)
    check(r.returncode == 0, f"native build failed:\n{r.stderr[-2000:]}")
    say(f"build: native libraries up to date "
        f"({time.perf_counter() - t0:.2f} s)")


# -- 2. cluster -------------------------------------------------------------


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _cli(*args, timeout=300):
    return subprocess.run([sys.executable, "-m", "goworld_tpu.cli", *args],
                          cwd=REPO, env=_child_env(), capture_output=True,
                          text=True, timeout=timeout)


def _exited(pid):
    """Gone, or a zombie: either way it holds no device any more."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


def cluster_phase():
    """A served cluster whose one game computes AOI on the chip."""
    from goworld_tpu.cli import _parse_prometheus

    os.makedirs(RUN_DIR, exist_ok=True)
    run = os.path.join(RUN_DIR, "run")
    disp, gate, http = _free_port(), _free_port(), _free_port()
    ini = os.path.join(RUN_DIR, "goworld.ini")
    with open(ini, "w") as f:
        f.write(f"""[deployment]
dispatchers = 1
games = 1
gates = 1

[dispatcher1]
host = 127.0.0.1
port = {disp}

[game_common]
boot_entity = Player
aoi_backend = tpu
aoi_pipeline = true
telemetry = true
position_sync_interval_ms = 100

[game1]
http_port = {http}

[gate1]
host = 127.0.0.1
port = {gate}

[storage]
backend = filesystem
directory = entity_storage

[kvdb]
backend = filesystem
directory = kvdb
""")
    script = os.path.join(REPO, "examples", "unity_demo", "server.py")
    t0 = time.perf_counter()
    r = _cli("start", "-c", ini, "-s", script, "-d", run)
    try:
        check(r.returncode == 0,
              f"cluster start failed (rc {r.returncode}):\n"
              f"{r.stdout[-2000:]}{r.stderr[-3000:]}")
        say(f"cluster: {r.stdout.strip()} "
            f"({time.perf_counter() - t0:.1f} s to ready)")
        bots = subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", "test_client.py"),
             "--gate", f"127.0.0.1:{gate}", "-N", str(BOTS), "--strict",
             "--duration", str(BOT_SECONDS)],
            cwd=REPO, env=_child_env(), capture_output=True, text=True,
            timeout=BOT_SECONDS + 240)
        ok_line = next((ln for ln in bots.stdout.splitlines()
                        if ln.endswith("bots OK")), "")
        say(f"cluster: {ok_line or 'no verdict line'}")
        check(bots.returncode == 0 and ok_line == f"{BOTS}/{BOTS} bots OK",
              f"strict bots failed (rc {bots.returncode}):\n"
              f"{bots.stdout[-1500:]}{bots.stderr[-1500:]}")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http}/debug/metrics", timeout=30) as resp:
            samples = _parse_prometheus(resp.read().decode())
        absent = [v for n, _l, v in samples if n == "gw_accelerator_absent"]
        levels = [v for n, _l, v in samples if n == "gw_aoi_calc_level"]
        buckets = sum(v for n, _l, v in samples if n == "gw_aoi_buckets")
        say(f"cluster: game /debug/metrics accelerator_absent={absent} "
            f"aoi_calc_level={levels} aoi_buckets={buckets}")
        check(absent == [0.0], "the game reports no accelerator")
        check(levels and all(v == 0 for v in levels),
              "the game's AOI calculator fell back from the kernel")
        check(buckets >= 1, "the game ran no AOI bucket")
    finally:
        pids = []
        if os.path.isdir(run):
            pids = [int(open(os.path.join(run, fn)).read())
                    for fn in os.listdir(run) if fn.endswith(".pid")]
        stop = _cli("stop", "-d", run)
        say(f"cluster: {stop.stdout.strip() or 'stop rc %d' % stop.returncode}")
        # anything that ignored SIGTERM still holds the chip
        _cli("kill", "-d", run)
        deadline = time.monotonic() + 30
        while not all(map(_exited, pids)) and time.monotonic() < deadline:
            time.sleep(0.1)


# -- 3. engine --------------------------------------------------------------


def device_identity(chips):
    import jax

    devs = jax.devices()
    d = devs[0]
    say(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    check(d.platform == "tpu",
          f"JAX found no TPU: its default platform is {d.platform!r}")
    check(len(devs) >= chips, f"need {chips} chips, JAX has {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _initial(n, world, zipf, rng):
    if not zipf:
        return rng.uniform(0, world, (2, n)).astype(np.float32)
    # 90% inside the central 1%-area (10%-linear) hot zone (bench.py)
    hot = rng.random(n) < 0.9
    lo, hi = 0.45 * world, 0.55 * world
    return np.where(hot, rng.uniform(lo, hi, (2, n)),
                    rng.uniform(0, world, (2, n))).astype(np.float32)


def make_walk(spaces, seed):
    """Per space: initial [2, n] positions and [ticks, 2, n] positions
    after each movement tick (the last one driven per entity)."""
    rng = np.random.default_rng(seed)
    out = []
    for sp in spaces:
        pos = _initial(sp["n_per"], sp["world"], sp.get("zipf", False), rng)
        steps = rng.uniform(-STEP, STEP,
                            (BULK_TICKS + 1, 2, sp["n_per"])).astype(
                                np.float32)
        path = np.clip(pos[None] + np.cumsum(steps, axis=0), 0,
                       np.float32(sp["world"])).astype(np.float32)
        out.append((pos, path))
    return out


def run_walk(spaces, walk, **runtime_kw):
    """Drive one Runtime through the walk.  Returns per-tick lists of
    per-space (enter, leave) pair arrays, per-tick host wall seconds
    (ending in block_until_ready on the device state), per-tick sums of
    the buckets' fallback stats, the runtime and its spaces."""
    from goworld_tpu.engine.entity import Entity
    from goworld_tpu.engine.runtime import Runtime
    from goworld_tpu.engine.space import Space
    from goworld_tpu.engine.vector import Vector3

    class SmokeScene(Space):
        pass

    class SmokeMob(Entity):
        use_aoi = True
        aoi_distance = RADIUS

    class SmokeWatcher(SmokeMob):
        # one consumer per space keeps it subscribed to the event stream
        def on_enter_aoi(self, other):
            pass

    rt = Runtime(**runtime_kw)
    for cls in (SmokeScene, SmokeMob, SmokeWatcher):
        rt.entities.register(cls)
    caught = {}
    take = rt.aoi.take_events

    def recording_take(h):
        ev = take(h)
        caught[id(h)] = (ev[0].copy(), ev[1].copy())
        return ev

    rt.aoi.take_events = recording_take
    scenes, ents = [], []
    for sp, (pos, _path) in zip(spaces, walk):
        scene = rt.entities.create_space("SmokeScene", kind=1)
        scene.enable_aoi(RADIUS, capacity=sp["cap"])
        es = [rt.entities.create(
            "SmokeWatcher" if i == 0 else "SmokeMob", space=scene,
            pos=Vector3(float(pos[0, i]), 0.0, float(pos[1, i])))
            for i in range(sp["n_per"])]
        scenes.append(scene)
        ents.append(es)
    slots = [np.array([e.aoi_slot for e in es], np.int64) for es in ents]

    def device_state():
        return [b.prev for b in rt.aoi._buckets.values()
                if hasattr(b, "prev")]

    ticks, walls, stats = [], [], []

    def tick():
        caught.clear()
        t0 = time.perf_counter()
        rt.tick()
        state = device_state()
        if state:
            import jax

            jax.block_until_ready(state)
        walls.append(time.perf_counter() - t0)
        stats.append({k: sum(getattr(b, "stats", {}).get(k, 0)
                             for b in rt.aoi._buckets.values())
                      for k in FALLBACK_STATS})
        empty = np.empty((0, 2), np.int32)
        ticks.append([caught.get(id(s._aoi_handle), (empty, empty))
                      for s in scenes])

    tick()  # mass enter
    for t in range(BULK_TICKS):
        for scene, sl, (_pos, path) in zip(scenes, slots, walk):
            scene.move_entities(sl, path[t, 0], path[t, 1])
        tick()
    for es, (_pos, path) in zip(ents, walk):
        last = path[BULK_TICKS]
        for i, e in enumerate(es):
            e.set_position(Vector3(float(last[0, i]), 0.0, float(last[1, i])))
    tick()
    tick()  # no movement: drains a pipelined flush
    return ticks, walls, stats, rt, scenes


def compare_streams(dev, ref, shifts):
    """Bit-exact parity: space i's device stream equals the C++ stream
    ``shifts[i]`` ticks earlier.  Returns the number of events compared."""
    n_events = 0
    for i, shift in enumerate(shifts):
        for t in range(len(ref)):
            if t < shift:
                check(all(len(a) == 0 for a in dev[t][i]),
                      f"space {i}: pipelined tick {t} delivered early")
                continue
            for kind, a, b in zip(("enter", "leave"), dev[t][i],
                                  ref[t - shift][i]):
                check(a.shape == b.shape and np.array_equal(a, b),
                      f"space {i} tick {t}: {kind} stream differs from "
                      f"cpp ({len(a)} vs {len(b)} pairs)")
                n_events += len(a)
        # what the shift pushes past the device run must be empty
        for t in range(len(ref) - shift, len(ref)):
            check(all(len(a) == 0 for a in ref[t][i]),
                  f"space {i}: cpp tick {t} has events the device run "
                  "did not reach")
    return n_events


def check_fallbacks(label, rt, stats, shift):
    """Every fallback counter at 0 over the run, but for the cap growth
    that the mass enter forces (harvested on tick ``shift``; the tick
    dispatched before that harvest is harvested on tick ``2 * shift``)."""
    growth = stats[2 * shift]["decode_overflow"]
    final = dict(stats[-1])
    final["cohort_demoted_spaces"] = rt.aoi.cohort_stats[
        "cohort_demoted_spaces"]
    final["decode_overflow"] -= growth
    say(f"{label}: fallback counters " + " ".join(
        f"{k}={v}" for k, v in final.items())
        + f" (decode_overflow excludes {growth} cap growth after the "
        "mass enter)")
    check(all(v == 0 for v in final.values()),
          "a fallback away from the compiled kernel fired")


def timing_line(label, walls):
    steady = walls[2:BULK_TICKS + 1]
    say(f"{label}: first tick (compile + mass enter) {walls[0]:.3f} s, "
        f"second tick {walls[1]:.3f} s, bulk-move ticks median "
        f"{float(np.median(steady)) * 1e3:.3f} ms "
        f"(min {min(steady) * 1e3:.3f}, max {max(steady) * 1e3:.3f}; "
        "host clock, block_until_ready)")


def engine_phase():
    import jax

    spaces = [dict(UNIFORM) for _ in range(8)]
    walk = make_walk(spaces, SEED)
    dev, walls, stats, rt, scenes = run_walk(
        spaces, walk, aoi_backend="tpu", aoi_pipeline=True)
    label = f"{len(spaces)}x{spaces[0]['n_per']}"
    timing_line(f"engine: tpu {label}", walls)
    kinds = {type(s._aoi_handle.bucket).__name__ for s in scenes}
    check(kinds == {"_TPUBucket"}, f"spaces ran on {sorted(kinds)}")
    shifts = [1 if s._aoi_handle.bucket._defer else 0 for s in scenes]
    check(shifts == [1] * len(scenes), "the tpu bucket is not pipelined")
    check_fallbacks("engine", rt, stats, 1)
    peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    say(f"engine: peak_bytes_in_use={peak}")
    ref, cwalls, _cst, _crt, _cs = run_walk(spaces, walk, aoi_backend="cpp")
    say(f"engine: cpp {label} ticks median "
        f"{float(np.median(cwalls[1:BULK_TICKS + 1])) * 1e3:.3f} ms")
    n = compare_streams(dev, ref, shifts)
    say(f"engine: parity with cpp bit-exact over {len(ref)} ticks, "
        f"{n} enter/leave events (shifted one tick)")
    check(n > 0, "the walk produced no events")


# -- four chips -------------------------------------------------------------


def four_chip_phase():
    import jax

    spaces = [dict(UNIFORM) for _ in range(16)] + [dict(HOTSPOT, zipf=True)]
    walk = make_walk(spaces, SEED + 1)
    dev, walls, stats, rt, scenes = run_walk(
        spaces, walk, aoi_backend="tpu", aoi_mesh=4, aoi_pipeline=True)
    timing_line(f"mesh4: tpu 16x{UNIFORM['n_per']} + hotspot "
                f"{HOTSPOT['n_per']}", walls)
    kinds = sorted({type(s._aoi_handle.bucket).__name__ for s in scenes})
    check(kinds == ["_MeshTPUBucket", "_RowShardTPUBucket"],
          f"expected the mesh and row-shard tiers, got {kinds}")
    for b in rt.aoi._buckets.values():
        n_dev = len(b.prev.sharding.device_set)
        say(f"mesh4: {type(b).__name__} state on {n_dev} devices")
        check(n_dev == 4, f"{type(b).__name__} state is not on 4 devices")
    shifts = [1 if getattr(s._aoi_handle.bucket, "_defer", False) else 0
              for s in scenes]
    check_fallbacks("mesh4", rt, stats, max(shifts))
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in jax.devices()[:4]]
    say(f"mesh4: bytes_in_use per device {in_use}")
    check(all(v > 0 for v in in_use), "a chip holds no state")
    ref, _cw, _cst, _crt, _cs = run_walk(spaces, walk, aoi_backend="cpp")
    n_mesh = compare_streams([[t[i] for i in range(16)] for t in dev],
                             [[t[i] for i in range(16)] for t in ref],
                             shifts[:16])
    say(f"mesh4: space-sharded parity with cpp bit-exact, {n_mesh} events "
        f"(shift {shifts[0]})")
    n_hot = compare_streams([[t[16]] for t in dev], [[t[16]] for t in ref],
                            shifts[16:])
    say(f"mesh4: row-sharded hotspot parity with cpp bit-exact, {n_hot} "
        f"events (shift {shifts[16]})")
    check(n_mesh > 0 and n_hot > 0, "the walk produced no events")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh and row-shard checks")
    args = ap.parse_args(argv)
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        say(f"JAX_PLATFORMS={plats} pins JAX off the TPU; nothing to prove")
        return 1
    sys.path.insert(0, REPO)
    from goworld_tpu.chip import use_compile_cache

    cache = use_compile_cache()
    try:
        say(f"compile cache: {cache}")
        build_native()
        if args.chips == 1:
            cluster_phase()
        device = device_identity(args.chips)
        if args.chips == 1:
            engine_phase()
        else:
            four_chip_phase()
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"compile cache: {n_cached} entries in {cache}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
