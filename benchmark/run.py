#!/usr/bin/env python3
"""Benchmark of one cell of ``BENCHMARK.json``: a game shard on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell names a configuration (``benchmark/configs/<config>.json``) and
a traffic mix (``benchmark/traffic/<traffic>.json``); both are found by
name, as is each per-layer metric (``benchmark/metrics/<name>.json``
naming a span, or ``<name>.py`` with a ``read(ctx)``).  Set-up builds the
world from the seed, runs the mass enter and the mix's fixed number of
warm-up ticks (``warmup_ticks``); then the window runs closed loop for
``--seconds``.  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from the program's spans, the
harness's own and one profiler trace of a stretch of the window that
starts after the ticks the check always keeps.

The last line of standard output is one JSON object; the numbers the
comparison with the plain reference produced, each beside its limit, are
the last lines of standard error and the last key of that object.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

# moving warm-up ticks after the mass enter, where the mix names none
WARMUP_TICKS = 10
# the comparison checks this many ticks of the window, drawn from the seed
CHECK_TICKS = 6
# the profiler traces this long from the start of tick CHECK_TICKS of a
# traced window (the first CHECK_TICKS ticks always keep their output)
PROFILE_SECONDS = 8.0
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def process_start() -> float:
    """This process's start on the ``perf_counter`` clock."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


_T_IMPORT = time.perf_counter()


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding a cell by name ---------------------------------------------------


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = REPO) -> dict:
    """The cell ``name`` with its configuration, mix and metric specs, all
    found by name under ``root`` (a checkout, or a test's own tree)."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = _load_json(os.path.join(root, cfgs[cell["config"]]["file"]))
    mix = _load_json(os.path.join(root, "benchmark", "traffic",
                                  cell["traffic"] + ".json"))

    def mine(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "config": cfg, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)],
            "metrics_dir": os.path.join(root, "benchmark", "metrics")}


def per_layer_reader(metrics_dir: str, name: str):
    """``read(ctx)`` of a per-layer metric: a span entry (``<name>.json``:
    ``{"span": ...}``, the span's milliseconds per tick) or a module of
    its own (``<name>.py``)."""
    spec = os.path.join(metrics_dir, name + ".json")
    if os.path.exists(spec):
        entry = _load_json(spec)
        return lambda ctx: ctx.span_ms_per_tick(entry["span"],
                                                entry.get("outside"))
    path = os.path.join(metrics_dir, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                                f"in {metrics_dir}")
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- the run ------------------------------------------------------------------


class Context:
    """What a per-layer reader may read: the window's spans (program and
    harness, on one clock), its tick count, and the trace reduction."""

    def __init__(self, spans, ticks, trace, traced_ticks):
        self.spans, self.ticks = spans, ticks
        self.trace, self.traced_ticks = trace, traced_ticks

    def span_ms_per_tick(self, name, outside=None):
        """Milliseconds per tick in spans ``name``, leaving out those that
        lie inside a span ``outside`` (a name used at two depths)."""
        d = [b - a for n, a, b in self.spans if n == name]
        if outside is not None:
            outer = [(a, b) for n, a, b in self.spans if n == outside]
            d = [b - a for n, a, b in self.spans if n == name
                 and not any(oa <= a and b <= ob for oa, ob in outer)]
        if not d or not self.ticks:
            return None
        return sum(d) / self.ticks * 1e3


def _p95(values, weights):
    import numpy as np

    w = np.asarray(weights, np.int64)
    if w.sum() <= 0:
        return None
    return float(np.percentile(np.repeat(np.asarray(values, float), w), 95))


def event_p95(ticks, shift):
    """event_ms_p95 over all moves of the window ticks: a move of tick t
    waits from t's start until the end of the tick that delivered its
    events (t + shift)."""
    ev_lat, ev_w = [], []
    for k, rec in enumerate(ticks):
        if k + shift < len(ticks):
            ev_lat.append((ticks[k + shift].end - rec.start) * 1e3)
            ev_w.append(rec.moves)
    return _p95(ev_lat, ev_w)


def run_cell(found: dict, seed: int, seconds: float, traced: bool,
             t_process: float, out_dir: str | None = None,
             control: str | None = None, fault=None) -> dict:
    """Build, warm up, measure, compare.  Returns the result object.
    ``fault(shard)``, for the tests, breaks the timed path after set-up."""
    import numpy as np

    import jax

    from goworld_tpu import telemetry
    from goworld_tpu.ops import dispatch_count

    from benchmark import check as C
    from benchmark.harness import FALLBACK_STATS, Shard

    cfg, mix = found["config"], found["mix"]
    shard = Shard(cfg, mix, seed)
    say(f"set-up: {shard.traffic.spaces} spaces x {shard.traffic.n} "
        f"entities, {shard.n_players} players, built in "
        f"{time.perf_counter() - t_process:.3f} s since process start")

    compiles = {"n": 0, "s": 0.0}

    def on_event(event, secs, **_kw):
        if event in COMPILE_EVENTS:
            compiles["n"] += 1
            compiles["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    # warm-up: the mass enter, then a fixed number of moving ticks, the
    # same work for every seed; their programs come from the compile cache
    # after a cell's first run
    kept = {0: shard.tick(moving=False)}
    warm = 1 + int(mix.get("warmup_ticks", WARMUP_TICKS))
    if warm < 3:
        raise ValueError("warmup_ticks below 2: the event shift is found "
                         "on the first two moving ticks")
    for k in range(1, warm):
        rec = shard.tick(keep=k in (1, 2))
        if k in (1, 2):
            kept[k] = rec
    shift, counts = C.find_shift(shard, kept, 1)
    say(f"warm-up: {warm} ticks, {compiles['n']} JAX trace/compile events, "
        f"bucket counters {shard.bucket_stats()}; the events of a tick's "
        f"moves arrive {shift} tick(s) later (mismatches at shift 0/1: "
        f"{counts})")
    kept.clear()
    if fault is not None:
        fault(shard)

    compiles["n"], compiles["s"] = 0, 0.0
    base_stats = shard.bucket_stats()
    dispatch_count.reset_keys()
    profiling, window_annot, t_prof = False, None, None
    if traced:
        telemetry.enable(clock=time.perf_counter, ring=1 << 20)
        telemetry.trace.enable_jax_annotations()
        from jax.profiler import TraceAnnotation

        shard.span.annotate = TraceAnnotation
        if os.path.isdir(out_dir):
            shutil.rmtree(out_dir)
        # no Python tracer: it doubles a Python-bound tick (1.75 s -> 3.3 s
        # in the uniform cell); spans arrive as TraceAnnotations anyway
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0

    def stop_profile():
        window_annot.__exit__(None, None, None)
        jax.profiler.stop_trace()
    check_rng = np.random.default_rng([int(seed) % (1 << 64), 3])
    sampled: list[int] = []
    ticks, traced_ticks = [], 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        k = len(ticks)
        if traced and k == CHECK_TICKS:
            # the profiled stretch: ticks the check keeps only as often as
            # the sample draws them, like the rest of the window
            jax.profiler.start_trace(out_dir, profiler_options=opts)
            profiling, t_prof = True, time.perf_counter()
            window_annot = TraceAnnotation("bench.window")
            window_annot.__enter__()
        if k < CHECK_TICKS:
            slot = k
        else:
            slot = int(check_rng.integers(0, k + 1))
            slot = slot if slot < CHECK_TICKS else None
        if slot is not None:
            if slot < len(sampled):
                sampled[slot] = k
                # keep only the sampled ticks and the ticks that deliver
                # their events
                need = set(sampled) | {t + shift for t in sampled}
                for t in [t for t in kept if t not in need]:
                    del kept[t]
            else:
                sampled.append(k)
        keep = slot is not None or (shift and k - shift in sampled)
        rec = shard.tick(keep=keep)
        ticks.append(rec)
        if keep:
            kept[k] = rec
        if profiling:
            traced_ticks += 1
            if rec.end - t_prof >= PROFILE_SECONDS:
                stop_profile()
                profiling = False
        if rec.end >= t_end:
            break
    if profiling:
        stop_profile()
    # a sampled tick whose events would arrive after the window is dropped
    sampled = [t for t in sampled if t + shift < len(ticks)]
    t1 = ticks[-1].end
    jax.monitoring.unregister_event_duration_listener(on_event)
    stats = shard.bucket_stats()
    devs = jax.devices()
    mem = devs[0].memory_stats() or {}
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devs), default=0)
    moves = sum(r.moves for r in ticks)
    say(f"window: {len(ticks)} ticks in {t1 - t0:.3f} s, {moves} moves, "
        f"{sum(r.records for r in ticks)} sync records, "
        f"{sum(r.ops for r in ticks)} client ops, {shard.errors} errors")
    say(f"window: compiles inside the window: {dispatch_count.new_keys()} "
        f"new program keys, {compiles['n']} JAX trace/compile events "
        f"taking {compiles['s']:.3f} s")
    say("window: bucket counters " + " ".join(
        f"{k}+{stats[k] - base_stats[k]}" for k in stats)
        + f"; memory bytes_in_use={mem.get('bytes_in_use')}")
    left = [k for k in FALLBACK_STATS if stats[k] > base_stats[k]]
    if stats["calc_level"] > 0:
        # demoted during set-up: the window ran off the compiled kernel
        left.append(f"calc_level={stats['calc_level']}")
    if left:
        raise SystemExit(f"FAILED: the AOI computation left the chip in the "
                         f"window ({', '.join(left)} rose)")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    metrics, breakdown = {}, None
    if not traced:
        values = {"moves_per_s": moves / (t1 - t0),
                  "event_ms_p95": event_p95(ticks, shift),
                  "setup_s": t0 - t_process}
        for m in found["end_to_end"]:
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        from benchmark import trace as T

        if not traced_ticks:
            raise SystemExit(f"FAILED: the window ran {len(ticks)} ticks, "
                             f"too few to trace after {CHECK_TICKS}")
        red = T.reduce(T.find_xplane(out_dir))
        if red["busy_s"] is None:
            raise SystemExit("FAILED: the trace holds no device operation")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": [[n, s] for n, s in red["ops"]],
                     "idle_gaps": [[n, s] for n, s in red["gaps"]]}
        spans = [(n, a, b) for n, _tid, a, b in telemetry.trace.spans()
                 if a >= t0 and b <= t1]
        spans += [(n, a, b) for n, a, b in shard.span.spans
                  if a >= t0 and b <= t1]
        ctx = Context(spans, len(ticks), red, traced_ticks)
        for m in found["per_layer"]:
            v = per_layer_reader(found["metrics_dir"], m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        telemetry.disable()

    # the comparison runs after the window and the memory reading
    t_check = time.perf_counter()
    numbers = C.compare(shard, kept, sampled, shift, control=control)
    if control is not None:
        sound = C.compare(shard, kept, sampled, shift)
        say("check: the program itself on the same ticks: " + " ".join(
            f"{k} {sound[k]}" for k in C.LIMITS))
    if shift is None:
        numbers["event_pairs_wrong"] = max(numbers["event_pairs_wrong"],
                                           min(counts))
    say(f"check: {numbers['ticks_checked']} ticks checked, "
        f"{numbers['events_owed']} events and {numbers['records_owed']} "
        f"records owed, in {time.perf_counter() - t_check:.3f} s"
        + (f" (control: {control})" if control else ""))
    correct = C.verdict(numbers) and shift is not None
    checks = {k: {"value": numbers[k], "limit": v}
              for k, v in C.LIMITS.items()}
    for k, v in checks.items():
        say(f"{k} {v['value']} limit {v['limit']}")
    out = {"correct": bool(correct), "attempted": moves,
           "failed": shard.errors, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    t_process = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bfloat16",), default=None,
                    help="put the reference at bfloat16 in the program's "
                         "place (proves the check fails; not a benchmark "
                         "run)")
    args = ap.parse_args(argv)
    found = load_cell(args.workload)
    # the persistent compile cache: where the environment says, else at a
    # fixed path in the checkout; everything is cached, however quick
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))
    sys.path.insert(0, REPO)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    chips = int(found["cell"]["chips"])
    if devs[0].platform != "tpu" or len(devs) < chips:
        say(f"FAILED: the cell needs {chips} TPU chip(s); JAX has "
            f"{len(devs)} device(s) of platform {devs[0].platform!r}")
        return 1
    from benchmark import trace as T

    try:
        T.peaks(devs[0].device_kind)
    except KeyError as e:
        say(f"FAILED: {e}")
        return 1
    out_dir = os.path.join(HERE, ".out", args.workload)
    result = run_cell(found, args.seed, args.seconds, bool(args.trace),
                      t_process, out_dir=out_dir, control=args.control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # run as a script: the checkout's root, not this directory, on the path
    sys.path[0] = REPO
    sys.exit(main())
