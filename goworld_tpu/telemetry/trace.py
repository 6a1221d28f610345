"""Tick tracing: spans -> bounded ring -> Chrome trace-event JSON.

The span API times the tick pipeline (stage/h2d -> kernel -> diff -> fetch
-> event emit -> net flush) with three spellings matched to the call sites:

* ``with trace.span("tick.aoi"): ...`` -- block-shaped phases (runtime tick
  phases, component handlers);
* ``with trace.Segments(perf) as seg: seg.begin("aoi.fetch", "fetch_s")``
  -- consecutive segments of one bucket phase that also feed an always-on
  seconds sum; one clock read per boundary serves both;
* ``t0 = trace.t(); ...; trace.lap("aoi.fetch", t0)`` -- a completed
  interval recorded after the fact (the multi-chip tiers' segments).

Disabled (the default) ``span``/``t``/``lap`` are near-free: ``span``
returns a shared no-op context manager, ``t`` returns 0.0 and ``lap`` does
nothing -- one global load and an ``is None`` test each, the same contract
as ``faults.check``.  ``Segments`` reads ``time.perf_counter`` then, for
its sums alone.  ``span`` and ``Segments`` open a profiler annotation per
span while annotations are on; a ``lap`` never does.
Tracing reads the clock and nothing else -- never device state -- so
enabling it cannot perturb the bit-exact event stream.

The clock is injectable (the ``Runtime.now`` seam): ``enable(clock=...)``
or ``set_clock`` route every timestamp through it, so tests drive spans
with a deterministic clock.  Completed spans land in a bounded ring
(``collections.deque(maxlen=...)``: appends are atomic, old spans fall off)
tagged with thread id; ``mark_tick`` records tick boundaries so exports can
window to the last N ticks.  ``export_chrome_trace`` emits the Chrome
trace-event JSON that https://ui.perfetto.dev loads directly, and
``enable_jax_annotations`` optionally bridges spans onto
``jax.profiler.TraceAnnotation`` so they show up inside XLA device traces.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time

from ..consts import TRACE_RING_SPANS, TRACE_TICK_MARKS


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "tracer", "t0", "_annot")

    def __init__(self, name: str, tracer: "Tracer"):
        self.name = name
        self.tracer = tracer

    def __enter__(self):
        tr = self.tracer
        factory = tr.annot_factory
        self._annot = None
        if factory is not None:
            self._annot = factory(self.name)
            self._annot.__enter__()
        _active_stack().append(self.name)
        self.t0 = tr.clock()
        return self

    def __exit__(self, *exc):
        tr = self.tracer
        t1 = tr.clock()
        if self._annot is not None:
            self._annot.__exit__(None, None, None)
        stack = _active_stack()
        if stack and stack[-1] == self.name:
            stack.pop()
        tr.record(self.name, self.t0, t1)
        return False


_ACTIVE = threading.local()


def _active_stack() -> list:
    stack = getattr(_ACTIVE, "stack", None)
    if stack is None:
        stack = _ACTIVE.stack = []
    return stack


def current_span() -> str | None:
    """Name of the innermost open ``with span(...)`` block on this thread
    (None when outside any span or while tracing is disabled).  Log lines
    use it to self-locate in the tick pipeline (utils/gwlog.py)."""
    stack = getattr(_ACTIVE, "stack", None)
    return stack[-1] if stack else None


class Tracer:
    def __init__(self, clock=time.perf_counter, ring: int = TRACE_RING_SPANS):
        self.clock = clock
        self.annot_factory = None  # set by enable_jax_annotations
        # (name, tid, t0, t1) per completed span; deque appends are atomic
        self.ring = collections.deque(maxlen=ring)
        self.ticks = collections.deque(maxlen=TRACE_TICK_MARKS)

    def record(self, name: str, t0: float, t1: float) -> None:
        if t1 < t0:  # a clock swapped mid-span; clamp, don't corrupt
            t1 = t0
        self.ring.append((name, threading.get_ident(), t0, t1))

    def mark_tick(self, n: int) -> None:
        self.ticks.append((n, self.clock()))

    def reset(self) -> None:
        self.ring.clear()
        self.ticks.clear()


_TRACER: Tracer | None = None


def enabled() -> bool:
    return _TRACER is not None


def enable(clock=None, ring: int | None = None) -> Tracer:
    """Install a live tracer (idempotent; a new clock/ring replaces it)."""
    global _TRACER
    tr = _TRACER
    if tr is None or ring is not None or (clock is not None
                                          and clock is not tr.clock):
        tr = Tracer(clock or time.perf_counter, ring or TRACE_RING_SPANS)
        _TRACER = tr
    return tr


def disable() -> None:
    global _TRACER
    _TRACER = None


def set_clock(clock) -> None:
    """Route span timestamps through ``clock`` (the Runtime.now seam).
    No-op while tracing is disabled."""
    tr = _TRACER
    if tr is not None:
        tr.clock = clock


def span(name: str):
    """Context manager timing a block; the no-op singleton when disabled."""
    tr = _TRACER
    if tr is None:
        return _NOOP
    return _Span(name, tr)


def t() -> float:
    """Span start stamp for ``lap``; 0.0 (and free) when disabled."""
    tr = _TRACER
    if tr is None:
        return 0.0
    return tr.clock()


class Segments:
    """Consecutive timed segments of one phase.  ``begin(name, key)`` ends
    the open segment and starts the next; each segment's seconds add into
    ``sink[key]`` (a bucket's always-on ``perf`` dict), and while tracing
    is on a named segment is also a span, with a profiler annotation like
    ``span``'s (``name`` None: the sum alone).  Each boundary reads the
    clock once -- the tracer's while tracing is on, ``time.perf_counter``
    otherwise -- and that stamp feeds the sum and the span alike.  Leaving
    the ``with`` block ends the last segment on every exit (an exception
    or an early return included), so no annotation is left open."""

    __slots__ = ("sink", "tr", "clock", "name", "key", "t0", "annot")

    def __init__(self, sink: dict):
        tr = _TRACER
        self.sink, self.tr = sink, tr
        self.clock = tr.clock if tr is not None else time.perf_counter
        self.name = self.key = self.annot = None

    def __enter__(self):
        return self

    def begin(self, name: str | None, key: str) -> None:
        t = self._end()
        self.name, self.key, self.t0 = name, key, t
        tr = self.tr
        if tr is not None and name is not None:
            factory = tr.annot_factory
            if factory is not None:
                self.annot = factory(name)
                self.annot.__enter__()
            _active_stack().append(name)

    def _end(self) -> float:
        t = self.clock()
        if self.key is None:
            return t
        self.sink[self.key] += t - self.t0
        self.key = None
        name, tr = self.name, self.tr
        if tr is not None and name is not None:
            if self.annot is not None:
                self.annot.__exit__(None, None, None)
                self.annot = None
            stack = _active_stack()
            if stack and stack[-1] == name:
                stack.pop()
            tr.record(name, self.t0, t)
        return t

    def __exit__(self, *exc):
        if self.key is not None:
            self._end()
        return False


def lap(name: str, t0: float) -> float:
    """Record a completed span from a ``t()`` start stamp; returns the
    duration (0.0 when disabled)."""
    tr = _TRACER
    if tr is None:
        return 0.0
    t1 = tr.clock()
    tr.record(name, t0, t1)
    return t1 - t0


def mark_tick(n: int) -> None:
    tr = _TRACER
    if tr is not None:
        tr.mark_tick(n)


def reset() -> None:
    tr = _TRACER
    if tr is not None:
        tr.reset()


def spans() -> list[tuple]:
    """Snapshot of the ring: (name, tid, t0, t1) tuples, oldest first."""
    tr = _TRACER
    if tr is None:
        return []
    return list(tr.ring)


def enable_jax_annotations(on: bool = True) -> bool:
    """Bridge spans onto ``jax.profiler.TraceAnnotation`` so they appear
    inside device traces.  Imported lazily and only here -- the telemetry
    package never touches jax otherwise; returns False when jax is
    unavailable or tracing is disabled."""
    tr = _TRACER
    if tr is None:
        return False
    if not on:
        tr.annot_factory = None
        return True
    try:
        from jax.profiler import TraceAnnotation
    except Exception:
        return False
    tr.annot_factory = TraceAnnotation
    return True


def export_chrome_trace(path: str | None = None,
                        last_ticks: int | None = None) -> dict:
    """Chrome trace-event JSON for the buffered spans (Perfetto loads it
    as-is).  ``last_ticks`` windows to the most recent N tick marks;
    ``path`` additionally writes the JSON to a file."""
    tr = _TRACER
    events: list[dict] = []
    pid = os.getpid()
    if tr is not None:
        ring = list(tr.ring)
        ticks = list(tr.ticks)
        cutoff = None
        if last_ticks is not None and len(ticks) > last_ticks:
            cutoff = ticks[-last_ticks][1]
            ticks = ticks[-last_ticks:]
        stamps = [t0 for _, _, t0, _ in ring] + [ts for _, ts in ticks]
        base = min(stamps) if stamps else 0.0
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": "goworld_tpu"}})
        for name, tid, t0, t1 in ring:
            if cutoff is not None and t1 < cutoff:
                continue
            events.append({
                "name": name, "cat": "tick", "ph": "X",
                "ts": round((t0 - base) * 1e6, 3),
                "dur": round((t1 - t0) * 1e6, 3),
                "pid": pid, "tid": tid,
            })
        for n, ts in ticks:
            events.append({
                "name": "tick %d" % n, "cat": "tick", "ph": "i", "s": "p",
                "ts": round((ts - base) * 1e6, 3), "pid": pid, "tid": 0,
            })
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh)
    return doc
