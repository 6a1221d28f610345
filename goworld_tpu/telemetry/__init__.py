"""Unified telemetry: metrics registry + tick tracing + exposition.

The observability layer for the whole engine (docs/observability.md).
Stdlib-only -- importable from anywhere in the package (faults, opmon,
netutil, the engine buckets) with no cycle and no jax dependency.

* :mod:`.metrics` -- the process-wide :class:`~.metrics.Registry` of
  counters/gauges/pow2-bucket histograms plus the collector pull point
  that unifies the pre-existing stat sources (AOI bucket ``stats``,
  ``dispatchercluster.status()``, ``faults`` counters, the ``opmon`` op
  table) under stable dotted names.
* :mod:`.trace` -- span API over a bounded ring with Chrome trace-event
  (Perfetto) export and an optional ``jax.profiler`` annotation bridge.

``enable()`` turns both on (``Runtime(telemetry=True)`` and the component
``telemetry`` config key call it); disabled -- the default -- every hot-path
hook is a no-op and the engine's behavior stays bit-identical.  Exposition
(`snapshot`/`render_prometheus`, served at ``/debug/metrics``) works even
while disabled: collectors read stat sources that are always on anyway.

``GW_TELEMETRY=1`` in the environment enables at import (ops deployments
that cannot reach the config file).
"""

from __future__ import annotations

import os
import sys

from . import metrics, trace
from .metrics import HIST_BOUNDS, Counter, Gauge, Histogram, Registry, Sample

_REGISTRY = Registry(enabled=False)


def accelerator_absent() -> bool:
    """True when this process has no TPU backend attached.  Reads
    ``sys.modules`` instead of importing jax -- the telemetry package
    stays jax-free, and a process that never imported jax (gates,
    dispatchers) truthfully has no accelerator."""
    jax = sys.modules.get("jax")
    if jax is None:
        return True
    try:
        return jax.default_backend() != "tpu"
    except Exception:
        return True


def _accelerator_collect() -> list[Sample]:
    # always-on (registered at import, served even with telemetry off):
    # a process running without its accelerator must be scrapeable
    # from /debug/metrics, not just a stdout banner (docs/observability.md)
    return [Sample("accelerator_absent", "gauge",
                   1.0 if accelerator_absent() else 0.0,
                   help="1 when this process has no TPU backend attached "
                        "(its perf numbers are not accelerator evidence)")]


_REGISTRY.register_collector(_accelerator_collect)


def registry() -> Registry:
    return _REGISTRY


def enabled() -> bool:
    return _REGISTRY.enabled


def enable(clock=None, ring: int | None = None) -> None:
    """Turn on instruments and span tracing process-wide.  ``clock`` routes
    span timestamps through an injected time source (the Runtime.now
    seam); ``ring`` bounds the span buffer."""
    _REGISTRY.enabled = True
    trace.enable(clock=clock, ring=ring)


def disable() -> None:
    _REGISTRY.enabled = False
    trace.disable()


def counter(name: str, help: str = "") -> Counter:
    return _REGISTRY.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return _REGISTRY.gauge(name, help)


def histogram(name: str, help: str = "") -> Histogram:
    return _REGISTRY.histogram(name, help)


def register_collector(fn, weak: bool = False) -> None:
    _REGISTRY.register_collector(fn, weak=weak)


def snapshot() -> dict:
    return _REGISTRY.snapshot()


def render_prometheus() -> str:
    return _REGISTRY.render_prometheus()


__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Sample", "HIST_BOUNDS",
    "metrics", "trace", "registry", "enabled", "enable", "disable",
    "counter", "gauge", "histogram", "register_collector", "snapshot",
    "render_prometheus", "accelerator_absent",
]

if os.environ.get("GW_TELEMETRY", "") in ("1", "true", "yes"):
    enable()
