"""Test-visible counter of XLA program launches (device dispatches).

The fused-tick contract (ROADMAP #3, docs/perf.md "Fused tick") is "one
enqueue + one D2H fetch per steady-state tick" -- and a contract nobody
can measure is a contract that silently rots.  Every engine call site
that launches a compiled XLA program (delta scatter, bucket step,
maintenance scatter, fused step, sharded step, the single-chip
harvest's eager triples slice) reports here via :func:`record`, and
tests/test_fused.py plus scripts/fused_smoke.py bracket a tick with
:func:`read` to pin the count: 1 for a fused single-chip bucket, 2 for
the unfused delta-staged path (scatter + step), one more for the slice
on a tick with events, and the documented per-chip program counts for
the sharded tiers.

Counting is launch-side (did the host enqueue a program), not
device-side (XLA may still fuse or cache internally) -- that is exactly
the host-overhead boundary the fused tick exists to cross fewer times.
Transfers (``jnp.asarray`` uploads, ``copy_to_host_async``) are NOT
dispatches and are tracked separately as ``aoi.h2d_bytes``.

Pure host-side integers: importing this module never loads jax, and
recording is a plain increment, so the counter is safe inside
``dispatch()`` (the gwlint flush-phase rule walks through it).  Each
record also feeds the registry counter ``aoi.launches`` (a no-op while
telemetry is off), so operators and the benchmark read the same count.
"""

from __future__ import annotations

from .. import telemetry

_LAUNCHES = telemetry.counter(
    "aoi.launches", "XLA program launches enqueued by the AOI engine")
_n = 0
_keys: set = set()
_fresh: list = []  # the site of each key first seen since reset_keys


def record(n=1):
    """Count ``n`` XLA program launches (call beside the jitted call)."""
    global _n
    _n += n
    _LAUNCHES.inc(n)


def read():
    """Total launches recorded since the last :func:`reset`."""
    return _n


def reset():
    """Zero the counter (test/smoke harness hook)."""
    global _n
    _n = 0


def record_key(site: str, key) -> bool:
    """Record the jit compile key a launch site is about to call with.

    Every call site in this repo reaches XLA through a module-level
    memoized wrapper, so a *recompile* happens exactly when a site sees
    a ``(static args, shapes)`` combination for the first time.  Sites
    report that combination here (hashable, host-side), and the
    steady-state-recompiles-=-0 pins (bench engine_multispace,
    scripts/multispace_smoke.py) bracket the measured window with
    :func:`reset_keys` / :func:`new_keys`.  Returns True when the key is
    new since the last :func:`clear_keys` (i.e. this call compiles)."""
    k = (site, key)
    if k in _keys:
        return False
    _keys.add(k)
    _fresh.append(site)
    return True


def new_keys(site: str | None = None) -> int:
    """Fresh compile keys observed since the last :func:`reset_keys`
    (those of ``site`` alone when it is given)."""
    return len(_fresh) if site is None else _fresh.count(site)


def reset_keys():
    """Zero the new-key counter, KEEPING the seen set -- the warmup/
    measure bracket (warm keys must not count as steady recompiles)."""
    _fresh.clear()


def clear_keys():
    """Forget every seen key (full harness reset between configs)."""
    _keys.clear()
    _fresh.clear()
