"""Test config: hermetic 8-virtual-device CPU backend.

Must run before anything imports jax, hence the env mutation at module
import time (pytest imports conftest first).

The suite PINS the cpu platform by default: kernel tests run in interpret
mode and mesh tests reach the 8 virtual devices -- hermetic and
deterministic (SURVEY §4).  The TPU compiles of the main-path kernels are
checked against a described v5e in tests/test_chip_compile.py; running
them on the chip is ``chip_smoke.py``'s job.  Set ``GW_TPU_TESTS=1`` to
let the suite use an attached accelerator for the single-chip kernel tests
instead.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

if os.environ.get("GW_TPU_TESTS") != "1":
    # Pin BEFORE jax loads.  Where something imported jax first (its
    # config already latched the env), update the live config too.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import sys

    if "jax" in sys.modules:
        try:  # private API: best-effort, never break collection over it
            import jax

            from jax._src import xla_bridge as _xb

            if not _xb.backends_are_initialized():
                jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: exhaustive sweeps excluded from the tier-1 `-m 'not slow'` "
        "run (each fresh mesh/rowshard engine re-JITs its kernels, ~12s "
        "per combination on the CPU backend)")
