"""Driver contract: entry() compiles and runs; dryrun_multichip(8) executes
the sharded tick on the virtual CPU mesh."""

import os
import subprocess
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))


def test_entry_runs():
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    new, ent, lv = out
    assert new.shape == ent.shape == lv.shape


def test_dryrun_multichip_8():
    import __graft_entry__ as g

    g.dryrun_multichip(8)


def test_dryrun_survives_poisoned_default_platform():
    """Round-3 regression: the official MULTICHIP artifact went red because
    a broken accelerator plugin (rolling libtpu upgrade) poisoned
    default-backend init for a dryrun that never touches the accelerator.
    The dryrun must pin the host platform, so a JAX_PLATFORMS naming an
    unloadable plugin cannot kill it."""
    env = os.environ.copy()
    # Poison: JAX_PLATFORMS names a backend no plugin registers.  Prove
    # the poison is real first (control), then that the dryrun is immune.
    env["JAX_PLATFORMS"] = "no_such_platform"
    env.pop("XLA_FLAGS", None)
    control = subprocess.run(
        [sys.executable, "-c", "import jax; jax.devices()"],
        cwd=str(_REPO), env=env, capture_output=True, timeout=300)
    assert control.returncode != 0, (
        "poison platform unexpectedly loadable -- test is vacuous")
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8); "
         "print('DRYRUN_OK')"],
        cwd=str(_REPO), env=env, capture_output=True, timeout=900)
    assert r.returncode == 0, r.stderr.decode()[-4000:]
    assert b"DRYRUN_OK" in r.stdout
