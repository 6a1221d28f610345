"""goworld_tpu/chip.py: the platform rule and the compile-cache placement."""

import os
from pathlib import Path

import pytest

_REPO = Path(__file__).resolve().parent.parent


def test_mesh_wider_than_the_backend_raises():
    """No drop to host CPU devices: too few devices is an error."""
    import jax

    from goworld_tpu.parallel import multichip_devices

    with pytest.raises(RuntimeError, match="mesh needs"):
        multichip_devices(len(jax.devices()) + 1)


@pytest.mark.parametrize("mesh", [None, 4], ids=["single", "mesh4"])
def test_tpu_backend_off_tpu_raises_unless_cpu_pinned(monkeypatch, mesh):
    """aoi_backend=tpu on CPU devices runs only where the process pinned
    JAX_PLATFORMS=cpu (as this suite does); anywhere else it fails at
    boot instead of interpreting the kernel under a tpu name."""
    from goworld_tpu import chip
    from goworld_tpu.engine.aoi import AOIEngine

    AOIEngine(default_backend="tpu", mesh=mesh)
    monkeypatch.setattr(chip, "cpu_pinned", lambda: False)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        AOIEngine(default_backend="tpu", mesh=mesh)


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and is left alone; without it the
    cache goes to one fixed path inside the checkout, exported for child
    processes and applied to an already-imported JAX."""
    import jax

    from goworld_tpu import chip

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert chip.use_compile_cache() == "/elsewhere/cache"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    try:
        path = chip.use_compile_cache()
        assert path == str(_REPO / ".jax_cache")
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
