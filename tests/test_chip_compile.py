"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles one program at a real width
for a ``v5e:2x2`` topology that JAX describes without a chip, and checks
that the Pallas kernel reached the TPU compiler (``tpu_custom_call`` in
the compiled text) instead of the interpreter.  What the chip's compiler
would refuse -- tiling, VMEM, device memory -- fails here at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a module that loads it
while pytest-xdist workers collect would give them different tests.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS, \
    SingleDeviceSharding

from goworld_tpu.ops.aoi_predicate import words_per_row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), (
        "the Pallas kernel was not compiled for the TPU (interpreted?)")


@pytest.mark.parametrize("s,c", [(8, 1024), (8, 16384), (1, 131072)],
                         ids=["C1024", "C16384", "C131072"])
def test_aoi_step_pallas_compiles(one_chip, s, c):
    """The plain kernel in each of its block layouts (narrow rows, the
    3-dim grid, the plane-wise 4-dim grid of very wide rows)."""
    from goworld_tpu.ops.aoi_pallas import aoi_step_pallas

    f32 = _spec((s, c), jnp.float32, one_chip)
    act = _spec((s, c), jnp.bool_, one_chip)
    prev = _spec((s, c, words_per_row(c)), jnp.uint32, one_chip)
    step = jax.jit(lambda x, z, r, a, p: aoi_step_pallas(
        x, z, r, a, p, emit="chg", interpret=False))
    _assert_kernel(step.lower(f32, f32, f32, act, prev).compile())


def test_fused_tri_step_compiles_the_kernel(one_chip):
    """The engine's fused tick routed to the TPU by ``platform``: its
    interpret mode must follow the routed platform, not this process's
    CPU backend."""
    from goworld_tpu.ops.aoi_fused import fused_tri_step

    s, c, npk, mt = 8, 16384, 1024, 65536
    w = words_per_row(c)
    words = _spec((s, c, w), jnp.uint32, one_chip)
    f32 = _spec((s, c), jnp.float32, one_chip)
    i32 = _spec((npk,), jnp.int32, one_chip)
    v32 = _spec((npk,), jnp.float32, one_chip)
    step = jax.jit(fused_tri_step, static_argnums=(14, 15))
    lowered = step.lower(
        words, words, words, _spec((mt, 3), jnp.int32, one_chip), f32, f32,
        i32, i32, v32, v32, _spec((s,), jnp.int32, one_chip), f32,
        _spec((s, c), jnp.bool_, one_chip), _spec((s,), jnp.bool_, one_chip),
        mt, "tpu")
    _assert_kernel(lowered.compile())


def test_sharded_step_compiles_over_four_chips(topo):
    """The space-sharded tick with chip-local chunk extraction over the
    four described chips: the kernel on every chip, one psum."""
    from goworld_tpu.parallel import SpaceMesh, make_sharded_aoi_step

    sm = SpaceMesh(list(topo.devices))
    assert sm.n_devices == 4 and sm.platform == "tpu"
    step = make_sharded_aoi_step(sm, use_pallas=True, max_words=4096)
    s, c = 16, 16384
    sh = NamedSharding(sm.mesh, PS(sm.axis))
    f32 = _spec((s, c), jnp.float32, sh)
    compiled = step.lower(f32, f32, f32, _spec((s, c), jnp.bool_, sh),
                          _spec((s, c, words_per_row(c)), jnp.uint32,
                                sh)).compile()
    _assert_kernel(compiled)
    per_chip = compiled.memory_analysis()
    assert per_chip is not None
    # each chip holds its quarter of the [16, C, W] words, not all of them
    assert per_chip.argument_size_in_bytes < np.prod(
        (s, c, words_per_row(c))) * 4
