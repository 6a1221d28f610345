"""Space sharding across TPU chips.

The framework's unit of parallelism is the Space (reference analog: spaces
shard across game processes and never move -- /root/reference/cn docs, SURVEY
§2.4).  On TPU, the AOI arrays of S spaces form a leading batch dimension and
shard over a 1-D device mesh ('space' axis): every space's [C] rows live
wholly on one chip, so the per-tick AOI kernel needs **zero cross-chip
collectives** -- the only collective in the step is an optional psum of event
counts for cluster monitoring (riding ICI, negligible).

This mirrors the reference's key scaling property (all entities of a space
co-located; intra-space work never crosses process boundaries) in XLA terms:
shard_map partitions the batched step; each chip runs its own Pallas grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from ..ops.aoi_pallas import aoi_step_pallas
from ..ops.aoi_dense import aoi_step_dense_batched
from ..chip import interpret_for


def multichip_devices(n: int | None = None):
    """The first ``n`` devices of JAX's default backend (all of them for
    ``n=None``).  Too few is an error, never a drop to host CPU devices:
    virtual CPU devices come only from a process that pinned
    ``JAX_PLATFORMS=cpu`` with ``--xla_force_host_platform_device_count``
    (the tests and the dryrun), where they ARE the default backend."""
    devs = jax.devices()
    if n is None:
        return devs
    if len(devs) < n:
        raise RuntimeError(
            f"a {n}-device mesh needs {n} devices; the default backend "
            f"({devs[0].platform}) has {len(devs)}")
    return devs[:n]


class SpaceMesh:
    """A 1-D mesh over which space batches shard."""

    def __init__(self, devices=None, axis: str = "space"):
        devices = devices if devices is not None else multichip_devices()
        self.axis = axis
        self.mesh = Mesh(list(devices), (axis,))
        self.n_devices = len(devices)
        self.platform = devices[0].platform

    def sharding(self) -> NamedSharding:
        """NamedSharding that splits the leading (space) axis."""
        return NamedSharding(self.mesh, PS(self.axis))

    def device_put(self, arr):
        return jax.device_put(arr, self.sharding())


def make_sharded_aoi_step(space_mesh: SpaceMesh, *, use_pallas: bool = True,
                          block_rows: int = 128, max_words: int = 0,
                          chunk_k: int = 8):
    """Build the multi-chip AOI tick: [S, C] arrays sharded over chips.

    S must be a multiple of the mesh size.  Returns a jitted function
    ``step(x, z, r, active, prev) -> (new, enter, leave, total_events)``
    where total_events is a scalar psum over the mesh (the only collective).

    With ``max_words > 0`` each chip also compacts its own diff words
    chip-locally via the chunk extraction (ops/events.extract_chunks, the
    same gather-free path the single-chip production bucket runs) -- event
    delivery needs no collectives either.  The function then returns
    ``(new, ent_stream, lv_stream, total)`` where each stream is
    ``(vals, idx, n, n_dirty, max_ccnt)`` with per-chip arrays stacked on
    the leading axis: vals/idx are ``[n_dev * max_chunks, chunk_k]``
    sharded (reshape to ``[n_dev, max_chunks, chunk_k]``; idx -1 = empty
    slot), ``n`` the per-chip count of nonzero WORDS extracted, and
    ``n_dirty``/``max_ccnt`` the EXACT per-chip dirty-chunk count and
    words-per-chunk peak -- ``n_dirty > max_chunks`` or ``max_ccnt >
    chunk_k`` means that chip's stream is incomplete and the caller must
    fall back (the same overflow contract as ops/events.extract_chunks).
    ``max_chunks`` is ``max_words`` rounded down to whole 128-lane chunks
    (minimum 1).  Word indices are LOCAL to the chip's space block: global
    space index = chip * S_local + local_space.
    """
    mesh = space_mesh.mesh
    axis = space_mesh.axis
    interpret = interpret_for(space_mesh.platform)

    def _kernel(x, z, r, act, prev):
        if use_pallas:
            return aoi_step_pallas(x, z, r, act, prev,
                                   block_rows=block_rows,
                                   interpret=interpret)
        return aoi_step_dense_batched(x, z, r, act, prev)

    def _total(ent, lv):
        local_events = jnp.sum(
            jax.lax.population_count(ent) + jax.lax.population_count(lv),
            dtype=jnp.int32,
        )
        return jax.lax.psum(local_events, axis)

    spec = PS(axis)

    if not max_words:
        def _local(x, z, r, act, prev):
            new, ent, lv = _kernel(x, z, r, act, prev)
            return new, ent, lv, _total(ent, lv)

        out_specs = (spec, spec, spec, PS())
    else:
        from ..ops.events import extract_chunks

        max_chunks = max(1, max_words // 128)

        def _extract(words):
            vals, _aux, lane, csel, ccnt, nd, mcc = extract_chunks(
                words, max_chunks, chunk_k, lanes=128)
            gidx = jnp.where(lane >= 0,
                             csel[:, None] * 128 + jnp.maximum(lane, 0), -1)
            n_words = jnp.sum(jnp.minimum(ccnt, chunk_k), dtype=jnp.int32)
            # scalars become [1] so they stack into [n_dev] across the mesh
            return (vals, gidx, n_words.reshape(1), nd.reshape(1),
                    mcc.reshape(1))

        def _local(x, z, r, act, prev):
            new, ent, lv = _kernel(x, z, r, act, prev)
            return new, _extract(ent), _extract(lv), _total(ent, lv)

        # vals, idx, n_words, n_dirty, max_ccnt stack per chip
        ev_spec = (spec, spec, spec, spec, spec)
        out_specs = (spec, ev_spec, ev_spec, PS())

    step = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(spec, spec, spec, spec, spec),
        out_specs=out_specs,
        # pallas_call out_shapes carry no vma annotations; skip the check
        check_vma=False,
    )
    return jax.jit(step)
