"""Multi-chip space sharding over a jax device mesh."""

from .mesh import SpaceMesh, make_sharded_aoi_step, multichip_devices  # noqa: F401
