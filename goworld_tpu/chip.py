"""Which platform the AOI kernels run on, decided in one place.

JAX-free at import: entry points call ``use_compile_cache`` before the
process touches JAX.

Two rules every device path follows:

* a Pallas kernel runs compiled (Mosaic) exactly when the platform its
  caller routes on is ``"tpu"``, and interpreted anywhere else;
* a request for the TPU never quietly runs on the host CPU.  Only a
  process that pinned JAX to the CPU itself (``JAX_PLATFORMS=cpu``: the
  tests, the virtual-device dryrun) may run TPU buckets there, in
  interpret mode.

It also places JAX's persistent compilation cache (``use_compile_cache``).
"""

from __future__ import annotations

import os
import sys

# <checkout>/.jax_cache: fixed, absolute and independent of the cwd (the
# CLI starts games inside their run directory), so every process of one
# checkout shares it across runs.  Listed in .gitignore.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def interpret_for(platform: str | None = None) -> bool:
    """Pallas interpret mode for a kernel placed on ``platform`` (default:
    JAX's default backend)."""
    if platform is None:
        import jax

        platform = jax.default_backend()
    return platform != "tpu"


def cpu_pinned() -> bool:
    """True when this process pinned JAX to the host CPU."""
    import jax

    return jax.config.jax_platforms == "cpu"


def require_tpu(platform: str, what: str) -> None:
    """Raise unless ``platform`` is a TPU or the process pinned the CPU."""
    if platform != "tpu" and not cpu_pinned():
        raise RuntimeError(
            f"{what} needs a TPU, but JAX runs it on {platform!r}.  Set "
            "JAX_PLATFORMS=cpu to run the kernels interpreted on purpose "
            "(tests, dryruns); anything else is a device that failed to "
            "start or is held by another process.")


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set, and is left alone.
    Otherwise the cache goes to ``<checkout>/.jax_cache``, exported in the
    environment so the processes this one starts use it too.  Entry points
    that compile call this; importing a module never does, so tests keep
    JAX's default (no cache)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR
    jax = sys.modules.get("jax")
    if jax is not None:  # imported already: its config latched the env
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR
