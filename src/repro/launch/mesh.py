"""Production mesh builders, the platform / XLA-flag recipe and the
compile-cache placement.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; everything else
sees the real single device).
"""
from __future__ import annotations

import os
from typing import List, Optional

import jax
from jax.sharding import AxisType

# the checkout root: the directory that holds src/
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


# The latency-hiding recipe (docs/spmd.md): async collectives + the
# latency-hiding scheduler let each bucket's psum from the fused
# bucketed reduce (kernels/bucketed_reduce) overlap the remaining
# per-worker gradient compute instead of serializing behind it.
# These are GPU flags: CPU/TPU XLA builds treat unknown --xla_gpu_*
# flags as a FATAL parse error, so they are only ever applied when the
# target platform is 'gpu' (or explicitly forced).
LATENCY_HIDING_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def enable_latency_hiding(*, platform: Optional[str] = None,
                          force: bool = False) -> List[str]:
    """Append the latency-hiding XLA flags to ``XLA_FLAGS`` (idempotent).

    Only takes effect before the first jax device query (XLA parses the
    env once at backend init), and only when ``platform == 'gpu'`` or
    ``force=True`` — see ``LATENCY_HIDING_FLAGS``. Returns the flags
    actually added, so callers can log what changed.
    """
    if platform != "gpu" and not force:
        return []
    flags = os.environ.get("XLA_FLAGS", "")
    added = [f for f in LATENCY_HIDING_FLAGS
             if f.split("=")[0] not in flags]
    if added:
        os.environ["XLA_FLAGS"] = " ".join([flags] + added).strip()
    return added


def set_platform(platform: str = "cpu", *,
                 latency_hiding: bool = True) -> List[str]:
    """Pin the jax platform and apply its XLA flag recipe.

    Call before any jax computation (the platform pin and ``XLA_FLAGS``
    both only take effect at backend init). On ``'gpu'`` this applies
    the latency-hiding flags the fused bucketed reduce-then-psum is
    shaped for; on ``'cpu'``/``'tpu'`` the flag recipe is a no-op (the
    flags are unknown to those XLA builds). Returns the flags added.
    """
    jax.config.update("jax_platform_name", platform)
    return enable_latency_hiding(platform=platform) if latency_hiding else []


def init_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    Called first thing by the CLI entry points (never on import). When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and this
    sets nothing. Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``, never a temporary or per-process name: a
    directory that moved from run to run would never be found again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return _mesh((data, model), ("data", "model"))


def use_mesh(mesh):
    """Context manager setting the ambient mesh (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)
