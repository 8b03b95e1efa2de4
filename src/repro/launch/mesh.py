"""Production mesh factory (TPU v5e).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — only the dry-run sets the 512-placeholder-
device XLA flag, and only in its own process.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> tuple[AxisType, ...]:
    # the sharding code places arrays with NamedSharding and
    # with_sharding_constraint, which need Auto axes (make_mesh's default is
    # Explicit)
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(shape)))


def make_host_mesh(model_parallel: int = 1):
    """Whatever fits the local devices (CPU smoke tests / examples)."""
    n = jax.device_count()
    dp = n // model_parallel
    return jax.make_mesh((dp, model_parallel), ("data", "model"), _auto(2))


def make_sampler_mesh(max_devices: int | None = None):
    """Data-only mesh for the batched sampling engine.

    The sampler shards only the batch dimension (params replicate, per-
    sample ERS stays shard-local), so a single "data" axis over the local
    devices is the whole topology.  ``max_devices`` caps the axis for tests
    that want a fixed dp on machines with more devices."""
    n = jax.device_count()
    if max_devices is not None:
        n = min(n, max_devices)
    return jax.make_mesh((n,), ("data",), _auto(1), devices=jax.devices()[:n])

