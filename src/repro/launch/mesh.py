"""Production mesh definitions.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (smoke tests run on 1 CPU device; only the dry-run
sets XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the model code places with sharding constraints, which
    # Explicit axes (jax.make_mesh's default) turn into type errors
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the production axis names — lets every smoke test
    run the exact production code path (shard_map included) on CPU."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_mesh_from_devices(devices, *, data: int, model: int,
                           pod: int | None = None):
    """Elastic variant: build a mesh over an explicit device list (used by
    runtime.elastic after excluding failed hosts)."""
    import numpy as np
    n = data * model * (pod or 1)
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    arr = np.asarray(devices[:n])
    if pod:
        return jax.sharding.Mesh(arr.reshape(pod, data, model),
                                 ("pod", "data", "model"))
    return jax.sharding.Mesh(arr.reshape(data, model), ("data", "model"))
