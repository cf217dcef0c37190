"""Production meshes.

Single-pod: 16 x 16 = 256 chips (one v5e pod), axes (data, model).
Multi-pod: 2 x 16 x 16 = 512 chips, axes (pod, data, model); the pod axis
extends data parallelism (and sequence sharding for long-context decode).

``make_production_mesh`` is a function — importing this module never touches
jax device state. Mesh axes are ``Auto``: the model places arrays with
sharding constraints (``distributed/sharding.shard``), which JAX accepts
only on ``Auto`` axes (``jax.make_mesh`` defaults to ``Explicit``).
"""

from __future__ import annotations

from typing import Optional

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_devices: Optional[int] = None):
    """Small mesh over whatever devices exist (CI / unit tests)."""
    n = n_devices or len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return _auto_mesh((n // model, model), ("data", "model"))
