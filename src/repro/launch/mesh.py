"""Production mesh builders.

Functions, not module constants: importing this module never touches jax
device state.  The dry-run process sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so these shapes are satisfiable on the CPU host.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_block_mesh(devices, shape, axis_names=("data", "model")):
    """Mesh over an explicit device subset (a tenant block's sub-mesh)."""
    import numpy as np
    arr = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(arr, axis_names)
