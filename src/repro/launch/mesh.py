"""Production mesh construction (dry-run contract, DESIGN.md §6).

``make_production_mesh`` is a FUNCTION (not module state) so importing this
module never touches jax device initialization.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else in the repo sees the real (single) device.

``make_mesh`` builds a mesh whose axes are all ``Auto`` (the compiler
places collectives); install one with ``jax.set_mesh``.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "POD_SHAPE",
           "MULTIPOD_SHAPE"]

POD_SHAPE = (16, 16)                 # 256 chips (one v5e pod slice)
MULTIPOD_SHAPE = (2, 16, 16)         # 2 pods = 512 chips


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
