"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; everything else
sees the real device count).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has (CI / examples / tests)."""
    n = len(jax.devices())
    model = max(1, min(model_parallel, n))
    data = n // model
    return make_auto_mesh((data, model), ("data", "model"))


def make_auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the model code places arrays with
    ``with_sharding_constraint``, which the default Explicit axes reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_chip_count(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
