"""Production mesh construction (assignment MULTI-POD DRY-RUN step 1).

``make_production_mesh`` is a FUNCTION, not a module-level constant, so
importing this module never touches jax device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         (jax.sharding.AxisType.Auto,) * len(axes))


def mesh_shape_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
