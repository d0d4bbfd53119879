"""Production mesh construction (per the assignment spec).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def production_dist(*, multi_pod: bool = False, sp: bool = False):
    from ..models.tp import Dist
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp_axes = ("pod", "data") if multi_pod else ("data",)
    return Dist(mesh=mesh, dp_axes=dp_axes, tp_axis="model", sp=sp)
