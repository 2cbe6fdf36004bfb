"""Mesh construction (function — importing this module never touches jax
device state)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
