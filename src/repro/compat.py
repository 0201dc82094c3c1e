"""Mesh construction shared by the library, tests, benchmarks and examples.

``make_mesh`` builds every mesh with explicit ``AxisType.Auto`` axes, so
sharding stays with the compiler (GSPMD propagation) whatever the default
axis type of the installed jax is.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(axis_shapes, axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))
