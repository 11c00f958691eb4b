"""Mesh construction — the one place the repo builds a ``jax.sharding.Mesh``.

``jax.make_mesh`` gives **Explicit** axes by default. The repo's sharding
code is written for **Auto** axes: ``with_sharding_constraint`` inside the
steps, pjit ``in_shardings``/``out_shardings`` and plain ``jnp`` indexing
under ``with mesh:`` all raise on Explicit axes (``ShardingTypeError``).
So every mesh in ``src/`` and ``tests/`` is built here, with Auto axes.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_shapes),
                         devices=devices)
