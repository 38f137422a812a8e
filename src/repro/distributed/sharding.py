"""Serving meshes: `jax.make_mesh` with every axis `AxisType.Auto`."""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def auto_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """`jax.make_mesh` with every axis `AxisType.Auto`.

    `jax.make_mesh` makes Explicit axes by default, which type-check
    every sharded array op: the serving top-k merge and the sharded
    ticks rely on the compiler choosing shardings (a (p·k,) candidate
    row does not split evenly over p shards), so their meshes are Auto.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
