"""Mesh construction with the axis types this repo's shard_map code expects.

``jax.make_mesh`` defaults to ``Explicit`` axes; every mesh here is built
with ``Auto`` axes, which is what the ``jax.shard_map`` bodies (manual
collectives over named axes) are written against.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with ``Auto`` axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names))
