"""Mesh construction with Auto axis types.

``jax.make_mesh`` defaults to Explicit axis types; every mesh in this
repo is placed by ``shard_map``/``NamedSharding`` from solved layouts and
expects Auto (GSPMD-propagated) axes, so construction funnels through
:func:`make_mesh`.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes))
