"""Production mesh construction (TPU v5e pods; 256 chips/pod).

Defined as functions (never module-level constants) so importing this
module touches no jax device state — required by the dry-run, which must
set XLA_FLAGS before the first device query.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import Mesh

from repro import compat


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return compat.make_mesh(shape, axes)


def make_local_mesh(model: int = 1) -> Mesh:
    """Whatever this host has (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
