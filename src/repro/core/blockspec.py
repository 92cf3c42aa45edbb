"""Axe-layout-driven Pallas BlockSpec derivation (paper §3.4 adapted to TPU).

The paper dispatches a TMA copy by (1) slicing the layouts to the region,
(2) finding a tiler T with ``L_S ≡ T ⊗ L_atom`` for the *compact*
shared-memory atom, and (3) verifying the *global-memory* side is a
strided box — recognized by the direct-sum operator (App. F), since
global boxes may be strided while on-chip atoms must be compact.

TPU analogue: a ``pl.pallas_call`` grid step copies an HBM tile into
VMEM.  The HBM side of tile (i, j) must be a strided box (direct-sum
decomposition of the dense layout), and the VMEM side must be a compact
atom aligned to the VREG plane (sublane × lane = 8×128 for f32, 16×128
bf16, 32×128 int8/fp8) and, for matmul operands, to the 128×128 MXU.

``derive_blockspec`` performs exactly this derivation and returns the
grid + BlockSpec; it *raises* when the Axe check fails, which is how
kernel wrappers validate their tilings.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence, Tuple

import jax.numpy as jnp

from repro.core.layout import direct_sum, from_shape, layouts_equal, strided


def vreg_atom(dtype) -> Tuple[int, int]:
    """The TPU vector-register tile (sublane, lane) for a dtype."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = {4: 8, 2: 16, 1: 32}.get(itemsize, 8)
    return (sublane, 128)


MXU_TILE = (128, 128)


class TilingError(ValueError):
    """A tile the Axe algebra rejects for a shape. Raised through one
    shared path (``check_tiling``) by every kernel call site, so a
    non-divisible shape surfaces one actionable message (shape, tile,
    nearest valid tile) instead of a backend-dependent Pallas failure."""


@dataclasses.dataclass(frozen=True)
class TileDerivation:
    shape: Tuple[int, ...]
    tile: Tuple[int, ...]
    grid: Tuple[int, ...]
    hbm_box_strides: Tuple[int, ...]   # strides of the per-cell HBM box
    vreg_aligned: bool
    mxu_aligned: bool


def derive_tiling(shape: Sequence[int], tile: Sequence[int], dtype=jnp.float32) -> TileDerivation:
    """Verify (via the Axe algebra) that ``tile`` induces a valid
    grid decomposition of a dense row-major tensor of ``shape``.

    Checks ``dense(shape) == Grid + Box`` (direct sum, App. F) where
    Grid enumerates tile origins and Box is the strided HBM tile.
    """
    shape = tuple(int(s) for s in shape)
    tile = tuple(int(t) for t in tile)
    if len(shape) != len(tile):
        raise TilingError(f"rank mismatch {shape} vs {tile}")
    for s, t in zip(shape, tile):
        if t <= 0 or s % t:
            raise TilingError(f"tile {tile} does not divide shape {shape}")
    grid = tuple(s // t for s, t in zip(shape, tile))

    # row-major strides of the full tensor
    full_strides = []
    acc = 1
    for s in reversed(shape):
        full_strides.append(acc)
        acc *= s
    full_strides.reverse()

    if not _direct_sum_ok(shape, tile, grid, tuple(full_strides)):
        raise TilingError(f"direct-sum decomposition failed for {shape} / {tile}")

    sub, lane = vreg_atom(dtype)
    vreg_ok = len(tile) >= 2 and tile[-1] % lane == 0 and tile[-2] % sub == 0
    mxu_ok = len(tile) >= 2 and tile[-1] % MXU_TILE[1] == 0 and tile[-2] % MXU_TILE[0] == 0
    return TileDerivation(shape, tile, grid, tuple(full_strides), vreg_ok, mxu_ok)


@functools.lru_cache(maxsize=4096)
def _direct_sum_ok(shape, tile, grid, full_strides) -> bool:
    """``dense(shape) == Grid + Box``, memoised: the planner asks it of the
    same tiles for every layer of a model."""
    grid_strides = tuple(t * st for t, st in zip(tile, full_strides))
    A = strided(grid, grid_strides)           # tile origins
    B = strided(tile, full_strides)           # strided HBM box
    T, _ = direct_sum(A, grid, B, tile)
    return layouts_equal(T, from_shape(shape))


def nearest_valid_tile(
    shape: Sequence[int], tile: Sequence[int], dtype=jnp.float32
) -> Tuple[int, ...]:
    """The valid tile closest to the requested one, per dim, drawn from
    ``candidate_blocks`` — what the unified TilingError suggests."""
    shape = tuple(int(s) for s in shape)
    tile = tuple(int(t) for t in tile) + (1,) * (len(shape) - len(tile))
    sub, lane = vreg_atom(dtype)
    mins = [1] * len(shape)
    if len(shape) >= 2:
        mins[-2], mins[-1] = sub, lane
    elif len(shape) == 1:
        mins[-1] = lane
    out = []
    for s, t, mn in zip(shape, tile, mins):
        cands = candidate_blocks(s, minimum=mn) or (s,)
        out.append(min(cands, key=lambda c: (abs(c - t), c)))
    return tuple(out)


def check_tiling(
    shape: Sequence[int],
    tile: Sequence[int],
    dtype=jnp.float32,
    *,
    op: str = "pallas",
    require_vreg: bool = False,
) -> TileDerivation:
    """The single kernel-facing tiling validation path.

    Wraps ``derive_tiling`` so every kernel call site raises the same
    actionable ``TilingError`` — naming the op, the offending shape and
    tile, and the nearest Axe-valid tile from ``candidate_blocks`` —
    rather than a backend-dependent Pallas shape assertion."""
    try:
        d = derive_tiling(shape, tile, dtype)
    except TilingError as e:
        suggestion = nearest_valid_tile(shape, tile, dtype)
        raise TilingError(
            f"[{op}] tile {tuple(int(t) for t in tile)} is not Axe-valid for shape "
            f"{tuple(int(s) for s in shape)} ({jnp.dtype(dtype).name}): {e}; "
            f"nearest valid tile {suggestion}"
        ) from e
    if require_vreg and not d.vreg_aligned:
        suggestion = nearest_valid_tile(shape, tile, dtype)
        raise TilingError(
            f"[{op}] tile {tuple(int(t) for t in tile)} not VREG-aligned for shape "
            f"{tuple(int(s) for s in shape)} ({jnp.dtype(dtype).name}, atom "
            f"{vreg_atom(dtype)}); nearest valid tile {suggestion}"
        )
    return d


def derive_blockspec(
    shape: Sequence[int],
    tile: Sequence[int],
    dtype=jnp.float32,
    *,
    index_map=None,
    require_vreg: bool = False,
    op: str = "pallas",
):
    """Return ``(grid, pl.BlockSpec)`` for a dense tensor, Axe-verified.

    Kept as the shape-level entry point; the spec-level adapter is
    ``repro.axe.lower.to_blockspec`` (which routes here conceptually —
    both share the ``check_tiling`` error path)."""
    from jax.experimental import pallas as pl  # deferred: keep core import-light

    d = check_tiling(shape, tile, dtype, op=op, require_vreg=require_vreg)
    if index_map is None:
        rank = len(d.grid)
        index_map = lambda *ids: ids[:rank]
    return d.grid, pl.BlockSpec(d.tile, index_map)


def candidate_blocks(
    dim: int,
    *,
    minimum: int,
    prefer: Sequence[int] = (512, 256, 128),
) -> Tuple[int, ...]:
    """All block sizes from ``prefer`` that divide ``dim`` and respect
    the alignment ``minimum`` — the planner's per-dimension candidate
    set. Falls back to the largest aligned divisor (or ``dim`` itself
    for small problems) so the set is never empty when a valid tiling
    exists at all."""
    dim = int(dim)
    out = [c for c in prefer if c <= dim and dim % c == 0 and c % minimum == 0]
    if not out:
        if dim < minimum:
            out.append(dim)  # whole (sub-atom) dim: one grid cell
        else:
            best = max(
                (d for d in range(minimum, dim + 1, minimum) if dim % d == 0),
                default=0,
            )
            if best:
                out.append(best)
    return tuple(sorted(set(out), reverse=True))


def candidate_tilings(
    shape: Sequence[int],
    dtype=jnp.float32,
    *,
    mxu: bool = True,
    prefer: Sequence[int] = (512, 256, 128),
    vmem_budget_bytes: int = 8 * 1024 * 1024,
) -> Tuple[TileDerivation, ...]:
    """Axe-validated 2-D tilings of ``shape[-2:]`` the planner may rank.

    Every returned derivation passed ``derive_tiling`` (the App. F
    direct-sum check) and fits the VMEM budget; invalid combinations are
    silently dropped, so an empty result means "no Pallas schedule
    exists for this shape" and the planner must fall back to XLA."""
    shape = tuple(int(s) for s in shape)
    if len(shape) < 2:
        return ()
    itemsize = jnp.dtype(dtype).itemsize
    sub, lane = vreg_atom(dtype)
    min_r, min_c = (MXU_TILE if mxu else (sub, lane))
    out = []
    for r in candidate_blocks(shape[-2], minimum=min_r, prefer=prefer):
        for c in candidate_blocks(shape[-1], minimum=min_c, prefer=prefer):
            if r * c * itemsize > vmem_budget_bytes:
                continue
            try:
                out.append(derive_tiling(shape[-2:], (r, c), dtype))
            except TilingError:
                continue
    return tuple(out)


#: VMEM one tiled-matmul grid step may hold: under Mosaic's default
#: 16 MiB of scoped VMEM on a v5e, so no compiler parameter changes
MATMUL_VMEM_BYTES = 12 * 1024 * 1024


def matmul_vmem_bytes(bm: int, bn: int, bk: int, dtype, *, c_tiles: int = 1) -> int:
    """VMEM one grid step of ``C = A @ B`` holds: the double-buffered A
    ``(bm, bk)`` and B ``(bk, bn)`` tiles, ``c_tiles`` double-buffered
    ``(bm, bn)`` tiles (C and any operand tiled like it) and the f32
    accumulator."""
    item = jnp.dtype(dtype).itemsize
    return 2 * (bm * bk + bk * bn + c_tiles * bm * bn) * item + bm * bn * 4


def matmul_k_blocks(k: int, bm: int, bn: int, dtype, *, c_tiles: int = 1) -> Tuple[int, ...]:
    """The K blocks a tiled matmul with ``(bm, bn)`` tiles may take,
    largest first: every multiple of the lane width that divides K, up
    to K itself, whose grid step fits :data:`MATMUL_VMEM_BYTES`."""
    lane = MXU_TILE[1]
    return tuple(
        bk for bk in range(k - k % lane, 0, -lane)
        if k % bk == 0
        and matmul_vmem_bytes(bm, bn, bk, dtype, c_tiles=c_tiles) <= MATMUL_VMEM_BYTES
    )


def pick_tile(
    shape: Sequence[int],
    dtype=jnp.float32,
    *,
    vmem_budget_bytes: int = 4 * 1024 * 1024,
    prefer: Sequence[int] = (512, 256, 128),
    mxu: bool = True,
) -> Tuple[int, ...]:
    """Choose the largest aligned tile for the trailing 2 dims that fits
    the VMEM budget; leading dims get tile size 1 (grid-iterated)."""
    shape = tuple(int(s) for s in shape)
    itemsize = jnp.dtype(dtype).itemsize
    sub, lane = vreg_atom(dtype)
    min_r, min_c = (MXU_TILE if mxu else (sub, lane))

    def best(dim: int, minimum: int) -> int:
        for cand in prefer:
            c = min(cand, dim)
            if c % minimum == 0 and dim % c == 0:
                return c
        return math.gcd(dim, minimum) if dim % minimum else minimum

    rows = best(shape[-2], min_r) if len(shape) >= 2 else 1
    cols = best(shape[-1], min_c)
    while rows * cols * itemsize > vmem_budget_bytes and rows > min_r:
        rows //= 2
    lead = (1,) * (len(shape) - 2) if len(shape) >= 2 else ()
    return lead + ((rows,) if len(shape) >= 2 else ()) + (cols,)
