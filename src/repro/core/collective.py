"""Layout-driven collective inference (paper §3.2: a `copy` whose source
and destination layouts disagree across device axes dispatches to a
collective; Fig. 8 reduce-scatter signature).

``infer_redistribution(src, dst)`` compares the per-dim mesh-axis
placement of two DTensorSpecs and emits an ordered plan of collective
steps. ``lower_step`` maps each step to the corresponding ``jax.lax``
collective inside a ``shard_map`` body — the TPU/ICI analogue of the
paper's NVSHMEM-backed distributed copies.

``lower_step(..., overlap=True)`` selects the *async* lowerings: an
AllGather becomes :func:`ring_all_gather`, the ppermute double-buffer
from ``kernels.collective_matmul`` generalized to a plain gather —
p-1 chunk rotations the XLA latency-hiding scheduler can interleave
with unrelated compute issued after it, instead of one monolithic
barrier. The result is bit-identical to the tiled ``lax.all_gather``;
only the issue structure changes (docs/overlap.md).
"""
from __future__ import annotations

import dataclasses
from typing import List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.dtensor import DTensorSpec


# ---------------------------------------------------------------------------
# plan steps
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AllGather:
    axis: str          # mesh axis to gather over
    dim: int           # logical dim that was sharded on it

    def flops(self) -> int:
        return 0


@dataclasses.dataclass(frozen=True)
class DynamicSlice:
    axis: str          # mesh axis the dst newly shards on (no comm; local chop)
    dim: int


@dataclasses.dataclass(frozen=True)
class AllToAll:
    axis: str
    src_dim: int       # dim that stops being sharded on `axis`
    dst_dim: int       # dim that becomes sharded on `axis`


@dataclasses.dataclass(frozen=True)
class ReduceScatter:
    axis: str
    dim: int


@dataclasses.dataclass(frozen=True)
class AllReduce:
    axis: str


@dataclasses.dataclass(frozen=True)
class Transfer:
    """Class-crossing movement over a device-class mesh axis (e.g. the
    ``host`` tier of ``repro.axe.hetero``) — same data motion as a
    gather/slice but charged against the class link, never the ICI.

    ``op`` is ``"gather"`` (un-park: reconstruct the tensor from the
    class tier) or ``"slice"`` (park: each class shard keeps its chunk).
    """

    axis: str
    dim: int
    op: str = "gather"

    def __post_init__(self) -> None:
        if self.op not in ("gather", "slice"):
            raise ValueError(f"Transfer op must be gather|slice, got {self.op!r}")


Step = object


def _placement(spec: DTensorSpec, mesh_shape: Mapping[str, int]) -> List[Tuple[str, ...]]:
    from repro.axe.lower import pspec_of_layout

    p = pspec_of_layout(spec.layout, spec.shape, mesh_shape)
    out: List[Tuple[str, ...]] = []
    for i in range(len(spec.shape)):
        e = p[i] if i < len(p) else None
        if e is None:
            out.append(())
        elif isinstance(e, str):
            out.append((e,))
        else:
            out.append(tuple(e))
    return out


def infer_redistribution(
    src: DTensorSpec,
    dst: DTensorSpec,
    mesh_shape: Mapping[str, int],
    *,
    partial_axes: Sequence[str] = (),
) -> List[Step]:
    """Plan the collectives converting ``src`` placement into ``dst``.

    ``partial_axes``: mesh axes over which ``src`` holds *partial sums*
    (pending reduction) — these lower to ReduceScatter (when dst shards
    the axis) or AllReduce (when dst replicates it), matching Fig. 8.
    """
    if src.shape != dst.shape:
        raise ValueError(f"shape mismatch {src.shape} vs {dst.shape}")
    sp = _placement(src, mesh_shape)
    dp = _placement(dst, mesh_shape)

    plan: List[Step] = []
    # 1) pending reductions
    for ax in partial_axes:
        tgt_dim = next((i for i, axes in enumerate(dp) if ax in axes), None)
        if tgt_dim is not None and ax not in {a for axes in sp for a in axes}:
            plan.append(ReduceScatter(ax, tgt_dim))
            dp[tgt_dim] = tuple(a for a in dp[tgt_dim] if a != ax)  # satisfied
        else:
            plan.append(AllReduce(ax))

    src_loc = {a: i for i, axes in enumerate(sp) for a in axes}
    dst_loc = {a: i for i, axes in enumerate(dp) for a in axes}

    # 2) axis moves dim i -> dim j: all_to_all
    for ax, i in sorted(src_loc.items()):
        j = dst_loc.get(ax)
        if j is not None and j != i:
            plan.append(AllToAll(ax, i, j))
    # 3) axis dropped by dst: all_gather. Axes composed on one dim
    #    nest major→minor in placement order, so the tiled gathers must
    #    run minor-first — gathering the major axis first interleaves
    #    the minor-axis chunks out of mesh order.
    for i, axes in enumerate(sp):
        for ax in reversed(axes):
            if ax not in dst_loc:
                plan.append(AllGather(ax, i))
    # 4) axis introduced by dst from replication: local slice (no
    #    comm); composed axes slice major-first (placement order) so
    #    each inner slice subdivides the outer axis's chunk.
    for j, axes in enumerate(dp):
        for ax in axes:
            if ax not in src_loc:
                plan.append(DynamicSlice(ax, j))
    return plan


def plan_comm_bytes(
    plan: Sequence[Step],
    spec: DTensorSpec,
    mesh_shape: Mapping[str, int],
    itemsize: int,
) -> int:
    """Per-device communicated bytes of a plan (ring algorithms)."""
    import math

    total = math.prod(spec.shape) * itemsize
    n_dev = math.prod(mesh_shape.values()) or 1
    shard = total // n_dev
    out = 0
    for step in plan:
        if isinstance(step, AllGather):
            p = mesh_shape[step.axis]
            out += shard * (p - 1)
        elif isinstance(step, ReduceScatter):
            p = mesh_shape[step.axis]
            out += shard * (p - 1)
        elif isinstance(step, AllReduce):
            p = mesh_shape[step.axis]
            out += 2 * shard * (p - 1)
        elif isinstance(step, AllToAll):
            p = mesh_shape[step.axis]
            out += shard * (p - 1) // p
        # Transfer steps are class-crossing, not ICI: see plan_transfer_bytes
    return out


def plan_transfer_bytes(
    plan: Sequence[Step],
    spec: DTensorSpec,
    mesh_shape: Mapping[str, int],
    itemsize: int,
) -> int:
    """Per-device bytes crossing a device-class link (Transfer steps
    only). A gather moves every remote class shard in (``shard*(p-1)``,
    mirroring the ring AllGather); a park (``slice``) is a local chop —
    the page-out bytes are accounted where the data is actually written
    (serve.batcher), not here."""
    import math

    total = math.prod(spec.shape) * itemsize
    n_dev = math.prod(mesh_shape.values()) or 1
    shard = total // n_dev
    out = 0
    for step in plan:
        if isinstance(step, Transfer) and step.op == "gather":
            p = mesh_shape[step.axis]
            out += shard * (p - 1)
    return out


# ---------------------------------------------------------------------------
# lowering inside shard_map
# ---------------------------------------------------------------------------


def ring_all_gather(x: jax.Array, axis: str, dim: int) -> jax.Array:
    """Double-buffered ring all-gather: p-1 ``ppermute`` chunk rotations,
    each landed into the output with a dynamic-update-slice.

    Bit-identical to ``jax.lax.all_gather(x, axis, axis=dim, tiled=True)``
    (pure data movement, no arithmetic), but issued as a pipeline of
    neighbor exchanges the latency-hiding scheduler can interleave with
    compute issued after it — the async form ``max(comm, compute)``
    charging assumes (docs/overlap.md)."""

    p = jax.lax.axis_size(axis)
    if p == 1:
        return x
    idx = jax.lax.axis_index(axis)
    chunk = x.shape[dim]
    out = jnp.zeros(x.shape[:dim] + (chunk * p,) + x.shape[dim + 1 :], x.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, x, idx * chunk, axis=dim)
    buf = x
    perm = [(s, (s + 1) % p) for s in range(p)]
    for t in range(1, p):
        buf = jax.lax.ppermute(buf, axis, perm)
        src = (idx - t) % p
        out = jax.lax.dynamic_update_slice_in_dim(out, buf, src * chunk, axis=dim)
    return out


def lower_step(x: jax.Array, step: Step, *, overlap: bool = False) -> jax.Array:
    """Lower one plan step inside a shard_map body."""
    if isinstance(step, AllGather):
        if overlap:
            return ring_all_gather(x, step.axis, step.dim)
        return jax.lax.all_gather(x, step.axis, axis=step.dim, tiled=True)
    if isinstance(step, ReduceScatter):
        return jax.lax.psum_scatter(x, step.axis, scatter_dimension=step.dim, tiled=True)
    if isinstance(step, AllReduce):
        return jax.lax.psum(x, step.axis)
    if isinstance(step, AllToAll):
        return jax.lax.all_to_all(
            x, step.axis, split_axis=step.dst_dim, concat_axis=step.src_dim, tiled=True
        )
    if isinstance(step, DynamicSlice):

        idx = jax.lax.axis_index(step.axis)
        size = jax.lax.axis_size(step.axis)
        chunk = x.shape[step.dim] // size
        return jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=step.dim)
    if isinstance(step, Transfer):
        # Class-crossing movement lowers to the same SPMD primitives as
        # its homogeneous twin (the class tier mirrors the mesh), so
        # host-parked executables stay bit-comparable to all-accel runs;
        # only the *cost model* treats Transfer differently.
        if step.op == "gather":
            return jax.lax.all_gather(x, step.axis, axis=step.dim, tiled=True)
        return lower_step(x, DynamicSlice(step.axis, step.dim))
    raise TypeError(f"unknown step {step}")


def apply_plan(x: jax.Array, plan: Sequence[Step], *, overlap: bool = False) -> jax.Array:
    for step in plan:
        x = lower_step(x, step, overlap=overlap)
    return x
