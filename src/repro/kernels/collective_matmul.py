"""K-sharded GEMM + reduce-scatter as an ``axe.program`` MESH stage
(paper §4.2) — cross-device schedule choice (ring vs psum_scatter) is a
stage *variant* under the one tune key ``collective_matmul/kshard``,
not a separate op.

``a``: [M, K_local], ``b``: [K_local, N]; K is sharded over a mesh axis
(P devices). Output: rows scattered over the axis, [M / P, N] per
device. The axis comes from the operand AxeSpecs (the contraction-dim
placement of ``a``) or an explicit ``axis_name``.

Variants:

* ``psum_scatter`` — baseline: full local partial GEMM, then the
  collectives of the redistribution plan (``core.collective.
  infer_redistribution``: partial-sum spec → row-scattered spec, i.e.
  one ReduceScatter) — the cuBLAS+NCCL analogue.
* ``ring`` — M is chunked into P pieces; each step computes one chunk's
  partial GEMM (the BLOCK-scope ``partial`` stage) and accumulates into
  a rotating buffer (ppermute), so ICI transfer of chunk t overlaps the
  MXU work of chunk t+1 — the paper's fused GEMM+RS kernel, on ICI.

With neither pinned, the planner ranks the two schedules with the
roofline collective model (``repro.tune``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.axe.program import program
from repro.core.scopes import Scope


def derive_axis_name(a_spec) -> str:
    """The mesh axis K is sharded over, read off ``a``'s AxeSpec (the
    contraction dim is a's last dim)."""
    if a_spec is None:
        raise ValueError(
            "collective_matmul needs axis_name or an AxeSpec for `a` "
            "whose last (contraction) dim is sharded over one mesh axis"
        )
    k_axes = a_spec.placement()[-1]
    if len(k_axes) != 1:
        raise ValueError(
            f"a's contraction dim must be sharded over exactly one mesh "
            f"axis, got placement {k_axes} in {a_spec!r}"
        )
    return k_axes[0]


def _axis_of(kw, arg_specs) -> str:
    axis = kw.get("axis_name")
    if axis is not None:
        return axis
    return derive_axis_name(arg_specs[0] if arg_specs else None)


def _cm_key(args, kw, arg_specs=()):
    a, b = args[0], args[1]
    p = jax.lax.axis_size(_axis_of(kw, arg_specs))
    return {
        "shapes": (tuple(a.shape), tuple(b.shape), (p,)),
        "dtypes": (a.dtype, b.dtype),
    }


def _cm_flops(args, kw) -> float:
    a, b = args[0], args[1]
    return 2.0 * a.shape[0] * a.shape[1] * b.shape[1]


collective_matmul_program = program(
    "collective_matmul",
    doc="K-sharded GEMM with fused/unfused reduce-scatter schedules",
)


@collective_matmul_program.stage("partial", scope=Scope.BLOCK)
def _partial(ctx, a, b):
    """Local partial product in f32 (the per-device MXU work both
    cross-device schedules are built from)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _scatter_plan(shape, axis_name: str, p: int):
    """The collectives resolving a partial-sum [M, N] into row-scattered
    shards: drawn from the redistribution planner when the axis is a
    registered mesh axis, else the equivalent single ReduceScatter."""
    from repro.core import collective as coll

    mesh_shape = {axis_name: p}
    try:
        from repro.core.dtensor import DTensorSpec

        src = DTensorSpec.from_pspec(shape, (None, None), mesh_shape, "float32")
        dst = DTensorSpec.from_pspec(shape, (axis_name, None), mesh_shape, "float32")
        return coll.infer_redistribution(
            src, dst, mesh_shape, partial_axes=(axis_name,)
        )
    except ValueError:
        return [coll.ReduceScatter(axis_name, 0)]


@collective_matmul_program.stage(
    "kshard", scope=Scope.MESH, entry=True,
    variants=("ring", "psum_scatter"),
    key=_cm_key,
    flops=_cm_flops,
)
def _kshard(ctx, a, b, *, axis_name: str | None = None, out_dtype=None):
    from repro.core import collective as coll

    axis_name = axis_name if axis_name is not None else derive_axis_name(
        ctx.arg_specs[0] if ctx.arg_specs else None
    )
    out_dtype = out_dtype or a.dtype
    p = jax.lax.axis_size(axis_name)

    if ctx.impl != "ring" or p == 1:
        partial = ctx.run("partial", a, b)
        plan = _scatter_plan((a.shape[0], b.shape[1]), axis_name, p)
        # ctx.overlap selects the async lowerings (ring gathers) for any
        # data-movement steps of the plan — bit-identical, issue-only
        return coll.apply_plan(partial, plan, overlap=ctx.overlap).astype(out_dtype)

    m = a.shape[0]
    assert m % p == 0, f"M={m} must divide over {axis_name}={p}"
    chunk = m // p
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(t, acc):
        # the accumulator on device i at step t is destined for chunk
        # d = (i - t - 1) mod p (it still has to traverse the remaining
        # devices and land on device d with no permute after the last add)
        src = (idx + p - 1 - t) % p
        part = ctx.run(
            "partial",
            jax.lax.dynamic_slice_in_dim(a, src * chunk, chunk, axis=0),
            b,
        )
        acc = acc + part
        acc = jax.lax.cond(
            t < p - 1,
            lambda x: jax.lax.ppermute(x, axis_name, perm),
            lambda x: x,
            acc,
        )
        return acc

    acc = jnp.zeros((chunk, b.shape[1]), jnp.float32)
    acc = jax.lax.fori_loop(0, p, body, acc, unroll=True)
    return acc.astype(out_dtype)
