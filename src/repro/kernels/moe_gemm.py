"""Grouped (per-expert) GEMM for fused MoE layers (paper §4.1), as an
``axe.program`` stage graph.

The dispatched activations are a dense [E, C, d] tensor (E experts ×
C rows), so the expert FFN is a batched GEMM with per-expert weights
[E, d, f]:

* ``moe_gemm/einsum``      (BLOCK) — the functional oracle-shaped body
  (``ecd,edf->ecf``); the XLA variant and the MESH-scope dispatch.
* ``moe_gemm/expert_gemm`` (GRID)  — the Pallas launch tiling (C, f, d)
  per expert on the MXU, expert dim outermost "parallel". Schedule key
  ``moe_gemm/expert_gemm`` (blocks bc/bf/bd; variants kernel|xla).
* ``moe_gemm/mac``         (BLOCK) — the per-cell body on VMEM refs.

The second group GEMM (f -> d) reuses the same program with swapped
weight dims. The weight may be an :class:`ExpertView`, one layer of a
stacked expert weight, which ``expert_gemm`` reads where it lies.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.axe.lower import block_lowering
from repro.axe.program import KernelFallbackWarning, program
from repro.core.scopes import Scope


@jax.tree_util.register_pytree_node_class
class ExpertView:
    """Layer ``layer`` of a stacked expert weight ``stored [L, E, K, N]``:
    ``W = stored[layer]``, ``[E, K, N]``. ``shape``/``dtype``/``ndim``
    describe W. The ``moe_gemm/expert_gemm`` kernel reads W's tiles where
    they lie, the layer reaching its index map as a scalar-prefetched
    operand, so every layer shares one compiled kernel; any other
    consumer reads :meth:`materialize`. A pytree (the stored array is
    its one leaf)."""

    ndim = 3

    def __init__(self, stored, layer: int):
        self.stored = stored
        self.layer = layer

    @property
    def shape(self):
        return tuple(self.stored.shape[1:])

    @property
    def dtype(self):
        return self.stored.dtype

    def materialize(self):
        return self.stored[self.layer]

    def tree_flatten(self):
        return (self.stored,), (self.layer,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


moe_gemm_program = program(
    "moe_gemm", doc="per-expert batched GEMM [E,C,d] @ [E,d,f] -> [E,C,f]"
)


def _flops(args, kw) -> float:
    x, w = args[0], args[1]
    e, c, d = x.shape
    return 2.0 * e * c * d * w.shape[2]


@moe_gemm_program.stage("einsum", scope=Scope.BLOCK,
                        dispatch=(Scope.MESH, Scope.BLOCK))
def _einsum(ctx, x, w, *, out_dtype=None):
    if isinstance(w, ExpertView):
        w = w.materialize()
    return jnp.einsum(
        "ecd,edf->ecf", x.astype(jnp.float32), w.astype(jnp.float32)
    ).astype(out_dtype or x.dtype)


@moe_gemm_program.stage("mac", scope=Scope.BLOCK)
def _mac(ctx, x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _done():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


@moe_gemm_program.stage(
    "expert_gemm", scope=Scope.GRID, entry=True,
    dispatch=(Scope.DEVICE, Scope.GRID),
    blocks=(("bc", 128), ("bf", 256), ("bd", 512)),
    variants=("kernel", "xla"),
    flops=_flops,
)
def _expert_gemm(ctx, x, w, *, out_dtype=None):
    out_dtype = out_dtype or x.dtype
    if ctx.impl != "kernel":
        return ctx.run("einsum", x, w, out_dtype=out_dtype)
    e, c, d = x.shape
    _, _, f = w.shape
    bc = min(ctx.block("bc"), c)
    bf = min(ctx.block("bf"), f)
    bd = min(ctx.block("bd"), d)
    # a view hands its layer to W's index map as an SMEM scalar
    n_pre = int(isinstance(w, ExpertView))

    def make():
        def launch(*args):
            x, w = args[n_pre:]
            e, c, d = x.shape
            f = w.shape[-1]
            x_low = block_lowering((e, c, d), (1, bc, bd), x.dtype,
                                   index_map=lambda ei, ci, fi, ki, *_: (ei, ci, ki),
                                   op="moe_gemm.X")
            w_low = block_lowering((e, d, f), (1, bd, bf), w.dtype,
                                   index_map=lambda ei, ci, fi, ki, *_: (ei, ki, fi),
                                   op="moe_gemm.W")
            o_low = block_lowering((e, c, f), (1, bc, bf), out_dtype,
                                   index_map=lambda ei, ci, fi, ki, *_: (ei, ci, fi),
                                   op="moe_gemm.O")
            w_spec = w_low.spec
            if n_pre:  # w is the stored stack [L, E, d, f]: W's tiles inside it
                w_spec = pl.BlockSpec((None, 1, bd, bf),
                                      lambda ei, ci, fi, ki, layer: (layer[0], ei, ki, fi))
            k_steps = x_low.grid[2]
            return ctx.pallas_call(
                lambda *refs: ctx.run("mac", *refs[n_pre:], k_steps=k_steps),
                grid=(e, x_low.grid[1], w_low.grid[2], k_steps),
                in_specs=[x_low.spec, w_spec],
                out_specs=o_low.spec,
                out_shape=jax.ShapeDtypeStruct((e, c, f), out_dtype),
                scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
                dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
                num_scalar_prefetch=n_pre,
            )(*args)

        return launch

    from repro.core.blockspec import TilingError

    operands = (x, w)
    if n_pre:
        operands = (jnp.array([w.layer], jnp.int32), x, w.stored)
    try:
        return ctx.jit((bc, bf, bd, str(out_dtype), n_pre), make)(*operands)
    except TilingError as exc:
        if ctx.pinned:
            raise  # caller pinned the kernel: the unified error path
        if not ctx.interpret:
            warnings.warn(KernelFallbackWarning(
                f"moe_gemm/expert_gemm {x.shape}x{w.shape}: {exc}; running the XLA einsum"
            ), stacklevel=3)
        return ctx.run("einsum", x, w, out_dtype=out_dtype)


def moe_gemm_pallas(
    x: jax.Array,  # [E, C, d]
    w: jax.Array,  # [E, d, f]
    *,
    block_c: int | None = None,
    block_f: int | None = None,
    block_d: int | None = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel launcher: the ``moe_gemm/expert_gemm`` stage pinned to
    the Pallas variant (unset blocks resolve through the planner)."""
    blocks = {n: s for n, s in
              (("bc", block_c), ("bf", block_f), ("bd", block_d)) if s is not None}
    return moe_gemm_program(
        x, w, stage="expert_gemm", impl="kernel", blocks=blocks or None,
        out_dtype=out_dtype, interpret=interpret,
    )
