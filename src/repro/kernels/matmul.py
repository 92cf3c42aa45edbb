"""Tiled MXU GEMM as an ``axe.program`` stage graph (paper §3.2/§3.4).

The kernel is written once as three scope-tagged stages:

* ``matmul/dot``  (BLOCK) — the functional single-tile body: one
  f32-accumulated ``jnp.dot``. Doubles as the whole-array XLA schedule
  at MESH scope (where GSPMD distributes it) and as the fallback when a
  tile is infeasible.
* ``matmul/tile`` (GRID)  — the Pallas launch: operand tilings derived
  the way the paper derives TensorEngine matmuls (group by (M,K),
  (K,N), (M,N); pick the largest admissible instruction tile; loop the
  remaining iters), realized by ``axe.lower.block_lowering`` (App. F
  direct-sum check) with K as the innermost "arbitrary" grid dim and a
  VMEM f32 scratch accumulating across K steps. Schedule key
  ``matmul/tile`` (blocks bm/bn/bk; variants kernel|xla).
* ``matmul/mac``  (BLOCK) — the per-grid-cell body on VMEM refs.

Dispatch by execution scope: MESH/BLOCK → ``dot``, DEVICE/GRID →
``tile``. Placement comes only from operand AxeSpecs (``arg_specs``).
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.axe.lower import block_lowering
from repro.axe.program import KernelFallbackWarning, program
from repro.core.blockspec import TilingError, check_tiling
from repro.core.scopes import Scope

matmul_program = program(
    "matmul", doc="C[M,N] = A[M,K] @ B[K,N] with f32 VMEM accumulation"
)


def _flops(args, kw) -> float:
    a, b = args[0], args[1]
    return 2.0 * a.shape[0] * a.shape[1] * b.shape[1]


@matmul_program.stage("dot", scope=Scope.BLOCK,
                      dispatch=(Scope.MESH, Scope.BLOCK))
def _dot(ctx, a, b, *, out_dtype=None):
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(
        out_dtype or a.dtype
    )


@matmul_program.stage("mac", scope=Scope.BLOCK)
def _mac(ctx, a_ref, b_ref, *refs, k_steps: int, fused: bool = False):
    *extra_refs, o_ref, acc_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        tile = acc_ref[...]
        if fused:
            # the fused epilogue runs on the f32 accumulator tile while
            # it is still in VMEM — the chain never round-trips HBM
            tile = ctx.epilogue.body(tile, *[r[...] for r in extra_refs])
        o_ref[...] = tile.astype(o_ref.dtype)


@matmul_program.stage(
    "tile", scope=Scope.GRID, entry=True,
    dispatch=(Scope.DEVICE, Scope.GRID),
    blocks=(("bm", 256), ("bn", 256), ("bk", 512)),
    variants=("kernel", "xla"),
    flops=_flops,
)
def _tile(ctx, a, b, *, out_dtype=None):
    out_dtype = out_dtype or a.dtype
    epi = ctx.epilogue

    def fallback(exc):
        """An infeasible tile: the caller's pinned kernel fails loudly;
        a resolved one runs the XLA dot, and says so when compiled."""
        if ctx.pinned:
            raise exc  # the unified error path
        if not ctx.interpret:
            warnings.warn(KernelFallbackWarning(
                f"matmul/tile {a.shape}x{b.shape}: {exc}; running the XLA dot"
            ), stacklevel=3)
        return finish(ctx.run("dot", a, b, out_dtype=out_dtype))

    def finish(out):
        """Functional epilogue application — the fallback whenever the
        chain cannot run inside the Pallas launch (XLA variant, non-2D
        operands, infeasible tile, extras not output-shaped)."""
        if epi is None:
            return out
        return epi.body(out.astype(jnp.float32), *epi.args).astype(out_dtype)

    if a.ndim != 2 or b.ndim != 2:
        return finish(ctx.run("dot", a, b, out_dtype=out_dtype))
    if ctx.impl != "kernel":
        return finish(ctx.run("dot", a, b, out_dtype=out_dtype))
    m, k = a.shape
    _, n = b.shape
    # the epilogue runs in-kernel only when every extra operand tiles
    # exactly like C; anything else applies functionally on the result
    inline = epi is not None and all(
        tuple(x.shape) == (m, n) for x in epi.args
    )
    bm = min(ctx.block("bm"), m)
    # a whole-row epilogue (norm) must see complete output rows per tile
    bn = n if (inline and epi.full_rows) else min(ctx.block("bn"), n)
    bk = min(ctx.block("bk"), k)
    try:
        # fail fast on infeasible output tiles (same precheck the legacy
        # dispatch made); A/B tilings are re-validated inside the launch
        check_tiling((m, n), (bm, bn), a.dtype, op="matmul/tile")
    except TilingError as exc:
        return fallback(exc)

    n_extras = len(epi.args) if inline else 0

    def make():
        def launch(a, b, *extras):
            m, k = a.shape
            _, n = b.shape
            a_low = block_lowering((m, k), (bm, bk), a.dtype,
                                   index_map=lambda i, j, kk: (i, kk),
                                   op="matmul.A")
            b_low = block_lowering((k, n), (bk, bn), b.dtype,
                                   index_map=lambda i, j, kk: (kk, j),
                                   op="matmul.B")
            o_low = block_lowering((m, n), (bm, bn), out_dtype,
                                   index_map=lambda i, j, kk: (i, j),
                                   op="matmul.C")
            e_lows = [
                block_lowering((m, n), (bm, bn), x.dtype,
                               index_map=lambda i, j, kk: (i, j),
                               op="matmul.epilogue")
                for x in extras
            ]
            k_steps = a_low.grid[1]
            return ctx.pallas_call(
                lambda *refs: ctx.run(
                    "mac", *refs, k_steps=k_steps, fused=bool(extras)
                ),
                grid=(a_low.grid[0], b_low.grid[1], k_steps),
                in_specs=[a_low.spec, b_low.spec] + [e.spec for e in e_lows],
                out_specs=o_low.spec,
                out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
                scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            )(a, b, *extras)

        return launch

    key = (bm, bn, bk, str(out_dtype), epi.tag if inline else None, n_extras)
    try:
        out = ctx.jit(key, make)(a, b, *(tuple(epi.args) if inline else ()))
    except TilingError as exc:
        return fallback(exc)
    return out if inline else finish(out)


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel launcher: the ``matmul/tile`` stage pinned to the
    Pallas variant. Unset block sizes resolve through the planner under
    the ``matmul/tile`` key."""
    blocks = {k: v for k, v in
              (("bm", block_m), ("bn", block_n), ("bk", block_k)) if v is not None}
    return matmul_program(
        a, b, stage="tile", impl="kernel", blocks=blocks or None,
        out_dtype=out_dtype, interpret=interpret,
    )
