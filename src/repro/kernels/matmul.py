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

B may be a :class:`WeightView`: a 2-D weight read where it lies inside a
stored array (a layer of a stacked param, whole heads of a per-head
projection, or a transposed table). ``tile`` then carries the offset in
B's index map instead of reading a sliced copy; every other stage reads
the view's :meth:`~WeightView.materialize`, which XLA fuses into its op.
"""
from __future__ import annotations

import math
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.axe.lower import block_lowering
from repro.axe.program import KernelFallbackWarning, program
from repro.core.blockspec import (
    MATMUL_VMEM_BYTES, TilingError, check_tiling, matmul_k_blocks, matmul_vmem_bytes,
    vreg_atom,
)
from repro.core.scopes import Scope


@jax.tree_util.register_pytree_node_class
class WeightView:
    """A 2-D weight ``B [K, N]`` that lies inside a stored array:

    * layer offset — ``stored [L, K, N]``: ``B = stored[layer]``;
    * head split — ``stored [L, K, H, hd]``: ``B = stored[layer]`` with
      its heads side by side, ``[K, H·hd]``. Under the TPU's tiled
      layout that reshape is a relayout, not a bitcast;
    * transposed — ``stored [N, K]`` (a tied head) or ``[L, N, K]``:
      ``B = stored.T`` or ``stored[layer].T``.

    ``shape``/``dtype``/``ndim`` describe B, so a view passes every check
    a plain 2-D weight passes. The ``matmul/tile`` kernel reads B's tiles
    where they lie, through its index maps; any other consumer reads
    :meth:`materialize`. A pytree (the stored array is its one leaf), so
    it crosses ``jax.jit`` boundaries."""

    def __init__(self, stored, layer: Optional[int] = None, transposed: bool = False):
        self.stored = stored
        self.layer = layer
        self.transposed = transposed

    ndim = 2

    @property
    def shape(self) -> Tuple[int, int]:
        s = self.stored.shape[0 if self.layer is None else 1:]
        return (s[1], s[0]) if self.transposed else (s[0], math.prod(s[1:]))

    @property
    def dtype(self):
        return self.stored.dtype

    @property
    def heads(self) -> Optional[Tuple[int, int]]:
        """``(H, hd)`` of a head-split view, else None."""
        return tuple(self.stored.shape[2:]) if self.stored.ndim == 4 else None

    def materialize(self):
        """B as a plain array (a slice, relayout or transpose of ``stored``)."""
        w = self.stored if self.layer is None else self.stored[self.layer]
        return w.T if self.transposed else w.reshape(self.shape)

    def block_n(self, bn: int) -> int:
        """The column block the kernel can read in place nearest above
        ``bn``: whole heads, as many as the tiling rule admits in a block's
        second-minor dim (a multiple of the dtype's sublane count, or all)."""
        if self.heads is None:
            return bn
        h, hd = self.heads
        sub = vreg_atom(self.dtype)[0]
        return hd * next(g for g in range(1, h + 1)
                         if h % g == 0 and (g % sub == 0 or g == h) and g * hd >= bn)

    def block_spec(self, bk: int, bn: int):
        """The BlockSpec of B's ``(bk, bn)`` tile ``(kk, j)`` inside
        ``stored``. Index maps take the grid ids ``(i, j, kk)`` and, for a
        view with a layer, its scalar-prefetched ``layer`` ref."""
        if self.transposed:
            block, at = (bn, bk), lambda j, kk: (j, kk)
        elif self.heads is not None:
            hd = self.heads[1]
            block, at = (bk, bn // hd, hd), lambda j, kk: (kk, j, 0)
        else:
            block, at = (bk, bn), lambda j, kk: (kk, j)
        if self.layer is None:
            return pl.BlockSpec(block, lambda i, j, kk: at(j, kk))
        return pl.BlockSpec((None,) + block,
                            lambda i, j, kk, layer: (layer[0],) + at(j, kk))

    @classmethod
    def of_layer(cls, stacked, layer: int) -> "WeightView":
        """Layer ``layer`` of ``stacked [L, K, N]``. A weight narrower than
        a lane tile is stored K-minor under the TPU's default layout (the
        one with less padding), where the transposed stack is a bitcast:
        the view reads that."""
        if stacked.shape[-1] < vreg_atom(stacked.dtype)[1]:
            return cls(jnp.swapaxes(stacked, 1, 2), layer, transposed=True)
        return cls(stacked, layer)

    def tree_flatten(self):
        return (self.stored,), (self.layer, self.transposed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


matmul_program = program(
    "matmul", doc="C[M,N] = A[M,K] @ B[K,N] with f32 VMEM accumulation"
)


def _flops(args, kw) -> float:
    a, b = args[0], args[1]
    return 2.0 * a.shape[0] * a.shape[1] * b.shape[1]


@matmul_program.stage("dot", scope=Scope.BLOCK,
                      dispatch=(Scope.MESH, Scope.BLOCK))
def _dot(ctx, a, b, *, out_dtype=None):
    if isinstance(b, WeightView):
        b = b.materialize()
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(
        out_dtype or a.dtype
    )


@matmul_program.stage("mac", scope=Scope.BLOCK)
def _mac(ctx, a_ref, b_ref, *refs, k_steps: int, fused: bool = False,
         transposed: bool = False):
    *extra_refs, o_ref, acc_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if b_ref.ndim == 3:
        # whole heads [bk, heads, hd] of a head-split weight, laid side by
        # side in VMEM: the tile a plain kernel would read from a copy
        b = b_ref[...]
        acc_ref[...] += jnp.dot(
            a_ref[...], b.reshape(b.shape[0], b.shape[1] * b.shape[2]),
            preferred_element_type=jnp.float32,
        )
    elif transposed:
        # a transposed table's tile arrives [bn, bk]: contract both last dims
        acc_ref[...] += jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    else:
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
    view = b if isinstance(b, WeightView) else None
    bm = min(ctx.block("bm"), m)
    # a whole-row epilogue (norm) must see complete output rows per tile
    bn = n if (inline and epi.full_rows) else min(ctx.block("bn"), n)
    if view is not None:
        bn = view.block_n(bn)
    bk = min(ctx.block("bk"), k)
    n_extras = len(epi.args) if inline else 0
    if matmul_vmem_bytes(bm, bn, bk, a.dtype, c_tiles=1 + n_extras) > MATMUL_VMEM_BYTES:
        # a column block widened past the planned one (whole heads, whole
        # rows) takes the largest K block that still fits beside it
        bk = next(iter(matmul_k_blocks(k, bm, bn, a.dtype, c_tiles=1 + n_extras)), bk)
    try:
        # fail fast on infeasible output tiles (same precheck the legacy
        # dispatch made); A/B tilings are re-validated inside the launch
        check_tiling((m, n), (bm, bn), a.dtype, op="matmul/tile")
    except TilingError as exc:
        return fallback(exc)

    # everything the cached launcher reads besides its arguments is fixed
    # by its key: never the first call's view or layer
    is_view = view is not None
    transposed = is_view and view.transposed
    # a view with a layer hands the layer to B's index map as an SMEM
    # scalar, so every layer of a stack shares one compiled kernel
    n_pre = int(is_view and view.layer is not None)

    def make():
        def launch(*args):
            a, b, *extras = args[n_pre:]
            if is_view:  # b is the stored array: B's placement inside it
                b = WeightView(b, 0 if n_pre else None, transposed)
            m, k = a.shape
            _, n = b.shape
            a_low = block_lowering((m, k), (bm, bk), a.dtype,
                                   index_map=lambda i, j, kk, *_: (i, kk),
                                   op="matmul.A")
            b_low = block_lowering((k, n), (bk, bn), b.dtype,
                                   index_map=lambda i, j, kk, *_: (kk, j),
                                   op="matmul.B")
            o_low = block_lowering((m, n), (bm, bn), out_dtype,
                                   index_map=lambda i, j, kk, *_: (i, j),
                                   op="matmul.C")
            e_lows = [
                block_lowering((m, n), (bm, bn), x.dtype,
                               index_map=lambda i, j, kk, *_: (i, j),
                               op="matmul.epilogue")
                for x in extras
            ]
            b_spec = b.block_spec(bk, bn) if is_view else b_low.spec
            k_steps = a_low.grid[1]
            return ctx.pallas_call(
                lambda *refs: ctx.run(
                    "mac", *refs[n_pre:], k_steps=k_steps,
                    fused=bool(extras), transposed=transposed,
                ),
                grid=(a_low.grid[0], b_low.grid[1], k_steps),
                in_specs=[a_low.spec, b_spec] + [e.spec for e in e_lows],
                out_specs=o_low.spec,
                out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
                scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                num_scalar_prefetch=n_pre,
            )(*args)

        return launch

    key = (bm, bn, bk, str(out_dtype), epi.tag if inline else None, n_extras)
    operands = (a, b)
    if is_view:
        # the launch's B spec follows the view's placement
        key += (transposed, n_pre)
        operands = (a, view.stored)
        if n_pre:
            operands = (jnp.array([view.layer], jnp.int32),) + operands
    try:
        out = ctx.jit(key, make)(*operands, *(tuple(epi.args) if inline else ()))
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
