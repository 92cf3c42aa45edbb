"""``axe.compile`` — one Executable API from GraphSpec + LayoutPlan to
running numerics (docs/compile.md).

This is the surface the repo standardizes on: ``axe.compile(graph,
mesh, plan)`` turns a :class:`~repro.axe.graphs.GraphSpec` plus a
solved (or given) layout into a callable, jitted, pytree-in/pytree-out
function. The compiler:

1. **solves** the layout when ``plan is None`` (``repro.axe.solve``);
2. **binds** each graph op to a backend — the ``axe.program`` kernel
   programs (matmul / flash_attention / moe_gemm / rmsnorm) where one
   matches, jnp bodies otherwise — through the public
   :data:`OP_BACKENDS` table (:func:`register_op_backend`), mirroring
   ``propagate``'s rule registry; operand AxeSpecs ride along as
   ``arg_specs`` so every program stage resolves its schedule under the
   solved layout's signature (``repro.tune``);
3. **inserts** the redistribution collectives the plan recorded
   (``propagate.infer_redistribution``) between ops inside a single
   ``shard_map``, so the solver's comm estimates become real transfers
   — ``launch.dryrun --execute`` cross-checks the issued sequence
   against the solver's :class:`~repro.axe.solve.Decision` trace.

The body runs in DEVICE scope: program dispatches lower to Pallas
launches on TPU and resolve to their XLA variants (via the planner's
interpret-penalty ranking) on CPU — one binding, both backends.

``model_inputs`` maps a reference model param pytree
(``repro.models``) onto graph inputs + the auxiliary tensors the
execution attrs name, and ``model_executable`` / ``compiled_loss_fn``
are the consumer-facing constructors ``ServeEngine``,
``launch/train.py --solve`` and ``launch/dryrun.py --execute`` build
their forward passes from.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.axe.graphs import GraphSpec
from repro.axe.propagate import (
    LayoutPlan,
    OpNode,
    PlanEntry,
    compose_epilogue,
    epilogue_steps,
    step_node,
)
from repro.axe.solve import (
    SolveResult,
    evaluate_env,
    finalize_entries,
    producer_indices,
    redist_overlappable,
    solve,
)
from repro.axe.spec import AxeSpec
from repro.core import collective as coll
from repro.core.scopes import Scope, scope


class CompileError(ValueError):
    pass


# ---------------------------------------------------------------------------
# the op-backend registry (mirrors propagate._RULES)
# ---------------------------------------------------------------------------

#: op kind → backend callable ``(ctx, *local_operands) -> local output``
OP_BACKENDS: Dict[str, Callable] = {}


def register_op_backend(kind: str, fn: Optional[Callable] = None):
    """Register (or decorate) the execution backend for one op kind.

    The backend receives an :class:`ExecCtx` (node attrs, post-
    redistribution operand specs, auxiliary tensors, mesh helpers) and
    the operand arrays *as device-local shards inside the executable's
    shard_map*; it returns the local output shard matching the plan's
    output spec."""

    def deco(f: Callable) -> Callable:
        OP_BACKENDS[kind] = f
        return f

    return deco(fn) if fn is not None else deco


def op_backend(kind: str) -> Callable:
    try:
        return OP_BACKENDS[kind]
    except KeyError:
        raise CompileError(
            f"no execution backend for op kind {kind!r} "
            f"(registered: {sorted(OP_BACKENDS)}); add one with "
            f"compile.register_op_backend"
        ) from None


# ---------------------------------------------------------------------------
# execution context handed to backends
# ---------------------------------------------------------------------------


class ExecCtx:
    """What one op backend sees: the node, the operand specs *after*
    the plan's redistributions, the shared auxiliary tensors, and the
    mesh arithmetic helpers."""

    def __init__(self, node: OpNode, entry: PlanEntry, in_specs, aux, side,
                 shape_steps, mesh_shape, interpret: bool, *,
                 out_spec: Optional[AxeSpec] = None):
        self.node = node
        self.entry = entry
        self.in_specs = tuple(in_specs)
        #: the *segment* out spec — for a fused node's epilogue segments
        #: this overrides the entry's (final-chain) out spec
        self.out_spec: AxeSpec = entry.out_spec if out_spec is None else out_spec
        self._aux = aux
        self.side = side
        #: collective steps of the plan's shape-changing redistribution
        #: (MoE dispatch/combine own their exchange; everything else ())
        self.shape_steps = tuple(shape_steps)
        self.mesh_shape = dict(mesh_shape)
        self.interpret = interpret

    def attr(self, key: str, default=None):
        return self.node.attr(key, default)

    def aux(self, name: Optional[str], *, required: bool = True):
        if name is None:
            return None
        arr = self._aux.get(name)
        if arr is None and required:
            raise CompileError(
                f"{self.node.name}: auxiliary tensor {name!r} missing from "
                f"the executable's params (see compile.model_inputs)"
            )
        return arr

    def ext(self, axes: Sequence[str]) -> int:
        return math.prod(self.mesh_shape[a] for a in axes) if axes else 1

    def out_spec_dtype(self):
        return jnp.dtype(self.out_spec.dtype)

    def axis_index(self, axes: Sequence[str]):
        """This device's combined shard index over ``axes`` (placement
        order: first axis is major — the AxeSpec iter order)."""
        idx = 0
        for a in axes:
            idx = idx * self.mesh_shape[a] + jax.lax.axis_index(a)
        return idx


# ---------------------------------------------------------------------------
# default backends
# ---------------------------------------------------------------------------


@register_op_backend("matmul")
def _exec_matmul(ctx: ExecCtx, a, b):
    """2D matmuls bind to the ``matmul`` program, grouped (rank-3
    weight) matmuls to ``moe_gemm``; a K-sharded local dot yields the
    partial sums the out spec's ``partial`` axes announce."""
    from repro.kernels import programs

    if b.ndim == 3:
        return programs.moe_gemm(
            a, b, arg_specs=ctx.in_specs, interpret=ctx.interpret
        )
    return programs.matmul(a, b, arg_specs=ctx.in_specs, interpret=ctx.interpret)


@register_op_backend("norm")
def _exec_norm(ctx: ExecCtx, x):
    from repro.kernels import programs

    w = ctx.aux(ctx.attr("weight"), required=False)
    if w is None:
        w = jnp.ones((x.shape[-1],), x.dtype)
    return programs.rmsnorm(x, w, arg_specs=ctx.in_specs[:1], interpret=ctx.interpret)


@register_op_backend("elementwise")
def _exec_elementwise(ctx: ExecCtx, *xs):
    fn = ctx.attr("fn", "add")
    if fn == "add":
        out = xs[0]
        for x in xs[1:]:
            out = out + x
        return out
    if fn == "swiglu":
        return jax.nn.silu(xs[0]) * xs[1]
    if fn == "mul_silu":
        return xs[0] * jax.nn.silu(xs[1])
    if fn == "gelu":
        return jax.nn.gelu(xs[0])
    raise CompileError(f"{ctx.node.name}: unknown elementwise fn {fn!r}")


@register_op_backend("embed")
def _exec_embed(ctx: ExecCtx, tok, table):
    """Token lookup; a vocab-sharded table answers only its own rows
    (zeros elsewhere), producing the partial sums the spec declares."""
    v_axes = ctx.in_specs[1].placement()[0]
    if not v_axes:
        return table[tok]
    v_local = table.shape[0]
    start = ctx.axis_index(v_axes) * v_local
    idx = tok - start
    valid = (idx >= 0) & (idx < v_local)
    safe = jnp.clip(idx, 0, v_local - 1)
    return jnp.where(valid[:, None], table[safe], jnp.zeros((), table.dtype))


@register_op_backend("reshape")
def _exec_reshape(ctx: ExecCtx, x):
    """Value-preserving boundaries. ``select`` attrs mark the model
    boundaries with real math: q/k/v head split (+ qk-norm + rope, per
    the reference models) and the head merge before the output
    projection; plain reshapes map locally."""
    sel = ctx.attr("select")
    out_local = ctx.out_spec.local_shape()
    if sel in ("q", "k", "v"):
        from repro.models.common import rmsnorm, rope

        b_l, n_l, s, hd = out_local
        y = x.reshape(b_l, s, n_l, hd)
        w = ctx.aux(ctx.attr("norm_weight"), required=False)
        if w is not None:
            y = rmsnorm(y, w)
        theta = ctx.attr("rope_theta")
        if theta:
            y = rope(y, jnp.arange(s)[None, :], theta)
        return y.transpose(0, 2, 1, 3)
    if sel == "merge_heads":
        t_l, nhd_l = out_local
        return x.transpose(0, 2, 1, 3).reshape(t_l, nhd_l)
    return x.reshape(out_local)


@register_op_backend("attention")
def _exec_attention(ctx: ExecCtx, q, k, v):
    """Binds to the ``flash_attention`` program; GQA kv heads broadcast
    locally (aligned to this device's query-head chunk when only the
    query heads are sharded)."""
    q_spec, k_spec = ctx.in_specs[0], ctx.in_specs[1]
    if q_spec.placement()[2]:
        raise CompileError(
            f"{ctx.node.name}: sharded query sequence is not executable "
            f"(causal masking needs local positions); got {q_spec!r}"
        )
    h_axes = q_spec.placement()[1]
    kv_axes = k_spec.placement()[1]
    g = q_spec.shape[1] // k_spec.shape[1]
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        if h_axes and not kv_axes:
            start = ctx.axis_index(h_axes) * q.shape[1]
            k = jax.lax.dynamic_slice_in_dim(k, start, q.shape[1], axis=1)
            v = jax.lax.dynamic_slice_in_dim(v, start, q.shape[1], axis=1)
        elif h_axes and kv_axes != h_axes:
            raise CompileError(
                f"{ctx.node.name}: query/kv head shardings disagree "
                f"({h_axes} vs {kv_axes})"
            )
    # the trainable wrapper runs the flash program forward and a
    # recompute backward, so compiled executables stay differentiable
    # (compiled_loss_fn / launch.train --solve)
    from repro.kernels.flash_attention import flash_attention_trainable

    return flash_attention_trainable(
        q, k, v,
        bool(ctx.attr("causal", True)),
        ctx.attr("window"),
        None,
        ctx.interpret,
    )


@register_op_backend("moe_dispatch")
def _exec_moe_dispatch(ctx: ExecCtx, x):
    """Capacity routing on this device's token shard, then the plan's
    expert-axis exchange: AllToAll steps swap capacity buffers with the
    other token shards on the axis (classic expert parallelism);
    DynamicSlice steps keep only this device's expert chunk."""
    from repro.models import moe as moe_mod

    e = int(ctx.attr("experts"))
    c = int(ctx.attr("capacity"))
    k = int(ctx.attr("experts_per_tok", 1))
    router = ctx.aux(ctx.attr("router"))
    t_axes = ctx.in_specs[0].placement()[0]
    c_src = c // ctx.ext(t_axes)
    buf, meta = moe_mod.local_dispatch(
        x, router, num_experts=e, experts_per_tok=k, capacity=c_src
    )
    for step in ctx.shape_steps:
        if isinstance(step, coll.AllToAll):
            buf = jax.lax.all_to_all(
                buf, step.axis, split_axis=0, concat_axis=1, tiled=True
            )
        elif isinstance(step, coll.DynamicSlice):
            p = ctx.mesh_shape[step.axis]
            chunk = buf.shape[0] // p
            buf = jax.lax.dynamic_slice_in_dim(
                buf, jax.lax.axis_index(step.axis) * chunk, chunk, axis=0
            )
        else:  # pragma: no cover - the rule only emits the two above
            raise CompileError(f"{ctx.node.name}: unexpected dispatch step {step}")
    ctx.side[ctx.node.out] = {
        "meta": meta, "tokens": x.shape[0], "d": x.shape[1],
    }
    return buf


@register_op_backend("moe_combine")
def _exec_moe_combine(ctx: ExecCtx, oe):
    """Unwinds the dispatch exchange (reverse step order), then combines
    this device's own tokens with the routing metadata the dispatch
    backend stashed."""
    from repro.models import moe as moe_mod

    side = ctx.side.get(ctx.attr("dispatch"))
    if side is None:
        raise CompileError(
            f"{ctx.node.name}: no dispatch state — moe_combine is only "
            f"executable in a graph whose 'dispatch' attr names the "
            f"matching moe_dispatch node"
        )
    for step in reversed(ctx.shape_steps):
        if isinstance(step, coll.AllToAll):
            oe = jax.lax.all_to_all(
                oe, step.axis, split_axis=1, concat_axis=0, tiled=True
            )
        elif isinstance(step, coll.AllGather):
            oe = jax.lax.all_gather(oe, step.axis, axis=step.dim, tiled=True)
        else:  # pragma: no cover
            raise CompileError(f"{ctx.node.name}: unexpected combine step {step}")
    y = moe_mod.local_combine(oe, side["meta"], side["tokens"], side["d"])
    return y.astype(ctx.out_spec_dtype())


@register_op_backend("ssm_mix")
def _exec_ssm_mix(ctx: ExecCtx, xz, bb, cc, dt_raw):
    """The Mamba2 SSD mixer, reusing the reference ``models.ssm`` math
    (causal conv → silu → chunked SSD scan → D skip). The inner dim may
    be head-sharded: this device computes its head chunk, slicing the
    replicated auxiliaries (conv filter, dt bias, A, D) to match."""
    from repro.models import ssm as ssm_mod

    seq = int(ctx.attr("seq"))
    hd = int(ctx.attr("head_dim"))
    di = int(ctx.attr("d_inner"))
    n = int(ctx.attr("state"))
    t_l, di_l = xz.shape
    b_l = t_l // seq
    h_l = di_l // hd

    conv_w = ctx.aux(ctx.attr("conv_w"))
    dt_bias = ctx.aux(ctx.attr("dt_bias"))
    a_log = ctx.aux(ctx.attr("A_log"))
    d_skip = ctx.aux(ctx.attr("D"))
    di_axes = ctx.in_specs[0].placement()[1]
    if di_axes:
        idx = ctx.axis_index(di_axes)
        conv_x = jax.lax.dynamic_slice_in_dim(
            conv_w[:, :di], idx * di_l, di_l, axis=1
        )
        dt_bias = jax.lax.dynamic_slice_in_dim(dt_bias, idx * h_l, h_l, axis=0)
        a_log = jax.lax.dynamic_slice_in_dim(a_log, idx * h_l, h_l, axis=0)
        d_skip = jax.lax.dynamic_slice_in_dim(d_skip, idx * h_l, h_l, axis=0)
    else:
        conv_x = conv_w[:, :di]
    w_cat = jnp.concatenate(
        [conv_x, conv_w[:, di: di + n], conv_w[:, di + n:]], axis=-1
    )

    u = jnp.concatenate([xz, bb, cc], axis=-1).reshape(b_l, seq, -1)
    u = jax.nn.silu(ssm_mod._causal_conv(u, w_cat))
    xs = u[..., :di_l].reshape(b_l, seq, h_l, hd)
    bs = u[..., di_l: di_l + n]
    cs = u[..., di_l + n:]
    dt3 = dt_raw.reshape(b_l, seq, -1).astype(jnp.float32)
    if di_axes:
        dt3 = jax.lax.dynamic_slice_in_dim(dt3, idx * h_l, h_l, axis=2)
    dt = jax.nn.softplus(dt3 + dt_bias)
    a_neg = -jnp.exp(a_log)
    y, _ = ssm_mod.ssd_scan(xs, dt, a_neg, bs, cs)
    y = y + xs.astype(jnp.float32) * d_skip[:, None]
    return y.reshape(t_l, di_l).astype(ctx.out_spec_dtype())


@register_op_backend("decode_select")
def _exec_decode_select(ctx: ExecCtx, x, pos):
    """The decode-time q/k/v boundary: head split + qk-norm + rope at
    the *runtime* per-slot positions (the prefill ``reshape`` select
    ropes at static ``arange(seq)`` positions; decode cannot)."""
    from repro.models.common import rmsnorm, rope

    b_l, h_l, _one, hd = ctx.out_spec.local_shape()
    y = x.reshape(b_l, 1, h_l, hd)
    w = ctx.aux(ctx.attr("norm_weight"), required=False)
    if w is not None:
        y = rmsnorm(y, w)
    theta = ctx.attr("rope_theta")
    if theta:
        y = rope(y, pos[:, None], theta)
    return y.transpose(0, 2, 1, 3)


@register_op_backend("cache_update")
def _exec_cache_update(ctx: ExecCtx, cache, new, pos):
    """Write one token into the cache at each slot's own position
    (ring buffers wrap). A per-slot one-hot select rather than a
    dynamic-update-slice: every slot in the batch may sit at a
    different depth under continuous batching."""
    w = cache.shape[1]
    write = (pos % w) if ctx.attr("ring") else pos
    oh = (jnp.arange(w, dtype=jnp.int32)[None, :] == write[:, None])
    token = new.transpose(0, 2, 1, 3).astype(cache.dtype)  # [B, 1, KV, hd]
    return jnp.where(oh[:, :, None, None], token, cache)


@register_op_backend("decode_attention")
def _exec_decode_attention(ctx: ExecCtx, q, k, v, pos):
    """Single-token attention over the laid-out cache, bound to the
    ``flash_attention/decode`` GRID stage; GQA kv heads broadcast
    locally when only the query heads are sharded (mirroring the
    prefill ``attention`` backend)."""
    from repro.kernels.flash_attention import flash_decode_pallas

    q_spec, k_spec = ctx.in_specs[0], ctx.in_specs[1]
    h_axes = q_spec.placement()[1]
    kv_axes = k_spec.placement()[2]
    b_l, h_l, _one, hd = q.shape
    kv_l = k.shape[2]
    g = q_spec.shape[1] // k_spec.shape[2]
    if h_axes and kv_axes and tuple(h_axes) != tuple(kv_axes):
        raise CompileError(
            f"{ctx.node.name}: query/kv head shardings disagree "
            f"({h_axes} vs {kv_axes})"
        )
    if h_axes and not kv_axes and g > 1:
        # kv replicated, query heads sharded: expand the cache to
        # per-query-head rows and keep this device's head chunk
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        start = ctx.axis_index(h_axes) * h_l
        k = jax.lax.dynamic_slice_in_dim(k, start, h_l, axis=2)
        v = jax.lax.dynamic_slice_in_dim(v, start, h_l, axis=2)
        kv_l = h_l
    g_l = h_l // kv_l
    qg = q.reshape(b_l, kv_l, g_l, hd)      # heads grouped per kv head
    kc = k.transpose(0, 2, 1, 3)            # [B, KV, W, hd]
    vc = v.transpose(0, 2, 1, 3)
    out = flash_decode_pallas(
        qg, kc, vc, pos,
        ring=bool(ctx.attr("ring")), interpret=ctx.interpret,
    )
    return out.reshape(b_l, h_l, 1, hd)


@register_op_backend("ssm_decode")
def _exec_ssm_decode(ctx: ExecCtx, xz, bb, cc, dt_raw, ssm_state, conv_state):
    """One recurrent step of the SSD mixer — the exact
    ``models.ssm.ssd_decode`` math on the cache-in state tensors; the
    advanced states are stashed on the side channel for the
    ``side_output`` boundary nodes."""
    hd = int(ctx.attr("head_dim"))
    di = int(ctx.attr("d_inner"))
    n = int(ctx.attr("state"))
    b_l = xz.shape[0]
    conv_w = ctx.aux(ctx.attr("conv_w"))
    dt_bias = ctx.aux(ctx.attr("dt_bias"))
    a_log = ctx.aux(ctx.attr("A_log"))
    d_skip = ctx.aux(ctx.attr("D"))

    u = jnp.concatenate([xz, bb, cc], axis=-1)
    hist = jnp.concatenate([conv_state, u[:, None]], axis=1)
    conv_out = jnp.einsum(
        "bkc,kc->bc", hist.astype(jnp.float32), conv_w.astype(jnp.float32)
    )
    u_act = jax.nn.silu(conv_out)
    xs = u_act[:, :di]
    bs = u_act[:, di: di + n]
    cs = u_act[:, di + n:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
    lam = jnp.exp(dt * -jnp.exp(a_log))
    xh = xs.reshape(b_l, -1, hd)
    s_new = ssm_state * lam[:, :, None, None] + jnp.einsum(
        "bn,bhp,bh->bhnp", bs, xh, dt
    )
    y = jnp.einsum("bn,bhnp->bhp", cs, s_new) + xh * d_skip[:, None]
    ctx.side[ctx.node.out] = {
        "ssm": s_new,
        "conv": hist[:, 1:].astype(conv_state.dtype),
    }
    return y.reshape(b_l, di).astype(ctx.out_spec_dtype())


@register_op_backend("side_output")
def _exec_side_output(ctx: ExecCtx, _x):
    """Surface a tensor the producing op stashed on the side channel
    (the SSD mixer's advanced states) as a graph output."""
    side = ctx.side.get(ctx.attr("side"))
    if side is None:
        raise CompileError(
            f"{ctx.node.name}: no side state — side_output is only "
            f"executable in a graph whose 'side' attr names an earlier "
            f"node output with stashed state"
        )
    return side[ctx.attr("channel")]


# ---------------------------------------------------------------------------
# the Executable
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LoweredOp:
    """One row of the executable's deterministic lowering trace."""

    op: str
    kind: str
    backend: str
    out_spec: str
    collectives: Tuple[Tuple[str, Tuple[str, ...]], ...]  # (operand, steps)
    comm_bytes: int
    schedule: Optional[str] = None
    #: operands whose collectives the overlap schedule issues one entry
    #: early, hiding them under the previous op's compute (docs/overlap.md)
    prefetched: Tuple[str, ...] = ()

    def describe(self) -> str:
        cols = "; ".join(f"{o}:{'+'.join(s)}" for o, s in self.collectives)
        sched = f"  sched={self.schedule}" if self.schedule else ""
        comm = f"  comm={self.comm_bytes}B" if self.comm_bytes else ""
        pre = (f"  prefetch=[{', '.join(self.prefetched)}]"
               if self.prefetched else "")
        return f"{self.op} [{self.kind} -> {self.backend}]{sched}{comm}{pre}" + (
            f"  [{cols}]" if cols else ""
        )


def _backend_name(node: OpNode, in_specs: Sequence[AxeSpec] = ()) -> str:
    if node.kind == "matmul":
        grouped = len(in_specs) > 1 and len(in_specs[1].shape) == 3
        base = "program:moe_gemm" if grouped else "program:matmul"
    elif node.kind == "attention":
        base = "program:flash_attention"
    elif node.kind == "decode_attention":
        base = "program:flash_attention/decode"
    elif node.kind == "norm":
        base = "program:rmsnorm"
    elif node.kind == "finalize":
        base = "collective"
    else:
        base = f"jnp:{node.kind}"
    steps = epilogue_steps(node)
    if steps:
        base += "+epi:" + "+".join(str(s[0]) for s in steps)
    return base


#: attr keys whose values name auxiliary (replicated) input tensors
_AUX_ATTRS = ("weight", "norm_weight", "router", "dt_bias", "A_log", "D", "conv_w")


class Executable:
    """A compiled graph: callable pytree-in/pytree-out jitted function.

    ``exe(params, *activations)`` — ``params`` maps graph input names
    (role ``param``) and auxiliary names to arrays; activations are
    positional, in graph declaration order. Introspection surfaces:
    :attr:`lowering_trace` (deterministic per plan),
    :meth:`collective_sequence` (the redistribution steps the body
    issues, for the dryrun cross-check), and :attr:`plan`.
    """

    def __init__(self, graph: GraphSpec, mesh, plan: LayoutPlan,
                 assignment: Mapping[str, AxeSpec], *,
                 interpret: Optional[bool] = None,
                 solve_result: Optional[SolveResult] = None,
                 overlap: bool = False):
        self.graph = graph
        self.mesh = mesh
        self.plan = plan
        self.assignment = dict(assignment)
        self.solve_result = solve_result
        self.overlap = bool(overlap)
        self.interpret = (
            jax.default_backend() != "tpu" if interpret is None else bool(interpret)
        )
        if mesh is not None:
            mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            if mesh_shape != graph.space.mesh_shape:
                raise CompileError(
                    f"mesh {mesh_shape} does not match the graph space "
                    f"{graph.space.mesh_shape}"
                )

        self.activation_names = tuple(
            m.name for m in graph.inputs.values() if m.role == "activation"
        )
        self.param_names = tuple(
            m.name for m in graph.inputs.values() if m.role != "activation"
        )
        aux: List[str] = []
        for node in graph.nodes:
            # a fused node's epilogue steps carry the absorbed ops'
            # attrs — their auxiliary tensors are still required
            subs = (node,) + tuple(step_node(s) for s in epilogue_steps(node))
            for sub in subs:
                for key in _AUX_ATTRS:
                    name = sub.attr(key)
                    if name is not None and name not in aux:
                        aux.append(name)
        self.aux_names: Tuple[str, ...] = tuple(aux)
        self.outputs = graph.outputs()

        # output specs: the finalize entries' resolved specs win
        self._out_specs: Dict[str, AxeSpec] = {
            name: plan.env[name] for name in self.outputs
        }
        for e in plan.entries:
            if e.op.kind == "finalize":
                self._out_specs[e.op.out] = e.out_spec

        # the overlap schedule: hoist every overlappable redistribution
        # (repro.axe.solve.redist_overlappable — the same predicate the
        # solver's max(comm, compute) objective charges) one entry
        # earlier, so the body issues it before the previous op's
        # compute and the collective's latency hides under it.
        # _prefetch: issue slot -> [(consumer entry idx, redistribution)];
        # _hoisted: {(consumer entry idx, operand)} consumed from the
        # prefetch buffer instead of re-issued in place.
        self._prefetch: Dict[int, List] = {}
        self._hoisted: set = set()
        if self.overlap:
            producer = producer_indices(graph.nodes)
            for i, e in enumerate(plan.entries):
                if e.op.kind == "finalize":
                    continue
                for r in e.redistributions:
                    if redist_overlappable(r, i, e.op, producer):
                        self._prefetch.setdefault(i - 1, []).append((i, r))
                        self._hoisted.add((i, r.operand))

        self.lowering_trace: Tuple[LoweredOp, ...] = tuple(
            self._lower_entry(e, i) for i, e in enumerate(plan.entries)
        )
        self._issued: List[Tuple[str, str, Tuple[str, ...]]] = []
        self._jitted = None
        #: the FusionReport when the graph came through fuse_graph
        #: (set by compile(..., fuse=True) / model_executable)
        self.fusion_report = None
        self.cotune_report = None

    # -- introspection ---------------------------------------------------
    def _lower_entry(self, entry: PlanEntry, idx: int) -> LoweredOp:
        from repro.tune import planner as tune_planner

        node = entry.op
        sched = None
        in_specs: Tuple[AxeSpec, ...] = ()
        if node.kind != "finalize":
            # plan schedules from the POST-redistribution specs — the
            # local problem + layout signature the program dispatch
            # actually resolves under at trace time
            in_specs = entry.input_specs(self.plan.env)
            sp = tune_planner.plan_from_specs(node.kind, in_specs, backend=None)
            if sp is not None and sp.schedule is not None:
                sched = f"{sp.op}={sp.schedule.describe()}"
        return LoweredOp(
            op=node.name,
            kind=node.kind,
            backend=_backend_name(node, in_specs),
            out_spec=entry.out_spec.signature(),
            collectives=tuple(
                (r.operand, tuple(type(s).__name__ for s in r.steps))
                for r in entry.redistributions if r.steps
            ),
            comm_bytes=entry.comm_bytes,
            schedule=sched,
            prefetched=tuple(
                op for (j, op) in sorted(self._hoisted) if j == idx
            ),
        )

    def collective_sequence(self) -> Tuple[Tuple[str, str, Tuple[str, ...]], ...]:
        """Every redistribution the body issues, in execution order:
        ``(op, operand, step type names)``. Under the overlap schedule a
        hoisted collective appears at its *issue* slot (one entry early),
        still attributed to the consuming op — this is exactly the order
        ``_body`` issues, so the dryrun issued==planned cross-check holds
        in both modes."""
        if not self._prefetch:
            return tuple(
                (row.op, operand, steps)
                for row in self.lowering_trace
                for operand, steps in row.collectives
            )
        entries = self.plan.entries
        seq: List[Tuple[str, str, Tuple[str, ...]]] = []
        for i, row in enumerate(self.lowering_trace):
            for tgt, r in self._prefetch.get(i, ()):
                seq.append((entries[tgt].op.name, r.operand,
                            tuple(type(s).__name__ for s in r.steps)))
            for operand, steps in row.collectives:
                if (i, operand) in self._hoisted:
                    continue
                seq.append((row.op, operand, steps))
        return tuple(seq)

    @property
    def observed_collectives(self):
        """The collectives the traced body actually issued (populated on
        first call; the dryrun ``--execute`` cross-check compares this
        against :meth:`collective_sequence` and the Decision trace)."""
        return tuple(self._issued)

    def input_spec(self, name: str) -> AxeSpec:
        return self.plan.env[name]

    def describe(self) -> str:
        lines = [
            f"executable over {self.graph.space.signature()}: "
            f"{len(self.plan.entries)} ops, "
            f"{self.plan.total_comm_bytes} comm B/dev"
        ]
        lines += ["  " + row.describe() for row in self.lowering_trace]
        return "\n".join(lines)

    # -- execution -------------------------------------------------------
    def _ordered_inputs(self, params: Mapping[str, Any], acts: Sequence[Any]):
        if len(acts) != len(self.activation_names):
            raise CompileError(
                f"expected {len(self.activation_names)} activation inputs "
                f"{self.activation_names}, got {len(acts)}"
            )
        arrays = list(acts)
        for name in self.param_names:
            if name not in params:
                raise CompileError(
                    f"graph input {name!r} missing from params (have "
                    f"{sorted(params)[:8]}...)"
                )
            arrays.append(params[name])
        for name in self.aux_names:
            if name not in params:
                raise CompileError(f"auxiliary tensor {name!r} missing from params")
            arrays.append(params[name])
        for name, arr in zip(self.activation_names + self.param_names, arrays):
            want = self.graph.inputs[name].shape
            if tuple(arr.shape) != want:
                raise CompileError(
                    f"input {name!r}: expected shape {want}, got {tuple(arr.shape)}"
                )
        return arrays

    def _body(self, *arrays):
        names = self.activation_names + self.param_names
        env: Dict[str, Any] = dict(zip(names, arrays[: len(names)]))
        aux = dict(zip(self.aux_names, arrays[len(names):]))
        self._issued.clear()
        side: Dict[str, Any] = {}
        mesh_shape = self.graph.space.mesh_shape

        prefetched: Dict[Tuple[int, str], Any] = {}
        with scope(Scope.DEVICE):
            for ei, entry in enumerate(self.plan.entries):
                node = entry.op
                # issue the collectives scheduled to hide under THIS
                # entry's compute (each feeds a later entry; its input
                # is already final — see redist_overlappable)
                # interpret mode (CPU) keeps the monolithic lowerings —
                # the double-buffered ring costs extra primitives with
                # no latency to hide there; the schedule still reorders
                # issue, which is what the bench A/B measures. On real
                # accelerators the ring form engages (same dispatch
                # convention as the program stages' XLA variants).
                for tgt, r in self._prefetch.get(ei, ()):
                    prefetched[(tgt, r.operand)] = coll.apply_plan(
                        env[r.operand], r.steps, overlap=not self.interpret
                    )
                    self._issued.append(
                        (self.plan.entries[tgt].op.name, r.operand,
                         tuple(type(s).__name__ for s in r.steps))
                    )
                if node.kind == "finalize":
                    x = env[node.out]
                    for r in entry.redistributions:
                        x = coll.apply_plan(x, r.steps)
                        self._issued.append(
                            (node.name, r.operand,
                             tuple(type(s).__name__ for s in r.steps))
                        )
                    env[node.out] = x
                    continue
                vals = {nm: env[nm] for nm in node.inputs}
                specs = {nm: self.plan.env[nm] for nm in node.inputs}
                shape_steps = ()
                internal: Dict[str, List] = {}
                for r in entry.redistributions:
                    if r.operand not in vals:
                        # a fused chain intermediate (not a node input):
                        # the fused runner applies it between segments
                        internal.setdefault(r.operand, []).append(r)
                    elif (ei, r.operand) in self._hoisted:
                        # issued one entry early; consume the buffer
                        # (already recorded in _issued at the issue slot)
                        vals[r.operand] = prefetched.pop((ei, r.operand))
                        specs[r.operand] = r.dst
                        continue
                    elif r.dst.shape == r.src.shape:
                        vals[r.operand] = coll.apply_plan(vals[r.operand], r.steps)
                        specs[r.operand] = r.dst
                    else:
                        # shape-changing exchange: the op backend
                        # owns these steps (MoE dispatch/combine)
                        shape_steps = r.steps
                    if r.steps:
                        self._issued.append(
                            (node.name, r.operand,
                             tuple(type(s).__name__ for s in r.steps))
                        )
                if epilogue_steps(node):
                    out = self._run_fused(node, entry, vals, specs,
                                          internal, aux, side, mesh_shape)
                else:
                    ins = [vals[nm] for nm in node.inputs]
                    in_specs = [specs[nm] for nm in node.inputs]
                    ctx = ExecCtx(node, entry, in_specs, aux, side, shape_steps,
                                  mesh_shape, self.interpret)
                    out = op_backend(node.kind)(ctx, *ins)
                want = entry.out_spec.local_shape()
                if tuple(out.shape) != tuple(want):
                    raise CompileError(
                        f"{node.name} [{node.kind}]: backend produced local "
                        f"shape {tuple(out.shape)}, plan says {tuple(want)}"
                    )
                env[node.out] = out
        outs = tuple(env[o] for o in self.outputs)
        return outs[0] if len(outs) == 1 else outs

    # -- fused-epilogue execution (axe.passes, docs/passes.md) -----------
    def _run_fused(self, node, entry, vals, specs, internal, aux, side,
                   mesh_shape):
        """Execute a node carrying a fused epilogue: run the base op's
        backend, then each absorbed step's backend on the evolving chain
        value, applying the plan's *internal* redistributions (chain
        tensors that no longer exist in the fused graph) between
        segments. A 2-D matmul base with an elementwise-only chain and
        no internal moves instead runs the chain inside the kernel on
        the f32 accumulator tile (:func:`_kernel_epilogue`)."""
        out = self._kernel_epilogue(node, entry, vals, specs, internal)
        if out is not None:
            return out
        operands = tuple(self.plan.env[nm] for nm in node.inputs)
        _, _, segments = compose_epilogue(node, operands, self.plan.env)
        for sub, seg_spec in segments:
            try:
                sub_ins = [vals[nm] for nm in sub.inputs]
                sub_specs = [specs[nm] for nm in sub.inputs]
            except KeyError as exc:
                raise CompileError(
                    f"{node.name}: fused segment {sub.name} consumes "
                    f"{exc.args[0]!r}, which no earlier segment produced"
                ) from None
            ctx = ExecCtx(sub, entry, sub_specs, aux, side, (),
                          mesh_shape, self.interpret, out_spec=seg_spec)
            out = op_backend(sub.kind)(ctx, *sub_ins)
            cur_spec = seg_spec
            for r in internal.get(sub.out, ()):
                out = coll.apply_plan(out, r.steps)
                cur_spec = r.dst
            vals[sub.out] = out
            specs[sub.out] = cur_spec
        return out

    def _kernel_epilogue(self, node, entry, vals, specs, internal):
        """The in-VMEM fast path: when the base is a plain 2-D matmul
        and every absorbed step is a known elementwise op with no
        internal redistributions, hand the whole chain to the matmul
        program as a :class:`~repro.axe.program.Epilogue` — it runs on
        the f32 accumulator tile before writeback (or functionally on
        the result when the extras don't tile like C). Returns None when
        the chain needs the general segment path."""
        if node.kind != "matmul" or internal:
            return None
        steps = [step_node(s) for s in epilogue_steps(node)]
        if any(s.kind != "elementwise" for s in steps):
            return None
        n_base = int(node.attr("base_inputs") or len(node.inputs))
        if n_base != 2:
            return None
        a_nm, b_nm = node.inputs[:2]
        a, b = vals[a_nm], vals[b_nm]
        if a.ndim != 2 or b.ndim != 2:
            return None
        fns = []
        for s in steps:
            fn = s.attr("fn", "add")
            if fn not in ("add", "swiglu", "mul_silu", "gelu"):
                return None
            fns.append(fn)
        chain0 = str(node.attr("base_out") or node.out)
        extras: List[str] = []
        for s in steps:
            for nm in s.inputs:
                produced = nm == chain0 or any(t.out == nm for t in steps)
                if not produced and nm not in extras:
                    if nm not in vals:
                        return None
                    extras.append(nm)

        def body(tile, *xs):
            named = dict(zip(extras, xs))
            named[chain0] = tile
            cur = tile
            for s, fn in zip(steps, fns):
                args = [named[nm] for nm in s.inputs]
                if fn == "add":
                    cur = args[0]
                    for x in args[1:]:
                        cur = cur + x
                elif fn == "swiglu":
                    cur = jax.nn.silu(args[0]) * args[1]
                elif fn == "mul_silu":
                    cur = args[0] * jax.nn.silu(args[1])
                else:  # gelu
                    cur = jax.nn.gelu(args[0])
                named[s.out] = cur
            return cur

        from repro.kernels import programs

        epi = programs.Epilogue(
            tag="+".join(fns), body=body,
            args=tuple(vals[nm] for nm in extras),
        )
        return programs.matmul(
            a, b, arg_specs=(specs[a_nm], specs[b_nm]),
            out_dtype=jnp.dtype(entry.out_spec.dtype),
            interpret=self.interpret, epilogue=epi,
        )

    def _sharded_fn(self):
        from repro.axe import lower as axe_lower

        names = self.activation_names + self.param_names
        if self.mesh is None:
            sharded = any(
                any(self.plan.env[n].placement()) for n in names
            ) or any(r.steps for e in self.plan.entries for r in e.redistributions)
            if sharded:
                raise CompileError(
                    "this plan shards tensors / issues collectives: "
                    "pass a concrete mesh to axe.compile"
                )
            return self._body
        in_pspecs = tuple(
            axe_lower.to_pspec(self.plan.env[n]) for n in names
        ) + tuple(jax.sharding.PartitionSpec() for _ in self.aux_names)
        outs = tuple(axe_lower.to_pspec(self._out_specs[o]) for o in self.outputs)
        return jax.shard_map(
            self._body, mesh=self.mesh, in_specs=in_pspecs,
            out_specs=outs[0] if len(outs) == 1 else outs, check_vma=False,
        )

    def apply(self, params: Mapping[str, Any], *activations):
        """Run un-jitted (trace-transparent: use this inside an outer
        ``jax.jit`` / ``value_and_grad``, e.g. a train step)."""
        return self._sharded_fn()(*self._ordered_inputs(params, activations))

    def __call__(self, params: Mapping[str, Any], *activations):
        if self._jitted is None:
            self._jitted = jax.jit(self._sharded_fn())
        return self._jitted(*self._ordered_inputs(params, activations))


# ---------------------------------------------------------------------------
# compile()
# ---------------------------------------------------------------------------


def _plan_assignment(plan) -> Optional[Mapping[str, AxeSpec]]:
    """The name → AxeSpec input assignment a plan object carries."""
    if isinstance(plan, SolveResult):
        return plan.assignment
    if isinstance(plan, LayoutPlan):
        return plan.env
    if isinstance(plan, Mapping):
        return plan
    return None


def plan_covers(graph: GraphSpec, plan) -> bool:
    """Whether ``plan`` was produced for (a graph shaped like)
    ``graph``: every graph input has an assigned spec with the right
    shape over the right space. A plan solved at a different
    batch/seq/depth does not cover and must be re-solved."""
    env = _plan_assignment(plan)
    if env is None:
        return False
    for name, meta in graph.inputs.items():
        spec = env.get(name)
        if spec is None or spec.shape != meta.shape or spec.space != graph.space:
            return False
    # a LayoutPlan/SolveResult must also have been planned over these
    # exact nodes — a plan solved on the unfused graph does not cover
    # its fused rewrite (and vice versa), even at the same shapes
    layout = plan.plan if isinstance(plan, SolveResult) else plan
    if isinstance(layout, LayoutPlan):
        have = {e.op.name: e.op for e in layout.entries}
        # compare the whole OpNode, not just the name: fusion keeps base
        # node names but rewrites inputs/attrs, so name-subset would let
        # an unfused plan silently drive the fused rewrite
        if any(have.get(n.name) != n for n in graph.nodes):
            return False
    return True


def compile(  # noqa: A001 - the paper-facing API name
    graph: GraphSpec,
    mesh=None,
    plan=None,
    *,
    schedule_cache: Optional[str] = None,
    interpret: Optional[bool] = None,
    beam: int = 4,
    fuse: bool = False,
    overlap: bool = False,
) -> Executable:
    """Compile ``graph`` for ``mesh`` under ``plan`` (see module doc).

    ``overlap=True`` does two things (docs/overlap.md): the layout
    solver (when it runs, i.e. ``plan=None``) scores overlappable comm
    at ``max(comm, compute)``, and the executable's body hoists each
    overlappable collective one entry early so its latency hides under
    the previous op's compute. The schedule reorders collective *issue*
    only — every op still consumes bit-identical operand values, so
    overlap and sync executables agree bit-for-bit.

    ``plan`` may be a :class:`~repro.axe.solve.SolveResult`, a
    :class:`~repro.axe.propagate.LayoutPlan`, a plain ``name → AxeSpec``
    input assignment, or None — in which case the layout solver runs
    (``beam`` forwarded). ``schedule_cache`` pins the process-wide
    schedule cache (``repro.tune``) so program stages traced inside the
    executable reuse autotuned schedules. ``fuse=True`` rewrites the
    graph through :func:`repro.axe.passes.fuse_graph` first (epilogue
    fusion, reshape collapse, DCE — docs/passes.md); a ``plan`` handed
    alongside must cover the *fused* graph (use :func:`plan_covers` to
    check — a plan solved on the unfused rewrite does not cover).

    With ``fuse=True`` and ``plan=None`` the layout is solved on the
    **pre-rewrite** graph and its input assignment is propagated through
    the fused graph (``compose_epilogue`` parity: identical specs and
    comm bytes). Fusing changes execution structure, never layout
    decisions — a beam search run directly on the rewritten graph walks
    a subtly different state space and can settle on a different
    near-tie (e.g. replicated attention heads) that costs the same in
    the model but executes measurably worse."""
    if schedule_cache is not None:
        from repro import tune

        tune.use_cache(schedule_cache)

    fusion_report = None
    if fuse:
        from repro.axe.passes import fuse_graph

        unfused = graph
        graph, fusion_report = fuse_graph(graph)
        if plan is not None and not plan_covers(graph, plan):
            raise CompileError(
                "the layout plan does not cover the fused graph (it was "
                "solved on a different rewrite); pass a covering plan "
                "or plan=None"
            )
        if plan is None:
            res = solve(unfused, beam=beam, overlap=overlap)
            plan = {n: res.assignment[n] for n in graph.inputs}

    solve_result: Optional[SolveResult] = None
    if plan is None:
        plan = solve(graph, beam=beam, overlap=overlap)
    if isinstance(plan, SolveResult):
        solve_result = plan
        layout = plan.plan
        assignment = plan.assignment
    elif isinstance(plan, LayoutPlan):
        layout = plan
        missing = [n for n in graph.inputs if n not in layout.env]
        if missing:
            raise CompileError(f"plan env lacks graph inputs {missing}")
        assignment = {n: layout.env[n] for n in graph.inputs}
        have = {e.op.name for e in layout.entries}
        extra = [
            e for e in finalize_entries(graph.outputs(), layout.env)
            if e.op.name not in have
        ]
        if extra:
            layout = LayoutPlan(
                layout.space, list(layout.entries) + extra, dict(layout.env)
            )
    elif isinstance(plan, Mapping):
        assignment = dict(plan)
        layout, _, _ = evaluate_env(graph, assignment)
    else:
        raise CompileError(
            f"plan must be a SolveResult, LayoutPlan, mapping, or None; "
            f"got {type(plan).__name__}"
        )
    exe = Executable(
        graph, mesh, layout, assignment,
        interpret=interpret, solve_result=solve_result, overlap=overlap,
    )
    exe.fusion_report = fusion_report
    return exe


# ---------------------------------------------------------------------------
# model binding: reference param pytrees -> graph inputs (+ aux)
# ---------------------------------------------------------------------------

#: families whose reference params map onto executable model graphs
SUPPORTED_FAMILIES = ("dense", "moe", "ssm", "hybrid")


def _period(cfg) -> int:
    if cfg.local_global_ratio:
        return cfg.local_global_ratio + 1
    if cfg.attn_period:
        return cfg.attn_period
    return 1


def _graph_layers(graph: GraphSpec) -> List[int]:
    seen = set()
    for node in graph.nodes:
        if node.name.startswith("L") and "." in node.name:
            head = node.name[1:].split(".", 1)[0]
            if head.isdigit():
                seen.add(int(head))
    return sorted(seen)


def model_inputs(graph: GraphSpec, cfg, params) -> Dict[str, Any]:
    """Map a reference model param pytree (``models.transformer``
    layout: scanned super-blocks) onto the graph's input tensors and
    auxiliary names, reshaping per-head projections onto the graph's
    2-D views (``wq [d, H, hd] → [d, H·hd]`` — head-major columns, so a
    solved column sharding is a head sharding of the model leaf)."""
    if cfg.family not in SUPPORTED_FAMILIES:
        raise CompileError(
            f"family {cfg.family!r} has no model binding "
            f"(supported: {SUPPORTED_FAMILIES})"
        )
    d = cfg.d_model
    per = _period(cfg)
    out: Dict[str, Any] = {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "lm_head": params["embed"].T if cfg.tie_embeddings else params["lm_head"],
    }
    for i in _graph_layers(graph):
        sup, slot = i // per, i % per
        lp = jax.tree.map(lambda a: a[sup], params["blocks"][f"l{slot}"])
        p = f"L{i}."
        out[f"{p}norm1"] = lp["norm1"]
        if "attn" in lp:
            ap = lp["attn"]
            h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
            out[f"{p}wq"] = ap["wq"].reshape(d, h * hd)
            out[f"{p}wk"] = ap["wk"].reshape(d, kv * hd)
            out[f"{p}wv"] = ap["wv"].reshape(d, kv * hd)
            out[f"{p}wo"] = ap["wo"].reshape(h * hd, d)
            if cfg.qk_norm:
                out[f"{p}q_norm"] = ap["q_norm"]
                out[f"{p}k_norm"] = ap["k_norm"]
        if "ssm" in lp:
            sp = lp["ssm"]
            for name in ("wx", "wz", "wB", "wC", "wdt",
                         "dt_bias", "A_log", "D", "conv_w", "gate_norm"):
                out[f"{p}{name}"] = sp[name]
            out[f"{p}ssm_wo"] = sp["wo"]
        if "norm2" in lp:
            out[f"{p}norm2"] = lp["norm2"]
        if "mlp" in lp:
            mp = lp["mlp"]
            if cfg.mlp_type == "swiglu":
                out[f"{p}wg"] = mp["wg"]
                out[f"{p}wu"] = mp["wu"]
            else:
                out[f"{p}wi"] = mp["wi"]
            out[f"{p}wo2"] = mp["wo"]
        if "moe" in lp:
            mo = lp["moe"]
            out[f"{p}router"] = mo["router"]
            out[f"{p}moe_wg"] = mo["wg"]
            out[f"{p}moe_wu"] = mo["wu"]
            out[f"{p}moe_wo"] = mo["wo"]
    return out


def model_executable(
    cfg,
    mesh,
    batch: int,
    seq: int,
    *,
    plan=None,
    layers: Optional[int] = None,
    schedule_cache: Optional[str] = None,
    beam: int = 4,
    dtype: Optional[str] = None,
    fuse: bool = False,
    classes=None,
    offload: Sequence[str] = (),
    overlap: bool = False,
    cotune: bool = False,
    cotune_iters: int = 4,
    cotune_measure: bool = False,
    cost_model=None,
) -> Executable:
    """The consumer-facing constructor: build the model-zoo graph for
    ``cfg`` at (batch, seq) and compile it. ``layers=None`` compiles the
    full depth (what training/serving needs); pass a small cap for
    layout studies. ``fuse=True`` runs the graph-level fusion passes
    before solving (docs/passes.md). A ``plan`` solved for a *different*
    graph shape (other batch/seq/depth — e.g. a layout-study solve
    handed to a serving engine) or a different fusion rewrite does not
    cover this graph: it is dropped with a warning and the layout is
    re-solved.

    ``classes`` annotates mesh axes with device classes
    (``{"host": "host"}`` — repro.axe.hetero) and ``offload`` names
    graph inputs the solver must park on the non-default class; the
    executable then carries the class-crossing Transfer collectives in
    its plan (docs/heterogeneous.md).

    ``cotune=True`` runs the solve↔tune fixed-point loop
    (``repro.axe.cotune``, docs/cotune.md) instead of a one-shot solve:
    measured schedule timings from the ambient cache (or an explicit
    ``cost_model``) correct the solver's rooflines and the layout is
    re-solved until the plan stops changing (≤ ``cotune_iters``
    solves). With no measurements the loop degenerates to exactly the
    one-shot solve, bit-identical plans. ``cotune_measure=True``
    additionally autotunes the measurable local problems in-loop. The
    loop trace lands on ``executable.cotune_report``."""
    import warnings

    from repro.axe.graphs import model_graph
    from repro.axe.spec import PhysicalSpace

    if mesh is not None:
        space = PhysicalSpace.from_mesh_shape(
            dict(zip(mesh.axis_names, mesh.devices.shape)),
            classes=dict(classes) if classes else (),
        )
    else:
        space = PhysicalSpace(())
    gs = model_graph(
        cfg, batch, seq, space,
        dtype=dtype or cfg.dtype,
        layers=cfg.num_layers if layers is None else layers,
    )
    gs_run = gs
    if fuse:
        from repro.axe.passes import fuse_graph

        # the rewrite is deterministic, so this fused view matches the
        # one compile(fuse=True) produces — used only for the cover check
        gs_run, _ = fuse_graph(gs)
    if plan is not None and not plan_covers(gs_run, plan):
        warnings.warn(
            f"layout plan does not cover the {cfg.name} graph at "
            f"batch={batch}, seq={seq} (different shape/depth/space/"
            f"fusion): re-solving",
            UserWarning, stacklevel=2,
        )
        plan = None
    cotune_report = None
    if plan is None and cotune:
        # same pre-rewrite graph + solve arguments compile() would use
        # internally, so an empty measurement table yields bit-identical
        # plans to cotune=False
        from repro.axe.cotune import cotune as _cotune

        ct = _cotune(
            gs, beam=beam, max_iters=cotune_iters, cost_model=cost_model,
            measure=cotune_measure, overlap=overlap, offload=offload,
            compare_seeded=not offload,
        )
        cotune_report = ct
        plan = ({n: ct.assignment[n] for n in gs_run.inputs}
                if fuse else ct.result)
    elif plan is None and offload:
        # solve on the pre-rewrite graph (see compile's docstring) with
        # the offload targets pinned to parked placements; no seeded
        # budget — the rules never park
        res = solve(gs, beam=beam, compare_seeded=False, offload=offload,
                    overlap=overlap)
        plan = ({n: res.assignment[n] for n in gs_run.inputs}
                if fuse else res)
    exe = compile(gs, mesh, plan, schedule_cache=schedule_cache, beam=beam,
                  fuse=fuse, overlap=overlap)
    exe.cotune_report = cotune_report
    return exe


def decode_inputs(graph: GraphSpec, cfg, params, cache) -> Dict[str, Any]:
    """:func:`model_inputs` plus the cache tensors: slice each layer's
    cache leaves out of the reference pytree (``models.transformer``
    layout — per-slot dicts stacked over super-blocks) onto the graph's
    per-layer cache-in names."""
    out = model_inputs(graph, cfg, params)
    per = _period(cfg)
    for i in _graph_layers(graph):
        sup, slot = i // per, i % per
        leaf = cache[f"l{slot}"]
        p = f"L{i}."
        if "k" in leaf:
            out[f"{p}k_cache"] = leaf["k"][sup]
            out[f"{p}v_cache"] = leaf["v"][sup]
        else:
            out[f"{p}ssm_state"] = leaf["ssm"][sup]
            out[f"{p}conv_state"] = leaf["conv"][sup]
    return out


def decode_cache(graph: GraphSpec, cfg, outputs: Sequence[Any], cache):
    """Reassemble the reference cache pytree from a decode executable's
    output tuple (the cache-out tensors, one pair per layer) — the
    inverse of :func:`decode_inputs`'s per-layer slicing. ``cache`` is
    only consulted for leaf kinds (attention vs SSM slots)."""
    per = _period(cfg)
    vals = dict(zip(graph.outputs(), outputs))
    layers = _graph_layers(graph)
    sups = sorted({i // per for i in layers})
    new = {}
    for slot in sorted({i % per for i in layers}):
        leaf = cache[f"l{slot}"]
        names = ({"k": "k_cache_out", "v": "v_cache_out"} if "k" in leaf
                 else {"ssm": "ssm_state_out", "conv": "conv_state_out"})
        new[f"l{slot}"] = {
            key: jnp.stack([vals[f"L{s * per + slot}.{g}"] for s in sups])
            for key, g in names.items()
        }
    return new


def decode_executable(
    cfg,
    mesh,
    batch: int,
    max_seq: int,
    *,
    plan=None,
    layers: Optional[int] = None,
    schedule_cache: Optional[str] = None,
    beam: int = 4,
    dtype: Optional[str] = None,
    fuse: bool = False,
    overlap: bool = False,
) -> Executable:
    """Build the single-token decode-step graph for ``cfg`` (cache
    tensors as first-class inputs/outputs) and compile it — the serving
    twin of :func:`model_executable`. ``fuse=True`` runs the graph-level
    fusion passes first (docs/passes.md; DCE provably preserves the
    cache-out / side-output channels). A ``plan`` solved for a different
    graph (e.g. the prefill forward, or an unfused rewrite) does not
    cover the decode graph and is dropped with a warning; pass a plan
    solved on a matching decode graph (or None) to avoid the re-solve."""
    import warnings

    from repro.axe.graphs import decode_graph
    from repro.axe.spec import PhysicalSpace

    if mesh is not None:
        space = PhysicalSpace.from_mesh_shape(
            dict(zip(mesh.axis_names, mesh.devices.shape))
        )
    else:
        space = PhysicalSpace(())
    gs = decode_graph(
        cfg, batch, max_seq, space,
        dtype=dtype or cfg.dtype,
        layers=cfg.num_layers if layers is None else layers,
    )
    gs_run = gs
    if fuse:
        from repro.axe.passes import fuse_graph

        gs_run, _ = fuse_graph(gs)
    if plan is not None and not plan_covers(gs_run, plan):
        warnings.warn(
            f"layout plan does not cover the {cfg.name} decode graph at "
            f"batch={batch}, max_seq={max_seq} (different shape/depth/"
            f"space/fusion): re-solving",
            UserWarning, stacklevel=2,
        )
        plan = None
    return compile(gs, mesh, plan, schedule_cache=schedule_cache, beam=beam,
                   fuse=fuse, overlap=overlap)


def compiled_loss_fn(exe: Executable, cfg) -> Callable:
    """Cross-entropy LM loss over the compiled forward — the function
    ``launch/train.py --solve`` hands to ``make_train_step`` instead of
    the bespoke module wiring. Differentiates through the executable's
    shard_map (collectives transpose to their duals)."""
    from repro.models.common import cross_entropy_loss

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        inputs = model_inputs(exe.graph, cfg, params)
        logits = exe.apply(inputs, tokens.reshape(-1))
        return cross_entropy_loss(
            logits.reshape(b, s, logits.shape[-1]), batch["labels"]
        )

    return loss_fn
