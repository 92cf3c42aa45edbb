"""Pipeline parallelism (GPipe schedule) over a ``pipe`` mesh axis.

For 1000+-chip jobs a third model axis becomes necessary (TP is
ICI-bound at ~16, FSDP gathers grow with DP). This module implements
microbatch pipelining as a shard_map program:

* every pipeline device holds its stage's layer slice (stacked layer
  params with the leading stage dim sharded over ``pipe``),
* at step t, stage s processes microbatch (t − s); activations move
  stage→stage via ``ppermute`` (neighbor ICI transfers only),
* the schedule runs T = n_micro + n_stages − 1 steps (bubble fraction
  (P−1)/T — amortized by more microbatches).

The schedule is expressed with ``lax.scan`` so HLO size is O(1) in T,
and the whole pipeline is differentiable (grads flow through ppermute),
so it composes with the existing train step.

In Axe terms the activation layout is
``D: (n_micro · stage@pipe, …)`` with the stage iter walking the pipe
axis over time — the same named-axis vocabulary as every other layout
in this framework: ``pipe`` is a registered mesh axis (``core.axes``)
and the stage-param / microbatch placements handed to shard_map are
AxeSpecs lowered through ``repro.axe.lower``, not hand-written
PartitionSpecs.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.axe import lower as axe_lower
from repro.axe.spec import AxeSpec, PhysicalSpace, SpecError
from repro.core.scopes import Scope, scope


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,   # [n_micro, mb, ...] (replicated input)
    mesh: Mesh,
    *,
    axis_name: str = "pipe",
) -> jax.Array:
    """Run microbatches through P pipeline stages; returns [n_micro, ...].

    ``stage_params`` leaves must have a leading stage dim of size P
    (sharded over ``axis_name``); ``stage_fn(params_for_stage, x) -> y``
    must keep x/y the same shape (a transformer block stack slice).
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    n_micro = microbatches.shape[0]
    total_steps = n_micro + n_stages - 1

    def body(xl_params, mb):
        # the shard_map body is per-device code: enter DEVICE scope so
        # any axe.program dispatched inside stage_fn picks its
        # device-scope stage (e.g. matmul -> the Pallas tile stage)
        with scope(Scope.DEVICE):
            return _pipeline_body(xl_params, mb)

    def _pipeline_body(xl_params, mb):
        params_local = jax.tree.map(lambda p: p[0], xl_params)  # drop stage dim
        s = jax.lax.axis_index(axis_name)
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def step(carry, t):
            cur, outputs = carry
            # stage 0 ingests microbatch t (if in range); others use the
            # activation that just arrived from the previous stage.
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(mb, mb_idx, keepdims=False)
            x_in = jnp.where(s == 0, fresh, cur)
            y = stage_fn(params_local, x_in)
            # last stage emits microbatch (t - (P-1)) when valid
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = (t - (n_stages - 1) >= 0) & (s == n_stages - 1)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, y, out_idx, 0),
                lambda o: o,
                outputs,
            )
            nxt = jax.lax.ppermute(y, axis_name, fwd_perm)
            return (nxt, outputs), None

        zero = jnp.zeros_like(mb[0])
        outs0 = jnp.zeros_like(mb)
        (_, outputs), _ = jax.lax.scan(
            step, (zero, outs0), jnp.arange(total_steps)
        )
        # only the last stage holds real outputs; broadcast them
        outputs = jax.lax.psum(
            jnp.where(s == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis_name,
        )
        return outputs

    # stage-param placement: leading stage dim sharded over `pipe`,
    # everything else replicated — stated as an AxeSpec and lowered.
    space = PhysicalSpace.from_mesh_shape(
        dict(zip(mesh.axis_names, mesh.devices.shape))
    )

    def stage_pspec(p):
        try:
            return axe_lower.to_pspec(
                AxeSpec.sharded(p.shape, space, {0: (axis_name,)})
            )
        except SpecError as e:
            raise ValueError(
                f"stage params of shape {p.shape} not shardable over "
                f"{axis_name}={n_stages}: {e}"
            ) from e

    spec_params = jax.tree.map(stage_pspec, stage_params)
    replicated = axe_lower.to_pspec(
        AxeSpec.replicated(microbatches.shape, space)
    )

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec_params, replicated),
        out_specs=replicated,
        check_vma=False,
    )(stage_params, microbatches)


def split_layers_into_stages(stacked_params: Any, n_stages: int) -> Any:
    """Reshape stacked per-layer params [L, ...] -> [P, L/P, ...]."""

    def re(p):
        l = p.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return p.reshape(n_stages, l // n_stages, *p.shape[1:])

    return jax.tree.map(re, stacked_params)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
