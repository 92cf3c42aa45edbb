"""Distributed execution demo on 8 host-platform devices: Axe layouts
drive shardings, collective inference, and the fused GEMM+ReduceScatter.

The fake mesh is CPU-only: the script re-execs itself, before touching
JAX, with ``JAX_PLATFORMS=cpu`` and 8 host devices, so it never holds or
waits for a chip. Everything it prints is a CPU result.

Run:  PYTHONPATH=src python examples/distributed_demo.py
"""
import os
import sys

_FLAGS = "--xla_force_host_platform_device_count=8"
if os.environ.get("JAX_PLATFORMS") != "cpu" or os.environ.get("XLA_FLAGS") != _FLAGS:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = _FLAGS
    os.execv(sys.executable, [sys.executable] + sys.argv)

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.axe import rules as axe_rules
from repro.axe.spec import AxeSpec, PhysicalSpace
from repro.core import DTensorSpec, collective as coll
from repro.kernels import programs
from repro.train import act_sharding


def main():
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    ms = axe_rules.mesh_shape_of(mesh)
    space = PhysicalSpace.from_mesh_shape(ms)
    print(f"mesh: {ms} (backend {jax.default_backend()}: fake host devices)")

    # --- Axe layout -> sharding for a weight matrix --------------------
    spec = DTensorSpec.from_pspec((1024, 512), (None, "model"), ms)
    print("weight layout:", spec.layout)
    print("as sharding:", spec.sharding(mesh))

    # --- collective inference from a layout pair ----------------------
    src = DTensorSpec.from_pspec((256, 512), ("model", None), ms)
    dst = DTensorSpec.from_pspec((256, 512), (None, "model"), ms)
    plan = coll.infer_redistribution(src, dst, ms)
    print("redistribution plan (model-dim0 -> model-dim1):",
          [type(s).__name__ for s in plan])
    per_dev = coll.plan_comm_bytes(plan, src, ms, 4)
    print(f"  bytes/device: {per_dev}")

    # --- fused GEMM+ReduceScatter: the collective_matmul program ------
    # operand/result AxeSpecs are the only placement input; the ring
    # schedule is the program's "ring" stage variant (docs/kernel-dsl.md)
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 128), jnp.float32)
    sa = AxeSpec.sharded((256, 512), space, {1: ("model",)})
    sb = AxeSpec.sharded((512, 128), space, {0: ("model",)})
    so = AxeSpec.sharded((256, 128), space, {0: ("model",)})
    f = jax.jit(programs.collective_matmul.shard_map(mesh, (sa, sb), so, impl="ring"))
    out = f(a, b)
    err = float(jnp.max(jnp.abs(out - a @ b)))
    print(f"fused GEMM+RS max err vs dense: {err:.2e}")

    # --- a sharded train-style forward with Axe activation constraints -
    from repro.configs import get_config, smoke_variant
    from repro.models.model_zoo import ShapeSpec, build_model

    cfg = smoke_variant(get_config("qwen3-4b"))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    pspecs = axe_rules.pspec_tree(axe_rules.param_specs(params, space))
    n_sharded = sum(any(e is not None for e in ps) for ps in jax.tree.leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P)))
    print(f"param tensors with sharded dims: {n_sharded}")
    batch = api.make_train_batch(jax.random.PRNGKey(2), ShapeSpec("s", "train", 64, 4))
    with act_sharding.mesh_context(mesh), mesh:
        loss = jax.jit(api.loss_fn)(params, batch)
    print("sharded forward loss:", float(loss))


if __name__ == "__main__":
    main()
