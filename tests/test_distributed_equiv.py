"""Distributed-vs-local numerical equivalence on an 8-device host mesh
(subprocess): the expert-parallel shard_map MoE and the fully-sharded
train forward must match their single-device references."""
import json
import os
import subprocess
import sys

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro import compat

from repro.configs import get_config, smoke_variant
from repro.models import moe as moe_mod
from repro.models.model_zoo import ShapeSpec, build_model
from repro.train import act_sharding

mesh = compat.make_mesh((2, 4), ("data", "model"))

# --- 1. expert-parallel MoE vs local path -------------------------------
cfg = dataclasses.replace(
    smoke_variant(get_config("dbrx-132b")), num_experts=8, experts_per_tok=2,
)
p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)

y_local = moe_mod.moe_apply(p, x, cfg)          # no mesh context
with act_sharding.mesh_context(mesh), mesh:
    assert moe_mod._ep_eligible(x, cfg, mesh)
    y_ep = jax.jit(lambda p, x: moe_mod.moe_apply(p, x, cfg))(p, x)
err_moe = float(jnp.max(jnp.abs(y_local - y_ep)))

# --- 2. full train-loss forward, sharded vs unsharded -------------------
cfg2 = smoke_variant(get_config("qwen3-moe-235b-a22b"))
cfg2 = dataclasses.replace(cfg2, num_experts=8)
api = build_model(cfg2)
params = api.init(jax.random.PRNGKey(0))
batch = api.make_train_batch(jax.random.PRNGKey(1), ShapeSpec("s", "train", 64, 4))
loss_ref = float(api.loss_fn(params, batch))
with act_sharding.mesh_context(mesh), mesh:
    loss_sh = float(jax.jit(api.loss_fn)(params, batch))

# --- 3. gradient equivalence through the EP path ------------------------
def lf(p_, x_):
    return jnp.sum(moe_mod.moe_apply(p_, x_, cfg) ** 2)

g_local = jax.grad(lf)(p, x)
with act_sharding.mesh_context(mesh), mesh:
    g_ep = jax.jit(jax.grad(lf))(p, x)
g_err = max(
    float(jnp.max(jnp.abs(a - b)))
    for a, b in zip(jax.tree.leaves(g_local), jax.tree.leaves(g_ep))
)

print(json.dumps({
    "err_moe": err_moe,
    "loss_ref": loss_ref, "loss_sh": loss_sh,
    "g_err": g_err,
}))
"""


def test_distributed_equivalence():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True, env=env,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["err_moe"] < 1e-4, data
    assert abs(data["loss_ref"] - data["loss_sh"]) < 1e-3, data
    assert data["g_err"] < 1e-2, data
