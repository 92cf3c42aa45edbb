"""axe.compile: compiled-graph numerics vs the reference models
(dense / MoE / SSM, f32 tight + bf16 loose, 1 and 8 host devices),
lowering-trace determinism, the op-backend registry, and the consumer
wiring (compiled loss grads, ServeEngine.score)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import axe, compat
from repro.configs import get_config, smoke_variant
from repro.models import transformer as tf_mod
from repro.models.model_zoo import build_model

ARCHS = ("qwen3-4b", "qwen3-moe-235b-a22b", "mamba2-2.7b")


def _cfg(arch, dtype=None):
    cfg = smoke_variant(get_config(arch))
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    return cfg


def _run(cfg, mesh, b, s, seed=0):
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(seed))
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (b, s), 0, cfg.vocab_size, jnp.int32
    )
    ref = np.asarray(
        tf_mod.lm_forward(params, {"tokens": tokens}, cfg, remat=False),
        dtype=np.float32,
    )
    exe = axe.model_executable(cfg, mesh, b, s, dtype=cfg.dtype)
    inputs = axe.model_inputs(exe.graph, cfg, params)
    got = np.asarray(
        exe(inputs, tokens.reshape(-1)), dtype=np.float32
    ).reshape(b, s, -1)
    return exe, got, ref


# ---------------------------------------------------------------------------
# numerics vs the reference forward (single device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_compiled_matches_reference_f32(arch):
    cfg = _cfg(arch)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    _, got, ref = _run(cfg, mesh, 2, 32)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_compiled_matches_reference_bf16():
    cfg = _cfg("qwen3-4b", dtype="bfloat16")
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    _, got, ref = _run(cfg, mesh, 2, 32)
    np.testing.assert_allclose(got, ref, rtol=0.1, atol=0.25)


def test_compile_without_mesh_runs_locally():
    cfg = _cfg("qwen3-4b")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg.vocab_size, jnp.int32)
    exe = axe.model_executable(cfg, None, 2, 32, dtype=cfg.dtype)
    got = exe(axe.model_inputs(exe.graph, cfg, params), tokens.reshape(-1))
    ref = tf_mod.lm_forward(params, {"tokens": tokens}, cfg, remat=False)
    np.testing.assert_allclose(
        np.asarray(got).reshape(2, 32, -1), np.asarray(ref),
        rtol=2e-4, atol=2e-4,
    )


# ---------------------------------------------------------------------------
# 8 host devices (subprocess, like test_distributed_equiv)
# ---------------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np
from repro import axe, compat
from repro.configs import get_config, smoke_variant
from repro.models import transformer as tf_mod
from repro.models.model_zoo import build_model

out = {}
mesh = compat.make_mesh((2, 4), ("data", "model"))
for arch in ("qwen3-4b", "qwen3-moe-235b-a22b", "mamba2-2.7b"):
    cfg = smoke_variant(get_config(arch))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, s = 4, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                                cfg.vocab_size, jnp.int32)
    ref = np.asarray(tf_mod.lm_forward(params, {"tokens": tokens}, cfg,
                                       remat=False))
    exe = axe.model_executable(cfg, mesh, b, s, dtype=cfg.dtype)
    got = np.asarray(exe(axe.model_inputs(exe.graph, cfg, params),
                         tokens.reshape(-1))).reshape(b, s, -1)
    out[arch] = {
        "max_diff": float(np.max(np.abs(got - ref))),
        "collectives": len(exe.collective_sequence()),
        "issued_matches_plan": list(exe.observed_collectives)
                               == list(exe.collective_sequence()),
    }
print("RESULT " + json.dumps(out))
"""


def test_compiled_matches_reference_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], env=env,
        capture_output=True, text=True, timeout=1500,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    for arch, rec in out.items():
        assert rec["max_diff"] < 2e-4, (arch, rec)
        assert rec["collectives"] > 0, arch  # sharded: real transfers
        assert rec["issued_matches_plan"], arch


# ---------------------------------------------------------------------------
# lowering trace: deterministic, schedule-keyed, collective-faithful
# ---------------------------------------------------------------------------


def test_lowering_trace_deterministic():
    cfg = _cfg("qwen3-4b")
    space = axe.PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})
    gs = axe.model_graph(cfg, 4, 32, space, dtype=cfg.dtype, layers=2)
    res = axe.solve(gs, beam=4)
    e1 = axe.compile(gs, None, plan=dict(res.assignment))
    e2 = axe.compile(gs, None, plan=dict(res.assignment))
    assert e1.lowering_trace == e2.lowering_trace
    assert e1.collective_sequence() == e2.collective_sequence()
    # re-solving is deterministic too, so plan=None composes the same
    e3 = axe.compile(gs, None)
    assert e3.collective_sequence() == e1.collective_sequence()


def test_lowering_trace_stage_keyed_schedules():
    """Trace rows for program-backed ops carry program/stage schedule
    keys — the same keys the tune cache resolves at dispatch."""
    cfg = _cfg("qwen3-moe-235b-a22b")
    space = axe.PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})
    gs = axe.model_graph(cfg, 4, 32, space, dtype=cfg.dtype, layers=1)
    exe = axe.compile(gs, None)
    scheds = [r.schedule for r in exe.lowering_trace if r.schedule]
    assert any(s.startswith("matmul/tile=") for s in scheds), scheds
    assert any(s.startswith("flash_attention/attend=") for s in scheds), scheds
    assert any(s.startswith("moe_gemm/expert_gemm=") for s in scheds), scheds
    assert any(s.startswith("rmsnorm/rows=") for s in scheds), scheds


def test_lowering_trace_counts_matmul_grid_steps(monkeypatch):
    """Each planned ``matmul/tile`` row carries its schedule's grid steps,
    as the planner ranks it for the chip, and prints them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _cfg("qwen3-4b")
    space = axe.PhysicalSpace.from_mesh_shape({"data": 1})
    gs = axe.model_graph(cfg, 16, 1, space, dtype=cfg.dtype, layers=1)
    rows = [r for r in axe.compile(gs, None).lowering_trace
            if r.schedule and r.schedule.startswith("matmul/tile=kernel")]
    assert rows
    for r in rows:
        assert r.grid_steps >= 1
        assert f"steps={r.grid_steps}" in r.describe()


def test_compile_accepts_solve_result_and_mapping():
    cfg = _cfg("qwen3-4b")
    space = axe.PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})
    gs = axe.model_graph(cfg, 4, 32, space, dtype=cfg.dtype, layers=1)
    res = axe.solve(gs, beam=2)
    via_result = axe.compile(gs, None, plan=res)
    via_mapping = axe.compile(gs, None, plan=dict(res.assignment))
    via_plan = axe.compile(gs, None, plan=res.plan)
    assert via_result.collective_sequence() == via_mapping.collective_sequence()
    assert via_plan.collective_sequence() == via_mapping.collective_sequence()
    assert via_result.solve_result is res


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------


def test_register_op_backend_mirrors_rule_registry():
    from repro.axe import compile as _  # noqa: F401 - ensure registered
    from repro.axe.compile import OP_BACKENDS, op_backend, register_op_backend
    from repro.axe.propagate import _RULES

    # every propagation rule has an execution backend (finalize is the
    # pass-internal pseudo-kind handled by the body itself)
    assert set(_RULES) <= set(OP_BACKENDS) | {"finalize"}

    calls = []

    @register_op_backend("test_kind")
    def _backend(ctx, x):
        calls.append(ctx.node.name)
        return x

    try:
        assert op_backend("test_kind") is _backend
    finally:
        del OP_BACKENDS["test_kind"]
    with pytest.raises(axe.CompileError, match="register_op_backend"):
        op_backend("test_kind")


def test_missing_param_raises_compile_error():
    cfg = _cfg("qwen3-4b")
    exe = axe.model_executable(cfg, None, 2, 32, dtype=cfg.dtype)
    with pytest.raises(axe.CompileError, match="missing from params"):
        exe({}, jnp.zeros((64,), jnp.int32))


def test_stale_plan_is_resolved_not_crashed():
    """A plan solved for a different (batch, seq) does not cover the
    new graph: model_executable warns and re-solves instead of
    compiling stale shapes (the ServeEngine layout_plan path)."""
    cfg = _cfg("qwen3-4b")
    space = axe.PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})
    stale = axe.solve(axe.model_graph(cfg, 8, 64, space, dtype=cfg.dtype,
                                      layers=1), beam=2)
    with pytest.warns(UserWarning, match="does not cover"):
        exe = axe.model_executable(cfg, None, 2, 32, plan=stale, dtype=cfg.dtype)
    assert exe.graph.inputs["tokens"].shape == (64,)
    gs = axe.model_graph(cfg, 8, 64, space, dtype=cfg.dtype, layers=1)
    assert axe.plan_covers(gs, stale)
    assert not axe.plan_covers(exe.graph, stale)


def test_lowering_trace_schedules_use_post_redistribution_specs():
    """A K-partial matmul's operands are redistributed before the next
    op runs; its trace schedule must be planned for the
    post-redistribution local problem (what dispatch resolves), not the
    pre-redistribution one."""
    from repro.axe.propagate import OpNode, propagate

    space = axe.PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})
    a = axe.AxeSpec.sharded((64, 128), space, {1: ("model",)})
    w = axe.AxeSpec.sharded((128, 64), space, {0: ("model",)})
    nodes = [
        OpNode("proj", "matmul", ("a", "w"), "y"),
        OpNode("nrm", "norm", ("y",), "z"),
    ]
    plan = propagate(nodes, {"a": a, "w": w})
    nrm = plan.entries[1]
    # the norm's input is partial pre-redistribution; post, it is dense
    (spec,) = nrm.input_specs(plan.env)
    assert plan.env["y"].partial == ("model",)
    assert spec.partial == ()
    # the MoE dispatch holds one row per token: nothing to drop
    cfg = _cfg("qwen3-moe-235b-a22b")
    gs = axe.model_graph(cfg, 2, 32, axe.PhysicalSpace(()), dtype=cfg.dtype, layers=1)
    (disp,) = [n for n in gs.nodes if n.kind == "moe_dispatch"]
    assert disp.attr("capacity") == 2 * 32


# ---------------------------------------------------------------------------
# consumers: compiled loss (train) and ServeEngine.score
# ---------------------------------------------------------------------------


def test_compiled_loss_grads_match_reference():
    cfg = _cfg("qwen3-4b")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = api.make_train_batch(
        jax.random.PRNGKey(1), type("S", (), {"batch": 2, "seq": 32})()
    )
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    exe = axe.model_executable(cfg, mesh, 2, 32, dtype=cfg.dtype)
    loss_fn = axe.compiled_loss_fn(exe, cfg)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
    loss_ref, grads_ref = jax.value_and_grad(api.loss_fn)(params, batch)
    assert abs(float(loss) - float(loss_ref)) < 1e-4
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_serve_engine_score_uses_compiled_forward():
    from repro.serve.engine import ServeEngine

    cfg = _cfg("qwen3-4b")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api=api, batch_size=2, max_seq=64)
    eng.load(params)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0,
                                cfg.vocab_size, jnp.int32)
    logits = eng.score(tokens)
    ref = tf_mod.lm_forward(params, {"tokens": tokens}, cfg, remat=False)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # memoized per shape
    assert eng.compiled_forward(32, batch=2) is eng.compiled_forward(32, batch=2)


def test_from_plan_divisibility_warning_is_structured():
    from repro.axe import rules
    from repro.axe.spec import AxeSpec, PhysicalSpace

    space = PhysicalSpace.from_mesh_shape({"data": 4, "model": 4})
    plan = rules.from_plan({
        "L0.wk": AxeSpec.sharded((64, 24), space, {1: ("model",)}),
    })
    with pytest.warns(rules.PlanDivisibilityWarning) as rec:
        spec = plan.spec_for("compiletest.wk", (64, 6, 4), space)
    assert spec is not None and spec.placement() == ((), (), ())
    w = rec[0].message
    assert w.param == "compiletest.wk" and w.dim == 1 and w.axes == ("model",)
    # one structured warning per (param, dim, axes): a second resolve
    # of the same stacked leaf stays quiet
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        plan.spec_for("compiletest.wk", (64, 6, 4), space)
