"""repro.axe.passes: graph-level fusion before solve/compile.

Covers the pass framework (determinism, idempotence, verification),
fused-vs-unfused executable parity for all four model families —
forward, gradients through ``compiled_loss_fn``, and the compiled
decode step — DCE's ``extra_outputs`` / ``side_output`` guarantees, and
the ServeEngine-level warning dedupe that fused recompiles lean on.

Fused executables inherit the unfused solve's layout assignment
(``axe.compile`` transfer semantics), so parity here is bit-exact, not
merely within tolerance.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import axe, compat
from repro.configs import get_config, smoke_variant
from repro.models.model_zoo import build_model
from repro.axe.graphs import GraphSpec, OpNode, TensorMeta, decode_graph, model_graph
from repro.axe.passes import (
    DeadCodeElimination,
    EpilogueFusion,
    PassPipeline,
    ReshapePairCollapse,
    fuse_graph,
)
from repro.axe.rules import mesh_shape_of
from repro.axe.spec import AxeSpec, PhysicalSpace

ARCHS = (
    "qwen3-4b", "qwen3-moe-235b-a22b", "mamba2-2.7b", "jamba-1.5-large-398b",
)


def _cfg(arch):
    cfg = smoke_variant(get_config(arch))
    return cfg


def _model(cfg, seed=0):
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(seed))
    return api, params


# ---------------------------------------------------------------------------
# graph-level properties (no execution)
# ---------------------------------------------------------------------------


def _graphs(arch, b=2, s=32):
    cfg = _cfg(arch)
    space = PhysicalSpace.from_mesh_shape({"data": 1, "model": 1})
    return (model_graph(cfg, b, s, space, dtype=cfg.dtype),
            decode_graph(cfg, b, s, space, dtype=cfg.dtype))


@pytest.mark.parametrize("arch", ARCHS)
def test_fuse_graph_shrinks_and_preserves_outputs(arch):
    for gs in _graphs(arch):
        fused, rep = fuse_graph(gs)  # verify=True re-propagates inside
        assert len(fused.nodes) < len(gs.nodes)
        assert fused.outputs() == gs.outputs()
        assert fused.extra_outputs == gs.extra_outputs
        assert rep.patterns_fired
        assert len(rep.eliminated) == len(gs.nodes) - len(fused.nodes)


@pytest.mark.parametrize("arch", ("qwen3-4b", "mamba2-2.7b"))
def test_fuse_graph_deterministic(arch):
    gs, _ = _graphs(arch)
    f1, r1 = fuse_graph(gs)
    f2, r2 = fuse_graph(gs)
    assert [(n.name, n.kind, n.inputs, n.out, n.attrs) for n in f1.nodes] \
        == [(n.name, n.kind, n.inputs, n.out, n.attrs) for n in f2.nodes]
    assert r1.to_dict() == r2.to_dict()


def test_fuse_graph_idempotent():
    gs, _ = _graphs("qwen3-4b")
    once, _ = fuse_graph(gs)
    twice, rep = fuse_graph(once)
    assert [(n.name, n.attrs) for n in twice.nodes] \
        == [(n.name, n.attrs) for n in once.nodes]
    assert not rep.patterns_fired


def test_fusion_preserves_seeded_specs_and_comm():
    """compose parity: the fused graph propagates the seeded env to the
    same output specs and the same total comm bytes as the original."""
    from repro.axe.propagate import propagate

    gs, _ = _graphs("qwen3-4b")
    fused, _ = fuse_graph(gs)
    env = gs.seeded_env()
    plan_u = propagate(gs.nodes, env)
    plan_f = propagate(fused.nodes, {n: env[n] for n in fused.inputs})
    comm = lambda p: sum(  # noqa: E731
        r.comm_bytes for e in p.entries for r in e.redistributions
    )
    assert comm(plan_f) == comm(plan_u)
    for out in gs.outputs():
        assert plan_f.env[out].signature() == plan_u.env[out].signature()


# ---------------------------------------------------------------------------
# DCE: extra_outputs / side channels are never dropped
# ---------------------------------------------------------------------------


def _toy_graph(extra=()):
    """x @ w1 feeds both a consumed branch and the graph result; ``mid``
    is consumed (so only ``extra_outputs`` keeps it a graph result)."""
    space = PhysicalSpace.from_mesh_shape({"data": 1, "model": 1})
    sp = lambda *s: s  # noqa: E731
    nodes = [
        OpNode("m1", "matmul", ("x", "w1"), "mid"),
        OpNode("m2", "matmul", ("mid", "w2"), "out"),
    ]
    inputs = {
        "x": TensorMeta("x", (8, 16), "float32", "activation"),
        "w1": TensorMeta("w1", (16, 16), "float32", "param"),
        "w2": TensorMeta("w2", (16, 4), "float32", "param"),
        "w_dead": TensorMeta("w_dead", (16, 4), "float32", "param"),
    }
    return GraphSpec(nodes, inputs, space, tuple(extra)), sp


def test_dce_preserves_extra_outputs():
    gs, _ = _toy_graph(extra=("mid",))
    out, rep = DeadCodeElimination().run(gs)
    assert "mid" in out.outputs()
    assert [n.name for n in out.nodes] == ["m1", "m2"]
    # the unreferenced param meta is swept, the referenced ones stay
    assert "w_dead" not in out.inputs and "w1" in out.inputs


def test_dce_keeps_activation_inputs():
    gs, _ = _toy_graph()
    out, _ = DeadCodeElimination().run(gs)
    assert "x" in out.inputs  # positional calling convention survives


@pytest.mark.parametrize("arch", ("qwen3-4b", "jamba-1.5-large-398b"))
def test_fused_decode_graph_keeps_cache_outs(arch):
    _, dec = _graphs(arch)
    assert dec.extra_outputs  # decode graphs declare the cache boundary
    fused, _ = fuse_graph(dec)
    assert set(dec.extra_outputs) <= set(fused.outputs())
    assert fused.outputs() == dec.outputs()


def test_pipeline_verification_catches_dropped_output():
    """A pass that silently drops a graph result must be rejected."""
    from repro.axe.passes import Pass, PassError, PassReport

    class Broken(Pass):
        name = "broken"

        def rewrite(self, graph):
            return (
                GraphSpec(list(graph.nodes[:-1]), dict(graph.inputs),
                          graph.space, graph.extra_outputs),
                PassReport(self.name),
            )

    gs, _ = _toy_graph()
    with pytest.raises(PassError):
        PassPipeline((Broken(),)).run(gs)


def test_reshape_pair_collapse_composes_carry():
    space = PhysicalSpace.from_mesh_shape({"data": 1, "model": 2})
    nodes = [
        OpNode("r1", "reshape", ("x",), "r1",
               attrs=(("shape", (4, 8, 16)), ("carry", ((1, 2),)))),
        OpNode("r2", "reshape", ("r1",), "r2",
               attrs=(("shape", (32, 16)), ("carry", ((2, 1),)))),
    ]
    inputs = {"x": TensorMeta("x", (32, 16), "float32", "activation")}
    gs = GraphSpec(nodes, inputs, space)
    out, rep = ReshapePairCollapse().run(gs)
    assert [n.name for n in out.nodes] == ["r2"]
    assert out.nodes[0].inputs == ("x",)
    # x dim 1 -> r1 dim 2 -> r2 dim 1 composes to x dim 1 -> out dim 1
    assert out.nodes[0].attr("carry") == ((1, 1),)
    assert rep.eliminated == ["r1"]


# ---------------------------------------------------------------------------
# executable parity: fused == unfused (bit-exact under transfer layouts)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_fused_forward_matches_unfused(arch):
    cfg = _cfg(arch)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    _, params = _model(cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2 * 32,), 0, cfg.vocab_size, jnp.int32
    )
    base = axe.model_executable(cfg, mesh, 2, 32, dtype=cfg.dtype)
    exe = axe.model_executable(cfg, mesh, 2, 32, dtype=cfg.dtype, fuse=True)
    assert exe.fusion_report is not None
    assert exe.fusion_report.patterns_fired
    assert len(exe.graph.nodes) < len(base.graph.nodes)
    # the transfer plan carries the unfused layouts across the rewrite
    assert exe.plan.total_comm_bytes == base.plan.total_comm_bytes
    ref = np.asarray(base(axe.model_inputs(base.graph, cfg, params), tokens))
    got = np.asarray(exe(axe.model_inputs(exe.graph, cfg, params), tokens))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("arch", ARCHS)
def test_fused_decode_step_matches_unfused(arch):
    cfg = _cfg(arch)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    api, params = _model(cfg)
    b, max_seq = 2, 16
    cache = api.cache_init(b, max_seq)
    tok = jax.random.randint(jax.random.PRNGKey(3), (b,), 0,
                             cfg.vocab_size, jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    base = axe.decode_executable(cfg, mesh, b, max_seq, dtype=cfg.dtype)
    exe = axe.decode_executable(cfg, mesh, b, max_seq, dtype=cfg.dtype,
                                fuse=True)
    outs_b = base(axe.decode_inputs(base.graph, cfg, params, cache), tok, pos)
    outs_f = exe(axe.decode_inputs(exe.graph, cfg, params, cache), tok, pos)
    vb = dict(zip(base.graph.outputs(),
                  outs_b if isinstance(outs_b, tuple) else (outs_b,)))
    vf = dict(zip(exe.graph.outputs(),
                  outs_f if isinstance(outs_f, tuple) else (outs_f,)))
    assert set(vb) == set(vf)  # DCE kept every cache-out / side channel
    for name in vb:
        np.testing.assert_array_equal(np.asarray(vf[name]),
                                      np.asarray(vb[name]))


def test_fused_loss_grads_match_unfused():
    cfg = _cfg("qwen3-4b")
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    api, params = _model(cfg)
    batch = api.make_train_batch(
        jax.random.PRNGKey(1), type("S", (), {"batch": 2, "seq": 32})()
    )
    base = axe.model_executable(cfg, mesh, 2, 32, dtype=cfg.dtype)
    exe = axe.model_executable(cfg, mesh, 2, 32, dtype=cfg.dtype, fuse=True)
    loss_u, grads_u = jax.jit(
        jax.value_and_grad(axe.compiled_loss_fn(base, cfg))
    )(params, batch)
    loss_f, grads_f = jax.jit(
        jax.value_and_grad(axe.compiled_loss_fn(exe, cfg))
    )(params, batch)
    assert abs(float(loss_f) - float(loss_u)) < 1e-6
    flat_u = jax.tree_util.tree_leaves_with_path(grads_u)
    flat_f = dict(jax.tree_util.tree_leaves_with_path(grads_f))
    for path, g in flat_u:
        np.testing.assert_allclose(
            np.asarray(flat_f[path], np.float32), np.asarray(g, np.float32),
            rtol=1e-5, atol=1e-6, err_msg=str(path),
        )


def test_fused_lowering_trace_tags_epilogues():
    cfg = _cfg("qwen3-4b")
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    exe = axe.model_executable(cfg, mesh, 2, 32, dtype=cfg.dtype, fuse=True)
    tagged = [r for r in exe.lowering_trace if "+epi:" in r.backend]
    assert tagged, "fused nodes must surface their epilogue in the trace"


def test_stale_plan_on_fused_graph_rejected():
    """A plan solved on the unfused graph must not silently drive the
    fused rewrite (plan_covers node check + compile hard error)."""
    from repro.axe.compile import CompileError, plan_covers
    from repro.axe.solve import solve
    import sys

    _c = sys.modules["repro.axe.compile"]

    cfg = _cfg("qwen3-4b")
    space = PhysicalSpace.from_mesh_shape({"data": 1, "model": 1})
    gs = model_graph(cfg, 2, 32, space, dtype=cfg.dtype)
    res = solve(gs, beam=2)
    fused, _ = fuse_graph(gs)
    assert plan_covers(gs, res)
    assert not plan_covers(fused, res)
    with pytest.raises(CompileError):
        _c.compile(gs, None, res, fuse=True)


# ---------------------------------------------------------------------------
# ServeEngine: fused serving + warning dedupe on memoized recompiles
# ---------------------------------------------------------------------------


def test_serve_engine_fused_scores_match():
    from repro.serve.engine import ServeEngine

    cfg = _cfg("qwen3-4b")
    api, params = _model(cfg)
    eng_u = ServeEngine(api=api, batch_size=2, max_seq=32)
    eng_f = ServeEngine(api=api, batch_size=2, max_seq=32, fuse=True)
    eng_u.load(params)
    eng_f.load(params)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0,
                                cfg.vocab_size, jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(eng_f.score(tokens)), np.asarray(eng_u.score(tokens))
    )


def test_serve_engine_dedupes_repeated_warnings():
    """The same placement warning surfacing from repeated compiles /
    cache placements is re-emitted once per engine, not once per call."""
    from repro.serve.engine import ServeEngine

    cfg = _cfg("qwen3-4b")
    api, _ = _model(cfg)
    eng = ServeEngine(api=api, batch_size=2, max_seq=32)

    class _W(UserWarning):
        pass

    emitted = []
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for _ in range(3):
            with eng._dedup_warnings():
                warnings.warn(_W("plan does not cover: re-solving"))
        emitted = [w for w in rec if issubclass(w.category, _W)]
    assert len(emitted) == 1

    # a *different* message is its own key and still surfaces
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        with eng._dedup_warnings():
            warnings.warn(_W("another distinct condition"))
        emitted = [w for w in rec if issubclass(w.category, _W)]
    assert len(emitted) == 1


def test_serve_engine_stale_plan_warns_once_across_recompiles():
    from repro.axe.solve import solve
    from repro.serve.engine import ServeEngine

    cfg = _cfg("qwen3-4b")
    api, _ = _model(cfg)
    space = PhysicalSpace.from_mesh_shape({"data": 1, "model": 1})
    # a plan solved at a different seq never covers the engine's graphs
    stale = solve(model_graph(cfg, 2, 8, space, dtype=cfg.dtype), beam=2)
    eng = ServeEngine(api=api, batch_size=2, max_seq=32, layout_plan=stale)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        eng.compiled_forward(16)
        first = [w for w in rec if "does not cover" in str(w.message)]
        # drop the memo so the same shape recompiles from scratch
        eng._compiled.clear()
        eng.compiled_forward(16)
        total = [w for w in rec if "does not cover" in str(w.message)]
    assert len(first) == 1
    assert len(total) == 1
