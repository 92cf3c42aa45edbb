"""Compute/communication overlap (docs/overlap.md): the solver-visible
overlap dimension end-to-end.

Three layers under test. (1) The objective: ``redist_overlappable``
decides which redistributions an overlap schedule may hoist, and
``solve(..., overlap=True)`` charges that comm at ``max(comm,
compute)`` — on a constructed cost table the solver provably flips to a
comm-heavier placement whose collectives hide under compute, and every
Decision's ``hidden + exposed`` accounts exactly for its comm seconds.
(2) The collective: ``ring_all_gather`` (the async double-buffered
lowering MESH stages issue under overlap) is bit-identical to
``lax.all_gather(tiled=True)`` inside ``shard_map``. (3) The schedule:
overlap executables built on the *same solved plan* as their
synchronous twin are bit-comparable on forward / decode / grads across
all four model families, at 1 and 8 host devices, with the interleaved
issue order still satisfying the planned-vs-issued cross-check.
"""
import dataclasses
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import axe, compat
from repro.axe import hetero
from repro.axe.graphs import GraphSpec, TensorMeta
from repro.axe.propagate import OpNode, redistribute
from repro.axe.solve import (
    comm_seconds,
    overlappable_comm_bytes,
    producer_indices,
    redist_overlappable,
    solve,
)
from repro.axe.spec import AxeSpec, PhysicalSpace
from repro.configs import get_config, smoke_variant
from repro.models.model_zoo import build_model

ARCHS = ("qwen3-4b", "qwen3-moe-235b-a22b", "mamba2-2.7b",
         "jamba-1.5-large-398b")

_SPACE = PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})


def _cfg(arch, dtype=None):
    cfg = smoke_variant(get_config(arch))
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    return cfg


def _exe_pair(cfg, mesh, b, s, layers=2):
    """(sync, overlap) executables sharing one solved plan, so the A/B
    isolates the issue schedule from the solver."""
    exe_s = axe.model_executable(cfg, mesh, b, s, layers=layers,
                                 dtype=cfg.dtype)
    exe_o = axe.model_executable(cfg, mesh, b, s, layers=layers,
                                 dtype=cfg.dtype, plan=exe_s.solve_result,
                                 overlap=True)
    return exe_s, exe_o


# ---------------------------------------------------------------------------
# the overlappability predicate
# ---------------------------------------------------------------------------


def test_redist_overlappable_rules():
    sharded = AxeSpec.sharded((8, 64), _SPACE, {1: ("model",)}, "float32")
    full = sharded.with_placement({})
    node = OpNode("op", "norm", ("t",), "o")
    gather = redistribute(sharded, full, "t")
    assert gather.steps  # a real exchange, not a no-op

    # graph-input operand (no producer): overlappable at any idx > 0
    assert redist_overlappable(gather, 2, node, {})
    assert redist_overlappable(gather, 1, node, {})
    # entry 0 has no preceding compute slot to hide under
    assert not redist_overlappable(gather, 0, node, {})
    # nothing to hide
    noop = redistribute(full, full, "t")
    assert not redist_overlappable(noop, 2, node, {})
    # produced at idx-1: the input is not final when idx-1 starts
    assert not redist_overlappable(gather, 2, node, {"t": 1})
    assert redist_overlappable(gather, 2, node, {"t": 0})
    # fused-chain internal redistributions (operand not a direct input)
    other = OpNode("op", "norm", ("u",), "o")
    assert not redist_overlappable(gather, 2, other, {})
    # shape-changing exchanges are part of the op's own dataflow
    wide = AxeSpec.sharded((8, 128), _SPACE, {}, "float32")
    fake = types.SimpleNamespace(src=sharded, dst=wide, operand="t",
                                 steps=gather.steps)
    assert not redist_overlappable(fake, 2, node, {})
    # class-crossing Transfers are paced by the host link, never hidden
    tiered = PhysicalSpace.from_mesh_shape(
        {"model": 2, "host": 2}, classes={"host": "host"}
    )
    parked = AxeSpec.sharded((8, 64), tiered, {0: ("host",)}, "float32")
    xfer = redistribute(parked, hetero.declassed(parked), "t")
    assert not redist_overlappable(xfer, 2, node, {})

    assert overlappable_comm_bytes([gather, noop], 2, node, {}) == \
        gather.comm_bytes
    assert overlappable_comm_bytes([gather], 0, node, {}) == 0


def test_producer_indices_maps_outputs_only():
    nodes = [OpNode("a", "norm", ("x",), "y"),
             OpNode("b", "norm", ("y",), "z")]
    idx = producer_indices(nodes)
    assert idx == {"y": 0, "z": 1}
    assert "x" not in idx  # graph inputs are ready before entry 0


# ---------------------------------------------------------------------------
# the overlap objective flips a placement decision
# ---------------------------------------------------------------------------

# One compute class, memory-bound everywhere (peak flops effectively
# infinite), link four times slower than HBM. For the graph below the
# sync objective then charges the sharded-weight lineage
#   op0(small) + gather(y) + op2  >  op0(big) + op2
# while the overlap objective hides the gather under op2's compute and
# the inequality flips. Margins are ~12-18%, far from the knife edge.
_OVERLAP_TABLE = hetero.ClassTable(classes=(
    hetero.DeviceClass("accel", 1e15, 1e9, 0.25e9),
))


def _flip_graph():
    """proj: y = x @ w; filler: f = norm(q); read: z = norm(y).

    ``x`` [6,6] and ``q`` [6,6] admit only replication over {model:4},
    so the single real choice is ``w`` [6,1024]: replicated (y lands
    replicated, zero comm) vs dim-1 sharded (proj runs 4x narrower but
    ``read`` must gather y — comm produced at entry 0, consumed at
    entry 2, exactly the hoistable gap ``redist_overlappable`` wants).
    """
    nodes = [
        OpNode("proj", "matmul", ("x", "w"), "y"),
        OpNode("filler", "norm", ("q",), "f"),
        OpNode("read", "norm", ("y",), "z"),
    ]
    inputs = {
        "x": TensorMeta("x", (6, 6), "float32", "activation"),
        "w": TensorMeta("w", (6, 1024), "float32", "param"),
        "q": TensorMeta("q", (6, 6), "float32", "activation"),
    }
    return GraphSpec(nodes, inputs, PhysicalSpace.from_mesh_shape({"model": 4}))


def test_overlap_objective_flips_placement():
    gs = _flip_graph()
    with hetero.use_class_table(_OVERLAP_TABLE):
        sync = solve(gs, beam=4, compare_seeded=False)
        over = solve(gs, beam=4, compare_seeded=False, overlap=True)
    # sync: the gather is on the critical path, replication wins
    assert sync.assignment["w"].placement() == ((), ())
    assert sync.comm_bytes == 0
    assert sync.hidden_comm_s == 0.0
    # overlap: the same gather hides under the norm's compute, so the
    # solver provably chooses the comm-heavier sharded weight
    assert over.assignment["w"].placement() == ((), ("model",))
    assert over.comm_bytes > sync.comm_bytes
    assert over.hidden_comm_s > 0
    assert over.overlap and not sync.overlap
    # the hidden comm shows up on the consuming op's Decision
    read = [d for d in over.trace if d.op == "read"]
    assert read and read[0].hidden_comm_s > 0
    assert "hidden=" in read[0].describe()


def test_decision_trace_accounts_comm_split():
    """Per-Decision invariant: hidden + exposed == comm_seconds(comm),
    hidden == 0 everywhere without overlap, hidden > 0 somewhere with it
    — on a real model graph, not a construction."""
    cfg = _cfg("qwen3-4b")
    gs = axe.model_graph(cfg, 4, 32, _SPACE, dtype=cfg.dtype, layers=2)
    res_s = solve(gs)
    res_o = solve(gs, overlap=True)
    for d in res_s.trace:
        assert d.hidden_comm_s == 0.0
        assert abs(d.exposed_comm_s - comm_seconds(d.comm_bytes)) < 1e-15
    assert res_s.hidden_comm_s == 0.0
    for d in res_o.trace:
        assert d.hidden_comm_s >= 0.0 and d.exposed_comm_s >= 0.0
        assert abs(d.hidden_comm_s + d.exposed_comm_s
                   - comm_seconds(d.comm_bytes)) < 1e-15
        assert d.hidden_comm_s <= d.op_time_s + 1e-18
    assert any(d.hidden_comm_s > 0 for d in res_o.trace)
    # result-level split covers the *whole* plan's comm (incl. finalize)
    assert abs(res_o.hidden_comm_s + res_o.exposed_comm_s
               - comm_seconds(res_o.comm_bytes)) < 1e-12
    assert res_o.hidden_comm_s > 0
    assert "overlap: comm hidden=" in res_o.describe(trace=False)
    d = res_o.to_dict()
    assert d["overlap"] and d["hidden_comm_s"] == res_o.hidden_comm_s


# ---------------------------------------------------------------------------
# schedule parity at one device (the degenerate no-collective case)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_overlap_forward_bit_equal_single_device(arch):
    cfg = _cfg(arch)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    exe_s, exe_o = _exe_pair(cfg, mesh, 2, 32)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    inputs = axe.model_inputs(exe_s.graph, cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (64,), 0,
                              cfg.vocab_size, jnp.int32)
    assert np.array_equal(np.asarray(exe_s(inputs, toks)),
                          np.asarray(exe_o(inputs, toks)))
    assert tuple(exe_o.observed_collectives) == exe_o.collective_sequence()


def test_overlap_grads_bit_equal_single_device():
    for arch in ("qwen3-4b", "qwen3-moe-235b-a22b"):
        cfg = _cfg(arch)
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        exe_s, exe_o = _exe_pair(cfg, mesh, 2, 32)
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        batch = api.make_train_batch(
            jax.random.PRNGKey(1), type("S", (), {"batch": 2, "seq": 32})()
        )
        loss_s, grads_s = jax.value_and_grad(
            axe.compiled_loss_fn(exe_s, cfg))(params, batch)
        loss_o, grads_o = jax.value_and_grad(
            axe.compiled_loss_fn(exe_o, cfg))(params, batch)
        assert float(loss_s) == float(loss_o), arch
        for a, b in zip(jax.tree.leaves(grads_s), jax.tree.leaves(grads_o)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), arch


def test_overlap_decode_bit_equal_single_device():
    cfg = _cfg("qwen3-4b", dtype="float32")
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    b, max_seq, s0 = 2, 32, 5
    exe_s = axe.decode_executable(cfg, mesh, b, max_seq, dtype="float32")
    exe_o = axe.decode_executable(cfg, mesh, b, max_seq, dtype="float32",
                                  plan=exe_s.solve_result, overlap=True)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    cache = api.cache_init(b, max_seq)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (b, s0), 0,
                                 cfg.vocab_size, jnp.int32)
    logits0, cache = api.prefill(params, {"tokens": prompts}, cache)
    tok = jnp.argmax(logits0[:, -1], axis=-1).astype(jnp.int32)
    pos = jnp.full((b,), s0, jnp.int32)
    outs_s = exe_s(axe.decode_inputs(exe_s.graph, cfg, params, cache), tok, pos)
    outs_o = exe_o(axe.decode_inputs(exe_o.graph, cfg, params, cache), tok, pos)
    for a, b_ in zip(outs_s, outs_o):
        assert np.array_equal(np.asarray(a), np.asarray(b_))


# ---------------------------------------------------------------------------
# ring_all_gather == lax.all_gather(tiled) inside shard_map (8 devices)
# ---------------------------------------------------------------------------

_RING_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core.collective import ring_all_gather

mesh = compat.make_mesh((8,), ("x",))
out = {}
for dim, shape in ((0, (16, 5)), (1, (4, 24))):
    x = jax.random.normal(jax.random.PRNGKey(dim), shape, jnp.float32)
    spec = P("x") if dim == 0 else P(None, "x")
    ring = jax.shard_map(lambda v: ring_all_gather(v, "x", dim),
                            mesh=mesh, in_specs=(spec,), out_specs=P(),
                            check_vma=False)
    ref = jax.shard_map(
        lambda v: jax.lax.all_gather(v, "x", axis=dim, tiled=True),
        mesh=mesh, in_specs=(spec,), out_specs=P(), check_vma=False)
    got, want = np.asarray(ring(x)), np.asarray(ref(x))
    out[f"dim{dim}"] = {
        "bit_equal": bool(np.array_equal(got, want)),
        "shape_ok": got.shape == want.shape == shape,
    }
print("RESULT " + json.dumps(out))
"""


def _run_child(src, timeout=1500):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    proc = subprocess.run(
        [sys.executable, "-c", src], env=env,
        capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_ring_all_gather_matches_lax_8_devices():
    out = _run_child(_RING_CHILD)
    for dim, rec in out.items():
        assert rec["bit_equal"], (dim, rec)
        assert rec["shape_ok"], (dim, rec)


# ---------------------------------------------------------------------------
# schedule parity at 8 host devices (real collectives, real prefetch)
# ---------------------------------------------------------------------------

_OVERLAP_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np
from repro import axe, compat
from repro.configs import get_config, smoke_variant
from repro.models.model_zoo import build_model

def cfg_for(arch, dtype=None):
    cfg = smoke_variant(get_config(arch))
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    return cfg

out = {}
mesh = compat.make_mesh((2, 4), ("data", "model"))
for arch in ("qwen3-4b", "qwen3-moe-235b-a22b", "mamba2-2.7b",
             "jamba-1.5-large-398b"):
    cfg = cfg_for(arch)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, s = 2, 32
    exe_s = axe.model_executable(cfg, mesh, b, s, layers=2, dtype=cfg.dtype)
    exe_o = axe.model_executable(cfg, mesh, b, s, layers=2, dtype=cfg.dtype,
                                 plan=exe_s.solve_result, overlap=True)
    inputs = axe.model_inputs(exe_s.graph, cfg, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b * s,), 0,
                              cfg.vocab_size, jnp.int32)
    ys = np.asarray(exe_s(inputs, toks))
    yo = np.asarray(exe_o(inputs, toks))
    out[arch] = {
        "bit_equal": bool(np.array_equal(ys, yo)),
        "prefetched": sum(len(r.prefetched) for r in exe_o.lowering_trace),
        "issued_matches_plan": list(exe_o.observed_collectives)
                               == list(exe_o.collective_sequence()),
        "collectives": len(exe_o.collective_sequence()),
    }

# decode parity on the two cache styles (KV-attention and SSM+attention)
for arch in ("qwen3-4b", "jamba-1.5-large-398b"):
    cfg = cfg_for(arch, dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, max_seq, s0 = 4, 32, 5
    cache = api.cache_init(b, max_seq)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (b, s0), 0,
                                 cfg.vocab_size, jnp.int32)
    logits0, cache = api.prefill(params, {"tokens": prompts}, cache)
    tok = jnp.argmax(logits0[:, -1], axis=-1).astype(jnp.int32)
    pos = jnp.full((b,), s0, jnp.int32)
    exe_s = axe.decode_executable(cfg, mesh, b, max_seq, dtype="float32")
    exe_o = axe.decode_executable(cfg, mesh, b, max_seq, dtype="float32",
                                  plan=exe_s.solve_result, overlap=True)
    outs_s = exe_s(axe.decode_inputs(exe_s.graph, cfg, params, cache), tok, pos)
    outs_o = exe_o(axe.decode_inputs(exe_o.graph, cfg, params, cache), tok, pos)
    out[arch + ".decode"] = {
        "bit_equal": all(np.array_equal(np.asarray(a), np.asarray(c))
                         for a, c in zip(outs_s, outs_o)),
    }

# grads through the overlap schedule (dense)
cfg = cfg_for("qwen3-4b")
api = build_model(cfg)
params = api.init(jax.random.PRNGKey(0))
batch = api.make_train_batch(jax.random.PRNGKey(1),
                             type("S", (), {"batch": 4, "seq": 32})())
exe_s = axe.model_executable(cfg, mesh, 4, 32, layers=2, dtype=cfg.dtype)
exe_o = axe.model_executable(cfg, mesh, 4, 32, layers=2, dtype=cfg.dtype,
                             plan=exe_s.solve_result, overlap=True)
ls, gs = jax.value_and_grad(axe.compiled_loss_fn(exe_s, cfg))(params, batch)
lo, go = jax.value_and_grad(axe.compiled_loss_fn(exe_o, cfg))(params, batch)
out["qwen3-4b.grads"] = {
    "loss_equal": float(ls) == float(lo),
    "grads_equal": all(np.array_equal(np.asarray(a), np.asarray(c))
                       for a, c in zip(jax.tree.leaves(gs),
                                       jax.tree.leaves(go))),
}
print("RESULT " + json.dumps(out))
"""


def test_overlap_bit_equal_8_devices():
    out = _run_child(_OVERLAP_CHILD)
    for arch in ARCHS:
        rec = out[arch]
        assert rec["bit_equal"], (arch, rec)
        assert rec["issued_matches_plan"], (arch, rec)
        assert rec["collectives"] > 0, (arch, rec)
        # sharded models really hoist something: the schedule is live
        assert rec["prefetched"] > 0, (arch, rec)
    for key in ("qwen3-4b.decode", "jamba-1.5-large-398b.decode"):
        assert out[key]["bit_equal"], (key, out[key])
    assert out["qwen3-4b.grads"]["loss_equal"], out["qwen3-4b.grads"]
    assert out["qwen3-4b.grads"]["grads_equal"], out["qwen3-4b.grads"]
