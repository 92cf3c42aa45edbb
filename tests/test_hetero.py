"""Heterogeneous device classes (``repro.axe.hetero``): memory-tiered
PhysicalSpace, the class-aware solver, the class-crossing Transfer
collective, and host-offload of cold tensors.

CPU-only correctness story: two *logical* device classes with different
cost tables. The deviceless tests assert the solver's placement flips
when the tables flip and that no compute op ever sees a host-parked
operand; the subprocess tests run host-parked executables on 1, 2, and
8 forced host-platform devices and check bit-level agreement with the
all-accelerator reference plus the planned-vs-issued Transfer count.
"""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.axe import hetero
from repro.axe import rules as axe_rules
from repro.axe.graphs import GraphSpec, TensorMeta
from repro.axe.propagate import OpNode, redistribute
from repro.axe.solve import SolveError, solve
from repro.axe.spec import AxeSpec, PhysicalSpace
from repro.core.collective import AllGather, AllReduce, Transfer
from repro.launch import roofline

V5E = roofline.device_peaks(roofline.TARGET_KIND)

# ---------------------------------------------------------------------------
# tables / parsing
# ---------------------------------------------------------------------------


def test_default_table_matches_accelerator_constants():
    # homogeneous costing must be bit-identical with or without the
    # hetero module in the loop: the default accel class IS the v5e
    # profile launch/roofline always used
    assert hetero.default_peaks() == (V5E.flops_bf16, V5E.hbm_bw)
    assert hetero.default_link_bw() == V5E.ici_bw_per_link * V5E.ici_links
    table = hetero.default_class_table()
    host = table.cls("host")
    assert host.peak_flops == 0.0 and not host.computes
    assert table.cls(table.default).computes


def test_device_peaks_keyed_by_kind():
    # the table is keyed by jax's device_kind; an unknown kind raises
    # instead of pricing with another chip's (or the CPU's) peaks
    assert V5E.flops_bf16 == 197e12 and V5E.hbm_bw == 819e9
    with pytest.raises(ValueError, match="no peak rates"):
        roofline.device_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        roofline.schedule_time(flops=1.0, mem_bytes=1.0, backend="gpu")


def test_parse_classes_roundtrip_and_errors():
    t = hetero.parse_classes("host=0:50e9:8e9:1e6")
    host = t.cls("host")
    assert (host.peak_flops, host.mem_bw, host.link_bw) == (0.0, 50e9, 8e9)
    assert host.capacity == 1e6
    # unnamed classes keep their defaults; the default class stays accel
    assert t.default == "accel"
    assert t.cls("accel").peak_flops == V5E.flops_bf16
    with pytest.raises(hetero.HeteroError):
        hetero.parse_classes("garbage")
    with pytest.raises(hetero.HeteroError):
        hetero.parse_classes("host=1:2")
    with pytest.raises(hetero.HeteroError):
        # the default class must be able to compute
        hetero.ClassTable(
            classes=(hetero.DeviceClass("accel", 0.0, 1e9, 1e9),)
        )


def test_space_classes_signature_and_accessors():
    plain = PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})
    assert not plain.has_classes
    assert "|" not in plain.signature()  # homogeneous signature unchanged
    tiered = PhysicalSpace.from_mesh_shape(
        {"data": 2, "model": 4, "host": 2}, classes={"host": "host"}
    )
    assert tiered.has_classes
    assert tiered.signature().endswith("|host:host")
    assert tiered.axis_class("host") == "host"
    assert tiered.axis_class("model") == "accel"
    assert tiered.class_axes() == ("host",)
    with pytest.raises(Exception):
        PhysicalSpace.from_mesh_shape({"data": 2}, classes={"nope": "host"})


# ---------------------------------------------------------------------------
# spec-level helpers + the Transfer collective
# ---------------------------------------------------------------------------

_TIERED = PhysicalSpace.from_mesh_shape(
    {"model": 2, "host": 2}, classes={"host": "host"}
)


def test_parked_declassed_and_transfer_bytes():
    src = AxeSpec.sharded((64, 16), _TIERED, {0: ("host",)}, "float32")
    assert hetero.is_parked(src)
    assert hetero.parked_axes(src) == ("host",)
    dst = hetero.declassed(src)
    assert not hetero.is_parked(dst)
    assert dst.shape == src.shape

    r = redistribute(src, dst, "t")
    assert any(isinstance(s, Transfer) for s in r.steps)
    assert not any(isinstance(s, AllGather) for s in r.steps)
    # gather from the 2-way host tier: shard*(p-1) with the shard at
    # full mesh granularity (plan_comm_bytes' convention) — charged to
    # transfer, never to ICI comm
    shard = 64 * 16 * 4 // (2 * 2)
    assert r.transfer_bytes == shard * (2 - 1)
    assert r.comm_bytes == 0


def test_classify_steps_only_touches_class_axes():
    steps = (AllGather("host", 0), AllGather("model", 1), AllReduce("model"))
    out = hetero.classify_steps(steps, _TIERED)
    assert out[0] == Transfer("host", 0, "gather")
    assert out[1:] == steps[1:]


def test_accel_bytes_zero_when_parked():
    repl = AxeSpec.sharded((64, 16), _TIERED, {}, "float32")
    assert hetero.accel_bytes(repl) == 64 * 16 * 4  # replicated: full tensor
    shard = AxeSpec.sharded((64, 16), _TIERED, {0: ("model",)}, "float32")
    assert hetero.accel_bytes(shard) == 64 * 16 * 4 // 2
    parked = AxeSpec.sharded((64, 16), _TIERED, {0: ("host",)}, "float32")
    assert hetero.accel_bytes(parked) == 0


# ---------------------------------------------------------------------------
# solver: placement flips with the cost tables; compute never on host
# ---------------------------------------------------------------------------


def _tiny_graph(space):
    """embed(tok[32], table[64x16]) -> x; matmul(x, w[16x16]) -> y."""
    nodes = [
        OpNode("embed", "embed", ("tok", "table"), "x", ()),
        OpNode("mm", "matmul", ("x", "w"), "y", ()),
    ]
    inputs = {
        "tok": TensorMeta("tok", (32,), "int32", "activation"),
        "table": TensorMeta("table", (64, 16), "float32", "param"),
        "w": TensorMeta("w", (16, 16), "float32", "param"),
    }
    return GraphSpec(nodes, inputs, space)


def _capacity_table(host_link: float) -> hetero.ClassTable:
    """Accelerator capacity below the embedding table's 4096 B — the
    solver must shard or park it; the host link speed decides which."""
    return hetero.ClassTable(classes=(
        hetero.DeviceClass("accel", V5E.flops_bf16,
                           V5E.hbm_bw, 200e9, capacity=2048.0),
        hetero.DeviceClass("host", 0.0, 100e9, host_link),
    ))


def test_placement_flips_when_cost_tables_flip():
    gs = _tiny_graph(_TIERED)
    with hetero.use_class_table(_capacity_table(1e12)):
        fast = solve(gs, beam=4, compare_seeded=False)
    with hetero.use_class_table(_capacity_table(1e6)):
        slow = solve(gs, beam=4, compare_seeded=False)
    # cheap host link: the cold embedding table parks on the host tier
    assert hetero.is_parked(fast.assignment["table"])
    assert fast.transfer_bytes > 0
    # expensive host link: the same capacity squeeze is answered with
    # ICI sharding instead — the placement provably flips with the table
    assert not hetero.is_parked(slow.assignment["table"])


def test_compute_never_sees_a_parked_operand():
    gs = _tiny_graph(_TIERED)
    with hetero.use_class_table(_capacity_table(1e12)):
        res = solve(gs, beam=4, compare_seeded=False, offload=("table",))
    assert hetero.is_parked(res.assignment["table"])
    # the class-align pre-pass guarantees every op body runs on
    # declassed operands: a no-flops class can never be asked to compute
    for e in res.plan.entries:
        if e.op.kind == "finalize":
            continue
        for spec in e.input_specs(res.plan.env):
            assert not hetero.is_parked(spec), (e.op.name, spec.signature())


def test_offload_requires_class_annotated_space():
    gs = _tiny_graph(PhysicalSpace.from_mesh_shape({"model": 2, "host": 2}))
    with pytest.raises(SolveError):
        solve(gs, beam=2, compare_seeded=False, offload=("table",))


def test_offload_degrades_on_degree_one_tier():
    space = PhysicalSpace.from_mesh_shape(
        {"model": 2, "host": 1}, classes={"host": "host"}
    )
    res = solve(_tiny_graph(space), beam=2, compare_seeded=False,
                offload=("table",))
    # a 1-device host tier cannot park (the canonical layout drops
    # no-op shards): offload is a no-op, not an error
    assert not hetero.is_parked(res.assignment["table"])
    assert res.transfer_bytes == 0


# ---------------------------------------------------------------------------
# rules: class placement carries onto param/opt/cache leaves
# ---------------------------------------------------------------------------

_CARRY = PhysicalSpace.from_mesh_shape(
    {"data": 2, "model": 2, "host": 2}, classes={"host": "host"}
)


def test_offload_extend_parks_and_opt_specs_applies_it():
    spec = AxeSpec.sharded((64, 16), _CARRY, {}, "float32")
    parked = axe_rules.offload_extend(spec)
    assert hetero.parked_axes(parked) == ("host",)
    assert parked.placement()[0] == ("host",)  # largest dim first
    # degree-1 tier: no-op, never an error
    deg1 = PhysicalSpace.from_mesh_shape(
        {"model": 2, "host": 1}, classes={"host": "host"}
    )
    s1 = AxeSpec.sharded((64, 16), deg1, {}, "float32")
    assert axe_rules.offload_extend(s1) == s1

    o = axe_rules.opt_specs({"w": spec}, zero1=False, offload_axes=("host",))
    assert hetero.is_parked(o["w"])
    # without offload axes the tree is untouched
    assert axe_rules.opt_specs({"w": spec}, zero1=False) == {"w": spec}


def test_plan_rules_carry_class_placement_onto_param_leaves():
    parked = axe_rules.offload_extend(
        AxeSpec.sharded((64, 16), _CARRY, {}, "float32")
    )
    pr = axe_rules.PlanRules({"embed": parked})
    # the consuming space is the plain (un-annotated) mesh twin — the
    # solved class annotations must survive onto the leaf
    plain = PhysicalSpace.from_mesh_shape({"data": 2, "model": 2, "host": 2})
    leaf = pr.spec_for("embed", (64, 16), plain, "float32")
    assert leaf is not None
    assert leaf.space.has_classes
    assert hetero.is_parked(leaf)


def test_cache_specs_carry_class_placement():
    import jax

    k = jax.ShapeDtypeStruct((1, 4, 32, 2, 8), "float32")
    cache = {"l0": {"k": k, "v": k}}
    solved = {
        "k_cache": axe_rules.offload_extend(
            AxeSpec.sharded((4, 32, 2, 8), _CARRY, {}, "float32")
        )
    }
    plain = PhysicalSpace.from_mesh_shape({"data": 2, "model": 2, "host": 2})
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # v_cache falls back to the tables
        specs = axe_rules.cache_specs(cache, plain, plan=solved)
    leaf = specs["l0"]["k"]
    assert leaf.space.has_classes
    assert hetero.is_parked(leaf)


# ---------------------------------------------------------------------------
# serving tier: two-tier PagePool accounting + batcher preemption parity
# ---------------------------------------------------------------------------


def test_pagepool_two_tier_accounting():
    from repro.serve import PagePool, PagePoolError

    pool = PagePool(4, 4, host_pages=4)
    pool.alloc(1, 2)
    pool.alloc(2, 2)
    assert pool.available == 0
    with pytest.raises(PagePoolError):
        pool.evict(3)                     # no lease to evict
    assert pool.evict(1) == 2
    assert pool.available == 2
    assert pool.host_leased() == {1: 2}
    with pytest.raises(PagePoolError):
        pool.evict(1)                     # already evicted
    with pytest.raises(PagePoolError):
        pool.alloc(1, 1)                  # a host lease still blocks alloc
    pool.alloc(3, 2)
    with pytest.raises(PagePoolError):
        pool.lease_back(1)                # no accelerator pages free
    pool.free(3)
    assert len(pool.lease_back(1)) == 2
    with pytest.raises(PagePoolError):
        pool.lease_back(1)                # host lease consumed
    assert pool.transfer_pages == {"out": 2, "in": 2}
    # finishing while parked releases the host lease exactly once
    pool.evict(2)
    pool.free(2)
    assert pool.host_leased() == {}
    assert pool.freed_count[2] == 1
    pool.free(1)
    assert pool.available == 4            # nothing leaked in either tier


def test_pagepool_host_capacity_enforced():
    from repro.serve import PagePool, PagePoolError

    pool = PagePool(4, 4, host_pages=1)
    pool.alloc(9, 2)
    with pytest.raises(PagePoolError):
        pool.evict(9)                     # wants 2 host pages, only 1
    assert pool.host_leased() == {}
    assert pool.available == 2            # the lease survives the refusal


def _serve_engine():
    import jax

    from repro.configs import get_config, smoke_variant
    from repro.models.model_zoo import build_model
    from repro.serve import ServeEngine

    cfg = dataclasses.replace(smoke_variant(get_config("qwen3-4b")),
                              dtype="float32")
    api = build_model(cfg)
    eng = ServeEngine(api=api, batch_size=3, max_seq=32)
    eng.load(api.init(jax.random.PRNGKey(0)))
    return cfg, eng


def _drain(bat, reqs):
    for r in reqs:
        bat.submit(r)
    while bat.step():
        pass
    return {uid: list(res.tokens) for uid, res in bat.results.items()}


def test_batcher_offload_round_trip_token_parity():
    """6 requests through 3 slots with only 4 accelerator pages: head-of-
    line blocking forces page-outs; every evicted request leases back
    through the host tier and must emit the exact tokens it would have
    with unconstrained pages (uid-keyed sampling, position-exact cache)."""
    from repro.serve import ContinuousBatcher, Request

    cfg, eng = _serve_engine()
    rng = np.random.RandomState(7)

    def reqs():
        return [
            Request(uid=u,
                    prompt=rng.randint(0, cfg.vocab_size, size=4).astype(np.int32),
                    max_new_tokens=4, arrival=0)
            for u in range(1, 7)
        ]

    rng.seed(7)
    ref = _drain(ContinuousBatcher(eng, page_size=4), reqs())
    rng.seed(7)
    two = ContinuousBatcher(eng, page_size=4, n_pages=4, offload=True)
    got = _drain(two, reqs())

    assert got == ref                      # bit-exact token parity
    outs = [e for e in two.transfer_log if e[0] == "page_out"]
    ins = [e for e in two.transfer_log if e[0] == "page_in"]
    assert outs and ins                    # real round trips happened
    assert all(tag == "Transfer" for (_k, _u, tag) in two.transfer_log)
    assert two.pool.transfer_pages["out"] == two.pool.transfer_pages["in"]
    assert two.transfer_bytes > 0
    assert two.pool.available == two.pool.n_pages
    assert two.pool.host_leased() == {}


# ---------------------------------------------------------------------------
# compiled parity: host-parked executable == all-accelerator reference,
# every planned Transfer observed (1 / 2 / 8 forced host devices)
# ---------------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(n_dev)d"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np
from repro import axe, compat
from repro.configs import get_config, smoke_variant
from repro.models import transformer as tf_mod
from repro.models.model_zoo import build_model

cfg = dataclasses.replace(smoke_variant(get_config("qwen3-4b")),
                          dtype="float32")
api = build_model(cfg)
params = api.init(jax.random.PRNGKey(0))
b, s = 4, 32
tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                            cfg.vocab_size, jnp.int32)
ref = np.asarray(tf_mod.lm_forward(params, {"tokens": tokens}, cfg,
                                   remat=False))
mesh = compat.make_mesh(%(mesh)s, ("data", "model", "host"))
exe = axe.model_executable(cfg, mesh, b, s, dtype=cfg.dtype,
                           classes={"host": "host"}, offload=("embed",))
got = np.asarray(exe(axe.model_inputs(exe.graph, cfg, params),
                     tokens.reshape(-1))).reshape(b, s, -1)
planned = list(exe.collective_sequence())
out = {
    "max_diff": float(np.max(np.abs(got - ref))),
    "transfers": sum(1 for (_o, _t, steps) in planned
                     if "Transfer" in steps),
    "issued_matches_plan": list(exe.observed_collectives) == planned,
}
print("RESULT " + json.dumps(out))
"""


def _run_child(src):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    proc = subprocess.run(
        [sys.executable, "-c", src], env=env,
        capture_output=True, text=True, timeout=1500,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize(
    "n_dev,mesh_dims,min_transfers",
    [
        (1, (1, 1, 1), 0),   # degenerate tier: offload degrades to no-op
        (2, (1, 1, 2), 1),   # the whole accelerator is one device
        (8, (2, 2, 2), 1),   # sharded accel + 2-way host tier
    ],
    ids=["1dev", "2dev", "8dev"],
)
def test_host_parked_executable_matches_reference(n_dev, mesh_dims,
                                                  min_transfers):
    out = _run_child(_CHILD % {"n_dev": n_dev, "mesh": repr(mesh_dims)})
    assert out["max_diff"] < 1e-5, out
    assert out["transfers"] >= min_transfers, out
    if n_dev == 1:
        assert out["transfers"] == 0, out
    assert out["issued_matches_plan"], out
