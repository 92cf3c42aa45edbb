"""The axe.program kernel DSL: numerics parity (program vs oracle,
f32/bf16) for the five built-in programs, scope-tagged stage
validation, program/stage schedule keys through the tune layer, the
generic autotuner path, and the legacy-shim contract (keyword
compatibility + DeprecationWarning)."""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.axe.program import PROGRAMS, ProgramError, get_program
from repro.axe.stages import StageError
from repro.core.scopes import Scope, current_scope, scope
from repro.kernels import programs, ref

DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-3, atol=1e-4)


@pytest.fixture
def tmp_cache(tmp_path):
    cache = tune.use_cache(tmp_path / "schedules.json")
    yield cache
    tune.use_cache(None)


# ---------------------------------------------------------------------------
# numerics parity: program (Pallas path) vs oracle, f32 + bf16
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
def test_matmul_program_parity(dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a, b = _rand(k1, (256, 512), dtype), _rand(k2, (512, 256), dtype)
    got = programs.matmul(a, b, stage="tile", impl="kernel",
                          blocks={"bm": 128, "bn": 128, "bk": 256})
    np.testing.assert_allclose(
        got.astype(jnp.float32), ref.matmul_ref(a, b).astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_program_parity(dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (1, 2, 256, 64), dtype)
    k = _rand(ks[1], (1, 2, 256, 64), dtype)
    v = _rand(ks[2], (1, 2, 256, 64), dtype)
    got = programs.flash_attention(q, k, v, causal=True,
                                   blocks={"bq": 128, "bkv": 128})
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", DTYPES)
def test_moe_gemm_program_parity(dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    x = _rand(k1, (4, 128, 256), dtype)
    w = _rand(k2, (4, 256, 512), dtype)
    got = programs.moe_gemm(x, w, stage="expert_gemm", impl="kernel")
    np.testing.assert_allclose(
        got.astype(jnp.float32), ref.moe_gemm_ref(x, w).astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", DTYPES)
def test_rmsnorm_program_parity(dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = _rand(k1, (4, 96, 512), dtype)
    w = _rand(k2, (512,), dtype)
    got = programs.rmsnorm(x, w, stage="rows", impl="kernel")
    np.testing.assert_allclose(
        got.astype(jnp.float32), ref.rmsnorm_ref(x, w).astype(jnp.float32), **_tol(dtype)
    )


_CM_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro import compat
from repro.axe.spec import AxeSpec, PhysicalSpace
from repro.kernels import programs, ref

mesh = compat.make_mesh((8,), ("model",))
space = PhysicalSpace.from_mesh_shape({"model": 8})
M, K, N = 256, 512, 128
out = {}
for dtype in (jnp.float32, jnp.bfloat16):
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32).astype(dtype)
    want = ref.collective_matmul_ref(a, b, 8).astype(jnp.float32)
    out[f"{jnp.dtype(dtype).name}/max_want"] = float(jnp.max(jnp.abs(want)))
    sa = AxeSpec.sharded((M, K), space, {1: ("model",)})
    sb = AxeSpec.sharded((K, N), space, {0: ("model",)})
    so = AxeSpec.sharded((M, N), space, {0: ("model",)})
    for impl in ("ring", "psum_scatter"):
        f = jax.jit(programs.collective_matmul.shard_map(mesh, (sa, sb), so, impl=impl))
        got = f(a, b).astype(jnp.float32)
        out[f"{jnp.dtype(dtype).name}/{impl}"] = float(jnp.max(jnp.abs(got - want)))
print(json.dumps(out))
"""


def test_collective_matmul_program_parity_both_dtypes():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", _CM_CHILD], capture_output=True, text=True, env=env,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    errs = json.loads(out.stdout.strip().splitlines()[-1])
    assert errs["float32/ring"] < 1e-3, errs
    assert errs["float32/psum_scatter"] < 1e-3, errs
    # bf16 rounds the output once more than the f32 reference: bound the
    # error by two bf16 units in the last place at the output's magnitude
    # (8 mantissa bits: ulp(x) = 2**(floor(log2 x) - 7))
    ulp = 2.0 ** (math.floor(math.log2(errs["bfloat16/max_want"])) - 7)
    assert errs["bfloat16/ring"] <= 2 * ulp, (ulp, errs)
    assert errs["bfloat16/psum_scatter"] <= 2 * ulp, (ulp, errs)


# ---------------------------------------------------------------------------
# moe routing reference (satellite: routing oracle vs models.moe)
# ---------------------------------------------------------------------------

def test_moe_routing_matches_loop_oracle():
    from repro.models import moe as moe_mod

    class Cfg:
        num_experts = 4
        experts_per_tok = 2
        capacity_factor = 1.25
        moe_d_ff = 64
        d_model = 32

    cfg = Cfg()
    t, d = 64, cfg.d_model
    key = jax.random.PRNGKey(0)
    xf = jax.random.normal(key, (t, d), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(1), (d, cfg.num_experts), jnp.float32)

    buf, meta = moe_mod._local_dispatch(xf, router, cfg)
    c = moe_mod.capacity(t, cfg)
    ref_buf, ref_combine = ref.moe_routing_ref(
        np.asarray(xf), np.asarray(router),
        experts_per_tok=cfg.experts_per_tok, capacity=c,
    )
    np.testing.assert_allclose(np.asarray(buf), ref_buf, rtol=1e-5, atol=1e-5)

    # identity "FFN": combine must gate-weight and gather identically
    y = moe_mod._local_combine(buf, meta, t, d)
    np.testing.assert_allclose(np.asarray(y), ref_combine(ref_buf), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# stage-graph validation: scope ordering, unknown stages, registry
# ---------------------------------------------------------------------------

def test_programs_registered():
    for prog in programs.ALL_PROGRAMS:
        assert PROGRAMS[prog.name] is prog
        assert get_program(prog.name) is prog
    with pytest.raises(ProgramError, match="no program named"):
        get_program("nonexistent")


def test_stage_scope_validation():
    a = jnp.zeros((16, 16), jnp.float32)
    # the MESH-scope collective stage cannot be entered from BLOCK scope
    with scope(Scope.BLOCK):
        with pytest.raises(StageError, match="cannot be entered"):
            programs.collective_matmul(a, a, axis_name="model")
    # ...and the GRID-scope tile stage cannot be entered from BLOCK either
    with scope(Scope.BLOCK):
        with pytest.raises(StageError, match="cannot be entered"):
            programs.matmul(a, a, stage="tile")
    assert current_scope() == Scope.MESH


def test_unknown_stage_raises():
    a = jnp.zeros((16, 16), jnp.float32)
    with pytest.raises(ProgramError, match="no stage"):
        programs.matmul(a, a, stage="warp_specialize")


def test_program_describe_lists_stage_keys():
    text = programs.matmul.describe()
    assert "matmul/tile" in text and "matmul/dot" in text and "matmul/mac" in text
    assert "variants kernel|xla" in text


def test_block_stage_usable_directly():
    # BLOCK stages are plain jnp bodies: callable standalone via a
    # program dispatched at BLOCK scope (functional single-tile form)
    a = jnp.ones((8, 8), jnp.float32)
    with scope(Scope.BLOCK):
        out = programs.matmul(a, a)
    np.testing.assert_allclose(out, a @ a, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# schedules: program/stage keys through the one tune path
# ---------------------------------------------------------------------------

def test_stage_ops_registered_with_tune():
    from repro.tune.schedule import STAGE_IMPLS, allowed_impls, default_schedule

    assert STAGE_IMPLS["matmul/tile"] == ("kernel", "xla")
    assert STAGE_IMPLS["collective_matmul/kshard"] == ("ring", "psum_scatter")
    assert allowed_impls("rmsnorm/rows") == ("kernel", "xla")
    d = default_schedule("matmul/tile")
    assert d.impl == "kernel" and d.block("bm") == 256
    # invalid impls on stage keys are rejected like legacy ops
    with pytest.raises(tune.InvalidImplError):
        tune.Schedule("collective_matmul/kshard", "kernel")


def test_get_schedule_resolves_stage_keys(tmp_cache):
    s = tune.get_schedule(
        "matmul/tile", shapes=((256, 512), (512, 256)),
        dtypes=(jnp.float32, jnp.float32),
    )
    assert s.op == "matmul/tile"
    key = [k for k in tmp_cache.keys() if k.startswith("matmul/tile|")]
    assert key, tmp_cache.keys()


def test_planner_plans_stage_keys():
    cands = tune.planner.plan(
        "rmsnorm/rows", shapes=((1024, 512), (512,)), dtypes=(jnp.float32,),
        backend="tpu",
    )
    assert cands and all(c.schedule.op == "rmsnorm/rows" for c in cands)
    assert any(c.schedule.impl == "kernel" for c in cands)
    assert any(c.schedule.impl == "xla" for c in cands)


def test_disable_env_returns_stage_defaults(tmp_cache, monkeypatch):
    monkeypatch.setenv(tune.DISABLE_ENV, "1")
    s = tune.get_schedule(
        "flash_attention/attend", shapes=((1, 2, 256, 64), (1, 2, 256, 64)),
        dtypes=(jnp.float32, jnp.float32),
    )
    assert s == tune.Schedule("flash_attention/attend", "kernel",
                              (("bq", 128), ("bkv", 128)))


def test_force_schedule_mapping_pins_per_stage(tmp_cache):
    a = jnp.ones((256, 512), jnp.float32)
    b = jnp.ones((512, 256), jnp.float32)
    with tune.force_schedule({"matmul/tile": "kernel:bm=128,bn=128,bk=128"}):
        s = tune.get_schedule("matmul/tile", shapes=(a.shape, b.shape),
                              dtypes=(a.dtype, b.dtype))
        assert s.block("bm") == 128
        # other ops resolve normally
        s2 = tune.get_schedule("rmsnorm/rows", shapes=((256, 512), (512,)),
                               dtypes=(a.dtype,))
        assert s2.op == "rmsnorm/rows"


def test_force_env_scoped_syntax_parses():
    from repro.tune import _parse_forced_env

    parsed = _parse_forced_env("matmul/tile=xla;rmsnorm/rows=kernel:brows=512")
    assert parsed == {"matmul/tile": "xla", "rmsnorm/rows": "kernel:brows=512"}
    # a bare spec (even with = inside block args) stays global
    assert _parse_forced_env("kernel:bm=128,bn=128,bk=256") == "kernel:bm=128,bn=128,bk=256"
    assert _parse_forced_env("xla") == "xla"
    # mixed: the bare segment becomes the "*" fallback, not dropped
    mixed = _parse_forced_env("xla;matmul/tile=kernel:bm=128,bn=128,bk=128")
    assert mixed == {"*": "xla", "matmul/tile": "kernel:bm=128,bn=128,bk=128"}


def test_force_mixed_global_and_scoped_applies_both(tmp_cache):
    with tune.force_schedule({"*": "xla",
                              "matmul/tile": "kernel:bm=128,bn=128,bk=128"}):
        s = tune.get_schedule("matmul/tile", shapes=((256, 256), (256, 256)),
                              dtypes=(jnp.float32, jnp.float32))
        assert s.impl == "kernel" and s.block("bm") == 128
        s2 = tune.get_schedule("moe_gemm/expert_gemm",
                               shapes=((2, 128, 256), (2, 256, 128)),
                               dtypes=(jnp.float32, jnp.float32))
        assert s2.impl == "xla"  # the global fallback


def test_autotune_program_rejects_mesh_stage():
    a = jnp.ones((16, 16), jnp.float32)
    with pytest.raises(ValueError, match="MESH scope"):
        tune.autotune_program(programs.collective_matmul, a, a, axis_name="model")


def test_autotune_program_populates_stage_key(tmp_cache):
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    rep = tune.autotune_program(programs.matmul, a, b, stage="tile",
                                top_k=2, iters=1)
    assert rep.measurements and not rep.cached
    keys = [k for k in tmp_cache.keys() if k.startswith("matmul/tile|")]
    assert keys
    # dispatch resolves the measured winner
    s = tune.get_schedule("matmul/tile", shapes=(a.shape, b.shape),
                          dtypes=(a.dtype, b.dtype))
    assert s == rep.schedule
    # second run is a cache hit
    assert tune.autotune_program(programs.matmul, a, b, stage="tile").cached


def test_custom_program_resolves_declared_default(tmp_cache):
    # a user-defined program has no planner family: get_schedule must
    # fall back to the stage's registered default, not crash — and
    # autotune_program must measure + persist that default
    from repro import axe

    prog = axe.program("test_custom_prog")

    @prog.stage("body", scope=Scope.GRID, entry=True,
                blocks=(("bt", 32),), variants=("kernel",))
    def _body(ctx, x):
        return x * ctx.block("bt")

    x = jnp.ones((4, 4), jnp.float32)
    np.testing.assert_allclose(prog(x), 32 * x)
    s = tune.get_schedule("test_custom_prog/body", shapes=((4, 4),),
                          dtypes=(jnp.float32,))
    assert s == tune.Schedule("test_custom_prog/body", "kernel", (("bt", 32),))
    rep = tune.autotune_program(prog, x, stage="body", iters=1)
    assert rep.schedule == s and rep.measurements


def test_autotune_program_rejects_untunable_stage():
    a = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(ValueError, match="no schedule surface"):
        tune.autotune_program(programs.matmul, a, a, stage="dot")


def test_axespec_keyed_schedules_separate(tmp_cache):
    from repro.axe.spec import AxeSpec, PhysicalSpace

    space = PhysicalSpace.from_mesh_shape({"data": 4, "model": 2})
    sa = AxeSpec.sharded((256, 512), space, {0: ("data",)})
    a = jnp.ones((256, 512), jnp.float32)
    b = jnp.ones((512, 256), jnp.float32)
    programs.matmul(a, b, stage="tile", impl="kernel", arg_specs=(sa, None))
    programs.matmul(a, b, stage="tile", impl="kernel")
    keys = [k for k in tmp_cache.keys() if k.startswith("matmul/tile#kernel|")]
    sigs = {k.split("|")[3] for k in keys}
    assert "dense" in sigs
    assert any(s != "dense" for s in sigs), keys


def test_jit_cache_does_not_retain_operands():
    # the memoized launcher closure must not pin the first call's arrays
    import gc
    import weakref

    a = jax.random.normal(jax.random.PRNGKey(40), (128, 128), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(41), (128, 128), jnp.float32)
    ra, rb = weakref.ref(a), weakref.ref(b)
    out = programs.matmul(a, b, stage="tile", impl="kernel",
                          blocks={"bm": 128, "bn": 128, "bk": 128})
    del a, b, out
    gc.collect()
    assert ra() is None and rb() is None


def test_force_schedule_scoped_invalid_impl_raises(tmp_cache):
    # a pin addressed to this exact op must never silently not apply
    with tune.force_schedule({"matmul/tile": "ring"}):
        with pytest.raises(tune.InvalidImplError):
            tune.get_schedule("matmul/tile", shapes=((256, 256), (256, 256)),
                              dtypes=(jnp.float32, jnp.float32))
    # a *global* spec reaching an op it is invalid for still falls through
    with tune.force_schedule("ring"):
        s = tune.get_schedule("matmul/tile", shapes=((256, 256), (256, 256)),
                              dtypes=(jnp.float32, jnp.float32))
        assert s.op == "matmul/tile"


# ---------------------------------------------------------------------------
# legacy shims: removed after their deprecation window
# ---------------------------------------------------------------------------

def test_kernels_ops_shims_removed_with_migration_pointer():
    """The PR-3 kernels.ops keyword shims are gone; the module points
    every stale import at the corresponding program. The programs
    themselves cover the old keyword surface (pinned blocks)."""
    from repro.kernels import ops as kops

    with pytest.raises(AttributeError, match="repro.kernels.programs.matmul"):
        kops.matmul
    with pytest.raises(AttributeError, match="flash_attention"):
        kops.flash_attention
    with pytest.raises(AttributeError, match="repro.kernels.programs"):
        kops.anything_else

    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    a, b = _rand(k1, (256, 512), jnp.float32), _rand(k2, (512, 256), jnp.float32)
    got = programs.matmul(a, b, stage="tile", impl="kernel",
                          blocks={"bm": 128, "bn": 128, "bk": 256})
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), rtol=2e-4, atol=2e-4)


def test_core_ops_matmul_shim_warns_and_dispatches():
    from repro.core import ops as cops

    k1, k2 = jax.random.split(jax.random.PRNGKey(13))
    a, b = _rand(k1, (256, 256), jnp.float32), _rand(k2, (256, 256), jnp.float32)
    want = ref.matmul_ref(a, b)
    with pytest.warns(DeprecationWarning, match="core.ops.matmul is deprecated"):
        got_mesh = cops.matmul(a, b)
    np.testing.assert_allclose(got_mesh, want, rtol=2e-5, atol=2e-5)
    with scope(Scope.DEVICE):
        with pytest.warns(DeprecationWarning):
            got_dev = cops.matmul(a, b, block_m=128, block_n=128, block_k=128)
    np.testing.assert_allclose(got_dev, want, rtol=2e-5, atol=2e-5)
    # prefer_kernel=False still forces the XLA path
    with scope(Scope.DEVICE):
        with pytest.warns(DeprecationWarning):
            got_xla = cops.matmul(a, b, prefer_kernel=False)
    np.testing.assert_allclose(got_xla, want, rtol=2e-5, atol=2e-5)


def test_core_ops_matmul_shim_keeps_legacy_tiling_fallback():
    # documented legacy behavior: infeasible explicit block_* sizes fall
    # back to the XLA dot instead of failing the trace (the raw program
    # launchers, by contrast, raise — pinned schedules fail loudly)
    from repro.core import ops as cops
    from repro.core.blockspec import TilingError
    from repro.kernels.matmul import matmul_pallas

    a = jax.random.normal(jax.random.PRNGKey(20), (257, 300), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(21), (300, 257), jnp.float32)
    with scope(Scope.DEVICE):
        with pytest.warns(DeprecationWarning):
            got = cops.matmul(a, b, block_m=128, block_n=128, block_k=128)
    np.testing.assert_allclose(got, a @ b, rtol=2e-4, atol=2e-4)
    with pytest.raises(TilingError, match="nearest valid tile"):
        matmul_pallas(a, b, block_m=128, block_n=128, block_k=128, interpret=True)


def test_train_sharding_shims_removed():
    from repro.axe import rules
    from repro.train import sharding as shim

    with pytest.raises(AttributeError, match="repro.axe.rules.dp_axes"):
        shim.dp_axes
    assert rules.dp_axes({"data": 4, "model": 2}) == ("data",)


def test_dtensor_shims_removed_adapter_remains():
    from jax.sharding import PartitionSpec as P

    from repro.axe import lower as axe_lower
    from repro.core import dtensor
    import repro.core as core_pkg

    with pytest.raises(AttributeError, match="repro.axe.lower.layout_of_pspec"):
        dtensor.layout_of_pspec
    with pytest.raises(AttributeError, match="repro.axe.lower.pspec_of_layout"):
        core_pkg.pspec_of_layout
    # DTensorSpec (the collective layer's signature type) remains
    mesh_shape = {"data": 4, "model": 2}
    L = axe_lower.layout_of_pspec((64, 128), ("data", "model"), mesh_shape)
    spec = dtensor.DTensorSpec((64, 128), L, "float32")
    assert spec.pspec(mesh_shape) == P("data", "model")


# ---------------------------------------------------------------------------
# mesh lowering helper
# ---------------------------------------------------------------------------

def test_derive_axis_name_from_spec():
    from repro.axe.spec import AxeSpec, PhysicalSpace

    space = PhysicalSpace.from_mesh_shape({"model": 8})
    sa = AxeSpec.sharded((256, 512), space, {1: ("model",)})
    assert programs.derive_axis_name(sa) == "model"
    with pytest.raises(ValueError, match="needs axis_name"):
        programs.derive_axis_name(None)
    replicated = AxeSpec.replicated((256, 512), space)
    with pytest.raises(ValueError, match="exactly one mesh axis"):
        programs.derive_axis_name(replicated)
