"""Schedule planner + autotuner subsystem (repro.tune).

Covers: cache round-trip + corruption tolerance, deterministic
candidate enumeration, the force-schedule escape hatch, and the
regression guarantee that tuned dispatch never selects an invalid
tiling (TilingError) — on any shape, including non-tileable ones.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core.blockspec import derive_tiling
from repro.tune import planner
from repro.tune.cache import ScheduleCache
from repro.tune.schedule import Schedule, layout_signature, schedule_key


@pytest.fixture
def tmp_cache(tmp_path):
    """Pin the process-wide cache to a temp file for the test."""
    cache = tune.use_cache(tmp_path / "schedules.json")
    yield cache
    tune.use_cache(None)  # memory-only afterwards; never the user's file


# ---------------------------------------------------------------------------
# Schedule object + cache round-trip
# ---------------------------------------------------------------------------

def test_schedule_describe_parse_roundtrip():
    s = Schedule("matmul", "kernel", (("bm", 256), ("bn", 128), ("bk", 512)))
    assert Schedule.parse(s.describe(), op="matmul") == s
    assert Schedule.parse("xla", op="matmul") == Schedule("matmul", "xla")
    assert Schedule.from_dict(s.to_dict()) == s
    with pytest.raises(ValueError):
        Schedule("matmul", "nonsense")


def test_cache_roundtrip(tmp_path):
    path = tmp_path / "sub" / "schedules.json"
    c1 = ScheduleCache(path)
    key = schedule_key("matmul", ((256, 512), (512, 256)),
                       (jnp.float32, jnp.float32), "dense", "cpu")
    sched = Schedule("matmul", "kernel", (("bm", 128), ("bn", 128), ("bk", 256)))
    c1.put(key, sched, us=123.4, source="measured")
    assert path.exists()

    c2 = ScheduleCache(path)
    hit = c2.get(key)
    assert hit is not None
    assert hit.schedule == sched
    assert hit.us == 123.4
    assert hit.source == "measured"


def test_cache_tolerates_corruption(tmp_path):
    path = tmp_path / "schedules.json"
    path.write_text("{not json")
    c = ScheduleCache(path)
    assert len(c) == 0
    # planned entries stay in memory only
    c.put("k", Schedule("matmul", "xla"), source="planned", persist=False)
    assert json.loads(path.read_text()) if path.read_text().startswith("{\"") else True


def test_cache_versioning(tmp_path):
    path = tmp_path / "schedules.json"
    path.write_text(json.dumps({"version": 999, "entries": {"k": {}}}))
    assert len(ScheduleCache(path)) == 0


# ---------------------------------------------------------------------------
# planner: deterministic, Axe-validated enumeration
# ---------------------------------------------------------------------------

def test_enumeration_deterministic():
    kw = dict(shapes=((2048, 1024), (1024, 1536)),
              dtypes=(jnp.float32, jnp.float32), backend="tpu")
    a = planner.plan("matmul", **kw)
    b = planner.plan("matmul", **kw)
    assert [c.schedule for c in a] == [c.schedule for c in b]
    assert [c.cost_s for c in a] == [c.cost_s for c in b]
    assert len(a) > 1  # xla + at least one kernel tiling
    assert a == sorted(a, key=lambda c: (c.cost_s, c.schedule.describe()))


def test_kernel_candidates_are_axe_valid():
    m, k, n = 2048, 1280, 5440
    for c in planner.plan("matmul", shapes=((m, k), (k, n)),
                          dtypes=(jnp.bfloat16, jnp.bfloat16), backend="tpu",
                          impl="kernel"):
        bm, bn, bk = (c.schedule.block(x) for x in ("bm", "bn", "bk"))
        # must not raise: every candidate passed the direct-sum check
        derive_tiling((m, k), (bm, bk), jnp.bfloat16)
        derive_tiling((k, n), (bk, bn), jnp.bfloat16)
        derive_tiling((m, n), (bm, bn), jnp.bfloat16)


def test_untileable_shape_has_no_kernel_candidates():
    # 300 and 7 admit no MXU-aligned tiling -> only the XLA schedule
    cands = planner.plan("matmul", shapes=((300, 7), (7, 9)),
                         dtypes=(jnp.float32, jnp.float32), backend="tpu")
    assert cands
    assert all(c.schedule.impl == "xla" for c in cands)


def test_tpu_ranking_prefers_large_mxu_tiles():
    best = planner.plan("matmul", shapes=((2048, 1024), (1024, 1536)),
                        dtypes=(jnp.bfloat16, jnp.bfloat16), backend="tpu")[0]
    assert best.schedule.impl == "kernel"
    assert best.schedule.block("bm") == 512
    assert best.schedule.block("bn") == 512


def test_cpu_ranking_prefers_compiled_xla():
    best = planner.plan("matmul", shapes=((2048, 1024), (1024, 1536)),
                        dtypes=(jnp.float32, jnp.float32), backend="cpu")[0]
    assert best.schedule.impl == "xla"


def _plan_bf16(m, k, n, backend="tpu"):
    return planner.plan("matmul/tile", shapes=((m, k), (k, n)),
                        dtypes=(jnp.bfloat16, jnp.bfloat16), backend=backend)


@pytest.mark.parametrize("m,k", [(16, 2560), (64, 4096)])   # qwen3-4b, the MoE cell
def test_tpu_head_reads_whole_k_blocks(m, k):
    """A vocabulary head (151,936 = 128 · 1,187, 1,187 prime) takes
    ``bk`` = K: one grid step per 128-column block, and the XLA dot, at
    its 128³ tiling, ranks below it."""
    cands = _plan_bf16(m, k, 151936)
    best = cands[0]
    assert best.schedule.impl == "kernel"
    assert best.schedule.block("bk") == k
    assert best.terms_dict["grid_steps"] == 1187
    xla = next(c for c in cands if c.schedule.impl == "xla")
    assert xla.terms_dict["grid_steps"] > best.terms_dict["grid_steps"]
    assert xla.cost_s > best.cost_s


def test_tpu_mlp_weight_takes_few_grid_steps():
    best = _plan_bf16(16, 2560, 9728)[0]     # qwen3-4b's gate/up at 16 rows
    assert best.schedule.impl == "kernel"
    assert best.terms_dict["grid_steps"] <= 20


@pytest.mark.parametrize("m,k,n", [
    (16, 2560, 151936), (64, 4096, 151936), (16, 2560, 9728), (16, 9728, 2560),
    (64, 4096, 8192), (512, 2560, 9728), (2048, 1024, 1536),
])
def test_kernel_candidates_fit_vmem_budget(m, k, n):
    from repro.core.blockspec import MATMUL_VMEM_BYTES, matmul_vmem_bytes

    kernels = [c for c in _plan_bf16(m, k, n) if c.schedule.impl == "kernel"]
    assert kernels
    for c in kernels:
        bm, bn, bk = (c.schedule.block(x) for x in ("bm", "bn", "bk"))
        assert matmul_vmem_bytes(bm, bn, bk, jnp.bfloat16) <= MATMUL_VMEM_BYTES
        assert c.terms_dict["grid_steps"] == (m // bm) * (n // bn) * (k // bk)


@pytest.mark.parametrize("m,k,n", [(16, 2560, 151936), (16, 9728, 2560), (64, 4096, 8192)])
def test_grid_step_ranking_deterministic(m, k, n):
    a, b = _plan_bf16(m, k, n), _plan_bf16(m, k, n)
    assert [(c.schedule, c.cost_s, c.terms) for c in a] == \
        [(c.schedule, c.cost_s, c.terms) for c in b]
    assert a == sorted(a, key=lambda c: (c.cost_s, c.schedule.describe()))


@pytest.mark.parametrize("m,k,n", [(16, 2560, 151936), (16, 2560, 9728)])
def test_cpu_decode_weights_still_rank_xla_first(m, k, n):
    """Off the TPU the step count is reported, never charged: the
    interpret penalty keeps the XLA dot first."""
    best = _plan_bf16(m, k, n, backend="cpu")[0]
    assert best.schedule.impl == "xla"
    terms = best.terms_dict
    assert best.cost_s == max(terms["compute"], terms["memory"], terms["collective"])


@pytest.mark.parametrize("x_shape,w_shape,want", [
    ((8, 64, 4096), (8, 4096, 1536), "kernel:bc=64,bd=1024,bf=512"),  # gate/up
    ((8, 64, 1536), (8, 1536, 4096), "kernel:bc=64,bd=512,bf=512"),   # down
])
def test_moe_gemm_plan_unchanged_for_expert_shapes(x_shape, w_shape, want):
    """The grouped expert product keeps its own ranking: the MoE cell's
    expert shapes (8 held experts, 64 slots) plan as before."""
    best = planner.plan("moe_gemm/expert_gemm", shapes=(x_shape, w_shape),
                        dtypes=(jnp.bfloat16,), backend="tpu")[0]
    assert best.schedule.describe() == want


def test_plan_all_ops():
    assert planner.plan("flash_attention", shapes=((1, 2, 256, 64), (1, 2, 256, 64)),
                        dtypes=(jnp.float32,))
    assert planner.plan("moe_gemm", shapes=((8, 256, 512), (8, 512, 256)),
                        dtypes=(jnp.float32,))
    assert planner.plan("mha_blocked", shapes=((1, 512, 8, 64), (1, 512, 8, 64)),
                        dtypes=(jnp.float32,))
    cm = planner.plan("collective_matmul", shapes=((256, 64), (64, 128), (8,)),
                      dtypes=(jnp.float32,))
    assert {c.schedule.impl for c in cm} == {"ring", "psum_scatter"}
    with pytest.raises(ValueError):
        planner.plan("unknown_op", shapes=((1,),), dtypes=(jnp.float32,))


# ---------------------------------------------------------------------------
# get_schedule resolution order + escape hatches
# ---------------------------------------------------------------------------

def test_force_schedule_context(tmp_cache):
    kw = dict(shapes=((256, 512), (512, 256)), dtypes=(jnp.float32, jnp.float32))
    with tune.force_schedule("kernel:bm=128,bn=128,bk=256"):
        s = tune.get_schedule("matmul", **kw)
    assert s == Schedule("matmul", "kernel", (("bm", 128), ("bn", 128), ("bk", 256)))
    # nested None re-enables planning
    with tune.force_schedule("xla"):
        with tune.force_schedule(None):
            s2 = tune.get_schedule("matmul", **kw)
    assert s2 == tune.get_schedule("matmul", **kw)


def test_force_schedule_env(tmp_cache, monkeypatch):
    monkeypatch.setenv(tune.FORCE_ENV, "xla")
    s = tune.get_schedule("matmul", shapes=((2048, 1024), (1024, 1536)),
                          dtypes=(jnp.float32, jnp.float32))
    assert s == Schedule("matmul", "xla")


def test_disable_env_returns_legacy_defaults(tmp_cache, monkeypatch):
    monkeypatch.setenv(tune.DISABLE_ENV, "1")
    s = tune.get_schedule("matmul", shapes=((2048, 1024), (1024, 1536)),
                          dtypes=(jnp.float32, jnp.float32))
    assert s == tune.DEFAULT_SCHEDULES["matmul"]


def test_cached_measurement_wins_over_plan(tmp_cache):
    kw = dict(shapes=((2048, 1024), (1024, 1536)),
              dtypes=(jnp.float32, jnp.float32), backend="cpu")
    pinned = Schedule("matmul", "kernel", (("bm", 256), ("bn", 256), ("bk", 512)))
    key = schedule_key("matmul", kw["shapes"], kw["dtypes"], "dense", "cpu")
    tmp_cache.put(key, pinned, us=1.0, source="measured")
    assert tune.get_schedule("matmul", **kw) == pinned


def test_forced_spec_falls_through_for_inapplicable_op(tmp_cache):
    # "xla" is valid for matmul but not flash_attention: the force must
    # apply to the former and quietly not apply to the latter
    with tune.force_schedule("xla"):
        m = tune.get_schedule("matmul", shapes=((256, 512), (512, 256)),
                              dtypes=(jnp.float32, jnp.float32))
        fa = tune.get_schedule("flash_attention",
                               shapes=((1, 2, 256, 64), (1, 2, 256, 64)),
                               dtypes=(jnp.float32, jnp.float32))
    assert m.impl == "xla"
    assert fa.impl == "kernel"
    with pytest.raises(ValueError):  # malformed specs still raise
        with tune.force_schedule("kernel:bm=abc"):
            tune.get_schedule("matmul", shapes=((256, 512), (512, 256)),
                              dtypes=(jnp.float32, jnp.float32))


def test_measured_entry_reaches_kernel_restricted_query(tmp_cache):
    # the autotuner persists under the unrestricted key; a kernel-only
    # dispatch query must still see it when the impls agree
    shapes = ((256, 512), (512, 256))
    dtypes = (jnp.float32, jnp.float32)
    measured = Schedule("matmul", "kernel", (("bm", 128), ("bn", 128), ("bk", 256)))
    key = schedule_key("matmul", shapes, dtypes, "dense", "cpu")
    tmp_cache.put(key, measured, us=42.0, source="measured")
    s = tune.get_schedule("matmul", shapes=shapes, dtypes=dtypes,
                          backend="cpu", impl="kernel")
    assert s == measured


def test_save_persists_only_measurements(tmp_path):
    c = ScheduleCache(tmp_path / "schedules.json")
    c.put("planned-key", Schedule("matmul", "xla"), source="planned", persist=False)
    c.put("measured-key", Schedule("matmul", "xla"), us=1.0, source="measured")
    raw = json.loads((tmp_path / "schedules.json").read_text())
    assert set(raw["entries"]) == {"measured-key"}
    # but the planned entry is still live in memory
    assert c.get("planned-key") is not None


def test_no_duplicate_candidates_after_clamping():
    fa = planner.plan("flash_attention", shapes=((1, 2, 256, 64), (1, 2, 256, 64)),
                      dtypes=(jnp.float32,))
    descs = [c.schedule.describe() for c in fa]
    assert len(descs) == len(set(descs))
    mb = planner.plan("mha_blocked", shapes=((1, 128, 8, 64), (1, 128, 8, 64)),
                      dtypes=(jnp.float32,))
    descs = [c.schedule.describe() for c in mb]
    assert len(descs) == len(set(descs))


def test_mha_blocked_has_default_and_total_plan(tmp_cache, monkeypatch):
    # disabled-planner path must have a default for every planned op
    monkeypatch.setenv(tune.DISABLE_ENV, "1")
    s = tune.get_schedule("mha_blocked", shapes=((1, 512, 8, 64), (1, 512, 8, 64)),
                          dtypes=(jnp.float32,))
    assert s == tune.DEFAULT_SCHEDULES["mha_blocked"]
    monkeypatch.delenv(tune.DISABLE_ENV)
    # awkward lengths still plan (single-chunk fallback), never KeyError
    cands = planner.plan("mha_blocked", shapes=((1, 1000, 8, 64), (1, 1000, 8, 64)),
                         dtypes=(jnp.float32,))
    assert cands and cands[0].schedule.block("chunk") == 1000
    s2 = tune.get_schedule("mha_blocked", shapes=((1, 1000, 8, 64), (1, 1000, 8, 64)),
                           dtypes=(jnp.float32,))
    assert s2.block("chunk") == 1000


def test_candidate_blocks_largest_aligned_divisor():
    from repro.core.blockspec import candidate_blocks

    assert candidate_blocks(24, minimum=8) == (24,)     # not the fragmented (8,)
    assert candidate_blocks(4, minimum=8) == (4,)       # sub-atom dim: whole dim
    assert candidate_blocks(1024, minimum=128) == (512, 256, 128)
    assert candidate_blocks(13, minimum=8) == ()        # truly untileable


def test_program_resolves_schedule_per_call(tmp_cache):
    # stage schedules resolve outside the cached jit launcher, so a
    # measurement recorded after the first call takes effect on the next
    from repro.kernels import programs

    a = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    first = programs.matmul(a, b, stage="tile", impl="kernel")
    measured = Schedule("matmul/tile", "kernel",
                        (("bm", 128), ("bn", 128), ("bk", 128)))
    key = schedule_key("matmul/tile", (a.shape, b.shape), (a.dtype, b.dtype),
                       "dense", jax.default_backend())
    tmp_cache.put(key, measured, us=1.0, source="measured")
    second = programs.matmul(a, b, stage="tile", impl="kernel")
    np.testing.assert_allclose(first, a @ b, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(second, a @ b, rtol=2e-4, atol=2e-4)
    assert tune.get_schedule("matmul/tile", shapes=(a.shape, b.shape),
                             dtypes=(a.dtype, b.dtype), impl="kernel") == measured


def test_autotune_flash_unmeasurable_returns_planner_pick(tmp_cache):
    # off-TPU, a large flash shape has no measurable candidates: the
    # autotuner returns the planner's pick unmeasured instead of raising
    q = jnp.zeros((1, 8, 1024, 64), jnp.float32)
    rep = tune.autotune_flash_attention(q, q, q)
    assert rep.schedule.impl == "kernel"
    assert rep.us != rep.us  # NaN: not measured
    assert not rep.measurements
    assert not tmp_cache.path.exists()  # nothing persisted


# ---------------------------------------------------------------------------
# regression: tuned dispatch never selects an invalid tiling
# ---------------------------------------------------------------------------

def test_tuned_dispatch_never_raises_tiling_error(tmp_cache):
    from repro.core.scopes import Scope, scope
    from repro.kernels import programs

    key = jax.random.PRNGKey(0)
    # aligned, odd, sub-atom, and prime shapes
    for (m, k, n) in [(256, 512, 256), (300, 70, 9), (128, 384, 640), (17, 13, 29)]:
        a = jax.random.normal(jax.random.fold_in(key, m), (m, k), jnp.float32)
        b = jax.random.normal(jax.random.fold_in(key, n), (k, n), jnp.float32)
        with scope(Scope.DEVICE):
            got = programs.matmul(a, b)  # must not raise TilingError
        np.testing.assert_allclose(
            got, a @ b, rtol=2e-4, atol=2e-4,
        )


def test_autotune_matmul_populates_and_hits_cache(tmp_cache):
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    rep = tune.autotune_matmul(a, b, top_k=2, iters=1)
    assert not rep.cached and rep.measurements
    assert tmp_cache.path.exists()
    rep2 = tune.autotune_matmul(a, b)
    assert rep2.cached
    assert rep2.schedule == rep.schedule
    # dispatch now resolves to the measured winner under the stage key
    s = tune.get_schedule("matmul/tile", shapes=(a.shape, b.shape),
                          dtypes=(a.dtype, b.dtype))
    assert s == rep.schedule


# ---------------------------------------------------------------------------
# cost-model plumbing
# ---------------------------------------------------------------------------

def test_schedule_time_terms():
    from repro.launch.roofline import schedule_time

    t, terms = schedule_time(flops=1e12, mem_bytes=1e9, backend="tpu")
    assert t == max(terms.values())
    assert set(terms) == {"compute", "memory", "collective"}
    t_cpu, _ = schedule_time(flops=1e12, mem_bytes=1e9, backend="cpu")
    assert t_cpu > t  # cpu peaks are far lower


def test_hlo_refined_xla_candidate():
    from repro.launch import hlo_cost

    a = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    c = hlo_cost.analyze_jit(lambda a, b: a @ b, a, a)
    assert c.flops == 2 * 64**3
    cands = planner.plan("matmul", shapes=((64, 64), (64, 64)),
                         dtypes=(jnp.float32, jnp.float32), use_hlo=True)
    assert cands[0].schedule.impl in ("xla", "kernel")


def test_layout_signature():
    from repro.core.layout import It, Layout

    assert layout_signature(None, None) == "dense"
    L1 = Layout((It(2, 8, "m"), It(8, 1, "m")))
    L2 = Layout((It(16, 1, "m"),))  # canonically equal
    assert layout_signature(L1) == layout_signature(L2)
    assert layout_signature(L1) != "dense"
