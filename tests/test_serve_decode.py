"""Compiled decode-step parity: the decode-graph executable
(``axe.decode_executable`` — KV/SSM caches as first-class graph
tensors, docs/serving.md) vs the legacy cache-carrying model API
(``api.decode_step``), across all four model families, f32 tight +
bf16 loose, 1 and 8 host devices, mid-sequence cache positions, and
full short ``ServeEngine.generate`` runs token-for-token; plus the
sampling args (temperature / top-k) and the cache-placement plan flow
(``rules.cache_specs(plan=...)`` / ``CachePlanFallbackWarning``)."""
import dataclasses
import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import axe
from repro.axe import graphs as axe_graphs
from repro.axe import rules as axe_rules
from repro.axe.spec import AxeSpec, PhysicalSpace
from repro.configs import get_config, smoke_variant
from repro.models import ssm as ssm_mod
from repro.models.model_zoo import build_model
from repro.serve import ServeEngine

ARCHS = (
    "qwen3-4b",                # dense
    "qwen3-moe-235b-a22b",     # MoE
    "mamba2-2.7b",             # SSM
    "jamba-1.5-large-398b",    # hybrid
)

B, MAX_SEQ, S0 = 2, 32, 5


def _cfg(arch, dtype="float32"):
    cfg = smoke_variant(get_config(arch))
    if cfg.is_moe:
        # drop-free capacity: local and global routing agree exactly
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    return dataclasses.replace(cfg, dtype=dtype)


# model + params + compiled decode executables, shared across tests in
# this module (the executables are the expensive part)
_SETUP = {}
_EXE = {}


def _setup(arch, dtype="float32"):
    key = (arch, dtype)
    if key not in _SETUP:
        cfg = _cfg(arch, dtype)
        api = build_model(cfg)
        _SETUP[key] = (cfg, api, api.init(jax.random.PRNGKey(0)))
    return _SETUP[key]


def _decode_exe(cfg, arch, dtype, b=B, max_seq=MAX_SEQ):
    key = (arch, dtype, b, max_seq)
    if key not in _EXE:
        _EXE[key] = axe.decode_executable(cfg, None, b, max_seq, dtype=dtype)
    return _EXE[key]


def _prefill(api, cfg, b=B, s0=S0, seed=1):
    params = _setup_params(api)
    cache = api.cache_init(b, MAX_SEQ)
    prompts = jax.random.randint(
        jax.random.PRNGKey(seed), (b, s0), 0, cfg.vocab_size, jnp.int32
    )
    logits, cache = api.prefill(params, {"tokens": prompts}, cache)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    return prompts, cache, tok


def _setup_params(api):
    for _, a, params in _SETUP.values():
        if a is api:
            return params
    raise AssertionError("params for api not found")


def _compiled_step(cfg, exe, params, cache, tok, pos):
    """One step through the compiled decode executable, returning
    (logits [B, V], legacy-layout new cache)."""
    outs = exe(axe.decode_inputs(exe.graph, cfg, params, cache), tok, pos)
    logits = dict(zip(exe.graph.outputs(), outs))["logits"]
    return logits, axe.decode_cache(exe.graph, cfg, outs, cache)


def _cache_maxdiff(a, b):
    d = 0.0
    for slot in a:
        for leaf in a[slot]:
            d = max(d, float(np.max(np.abs(
                np.asarray(a[slot][leaf], np.float32)
                - np.asarray(b[slot][leaf], np.float32)
            ))))
    return d


# ---------------------------------------------------------------------------
# decode-step parity vs api.decode_step (single device)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_parity_f32(arch):
    cfg, api, params = _setup(arch)
    _, cache, tok = _prefill(api, cfg)
    ref_logits, ref_cache = api.decode_step(
        params, tok[:, None], cache, jnp.int32(S0)
    )
    exe = _decode_exe(cfg, arch, "float32")
    got_logits, got_cache = _compiled_step(
        cfg, exe, params, cache, tok, jnp.full((B,), S0, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(ref_logits[:, 0]),
        rtol=2e-4, atol=2e-4,
    )
    assert _cache_maxdiff(ref_cache, got_cache) < 2e-4


def test_decode_step_parity_bf16():
    arch = "qwen3-4b"
    cfg, api, params = _setup(arch, "bfloat16")
    _, cache, tok = _prefill(api, cfg)
    ref_logits, ref_cache = api.decode_step(
        params, tok[:, None], cache, jnp.int32(S0)
    )
    exe = _decode_exe(cfg, arch, "bfloat16")
    got_logits, got_cache = _compiled_step(
        cfg, exe, params, cache, tok, jnp.full((B,), S0, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(got_logits, np.float32),
        np.asarray(ref_logits[:, 0], np.float32),
        rtol=0.1, atol=0.25,
    )
    assert _cache_maxdiff(ref_cache, got_cache) < 0.25


@pytest.mark.parametrize("arch", ("qwen3-4b", "jamba-1.5-large-398b"))
def test_decode_step_parity_mid_sequence(arch):
    """Parity holds at a cache position deep inside the sequence — the
    legacy path advances the cache several steps first, then one
    compiled step must agree (ring-buffer writes, SSM state carry)."""
    cfg, api, params = _setup(arch)
    _, cache, tok = _prefill(api, cfg)
    pos = S0
    for _ in range(4):
        logits, cache = api.decode_step(params, tok[:, None], cache,
                                        jnp.int32(pos))
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        pos += 1
    ref_logits, ref_cache = api.decode_step(
        params, tok[:, None], cache, jnp.int32(pos)
    )
    exe = _decode_exe(cfg, arch, "float32")
    got_logits, got_cache = _compiled_step(
        cfg, exe, params, cache, tok, jnp.full((B,), pos, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(ref_logits[:, 0]),
        rtol=2e-4, atol=2e-4,
    )
    assert _cache_maxdiff(ref_cache, got_cache) < 2e-4


def test_decode_step_per_slot_positions():
    """The decode graph's ``pos`` activation is per-slot: two requests
    at different depths in one batch each match their own batch-1
    legacy step."""
    arch = "qwen3-4b"
    cfg, api, params = _setup(arch)
    prompts, cache, tok = _prefill(api, cfg)
    # advance slot 0 only, through batch-1 legacy decode
    c0 = jax.tree.map(lambda x: x[:, :1], cache)
    t0, p0 = tok[:1], S0
    for _ in range(3):
        lg, c0 = api.decode_step(params, t0[:, None], c0, jnp.int32(p0))
        t0 = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
        p0 += 1
    merged = jax.tree.map(
        lambda big, new: jax.lax.dynamic_update_slice_in_dim(
            big, new.astype(big.dtype), 0, axis=1
        ),
        cache, c0,
    )
    toks = jnp.stack([t0[0], tok[1]])
    pos = jnp.asarray([p0, S0], jnp.int32)
    exe = _decode_exe(cfg, arch, "float32")
    got_logits, _ = _compiled_step(cfg, exe, params, merged, toks, pos)
    # each slot vs its own batch-1 legacy step
    ref0, _ = api.decode_step(params, t0[:, None], c0, jnp.int32(p0))
    c1 = jax.tree.map(lambda x: x[:, 1:], cache)
    ref1, _ = api.decode_step(params, tok[1:, None], c1, jnp.int32(S0))
    np.testing.assert_allclose(np.asarray(got_logits[0]),
                               np.asarray(ref0[0, 0]), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_logits[1]),
                               np.asarray(ref1[0, 0]), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ServeEngine.generate: compiled decode is the default path
# ---------------------------------------------------------------------------

_ENGINES = {}


def _engine(arch, mode="compiled"):
    key = (arch, mode)
    if key not in _ENGINES:
        cfg, api, params = _setup(arch)
        eng = ServeEngine(api=api, batch_size=B, max_seq=MAX_SEQ,
                          decode_mode=mode)
        eng.load(params)
        _ENGINES[key] = eng
    return _ENGINES[key]


def _prompts(cfg, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (B, S0), 0, cfg.vocab_size, jnp.int32
    )


@pytest.mark.parametrize("arch", ARCHS)
def test_generate_compiled_matches_legacy(arch):
    """Full short generate, token-for-token: the compiled decode path
    (the default) vs ``decode_mode="legacy"`` greedy."""
    cfg, _, _ = _setup(arch)
    prompts = _prompts(cfg)
    out_c = _engine(arch, "compiled").generate(prompts, 6)
    out_l = _engine(arch, "legacy").generate(prompts, 6)
    assert out_c.shape == (B, 6)
    np.testing.assert_array_equal(out_c, out_l)


def test_generate_default_mode_is_compiled():
    eng = _engine("qwen3-4b", "compiled")
    assert eng.decode_mode == "compiled"
    assert ServeEngine.__dataclass_fields__["decode_mode"].default == "compiled"


def test_generate_temperature_zero_is_greedy():
    """``temperature=0`` (explicit arg) reproduces the engine-default
    greedy run exactly; ``top_k=1`` does too at any temperature."""
    cfg, _, _ = _setup("qwen3-4b")
    prompts = _prompts(cfg)
    eng = _engine("qwen3-4b", "compiled")
    greedy = eng.generate(prompts, 6)
    np.testing.assert_array_equal(greedy,
                                  eng.generate(prompts, 6, temperature=0.0))
    np.testing.assert_array_equal(
        greedy, eng.generate(prompts, 6, temperature=1.0, top_k=1)
    )


def test_generate_top_k_restricts_support():
    """Sampled ids at temperature>0 with top_k=k always come from the
    top-k of the greedy path's logits support — checked at the
    _sample level for a fixed logits row."""
    eng = _engine("qwen3-4b", "compiled")
    logits = jnp.asarray([[0.0, 3.0, 1.0, 2.0, -1.0]] * 4)
    allowed = {1, 3}  # top-2 ids
    for seed in range(5):
        toks = eng._sample(logits, jax.random.PRNGKey(seed),
                           temperature=1.0, top_k=2)
        assert set(np.asarray(toks).tolist()) <= allowed
    # k=1 is argmax regardless of temperature
    toks = eng._sample(logits, jax.random.PRNGKey(0),
                       temperature=5.0, top_k=1)
    assert np.asarray(toks).tolist() == [1, 1, 1, 1]


# ---------------------------------------------------------------------------
# cache placement flows from the solved plan (rules.cache_specs)
# ---------------------------------------------------------------------------


def test_cache_specs_follow_solved_plan():
    """A plan that carries decode-graph cache tensors places the legacy
    cache leaves with the solved layout (leading stacked-layer dim
    replicated); leaves the plan misses warn
    ``CachePlanFallbackWarning`` and fall back to the tables."""
    cfg, api, _ = _setup("qwen3-4b")
    space = PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})
    cache = api.cache_init(B, MAX_SEQ)
    k_leaf = next(iter(cache.values()))["k"]
    graph_shape = tuple(k_leaf.shape[1:])  # drop the stacked-layer dim
    plan = {
        "L0.k_cache": AxeSpec.sharded(graph_shape, space, {0: ("data",)},
                                      "float32"),
    }
    axe_rules._DIV_WARNED.clear()  # the fallback warning dedupes per leaf
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        specs = axe_rules.cache_specs(cache, space, plan=plan)
    fallbacks = [w for w in caught
                 if issubclass(w.category, axe_rules.CachePlanFallbackWarning)]
    # v (and any other) leaves are not covered -> structured fallback
    assert fallbacks and all(w.message.name in ("v_cache",)
                             for w in fallbacks)
    for slot in specs:
        k_spec = specs[slot]["k"]
        assert k_spec.placement()[0] == ()          # stacked dim replicated
        assert k_spec.placement()[1] == ("data",)   # solved batch sharding


def test_plan_cache_env_skips_forward_plans():
    """A forward-pass plan has no cache tensors; the engine must not
    re-solve on its account (``compiled_decode`` drops it silently)."""
    space = PhysicalSpace.from_mesh_shape({"data": 2, "model": 4})
    fwd_plan = {"tokens": AxeSpec.replicated((8,), space, "int32"),
                "L0.x": AxeSpec.replicated((8, 16), space, "float32")}
    assert axe_rules._plan_cache_env(fwd_plan) == {}
    got = axe_rules._plan_cache_env(
        {"L0.k_cache": AxeSpec.replicated((2, 32, 2, 8), space, "float32")}
    )
    assert set(got) == {"k_cache"}


def test_decode_graph_cache_shapes_match_legacy_cache():
    """The decode graph's cache inputs agree with the legacy
    ``cache_init`` allocation: CONV_K parity with models.ssm and the
    per-layer ring-buffer window from ``cache_window``."""
    assert axe_graphs.CONV_K == ssm_mod.CONV_K
    cfg = _cfg("jamba-1.5-large-398b")
    space = PhysicalSpace.from_mesh_shape({"data": 1, "model": 1})
    gs = axe_graphs.decode_graph(cfg, B, MAX_SEQ, space, dtype="float32")
    for i in range(cfg.num_layers):
        meta = gs.inputs.get(f"L{i}.k_cache")
        if meta is None:
            continue  # SSM layer
        assert meta.shape[1] == axe_graphs.cache_window(cfg, i, MAX_SEQ)


# ---------------------------------------------------------------------------
# 8 host devices (subprocess, like test_compile's distributed leg)
# ---------------------------------------------------------------------------

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np
from repro import compat
from repro.configs import get_config, smoke_variant
from repro.models.model_zoo import build_model
from repro.axe.compile import decode_cache, decode_executable, decode_inputs

out = {}
mesh = compat.make_mesh((2, 4), ("data", "model"))
for arch in ("qwen3-4b", "qwen3-moe-235b-a22b", "mamba2-2.7b",
             "jamba-1.5-large-398b"):
    cfg = dataclasses.replace(smoke_variant(get_config(arch)),
                              dtype="float32")
    if cfg.is_moe:
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, max_seq, s0 = 4, 32, 5
    cache = api.cache_init(b, max_seq)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (b, s0), 0,
                                 cfg.vocab_size, jnp.int32)
    logits0, cache = api.prefill(params, {"tokens": prompts}, cache)
    tok = jnp.argmax(logits0[:, -1], axis=-1).astype(jnp.int32)
    ref_logits, ref_cache = api.decode_step(params, tok[:, None], cache,
                                            jnp.int32(s0))
    exe = decode_executable(cfg, mesh, b, max_seq, dtype="float32")
    outs = exe(decode_inputs(exe.graph, cfg, params, cache), tok,
               jnp.full((b,), s0, jnp.int32))
    got_logits = dict(zip(exe.graph.outputs(), outs))["logits"]
    got_cache = decode_cache(exe.graph, cfg, outs, cache)
    cd = 0.0
    for slot in ref_cache:
        for leaf in ref_cache[slot]:
            cd = max(cd, float(np.max(np.abs(
                np.asarray(ref_cache[slot][leaf], np.float32)
                - np.asarray(got_cache[slot][leaf], np.float32)))))
    out[arch] = {
        "logits_maxdiff": float(np.max(np.abs(
            np.asarray(got_logits) - np.asarray(ref_logits[:, 0])))),
        "cache_maxdiff": cd,
    }
print("RESULT " + json.dumps(out))
"""


def test_decode_parity_8_devices():
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
    assert set(out) == set(ARCHS)
    for arch, rec in out.items():
        assert rec["logits_maxdiff"] < 2e-4, (arch, rec)
        assert rec["cache_maxdiff"] < 2e-4, (arch, rec)


_ENGINE_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
from repro import compat
from repro.configs import get_config, smoke_variant
from repro.models.model_zoo import build_model
from repro.serve.engine import ServeEngine

api = build_model(smoke_variant(get_config("qwen3-4b")))
eng = ServeEngine(api, batch_size=4, max_seq=32,
                  mesh=compat.make_mesh((1, 4), ("data", "model")))
eng.load(api.init(jax.random.PRNGKey(0)))
eng.generate(jnp.ones((4, 5), jnp.int32), 4)
compiles = eng.decode_fn()._cache_size()
placed = eng._place_cache(api.cache_init(4, 32))
want = [s.sharding for s in jax.tree.leaves(placed)]
_, cache = eng.decode_step(jnp.zeros(4, jnp.int32), placed,
                           jnp.full((4,), 5, jnp.int32))
got = [s.sharding for s in jax.tree.leaves(cache)]
print("RESULT " + json.dumps({
    "compiles": compiles,
    "same_placement": all(g.is_equivalent_to(w, 5) for g, w in zip(got, want)),
}))
"""


def test_sharded_engine_decode_keeps_cache_placement():
    """On a mesh the serving step returns its cache with the placed
    cache's shardings: every step of ``generate`` reuses one executable
    (a replicated new cache would recompile the second step and hold
    the whole cache on every device)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    proc = subprocess.run(
        [sys.executable, "-c", _ENGINE_CHILD], env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out == {"compiles": 1, "same_placement": True}, out
