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
from repro.axe.compile import SUPPORTED_FAMILIES
from repro.axe.spec import AxeSpec, PhysicalSpace
from repro.configs import ARCH_IDS, get_config, smoke_variant
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
# weights bound in place: decode_inputs' views vs plain per-layer slices
# ---------------------------------------------------------------------------

#: every product in the matmul or expert kernel (interpret mode), as on the chip
KERNELS = {"matmul/tile": "kernel", "moe_gemm/expert_gemm": "kernel"}
#: the benchmark cells' architectures at smoke size, heads tied as there:
#: qk-norm + GQA, the SSD mixer, and the expert layer; their matmul
#: weights (2 layers + head)
BOUND_WEIGHTS = {"qwen3-4b": 2 * 7 + 1, "mamba2-2.7b": 2 * 6 + 1,
                 "qwen3-moe-235b-a22b": 2 * (4 + 3) + 1}


def _tied_setup(arch):
    key = (arch, "tied")
    if key not in _SETUP:
        cfg = dataclasses.replace(_cfg(arch), tie_embeddings=True)
        api = build_model(cfg)
        _SETUP[key] = (cfg, api, api.init(jax.random.PRNGKey(0)))
    return _SETUP[key]


def _sliced_decode_inputs(graph, cfg, params, cache):
    """The binding before weight views: every weight a plain slice,
    relayout or transpose of the stored params (``model_inputs``)."""
    return {**axe.decode_inputs(graph, cfg, params, cache),
            **axe.model_inputs(graph, cfg, params)}


@pytest.mark.parametrize("fuse", [False, True])
@pytest.mark.parametrize("arch", sorted(BOUND_WEIGHTS))
def test_decode_step_weight_views_match_plain_slices(arch, fuse):
    """The served step, whose kernels read each weight where it lies in
    the stored params, gives the logits and new cache that the same
    executable gives on plain per-layer slices — step after step, slots
    at different positions — and ``bind_report`` counts no weight copied
    (the plain binding copies every one). With ``fuse`` the products
    carry their fused epilogues into the kernel. Equal up to f32
    rounding: a transposed view contracts B's last dim, which the
    interpreted dot sums in another order."""
    from repro import tune

    cfg, api, params = _tied_setup(arch)
    _, cache, tok = _prefill(api, cfg)
    eng = ServeEngine(api=api, batch_size=B, max_seq=MAX_SEQ,
                      force_schedule=KERNELS, fuse=fuse)
    eng.load(params)
    exe = eng.compiled_decode()

    @jax.jit
    def sliced_step(params, cache, tok, pos):
        outs = exe.apply(_sliced_decode_inputs(exe.graph, cfg, params, cache),
                         tok, pos)
        logits = dict(zip(exe.graph.outputs(), outs))["logits"]
        return logits, axe.decode_cache(exe.graph, cfg, outs, cache)

    c_view, c_sliced = jax.tree.map(jnp.copy, cache), cache
    for t in range(3):
        pos = jnp.asarray([S0 + t, S0 + 2 * t + 3], jnp.int32)
        got, c_view = eng.decode_step(tok, c_view, pos)
        with tune.force_schedule(KERNELS):
            want, c_sliced = sliced_step(params, c_sliced, tok, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert _cache_maxdiff(c_view, c_sliced) < 1e-5
        tok = jnp.argmax(got, axis=-1).astype(jnp.int32)

    n = BOUND_WEIGHTS[arch]
    rep = eng.bind_report
    assert (rep.in_place, rep.copied, rep.copied_bytes) == (n, 0, 0)
    sliced = axe.bind_report(exe.graph, params,
                             _sliced_decode_inputs(exe.graph, cfg, params, cache))
    assert (sliced.in_place, sliced.copied) == (0, n)
    assert sliced.copied_bytes == rep.in_place_bytes > 0
    # the products ran in the kernel, a view's layer its one SMEM scalar
    jaxpr = str(jax.make_jaxpr(eng.decode_fn())(params, cache, tok, pos))
    assert "matmul_tile" in jaxpr and "Ref<smem>{i32[1]}" in jaxpr


def _plain_model_inputs(graph, cfg, params):
    """``model_inputs`` for a dense config, as it read before decode-step
    views existed: per-layer slices, q/k/v/o relaid out to 2-D, the tied
    head transposed."""
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "lm_head": params["embed"].T}
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["blocks"]["l0"])
        ap, mp, p = lp["attn"], lp["mlp"], f"L{i}."
        out.update({
            p + "norm1": lp["norm1"],
            p + "wq": ap["wq"].reshape(d, h * hd),
            p + "wk": ap["wk"].reshape(d, kv * hd),
            p + "wv": ap["wv"].reshape(d, kv * hd),
            p + "wo": ap["wo"].reshape(h * hd, d),
            p + "q_norm": ap["q_norm"], p + "k_norm": ap["k_norm"],
            p + "norm2": lp["norm2"],
            p + "wg": mp["wg"], p + "wu": mp["wu"], p + "wo2": mp["wo"],
        })
    return out


def test_compiled_forward_binding_unchanged():
    """``model_inputs`` (score(), the compiled forward, training) keeps
    plain arrays: with every product in the kernel, its lowered program
    is the one the plain per-layer binding gives, op for op."""
    from repro import tune

    cfg, _, params = _tied_setup("qwen3-4b")
    exe = axe.model_executable(cfg, None, B, 8)
    tokens = jnp.zeros((B * 8,), jnp.int32)

    def lowered(bind):
        def forward(p, t):
            return exe.apply(bind(exe.graph, cfg, p), t)

        with tune.force_schedule(KERNELS):
            jaxpr = str(jax.make_jaxpr(forward)(params, tokens))
            return jaxpr, jax.jit(forward).lower(params, tokens).as_text()

    jaxpr, text = lowered(axe.model_inputs)
    assert "matmul_tile" in jaxpr and "Ref<smem>{i32[1]}" not in jaxpr
    assert text == lowered(_plain_model_inputs)[1]


# ---------------------------------------------------------------------------
# one owner for the stored format: the graph's records vs the pytrees
# ---------------------------------------------------------------------------

#: every preset with a compiled binding, at smoke size
BOUND_ARCHS = tuple(a for a in ARCH_IDS
                    if get_config(a).family in SUPPORTED_FAMILIES)


def _stored_trees(cfg):
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    cache = api.cache_init(B, MAX_SEQ)
    # distinct values in every cache entry, so a swapped layer shows
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    cache = jax.tree.unflatten(tree, [
        jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
        for k, x in zip(keys, leaves)])
    return params, cache


def _decode_graph(cfg):
    return axe_graphs.decode_graph(cfg, B, MAX_SEQ, PhysicalSpace(()),
                                   dtype=cfg.dtype)


@pytest.mark.parametrize("arch", BOUND_ARCHS)
def test_graph_layers_match_stored_superblocks(arch):
    """Each graph layer's mixer and window are those of the stored
    super-block slot its records point at, and the layers cover every
    (super-block, slot) entry of the stacked pytrees once."""
    cfg = _cfg(arch)
    params, cache = _stored_trees(cfg)
    dec = _decode_graph(cfg)
    fwd = axe_graphs.model_graph(cfg, B, 8, PhysicalSpace(()), dtype=cfg.dtype,
                                 layers=cfg.num_layers)
    nodes = {n.name: n for n in fwd.nodes + dec.nodes}
    seen = set()
    for i in range(cfg.num_layers):
        rec = dec.aux[f"L{i}.norm1"]
        assert rec == fwd.aux[f"L{i}.norm1"]
        _, slot, _ = rec.path
        seen.add((rec.block, slot))
        stored = params["blocks"][slot]
        mixer = "attn" if f"L{i}.decode_attention" in nodes else "ssm"
        assert {"attn", "ssm"} & set(stored) == {mixer}
        if mixer == "attn":
            length = cache[slot]["k"].shape[2]
            window = length if length < MAX_SEQ else None
            assert nodes[f"L{i}.attention"].attr("window") == window
            assert nodes[f"L{i}.decode_attention"].attr("ring") == (window is not None)
    n_super = jax.tree.leaves(params["blocks"])[0].shape[0]
    assert seen == {(b, s) for b in range(n_super) for s in params["blocks"]}


@pytest.mark.parametrize("arch", BOUND_ARCHS)
def test_graph_records_resolve_to_stored_leaves(arch):
    """Every param/cache input (and auxiliary tensor) of the decode and
    forward graphs names a leaf of ``init``/``cache_init``, and its view
    of that leaf, plain or in place, has the input's shape and dtype."""
    cfg = dataclasses.replace(_cfg(arch), dtype="bfloat16")
    api = build_model(cfg)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: api.cache_init(B, MAX_SEQ))
    trees = {"params": params, "cache": cache}
    for g in (_decode_graph(cfg),
              axe_graphs.model_graph(cfg, B, 8, PhysicalSpace(()), dtype=cfg.dtype,
                                     layers=cfg.num_layers)):
        stored = [m for m in g.inputs.values() if m.role != "activation"]
        assert all(m.stored.tree == ("params" if m.role == "param" else "cache")
                   for m in stored)
        for r in [m.stored for m in stored] + list(g.aux.values()):
            leaf = trees[r.tree]
            for k in r.path:
                leaf = leaf[k]
            assert r.block is None or 0 <= r.block < leaf.shape[0], r
        # plain arrays (model_inputs), and the in-place views decode_inputs binds
        plain = jax.eval_shape(lambda p: axe.model_inputs(g, cfg, p), params)
        binds = [(plain, [m for m in stored if m.role == "param"])]
        if g.cache_out:
            binds.append((jax.eval_shape(lambda p, c: axe.decode_inputs(g, cfg, p, c),
                                         params, cache), stored))
        for bound, metas in binds:
            for m in metas:
                assert (bound[m.name].shape, str(bound[m.name].dtype)) == (m.shape, m.dtype), m
            assert set(g.aux) <= set(bound)


@pytest.mark.parametrize("arch", BOUND_ARCHS)
def test_decode_cache_inverts_decode_inputs(arch):
    """``decode_cache`` puts each bound cache-in tensor back where it was
    read from: the cache comes back unchanged, leaf for leaf."""
    cfg = _cfg(arch)
    params, cache = _stored_trees(cfg)
    g = _decode_graph(cfg)
    bound = axe.decode_inputs(g, cfg, params, cache)
    assert set(g.cache_out.values()) == {n for n, m in g.inputs.items() if m.role == "cache"}
    outs = [bound[g.cache_out[o]] if o in g.cache_out else None for o in g.outputs()]
    back = axe.decode_cache(g, cfg, outs, cache)
    assert jax.tree.structure(back) == jax.tree.structure(cache)
    for got, want in zip(jax.tree.leaves(back), jax.tree.leaves(cache)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# ServeEngine.generate: decode runs through the compiled step
# ---------------------------------------------------------------------------

_ENGINES = {}


def _engine(arch):
    if arch not in _ENGINES:
        cfg, api, params = _setup(arch)
        eng = ServeEngine(api=api, batch_size=B, max_seq=MAX_SEQ)
        eng.load(params)
        _ENGINES[arch] = eng
    return _ENGINES[arch]


def _prompts(cfg, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (B, S0), 0, cfg.vocab_size, jnp.int32
    )


def _reference_generate(api, params, prompts, n):
    """Greedy decoding through the model's own ``api.prefill`` /
    ``api.decode_step``: the reference the compiled step answers to."""
    logits, cache = api.prefill(params, {"tokens": prompts},
                                api.cache_init(prompts.shape[0], MAX_SEQ))
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    outs, pos = [tok], prompts.shape[1]
    for _ in range(n - 1):
        logits, cache = api.decode_step(params, tok[:, None], cache, jnp.int32(pos))
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        outs.append(tok)
        pos += 1
    return np.stack([np.asarray(t) for t in outs], axis=1)


@pytest.mark.parametrize("arch", ARCHS)
def test_generate_compiled_matches_legacy(arch):
    """Full short generate, token-for-token: the engine's compiled decode
    path vs greedy decoding through the model's own prefill/decode_step."""
    cfg, api, params = _setup(arch)
    prompts = _prompts(cfg)
    out_c = _engine(arch).generate(prompts, 6)
    assert out_c.shape == (B, 6)
    np.testing.assert_array_equal(out_c, _reference_generate(api, params, prompts, 6))


def test_generate_temperature_zero_is_greedy():
    """``temperature=0`` (explicit arg) reproduces the engine-default
    greedy run exactly; ``top_k=1`` does too at any temperature."""
    cfg, _, _ = _setup("qwen3-4b")
    prompts = _prompts(cfg)
    eng = _engine("qwen3-4b")
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
    eng = _engine("qwen3-4b")
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
