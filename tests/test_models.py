"""Per-architecture smoke tests (reduced configs, CPU): one forward +
train-grad step and one prefill+decode step; asserts shapes + finite."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_variant
from repro.models.model_zoo import ShapeSpec, build_model, shape_applicable

SMOKE_TRAIN = ShapeSpec("smoke_train", "train", 64, 2)
SMOKE_DECODE = ShapeSpec("smoke_decode", "decode", 64, 2)


def _build(arch):
    cfg = smoke_variant(get_config(arch))
    return cfg, build_model(cfg)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg, api = _build(arch)
    key = jax.random.PRNGKey(0)
    params = api.init(key)
    batch = api.make_train_batch(jax.random.PRNGKey(1), SMOKE_TRAIN)
    loss, grads = jax.value_and_grad(api.loss_fn)(params, batch)
    assert jnp.isfinite(loss), (arch, loss)
    leaves = jax.tree.leaves(grads)
    assert leaves and all(jnp.all(jnp.isfinite(l)) for l in leaves), arch
    # loss should be near log(V) at init
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 3.0 * np.log(cfg.vocab_size)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_smoke(arch):
    cfg, api = _build(arch)
    params = api.init(jax.random.PRNGKey(0))
    b, s = SMOKE_DECODE.batch, SMOKE_DECODE.seq
    cache = api.cache_init(b, s)
    batch = api.make_train_batch(jax.random.PRNGKey(1), SMOKE_DECODE)
    prompt = {k: (v[:, : s // 2] if k in ("tokens", "labels") else v) for k, v in batch.items()}
    del prompt["labels"]
    logits, cache = api.prefill(params, prompt, cache)
    assert logits.shape == (b, 1, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits)), arch
    next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    logits2, cache = api.decode_step(params, next_tok, cache, jnp.int32(s // 2))
    assert logits2.shape == (b, 1, cfg.vocab_size)
    assert jnp.all(jnp.isfinite(logits2)), arch


#: the published layer structure of each preset: (super-block period,
#: attention layers per period (by slot), windowed layers per period)
LAYER_STRUCTURE = {
    "dbrx-132b": (1, (0,), ()),
    "qwen3-moe-235b-a22b": (1, (0,), ()),
    "llava-next-mistral-7b": (1, (0,), ()),
    "starcoder2-7b": (1, (0,), ()),
    "gemma3-12b": (6, (0, 1, 2, 3, 4, 5), (0, 1, 2, 3, 4)),  # 5 local : 1 global
    "qwen3-4b": (1, (0,), ()),
    "mistral-nemo-12b": (1, (0,), ()),
    "jamba-1.5-large-398b": (8, (7,), ()),  # 7 SSD + 1 attention
    "whisper-large-v3": (1, (0,), ()),
    "mamba2-2.7b": (1, (), ()),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_layer_structure_matches_published(arch):
    """``ModelConfig.period``/``layer(i)``, which the model, its graphs
    and their binding all read, give each preset's published structure at
    full depth and in its smoke variant."""
    period, attn, windowed = LAYER_STRUCTURE[arch]
    for cfg in (get_config(arch), smoke_variant(get_config(arch))):
        assert cfg.period == period
        assert cfg.superblocks * period == cfg.num_layers
        for i in range(cfg.num_layers):
            layer = cfg.layer(i)
            assert (layer.block, layer.slot) == divmod(i, period)
            assert layer.mixer == ("attn" if layer.slot in attn else "ssm")
            want = cfg.sliding_window if layer.slot in windowed else None
            assert layer.window == want and (want or layer.slot not in windowed), (arch, i)


def test_decode_matches_prefill_dense():
    """Decode-step logits must match a longer prefill's last logits."""
    cfg, api = _build("qwen3-4b")
    params = api.init(jax.random.PRNGKey(0))
    b, s = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab_size, jnp.int32)

    cache = api.cache_init(b, s)
    logits_full, _ = api.prefill(params, {"tokens": toks}, cache)

    cache2 = api.cache_init(b, s)
    _, cache2 = api.prefill(params, {"tokens": toks[:, : s - 1]}, cache2)
    logits_step, _ = api.decode_step(params, toks[:, s - 1 :], cache2, jnp.int32(s - 1))
    np.testing.assert_allclose(
        np.asarray(logits_full[:, 0]), np.asarray(logits_step[:, 0]), rtol=2e-4, atol=2e-4
    )


def test_decode_matches_prefill_ssm():
    cfg, api = _build("mamba2-2.7b")
    params = api.init(jax.random.PRNGKey(0))
    b, s = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(4), (b, s), 0, cfg.vocab_size, jnp.int32)
    cache = api.cache_init(b, s)
    logits_full, _ = api.prefill(params, {"tokens": toks}, cache)
    cache2 = api.cache_init(b, s)
    _, cache2 = api.prefill(params, {"tokens": toks[:, : s - 1]}, cache2)
    logits_step, _ = api.decode_step(params, toks[:, s - 1 :], cache2, jnp.int32(s - 1))
    np.testing.assert_allclose(
        np.asarray(logits_full[:, 0]), np.asarray(logits_step[:, 0]), rtol=5e-3, atol=5e-3
    )


def test_shape_applicability_rules():
    from repro.models.model_zoo import SHAPES

    ok, _ = shape_applicable(get_config("mamba2-2.7b"), SHAPES["long_500k"])
    assert ok
    ok, _ = shape_applicable(get_config("jamba-1.5-large-398b"), SHAPES["long_500k"])
    assert ok
    ok, _ = shape_applicable(get_config("gemma3-12b"), SHAPES["long_500k"])
    assert ok
    ok, why = shape_applicable(get_config("mistral-nemo-12b"), SHAPES["long_500k"])
    assert not ok and "full-attention" in why


def test_param_counts_reasonable():
    """Analytic parameter counts should be in the ballpark of the names."""
    approx = {
        "dbrx-132b": 132e9,
        "qwen3-moe-235b-a22b": 235e9,
        "llava-next-mistral-7b": 7e9,
        "starcoder2-7b": 7e9,
        "gemma3-12b": 12e9,
        "qwen3-4b": 4e9,
        "mistral-nemo-12b": 12e9,
        "mamba2-2.7b": 2.7e9,
    }
    for name, want in approx.items():
        got = get_config(name).param_count()
        assert 0.5 * want < got < 2.1 * want, (name, got, want)
