"""SSD chunked-scan vs token recurrence oracle; MoE dropless dispatch vs
dense routing reference."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod


@pytest.mark.parametrize("s,chunk", [(32, 8), (64, 16), (48, 16), (16, 16)])
def test_ssd_scan_matches_recurrence(s, chunk):
    b, h, p, n = 2, 3, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, s, n))
    Cm = jax.random.normal(ks[4], (b, s, n))
    y, final = ssm_mod.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, final_ref = ssm_mod.ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref.swapaxes(1, 1)), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(final_ref), rtol=1e-4, atol=1e-4)


def test_ssd_scan_chunk_invariance():
    b, s, h, p, n = 1, 64, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, s, n))
    Cm = jax.random.normal(ks[4], (b, s, n))
    y8, _ = ssm_mod.ssd_scan(x, dt, A, Bm, Cm, chunk=8)
    y32, _ = ssm_mod.ssd_scan(x, dt, A, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y32), rtol=1e-4, atol=1e-4)


def test_ssd_decode_continues_scan():
    cfg = smoke_variant(get_config("mamba2-2.7b"))
    p = ssm_mod.ssd_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model), jnp.float32)
    # full pass on 9 tokens
    y_full = ssm_mod.ssd_apply(p, x, cfg, chunk=4)
    # prefill 8 then decode 1 (replicating transformer.prefill's state path)
    xp, z, Bm, Cm, dt = ssm_mod._inputs(p, x[:, :8], cfg)
    A = -jnp.exp(p["A_log"])
    _, state = ssm_mod.ssd_scan(xp, dt, A, Bm, Cm, chunk=4)
    u = jnp.concatenate([x[:, :8] @ p["wx"], x[:, :8] @ p["wB"], x[:, :8] @ p["wC"]], axis=-1)
    st = {"ssm": state, "conv": u[:, -(ssm_mod.CONV_K - 1):]}
    y_step, _ = ssm_mod.ssd_decode(p, x[:, 8:9], cfg, st)
    np.testing.assert_allclose(
        np.asarray(y_step[:, 0]), np.asarray(y_full[:, 8]), rtol=2e-4, atol=2e-4
    )


def test_moe_no_drop_matches_dense_routing():
    """The dropless dispatch must equal the dense gather-everything
    reference."""
    cfg = smoke_variant(get_config("dbrx-132b"))
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
    y = moe_mod.moe_apply(p, x, cfg)

    # dense reference: every expert on every token, combined by gates
    t = 2 * 16
    xf = x.reshape(t, cfg.d_model)
    logits = xf @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.experts_per_tok)
    gate = gate / gate.sum(-1, keepdims=True)
    hg = jnp.einsum("td,edf->tef", xf, p["wg"])
    hu = jnp.einsum("td,edf->tef", xf, p["wu"])
    all_out = jnp.einsum("tef,efd->ted", jax.nn.silu(hg) * hu, p["wo"])
    sel = jnp.take_along_axis(all_out, idx[:, :, None], axis=1)
    want = (sel * gate[:, :, None]).sum(1).reshape(2, 16, cfg.d_model)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_moe_aux_losses_finite():
    cfg = smoke_variant(get_config("qwen3-moe-235b-a22b"))
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model), jnp.float32)
    y, aux = moe_mod.moe_apply(p, x, cfg, return_aux=True)
    assert jnp.isfinite(aux["aux_loss"]) and jnp.isfinite(aux["z_loss"])


@pytest.mark.parametrize("held", [1, 2])
def test_moe_shares_add_up_to_the_whole_layer(held):
    """Each device of an expert-parallel deployment holds ``held`` of the
    experts and routes over all of them: the parts its shares compute
    add up to the layer that holds every expert."""
    cfg = smoke_variant(get_config("qwen3-moe-235b-a22b"))
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
    parts = 0.0
    for rank in range(cfg.num_experts // held):
        share = dataclasses.replace(cfg, experts_held=held, expert_rank=rank)
        lo = share.expert_offset
        p_r = {k: v if k == "router" else v[lo:lo + held] for k, v in p.items()}
        parts = parts + moe_mod.moe_apply(p_r, x, share)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(moe_mod.moe_apply(p, x, cfg)),
                               rtol=1e-5, atol=1e-5)


def test_moe_one_expert_taking_every_token_drops_none():
    """A router that sends all 64 tokens to expert 1: all of them reach it
    (a capacity of t·k·1.25/E would keep 40), and the layer equals the
    dense gather-everything sum of each token's experts."""
    cfg = smoke_variant(get_config("qwen3-moe-235b-a22b"))
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    p["router"] = p["router"].at[:, 1].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))) + 0.5
    xf = x.reshape(64, cfg.d_model)
    r = moe_mod.route(xf, p["router"], experts_per_tok=cfg.experts_per_tok,
                      held=cfg.num_experts)
    assert bool(r.routed[:, 1].all()) and int(moe_mod.load(r.routed)[0]) == 64 * 2
    with jax.default_matmul_precision("highest"):
        y = moe_mod.moe_apply(p, x, cfg)[0]
        h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, p["wg"])) \
            * jnp.einsum("td,edf->tef", xf, p["wu"])
        every = jnp.einsum("tef,efd->ted", h, p["wo"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.einsum("te,ted->td", r.gates, every)),
                               rtol=1e-4, atol=1e-5)
