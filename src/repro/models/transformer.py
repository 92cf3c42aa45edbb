"""Decoder-only LM assembly for all assigned families.

Layer stacks are ``jax.lax.scan`` over stacked parameters → O(1) HLO
size regardless of depth (compile-time critical for the 512-device
dry-run). Families with periodic structure scan over *super-blocks*:

* gemma3  (local_global_ratio=5): super-block = 5 local + 1 global
* jamba   (attn_period=8):        super-block = 7 SSD + 1 attention
  (every layer's FFN is MoE per the assigned config)
* mamba2  (ssm):                  block = norm + SSD (no FFN)
* dense / moe / vlm:              uniform layers

(``ModelConfig.period`` / ``ModelConfig.layer`` hold the rule.)

This preserves exact per-layer cost accounting in ``cost_analysis`` —
a lax.cond-based mixed stack would double-count both branches.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.train.act_sharding import constrain
from repro.models.common import (
    Params,
    dense_init,
    dtype_of,
    embed_init,
    mlp_apply,
    mlp_init,
    rmsnorm,
)


# ---------------------------------------------------------------------------
# per-layer init/apply
# ---------------------------------------------------------------------------


def _layer_init(key, cfg, dtype, *, kind: str) -> Params:
    """kind: 'attn' | 'ssm' — the token mixer; FFN chosen by cfg."""
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {"norm1": jnp.ones((cfg.d_model,), dtype)}
    if kind == "attn":
        p["attn"] = attn.attn_init(k1, cfg, dtype)
    else:
        p["ssm"] = ssm_mod.ssd_init(k1, cfg, dtype)
    if cfg.family == "ssm":
        return p  # mamba2: no FFN
    p["norm2"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.is_moe:
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k2, cfg, dtype)
    return p


def _ffn(p: Params, x: jax.Array, cfg) -> jax.Array:
    h = rmsnorm(x, p["norm2"])
    if cfg.is_moe:
        return x + moe_mod.moe_apply(p["moe"], h, cfg)
    return x + mlp_apply(p["mlp"], h, cfg)


def _attn_layer(p: Params, x: jax.Array, cfg, *, window=None) -> jax.Array:
    h = rmsnorm(x, p["norm1"])
    x = x + attn.attn_apply(p["attn"], h, cfg, causal=True, window=window)
    if cfg.family == "ssm":
        return x
    return _ffn(p, x, cfg)


def _ssm_layer(p: Params, x: jax.Array, cfg) -> jax.Array:
    h = rmsnorm(x, p["norm1"])
    x = x + ssm_mod.ssd_apply(p["ssm"], h, cfg)
    if cfg.family == "ssm":
        return x
    return _ffn(p, x, cfg)


# ---------------------------------------------------------------------------
# block-stack structure per family
# ---------------------------------------------------------------------------


def _stack_init(key, cfg, dtype) -> Params:
    def init_super(k):
        ks = jax.random.split(k, cfg.period)
        # same-kind layers within a super-block keep distinct pytree slots
        return {f"l{i}": _layer_init(ks[i], cfg, dtype, kind=cfg.layer(i).mixer)
                for i in range(cfg.period)}

    keys = jax.random.split(key, cfg.superblocks)
    return jax.vmap(init_super)(keys)


def _super_apply(sp: Params, x: jax.Array, cfg) -> jax.Array:
    for i in range(cfg.period):
        p, layer = sp[f"l{i}"], cfg.layer(i)
        if layer.mixer == "ssm":
            x = _ssm_layer(p, x, cfg)
        else:
            x = _attn_layer(p, x, cfg, window=layer.window)
    return x


# ---------------------------------------------------------------------------
# LM init / forward / loss
# ---------------------------------------------------------------------------


def lm_init(cfg, key) -> Params:
    dtype = dtype_of(cfg)
    k_embed, k_blocks, k_head, k_proj = jax.random.split(key, 4)
    p: Params = {
        "embed": embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype),
        "blocks": _stack_init(k_blocks, cfg, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, dtype)
    if cfg.family == "vlm":
        p["mm_proj"] = dense_init(k_proj, (1024, cfg.d_model), 1024, dtype)
    return p


def _embed_inputs(params: Params, batch: Dict[str, jax.Array], cfg) -> jax.Array:
    x = params["embed"][batch["tokens"]]
    if cfg.family == "vlm" and "patches" in batch:
        # anyres frontend stub: precomputed patch embeddings [B, P, 1024]
        # projected and placed at the first P positions.
        proj = batch["patches"] @ params["mm_proj"]
        n = proj.shape[1]
        x = jnp.concatenate([proj.astype(x.dtype), x[:, n:]], axis=1)
    return x


REMAT_POLICY = "full"  # "full" | "dots" | "none" — set by launch drivers


def set_remat_policy(policy: str) -> None:
    global REMAT_POLICY
    assert policy in ("full", "dots", "none")
    REMAT_POLICY = policy


def _remat(body):
    if REMAT_POLICY == "none":
        return body
    if REMAT_POLICY == "dots":
        # save matmul outputs: backward recomputes only cheap elementwise
        # chains — ~2x less recompute traffic for ~linear activation memory
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(body)


def lm_forward(params: Params, batch: Dict[str, jax.Array], cfg, *, remat: bool = True) -> jax.Array:
    """tokens [B, S] (+patches) -> logits [B, S, V]."""
    x = constrain(_embed_inputs(params, batch, cfg), "batch", "seq_res", None)

    body = functools.partial(_super_apply, cfg=cfg)
    if remat:
        body = _remat(body)

    def scan_fn(x, sp):
        return constrain(body(sp, x), "batch", "seq_res", None), None

    x, _ = jax.lax.scan(scan_fn, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return constrain(x @ head, "batch", "seq", "vocab")


def lm_loss(params: Params, batch: Dict[str, jax.Array], cfg) -> jax.Array:
    from repro.models.common import cross_entropy_loss

    logits = lm_forward(params, batch, cfg)
    return cross_entropy_loss(logits, batch["labels"])


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def cache_init(cfg, batch: int, max_seq: int) -> Params:
    """Per-super-block stacked caches matching the scan structure."""
    dtype = dtype_of(cfg)

    def one(_):
        entry: Dict[str, Any] = {}
        for i in range(cfg.period):
            layer = cfg.layer(i)
            if layer.mixer == "ssm":
                entry[f"l{i}"] = ssm_mod.ssd_state_init(cfg, batch, dtype)
            else:
                entry[f"l{i}"] = attn.cache_init(
                    cfg, batch, max_seq, dtype, window=layer.window
                )
        return entry

    return jax.vmap(one)(jnp.arange(cfg.superblocks))


def _super_decode(sp, cache_sp, x, pos, cfg):
    new_cache = {}
    for i in range(cfg.period):
        p, c, layer = sp[f"l{i}"], cache_sp[f"l{i}"], cfg.layer(i)
        if layer.mixer == "ssm":
            h = rmsnorm(x, p["norm1"])
            y, c2 = ssm_mod.ssd_decode(p["ssm"], h, cfg, c)
            x = x + y
        else:
            h = rmsnorm(x, p["norm1"])
            y, c2 = attn.attn_decode(
                p["attn"], h, cfg, c, pos, window=layer.window
            )
            x = x + y
        if cfg.family != "ssm":
            x = _ffn(p, x, cfg)
        new_cache[f"l{i}"] = c2
    return x, new_cache


def decode_step(
    params: Params,
    tokens: jax.Array,   # [B, 1]
    cache: Params,
    pos: jax.Array,      # [] int32
    cfg,
) -> Tuple[jax.Array, Params]:
    """One new token for the whole batch against the KV/SSM caches."""
    x = params["embed"][tokens]

    def scan_fn(x, sc):
        sp, cache_sp = sc
        x, new_c = _super_decode(sp, cache_sp, x, pos, cfg)
        return x, new_c

    x, new_cache = jax.lax.scan(scan_fn, x, (params["blocks"], cache))
    x = rmsnorm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, new_cache


def prefill(
    params: Params,
    batch: Dict[str, jax.Array],
    cache: Params,
    cfg,
    *,
    expert_load: bool = False,
):
    """Run the prompt, fill caches, return last-position logits and the
    new caches; with ``expert_load`` also each layer's
    ``moe.expert_load`` (``[layers, 2]``; a model with experts only)."""
    x = _embed_inputs(params, batch, cfg)

    def super_prefill(sp, cache_sp, x):
        new_cache, loads = {}, []
        for i in range(cfg.period):
            p, c, layer = sp[f"l{i}"], cache_sp[f"l{i}"], cfg.layer(i)
            if layer.mixer == "ssm":
                h = rmsnorm(x, p["norm1"])
                xproj, z, Bm, Cm, dt = ssm_mod._inputs(p["ssm"], h, cfg)
                A = -jnp.exp(p["ssm"]["A_log"])
                y, final = ssm_mod.ssd_scan(xproj, dt, A, Bm, Cm)
                y = y + xproj.astype(jnp.float32) * p["ssm"]["D"][:, None]
                bsz, s = h.shape[:2]
                y = y.reshape(bsz, s, cfg.ssm_d_inner).astype(h.dtype)
                y = rmsnorm(y * jax.nn.silu(z), p["ssm"]["gate_norm"]) @ p["ssm"]["wo"]
                x = x + y
                new_c = dict(c)
                new_c["ssm"] = final
                # conv state: last K-1 pre-activation conv inputs
                u = jnp.concatenate([h @ p["ssm"]["wx"], h @ p["ssm"]["wB"], h @ p["ssm"]["wC"]], axis=-1)
                new_c["conv"] = u[:, -(ssm_mod.CONV_K - 1):].astype(c["conv"].dtype)
                c2 = new_c
            else:
                h = rmsnorm(x, p["norm1"])
                y, c2 = attn.attn_prefill(
                    p["attn"], h, cfg, c, window=layer.window
                )
                x = x + y
            if expert_load:
                # the same routing the FFN below computes (XLA merges the two)
                loads.append(moe_mod.expert_load(p["moe"], rmsnorm(x, p["norm2"]), cfg))
            if cfg.family != "ssm":
                x = _ffn(p, x, cfg)
            new_cache[f"l{i}"] = c2
        return x, (new_cache, jnp.stack(loads) if loads else None)

    def scan_fn(x, sc):
        sp, cache_sp = sc
        return super_prefill(sp, cache_sp, x)

    x, (new_cache, loads) = jax.lax.scan(scan_fn, x, (params["blocks"], cache))
    x = rmsnorm(x[:, -1:], params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if expert_load:
        return x @ head, new_cache, loads.reshape(cfg.num_layers, 2)
    return x @ head, new_cache
