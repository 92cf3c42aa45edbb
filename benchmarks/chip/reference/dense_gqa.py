"""Plain float32 reference of a dense decoder with grouped-query
attention and qk-norm, as Qwen3 publishes it
(https://huggingface.co/Qwen/Qwen3-4B, ``Qwen3ForCausalLM``):

    x = embed[tokens]
    per layer:  h = rmsnorm(x);  q, k, v = h Wq, h Wk, h Wv  (per head)
                q, k = rmsnorm_hd(q), rmsnorm_hd(k);  rope (rotate-half)
                x += softmax(q k^T / sqrt(hd), causal) v  Wo
                h = rmsnorm(x);  x += (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x) head   (head = embed^T when tied)

It imports nothing of the program. ``init_params`` makes seeded random
weights in the layout the program's serving engine loads (stacked over
layers); the benchmark makes the program's weights with it, and makes
them again from the seed for this reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import refmath as rm


def sizes(doc: dict) -> dict:
    """The sizes this reference and the work model read, from the
    configuration file's published ``config.json`` keys."""
    pub = doc["published"]
    return {
        "layers": int(pub["num_hidden_layers"]),
        "d_model": int(pub["hidden_size"]),
        "heads": int(pub["num_attention_heads"]),
        "kv_heads": int(pub["num_key_value_heads"]),
        "head_dim": int(pub["head_dim"]),
        "d_ff": int(pub["intermediate_size"]),
        "vocab": int(pub["vocab_size"]),
        "tied": bool(pub["tie_word_embeddings"]),
        "rope_theta": float(pub["rope_theta"]),
        "eps": float(pub["rms_norm_eps"]),
    }


def weight_products(sz: dict):
    """(K, N) of every per-token weight product of one forward step over
    all layers, and of the output head (``chipbench.work``)."""
    d, h, kv, hd, ff = (sz[k] for k in ("d_model", "heads", "kv_heads", "head_dim", "d_ff"))
    layer = [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d), (d, ff), (d, ff), (ff, d)]
    return layer * sz["layers"], (d, sz["vocab"])


def mixer_flops(sz: dict, context: int) -> float:
    """FLOPs of one token's attention over ``context`` positions, all
    layers: q k^T and the weighted sum of v."""
    return 4.0 * sz["heads"] * sz["head_dim"] * context * sz["layers"]


def decode_attention(sz: dict, positions) -> tuple:
    """(FLOPs, bytes) of one decode step's attention over all layers,
    for live slots writing at ``positions``: each reads K and V up to
    and including its own position, bfloat16."""
    h, kv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    live = sum(p + 1 for p in positions)
    flops = 4.0 * h * hd * live
    nbytes = 2 * (2 * kv * hd * live + 2 * h * hd * len(positions))
    return flops * sz["layers"], nbytes * sz["layers"]


def init_params(sz: dict, key, dtype=jnp.bfloat16) -> dict:
    """Seeded weights, stacked over layers. Norm gains are 1 + N(0, 0.1)
    so that a path that drops a gain shows."""
    n, d, h, kv, hd, ff, v = (sz[k] for k in (
        "layers", "d_model", "heads", "kv_heads", "head_dim", "d_ff", "vocab"))
    ks = iter(jax.random.split(key, 16))

    def gain(shape):
        return 1.0 + rm.normal(next(ks), shape, 0.1, dtype)

    p = {
        "embed": rm.normal(next(ks), (v, d), d ** -0.5, dtype),
        "final_norm": gain((d,)),
        "blocks": {"l0": {
            "norm1": gain((n, d)),
            "norm2": gain((n, d)),
            "attn": {
                "wq": rm.normal(next(ks), (n, d, h, hd), d ** -0.5, dtype),
                "wk": rm.normal(next(ks), (n, d, kv, hd), d ** -0.5, dtype),
                "wv": rm.normal(next(ks), (n, d, kv, hd), d ** -0.5, dtype),
                "wo": rm.normal(next(ks), (n, h, hd, d), (h * hd) ** -0.5, dtype),
                "q_norm": gain((n, hd)),
                "k_norm": gain((n, hd)),
            },
            "mlp": {
                "wg": rm.normal(next(ks), (n, d, ff), d ** -0.5, dtype),
                "wu": rm.normal(next(ks), (n, d, ff), d ** -0.5, dtype),
                "wo": rm.normal(next(ks), (n, ff, d), ff ** -0.5, dtype),
            },
        }},
    }
    if not sz["tied"]:
        p["lm_head"] = rm.normal(next(ks), (d, v), d ** -0.5, dtype)
    return p


def _rope(x, theta: float):
    """Rotate-half rotary embedding of ``x [S, H, D]`` at 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("sz", "fp8"))
def _layer(blocks, i, x, *, sz, fp8):
    """Layer ``i`` of the stacked ``blocks`` applied to ``x [S, d]``."""
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
                     .astype(jnp.float32), blocks["l0"])
    eps, kv, hd = sz["eps"], sz["kv_heads"], sz["head_dim"]
    g = sz["heads"] // kv
    s = x.shape[0]
    a = p["attn"]
    h = rm.rmsnorm(x, p["norm1"], eps)
    f8 = (1, 0) if fp8 else None
    q = rm.einsum("sd,dhk->shk", h, a["wq"], fp8_axes=f8)
    k = rm.einsum("sd,dhk->shk", h, a["wk"], fp8_axes=f8)
    v = rm.einsum("sd,dhk->shk", h, a["wv"], fp8_axes=f8)
    q = _rope(rm.rmsnorm(q, a["q_norm"], eps), sz["rope_theta"])
    k = _rope(rm.rmsnorm(k, a["k_norm"], eps), sz["rope_theta"])
    q = q.reshape(s, kv, g, hd)
    scores = rm.einsum("qkgd,skd->kgqs", q, k,
                       fp8_axes=(3, 2) if fp8 else None) * hd ** -0.5
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = rm.einsum("kgqs,skd->qkgd", w, v, fp8_axes=(3, 0) if fp8 else None)
    o = o.reshape(s, sz["heads"], hd)
    x = x + rm.mm(o.reshape(s, -1), a["wo"].reshape(-1, sz["d_model"]), fp8)
    m = p["mlp"]
    h = rm.rmsnorm(x, p["norm2"], eps)
    up = jax.nn.silu(rm.mm(h, m["wg"], fp8)) * rm.mm(h, m["wu"], fp8)
    return x + rm.mm(up, m["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("sz", "fp8"))
def _head(params, x, *, sz, fp8):
    x = rm.rmsnorm(x, params["final_norm"], sz["eps"])
    head = params["embed"].T if sz["tied"] else params["lm_head"]
    return rm.mm(x, head, fp8)


def forward(sz: dict, params: dict, tokens: jax.Array, *, fp8: bool = False):
    """Logits ``[S, V]`` (float32) of one sequence ``tokens [S]``,
    layer by layer."""
    szh = rm.Frozen(sz)
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(sz["layers"]):
        x = _layer(params["blocks"], i, x, sz=szh, fp8=fp8)
    return _head(params, x, sz=szh, fp8=fp8)
