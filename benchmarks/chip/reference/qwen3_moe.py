"""Plain float32 reference of a Qwen3 mixture-of-experts decoder, as
published (https://huggingface.co/Qwen/Qwen3-235B-A22B,
``Qwen3MoeForCausalLM``), at one chip's share of its experts:

    x = embed[tokens]
    per layer:  h = rmsnorm(x);  q, k, v = h Wq, h Wk, h Wv  (per head)
                q, k = rmsnorm_hd(q), rmsnorm_hd(k);  rope (rotate-half)
                x += softmax(q k^T / sqrt(hd), causal) v  Wo
                h = rmsnorm(x);  p = softmax(h Wr) over every expert
                g = top-k of p, renormalised over the k (norm_topk_prob)
                x += sum over the held experts e that the token picked of
                     g_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    logits = rmsnorm(x) head   (untied)

The router keeps its published width (``published.num_experts``) and
top-k; the layer adds only what the experts held here give
(``config.num_experts`` of them, from ``deployment.expert_rank``). Each
held expert is applied to every token and weighted by its gate, which is
zero for a token that did not pick it: no capacity, nothing dropped.

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
    configuration file: ``config`` as run, the router width from
    ``published``, the share from ``deployment``."""
    cfg, dep = doc["config"], doc["deployment"]
    held = int(cfg["num_experts"])
    return {
        "layers": int(cfg["num_hidden_layers"]),
        "d_model": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "d_expert": int(cfg["moe_intermediate_size"]),
        "experts": int(doc["published"]["num_experts"]),
        "held": held,
        "offset": int(dep["expert_rank"]) * held,
        "top_k": int(cfg["num_experts_per_tok"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "vocab": int(cfg["vocab_size"]),
        "tied": bool(cfg["tie_word_embeddings"]),
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "dtype": str(cfg["torch_dtype"]),
    }


def weight_products(sz: dict):
    """(K, N) of the per-token weight products every token passes
    through, all layers: attention and the router, not the experts
    (``expert_work``); and of the output head (``chipbench.work``)."""
    d, h, kv, hd = (sz[k] for k in ("d_model", "heads", "kv_heads", "head_dim"))
    layer = [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d), (d, sz["experts"])]
    return layer * sz["layers"], (d, sz["vocab"])


def expert_work(sz: dict, pairs: int, active: int) -> tuple:
    """(FLOPs, bytes) of one layer's held experts for ``pairs`` routed
    (token, held expert) pairs, ``active`` of the held experts having a
    token: three products of 2·d·f per pair; each active expert's three
    matrices read once, and each pair's row in and out, bfloat16."""
    d, f = sz["d_model"], sz["d_expert"]
    return 6.0 * d * f * pairs, 2.0 * (3 * d * f * active + 2 * d * pairs)


def expert_least_s(sz: dict, pairs: int, active: int, peak_flops: float,
                   hbm_bw: float) -> float:
    """Least time of one layer's expert products: bound by their
    operations or their bytes (``expert_work``), whichever is longer."""
    flops, nbytes = expert_work(sz, pairs, active)
    return max(flops / peak_flops, nbytes / hbm_bw)


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


def init_params(sz: dict, key, dtype=None) -> dict:
    """Seeded weights in the configuration's dtype (or ``dtype``),
    stacked over layers: the router over every expert, the held
    experts' matrices. Norm gains are 1 + N(0, 0.1) so that a path that
    drops a gain shows."""
    n, d, h, kv, hd, f, e, held, v = (sz[k] for k in (
        "layers", "d_model", "heads", "kv_heads", "head_dim", "d_expert", "experts",
        "held", "vocab"))
    dtype = jnp.dtype(dtype or sz["dtype"])
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
            "moe": {
                "router": rm.normal(next(ks), (n, d, e), d ** -0.5, dtype),
                "wg": rm.normal(next(ks), (n, held, d, f), d ** -0.5, dtype),
                "wu": rm.normal(next(ks), (n, held, d, f), d ** -0.5, dtype),
                "wo": rm.normal(next(ks), (n, held, f, d), f ** -0.5, dtype),
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


def gates(sz: dict, router_logits):
    """``[S, held]``: the renormalised top-k gate of each held expert a
    token picked, 0 where it did not pick it."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top = jax.lax.top_k(probs, sz["top_k"])
    if sz["norm_topk"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    held = sz["offset"] + jnp.arange(sz["held"])
    return jnp.sum(jnp.where(top[:, :, None] == held, top_p[:, :, None], 0.0), axis=1)


def moe(sz: dict, m: dict, h, *, fp8: bool = False):
    """The held experts' part of the layer for ``h [S, d]`` (float32
    weights ``m``: router, wg, wu, wo)."""
    g = gates(sz, rm.mm(h, m["router"], fp8))
    f8 = (1, 1) if fp8 else None
    up = jax.nn.silu(rm.einsum("sd,edf->esf", h, m["wg"], fp8_axes=f8)) \
        * rm.einsum("sd,edf->esf", h, m["wu"], fp8_axes=f8)
    out = rm.einsum("esf,efd->esd", up, m["wo"], fp8_axes=(2, 1) if fp8 else None)
    return jnp.sum(out * g.T[:, :, None], axis=0)


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
    return x + moe(sz, p["moe"], rm.rmsnorm(x, p["norm2"], eps), fp8=fp8)


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
