"""Plain float32 reference of Mamba-2 as ``mamba_ssm`` publishes it
(https://huggingface.co/state-spaces/mamba2-2.7b; Dao and Gu,
arXiv:2405.21060), one group, token by token:

    x = embed[tokens]
    per layer:  h = rmsnorm(x)
                z, xBC, dt = h Wz, h [Wx Wb Wc], h Wdt
                xBC = silu(causal depthwise conv(xBC) + conv bias)
                dt = softplus(dt + dt_bias);  A = -exp(A_log)
                S_t = exp(dt_t A) S_{t-1} + dt_t B_t (x) x_t   (per head)
                y_t = C_t . S_t + D x_t
                x += rmsnorm(y * silu(z)) Wo
    logits = rmsnorm(x) embed^T

It imports nothing of the program. ``init_params`` makes seeded random
weights in the layout the program's serving engine loads (stacked over
layers, the in-projection split by output); the program has no conv
bias, so the weights hold none and the reference adds none.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import refmath as rm

CONV_K = 4


def sizes(doc: dict) -> dict:
    """Sizes from the configuration file: the published ``config.json``
    and, under ``assumed``, the Mamba2 module's defaults it relies on."""
    pub, assumed = doc["published"], doc["assumed"]
    ssm = {**assumed["ssm_cfg"], **pub["ssm_cfg"]}
    d = int(pub["d_model"])
    di = int(ssm["expand"]) * d
    mult = int(pub["pad_vocab_size_multiple"])
    vocab = -(-int(pub["vocab_size"]) // mult) * mult
    return {
        "layers": int(pub["n_layer"]),
        "d_model": d,
        "d_inner": di,
        "state": int(ssm["d_state"]),
        "ssm_head_dim": int(ssm["headdim"]),
        "ssm_heads": di // int(ssm["headdim"]),
        "conv": int(ssm["d_conv"]),
        "vocab": vocab,
        "tied": bool(pub["tie_embeddings"]),
        "eps": float(assumed["norm_epsilon"]),
    }


def weight_products(sz: dict):
    """(K, N) of every per-token weight product of one forward step over
    all layers (x, z, B, C, dt in; out), and of the output head
    (``chipbench.work``)."""
    d, di, n, nh = (sz[k] for k in ("d_model", "d_inner", "state", "ssm_heads"))
    layer = [(d, di), (d, di), (d, n), (d, n), (d, nh), (di, d)]
    return layer * sz["layers"], (d, sz["vocab"])


def mixer_flops(sz: dict, context: int) -> float:
    """FLOPs of one token's sequence mixer beyond the weight products,
    all layers: the depthwise conv, the state update (4 per state
    element) and the read-out (2). The context does not change it."""
    conv_dim = sz["d_inner"] + 2 * sz["state"]
    state = sz["ssm_heads"] * sz["state"] * sz["ssm_head_dim"]
    return (2.0 * sz["conv"] * conv_dim + 6.0 * state) * sz["layers"]


def init_params(sz: dict, key, dtype=jnp.bfloat16) -> dict:
    """Seeded weights, stacked over layers, initialised as ``mamba_ssm``
    does: A in [1, 16], dt in [1e-3, 1e-1] through softplus, D = 1."""
    n, d, di, ns, nh, v = (sz[k] for k in (
        "layers", "d_model", "d_inner", "state", "ssm_heads", "vocab"))
    conv_dim = di + 2 * ns
    ks = iter(jax.random.split(key, 16))

    def gain(shape):
        return 1.0 + rm.normal(next(ks), shape, 0.1, dtype)

    dt = jnp.exp(jax.random.uniform(next(ks), (n, nh), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {
        "embed": rm.normal(next(ks), (v, d), d ** -0.5, dtype),
        "final_norm": gain((d,)),
        "blocks": {"l0": {
            "norm1": gain((n, d)),
            "ssm": {
                "wx": rm.normal(next(ks), (n, d, di), d ** -0.5, dtype),
                "wz": rm.normal(next(ks), (n, d, di), d ** -0.5, dtype),
                "wB": rm.normal(next(ks), (n, d, ns), d ** -0.5, dtype),
                "wC": rm.normal(next(ks), (n, d, ns), d ** -0.5, dtype),
                "wdt": rm.normal(next(ks), (n, d, nh), d ** -0.5, dtype),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(next(ks), (n, nh),
                                                    jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((n, nh), jnp.float32),
                "conv_w": (jax.random.uniform(next(ks), (n, CONV_K, conv_dim),
                                              jnp.float32, -0.5, 0.5)).astype(dtype),
                "gate_norm": gain((n, di)),
                "wo": rm.normal(next(ks), (n, di, d), di ** -0.5, dtype),
            },
        }},
    }


@functools.partial(jax.jit, static_argnames=("sz", "fp8"))
def _layer(blocks, i, x, *, sz, fp8):
    """Layer ``i`` of the stacked ``blocks`` applied to ``x [S, d]``."""
    b = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
                     .astype(jnp.float32), blocks["l0"])
    p = b["ssm"]
    eps, di, ns = sz["eps"], sz["d_inner"], sz["state"]
    nh, hp = sz["ssm_heads"], sz["ssm_head_dim"]
    s = x.shape[0]
    h = rm.rmsnorm(x, b["norm1"], eps)
    z = rm.mm(h, p["wz"], fp8)
    xbc = jnp.concatenate([rm.mm(h, p["wx"], fp8), rm.mm(h, p["wB"], fp8),
                           rm.mm(h, p["wC"], fp8)], axis=-1)
    w = p["conv_w"]                                   # [K, conv]
    pad = jnp.concatenate([jnp.zeros((CONV_K - 1, xbc.shape[1])), xbc])
    conv = sum(w[k] * pad[k:k + s] for k in range(CONV_K))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :di].reshape(s, nh, hp)
    bs, cs = xbc[:, di:di + ns], xbc[:, di + ns:]
    dt = jax.nn.softplus(rm.mm(h, p["wdt"], fp8) + p["dt_bias"])   # [S, H]
    a = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :]
        y_t = rm.einsum("n,hnp->hp", c_t, state,
                        fp8_axes=(0, 1) if fp8 else None)
        return state, y_t

    _, y = jax.lax.scan(step, jnp.zeros((nh, ns, hp), jnp.float32),
                        (xs, bs, cs, dt))
    y = (y + p["D"][None, :, None] * xs).reshape(s, di)
    y = rm.rmsnorm(y * jax.nn.silu(z), p["gate_norm"], eps)
    return x + rm.mm(y, p["wo"], fp8)


@functools.partial(jax.jit, static_argnames=("sz", "fp8"))
def _head(params, x, *, sz, fp8):
    x = rm.rmsnorm(x, params["final_norm"], sz["eps"])
    return rm.mm(x, params["embed"].T, fp8)


def forward(sz: dict, params: dict, tokens: jax.Array, *, fp8: bool = False):
    """Logits ``[S, V]`` (float32) of one sequence ``tokens [S]``,
    layer by layer."""
    szh = rm.Frozen(sz)
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(sz["layers"]):
        x = _layer(params["blocks"], i, x, sz=szh, fp8=fp8)
    return _head(params, x, sz=szh, fp8=fp8)
