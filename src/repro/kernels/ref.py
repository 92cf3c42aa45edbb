"""Pure oracles for every ``axe.program`` kernel in this package.

Each program's tests sweep shapes/dtypes and assert_allclose against
these references (kernels run in interpret mode on CPU; on TPU they
compile to Mosaic). The routing oracle is deliberately loop-based
numpy — independent of the sort/scatter implementation it checks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """f32-accumulated GEMM."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def rmsnorm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)


def attention_ref(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, H, Skv, D]
    v: jax.Array,  # [B, H, Skv, D]
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Multi-head attention oracle with optional causal / sliding-window
    masking (paper §4.3 MHA workload; Gemma-style local attention)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    sq, skv = q.shape[-2], k.shape[-2]
    q_pos = jnp.arange(sq)[:, None] + (skv - sq)  # right-aligned queries
    k_pos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def moe_gemm_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    """Grouped (per-expert) GEMM oracle: x [E, C, d] @ w [E, d, f]."""
    return jnp.einsum(
        "ecd,edf->ecf", x.astype(jnp.float32), w.astype(jnp.float32)
    ).astype(x.dtype)


def collective_matmul_ref(a: jax.Array, b: jax.Array, p: int) -> jax.Array:
    """Oracle for the K-sharded collective matmul (paper §4.2): the
    global result both schedules must reconstruct. ``a`` [M, K] /
    ``b`` [K, N] are the *logical* (unsharded) operands; the device
    view splits K into ``p`` local slices, computes partial products in
    f32, and the reduce-scatter sums them — reproduced here as the
    explicit p-way partial accumulation so the accumulation order (and
    dtype) matches what the ``psum_scatter``/``ring`` schedules do."""
    m, k = a.shape
    assert k % p == 0, (k, p)
    kl = k // p
    acc = jnp.zeros((m, b.shape[1]), jnp.float32)
    for i in range(p):
        acc = acc + jnp.dot(
            a[:, i * kl:(i + 1) * kl].astype(jnp.float32),
            b[i * kl:(i + 1) * kl].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    return acc.astype(a.dtype)


def moe_routing_ref(
    x: np.ndarray,       # [T, d] tokens
    router: np.ndarray,  # [d, E] router weights
    *,
    experts_per_tok: int,
):
    """Loop-based oracle for dropless routing (dispatch → combine),
    independent of the vectorised implementation in ``models.moe``.

    Token t picks the experts of the top-k of softmax(x_t @ router), its
    gates renormalised over the k; each picked expert gets x_t in its row
    t. Returns ``(buf, combine)`` where ``buf`` is the dense [E, T, d]
    dispatch buffer and ``combine(out)`` gate-weights an [E, T, d']
    expert output back to [T, d'] (the identity-FFN check:
    ``combine(buf)`` ≈ the gate-weighted reconstruction of the tokens)."""
    x = np.asarray(x, np.float32)
    router = np.asarray(router, np.float32)
    t, d = x.shape
    e = router.shape[1]
    logits = x @ router
    z = np.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = z / z.sum(axis=-1, keepdims=True)

    buf = np.zeros((e, t, d), np.float32)
    assignments = []  # (token, expert, gate)
    for ti in range(t):
        order = np.argsort(-probs[ti], kind="stable")[:experts_per_tok]
        gates = probs[ti][order]
        gates = gates / gates.sum()
        for ei, g in zip(order, gates):
            buf[ei, ti] = x[ti]
            assignments.append((ti, int(ei), float(g)))

    def combine(out):
        out = np.asarray(out, np.float32)
        y = np.zeros((t, out.shape[-1]), np.float32)
        for ti, ei, g in assignments:
            y[ti] += g * out[ei, ti]
        return y

    return buf, combine
