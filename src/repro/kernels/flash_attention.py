"""Blocked online-softmax attention (FlashAttention) as an
``axe.program`` stage graph — the paper's §4.3 MHA workload, adapted
from Trainium's NKI pipeline to the TPU grid/VMEM model.

* ``flash_attention/attend``      (GRID)  — the Pallas launch. Grid:
  (batch*heads, q_blocks, kv_blocks); kv is the innermost "arbitrary"
  dim. Schedule key ``flash_attention/attend`` (blocks bq/bkv; the
  causal flag tags the layout signature so causal and full sweeps tune
  separately).
* ``flash_attention/softmax_mac`` (BLOCK) — the per-cell online-softmax
  update on VMEM refs: running max / denominator / f32 accumulator live
  in scratch and are finalized on the last kv step. Causal and
  sliding-window masks (Gemma-3-style local attention) are computed
  from grid coordinates — the Axe story of deriving predicates from
  layout coordinates rather than hand-written index math.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.axe.lower import block_lowering
from repro.axe.program import program
from repro.core.scopes import Scope

NEG_INF = -1e30

flash_attention_program = program(
    "flash_attention",
    doc="softmax(Q Kᵀ / √d) V with online softmax, causal/window masking",
)


def _fa_key(args, kw, arg_specs=()):
    return {"tag": "causal" if kw.get("causal") else None}


def _fa_flops(args, kw) -> float:
    q, k = args[0], args[1]
    b, h, sq, d = q.shape
    return 4.0 * b * h * sq * k.shape[2] * d


@flash_attention_program.stage("softmax_mac", scope=Scope.BLOCK)
def _softmax_mac(
    ctx,
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    kv_steps: int,
    block_q: int,
    block_kv: int,
    causal: bool,
    window: int | None,
    scale: float,
    kv_len: int,
    q_len: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # [bq, d]
    k = k_ref[0].astype(jnp.float32)  # [bkv, d]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    # positions (queries right-aligned against kv, for decode/prefill mix)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) + (kv_len - q_len)
    k_pos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = jnp.ones((block_q, block_kv), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(kj == kv_steps - 1)
    def _done():
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


@flash_attention_program.stage(
    "attend", scope=Scope.GRID, entry=True,
    blocks=(("bq", 128), ("bkv", 128)),
    variants=("kernel",),
    key=_fa_key,
    flops=_fa_flops,
)
def _attend(ctx, q, k, v, *, causal: bool = False, window: int | None = None,
            scale: float | None = None):
    b, h, sq, d = q.shape
    _, _, skv, _ = k.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    block_q = min(ctx.block("bq"), sq)
    block_kv = min(ctx.block("bkv"), skv)

    def make():
        def launch(q, k, v):
            b, h, sq, d = q.shape
            skv = k.shape[2]
            bh = b * h
            qr = q.reshape(bh, sq, d)
            kr = k.reshape(bh, skv, d)
            vr = v.reshape(bh, skv, d)

            # Axe on-device lowering: q/k/v/o tiles validated through the
            # unified TilingError path.
            q_low = block_lowering((bh, sq, d), (1, block_q, d), q.dtype,
                                   index_map=lambda bhi, qi, kj: (bhi, qi, 0),
                                   op="flash_attention.Q")
            k_low = block_lowering((bh, skv, d), (1, block_kv, d), k.dtype,
                                   index_map=lambda bhi, qi, kj: (bhi, kj, 0),
                                   op="flash_attention.K")
            v_low = block_lowering((bh, skv, d), (1, block_kv, d), v.dtype,
                                   index_map=lambda bhi, qi, kj: (bhi, kj, 0),
                                   op="flash_attention.V")
            o_low = block_lowering((bh, sq, d), (1, block_q, d), q.dtype,
                                   index_map=lambda bhi, qi, kj: (bhi, qi, 0),
                                   op="flash_attention.O")
            kv_steps = k_low.grid[1]

            body = functools.partial(
                ctx.run, "softmax_mac",
                kv_steps=kv_steps, block_q=block_q, block_kv=block_kv,
                causal=causal, window=window, scale=scale,
                kv_len=skv, q_len=sq,
            )
            out = ctx.pallas_call(
                lambda *refs: body(*refs),
                grid=(bh, q_low.grid[1], kv_steps),
                in_specs=[q_low.spec, k_low.spec, v_low.spec],
                out_specs=o_low.spec,
                out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                scratch_shapes=[
                    pltpu.VMEM((block_q, d), jnp.float32),
                    pltpu.VMEM((block_q, 1), jnp.float32),
                    pltpu.VMEM((block_q, 1), jnp.float32),
                ],
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            )(qr, kr, vr)
            return out.reshape(b, h, sq, d)

        return launch

    return ctx.jit((block_q, block_kv, causal, window, scale), make)(q, k, v)


# ---------------------------------------------------------------------------
# flash decode: single-token queries attending over the laid-out cache
# ---------------------------------------------------------------------------


@flash_attention_program.stage("decode_mac", scope=Scope.BLOCK)
def _decode_mac(
    ctx,
    pos_ref, q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    kv_steps: int,
    block_kv: int,
    ring: bool,
    kv_len: int,
    scale: float,
):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # [g, d]
    k = k_ref[0].astype(jnp.float32)  # [bkv, d]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    # cache validity from the per-slot position (an SMEM scalar, one per
    # batch*kv-head row): linear caches attend to k_pos <= pos; a ring
    # buffer that has wrapped (pos + 1 >= W) is entirely live — the same
    # predicate the reference decode applies
    pos_b = pos_ref[pl.program_id(0)]
    k_pos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = k_pos <= pos_b
    if ring:
        valid = valid | (pos_b + 1 >= kv_len)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_cur

    @pl.when(kj == kv_steps - 1)
    def _done():
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


@flash_attention_program.stage("decode", scope=Scope.GRID)
def _decode(ctx, q, k, v, pos, *, ring: bool = False, scale: float | None = None):
    """Flash decode: grouped single-token queries ``q [B, KV, G, d]``
    attend over the cache ``k/v [B, KV, W, d]`` at per-slot positions
    ``pos [B]``. Grid: (batch*kv_heads, kv_blocks) with the online
    softmax accumulating across cache blocks — the decode twin of
    ``attend``, with the mask coming from the runtime position instead
    of grid coordinates. Untunable by design: the kv block size is the
    largest preferred size dividing the cache length (a cache is a
    fixed ring, not a schedule choice)."""
    b, kvh, g, d = q.shape
    w = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    block_kv = next((s for s in (512, 256, 128, 64) if s <= w and w % s == 0), w)

    def make():
        def launch(q, k, v, pos):
            b, kvh, g, d = q.shape
            w = k.shape[2]
            bh = b * kvh
            qr = q.reshape(bh, g, d)
            kr = k.reshape(bh, w, d)
            vr = v.reshape(bh, w, d)
            # positions ride in SMEM as scalar prefetch: a (1, 1) VMEM
            # block would break the TPU's (8, 128) block-shape rule
            pr = jnp.repeat(pos.astype(jnp.int32), kvh)

            q_low = block_lowering((bh, g, d), (1, g, d), q.dtype,
                                   index_map=lambda bhi, kj, _p: (bhi, 0, 0),
                                   op="flash_attention.decode.Q")
            k_low = block_lowering((bh, w, d), (1, block_kv, d), k.dtype,
                                   index_map=lambda bhi, kj, _p: (bhi, kj, 0),
                                   op="flash_attention.decode.K")
            v_low = block_lowering((bh, w, d), (1, block_kv, d), v.dtype,
                                   index_map=lambda bhi, kj, _p: (bhi, kj, 0),
                                   op="flash_attention.decode.V")
            o_low = block_lowering((bh, g, d), (1, g, d), q.dtype,
                                   index_map=lambda bhi, kj, _p: (bhi, 0, 0),
                                   op="flash_attention.decode.O")
            kv_steps = k_low.grid[1]

            body = functools.partial(
                ctx.run, "decode_mac",
                kv_steps=kv_steps, block_kv=block_kv,
                ring=ring, kv_len=w, scale=scale,
            )
            out = ctx.pallas_call(
                lambda *refs: body(*refs),
                grid=(bh, kv_steps),
                in_specs=[q_low.spec, k_low.spec, v_low.spec],
                out_specs=o_low.spec,
                out_shape=jax.ShapeDtypeStruct((bh, g, d), q.dtype),
                scratch_shapes=[
                    pltpu.VMEM((g, d), jnp.float32),
                    pltpu.VMEM((g, 1), jnp.float32),
                    pltpu.VMEM((g, 1), jnp.float32),
                ],
                dimension_semantics=("parallel", "arbitrary"),
                num_scalar_prefetch=1,
            )(pr, qr, kr, vr)
            return out.reshape(b, kvh, g, d)

        return launch

    return ctx.jit((block_kv, ring, scale), make)(q, k, v, pos)


def flash_decode_pallas(
    q: jax.Array,    # [B, KV, G, D] grouped single-token queries
    k: jax.Array,    # [B, KV, W, D] cache, head-major
    v: jax.Array,    # [B, KV, W, D]
    pos: jax.Array,  # [B] int32 per-slot positions
    *,
    ring: bool = False,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Raw launcher for the ``flash_attention/decode`` stage."""
    return flash_attention_program(
        q, k, v, pos, stage="decode", ring=ring, scale=scale,
        interpret=interpret,
    )


def flash_attention_pallas(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, H, Skv, D]
    v: jax.Array,  # [B, H, Skv, D]
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel launcher: the ``flash_attention/attend`` stage with
    optional pinned blocks (unset sizes resolve through the planner)."""
    blocks = {n: s for n, s in (("bq", block_q), ("bkv", block_kv)) if s is not None}
    return flash_attention_program(
        q, k, v, causal=causal, window=window, scale=scale,
        blocks=blocks or None, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# trainable flash attention: Pallas forward + recompute backward
# ---------------------------------------------------------------------------


def _ref_attention(q, k, v, causal, window, scale):
    from repro.kernels.ref import attention_ref

    return attention_ref(q, k, v, causal=causal, window=window, scale=scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_trainable(
    q, k, v, causal: bool = False, window=None, scale=None, interpret: bool = True
):
    """Differentiable flash attention: the ``flash_attention`` program
    runs the forward (VMEM-resident logits); the backward recomputes
    attention (flash-style — only q/k/v are saved, O(S²) logits never
    hit HBM in fwd). Grad-checked against the jnp oracle in tests."""
    return flash_attention_program(
        q, k, v, causal=causal, window=window, scale=scale, interpret=interpret
    )


def _fat_fwd(q, k, v, causal, window, scale, interpret):
    out = flash_attention_program(
        q, k, v, causal=causal, window=window, scale=scale, interpret=interpret
    )
    return out, (q, k, v)


def _fat_bwd(causal, window, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref_attention(q_, k_, v_, causal, window, scale), q, k, v)
    return vjp(g)


flash_attention_trainable.defvjp(_fat_fwd, _fat_bwd)
