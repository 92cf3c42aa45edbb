"""Float32 building blocks of the plain references, and the control's
lower precision. Every product runs at ``Precision.HIGHEST``: on a TPU
a float32 product otherwise runs in bfloat16 passes.

The control (``fp8=True``) rounds both operands of every product to
float8 e4m3 (4 exponent bits, 3 mantissa bits), each row or column
scaled by its largest magnitude, and accumulates in float32: the
reference computed one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: largest finite value of a 4-exponent-bit, 3-mantissa-bit float
E4M3_MAX = 240.0


def fp8(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded to e4m3, scaled per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def einsum(spec: str, a: jax.Array, b: jax.Array, *, fp8_axes=None) -> jax.Array:
    """``jnp.einsum`` in float32 at HIGHEST; with ``fp8_axes=(i, j)``
    the operands are first rounded to e4m3 along their contracted axes
    ``i`` of ``a`` and ``j`` of ``b``."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8_axes is not None:
        a, b = fp8(a, fp8_axes[0]), fp8(b, fp8_axes[1])
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def mm(x: jax.Array, w: jax.Array, use_fp8: bool = False) -> jax.Array:
    """``x [..., K] @ w [K, N]``."""
    return einsum("...k,kn->...n", x, w,
                  fp8_axes=(x.ndim - 1, 0) if use_fp8 else None)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def normal(key, shape, scale: float, dtype) -> jax.Array:
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


class Frozen(dict):
    """A hashable dict of sizes, to pass as a static jit argument."""

    def __init__(self, sizes: dict):
        super().__init__(sizes)
        self._key = tuple(sorted(sizes.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Frozen) and self._key == other._key
