"""Per-kernel correctness sweeps: the axe.program Pallas path
(interpret mode) vs the jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import programs, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    # f32 tolerance admits K-split accumulation-order differences
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [
        (256, 512, 256, 128, 128, 256),
        (128, 128, 128, 128, 128, 128),
        (512, 256, 384, 256, 128, 128),
        (256, 1024, 128, 128, 128, 512),
    ],
)
def test_matmul_matches_ref(dtype, m, k, n, bm, bn, bk):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a, b = _rand(k1, (m, k), dtype), _rand(k2, (k, n), dtype)
    got = programs.matmul(a, b, stage="tile", impl="kernel",
                          blocks={"bm": bm, "bn": bn, "bk": bk})
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "stored,layer,form",
    [
        # N = 384 and 640 are multiples of 128 that the default bn (256)
        # does not divide, like qwen3's vocabulary (151,936 = 1,187 · 128)
        ((3, 256, 384), 2, "layer"),        # layer offset
        ((3, 256, 80), 1, "layer"),         # narrower than a lane: the stack transposed
        ((2, 256, 32, 128), 1, "heads"),    # head split, query heads
        ((3, 256, 8, 128), 2, "heads"),     # head split, GQA kv heads
        ((640, 256), None, "transposed"),   # a tied head: embed^T
    ],
)
def test_matmul_reads_weight_views(dtype, stored, layer, form):
    """The kernel reads each form of weight view where it lies, at the
    decode step's 16 rows, and matches the XLA dot on the sliced weight."""
    from repro.kernels.matmul import WeightView

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    w = _rand(k2, stored, dtype)
    view = {"layer": lambda: WeightView.of_layer(w, layer),
            "heads": lambda: WeightView(w, layer),
            "transposed": lambda: WeightView(w, transposed=True)}[form]()
    a = _rand(k1, (16, view.shape[0]), dtype)
    got = jax.jit(lambda a, v: programs.matmul(a, v, stage="tile", impl="kernel"))(a, view)
    want = ref.matmul_ref(a, view.materialize())
    assert got.shape == (16, view.shape[1])
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


def _pallas_grids(fn, *args):
    """The grid of every Pallas launch ``fn`` traces."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield tuple(eqn.params["grid_mapping"].grid)
            for p in eqn.params.values():
                if hasattr(p, "jaxpr") and hasattr(p.jaxpr, "eqns"):
                    yield from walk(p.jaxpr)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize(
    "stored,layer,form,bk,grid",
    [
        # bk = K: one grid step per column block
        ((3, 1024, 384), 1, "layer", 1024, (1, 3, 1)),
        ((640, 1024), None, "transposed", 1024, (1, 5, 1)),     # a tied head
        # 16 whole heads widen bn 128 → 2,048; beside them a 2,048-deep
        # tile would need 17 MB of VMEM, so the kernel halves bk
        ((2, 2048, 16, 128), 1, "heads", 2048, (1, 1, 2)),
    ],
)
def test_matmul_reads_views_in_whole_k_blocks(stored, layer, form, bk, grid):
    """Large K blocks on each form of weight view give the exact product
    of the materialised weight (small integers: every sum is exact)."""
    from repro.kernels.matmul import WeightView

    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    w = jax.random.randint(k2, stored, -2, 3).astype(jnp.bfloat16)
    view = {"layer": lambda: WeightView.of_layer(w, layer),
            "heads": lambda: WeightView(w, layer),
            "transposed": lambda: WeightView(w, transposed=True)}[form]()
    a = jax.random.randint(k1, (16, view.shape[0]), -2, 3).astype(jnp.bfloat16)

    def product(a, v):
        return programs.matmul(a, v, stage="tile", impl="kernel", out_dtype=jnp.float32,
                               blocks={"bm": 16, "bn": 128, "bk": bk})

    assert _pallas_grids(product, a, view) == [grid]
    got = jax.jit(product)(a, view)
    want = jnp.dot(a, view.materialize(), preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "b,h,sq,skv,d",
    [(1, 2, 256, 256, 64), (2, 1, 128, 384, 128)],
)
def test_flash_attention_matches_ref(dtype, causal, b, h, sq, skv, d):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (b, h, sq, d), dtype)
    k = _rand(ks[1], (b, h, skv, d), dtype)
    v = _rand(ks[2], (b, h, skv, d), dtype)
    got = programs.flash_attention(q, k, v, causal=causal,
                                   blocks={"bq": 128, "bkv": 128})
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


def test_flash_attention_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (1, 2, 256, 64), jnp.float32)
    k = _rand(ks[1], (1, 2, 256, 64), jnp.float32)
    v = _rand(ks[2], (1, 2, 256, 64), jnp.float32)
    got = programs.flash_attention(q, k, v, causal=True, window=64)
    want = ref.attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_alignment():
    # queries right-aligned: 128 new tokens against a 384-token KV cache
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (1, 1, 128, 64), jnp.float32)
    k = _rand(ks[1], (1, 1, 384, 64), jnp.float32)
    v = _rand(ks[2], (1, 1, 384, 64), jnp.float32)
    got = programs.flash_attention(q, k, v, causal=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# moe grouped gemm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "e,c,d,f",
    [(4, 128, 256, 512), (8, 256, 512, 256), (2, 128, 1024, 128)],
)
def test_moe_gemm_matches_ref(dtype, e, c, d, f):
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    x = _rand(k1, (e, c, d), dtype)
    w = _rand(k2, (e, d, f), dtype)
    got = programs.moe_gemm(x, w, stage="expert_gemm", impl="kernel")
    want = ref.moe_gemm_ref(x, w)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 96, 512), (1000, 256), (3, 128)])
def test_rmsnorm_matches_ref(dtype, shape):
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    x = _rand(k1, shape, dtype)
    w = _rand(k2, shape[-1:], dtype)
    got = programs.rmsnorm(x, w, stage="rows", impl="kernel")
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


# ---------------------------------------------------------------------------
# scope-dispatched matmul (the program dispatch table)
# ---------------------------------------------------------------------------

def test_program_matmul_scope_dispatch():
    from repro.core.scopes import Scope, scope

    k1, k2 = jax.random.split(jax.random.PRNGKey(6))
    a, b = _rand(k1, (256, 256), jnp.float32), _rand(k2, (256, 256), jnp.float32)
    want = ref.matmul_ref(a, b)
    with scope(Scope.DEVICE):  # DEVICE -> the Pallas tile stage
        got = programs.matmul(a, b, blocks={"bm": 128, "bn": 128, "bk": 128})
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    got_mesh = programs.matmul(a, b)  # MESH scope -> the dot stage (XLA)
    np.testing.assert_allclose(got_mesh, want, rtol=2e-5, atol=2e-5)
    with scope(Scope.BLOCK):  # BLOCK scope -> functional dot on tiles
        got_blk = programs.matmul(a, b)
    np.testing.assert_allclose(got_blk, want, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# trainable flash attention (custom_vjp)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_trainable_grads(causal):
    from repro.kernels.flash_attention import flash_attention_trainable

    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (1, 2, 128, 64), jnp.float32)
    k = _rand(ks[1], (1, 2, 128, 64), jnp.float32)
    v = _rand(ks[2], (1, 2, 128, 64), jnp.float32)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention_trainable(q, k, v, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(ref.attention_ref(q, k, v, causal=causal) ** 2)

    gq, gk, gv = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in ((gq, rq), (gk, rk), (gv, rv)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)
