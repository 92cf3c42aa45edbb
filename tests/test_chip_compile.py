"""The serving path's kernels compile for a TPU v5e chip, with no chip
attached: ahead-of-time compiles against a described ``v5e:2x2``
topology at qwen3-4b's published widths (bf16), ``interpret=False`` and
the chip's own schedule choices. Each compile must hold a Pallas kernel
(``tpu_custom_call``) — interpret mode cannot show a block shape or a
scalar the chip's compiler refuses.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and pytest-xdist
workers import every test file."""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.scopes import Scope, scope

D, FF, V = 2560, 9728, 151936          # qwen3-4b widths
H, KV, HD = 32, 8, 128
B, PROMPT, MAX_SEQ = 4, 512, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_chip(monkeypatch):
    """Resolve schedules and the interpret flag as on the chip: the
    backend reads ``tpu`` and planner picks land in a private in-memory
    schedule cache."""
    from repro.tune import cache as tune_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tune_cache, "_default", tune_cache.ScheduleCache(None))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("m,k,n", [
    (PROMPT, D, FF),      # prefill MLP up/gate projection
    (B, D, H * HD),       # decode q projection
    (B, FF, D),           # decode MLP down projection
    (B, D, V),            # decode lm_head
])
def test_matmul_tile_compiles(one_chip, as_chip, m, k, n):
    from repro.kernels import programs

    a = _sds((m, k), jnp.bfloat16, one_chip)
    b = _sds((k, n), jnp.bfloat16, one_chip)
    with scope(Scope.DEVICE):
        compiled = jax.jit(lambda a, b: programs.matmul(a, b)).lower(a, b).compile()
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("rows", [PROMPT, B])
def test_rmsnorm_compiles(one_chip, as_chip, rows):
    from repro.kernels import programs

    x = _sds((rows, D), jnp.bfloat16, one_chip)
    w = _sds((D,), jnp.bfloat16, one_chip)
    with scope(Scope.DEVICE):
        compiled = jax.jit(lambda x, w: programs.rmsnorm(x, w)).lower(x, w).compile()
    assert _kernels(compiled) == 1


def test_flash_attention_prefill_compiles(one_chip, as_chip):
    from repro.kernels import programs

    q = _sds((1, H, PROMPT, HD), jnp.bfloat16, one_chip)
    with scope(Scope.DEVICE):
        compiled = jax.jit(
            lambda q, k, v: programs.flash_attention(q, k, v, causal=True)
        ).lower(q, q, q).compile()
    assert _kernels(compiled) == 1


def test_flash_decode_compiles(one_chip, as_chip):
    """The decode kernel takes per-slot positions as SMEM scalar
    prefetch; a (1, 1) VMEM block of them is refused by the compiler."""
    from repro.kernels import programs

    q = _sds((B, KV, H // KV, HD), jnp.bfloat16, one_chip)
    kv = _sds((B, KV, MAX_SEQ, HD), jnp.bfloat16, one_chip)
    pos = _sds((B,), jnp.int32, one_chip)
    with scope(Scope.DEVICE):
        compiled = jax.jit(programs.flash_decode).lower(q, kv, kv, pos).compile()
    assert _kernels(compiled) == 1


def test_serving_decode_step_compiles(one_chip, as_chip):
    """A two-layer cut of qwen3-4b's full-width serving step — binding,
    compiled graph and cache restack in one jit — holds only kernels for
    its matmuls, norms and attention."""
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.serve.engine import ServeEngine

    cfg = dataclasses.replace(get_config("qwen3-4b"), num_layers=2)
    api = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: api.cache_init(B, MAX_SEQ)))
    tok = _sds((B,), jnp.int32, one_chip)
    eng = ServeEngine(api, batch_size=B, max_seq=MAX_SEQ)
    compiled = eng.decode_fn().lower(params, cache, tok, tok).compile()
    text = compiled.as_text()
    # per layer: 7 matmuls, 2 norms, 1 flash decode; plus final norm + lm_head
    assert _kernels(compiled) == 2 * 10 + 2
    assert "flash_attention_decode" in text
