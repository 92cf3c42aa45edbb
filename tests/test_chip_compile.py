"""The serving path's kernels compile for a TPU v5e chip, with no chip
attached: ahead-of-time compiles against a described ``v5e:2x2``
topology at qwen3-4b's and qwen3-moe-235b-a22b's published widths
(bf16), ``interpret=False`` and
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


@pytest.mark.parametrize("stored,layer,transposed", [
    ((36, D, FF), 3, False),          # layer offset: MLP gate/up projection
    ((36, FF, D), 35, False),         # layer offset: MLP down projection
    ((36, D, H, HD), 1, False),       # head split: q projection
    ((36, D, KV, HD), 2, False),      # head split: k/v projections
    ((64, 2560, 80), 5, False),       # mamba2's dt projection: stored K-minor
    ((V, D), None, True),             # transposed: the tied head
])
def test_matmul_tile_reads_weight_views(one_chip, as_chip, stored, layer, transposed):
    """The kernel reads each form of weight view at 16 rows (the decode
    step's batch) from the stored array itself: one kernel, no copy."""
    from repro.kernels import programs
    from repro.kernels.matmul import WeightView

    def view(w):
        if transposed:
            return WeightView(w, transposed=True)
        return WeightView(w, layer) if len(stored) == 4 else WeightView.of_layer(w, layer)

    w = _sds(stored, jnp.bfloat16, one_chip)
    a = _sds((16, stored[-1] if transposed else stored[1]), jnp.bfloat16, one_chip)
    with scope(Scope.DEVICE):
        compiled = jax.jit(lambda a, w: programs.matmul(a, view(w))).lower(a, w).compile()
    text = compiled.as_text()
    assert _kernels(compiled) == 1
    assert " copy(" not in text and "slice" not in text and "transpose" not in text


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


E_HELD, D_MOE, F_MOE = 8, 4096, 1536   # qwen3-moe-235b-a22b: one chip's 8 of 128 experts


@pytest.mark.parametrize("rows", [64, 256])     # decode step's slots, largest prefill bucket
@pytest.mark.parametrize("k,n", [(D_MOE, F_MOE), (F_MOE, D_MOE)])
def test_moe_gemm_reads_a_layer_of_the_expert_stack(one_chip, as_chip, rows, k, n):
    """The expert kernel reads one layer of the stacked expert weight
    ``[L, E, k, n]`` where it lies: one kernel, no slice or copy."""
    from repro.kernels import programs
    from repro.kernels.moe_gemm import ExpertView

    x = _sds((E_HELD, rows, k), jnp.bfloat16, one_chip)
    w = _sds((16, E_HELD, k, n), jnp.bfloat16, one_chip)
    with scope(Scope.DEVICE):
        compiled = jax.jit(
            lambda x, w: programs.moe_gemm(x, ExpertView(w, 3))).lower(x, w).compile()
    text = compiled.as_text()
    assert _kernels(compiled) == 1 and "moe_gemm_expert_gemm" in text
    assert " copy(" not in text and "slice" not in text


def test_moe_serving_decode_step_reads_experts_in_place(one_chip, as_chip):
    """A two-layer cut of qwen3-moe-235b-a22b's serving step at published
    widths, 8 experts held of 128, 64 rows: every expert product is the
    Pallas kernel reading the stacked leaf, and no weight is copied."""
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.serve.engine import ServeEngine

    cfg = dataclasses.replace(get_config("qwen3-moe-235b-a22b"), num_layers=2,
                              experts_held=E_HELD)
    api = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: api.cache_init(64, MAX_SEQ)))
    tok = _sds((64,), jnp.int32, one_chip)
    eng = ServeEngine(api, batch_size=64, max_seq=MAX_SEQ)
    compiled = eng.decode_fn().lower(params, cache, tok, tok).compile()
    text = compiled.as_text()
    experts = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln and "moe_gemm_expert_gemm" in ln]
    assert len(experts) == 3 * 2
    assert all("params__blocks____l0____moe____w" in ln for ln in experts)
    report = eng.bind_report
    assert report.copied == 0 and report.in_place == 2 * (4 + 3) + 1
