"""The expert-share cell at a small size on the CPU: the plain reference
against the program (prefill, then the compiled decode step through the
cache), the shares of a small deployment adding up to the uncut layer,
routing with no drops however uneven, and the comparison that decides
``correct`` catching the float8 control and a broken timed path."""
from __future__ import annotations

import copy
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, harness

CELL = "qwen3-moe-235b-a22b.decode-batch-64"


def moe_smoke(held: int = 4, router: int = 16):
    """(doc, mix) of the cell cut to a size the CPU runs in seconds: small
    widths, ``held`` of ``router`` experts, top-4, the structure kept.
    Float32: at this size bfloat16 flips the top-4 of so few experts often
    enough that the program's widest gap reaches the float8 control's
    (0.37-0.44 against 0.38-0.56, on a busy host); in float32 the served
    tokens are the reference's own (gap 0)."""
    cell = cells.find_cell(cells.load_benchmark(), CELL)
    doc = copy.deepcopy(cells.load_config(cell.config))
    mix = copy.deepcopy(cells.load_traffic(cell.traffic))
    doc["config"].update(hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
                         head_dim=64, moe_intermediate_size=128, num_experts=held,
                         num_experts_per_tok=4, vocab_size=4096, num_hidden_layers=2,
                         torch_dtype="float32")
    doc["published"]["num_experts"] = router
    doc["program"]["set"].update(d_model=256, num_heads=4, num_kv_heads=2, head_dim=64,
                                 d_ff=512, expert_d_ff=128, num_experts=router,
                                 experts_per_tok=4, experts_held=held, vocab_size=4096,
                                 num_layers=2, dtype="float32")
    mix.update(slots=4, backlog=4, requests=20000, max_seq=128)
    mix["prompt"]["buckets"] = [16, 32, 64]
    mix["output"].update(min=8, max=40)
    # 120 served tokens: fewer let the float8 control's widest gap fall
    # under the cell's limit at this size
    mix["check"]["min_tokens"] = 120
    return doc, mix


def _program_cfg(doc, **over):
    from repro.configs import get_config

    return dataclasses.replace(get_config(doc["program"]["arch"]),
                               **{**doc["program"]["set"], **over})


def test_configuration_keeps_the_published_widths():
    doc = cells.load_config("qwen3-moe-235b-a22b")
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    cfg = _program_cfg(doc)
    assert (sz["experts"], sz["top_k"], sz["d_expert"], sz["d_model"]) == (128, 8, 1536, 4096)
    assert (cfg.num_experts, cfg.experts_per_tok, cfg.moe_d_ff, cfg.d_model) == \
        (sz["experts"], sz["top_k"], sz["d_expert"], sz["d_model"])
    assert (cfg.moe_experts_held, cfg.expert_offset, cfg.num_layers) == \
        (sz["held"], sz["offset"], sz["layers"]) == (8, 0, 16)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size) == \
        (sz["heads"], sz["kv_heads"], sz["head_dim"], sz["vocab"]) == (64, 4, 128, 151936)
    assert not cfg.tie_embeddings and cfg.rope_theta == sz["rope_theta"] == 1e6
    assert doc["reduced"] == ["num_experts", "num_hidden_layers"]
    assert doc["published"] == {"num_experts": 128, "num_hidden_layers": 94}


def test_reference_matches_prefill_then_compiled_decode():
    """Prefill through the program's model API, then the compiled decode
    step through the cache, against the reference's full forward, on
    the weights ``init_params`` makes (float32, a 4-of-16 share)."""
    from repro.models.model_zoo import build_model
    from repro.serve.engine import ServeEngine

    doc, _ = moe_smoke()
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    params = ref.init_params(sz, jax.random.PRNGKey(3), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (40,), 0, sz["vocab"])
    want = np.asarray(ref.forward(sz, params, tokens))

    api = build_model(_program_cfg(doc))
    engine = ServeEngine(api, batch_size=1, max_seq=64)
    engine.load(params)
    s0 = 32
    with jax.default_matmul_precision("highest"):
        logits, cache = engine.prefill({"tokens": tokens[None, :s0]},
                                       api.cache_init(1, 64))
        got = [np.asarray(logits[0, -1])]
        for pos in range(s0, len(tokens) - 1):
            step, cache = engine.decode_step(tokens[pos:pos + 1], cache,
                                             jnp.asarray([pos], jnp.int32))
            got.append(np.asarray(step[0]))
    np.testing.assert_allclose(np.stack(got), want[s0 - 1:len(tokens) - 1],
                               rtol=2e-4, atol=2e-4)
    assert engine.bind_report.copied == 0 and engine.bind_report.in_place > 0


def test_shares_add_up_to_the_uncut_layer():
    """Over all 16 shares of a small deployment (32 experts, 2 held per
    device, top-4), the program's expert layer at each share adds up to
    the uncut reference layer, and so do the reference's own shares."""
    from repro.models import moe as moe_mod

    router, held = 32, 2
    doc, _ = moe_smoke(held=router, router=router)
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    layer = jax.tree.map(lambda a: a[0], ref.init_params(sz, jax.random.PRNGKey(7),
                                                         jnp.float32)["blocks"]["l0"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 24, sz["d_model"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(sz, layer, h.reshape(-1, sz["d_model"]))
        prog = ref_parts = 0.0
        for rank in range(router // held):
            part = jax.tree.map(lambda a: a[rank * held:(rank + 1) * held], layer)
            part["router"] = layer["router"]
            cfg = _program_cfg(doc, experts_held=held, expert_rank=rank)
            prog = prog + moe_mod.moe_apply(part, h, cfg).reshape(whole.shape)
            ref_parts = ref_parts + ref.moe({**sz, "held": held, "offset": rank * held},
                                            part, h.reshape(whole.shape))
    np.testing.assert_allclose(np.asarray(prog), np.asarray(whole), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref_parts), np.asarray(whole), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows", [8, 64])
def test_one_expert_taking_every_token_drops_none(rows):
    """A router that sends every token to held expert 1: the layer, and
    the routing the compiled dispatch runs, keep all ``rows`` tokens
    (a capacity of rows·k·1.25/E would have kept a few)."""
    from repro.models import moe as moe_mod

    doc, _ = moe_smoke()
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    layer = jax.tree.map(lambda a: a[0], ref.init_params(sz, jax.random.PRNGKey(9),
                                                         jnp.float32)["blocks"]["l0"]["moe"])
    layer["router"] = layer["router"].at[:, 1].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (1, rows, sz["d_model"]))) + 0.5
    cfg = _program_cfg(doc)
    r = moe_mod.route(x[0], layer["router"], experts_per_tok=cfg.experts_per_tok,
                      held=cfg.moe_experts_held)
    assert bool(r.routed[:, 1].all())
    assert moe_mod.load(r.routed)[0] >= rows
    with jax.default_matmul_precision("highest"):
        got = moe_mod.moe_apply(layer, x, cfg)[0]
        want = ref.moe(sz, layer, x[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_control_is_float8_and_departs():
    doc, _ = moe_smoke()
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    params = ref.init_params(sz, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (48,), 0, sz["vocab"])
    hi = np.asarray(ref.forward(sz, params, tokens))
    lo = np.asarray(ref.forward(sz, params, tokens, fp8=True))
    err = np.abs(lo - hi).max() / np.abs(hi).max()
    assert 1e-3 < err < 0.5


def _run(tmp_path, **kw):
    doc, mix = moe_smoke()
    # a 5 s window finishes enough requests for the sample, on a busy host too
    return harness.run_cell(CELL, 2**31 + 99, 5.0, False, t_start=time.perf_counter(),
                            cache_dir=str(tmp_path), require_chip=False, doc=doc, mix=mix,
                            limits=cells.load_limits(CELL), **kw)


def test_sound_run_is_correct_and_its_control_is_not(tmp_path):
    line = _run(tmp_path, control=True)
    c = line["compared"]
    assert line["correct"], c
    assert line["metrics"]["out_tok_s"]["value"] > 0
    assert not line["control_correct"], c


def test_altered_token_is_caught(tmp_path, monkeypatch):
    from repro.serve.batcher import ContinuousBatcher

    sample = ContinuousBatcher._sample_batch

    def altered(self, uids, pos, logits):
        return (sample(self, uids, pos, logits) + 1) % logits.shape[-1]

    monkeypatch.setattr(ContinuousBatcher, "_sample_batch", altered)
    assert not _run(tmp_path)["correct"]


def test_step_that_returns_its_state_unchanged_is_caught(tmp_path, monkeypatch):
    from repro.serve.engine import ServeEngine

    step = ServeEngine.decode_step

    def stale(self, tok, cache, pos):
        logits, _ = step(self, tok, jax.tree.map(lambda a: a.copy(), cache), pos)
        return logits, cache

    monkeypatch.setattr(ServeEngine, "decode_step", stale)
    assert not _run(tmp_path)["correct"]
