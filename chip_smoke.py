#!/usr/bin/env python3
"""Chip smoke test: serve qwen3-4b at its full published widths (bf16,
36 layers, random weights from ``--seed``) through the normal serving
entry points — ``ServeEngine.load`` then ``ServeEngine.generate``: a
prefill, then the compiled decode executable with its Pallas kernels.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip phase only

One chip: answers a batch of requests and checks that the tokens are in
range, that the logits are finite, that the compiled decode step agrees
with the XLA reference ``api.decode_step`` on the same params and cache
within a bf16 tolerance, and that the decode step's compiled HLO holds
the flash-decode kernel. ``--chips 4`` serves the same model on a
(data 1, model 4) mesh under its solved placement, compares its
prefill and its first decode step (from the one-chip prefill's token and
cache) with the one-chip results of the same seed, and runs
the compiled forward with the overlap schedule against the synchronous
one.

Every number printed before the last line comes from this run on the
chip. The last line is the JSON result. With no TPU the script exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "qwen3-4b"
#: bf16 tolerance on logits, |got - want| <= ATOL + RTOL * |want|: the
#: compiled step's kernels and the reference round bf16 activations in
#: different places over 36 layers
RTOL, ATOL = 0.1, 0.25


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip run] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"check passed: {what}")


def _timed(fn, *args, **kw):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def _init(api, seed: int):
    import jax

    params, dt = _timed(jax.jit(api.init), jax.random.PRNGKey(seed))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"params: {nbytes} bytes ({nbytes / 2**30:.3f} GiB), init {dt:.3f} s")
    return params


def _prompts(cfg, args):
    import jax
    import jax.numpy as jnp

    return jax.random.randint(
        jax.random.PRNGKey(args.seed + 1), (args.batch, args.prompt_len),
        0, cfg.vocab_size, jnp.int32,
    )


def _prefill(engine, prompts):
    """The engine's prefill (the legacy XLA path ``generate`` runs).
    Returns ``(last-position logits as f32 numpy, cache)``."""
    import jax
    import numpy as np

    cache = engine.api.cache_init(prompts.shape[0], engine.max_seq)
    if engine.mesh is not None:
        cache = engine._place_cache(cache)
    logits, cache = jax.jit(engine.api.prefill)(
        engine.params, {"tokens": prompts}, cache)
    return np.asarray(logits[:, -1], np.float32), cache


def _first_step(engine, tok, cache, pos: int):
    """One compiled decode step and the XLA reference
    ``api.decode_step`` on the same params, token and cache (which the
    compiled step donates). Returns ``(compiled, reference)`` logits as
    f32 numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    want, _ = jax.jit(engine.api.decode_step)(engine.params, tok[:, None],
                                              cache, jnp.int32(pos))
    want = np.asarray(want[:, 0], np.float32)  # read before the cache is donated
    got, _ = engine.decode_step(tok, cache, jnp.full(tok.shape, pos, jnp.int32))
    return np.asarray(got, np.float32), want


def _compare(name: str, got, want) -> None:
    import numpy as np

    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          f"{name}: logits finite")
    diff = np.abs(got - want)
    excess = float(np.max(diff - (ATOL + RTOL * np.abs(want))))
    log(f"{name}: max |logit diff| {float(diff.max())} "
        f"(tolerance {ATOL} + {RTOL}*|want|, max|want| {float(np.abs(want).max())})")
    check(excess <= 0.0, f"{name}: logits within bf16 tolerance")


def one_chip(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.roofline import device_peaks
    from repro.models.model_zoo import build_model
    from repro.serve.engine import ServeEngine

    dev = jax.devices()[0]
    peaks = device_peaks(dev.device_kind)
    cfg = get_config(ARCH)
    api = build_model(cfg)
    params = _init(api, args.seed)
    engine = ServeEngine(api, batch_size=args.batch, max_seq=args.max_seq)
    engine.load(params)
    prompts = _prompts(cfg, args)

    t0 = time.perf_counter()
    step = engine.decode_fn()
    log(f"solve (decode graph, {cfg.num_layers} layers): "
        f"{time.perf_counter() - t0:.3f} s")
    cache = api.cache_init(args.batch, args.max_seq)
    tok = jnp.zeros((args.batch,), jnp.int32)
    t0 = time.perf_counter()
    compiled = step.lower(engine.params, cache, tok, tok).compile()
    log(f"compile (decode step): {time.perf_counter() - t0:.3f} s")
    mem = compiled.memory_analysis()
    log(f"decode step memory analysis: {mem.argument_size_in_bytes} argument, "
        f"{mem.temp_size_in_bytes} temporary bytes")
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    n_decode = sum("flash_attention_decode" in line for line in calls)
    log(f"decode step HLO: {len(calls)} tpu_custom_call kernels, "
        f"{n_decode} of them flash_attention_decode")
    check(n_decode == cfg.num_layers,
          "flash-decode kernel in every layer of the compiled decode step")
    del cache

    t0 = time.perf_counter()
    last, cache = _prefill(engine, prompts)
    tok = jnp.asarray(last.argmax(-1), jnp.int32)
    got, want = _first_step(engine, tok, cache, args.prompt_len)
    log(f"first step (prefill + reference + compiled step, first call): "
        f"{time.perf_counter() - t0:.3f} s")
    _compare("compiled decode step vs api.decode_step", got, want)

    # warm: every shape below is compiled now
    _, t_prefill = _timed(engine.generate, prompts, 1)
    out, t_gen = _timed(engine.generate, prompts, args.new_tokens)
    steps = args.new_tokens - 1
    t_step = (t_gen - t_prefill) / steps
    log(f"generate: batch {args.batch}, prompt {args.prompt_len}, "
        f"{args.new_tokens} new tokens in {t_gen:.4f} s "
        f"({args.batch * args.new_tokens / t_gen:.2f} tok/s)")
    log(f"prefill (generate with 1 new token): {t_prefill:.4f} s")
    log(f"decode: {steps} steps, {t_step * 1e3:.3f} ms/step")
    read = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    bound = read / peaks.hbm_bw
    log(f"decode step HBM bound from the peak table ({dev.device_kind}, "
        f"{peaks.hbm_bw:.3e} B/s, weights only): {bound * 1e3:.3f} ms "
        f"-> {bound / t_step:.3f} of it reached")
    out = np.asarray(out)
    check(out.shape == (args.batch, args.new_tokens), f"output shape {out.shape}")
    check(bool(((out >= 0) & (out < cfg.vocab_size)).all()), "tokens in range")


def four_chip(args) -> None:
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat
    from repro.axe.compile import model_executable, model_inputs
    from repro.axe.graphs import decode_graph
    from repro.axe.solve import solve
    from repro.axe.spec import PhysicalSpace
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.serve.engine import ServeEngine

    devices = jax.devices()
    check(len(devices) == 4, f"{len(devices)} devices visible, 4 needed")
    mesh = compat.make_mesh((1, 4), ("data", "model"))
    ids = sorted(d.id for d in mesh.devices.flat)
    log(f"mesh {dict(mesh.shape)} over device ids {ids}")
    check(len(set(ids)) == 4, "mesh spans four distinct devices")

    cfg = get_config(ARCH)
    api = build_model(cfg)
    params = _init(api, args.seed)
    prompts = _prompts(cfg, args)

    # both placements take their first step from the one-chip prefill's
    # token and cache: a near-tie in the greedy token would otherwise
    # turn bf16 noise between the two prefills into different inputs
    one = ServeEngine(api, batch_size=args.batch, max_seq=args.max_seq)
    one.load(params)
    t0 = time.perf_counter()
    last1, cache = _prefill(one, prompts)
    tok = jnp.asarray(last1.argmax(-1), jnp.int32)
    cache_host = jax.device_get(cache)
    got1, want1 = _first_step(one, tok, cache, args.prompt_len)
    log(f"one chip first step (solve + compile + run): "
        f"{time.perf_counter() - t0:.3f} s")
    _compare("one chip: compiled vs api.decode_step", got1, want1)
    del one, cache
    gc.collect()

    space = PhysicalSpace.from_mesh_shape(dict(mesh.shape))
    t0 = time.perf_counter()
    plan = solve(decode_graph(cfg, args.batch, args.max_seq, space,
                              dtype=cfg.dtype))
    log(f"solve (decode graph on the mesh): {time.perf_counter() - t0:.3f} s, "
        f"{plan.plan.total_comm_bytes} comm B/dev")
    four = ServeEngine(api, batch_size=args.batch, max_seq=args.max_seq,
                       mesh=mesh, layout_plan=plan)
    four.load(params)
    del params
    gc.collect()
    t0 = time.perf_counter()
    last4, _ = _prefill(four, prompts)
    got4, want4 = _first_step(four, tok, four._place_cache(cache_host),
                              args.prompt_len)
    log(f"four chips prefill + first step (compile + run): "
        f"{time.perf_counter() - t0:.3f} s")
    _compare("four chips: compiled vs api.decode_step", got4, want4)
    _compare("four chips vs one chip (compiled first step)", got4, got1)
    log(f"greedy first tokens agree: {bool((last4.argmax(-1) == last1.argmax(-1)).all())}")
    _compare("four chips vs one chip (prefill, last position)", last4, last1)
    # warm: the first generate's step takes the prefill's cache, whose
    # sharding jit keys apart from the placed cache's equivalent one
    four.generate(prompts, 2)
    _, t_prefill = _timed(four.generate, prompts, 1)
    out, t_gen = _timed(four.generate, prompts, args.new_tokens)
    out = np.asarray(out)
    log(f"four chips generate: {args.new_tokens} new tokens in {t_gen:.4f} s, "
        f"prefill {t_prefill:.4f} s, decode "
        f"{(t_gen - t_prefill) / (args.new_tokens - 1) * 1e3:.3f} ms/step")
    check(bool(((out >= 0) & (out < cfg.vocab_size)).all()), "tokens in range")

    # the overlap executable reuses the sync one's solved plan, so the
    # A/B differs only in how and when collectives issue
    tokens = prompts.reshape(-1)
    logits = {}
    plan = None
    for overlap in (False, True):
        t0 = time.perf_counter()
        exe = model_executable(cfg, mesh, args.batch, args.prompt_len,
                               dtype=cfg.dtype, overlap=overlap, plan=plan)
        plan = exe.solve_result
        hidden = sum(len(r.prefetched) for r in exe.lowering_trace)

        def fwd(p, t, exe=exe):
            return exe.apply(model_inputs(exe.graph, cfg, p), t)

        fn = jax.jit(fwd)
        hlo = fn.lower(four.params, tokens).compile().as_text()
        logits[overlap], dt = _timed(fn, four.params, tokens)
        log(f"forward overlap={overlap}: solve+compile+run "
            f"{time.perf_counter() - t0:.3f} s, run {dt:.4f} s, "
            f"{hidden} prefetched collectives, "
            f"{hlo.count('collective-permute')} collective-permute and "
            f"{hlo.count('all-gather')} all-gather in HLO")
    got, want = (np.asarray(logits[k], np.float32) for k in (True, False))
    log(f"overlap vs sync forward: bit-identical {bool((got == want).all())}")
    _compare("overlap vs sync forward", got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=512)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r})",
              file=sys.stderr)
        return 2
    from repro import tune
    from repro.axe import KernelFallbackWarning

    # schedules come from the planner alone, never from a cache outside
    # the checkout; a kernel that gives way to its XLA body is an error
    tune.use_cache(None)
    warnings.simplefilter("error", KernelFallbackWarning)

    def entries():
        return sum(len(f) for _, _, f in os.walk(cache_dir)) if os.path.isdir(cache_dir) else 0

    before = entries()
    dev = jax.devices()[0]
    log(f"device kind {dev.device_kind!r}, {len(jax.devices())} visible, "
        f"phase for {args.chips} chip(s)")
    try:
        (four_chip if args.chips == 4 else one_chip)(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    for d in jax.devices():
        log(f"peak_bytes_in_use (device {d.id}): "
            f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")
    log(f"compile cache {cache_dir}: {before} files before, {entries()} after")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
