"""Serving launcher: load (or init) a checkpoint and serve batched
generation requests.

    python -m repro.launch.serve --arch qwen3-4b --smoke --batch 4 \
        --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCH_IDS, get_config, smoke_variant
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import build_model
from repro.serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        restored = mgr.restore_latest(params)
        if restored is not None:
            params = restored
            print(f"loaded checkpoint step {mgr.latest_step()}")

    engine = ServeEngine(api, batch_size=args.batch, max_seq=args.max_seq,
                         temperature=args.temperature)
    engine.load(params)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size, jnp.int32,
    )
    extra = {}
    if cfg.family == "vlm":
        extra["patches"] = jnp.ones((args.batch, cfg.num_patches, 1024), cfg.dtype)
    if cfg.family == "encdec":
        extra["frames"] = jnp.ones((args.batch, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    t0 = time.perf_counter()
    out = engine.generate(prompts, args.new_tokens, extra_inputs=extra or None)
    dt = time.perf_counter() - t0
    print(f"{args.batch}x{args.new_tokens} tokens in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print(out[:, :12])


if __name__ == "__main__":
    main()
