"""Serving benchmark: a synthetic many-user request stream through the
continuous batcher (``repro.serve.ContinuousBatcher``) over the
compiled decode executable, reporting decode tokens/s and per-request
p50/p99 completion latency (in scheduler steps) per model family,
merged into ``BENCH_serve.json`` for the nightly regression gate
(``benchmarks/check_regression.py``).

The stream is deterministic (seeded prompt lengths / arrival gaps), so
runs are comparable across commits; latency is measured in decode
steps, not wall-clock, keeping the gate host-independent — the wall
metric is the ``us`` column (median decode-step time), from which
tokens/s derives.

Usage:
    python benchmarks/bench_serve.py [--slots 4] [--max-seq 64]
        [--requests 12] [--new-tokens 8] [--archs qwen3-4b,...]
"""
from __future__ import annotations

import argparse
import dataclasses
import pathlib
import sys
import time

if __package__ in (None, ""):  # script mode: make `benchmarks.*` importable
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import row, write_bench_json
from repro.launch.compile_cache import enable_compile_cache

BENCH_SERVE_JSON = "BENCH_serve.json"

ARCHS = ("qwen3-4b", "mamba2-2.7b")


def synth_requests(n: int, max_seq: int, new_tokens: int, vocab: int,
                   seed: int = 0) -> list:
    """A deterministic arrival trace: prompt lengths 3..max_prompt,
    arrivals in bursts (0-2 step gaps) — enough churn that slots join
    and leave mid-stream."""
    from repro.serve import Request

    rng = np.random.RandomState(seed)
    max_prompt = max(4, min(max_seq - new_tokens - 1, 12))
    reqs, arrival = [], 0
    for uid in range(1, n + 1):
        s = int(rng.randint(3, max_prompt + 1))
        reqs.append(Request(
            uid=uid,
            prompt=rng.randint(0, vocab, size=s).astype(np.int32),
            max_new_tokens=new_tokens,
            arrival=arrival,
        ))
        arrival += int(rng.randint(0, 3))
    return reqs


def run(slots: int, max_seq: int, n_requests: int, new_tokens: int,
        archs) -> list:
    import jax

    from repro.configs import get_config, smoke_variant
    from repro.models.model_zoo import build_model
    from repro.serve import ContinuousBatcher, ServeEngine

    rows = []
    for arch in archs:
        cfg = smoke_variant(get_config(arch))
        if cfg.is_moe:
            cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        engine = ServeEngine(api=api, batch_size=slots, max_seq=max_seq)
        engine.load(params)
        reqs = synth_requests(n_requests, max_seq, new_tokens, cfg.vocab_size)

        # warmup: compile prefill + the decode executable
        warm = ContinuousBatcher(engine)
        warm.run([dataclasses.replace(reqs[0], uid=10_000)])

        bat = ContinuousBatcher(engine)
        t0 = time.perf_counter()
        results = bat.run(reqs)
        wall_s = time.perf_counter() - t0

        assert len(results) == n_requests
        total_tokens = sum(len(r.tokens) for r in results.values())
        steps = bat.step_count
        us_per_step = wall_s / max(steps, 1) * 1e6
        tok_s = total_tokens / wall_s
        lat = np.sort(np.asarray(
            [r.finished - r.submitted for r in results.values()], np.float64
        ))
        p50 = float(np.percentile(lat, 50))
        p99 = float(np.percentile(lat, 99))
        rows.append(row(
            f"serve.stream.{arch}", us_per_step,
            f"tokens/s={tok_s:.0f} total_tokens={total_tokens} "
            f"steps={steps} p50_steps={p50:.1f} p99_steps={p99:.1f} "
            f"slots={slots} requests={n_requests}",
        ))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--archs", type=str, default=",".join(ARCHS))
    args = ap.parse_args()
    enable_compile_cache()
    rows = run(args.slots, args.max_seq, args.requests, args.new_tokens,
               tuple(a for a in args.archs.split(",") if a))
    path = write_bench_json("serve", rows, filename=BENCH_SERVE_JSON)
    for r in rows:
        print(r)
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
