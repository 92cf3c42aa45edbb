"""Paper Fig. 10 — GEMM throughput across real-model weight shapes.

On this CPU container we time the XLA schedule (the MESH-scope dispatch
of the ``matmul`` program) at reduced batch and validate the Pallas
kernel (the DEVICE-scope ``matmul/tile`` stage) in interpret mode; the
derived column reports achieved GFLOP/s and the Axe-verified MXU tiling
the kernel would use on TPU. Weight shapes follow the paper's eval set
(Qwen3 / LLaMA-3.1 / Gemma-2), scaled 1/4 in each dim to keep CPU
wall-time sane.

Modes (``python benchmarks/bench_gemm.py [--default | --tuned | --program]``):

  --default  time the fixed default dispatch only
  --tuned    additionally run the autotuner per shape (populating the
             on-disk schedule cache at ``repro.tune.default_cache_path()``
             or ``$REPRO_TUNE_CACHE``) and report tuned vs default µs
  --program  benchmark the axe.program DSL path against the legacy
             deprecated-shim path (same schedules) and write the
             ``BENCH_kernels.json`` perf baseline
"""
from __future__ import annotations

import pathlib
import sys

if __package__ in (None, ""):  # script mode: make `benchmarks.*` importable
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from benchmarks.common import row, time_jitted, write_bench_json
from repro.core.blockspec import derive_tiling, pick_tile
from repro.kernels import programs, ref as kref
from repro.launch.compile_cache import enable_compile_cache

# (name, M(batch), K, N) — paper weight shapes / 4
SHAPES = [
    ("qwen3-8b.qkv", 2048, 1024, 1536),
    ("qwen3-32b.mlp", 2048, 1280, 5440),
    ("llama3-8b.mlp", 2048, 1024, 3584),
    ("gemma2-9b.mlp", 2048, 896, 3584),
    ("gpt3-175b.attn", 2048, 3072, 3072),
]

#: interpret-mode kernel comparison shape for --program (small enough
#: that the Python-interpreted Pallas body is not the whole budget)
PROGRAM_SHAPE = (256, 512, 256)
PROGRAM_BLOCKS = dict(bm=128, bn=128, bk=256)


def run(mode: str = "default") -> list:
    from repro import tune

    tuned = mode == "tuned"
    rows = []
    key = jax.random.PRNGKey(0)
    for name, m, k, n in SHAPES:
        k1, k2 = jax.random.split(jax.random.fold_in(key, hash(name) % 2**31))
        a = jax.random.normal(k1, (m, k), jnp.float32)
        b = jax.random.normal(k2, (k, n), jnp.float32)
        fn = jax.jit(lambda a, b: programs.matmul(a, b))
        us = time_jitted(fn, a, b)
        gflops = 2 * m * k * n / (us * 1e-6) / 1e9
        tile = pick_tile((m, n), jnp.bfloat16)
        d = derive_tiling((m, n), tile, jnp.bfloat16)
        rows.append(row(f"gemm.{name}", us,
                        f"{gflops:.1f}GFLOP/s xla; tpu_tile={tile} mxu={d.mxu_aligned}"))
        if tuned:
            rep = tune.autotune_matmul(a, b)
            # delta against the default (XLA) candidate measured in the
            # same autotune loop — back-to-back, so not timing noise
            meas = dict(rep.measurements)
            base = meas.get("xla")
            if rep.cached or base is None:
                derived = f"sched={rep.schedule.describe()} cached={rep.cached}"
            else:
                delta = (base - rep.us) / base * 100.0
                derived = (f"sched={rep.schedule.describe()} "
                           f"default={base:.1f}us delta={delta:+.1f}%")
            rows.append(row(f"gemm.{name}.tuned", rep.us, derived))
    # kernel-vs-oracle validation at one shape (interpret mode)
    a = jax.random.normal(key, (256, 512), jnp.float32)
    b = jax.random.normal(key, (512, 256), jnp.float32)
    got = programs.matmul(a, b, stage="tile", impl="kernel",
                          blocks=PROGRAM_BLOCKS)
    err = float(jnp.max(jnp.abs(got - kref.matmul_ref(a, b))))
    rows.append(row("gemm.pallas_check", 0.0, f"max_err={err:.2e}"))
    if tuned:
        from repro.tune import cache as tcache

        c = tune.default_cache()
        path = c.path if c.path is not None else tcache.default_cache_path()
        rows.append(row("gemm.schedule_cache", 0.0, f"entries={len(c)} path={path}"))
    return rows


def run_program_mode() -> list:
    """DSL path vs the raw pinned launcher, identical schedules, plus
    the MESH-scope dispatch at the paper shapes — the perf baseline
    later PRs diff against (BENCH_kernels.json). (The legacy
    ``kernels.ops`` shim this used to compare against was removed after
    its deprecation window.)"""
    from repro.kernels.matmul import matmul_pallas

    rows = []
    m, k, n = PROGRAM_SHAPE
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)

    us_prog = time_jitted(
        lambda a, b: programs.matmul(a, b, stage="tile", impl="kernel",
                                     blocks=PROGRAM_BLOCKS), a, b)
    us_launch = time_jitted(
        lambda a, b: matmul_pallas(a, b,
                                   block_m=PROGRAM_BLOCKS["bm"],
                                   block_n=PROGRAM_BLOCKS["bn"],
                                   block_k=PROGRAM_BLOCKS["bk"],
                                   interpret=jax.default_backend() != "tpu"),
        a, b)
    delta = (us_launch - us_prog) / us_launch * 100.0
    rows.append(row("gemm.program.kernel", us_prog,
                    f"matmul/tile kernel:{PROGRAM_BLOCKS}"))
    rows.append(row("gemm.launcher.kernel", us_launch,
                    f"matmul_pallas pinned blocks; program delta={delta:+.1f}%"))

    for name, m, k, n in SHAPES[:2]:
        k1, k2 = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0),
                                                     hash(name) % 2**31))
        a = jax.random.normal(k1, (m, k), jnp.float32)
        b = jax.random.normal(k2, (k, n), jnp.float32)
        us_p = time_jitted(jax.jit(lambda a, b: programs.matmul(a, b)), a, b)
        rows.append(row(f"gemm.program.{name}", us_p, "mesh dispatch (dot stage)"))
    path = write_bench_json("gemm", rows)
    rows.append(row("gemm.bench_json", 0.0, f"path={path}"))
    return rows


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--tuned", action="store_true",
                   help="autotune each shape and report tuned vs default")
    g.add_argument("--default", dest="default_", action="store_true",
                   help="fixed default schedules only (the default)")
    g.add_argument("--program", dest="program_", action="store_true",
                   help="DSL-vs-legacy-shim comparison; writes BENCH_kernels.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    print("name,us_per_call,derived")
    rows = run_program_mode() if args.program_ else \
        run("tuned" if args.tuned else "default")
    for line in rows:
        print(line)
        sys.stdout.flush()


if __name__ == "__main__":
    main()
