"""Paper Fig. 13 — multi-head attention across input lengths (the
Trainium workload, adapted): full-materialization attention vs the
blocked online-softmax schedule (identical math to the Pallas kernel),
plus a kernel-vs-oracle check in interpret mode.

Modes (``python benchmarks/bench_mha.py [--default | --tuned | --program]``):

  --default  fixed chunk=256 blocked schedule
  --tuned    autotune the blocked schedule's chunk size per length
             (persisted in the schedule cache) and report the delta
  --program  benchmark the axe.program flash-attention path against the
             legacy deprecated-shim path (same blocks) and append to the
             ``BENCH_kernels.json`` perf baseline
"""
from __future__ import annotations

import functools
import pathlib
import sys

if __package__ in (None, ""):  # script mode: make `benchmarks.*` importable
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from benchmarks.common import row, time_jitted, write_bench_json
from repro.kernels import programs, ref as kref
from repro.launch.compile_cache import enable_compile_cache
from repro.models import attention as attn_mod

LENS = [512, 1024, 2048]


def run(mode: str = "default") -> list:
    from repro import tune

    tuned = mode == "tuned"
    rows = []
    cfgish = type("C", (), {"num_heads": 8, "num_kv_heads": 8, "head_dim": 64})()
    b, h, hd = 1, 8, 64
    for s in LENS:
        ks = jax.random.split(jax.random.PRNGKey(s), 3)
        q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, h, hd), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, h, hd), jnp.float32)
        full = jax.jit(functools.partial(attn_mod._gqa_full, cfg=None, causal=False, window=None))
        blocked = jax.jit(functools.partial(
            attn_mod._gqa_blocked, cfg=None, causal=False, window=None, chunk=256))
        us_full = time_jitted(full, q, k, v)
        us_blk = time_jitted(blocked, q, k, v)
        flops = 4 * b * h * s * s * hd
        rows.append(row(f"mha.full.s{s}", us_full, f"{flops/(us_full*1e-6)/1e9:.1f}GFLOP/s"))
        rows.append(row(f"mha.blocked.s{s}", us_blk, f"{flops/(us_blk*1e-6)/1e9:.1f}GFLOP/s"))
        if tuned:
            rep = tune.autotune_mha_blocked(q, k, v)
            meas = dict(rep.measurements)
            base = meas.get("xla:chunk=256")  # the --default chunk
            if rep.cached or base is None:
                derived = f"sched={rep.schedule.describe()} cached={rep.cached}"
            else:
                delta = (base - rep.us) / base * 100.0
                derived = (f"sched={rep.schedule.describe()} "
                           f"default={base:.1f}us delta={delta:+.1f}%")
            rows.append(row(f"mha.blocked.s{s}.tuned", rep.us, derived))
    # Pallas kernel check (interpret) on one shape
    q = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 256, 64), jnp.float32)
    kk = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 256, 64), jnp.float32)
    vv = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 256, 64), jnp.float32)
    got = programs.flash_attention(q, kk, vv, causal=True)
    err = float(jnp.max(jnp.abs(got - kref.attention_ref(q, kk, vv, causal=True))))
    rows.append(row("mha.pallas_check", 0.0, f"max_err={err:.2e}"))
    return rows


def run_program_mode() -> list:
    """DSL path vs the raw pinned launcher for the flash-attention
    kernel (interpret mode, identical blocks), appended to
    BENCH_kernels.json. (The legacy ``kernels.ops`` shim this used to
    compare against was removed after its deprecation window.)"""
    from repro.kernels.flash_attention import flash_attention_pallas

    rows = []
    q = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 256, 64), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 256, 64), jnp.float32)
    blocks = {"bq": 128, "bkv": 128}
    us_prog = time_jitted(
        lambda q, k, v: programs.flash_attention(q, k, v, causal=True,
                                                 blocks=blocks), q, k, v)
    us_launch = time_jitted(
        lambda q, k, v: flash_attention_pallas(
            q, k, v, causal=True, block_q=128, block_kv=128,
            interpret=jax.default_backend() != "tpu"), q, k, v)
    delta = (us_launch - us_prog) / us_launch * 100.0
    rows.append(row("mha.program.kernel", us_prog,
                    "flash_attention/attend kernel:bq=128,bkv=128"))
    rows.append(row("mha.launcher.kernel", us_launch,
                    f"flash_attention_pallas pinned blocks; program delta={delta:+.1f}%"))
    # the MESH-scope blocked-softmax schedule at one paper length
    s = 1024
    ks = jax.random.split(jax.random.PRNGKey(s), 3)
    qb = jax.random.normal(ks[0], (1, s, 8, 64), jnp.float32)
    kb = jax.random.normal(ks[1], (1, s, 8, 64), jnp.float32)
    vb = jax.random.normal(ks[2], (1, s, 8, 64), jnp.float32)
    blocked = jax.jit(functools.partial(
        attn_mod._gqa_blocked, cfg=None, causal=False, window=None, chunk=256))
    rows.append(row(f"mha.program.blocked.s{s}", time_jitted(blocked, qb, kb, vb),
                    "mha_blocked xla:chunk=256"))
    path = write_bench_json("mha", rows)
    rows.append(row("mha.bench_json", 0.0, f"path={path}"))
    return rows


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--tuned", action="store_true",
                   help="autotune the blocked chunk size per length")
    g.add_argument("--default", dest="default_", action="store_true",
                   help="fixed default schedules only (the default)")
    g.add_argument("--program", dest="program_", action="store_true",
                   help="DSL-vs-legacy-shim comparison; appends to BENCH_kernels.json")
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
