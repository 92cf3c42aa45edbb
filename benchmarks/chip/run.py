#!/usr/bin/env python3
"""Chip benchmark of the serving path: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``, with its reference in ``reference/``) and a
traffic mix (``traffic/<name>.json``); each metric has a reader in
``metrics/<name>.py``. The run makes the weights from the seed, warms
up every program the window runs, serves the mix for ``--seconds``,
then compares a sample of the served tokens with the plain float32
reference. The last line of standard output is the JSON result; the
last lines of standard error give each compared number beside its
limit. With no TPU, fewer chips than the cell asks for, or a device
kind with no published peaks, it exits with 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``,
    or ``$JAX_COMPILATION_CACHE_DIR`` where that is set), keeping every
    program however small or quick to compile, so that only a cell's
    first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    from chipbench import harness

    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                                t_start=T_START, cache_dir=cache_dir)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for k, c in line["compared"].items():
        print(f"compared {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
