#!/usr/bin/env python3
"""Readings that set a cell's comparison limit: for each seed, one run
of the cell (a short window at the cell's own load and sizes) and, on
the same sampled prompts and served tokens, the widest logit gap of the
program and of the float8 control, each put through the comparison
that decides ``correct`` under the cell's limits. All seeds run in one
process.

    python3 benchmarks/chip/control.py <cell> <seconds> <seed> [<seed> ...]

Prints one JSON line per seed; the benchmark's own runs do not run it.
"""
from __future__ import annotations

import json
import sys
import time


def main(argv) -> int:
    import run

    cell, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    cache_dir = run.enable_compile_cache()
    from chipbench import harness

    for seed in seeds:
        line = harness.run_cell(cell, seed, seconds, False, t_start=time.perf_counter(),
                                cache_dir=cache_dir, control=True)
        c = line["compared"]
        print(json.dumps({"cell": cell, "seed": seed, "correct": line["correct"],
                          "control_correct": line["control_correct"],
                          "program": c["max_logit_gap"]["value"],
                          "control": c["control_max_logit_gap"]["value"],
                          "limit": c["max_logit_gap"]["limit"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
