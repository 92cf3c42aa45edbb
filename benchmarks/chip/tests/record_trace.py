#!/usr/bin/env python3
"""Record the small chip trace ``test_reduce_trace.py`` reads: a run of
``qwen3-4b.decode-batch`` whose traced part is a quarter of a second
(a few decode steps at full size), kept under ``testdata/``. Run on a
TPU v5e:

    python3 benchmarks/chip/tests/record_trace.py
"""
from __future__ import annotations

import glob
import json
import os
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

CELL = "qwen3-4b.decode-batch"


def main() -> int:
    import run

    cache_dir = run.enable_compile_cache()
    from chipbench import cells, harness

    mix = cells.load_traffic(cells.find_cell(cells.load_benchmark(), CELL).traffic)
    mix.update(trace_from=2.0, trace_seconds=0.25)
    out = HERE.parent / "testdata"
    keep = out / "raw"
    line = harness.run_cell(CELL, 7, 3.0, True, t_start=time.perf_counter(),
                            cache_dir=cache_dir, mix=mix, keep_trace=str(keep))
    src = glob.glob(os.path.join(keep, "*.xplane.pb"))[0]
    shutil.move(src, out / f"{CELL}.xplane.pb")
    shutil.rmtree(keep)
    with open(out / f"{CELL}.json", "w") as f:
        json.dump({"device": line["device"]}, f, indent=1)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
