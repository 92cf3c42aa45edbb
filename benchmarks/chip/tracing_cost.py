#!/usr/bin/env python3
"""What tracing costs: ``out_tok_s`` of windows profiled whole against
unprofiled windows on the same seeds, in one process, and the host time
of one step's spans with the profiler on and off.

    python3 benchmarks/chip/tracing_cost.py --workload <cell> \\
        --seeds <n>,<n>,... [--seconds 10]

Set-up is the harness's (weights, engine, warm-up); then, for each seed,
one window with the profiler running from its first step to its end and
one without, in alternating order, each through a fresh batcher. The
profiler stops after the window's clock has stopped. No comparison with
the reference is made. Prints one JSON line. Run on a TPU v5e.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time
import warnings

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def _spread(vals):
    q = statistics.quantiles(vals, n=4)
    return (q[2] - q[0]) / statistics.median(vals)


def span_ns(reps: int = 2000) -> dict:
    """Host nanoseconds of one step's spans (the program's and the
    harness's around them), without and with an admission."""
    from jax.profiler import TraceAnnotation

    def plain(i):
        with TraceAnnotation("step"), TraceAnnotation("step", step=i, live=16, queued=16):
            with TraceAnnotation("inputs"):
                pass
            with TraceAnnotation("decode"), TraceAnnotation("decode"):
                pass
            with TraceAnnotation("sample"), TraceAnnotation("sample"):
                pass

    def admission(i):
        with TraceAnnotation("admit", uid=i), TraceAnnotation("admit"):
            with TraceAnnotation("prefill", uid=i, prompt_len=64):
                pass
            with TraceAnnotation("first_token", uid=i):
                pass
            with TraceAnnotation("slot_write", uid=i):
                pass

    out = {}
    for name, fn in (("step", plain), ("admission", admission)):
        t = time.perf_counter_ns()
        for i in range(reps):
            fn(i)
        out[name] = (time.perf_counter_ns() - t) / reps
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    import run

    run.enable_compile_cache()
    import jax

    from chipbench import cells, harness, serve_loop, stats
    from repro import tune
    from repro.axe import KernelFallbackWarning

    cell = cells.find_cell(cells.load_benchmark(), args.workload)
    try:
        dev = harness.device_of(cell.chips)
    except harness.NoChip as e:
        print(f"tracing_cost.py: {e}", file=sys.stderr)
        return 2
    doc, mix = cells.load_config(cell.config), cells.load_traffic(cell.traffic)
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    tune.use_cache(None)
    warnings.simplefilter("error", KernelFallbackWarning)
    key = jax.random.PRNGKey(seeds[0] % 2**31)
    init = jax.jit(lambda k: ref.init_params(sz, k),
                   out_shardings=jax.sharding.SingleDeviceSharding(dev))
    engine = serve_loop.build_engine(doc, mix, jax.block_until_ready(init(key)))
    serve_loop.instrument(engine)
    gen = cells.load_generator(mix)
    lengths = sorted({len(r.prompt) for r in gen.feed(mix, seeds[0], sz["vocab"]).requests})
    serve_loop.warm_up(engine, lengths, sz["vocab"], seeds[0])
    setup_s = time.perf_counter() - T_START

    def window(seed: int, profiled: bool) -> float:
        rec = serve_loop.Recorder()
        batcher = serve_loop.batcher_class()(engine, rec)
        tdir = tempfile.mkdtemp(prefix="tracing_cost_")

        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tdir, profiler_options=opts)

        try:
            serve_loop.serve_window(batcher, gen.feed(mix, seed, sz["vocab"]), args.seconds,
                                    rec, trace_from=0.0,
                                    trace_to=float("inf") if profiled else 0.0,
                                    on_open=start if profiled else None)
        finally:
            if profiled:
                jax.profiler.stop_trace()
            shutil.rmtree(tdir, ignore_errors=True)
        tokens, _, _ = serve_loop.timings(rec, serve_loop.served(batcher))
        batcher.cache = None
        engine.bench_batcher = None
        return stats.rate(tokens, rec.window_s)

    runs = []
    for i, seed in enumerate(seeds):
        order = (False, True) if i % 2 == 0 else (True, False)
        got = {p: window(seed, p) for p in order}
        runs.append({"seed": seed, "off": got[False], "on": got[True]})
        print(f"[tracing_cost] seed {seed}: off {got[False]:.4f}, on {got[True]:.4f}",
              file=sys.stderr, flush=True)

    off = [r["off"] for r in runs]
    on = [r["on"] for r in runs]
    inactive = span_ns()
    tdir = tempfile.mkdtemp(prefix="tracing_cost_")
    jax.profiler.start_trace(tdir)
    active = span_ns()
    jax.profiler.stop_trace()
    shutil.rmtree(tdir, ignore_errors=True)
    out = {
        "workload": args.workload, "seconds": args.seconds, "setup_s": setup_s,
        "device": {"kind": dev.device_kind, "count": len(jax.devices())},
        "runs": runs,
        "out_tok_s": {"off_median": statistics.median(off), "on_median": statistics.median(on),
                      "off_spread": _spread(off) if len(off) > 1 else None,
                      "on_spread": _spread(on) if len(on) > 1 else None,
                      "on_over_off": statistics.median(on) / statistics.median(off)},
        "span_ns": {"profiler_off": inactive, "profiler_on": active},
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
