"""One run of one cell: set-up, the measured window, the metrics, and
the comparison that decides ``correct``."""
from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import sys
import tempfile
import time
import warnings
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import cells, correctness, reduce_trace, serve_loop, stats, work
from chipbench.peaks import peaks_for


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for,
    or a device kind with no published peaks."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a metric reader is given: the whole record of the run. A
    reader takes what it needs; a new metric needs no new field."""

    cell: str
    sizes: dict                  # the reference module's sizes(config)
    work: work.Work              # operations and bytes the algorithm needs
    mix: dict
    peaks: Any
    engine: Any                  # the program's ServeEngine
    rec: serve_loop.Recorder     # what the window saw
    served: Dict[int, dict]      # serve_loop.served(batcher)
    setup_s: float
    solve_s: float
    window_s: float
    tokens: int                  # output tokens emitted in the window
    gaps: List[float]            # every inter-token gap in the window
    trace: Optional[reduce_trace.Summary] = None

    def traced_decodes(self):
        """Live positions of each decode step run inside the trace."""
        return [p for i, p in self.rec.decodes if self.rec.traced[i]]

    def traced_prompts(self):
        """Prompt lengths of the admissions run inside the trace."""
        return [s for i, _, s, _ in self.rec.admits if self.rec.traced[i]]


class _Compiles:
    """Counts of JAX compile events, from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n = {"trace": 0, "backend_compile": 0, "cache_hit": 0}
        names = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                 "/jax/core/compile/backend_compile_duration": "backend_compile"}

        def on_duration(event, _secs, **_kw):
            if event in names:
                self.n[names[event]] += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.n["cache_hit"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)


def _cache_files(path: str) -> int:
    return sum(len(f) for _, _, f in os.walk(path)) if os.path.isdir(path) else 0


def device_of(chips: int):
    """The first device, after checking that JAX sees enough chips of a
    kind in the peak table. Raises ``NoChip`` otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips visible, the cell asks for {chips}")
    try:
        peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return devs[0]


class _HostClock:
    """What the host did while the window ran: its CPU seconds, its
    garbage collections, and the spread of the steps' wall times.
    Logged, and kept in the result's ``serving``."""

    def __init__(self):
        self.gc_n, self.gc_s, self._t = [0, 0, 0], 0.0, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc_n[info["generation"]] += 1
            self.gc_s += time.perf_counter() - self._t

    def start(self):
        self.gc_n, self.gc_s = [0, 0, 0], 0.0
        self.cpu0 = time.process_time()

    def stop(self, rec: serve_loop.Recorder) -> dict:
        gc.callbacks.remove(self._on_gc)
        ticks = np.diff([0.0] + rec.tick_end[:rec.window_ticks]) * 1e3
        admitting = {i for i, *_ in rec.admits}
        adm = [t for i, t in enumerate(ticks) if i in admitting]
        plain = [t for i, t in enumerate(ticks) if i not in admitting]
        return {
            "cpu_s": time.process_time() - self.cpu0,
            "gc": {"collections": list(self.gc_n), "s": self.gc_s},
            "tick_ms": {q: float(stats.percentile(list(ticks), p)) for q, p in
                        (("p50", 50), ("p95", 95), ("max", 100))} if len(ticks) else {},
            "admitting_tick_ms": float(np.mean(adm)) if adm else None,
            "plain_tick_ms": float(np.mean(plain)) if plain else None,
            # (step index, ms, admissions in it) of the three longest steps
            "slowest": [[int(i), float(ticks[i]), sum(1 for a, *_ in rec.admits if a == i)]
                        for i in np.argsort(ticks)[::-1][:3]],
        }


def judge(gaps: np.ndarray, out_of_range: int, limits: dict) -> bool:
    """The rule that decides ``correct``: some served tokens compared,
    the widest logit gap within its limit, and no token out of range."""
    return bool(len(gaps) >= 1 and float(gaps.max()) <= limits["max_logit_gap"]["limit"]
                and out_of_range == 0)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, cache_dir: str, require_chip: bool = True,
             bench: Optional[dict] = None, doc: Optional[dict] = None,
             mix: Optional[dict] = None, limits: Optional[dict] = None,
             keep_trace: Optional[str] = None, control: bool = False) -> dict:
    """Run cell ``name`` and return its result line (a dict). ``bench``,
    ``doc``, ``mix`` and ``limits`` replace what the files hold (tests
    at a small size); ``require_chip=False`` skips the look for a chip
    (tests on the CPU). ``control=True`` also puts the float8 control
    through the same comparison on the same sample (``control.py``; the
    benchmark's own runs never do)."""
    import jax

    from repro import tune
    from repro.axe import KernelFallbackWarning

    phases = {"imports": time.perf_counter() - t_start}
    bench = bench or cells.load_benchmark()
    cell = cells.find_cell(bench, name)
    doc = doc or cells.load_config(cell.config)
    mix = mix or cells.load_traffic(cell.traffic)
    limits = limits or cells.load_limits(name)
    t = time.perf_counter()
    if require_chip:
        dev = device_of(cell.chips)
        peaks = peaks_for(dev.device_kind)
    else:
        dev = jax.devices()[0]
        peaks = peaks_for("TPU v5 lite")
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    # schedules come from the planner alone, never from a cache outside
    # the checkout; a kernel that gives way to its XLA body is an error
    tune.use_cache(None)
    warnings.simplefilter("error", KernelFallbackWarning)
    compiles = _Compiles()
    files_before = _cache_files(cache_dir)
    phases["backend"] = time.perf_counter() - t

    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    def make_weights(k):
        return ref.init_params(sz, k)

    init = jax.jit(make_weights, out_shardings=jax.sharding.SingleDeviceSharding(dev))
    t = time.perf_counter()
    params = jax.block_until_ready(init(key))
    phases["weights"] = time.perf_counter() - t
    log(f"weights: {sum(x.nbytes for x in jax.tree.leaves(params))} bytes in "
        f"{phases['weights']:.3f} s")

    rec = serve_loop.Recorder()
    engine = serve_loop.build_engine(doc, mix, params)
    serve_loop.instrument(engine)
    t = time.perf_counter()
    engine.decode_fn()
    solve_s = phases["solve"] = time.perf_counter() - t
    log(f"solve (decode graph, {sz['layers']} layers): {solve_s:.3f} s")
    feed = cells.load_generator(mix).feed(mix, seed, sz["vocab"])
    t = time.perf_counter()
    serve_loop.warm_up(engine, sorted({len(r.prompt) for r in feed.requests}),
                       sz["vocab"], seed)
    phases["warm_up"] = time.perf_counter() - t
    log(f"warm-up (every program the window runs): {phases['warm_up']:.3f} s, "
        f"compiles so far {compiles.snapshot()}")
    batcher = serve_loop.batcher_class()(engine, rec, sync_admit=trace)
    gc.collect()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}: " + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()))

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    profiling = []

    def start_profiler():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        profiling.append(True)

    def stop_profiler():
        if profiling:
            jax.profiler.stop_trace()
            profiling.clear()

    before = compiles.snapshot()
    t_from = float(mix.get("trace_from", 0.0))
    t_to = t_from + float(mix.get("trace_seconds", seconds)) if trace else 0.0
    host = _HostClock()
    host.start()
    try:
        serve_loop.serve_window(batcher, feed, seconds, rec, trace_from=t_from, trace_to=t_to,
                                on_open=start_profiler, on_close=stop_profiler)
    finally:
        stop_profiler()
    host_stats = host.stop(rec)
    after = compiles.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    log(f"compiles inside the window (should be 0): {in_window}; compile cache "
        f"{cache_dir}: {files_before} files before, {_cache_files(cache_dir)} after")

    done = serve_loop.served(batcher)
    tokens, gaps, attempted = serve_loop.timings(rec, done)
    run = Run(cell=name, sizes=sz, work=work.Work(ref, sz), mix=mix, peaks=peaks,
              engine=engine, rec=rec, served=done, setup_s=setup_s, solve_s=solve_s,
              window_s=rec.window_s, tokens=tokens, gaps=gaps)
    log(f"window {rec.window_s:.3f} s: {rec.window_ticks} steps, {len(rec.admits)} "
        f"admissions, {tokens} output tokens, {len(gaps)} gaps; host {host_stats}")
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    line: Dict[str, Any] = {"correct": False, "attempted": attempted, "failed": 0}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    if trace:
        path = glob.glob(os.path.join(tdir, "plugins", "profile", "*", "*.xplane.pb"))[0]
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, keep_trace)
        run.trace = reduce_trace.reduce_file(path, serve_loop.SPANS)
        shutil.rmtree(tdir, ignore_errors=True)
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        line["metrics"] = cells.read_metrics(cell.per_layer, run)
        line["breakdown"] = {"device_ops": reduce_trace.top_ops(run.trace),
                             "idle_gaps": reduce_trace.idle_by_span(run.trace)}
    else:
        line["metrics"] = cells.read_metrics(cell.end_to_end, run)
    line["device"] = device
    line["serving"] = {
        "window_s": rec.window_s, "steps": rec.window_ticks,
        "admissions": sum(1 for i, *_ in rec.admits if i < rec.window_ticks),
        "output_tokens": tokens, "gaps": len(gaps),
        "compiles_in_window": in_window["backend_compile"],
        "traces_in_window": in_window["trace"], "cache_hits_in_window": in_window["cache_hit"],
        "setup": phases, "host": host_stats,
    }

    finished = {u: {"prompt": rec.requests[u].prompt, "tokens": r["tokens"]}
                for u, r in done.items() if r["finished"]}
    # free the program's state before the reference runs on the device
    engine.bench_batcher = engine.params = None
    del run, batcher, engine, params
    gc.collect()
    picked = [finished[u] for u in
              correctness.sample(finished, seed, int(mix["check"]["min_tokens"]))]
    t = time.perf_counter()
    ref_params = init(key)
    g = correctness.gaps(ref, sz, ref_params, picked, int(mix["max_seq"]))
    g_ctl = correctness.gaps(ref, sz, ref_params, picked, int(mix["max_seq"]),
                             control=True) if control else None
    del ref_params
    served_toks = np.concatenate([r["tokens"] for r in picked]) \
        if picked else np.zeros(0, np.int64)
    out_of_range = int(((served_toks < 0) | (served_toks >= sz["vocab"])).sum())
    log(f"reference over {len(picked)} requests, {len(g)} served tokens: "
        f"{time.perf_counter() - t:.3f} s")
    limit = limits["max_logit_gap"]["limit"]
    compared = {
        "max_logit_gap": {"value": float(g.max()) if len(g) else None, "limit": limit},
        "tokens_out_of_range": {"value": out_of_range, "limit": 0},
    }
    line["correct"] = judge(g, out_of_range, limits)
    if control:
        # the control's tokens are the float8 reference's own choices
        compared["control_max_logit_gap"] = {
            "value": float(g_ctl.max()) if len(g_ctl) else None, "limit": limit}
        line["control_correct"] = judge(g_ctl, 0, limits)
    line["compared"] = compared
    return line
