"""End-to-end compiled-forward benchmark: ``axe.compile`` executables
over the model-zoo graphs (dense / MoE / SSM smoke configs), reporting
wall time and tokens/s per config, merged into ``BENCH_graph.json`` for
the nightly regression gate (``benchmarks/check_regression.py``).

The default run measures each config twice — through the fusion passes
(``repro.axe.passes``, the gated ``graph.forward.*`` rows) and unfused
(``graph.forward.*.unfused``) — so the baseline carries the fused vs
unfused tokens/s side by side. ``--no-fuse`` is the A/B switch: it
measures only the unfused executables and overwrites the section with
them (a debugging mode — don't commit its output as the baseline).

Usage:
    python benchmarks/bench_graph.py [--batch 4] [--seq 64] [--no-fuse]
"""
from __future__ import annotations

import argparse
import dataclasses
import pathlib
import sys

if __package__ in (None, ""):  # script mode: make `benchmarks.*` importable
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp

from benchmarks.common import row, time_jitted, write_bench_json
from repro.launch.compile_cache import enable_compile_cache

BENCH_GRAPH_JSON = "BENCH_graph.json"

ARCHS = ("qwen3-4b", "qwen3-moe-235b-a22b", "mamba2-2.7b")


def _build(axe, cfg, mesh, params, batch, seq, *, fuse):
    exe = axe.model_executable(cfg, mesh, batch, seq, dtype=cfg.dtype,
                               fuse=fuse)
    return exe, axe.model_inputs(exe.graph, cfg, params)


def _interleaved(execs, tokens, *, warmup: int = 3, rounds: int = 25):
    """Best wall-time (µs) per executable, sampled in drift-symmetric
    rounds: each round runs the legs forward then reversed (A,B,B,A), so
    a linear host-load drift across the round hits every leg equally —
    the fused and unfused legs run identical layouts, and a sequential
    A-then-B sweep would let a few ms of machine noise decide the
    comparison. Min over rounds because the host is shared: the fastest
    observation is the least-contended one."""
    import time

    for exe, inputs in execs:
        for _ in range(warmup):
            jax.block_until_ready(exe(inputs, tokens))
    samples = [[] for _ in execs]
    order = list(range(len(execs)))
    for _ in range(rounds):
        for i in order + order[::-1]:
            exe, inputs = execs[i]
            t0 = time.perf_counter()
            jax.block_until_ready(exe(inputs, tokens))
            samples[i].append(time.perf_counter() - t0)
    return [min(ts) * 1e6 for ts in samples]


def run_offload(batch: int, seq: int) -> list:
    """One host-offload row: the dense config compiled with its
    embedding table parked on a carved host-class mesh axis
    (``model_executable(classes=..., offload=...)``), interleaved
    against the same graph all-accelerator on the same 3-axis mesh.
    Checks bit-level parity before timing — the Transfer collective
    lowers to the same SPMD primitives, only the cost model differs."""
    import numpy as np

    from repro import axe, compat
    from repro.configs import get_config, smoke_variant
    from repro.models.model_zoo import build_model

    n_dev = len(jax.devices())
    host_deg = 2 if n_dev % 2 == 0 else 1
    rest = n_dev // host_deg
    model_deg = 2 if rest % 2 == 0 else rest
    mesh = compat.make_mesh(
        (rest // model_deg, model_deg, host_deg), ("data", "model", "host")
    )

    arch = "qwen3-4b"
    cfg = smoke_variant(get_config(arch))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch * seq,), 0, cfg.vocab_size, jnp.int32
    )
    exe_a = axe.model_executable(cfg, mesh, batch, seq, dtype=cfg.dtype,
                                 classes={"host": "host"})
    exe_h = axe.model_executable(cfg, mesh, batch, seq, dtype=cfg.dtype,
                                 classes={"host": "host"}, offload=("embed",))
    ins_a = axe.model_inputs(exe_a.graph, cfg, params)
    ins_h = axe.model_inputs(exe_h.graph, cfg, params)
    out_a = np.asarray(jax.block_until_ready(exe_a(ins_a, tokens)))
    out_h = np.asarray(jax.block_until_ready(exe_h(ins_h, tokens)))
    err = float(np.max(np.abs(out_a - out_h)))
    if err > 1e-5:
        raise RuntimeError(f"host-parked forward deviates by {err:.2e}")
    transfers = sum(
        1 for (_op, _operand, steps) in exe_h.collective_sequence()
        if "Transfer" in steps
    )
    us_a, us_h = _interleaved([(exe_a, ins_a), (exe_h, ins_h)], tokens)
    tok_h = batch * seq / (us_h / 1e6)
    tok_a = batch * seq / (us_a / 1e6)
    return [row(
        f"graph.forward.{arch}.offload", us_h,
        f"compiled forward {batch}x{seq} embed host-parked "
        f"tokens/s={tok_h:.0f} (all-accel {tok_a:.0f}) "
        f"transfers={transfers} xfer={exe_h.plan.total_transfer_bytes}B/dev "
        f"max|d|={err:.1e}",
    )]


def run_overlap(batch: int, seq: int) -> list:
    """One compute/communication-overlap row: the MoE config (comm is a
    meaningful fraction of its step) compiled with the overlap schedule
    (``model_executable(..., overlap=True)``, docs/overlap.md) against
    the synchronous executable on the *same solved plan*, so the A/B
    isolates the schedule. Bit-comparability is asserted before timing
    (the schedule reorders collective issue only), and the legs share
    the drift-symmetric interleaved rounds (:func:`_interleaved`) so the
    tokens/s delta is not measurement drift."""
    import numpy as np

    from repro import axe, compat
    from repro.configs import get_config, smoke_variant
    from repro.models.model_zoo import build_model

    n_dev = len(jax.devices())
    model_deg = 4 if n_dev % 4 == 0 else n_dev
    mesh = compat.make_mesh((n_dev // model_deg, model_deg), ("data", "model"))

    arch = "qwen3-moe-235b-a22b"
    cfg = smoke_variant(get_config(arch))
    cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch * seq,), 0, cfg.vocab_size, jnp.int32
    )
    exe_s = axe.model_executable(cfg, mesh, batch, seq, dtype=cfg.dtype)
    exe_o = axe.model_executable(cfg, mesh, batch, seq, dtype=cfg.dtype,
                                 plan=exe_s.solve_result, overlap=True)
    ins = axe.model_inputs(exe_s.graph, cfg, params)
    out_s = np.asarray(jax.block_until_ready(exe_s(ins, tokens)))
    out_o = np.asarray(jax.block_until_ready(exe_o(ins, tokens)))
    if not np.array_equal(out_s, out_o):
        err = float(np.max(np.abs(out_s - out_o)))
        raise RuntimeError(f"overlap forward is not bit-equal (max|d|={err:.2e})")
    prefetched = sum(len(r.prefetched) for r in exe_o.lowering_trace)
    if prefetched == 0:
        raise RuntimeError("overlap schedule hoisted no collectives")
    # the solver's view of the same plan under the overlap objective:
    # how many ops get their comm charged at max(comm, compute)
    res = axe.solve(exe_s.graph, overlap=True)
    hidden_ops = sum(1 for d in res.trace if d.hidden_comm_s > 0)
    us_s, us_o = _interleaved([(exe_s, ins), (exe_o, ins)], tokens)
    tok_s = batch * seq / (us_s / 1e6)
    tok_o = batch * seq / (us_o / 1e6)
    return [row(
        f"graph.forward.{arch}.overlap", us_o,
        f"compiled forward {batch}x{seq} overlap tokens/s={tok_o:.0f} "
        f"(sync {tok_s:.0f}) prefetched={prefetched} "
        f"hidden_ops={hidden_ops} "
        f"hidden={res.hidden_comm_s * 1e6:.1f}us/dev bit-equal",
    )]


def run_cotune(batch: int, seq: int) -> list:
    """One cotune row: the dense config compiled through the
    solve<->tune fixed-point loop (``model_executable(cotune=True,
    cotune_measure=True)``, docs/cotune.md) against the one-shot-solved
    executable. The cotune leg autotunes the solver's matmul locals and
    re-solves under the measured-corrected cost model, so its plan may
    legitimately differ from the one-shot plan; numerics are checked to
    tolerance (layout changes reassociate float reductions) and the two
    legs share the drift-symmetric interleaved rounds
    (:func:`_interleaved`) so the tokens/s delta is not measurement
    drift."""
    import numpy as np

    from repro import axe, compat, tune
    from repro.configs import get_config, smoke_variant
    from repro.models.model_zoo import build_model

    n_dev = len(jax.devices())
    model_deg = 4 if n_dev % 4 == 0 else n_dev
    mesh = compat.make_mesh((n_dev // model_deg, model_deg), ("data", "model"))

    arch = "qwen3-4b"
    cfg = smoke_variant(get_config(arch))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch * seq,), 0, cfg.vocab_size, jnp.int32
    )
    exe_1 = axe.model_executable(cfg, mesh, batch, seq, dtype=cfg.dtype)
    exe_c = axe.model_executable(cfg, mesh, batch, seq, dtype=cfg.dtype,
                                 cotune=True, cotune_measure=True)
    ct = exe_c.cotune_report
    if ct is None:
        raise RuntimeError("cotune executable carries no cotune_report")
    if ct.objective_s > ct.iter0_objective_s * (1 + 1e-9):
        raise RuntimeError(
            f"cotune regressed the modeled objective: "
            f"{ct.iter0_objective_s:.6e} -> {ct.objective_s:.6e}"
        )
    ins_1 = axe.model_inputs(exe_1.graph, cfg, params)
    ins_c = axe.model_inputs(exe_c.graph, cfg, params)
    out_1 = np.asarray(jax.block_until_ready(exe_1(ins_1, tokens)))
    out_c = np.asarray(jax.block_until_ready(exe_c(ins_c, tokens)))
    err = float(np.max(np.abs(out_1 - out_c)))
    if err > 1e-5:
        raise RuntimeError(f"cotuned forward deviates by {err:.2e}")
    us_1, us_c = _interleaved([(exe_1, ins_1), (exe_c, ins_c)], tokens)
    tok_1 = batch * seq / (us_1 / 1e6)
    tok_c = batch * seq / (us_c / 1e6)
    cm = ct.cost_model
    table = len(cm) if cm is not None else 0
    # the schedule cache now holds this run's measured entries; the
    # nightly workflow merges it into the persistent service artifact
    tune.ServiceArtifact.from_cache(tune.default_cache()).save(
        "bench_out/schedule_service.json"
    )
    return [row(
        f"graph.forward.{arch}.cotune", us_c,
        f"compiled forward {batch}x{seq} cotuned tokens/s={tok_c:.0f} "
        f"(one-shot {tok_1:.0f}) iters={len(ct.iterations)} "
        f"converged={ct.converged} flipped={ct.flipped} "
        f"J={ct.iter0_objective_s * 1e3:.2f}->"
        f"{ct.objective_s * 1e3:.2f}ms table={table} "
        f"max|d|={err:.1e}",
    )]


def run(batch: int, seq: int, *, fuse: bool = True) -> list:
    from repro import axe, compat
    from repro.configs import get_config, smoke_variant
    from repro.models.model_zoo import build_model

    n_dev = len(jax.devices())
    model_deg = 4 if n_dev % 4 == 0 else n_dev
    mesh = compat.make_mesh((n_dev // model_deg, model_deg), ("data", "model"))

    rows = []
    for arch in ARCHS:
        cfg = smoke_variant(get_config(arch))
        if cfg.is_moe:
            cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.num_experts))
        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch * seq,), 0, cfg.vocab_size, jnp.int32
        )
        exe_u, ins_u = _build(axe, cfg, mesh, params, batch, seq, fuse=False)
        base = (
            f"compiled forward {batch}x{seq} "
            f"collectives={len(exe_u.collective_sequence())} "
            f"comm={exe_u.plan.total_comm_bytes}B/dev"
        )
        if not fuse:
            us_u = time_jitted(exe_u, ins_u, tokens)
            tok_u = batch * seq / (us_u / 1e6)
            rows.append(row(
                f"graph.forward.{arch}", us_u,
                f"{base} tokens/s={tok_u:.0f} (no-fuse mode)",
            ))
            continue
        exe_f, ins_f = _build(axe, cfg, mesh, params, batch, seq, fuse=True)
        us_u, us_f = _interleaved([(exe_u, ins_u), (exe_f, ins_f)], tokens)
        tok_u = batch * seq / (us_u / 1e6)
        tok_f = batch * seq / (us_f / 1e6)
        rep = exe_f.fusion_report
        rows.append(row(
            f"graph.forward.{arch}", us_f,
            f"compiled forward {batch}x{seq} fused tokens/s={tok_f:.0f} "
            f"(unfused {tok_u:.0f}) patterns={len(rep.patterns_fired)} "
            f"collectives={len(exe_f.collective_sequence())} "
            f"comm={exe_f.plan.total_comm_bytes}B/dev",
        ))
        rows.append(row(
            f"graph.forward.{arch}.unfused", us_u,
            f"{base} tokens/s={tok_u:.0f}",
        ))
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--no-fuse", action="store_true",
                    help="measure only the unfused executables (A/B "
                         "debugging; overwrites the section — don't "
                         "commit as the baseline)")
    ap.add_argument("--offload", action="store_true",
                    help="also measure the dense config with its "
                         "embedding table host-parked (repro.axe.hetero) "
                         "against the all-accelerator twin")
    ap.add_argument("--overlap", action="store_true",
                    help="also measure the MoE config under the "
                         "compute/communication-overlap schedule "
                         "(docs/overlap.md) against its synchronous twin "
                         "on the same solved plan")
    ap.add_argument("--cotune", action="store_true",
                    help="also measure the dense config through the "
                         "solve<->tune fixed-point loop (repro.axe.cotune, "
                         "docs/cotune.md) against its one-shot-solved twin; "
                         "exports the run's measured schedules to "
                         "bench_out/schedule_service.json")
    args = ap.parse_args()
    enable_compile_cache()
    rows = run(args.batch, args.seq, fuse=not args.no_fuse)
    if args.offload:
        rows += run_offload(args.batch, args.seq)
    if args.overlap:
        rows += run_overlap(args.batch, args.seq)
    if args.cotune:
        rows += run_cotune(args.batch, args.seq)
    path = write_bench_json(
        "graph", rows, filename=BENCH_GRAPH_JSON,
    )
    for r in rows:
        print(r)
    print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
