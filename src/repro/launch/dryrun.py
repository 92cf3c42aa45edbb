import os
# a CPU-only tool: its meshes are fake host devices, never a chip.
# Respect a caller-set XLA_FLAGS (the CI execute-smoke leg pins 8 host
# devices); default to the 512-device deviceless-lowering geometry
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell
with ShapeDtypeStruct inputs (no allocation), record memory/cost
analysis + collective bytes for the roofline.

``--layout-plan`` skips lowering entirely and reports the propagated
AxeSpec layout plan (per-op output specs, redistribution collectives,
and comm bytes from ``collective.plan_comm_bytes``) for one decoder
layer — the full layout story with no devices at all.

``--solve --execute`` goes the other way: compile the solved plan with
``axe.compile`` on this host's devices (smoke-reduced config), run the
numerics, and cross-check the redistribution collectives the traced
body *issued* against the plan and the solver's Decision trace.

Usage:
    python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
    python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k --layout-plan
    python -m repro.launch.dryrun --arch qwen3-4b --solve --execute
    python -m repro.launch.dryrun --all --out results.jsonl
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import SHAPES, build_model, shape_applicable
from repro.optim.adamw import AdamW
from repro.axe import lower as axe_lower
from repro.axe import rules as axe_rules
from repro.axe.spec import PhysicalSpace
from repro.train.train_loop import TrainState, make_train_step


def _tree_specs(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _mesh_shape(multi_pod: bool):
    # the make_production_mesh geometry, as a dict — no devices needed
    return {"pod": 2, "data": 16, "model": 16} if multi_pod else {"data": 16, "model": 16}


def _hetero_space(mesh_shape, classes_text: str, host_degree: int):
    """Class-annotated solve space: carve a ``host`` memory-tier axis
    into the mesh and parse the per-class cost table (``--classes``
    syntax, ``repro.axe.hetero.parse_classes``)."""
    from repro.axe import hetero

    table = hetero.parse_classes(classes_text)
    shape = dict(mesh_shape)
    shape["host"] = host_degree
    space = PhysicalSpace.from_mesh_shape(
        shape, classes={"host": hetero.HOST_CLASS}
    )
    return table, space


def _hetero_record(res, table):
    """Per-class placement + transfer-byte summary of a SolveResult."""
    from repro.axe import hetero

    parked = {
        name: spec.signature()
        for name, spec in sorted(res.assignment.items())
        if hetero.is_parked(spec)
    }
    return {
        "default_class": table.default,
        "placed": {
            table.default: len(res.assignment) - len(parked),
            hetero.HOST_CLASS: len(parked),
        },
        "parked": parked,
        "transfer_bytes": res.transfer_bytes,
    }


def _print_hetero(rec):
    het = rec["hetero"]
    placed = het["placed"]
    print("per-class placement: "
          + "  ".join(f"{c}={n}" for c, n in sorted(placed.items()))
          + f"  transfer={het['transfer_bytes'] / 2**10:.1f} KiB/dev")
    for name, sig in het["parked"].items():
        print(f"  parked {name}: {sig}")


def layout_plan_cell(arch: str, shape_name: str, multi_pod: bool, *, verbose: bool = True):
    """Propagate one decoder layer's layout plan — no mesh, no compile."""
    from repro.axe.graphs import decoder_layer_graph
    from repro.axe.propagate import PropagationError, propagate
    from repro.axe.spec import PhysicalSpace

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    space = PhysicalSpace.from_mesh_shape(_mesh_shape(multi_pod))
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "kind": shape.kind, "batch": shape.batch, "seq": shape.seq,
    }
    try:
        nodes, env = decoder_layer_graph(cfg, shape.batch, shape.seq, space)
        plan = propagate(nodes, env)
    except Exception as e:  # record an error row; never abort a sweep
        record.update(status="error", error=f"{type(e).__name__}: {e}")
        if not isinstance(e, PropagationError):
            record["traceback"] = traceback.format_exc()[-2000:]
        return record
    record["layout_plan"] = plan.to_dict()
    record["status"] = "ok"
    if verbose:
        print(plan.describe())
    return record


def solve_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    layers: int = 2,
    beam: int = 4,
    verbose: bool = True,
    trace: bool = False,
    fuse: bool = False,
    fusion_trace: bool = False,
    classes: str = None,
    host_degree: int = 2,
    offload: tuple = (),
    overlap: bool = False,
    cotune: bool = False,
    cotune_measure: bool = False,
    cotune_iters: int = 4,
):
    """Solve the whole-model layout for one cell — deviceless, like
    ``--layout-plan``, but the compiler *chooses* the placements: beam
    search over algebra-enumerated candidates (``repro.axe.solve``)
    against the rule-seeded baseline. Reports solved vs seeded comm
    bytes and the per-op decision trace, plus the planner schedule each
    solved op keys (``tune.planner.schedule_from_specs``).

    ``cotune=True`` replaces the one-shot solve with the solve↔tune
    fixed-point loop (``repro.axe.cotune``): measured timings from the
    ambient schedule cache correct the rooflines and the layout is
    re-solved to a fixed point; the per-iteration trace lands in the
    record's ``cotune`` block. ``cotune_measure=True`` autotunes the
    measurable local problems in-loop (touches the schedule cache)."""
    from repro.axe.graphs import model_graph
    from repro.axe.solve import SolveError, solve
    from repro.axe.spec import PhysicalSpace
    from repro.tune import planner as tune_planner

    import contextlib

    from repro.axe import hetero

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    table = None
    if classes:
        table, space = _hetero_space(_mesh_shape(multi_pod), classes, host_degree)
    else:
        space = PhysicalSpace.from_mesh_shape(_mesh_shape(multi_pod))
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "kind": shape.kind, "batch": shape.batch, "seq": shape.seq,
        "layers": layers, "beam": beam,
    }
    if classes:
        record["classes"] = classes
        record["offload"] = list(offload)
    try:
        gs = model_graph(cfg, shape.batch, shape.seq, space, layers=layers)
        if fuse:
            from repro.axe.passes import fuse_graph
            from repro.axe.propagate import propagate

            if fusion_trace:
                # comm bytes of the rule-seeded plan before the rewrite
                # — the --fusion-trace before/after-solve comparison
                pre = propagate(gs.nodes, gs.seeded_env(), space=space)
                record["unfused_seeded_comm_bytes"] = pre.total_comm_bytes
            gs, rep = fuse_graph(gs)
            record["fusion"] = rep.to_dict()
        # under a class table the rule-seeded baseline is not the budget
        # (the rules never park; the parked lineage must be free to
        # out-spend the seed on ICI comm to save accelerator memory)
        ctx = hetero.use_class_table(table) if table else contextlib.nullcontext()
        ct = None
        with ctx:
            if cotune:
                from repro.axe.cotune import cotune as _cotune

                ct = _cotune(gs, beam=beam, backend="tpu",
                             compare_seeded=not classes, offload=offload,
                             overlap=overlap, max_iters=cotune_iters,
                             measure=cotune_measure)
                res = ct.result
            else:
                res = solve(gs, beam=beam, backend="tpu",
                            compare_seeded=not classes, offload=offload,
                            overlap=overlap)
        if table is not None:
            record["hetero"] = _hetero_record(res, table)
    except Exception as e:  # record an error row; never abort a sweep
        record.update(status="error", error=f"{type(e).__name__}: {e}")
        if not isinstance(e, SolveError):
            record["traceback"] = traceback.format_exc()[-2000:]
        return record
    record["solve"] = res.to_dict()
    if ct is not None:
        record["cotune"] = ct.to_dict()
        if verbose:
            print(ct.describe())
    if fuse and verbose and fusion_trace:
        print(rep.describe())
    # the tune-planner schedule each solved op dispatches to, keyed on
    # the solved specs' canonical layout signature
    schedules = {}
    for e in res.plan.entries:
        if e.op.kind == "finalize":
            continue
        # post-redistribution specs: the local problem the backend's
        # program stage actually resolves its schedule for
        in_specs = e.input_specs(res.plan.env)
        sp = tune_planner.plan_from_specs(e.op.kind, in_specs, backend="tpu")
        if sp is not None and sp.schedule is not None:
            schedules[e.op.name] = {
                "op": sp.op,
                "layout_sig_len": len(sp.layout_sig),
                "schedule": sp.schedule.describe(),
            }
            steps = sp.candidates[0].terms_dict.get("grid_steps")
            if steps is not None:
                schedules[e.op.name]["grid_steps"] = steps
    record["schedules"] = schedules
    record["status"] = "ok"
    if verbose:
        print(res.describe(trace=trace))
        if "hetero" in record:
            _print_hetero(record)
    return record


def execute_cell(
    arch: str,
    *,
    batch: int = 4,
    seq: int = 32,
    beam: int = 4,
    verbose: bool = True,
    fuse: bool = False,
    fusion_trace: bool = False,
    classes: str = None,
    host_degree: int = 2,
    offload: tuple = (),
    overlap: bool = False,
):
    """Compile the solved plan with ``axe.compile`` and *run* it on
    this host's devices (smoke-reduced config): checks the numerics
    against the reference model forward and cross-checks the
    redistribution collectives the traced body issued against the plan
    and the solver's per-op Decision comm accounting.

    ``overlap=True`` solves under the ``max(comm, compute)`` objective
    and compiles the overlap schedule (docs/overlap.md); the record then
    carries the hidden/exposed comm-second split, and the issued==planned
    cross-check runs against the interleaved issue order."""
    import contextlib

    import numpy as np

    from repro.axe import hetero
    from repro.axe.compile import (
        SUPPORTED_FAMILIES, compile as axe_compile, model_inputs,
    )
    from repro.axe.graphs import model_graph
    from repro.axe.solve import solve
    from repro.configs import smoke_variant
    from repro.models import transformer as tf_mod

    import numpy as _np
    from jax.sharding import Mesh

    cfg = smoke_variant(get_config(arch))
    record = {"arch": arch, "mode": "execute", "batch": batch, "seq": seq}
    if cfg.family not in SUPPORTED_FAMILIES:
        record.update(status="skipped",
                      reason=f"family {cfg.family} has no model binding")
        return record

    # unlike the deviceless lowering modes, --execute RUNS the numerics:
    # cap the mesh at 8 devices even when this module's default 512
    # forced host devices are in effect
    n_dev = min(len(jax.devices()), 8)
    table = None
    if classes:
        # carve a host-class axis out of the device budget: 8 devices →
        # (data=2, model=2, host=2); 1 device degenerates to (1, 1, 1)
        hd = host_degree if n_dev % host_degree == 0 else 1
        rest = n_dev // hd
        model_deg = 2 if rest % 2 == 0 else rest
        mesh = Mesh(
            _np.asarray(jax.devices()[:n_dev]).reshape(
                rest // model_deg, model_deg, hd),
            ("data", "model", "host"),
        )
        table = hetero.parse_classes(classes)
        space = PhysicalSpace.from_mesh_shape(
            axe_rules.mesh_shape_of(mesh), classes={"host": hetero.HOST_CLASS}
        )
        record["classes"] = classes
        record["offload"] = list(offload)
    else:
        model_deg = 4 if n_dev % 4 == 0 else n_dev
        mesh = Mesh(
            _np.asarray(jax.devices()[:n_dev]).reshape(n_dev // model_deg, model_deg),
            ("data", "model"),
        )
        space = PhysicalSpace.from_mesh_shape(axe_rules.mesh_shape_of(mesh))
    record["mesh_shape"] = space.mesh_shape

    try:
        graph = model_graph(cfg, batch, seq, space,
                            dtype=cfg.dtype, layers=cfg.num_layers)
        if fuse:
            from repro.axe.passes import fuse_graph
            from repro.axe.propagate import propagate

            if fusion_trace:
                pre = propagate(graph.nodes, graph.seeded_env(), space=space)
                record["unfused_seeded_comm_bytes"] = pre.total_comm_bytes
            graph, rep = fuse_graph(graph)
            record["fusion"] = rep.to_dict()
            if verbose and fusion_trace:
                print(rep.describe())
        ctx = hetero.use_class_table(table) if table else contextlib.nullcontext()
        with ctx:
            res = solve(graph, beam=beam, backend="tpu",
                        compare_seeded=not classes, offload=offload,
                        overlap=overlap)
        if table is not None:
            record["hetero"] = _hetero_record(res, table)
        exe = axe_compile(graph, mesh, plan=res, overlap=overlap)

        api = build_model(cfg)
        params = api.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size, jnp.int32
        )
        t0 = time.time()
        logits = exe(model_inputs(graph, cfg, params), tokens.reshape(-1))
        logits = np.asarray(logits).reshape(batch, seq, -1)
        record["run_s"] = round(time.time() - t0, 2)
        if not np.all(np.isfinite(logits)):
            raise RuntimeError("compiled forward produced non-finite logits")
        ref = np.asarray(
            tf_mod.lm_forward(params, {"tokens": tokens}, cfg, remat=False)
        )
        record["max_abs_diff"] = float(np.max(np.abs(logits - ref)))
        tol = 5e-4 if cfg.dtype == "float32" else 5e-2
        if record["max_abs_diff"] > tol:
            raise RuntimeError(
                f"compiled logits deviate from the reference forward by "
                f"{record['max_abs_diff']:.2e} (> {tol:.0e})"
            )

        # --- cross-check: issued collectives == planned == decisions ---
        observed = list(exe.observed_collectives)
        planned = list(exe.collective_sequence())
        if observed != planned:
            raise RuntimeError(
                f"traced body issued {len(observed)} redistributions but the "
                f"plan records {len(planned)}: {observed} vs {planned}"
            )
        decision_comm = {d.op: d.comm_bytes for d in res.trace}
        mismatches = [
            (e.op.name, e.comm_bytes, decision_comm[e.op.name])
            for e in exe.plan.entries
            if e.op.name in decision_comm
            and e.comm_bytes != decision_comm[e.op.name]
        ]
        if mismatches:
            raise RuntimeError(
                f"plan comm disagrees with the solver Decision trace: "
                f"{mismatches[:4]}"
            )
        # class-crossing Transfer collectives: every one the plan holds
        # must have been issued by the traced body (observed == planned
        # above covers the sequence; count them out explicitly so the
        # hetero smoke leg can assert the offload actually moved bytes)
        transfers = sum(
            1 for (_op, _operand, steps) in planned if "Transfer" in steps
        )
        record["transfers"] = transfers
        parkable = any(
            space.mesh_shape[a] > 1 for a in space.class_axes()
        )
        if offload and parkable and transfers == 0:
            raise RuntimeError(
                f"offload={list(offload)} was requested but the compiled "
                f"plan issued no Transfer collective"
            )
        record.update(
            status="ok",
            fused=fuse,
            overlap=overlap,
            collectives=len(planned),
            comm_bytes=exe.plan.total_comm_bytes,
            solved_comm_bytes=res.comm_bytes,
            seeded_comm_bytes=res.seeded_comm_bytes,
            transfer_bytes=exe.plan.total_transfer_bytes,
        )
        if overlap:
            # the exposed-comm report: which ops hide comm under the
            # previous op's compute and how much stays on the critical
            # path — the overlap-smoke CI leg asserts hidden_ops >= 1
            hidden_ops = [d.op for d in res.trace if d.hidden_comm_s > 0]
            record.update(
                hidden_comm_s=res.hidden_comm_s,
                exposed_comm_s=res.exposed_comm_s,
                hidden_ops=len(hidden_ops),
                prefetched_collectives=sum(
                    len(row.prefetched) for row in exe.lowering_trace
                ),
            )
        if verbose:
            tagf = " fused" if fuse else ""
            tagx = (f" transfers={transfers} "
                    f"xfer={exe.plan.total_transfer_bytes / 2**10:.1f} KiB/dev"
                    if classes else "")
            tago = ""
            if overlap:
                tago = (f" hidden={res.hidden_comm_s * 1e6:.1f}us/"
                        f"exposed={res.exposed_comm_s * 1e6:.1f}us "
                        f"({record['hidden_ops']} ops overlap)")
            print(f"EXEC {arch}{tagf} mesh={space.signature()} "
                  f"max|Δ|={record['max_abs_diff']:.2e} "
                  f"collectives={len(planned)} (issued == planned == decisions) "
                  f"comm={exe.plan.total_comm_bytes / 2**10:.1f} KiB/dev{tagx}{tago} OK")
            if overlap:
                for d in res.trace:
                    if d.hidden_comm_s > 0:
                        print(f"  overlap {d.op}: comm={d.comm_bytes} B/dev "
                              f"hidden={d.hidden_comm_s * 1e6:.2f}us "
                              f"exposed={d.exposed_comm_s * 1e6:.2f}us "
                              f"(charged max(comm, compute))")
            if "hetero" in record:
                _print_hetero(record)
    except Exception as e:  # record an error row; never abort a sweep
        record.update(status="error", error=f"{type(e).__name__}: {e}")
        record["traceback"] = traceback.format_exc()[-2000:]
    return record


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    fsdp: bool = True,
    zero1: bool = True,
    microbatches: int = 1,
    compress_pod_grads: bool = False,
    remat: bool = True,
    remat_policy: str = "full",
    dump_hlo: str = None,
):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    api = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_shape = axe_rules.mesh_shape_of(mesh)
    space = PhysicalSpace.from_mesh_shape(mesh_shape)
    n_chips = 512 if multi_pod else 256

    from repro.train import act_sharding
    from repro.models import transformer as _tf

    act_sharding.set_mesh(mesh)  # activation constraints (Axe logical dims)
    _tf.set_remat_policy(remat_policy if remat else "none")
    # per-arch layout policy: VLM keeps a replicated-seq residual stream
    # (SP + the patch concat measured net-negative: EXPERIMENTS §Perf)
    act_sharding.set_logical_overrides(
        {"seq_res": (None,)} if cfg.family == "vlm" else None
    )

    t0 = time.time()
    params_s = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    p_specs = axe_rules.param_specs(params_s, space, fsdp=fsdp)
    p_sh = axe_rules.sharding_tree(p_specs, mesh)

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "kind": shape.kind, "batch": shape.batch, "seq": shape.seq,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        # compiled on CPU host devices; only the roofline is priced for
        # the target chip (nothing here ran on it)
        "backend": "cpu", "target_kind": rl.TARGET_KIND,
        "options": {"fsdp": fsdp, "zero1": zero1, "microbatches": microbatches,
                    "compress_pod_grads": compress_pod_grads, "remat": remat},
    }

    # propagated per-layer layout plan (AxeSpec redistributions + comm
    # bytes) — recorded alongside the compiled analyses so one dry-run
    # row carries both the planned and the XLA-observed collectives
    plan_rec = layout_plan_cell(arch, shape_name, multi_pod, verbose=False)
    if plan_rec.get("status") == "ok":
        record["layout_plan"] = plan_rec["layout_plan"]

    if shape.kind == "train":
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.optim.adamw import AdamWState

        opt = AdamW(learning_rate=1e-4)
        opt_s = jax.eval_shape(opt.init, params_s)
        o_specs = axe_rules.opt_specs(p_specs, zero1=zero1)
        o_sh = axe_rules.sharding_tree(o_specs, mesh)
        scalar_sh = NamedSharding(mesh, P())
        state_s = TrainState(params_s, opt_s, jax.ShapeDtypeStruct((), jnp.int32))
        state_sh = TrainState(p_sh, AdamWState(mu=o_sh, nu=o_sh, count=scalar_sh), scalar_sh)

        batch_s = api.train_batch_specs(shape)
        b_specs = axe_rules.batch_specs(batch_s, space)
        b_sh = {k: axe_lower.to_named_sharding(s_, mesh) for k, s_ in b_specs.items()}

        step = make_train_step(
            api.loss_fn, opt, microbatches=microbatches,
            compress_pod_grads=compress_pod_grads,
        )
        fn = jax.jit(
            step,
            in_shardings=(state_sh, b_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        with mesh:
            lowered = fn.lower(state_s, batch_s)
    elif shape.kind == "prefill":
        cache_s = jax.eval_shape(lambda: api.cache_init(shape.batch, shape.seq))
        c_sh = axe_rules.sharding_tree(axe_rules.cache_specs(cache_s, space), mesh)
        batch_s = api.train_batch_specs(shape)
        del batch_s["labels"]
        b_specs = axe_rules.batch_specs(batch_s, space)
        b_sh = {k: axe_lower.to_named_sharding(s_, mesh) for k, s_ in b_specs.items()}
        fn = jax.jit(
            api.prefill,
            in_shardings=(p_sh, b_sh, c_sh),
            out_shardings=(None, c_sh),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = fn.lower(params_s, batch_s, cache_s)
    else:  # decode
        cache_s = jax.eval_shape(lambda: api.cache_init(shape.batch, shape.seq))
        c_sh = axe_rules.sharding_tree(axe_rules.cache_specs(cache_s, space), mesh)
        tok_s = api.decode_token_specs(shape)["tokens"]
        pos_s = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(
            api.decode_step,
            in_shardings=(p_sh, None, c_sh, None),
            out_shardings=(None, c_sh),
            donate_argnums=(2,),
        )
        with mesh:
            lowered = fn.lower(params_s, tok_s, cache_s, pos_s)

    record["lower_s"] = round(time.time() - t0, 2)
    t1 = time.time()
    compiled = lowered.compile()
    record["compile_s"] = round(time.time() - t1, 2)

    # --- analyses ---
    try:
        mem = compiled.memory_analysis()
        record["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not support it
        record["memory"] = {"error": str(e)}

    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        record["cost"] = {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float)) and (
                              k in ("flops", "bytes accessed", "optimal_seconds")
                              or k.startswith("bytes accessed"))}
    except Exception as e:
        record["cost"] = {"error": str(e)}
        cost = {}

    hlo = compiled.as_text()
    if dump_hlo:
        with open(dump_hlo, "w") as f:
            f.write(hlo)
    mf = rl.model_flops(cfg, shape.kind, shape.batch, shape.seq)
    try:
        terms = rl.derive_terms(
            hlo_text=hlo, n_chips=n_chips,
            model_flops_total=mf, kind=rl.TARGET_KIND, pod_axis=multi_pod,
        )
        record["roofline"] = terms.to_dict()
    except Exception as e:
        record["roofline"] = {"error": str(e)}
    record["status"] = "ok"
    act_sharding.set_mesh(None)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-pod-grads", action="store_true")
    ap.add_argument("--dump-hlo", default=None, help="write compiled HLO text here")
    ap.add_argument("--remat-policy", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--layout-plan", action="store_true",
                    help="report the propagated AxeSpec layout plan only (no lowering, no devices)")
    ap.add_argument("--solve", action="store_true",
                    help="solve the whole-model layout (beam search over the "
                         "spec algebra) instead of seeding it; deviceless")
    ap.add_argument("--solve-compare", action="store_true",
                    help="solve and report solved vs rule-seeded comm bytes; "
                         "sweeps every model-zoo config when --arch is omitted; "
                         "exits nonzero if any solved plan out-spends its seed")
    ap.add_argument("--solve-trace", action="store_true",
                    help="with --solve: print the per-op decision trace")
    ap.add_argument("--execute", action="store_true",
                    help="compile the solved plan (axe.compile) and RUN it "
                         "on this host's devices: reference-numerics check "
                         "+ issued-vs-planned collective cross-check "
                         "(smoke-reduced config)")
    ap.add_argument("--exec-batch", type=int, default=4)
    ap.add_argument("--exec-seq", type=int, default=32)
    ap.add_argument("--fuse", dest="fuse", action="store_true", default=False,
                    help="with --solve/--execute: rewrite the graph through "
                         "the fusion passes (repro.axe.passes) before "
                         "solving — epilogue chains run fused")
    ap.add_argument("--no-fuse", dest="fuse", action="store_false",
                    help="disable the fusion passes (the default; the "
                         "explicit flag pins a sweep row)")
    ap.add_argument("--fusion-trace", action="store_true",
                    help="with --fuse: print/record which patterns fired, "
                         "the intermediates eliminated, and comm bytes "
                         "before/after the rewrite (implies --fuse)")
    ap.add_argument("--overlap", dest="overlap", action="store_true",
                    default=False,
                    help="with --solve/--execute: charge overlappable comm "
                         "at max(comm, compute) in the solver objective and "
                         "run the compute/communication-overlap schedule "
                         "(prefetched collectives) in the executable; "
                         "reports hidden vs exposed comm seconds "
                         "(docs/overlap.md)")
    ap.add_argument("--no-overlap", dest="overlap", action="store_false",
                    help="synchronous collectives (the default; the "
                         "explicit flag pins a sweep row)")
    ap.add_argument("--cotune", action="store_true",
                    help="with --solve: run the solve<->tune fixed-point "
                         "loop (repro.axe.cotune) instead of a one-shot "
                         "solve — measured schedule timings from the "
                         "ambient cache correct the rooflines and the "
                         "layout is re-solved until the plan stops "
                         "changing; implies --solve (docs/cotune.md)")
    ap.add_argument("--cotune-measure", action="store_true",
                    help="with --cotune: autotune the measurable local "
                         "problems in-loop (writes the schedule cache)")
    ap.add_argument("--cotune-iters", type=int, default=4,
                    help="max solve iterations of the cotune loop")
    ap.add_argument("--layers", type=int, default=2,
                    help="decoder depth of the solved model graph")
    ap.add_argument("--beam", type=int, default=4, help="layout solver beam width")
    ap.add_argument("--classes", default=None,
                    help="with --solve/--execute: heterogeneous device "
                         "classes as name=flops:mem_bw:link_bw[:capacity] "
                         "pairs (e.g. host=0:100e9:16e9,accel=197e12:819e9:"
                         "200e9); carves a host-class mesh axis and reports "
                         "per-class placement + transfer bytes")
    ap.add_argument("--host-degree", type=int, default=2,
                    help="with --classes: size of the carved host mesh axis")
    ap.add_argument("--offload", default=None,
                    help="with --classes: comma-separated input names (full "
                         "or basename, e.g. embed) the solver must park on "
                         "the host class")
    args = ap.parse_args()
    if args.fusion_trace:
        args.fuse = True
    if args.cotune_measure:
        args.cotune = True
    if args.cotune and not (args.solve or args.solve_compare):
        args.solve = True
    if args.offload and not args.classes:
        ap.error("--offload requires --classes")
    offload = tuple(filter(None, (args.offload or "").split(",")))

    cells = []
    if args.execute:
        # execute_cell runs one smoke-shaped cell per arch (shape/mesh
        # are fixed by the host's devices, so sweeping them is a no-op)
        for arch in ([args.arch] if args.arch else ARCH_IDS):
            cells.append((arch, args.shape or "train_4k", args.mesh))
    elif args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                for mesh in ("single", "multi"):
                    cells.append((arch, shape, mesh))
    elif (args.solve or args.solve_compare) and not args.arch:
        # the solver acceptance sweep: every model-zoo config
        for arch in ARCH_IDS:
            cells.append((arch, args.shape or "train_4k", args.mesh))
    else:
        assert args.arch and args.shape
        cells.append((args.arch, args.shape, args.mesh))

    out_f = open(args.out, "a") if args.out else None
    failures = 0
    improved = 0
    for arch, shape, mesh in cells:
        if args.execute:
            rec = execute_cell(
                arch, batch=args.exec_batch, seq=args.exec_seq, beam=args.beam,
                fuse=args.fuse, fusion_trace=args.fusion_trace,
                classes=args.classes, host_degree=args.host_degree,
                offload=offload, overlap=args.overlap,
            )
            line = json.dumps(rec)
            if rec["status"] == "error":
                failures += 1
                print(line)
            if out_f:
                out_f.write(line + "\n")
                out_f.flush()
            continue
        if args.solve or args.solve_compare:
            rec = solve_cell(
                arch, shape, mesh == "multi",
                layers=args.layers, beam=args.beam,
                verbose=args.solve and not args.solve_compare,
                trace=args.solve_trace,
                fuse=args.fuse, fusion_trace=args.fusion_trace,
                classes=args.classes, host_degree=args.host_degree,
                offload=offload, overlap=args.overlap,
                cotune=args.cotune, cotune_measure=args.cotune_measure,
                cotune_iters=args.cotune_iters,
            )
            line = json.dumps(rec)
            if rec["status"] != "ok":
                failures += 1
                print(line)
            elif args.cotune and not args.classes:
                s, c = rec["solve"], rec["cotune"]
                if c["final_objective_s"] > c["iter0_objective_s"] * (1 + 1e-9):
                    failures += 1
                print(f"COTUNE {arch} {shape} {mesh} "
                      f"iters={c['iters']} converged={c['converged']} "
                      f"flipped={c['flipped']} "
                      f"J={1e3 * c['iter0_objective_s']:.2f}->"
                      f"{1e3 * c['final_objective_s']:.2f} ms "
                      f"comm={s['comm_bytes'] / 2**20:.1f} MiB/dev "
                      f"{'OK' if c['final_objective_s'] <= c['iter0_objective_s'] * (1 + 1e-9) else 'WORSE'}")
            elif args.classes:
                # no seeded budget under a class table (the rules never
                # park) — report placement + transfer spend instead
                s, het = rec["solve"], rec["hetero"]
                print(f"SOLVE {arch} {shape} {mesh} classes "
                      f"solved={s['comm_bytes'] / 2**20:.1f} MiB/dev "
                      f"xfer={s['transfer_bytes'] / 2**20:.1f} MiB/dev "
                      f"parked={len(het['parked'])} "
                      f"J={1e3 * s['objective_s']:.2f} ms OK")
            else:
                s = rec["solve"]
                solved, seeded = s["comm_bytes"], s["seeded_comm_bytes"]
                if solved > seeded:
                    failures += 1
                if solved < seeded:
                    improved += 1
                print(f"SOLVE {arch} {shape} {mesh} "
                      f"seeded={seeded / 2**20:.1f} MiB/dev "
                      f"solved={solved / 2**20:.1f} MiB/dev "
                      f"({100 * (1 - solved / seeded) if seeded else 0:+.1f}% saved) "
                      f"J={1e3 * s['seeded_objective_s']:.2f}->"
                      f"{1e3 * s['objective_s']:.2f} ms "
                      f"{'OK' if solved <= seeded else 'WORSE'}")
            if out_f:
                out_f.write(line + "\n")
                out_f.flush()
            continue
        if args.layout_plan:
            rec = layout_plan_cell(arch, shape, mesh == "multi")
            line = json.dumps(rec)
            if rec["status"] != "ok":
                failures += 1
                print(line)
            else:
                lp = rec["layout_plan"]
                n_steps = sum(len(e["steps"]) for e in lp["entries"])
                print(f"PLAN {arch} {shape} {mesh} ops={len(lp['entries'])} "
                      f"redistributions={n_steps} "
                      f"comm={lp['total_comm_bytes']/2**20:.1f} MiB/device")
            if out_f:
                out_f.write(line + "\n")
                out_f.flush()
            continue
        try:
            rec = lower_cell(
                arch, shape, mesh == "multi",
                fsdp=not args.no_fsdp, zero1=not args.no_zero1,
                microbatches=args.microbatches,
                compress_pod_grads=args.compress_pod_grads,
                remat=not args.no_remat,
                remat_policy=args.remat_policy,
                dump_hlo=args.dump_hlo,
            )
        except Exception as e:
            failures += 1
            rec = {"arch": arch, "shape": shape, "mesh": mesh,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
        line = json.dumps(rec)
        print(line if rec["status"] != "ok" else
              f"OK {arch} {shape} {mesh} [cpu host devices; roofline "
              f"priced for {rec.get('target_kind')}] lower={rec.get('lower_s')}s "
              f"compile={rec.get('compile_s')}s "
              f"bottleneck={rec.get('roofline', {}).get('bottleneck')}")
        if rec["status"] == "ok":
            mem = rec.get("memory", {})
            if mem.get("peak_bytes"):
                print(f"   memory: peak={mem['peak_bytes']/2**30:.2f} GiB/device "
                      f"args={mem['argument_bytes']/2**30:.2f} GiB")
            cost = rec.get("cost", {})
            if "flops" in cost:
                print(f"   cost: flops/dev={cost['flops']:.3e}")
        if out_f:
            out_f.write(line + "\n")
            out_f.flush()
    if out_f:
        out_f.close()
    if args.solve_compare and not args.classes and len(cells) > 1 and improved == 0:
        print("SOLVE-COMPARE: no config strictly improved over its seeded plan")
        failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
