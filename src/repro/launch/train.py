"""Production training launcher.

    python -m repro.launch.train --arch qwen3-4b --steps 100 \
        --mesh-data 16 --mesh-model 16 [--multi-pod] [--smoke]

On real TPU pods this is launched once per host (jax.distributed
initializes from the TPU environment); on this CPU container use
``--smoke`` (reduced config, local mesh) to run end-to-end.
"""
from __future__ import annotations

import argparse

import jax

from repro.axe import rules as axe_rules
from repro.axe.spec import PhysicalSpace
from repro.checkpoint.manager import CheckpointManager
from repro.configs import ARCH_IDS, get_config, smoke_variant
from repro.data.pipeline import SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model_zoo import build_model
from repro.optim.adamw import AdamW
from repro.optim.schedule import warmup_cosine
from repro.train import act_sharding
from repro.train.train_loop import Trainer, init_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--mesh-data", type=int, default=0, help="0 = all local devices")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--compress-pod-grads", action="store_true")
    ap.add_argument("--solve", action="store_true",
                    help="solve the layout (repro.axe.solve) and run the "
                         "forward pass through the compiled executable "
                         "(axe.compile) instead of the module wiring")
    ap.add_argument("--solve-beam", type=int, default=4)
    ap.add_argument("--cotune", action="store_true",
                    help="with --solve: run the solve<->tune fixed-point "
                         "loop (repro.axe.cotune) — measured schedule "
                         "timings correct the solver's rooflines and the "
                         "layout is re-solved to a fixed point "
                         "(docs/cotune.md)")
    ap.add_argument("--cotune-iters", type=int, default=4)
    ap.add_argument("--fuse", action="store_true",
                    help="with --solve: rewrite the graph through the "
                         "fusion passes (repro.axe.passes) before "
                         "solving — epilogue chains run fused")
    ap.add_argument("--no-compiled-forward", action="store_true",
                    help="with --solve: keep the legacy module-wired "
                         "forward and only consume the solved param "
                         "placements (deprecated path)")
    ap.add_argument("--offload-opt", action="store_true",
                    help="park the optimizer moments on a host-class "
                         "mesh axis (repro.axe.hetero): carves a host "
                         "memory tier out of the device budget and "
                         "shards mu/nu over it, freeing accelerator HBM")
    ap.add_argument("--host-degree", type=int, default=2,
                    help="with --offload-opt: size of the carved host "
                         "mesh axis (must divide the device count; "
                         "degrades to 1 — a no-op — when it does not)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    print(f"arch={cfg.name} params={cfg.param_count()/1e9:.2f}B "
          f"(active {cfg.active_param_count()/1e9:.2f}B)")

    n_dev = len(jax.devices())
    from repro import compat

    if args.offload_opt:
        from repro.axe import hetero

        host_deg = (args.host_degree
                    if n_dev % (args.mesh_model * args.host_degree) == 0
                    else 1)
        data_deg = args.mesh_data or (n_dev // (args.mesh_model * host_deg))
        mesh = compat.make_mesh(
            (data_deg, args.mesh_model, host_deg), ("data", "model", "host")
        )
        space = PhysicalSpace.from_mesh_shape(
            axe_rules.mesh_shape_of(mesh), classes={"host": hetero.HOST_CLASS}
        )
    else:
        data_deg = args.mesh_data or (n_dev // args.mesh_model)
        mesh = compat.make_mesh((data_deg, args.mesh_model), ("data", "model"))
        space = PhysicalSpace.from_mesh_shape(axe_rules.mesh_shape_of(mesh))
    act_sharding.set_mesh(mesh if n_dev > 1 else None)

    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    opt = AdamW(learning_rate=warmup_cosine(args.lr, 20, args.steps))
    state = init_state(params, opt)

    plan = None
    executable = None
    if args.solve:
        from repro.axe.compile import SUPPORTED_FAMILIES
        from repro.axe.compile import compile as axe_compile
        from repro.axe.graphs import model_graph
        from repro.axe.solve import solve

        compiled = not args.no_compiled_forward and cfg.family in SUPPORTED_FAMILIES
        # one solve serves both param placement and the compiled
        # forward: full depth when the executable consumes it, the
        # cheap 2-layer layout study otherwise. The executable sees
        # per-microbatch activations (make_train_step splits the global
        # batch before the loss), so the graph is built at that size.
        assert args.global_batch % max(args.microbatches, 1) == 0, (
            args.global_batch, args.microbatches)
        mb_batch = args.global_batch // max(args.microbatches, 1)
        gs = model_graph(
            cfg, mb_batch, args.seq, space,
            dtype=cfg.dtype, layers=cfg.num_layers if compiled else 2,
        )
        if args.fuse:
            from repro.axe.passes import fuse_graph

            gs, rep = fuse_graph(gs)
            print(f"fusion: {len(rep.patterns_fired)} patterns fired, "
                  f"{len(rep.eliminated)} intermediates eliminated")
        if args.cotune:
            from repro.axe.cotune import cotune as axe_cotune

            ct = axe_cotune(gs, beam=args.solve_beam, backend="tpu",
                            max_iters=args.cotune_iters)
            res = ct.result
            print(ct.describe())
        else:
            res = solve(gs, beam=args.solve_beam, backend="tpu")
        plan = axe_rules.from_plan(res)
        print(f"layout solver: comm {res.seeded_comm_bytes / 2**20:.1f} -> "
              f"{res.comm_bytes / 2**20:.1f} MiB/dev "
              f"({100 * (res.comm_improvement or 0):.1f}% saved, "
              f"beam={res.beam}, {res.explored} states)")
        if not compiled:
            import warnings

            warnings.warn(
                "training on the module-wired forward under --solve is "
                "deprecated; the compiled executable (axe.compile) is the "
                "supported path (docs/compile.md)",
                DeprecationWarning, stacklevel=1,
            )
        else:
            # forward pass from the compiled graph under the SAME
            # solved plan the params are placed with: its collectives
            # run for real, fwd and bwd
            executable = axe_compile(gs, mesh, plan=res)
            print(f"compiled forward: {len(executable.plan.entries)} ops, "
                  f"{len(executable.collective_sequence())} redistributions")

    p_specs = axe_rules.param_specs(params, space, fsdp=n_dev > 1, plan=plan)
    state_sh = None
    if n_dev > 1:
        from repro.optim.adamw import AdamWState
        from jax.sharding import NamedSharding, PartitionSpec as P

        o_specs = axe_rules.opt_specs(
            p_specs,
            offload_axes=("host",) if args.offload_opt else (),
        )
        if args.offload_opt:
            from repro.axe import hetero

            leaves = jax.tree.leaves(
                o_specs, is_leaf=lambda x: hasattr(x, "placement")
            )
            parked = [s for s in leaves if hetero.is_parked(s)]
            host_b = sum(
                s.bytes_per_device(hetero.itemsize_of(s.dtype)) for s in parked
            )
            # mu and nu share the spec tree, so each parked leaf is held
            # twice in the AdamW state
            print(f"offload-opt: parked {len(parked)}/{len(leaves)} moment "
                  f"leaves on the host class "
                  f"({2 * host_b / 2**20:.1f} MiB/host-device)")
        p_sh = axe_rules.sharding_tree(p_specs, mesh)
        o_sh = axe_rules.sharding_tree(o_specs, mesh)
        scalar = NamedSharding(mesh, P())
        state_sh = type(state)(p_sh, AdamWState(o_sh, o_sh, scalar), scalar)
        state = jax.device_put(state, state_sh)

    data = SyntheticLMData(
        cfg.vocab_size, args.seq, args.global_batch,
        frontend=cfg.frontend, num_patches=cfg.num_patches,
        encoder_seq=cfg.encoder_seq, d_model=cfg.d_model, dtype=cfg.dtype,
    )
    if executable is not None:
        from repro.train.train_loop import make_compiled_train_step

        step_fn = make_compiled_train_step(
            executable, cfg, opt, microbatches=args.microbatches,
            compress_pod_grads=args.compress_pod_grads,
        )
    else:
        step_fn = make_train_step(
            api.loss_fn, opt, microbatches=args.microbatches,
            compress_pod_grads=args.compress_pod_grads,
        )
    jit_kwargs = {}
    if state_sh is not None:
        jit_kwargs = dict(in_shardings=(state_sh, None), out_shardings=(state_sh, None))
    trainer = Trainer(
        train_step=jax.jit(step_fn, donate_argnums=(0,), **jit_kwargs),
        data=data,
        checkpoint_manager=CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None,
        checkpoint_every=args.ckpt_every,
        step_deadline_s=600.0,
        on_straggler=lambda s, dt: print(f"[watchdog] step {s}: {dt:.1f}s"),
    )
    state = trainer.restore_or_init(state)
    with mesh:
        state, hist = trainer.run(state, args.steps)
    print(f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
