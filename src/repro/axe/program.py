"""``axe.program`` — the multi-granularity kernel DSL (paper §3.2,
Fig. 8; docs/kernel-dsl.md).

A :class:`Program` is a named graph of scope-tagged stages
(:mod:`repro.axe.stages`): MESH stages issue collectives inside
``shard_map`` bodies, GRID stages build Pallas launches through
``axe.lower.block_lowering``, BLOCK stages are plain jnp bodies on VMEM
refs. A kernel is written once as such a graph; *where* it runs comes
exclusively from operand/result :class:`~repro.axe.spec.AxeSpec`s and
the current execution scope — never from hand-plumbed ``block_*``
kwargs or per-op collective code.

Schedules attach per stage: a tunable stage resolves its
:class:`~repro.tune.schedule.Schedule` under the key
``program_name/stage_name`` through the one planner/autotuner path
(``repro.tune.get_schedule``), so in-kernel block sizes and
cross-device schedule choice (ring vs psum_scatter) are the same kind
of decision. Resolution is lazy — a stage that falls back (wrong rank,
infeasible tile) before touching ``ctx.schedule`` never invokes the
planner.

Minimal program::

    from repro import axe
    from repro.core.scopes import Scope

    scale = axe.program("scale_rows")

    @scale.stage("rows", scope=Scope.GRID, entry=True,
                 blocks=(("bt", 256),), variants=("kernel",))
    def _rows(ctx, x):
        bt = min(ctx.block("bt"), x.shape[0])
        low = axe.block_lowering(x.shape, (bt, x.shape[1]), x.dtype,
                                 index_map=lambda i: (i, 0), op="scale_rows")
        launch = ctx.jit((bt,), lambda: lambda x: ctx.pallas_call(
            lambda x_ref, o_ref: ctx.run("scale", x_ref, o_ref),
            grid=low.grid[:1], in_specs=[low.spec], out_specs=low.spec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x))
        return launch(x)

    @scale.stage("scale", scope=Scope.BLOCK)
    def _scale(ctx, x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple, Union

import jax

from repro.core.scopes import Scope, current_scope, scope
from repro.axe.stages import Stage, StageError, normalize_blocks

ScheduleLike = Union[str, "Any"]  # Schedule | parseable spec string


class ProgramError(StageError):
    pass


class KernelFallbackWarning(UserWarning):
    """A compiled (non-interpret) dispatch that resolved to a kernel ran
    its XLA body instead, because the kernel's tile was infeasible for
    these shapes. Raised to an error where a run must prove it executed
    the kernels (``chip_smoke.py``)."""


#: process-wide registry: program name → Program (latest definition wins,
#: so module reloads in tests do not error)
PROGRAMS: Dict[str, "Program"] = {}


def get_program(name: str) -> "Program":
    try:
        return PROGRAMS[name]
    except KeyError:
        raise ProgramError(
            f"no program named {name!r} (registered: {sorted(PROGRAMS)})"
        ) from None


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """A fused epilogue a GRID stage applies to its accumulated output
    tile before the HBM writeback — the BLOCK-scope tail of a
    ``repro.axe.passes`` epilogue fusion. ``body(tile, *extras)`` maps
    the f32 accumulator tile plus per-tile slices of ``args`` (extra
    operands, tiled like the output) to the final tile. ``tag`` is the
    chain's identity and feeds the schedule key: a fused launch must
    never share a compiled schedule (or jit cache slot) with the plain
    one. ``full_rows=True`` declares the body reads whole rows (a norm
    epilogue), so lowerings must keep the tile's last dim unsplit."""

    tag: str
    body: Callable
    args: Tuple[Any, ...] = ()
    full_rows: bool = False


@dataclasses.dataclass(frozen=True)
class _CallOptions:
    """Per-invocation options threaded through the stage graph."""

    schedules: Tuple[Tuple[str, ScheduleLike], ...] = ()  # stage name → override
    arg_specs: Tuple[Any, ...] = ()                       # operand AxeSpecs
    interpret: bool = False
    epilogue: Optional[Epilogue] = None
    overlap: bool = False   # MESH stages pick async/double-buffered collectives
    # entry-stage-only overrides: (stage_name, schedule, blocks, impl)
    entry: Optional[Tuple[str, Optional[Any], Optional[Dict[str, int]], Optional[str]]] = None

    def schedule_override(self, stage_name: str):
        return dict(self.schedules).get(stage_name)

    def child(self) -> "_CallOptions":
        """Options for stages invoked via ``ctx.run`` — entry overrides
        do not cascade."""
        return dataclasses.replace(self, entry=None)


class StageContext:
    """Handed to every stage body as its first argument: the resolved
    schedule surface plus the helpers a stage lowers through."""

    def __init__(self, program: "Program", stage: Stage, args, kw, opts: _CallOptions):
        self.program = program
        self.stage = stage
        self._args = args
        self._kw = kw
        self._opts = opts
        self._schedule: Optional[Any] = None
        self._resolved = False

    # -- schedule surface ----------------------------------------------
    @property
    def op(self) -> str:
        """This stage's schedule key, ``program_name/stage_name``."""
        return self.program.stage_key(self.stage.name)

    @property
    def schedule(self):
        """The stage's resolved :class:`~repro.tune.schedule.Schedule`
        (lazy: the planner only runs if a body asks)."""
        if not self._resolved:
            self._schedule = self.program._resolve_schedule(
                self.stage, self._args, self._kw, self._opts
            )
            self._resolved = True
        return self._schedule

    @property
    def impl(self) -> Optional[str]:
        s = self.schedule
        return s.impl if s is not None else None

    @property
    def pinned(self) -> bool:
        """True when this stage's schedule was explicitly supplied by
        the caller (``schedule=`` / ``schedules=`` / ``blocks=`` /
        ``impl=``) rather than resolved by the tune layer. Pinned
        schedules fail loudly (TilingError propagates); resolved ones
        may fall back to a coarser variant."""
        if self._opts.schedule_override(self.stage.name) is not None:
            return True
        e = self._opts.entry
        return bool(
            e and e[0] == self.stage.name
            and (e[1] is not None or e[2] or e[3] is not None)
        )

    def block(self, name: str, default: Optional[int] = None) -> Optional[int]:
        """Resolved block size for one tunable parameter (falls back to
        the stage's declared default, then ``default``)."""
        declared = self.stage.default_blocks().get(name, default)
        s = self.schedule
        return s.block(name, declared) if s is not None else declared

    @property
    def interpret(self) -> bool:
        return self._opts.interpret

    @property
    def arg_specs(self) -> Tuple[Any, ...]:
        return self._opts.arg_specs

    @property
    def epilogue(self) -> Optional[Epilogue]:
        """The fused epilogue of this invocation, if any — stages that
        support in-kernel application consume it; others ignore it and
        the caller applies the chain functionally on their result."""
        return self._opts.epilogue

    @property
    def overlap(self) -> bool:
        """True when the caller asked MESH stages for async/double-
        buffered collective issue (ppermute rings instead of monolithic
        gathers — ``collective.lower_step(..., overlap=True)``), so
        collective latency can hide under the following GRID compute.
        Results are bit-identical either way (docs/overlap.md)."""
        return self._opts.overlap

    # -- composition ----------------------------------------------------
    def run(self, stage_name: str, *args, **kw):
        """Invoke another stage of this program (scope-validated; only
        same-or-finer scopes are reachable)."""
        return self.program._run(stage_name, args, kw, self._opts.child())

    # -- lowering helpers -----------------------------------------------
    def pallas_call(self, body, *, grid, in_specs, out_specs, out_shape,
                    scratch_shapes=None, dimension_semantics=None,
                    num_scalar_prefetch: int = 0):
        """``pl.pallas_call`` with this invocation's interpret flag and
        the TPU compiler params applied. ``num_scalar_prefetch=n`` passes
        the first ``n`` operands through SMEM (TPU scalar prefetch): the
        body and every index map receive them as leading refs."""
        from jax.experimental import pallas as pl
        import jax.experimental.pallas.tpu as pltpu

        # the stage key names the kernel, so HLO and profiler traces can
        # find it (``flash_attention_decode``, ``matmul_tile``, ...)
        kwargs = dict(out_shape=out_shape, interpret=self.interpret,
                      name=self.op.replace("/", "_"))
        if num_scalar_prefetch:
            kwargs["grid_spec"] = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=num_scalar_prefetch, grid=grid,
                in_specs=list(in_specs), out_specs=out_specs,
                scratch_shapes=list(scratch_shapes or ()),
            )
        else:
            kwargs.update(grid=grid, in_specs=list(in_specs), out_specs=out_specs)
            if scratch_shapes:
                kwargs["scratch_shapes"] = list(scratch_shapes)
        if dimension_semantics is not None:
            kwargs["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=dimension_semantics
            )
        return pl.pallas_call(body, **kwargs)

    def jit(self, static_key: Tuple, make: Callable[[], Callable]):
        """Memoized ``jax.jit`` launcher for this stage. ``static_key``
        must cover every trace-relevant value that is not an argument
        (block sizes, flags, dtypes); the interpret flag is appended
        automatically. Shapes need not be included — jit retraces per
        shape."""
        fn = self.program._jitted(
            self.stage.name, tuple(static_key) + (self.interpret,), make
        )
        # the launcher closure typically captures this context and is
        # cached for the program's lifetime: drop the operand references
        # so the jit cache can never retain the first call's arrays
        # (schedule resolution needs them, so force it first)
        if self.stage.tunable:
            _ = self.schedule
        self._args = ()
        self._kw = {}
        return fn


class Program:
    """A named, callable graph of scope-tagged stages.

    Calling the program dispatches on ``current_scope()`` through the
    program's dispatch table (finer scopes pick finer stages) and runs
    the chosen stage; stages invoke other stages with ``ctx.run``.
    """

    def __init__(self, name: str, doc: Optional[str] = None):
        self.name = name
        self.doc = doc
        self.stages: Dict[str, Stage] = {}
        self._entry: Optional[str] = None
        self._dispatch: Dict[Scope, str] = {}
        self._jit: Dict[Tuple, Callable] = {}
        self._jit_lock = threading.Lock()
        PROGRAMS[name] = self

    # -- declaration ----------------------------------------------------
    def stage(
        self,
        name: str,
        *,
        scope: Union[Scope, str],
        blocks: Sequence[Tuple[str, int]] = (),
        variants: Sequence[str] = (),
        key: Optional[Callable] = None,
        flops: Optional[Callable] = None,
        entry: bool = False,
        dispatch: Sequence[Union[Scope, str]] = (),
    ) -> Callable:
        """Decorator registering one stage. ``entry=True`` marks the
        default stage (else: first registered). ``dispatch`` lists the
        execution scopes that select this stage when the *program* is
        called. Tunable stages (blocks or variants) are registered with
        the tune layer under ``program_name/stage_name``."""
        scope_ = Scope(scope) if isinstance(scope, str) else scope
        blocks_ = normalize_blocks(blocks)
        variants_ = tuple(variants)

        def deco(fn: Callable) -> Callable:
            st = Stage(name, scope_, fn, blocks_, variants_, key, flops)
            self.stages[name] = st
            if entry or self._entry is None:
                self._entry = name
            for s in dispatch:
                self._dispatch[Scope(s) if isinstance(s, str) else s] = name
            if st.tunable:
                from repro.tune import schedule as tsched

                tsched.register_stage_op(
                    self.stage_key(name), variants_ or ("kernel",), blocks_
                )
            return fn

        return deco

    def stage_key(self, stage_name: str) -> str:
        """The schedule/cache key prefix for one stage."""
        return f"{self.name}/{stage_name}"

    @property
    def entry_stage(self) -> str:
        if self._entry is None:
            raise ProgramError(f"program {self.name!r} has no stages")
        return self._entry

    def dispatch_stage(self, scope_: Optional[Scope] = None) -> str:
        scope_ = scope_ or current_scope()
        return self._dispatch.get(scope_, self.entry_stage)

    # -- execution ------------------------------------------------------
    def __call__(
        self,
        *args,
        stage: Optional[str] = None,
        schedule: Optional[ScheduleLike] = None,
        schedules: Optional[Mapping[str, ScheduleLike]] = None,
        blocks: Optional[Mapping[str, int]] = None,
        impl: Optional[str] = None,
        arg_specs: Sequence[Any] = (),
        interpret: Optional[bool] = None,
        epilogue: Optional[Epilogue] = None,
        overlap: bool = False,
        **kw,
    ):
        """Run the program on ``args``.

        ``arg_specs`` — operand :class:`AxeSpec`s, the only placement
        input: they key the schedule cache (canonical layout signature)
        and drive MESH-stage collective plans. ``schedule`` pins the
        dispatched stage's schedule; ``schedules`` pins per stage by
        name; ``blocks`` overrides individual block sizes (forcing the
        kernel-ish variant, legacy ``block_*`` compatibility); ``impl``
        restricts the dispatched stage to one variant. ``epilogue``
        attaches a fused :class:`Epilogue` — its tag joins the schedule
        key, so fused and plain launches tune and cache independently.
        ``overlap`` asks MESH stages for async/double-buffered collective
        issue (see :attr:`StageContext.overlap`).
        """
        name = stage or self.dispatch_stage()
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        opts = _CallOptions(
            schedules=tuple((schedules or {}).items()),
            arg_specs=tuple(arg_specs or ()),
            interpret=bool(interpret),
            epilogue=epilogue,
            overlap=bool(overlap),
            entry=(name, schedule, dict(blocks) if blocks else None, impl),
        )
        return self._run(name, args, kw, opts)

    def _run(self, name: str, args, kw, opts: _CallOptions):
        st = self.stages.get(name)
        if st is None:
            raise ProgramError(
                f"program {self.name!r} has no stage {name!r} "
                f"(stages: {sorted(self.stages)})"
            )
        st.validate_entry(current_scope(), self.name)
        ctx = StageContext(self, st, args, kw, opts)
        with scope(st.scope):
            return st.body(ctx, *args, **kw)

    # -- schedule resolution --------------------------------------------
    def _resolve_schedule(self, st: Stage, args, kw, opts: _CallOptions):
        from repro import tune

        if not st.tunable:
            return None
        op = self.stage_key(st.name)

        def as_schedule(spec):
            return tune.Schedule.parse(spec, op=op) if isinstance(spec, str) else spec

        override = opts.schedule_override(st.name)
        sched, blocks, impl = None, None, None
        if opts.entry is not None and opts.entry[0] == st.name:
            _, sched, blocks, impl = opts.entry
        if sched is not None:
            return as_schedule(sched)
        if override is not None:
            return as_schedule(override)

        parts = st.schedule_key_parts(args, kw, opts.arg_specs)
        shapes, dtypes = parts["shapes"], parts["dtypes"]
        tag = parts.get("tag")
        if opts.epilogue is not None:
            # a fused launch is a different kernel: its schedule entry
            # must never collide with the plain op's
            tag = f"{tag}+epi:{opts.epilogue.tag}" if tag else f"epi:{opts.epilogue.tag}"
        layout_sig = tune.layout_signature(*opts.arg_specs, tag=tag)

        if blocks:
            # explicit block sizes force the kernel-ish variant (legacy
            # ``block_*`` compatibility); missing blocks come from the
            # tuned/planned kernel schedule for these shapes
            impl = impl or ("kernel" if "kernel" in st.variants or not st.variants
                            else st.variants[0])
            merged = st.default_blocks()
            if set(blocks) != set(merged):
                base = tune.get_schedule(
                    op, shapes=shapes, dtypes=dtypes, layout_sig=layout_sig, impl=impl
                )
                merged.update(base.blocks_dict)
            merged.update(blocks)
            return tune.Schedule(op, impl, tuple(merged.items()))

        return tune.get_schedule(
            op, shapes=shapes, dtypes=dtypes, layout_sig=layout_sig, impl=impl
        )

    # -- jit memoization -------------------------------------------------
    def _jitted(self, stage_name: str, key: Tuple, make: Callable[[], Callable]):
        full = (stage_name,) + key
        fn = self._jit.get(full)
        if fn is None:
            with self._jit_lock:
                fn = self._jit.get(full)
                if fn is None:
                    fn = jax.jit(make())
                    self._jit[full] = fn
        return fn

    # -- mesh lowering ---------------------------------------------------
    def shard_map(self, mesh, arg_specs: Sequence[Any], out_spec: Any, **call_kw):
        """Lower this program to a ``shard_map`` body on ``mesh``:
        AxeSpecs are the only placement input — ``in_specs`` /
        ``out_specs`` are derived through the inter-device adapter
        (``axe.lower.to_pspec``), and the specs are forwarded to the
        program so MESH stages can draw their collective plans from
        them."""
        from repro.axe import lower

        arg_specs = tuple(arg_specs)
        in_pspecs = tuple(lower.to_pspec(s) for s in arg_specs)
        out_pspec = lower.to_pspec(out_spec)

        def body(*arrays):
            return self(*arrays, arg_specs=arg_specs, **call_kw)

        return jax.shard_map(
            body, mesh=mesh, in_specs=in_pspecs, out_specs=out_pspec,
            check_vma=False,
        )

    # -- introspection ---------------------------------------------------
    def describe(self) -> str:
        lines = [f"program {self.name} (entry: {self.entry_stage})"]
        order = sorted(self.stages.values(), key=lambda s: s.scope.rank)
        for st in order:
            extras = []
            if st.blocks:
                extras.append("blocks " + ",".join(f"{k}={v}" for k, v in st.blocks))
            if st.variants:
                extras.append("variants " + "|".join(st.variants))
            suffix = f"  [{'; '.join(extras)}]" if extras else ""
            lines.append(f"  {st.scope.value:>6}  {self.stage_key(st.name)}{suffix}")
        return "\n".join(lines)

    def __repr__(self) -> str:
        return f"Program({self.name!r}, stages={sorted(self.stages)})"


def program(name: str, doc: Optional[str] = None) -> Program:
    """Create (and register) a new empty :class:`Program`."""
    return Program(name, doc)


def kernel(
    name: str,
    *,
    blocks: Sequence[Tuple[str, int]] = (),
    variants: Sequence[str] = ("kernel",),
    key: Optional[Callable] = None,
    flops: Optional[Callable] = None,
) -> Callable[[Callable], Program]:
    """Decorator sugar for a single-GRID-stage program::

        @axe.kernel("scale_rows", blocks=(("bt", 256),))
        def scale_rows(ctx, x): ...

    The decorated function becomes the program's ``kernel`` stage (its
    schedule key is ``<name>/kernel``) and the returned object is the
    callable :class:`Program`.
    """

    def deco(fn: Callable) -> Program:
        prog = Program(name, doc=fn.__doc__)
        prog.stage(
            "kernel", scope=Scope.GRID, blocks=blocks, variants=variants,
            key=key, flops=flops, entry=True,
        )(fn)
        return prog

    return deco
