"""Batched serving engine: prefill + decode with per-slot position
tracking (continuous-batching-lite) and greedy/temperature sampling.
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Any, Dict, List, Mapping, Optional, Union  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class ServeEngine:
    """``schedule_cache`` pins the process-wide schedule cache
    (``repro.tune``) to a server-local file, so ``axe.program`` stage
    dispatches traced inside prefill/decode reuse schedules a prior
    autotune run measured for this model's shapes (keyed
    ``program_name/stage_name``) instead of re-planning per process.
    ``tune_service`` additionally folds a persistent service artifact
    (``tune.service`` — e.g. the CI-nightly merged one) into that cache
    under the measured-beats-planned / newest-wins merge rules, so a
    fresh host inherits tuned schedules without re-autotuning.
    ``force_schedule`` is the serve-time escape hatch — a
    ``Schedule.parse`` spec (e.g. ``"xla"``) applied to every dispatch,
    or a mapping pinning individual stages (e.g. ``{"matmul/tile":
    "kernel:bm=128,bn=128,bk=256", "collective_matmul/kshard":
    "psum_scatter"}``) while this engine's jitted functions trace.

    ``mesh`` opts into sharded serving: param and KV-cache placement
    comes from the AxeSpec rule engine (``repro.axe.rules``) lowered
    through ``repro.axe.lower.to_named_sharding`` — the same propagated
    layout plan the trainer and dry-run use, never a hand-written
    PartitionSpec table. ``mesh=None`` (tests, single host) keeps the
    unsharded behavior.

    ``layout_plan`` goes one step further: a solved layout
    (``repro.axe.solve.SolveResult``, a ``LayoutPlan``, or a plain
    name→AxeSpec assignment) consumed through ``rules.from_plan`` —
    param leaves the solver assigned take the *solved* placement and
    only the rest fall back to the rule tables.

    The full-sequence forward pass is constructed from ``axe.compile``
    on the model-zoo graph (:meth:`compiled_forward` / :meth:`score`):
    one :class:`~repro.axe.compile.Executable` per (batch, seq) whose
    ops bind to the kernel programs and whose redistributions are the
    solved plan's collectives — the same plan ``layout_plan`` places
    params with. Incremental decode (:meth:`generate`) runs through the
    compiled decode-step executable (:meth:`compiled_decode` — the
    KV/SSM caches are first-class graph tensors, docs/serving.md); the
    model's own ``api.decode_step`` stays the reference it is checked
    against. ``fuse=True`` runs the graph-level
    fusion passes (docs/passes.md) on both graphs before solving, so
    norm/elementwise/rope glue executes inside the adjacent kernels."""

    api: Any                 # ModelAPI
    batch_size: int
    max_seq: int
    temperature: float = 0.0
    rng_seed: int = 0
    schedule_cache: Optional[str] = None
    tune_service: Optional[str] = None  # persistent service artifact path
    force_schedule: Optional[Union[str, Mapping[str, str]]] = None
    mesh: Optional[Any] = None       # jax.sharding.Mesh
    layout_plan: Optional[Any] = None  # SolveResult | LayoutPlan | {name: AxeSpec}
    fuse: bool = False                 # graph-level fusion passes (docs/passes.md)

    def __post_init__(self):
        from repro import tune

        if self.schedule_cache is not None:
            tune.use_cache(self.schedule_cache)
        if self.tune_service is not None:
            # fold a shipped service artifact (tune.service — e.g. the
            # CI-nightly merged one) into the live cache: this host
            # inherits measured schedules instead of re-autotuning;
            # entries only replace local ones when they win the merge
            # order (measured beats planned, newest measurement wins)
            tune.load_into(tune.default_cache(), self.tune_service)
        self.params = None
        #: how the last traced decode step bound its matmul weights
        #: (``axe.compile.BindReport``: in place vs copied, bytes per step)
        self.bind_report = None
        #: the expert layer's load in the last prefill or decode step this
        #: engine ran: a device array ``[layers, 2]`` (``models.moe.load``),
        #: never read back here; None for a model without experts
        self.last_expert_load = None
        self._compiled: Dict[tuple, Any] = {}
        self._warned: set = set()
        if self.api.cfg.is_moe:
            def prefill(params, batch, cache):
                return self.api.prefill(params, batch, cache, expert_load=True)
        else:
            def prefill(params, batch, cache):
                return (*self.api.prefill(params, batch, cache), None)
        self._prefill = self._scheduled(jax.jit(prefill))

    @contextlib.contextmanager
    def _dedup_warnings(self):
        """Re-emit each distinct placement/plan warning once per engine.

        Executable construction and cache/param placement surface
        structured warnings (``PlanDivisibilityWarning``,
        ``CachePlanFallbackWarning``, the plan-does-not-cover re-solve
        notice). A serving engine hits those paths repeatedly — every
        ``generate()`` places a fresh cache, and a FIFO-evicted
        (batch, seq) recompiles from scratch — so without engine-level
        dedup the same warning fires once per request."""
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            yield
        for w in caught:
            key = (w.category.__name__, str(w.message))
            if key not in self._warned:
                self._warned.add(key)
                warnings.warn_explicit(
                    w.message, w.category, w.filename, w.lineno
                )

    def _space(self):
        from repro.axe.spec import PhysicalSpace

        return PhysicalSpace.from_mesh_shape(
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        )

    def _place_params(self, params):
        from repro.axe import rules as axe_rules

        with self._dedup_warnings():
            plan = (
                axe_rules.from_plan(self.layout_plan)
                if self.layout_plan is not None else None
            )
            specs = axe_rules.param_specs(params, self._space(), plan=plan)
        shardings = axe_rules.sharding_tree(specs, self.mesh)
        return jax.device_put(params, shardings)

    def _cache_shardings(self, cache):
        from repro.axe import rules as axe_rules

        with self._dedup_warnings():
            specs = axe_rules.cache_specs(
                cache, self._space(), plan=self.layout_plan
            )
        return axe_rules.sharding_tree(specs, self.mesh)

    def _place_cache(self, cache):
        return jax.device_put(cache, self._cache_shardings(cache))

    def _scheduled(self, fn):
        """Hold the forced-schedule context across calls so jit tracing
        (which happens lazily, on first call) sees it."""
        if self.force_schedule is None:
            return fn
        from repro import tune

        def wrapped(*args, **kwargs):
            with tune.force_schedule(self.force_schedule):
                return fn(*args, **kwargs)

        return wrapped

    def load(self, params) -> None:
        self.params = self._place_params(params) if self.mesh is not None else params

    #: compiled-forward memo bound: each entry holds a solved plan and a
    #: jitted executable, so callers should bucket sequence lengths
    MAX_COMPILED = 8

    # -- compiled full-sequence forward (axe.compile) -------------------
    def compiled_forward(self, seq: int, *, batch: Optional[int] = None,
                         layers: Optional[int] = None):
        """The :class:`~repro.axe.compile.Executable` for a
        (batch, seq) full-sequence forward of this engine's model,
        memoized per shape (FIFO-bounded at :data:`MAX_COMPILED` — each
        miss solves + compiles, so bucket/pad sequence lengths rather
        than scoring arbitrary ones). Uses ``layout_plan`` when it
        covers this shape (the same solved layout the params were
        placed with), else solves."""
        from repro.axe.compile import model_executable

        key = (batch or self.batch_size, seq, layers)
        exe = self._compiled.get(key)
        if exe is None:
            with self._dedup_warnings():
                exe = model_executable(
                    self.api.cfg, self.mesh, batch or self.batch_size, seq,
                    plan=self.layout_plan, layers=layers,
                    dtype=str(self.api.cfg.dtype), fuse=self.fuse,
                )
            while len(self._compiled) >= self.MAX_COMPILED:
                self._compiled.pop(next(iter(self._compiled)))
            self._compiled[key] = exe
        return exe

    # -- compiled decode step (axe.compile on the decode graph) ----------
    def compiled_decode(self, *, batch: Optional[int] = None,
                        layers: Optional[int] = None):
        """The :class:`~repro.axe.compile.Executable` for one decode
        step of this engine's model — the KV/SSM caches are graph
        inputs and outputs placed by the layout solver like any other
        tensor. Memoized in the same FIFO-bounded table as
        :meth:`compiled_forward` and sharing ``schedule_cache``.
        ``layout_plan`` is consumed when it covers the decode graph
        (i.e. it was solved on one — a forward-pass plan has no cache
        tensors and is skipped without re-solve noise)."""
        from repro.axe import rules as axe_rules
        from repro.axe.compile import decode_executable

        key = ("decode", batch or self.batch_size, layers)
        exe = self._compiled.get(key)
        if exe is None:
            plan = self.layout_plan
            if plan is not None and not axe_rules._plan_cache_env(plan):
                plan = None
            with self._dedup_warnings():
                exe = decode_executable(
                    self.api.cfg, self.mesh, batch or self.batch_size,
                    self.max_seq, plan=plan, layers=layers,
                    schedule_cache=self.schedule_cache,
                    dtype=str(self.api.cfg.dtype), fuse=self.fuse,
                )
            while len(self._compiled) >= self.MAX_COMPILED:
                self._compiled.pop(next(iter(self._compiled)))
            self._compiled[key] = exe
        return exe

    def decode_step(self, tok: jax.Array, cache, pos: jax.Array):
        """One compiled decode step: ``tok [B]`` current tokens,
        ``pos [B]`` per-slot positions (requests in one batch may sit at
        different depths), legacy-layout ``cache`` pytree in/out.
        Returns ``(logits [B, V], new_cache)``.

        ``cache`` is donated: the caller must drop its reference and use
        the returned one."""
        b = int(tok.shape[0])
        logits, cache, self.last_expert_load = self.decode_fn(batch=b)(
            self.params, cache, tok, pos)
        return logits, cache

    def prefill(self, batch: Dict[str, jax.Array], cache):
        """Run the prompts ``batch["tokens"] [B, S]`` through the model,
        filling ``cache``. Returns ``(logits [B, 1, V], new_cache)``."""
        logits, cache, self.last_expert_load = self._prefill(self.params, batch, cache)
        return logits, cache

    def decode_fn(self, *, batch: Optional[int] = None):
        """The jitted serving step ``(params, cache, tok, pos) ->
        (logits, new_cache, expert_load)`` around :meth:`compiled_decode`
        (``expert_load``: ``compile.decode_expert_load``). Binding the
        stacked params and cache onto the graph's per-layer inputs
        (``decode_inputs``/``decode_cache``) happens inside the jit, and
        each matmul weight is bound as a view of the stored param that the
        kernel reads in place, so a step copies no weight; the cache
        argument is donated so the new cache reuses its buffers. How the
        weights were bound lands on :attr:`bind_report` when the step
        traces. Its device ops are named by scope (docs/serving.md,
        "Tracing a server"): ``bind`` (the per-layer cache slices; no
        weight slices), ``restack`` (the new cache), and each graph op's
        ``<kind>/<node>`` from the executable.
        On a mesh the new cache keeps the placed cache's shardings, so the
        next step reuses this executable and the donation aliases."""
        from repro.axe.compile import (bind_report, decode_cache, decode_expert_load,
                                       decode_inputs)

        key = ("decode_fn", batch or self.batch_size)
        fn = self._compiled.get(key)
        if fn is None:
            exe = self.compiled_decode(batch=batch)
            cfg = self.api.cfg

            def step(params, cache, tok, pos):
                with jax.named_scope("bind"):
                    inputs = decode_inputs(exe.graph, cfg, params, cache)
                self.bind_report = bind_report(exe.graph, params, inputs)
                outs = exe.apply(inputs, tok, pos)
                logits = dict(zip(exe.graph.outputs(), outs))["logits"]
                with jax.named_scope("restack"):
                    new_cache = decode_cache(exe.graph, cfg, outs, cache)
                    if self.mesh is not None:
                        new_cache = jax.lax.with_sharding_constraint(
                            new_cache, self._cache_shardings(cache))
                return logits, new_cache, decode_expert_load(exe.graph, outs)

            fn = self._scheduled(jax.jit(step, donate_argnums=(1,)))
            while len(self._compiled) >= self.MAX_COMPILED:
                self._compiled.pop(next(iter(self._compiled)))
            self._compiled[key] = fn
        return fn

    def score(self, tokens: jax.Array) -> jax.Array:
        """Full-sequence logits [B, S, V] through the compiled graph —
        the engine's forward pass as one ``axe.compile`` executable
        (sharing ``schedule_cache`` and the solved layout)."""
        from repro.axe.compile import model_inputs

        assert self.params is not None, "call load() first"
        b, s = tokens.shape
        exe = self.compiled_forward(s, batch=b)
        inputs = model_inputs(exe.graph, self.api.cfg, self.params)
        run = self._scheduled(exe)
        logits = run(inputs, tokens.reshape(-1))
        return logits.reshape(b, s, -1)

    def generate(
        self,
        prompts: jax.Array,       # [B, S_prompt] int32 (padded batch)
        max_new_tokens: int,
        *,
        temperature: Optional[float] = None,
        top_k: Optional[int] = None,
        extra_inputs: Optional[Dict[str, jax.Array]] = None,
    ) -> np.ndarray:
        """Greedy / temperature / top-k sampling for a fixed batch.

        Prefill runs through the full-sequence model API; each decode
        step runs through the compiled decode executable
        (:meth:`decode_step`). ``temperature``/``top_k`` override the engine defaults per call;
        ``temperature=0`` (or unset with an engine default of 0) is
        exact greedy decoding."""
        assert self.params is not None, "call load() first"
        b, s_prompt = prompts.shape
        assert b == self.batch_size
        cache = self.api.cache_init(b, self.max_seq)
        if self.mesh is not None:
            cache = self._place_cache(cache)
        batch = {"tokens": prompts}
        if extra_inputs:
            batch.update(extra_inputs)
        logits, cache = self.prefill(batch, cache)

        key = jax.random.PRNGKey(self.rng_seed)
        outs: List[jax.Array] = []
        tok = self._sample(logits[:, -1], key,
                           temperature=temperature, top_k=top_k)
        outs.append(tok)
        pos = s_prompt
        for i in range(max_new_tokens - 1):
            key, sub = jax.random.split(key)
            step_logits, cache = self.decode_step(
                tok, cache, jnp.full((b,), pos, jnp.int32)
            )
            tok = self._sample(step_logits, sub,
                               temperature=temperature, top_k=top_k)
            outs.append(tok)
            pos += 1
        return np.stack([np.asarray(t) for t in outs], axis=1)

    def _sample(self, logits: jax.Array, key, *,
                temperature: Optional[float] = None,
                top_k: Optional[int] = None) -> jax.Array:
        t = self.temperature if temperature is None else temperature
        if top_k is not None and top_k > 0:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if t <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / t).astype(jnp.int32)
