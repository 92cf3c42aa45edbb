"""Schedule planner: enumerate candidate schedules for an operator
dispatch and rank them with the roofline cost model.

This is the §3.2 compiler step made explicit: given operand shapes,
dtypes, the (canonicalized) Axe layout signature, and a backend, produce
the ordered list of schedules the dispatch *could* run, each one
Axe-validated (``core.blockspec.derive_tiling`` — candidates whose grid
cells are not strided HBM boxes never appear). Ranking is analytic
(``launch.roofline.schedule_time``); the autotuner refines the top of
the list empirically. On the TPU a tiled matmul also pays
:data:`GRID_STEP_S` per grid step, so its blocks are sized by step
count as well as by HBM traffic.

Enumeration is deterministic: same inputs → same candidate list in the
same order (ties broken by the schedule's string form).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.blockspec import (
    MXU_TILE, candidate_blocks, candidate_tilings, derive_tiling, matmul_k_blocks,
    vreg_atom,
)
from repro.launch import roofline
from repro.tune.schedule import Schedule

#: interpreted Pallas kernels are only worth *measuring* off-TPU below
#: this op size (the autotuner would otherwise spend minutes per shape)
INTERPRET_MEASURE_FLOPS = 2 * 256**3 * 4

#: per-chunk-step dispatch overhead (s) — XLA launch + mask/softmax
#: epilogue per block; makes small chunks rank worse, as measured
MHA_CHUNK_OVERHEAD_S = 5e-6

#: fixed cost (s) of one grid step of a tiled matmul on a TPU v5e, on top
#: of its bytes. Kernel alone at 16 rows with 128-wide K blocks: qwen3-4b's
#: tied head 7.03 ms over 23,740 steps (0.30 us each, least time 0.95 ms),
#: the MLP gate 5.14 ms over 13,680 steps (0.38 us, least 2.19 ms)
GRID_STEP_S = 0.35e-6

#: column blocks ``plan_matmul`` offers: wide enough that a decode weight
#: product reads a few large tiles rather than thousands of small ones
MATMUL_BN_PREFER = (2048, 1024, 512, 256, 128)


@dataclasses.dataclass(frozen=True)
class Candidate:
    """A ranked schedule: analytic cost + its roofline terms."""

    schedule: Schedule
    cost_s: float
    terms: Tuple[Tuple[str, float], ...]

    @property
    def terms_dict(self) -> Dict[str, float]:
        return dict(self.terms)


def _backend() -> str:
    return jax.default_backend()


def _itemsize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _mk(schedule: Schedule, flops: float, mem_bytes: float, *,
        backend: str, comm_bytes: float = 0.0, compute_penalty: float = 1.0,
        grid_steps: Optional[int] = None) -> Candidate:
    """A candidate priced by the roofline; ``grid_steps`` adds the count
    to its terms and, on the TPU, :data:`GRID_STEP_S` per step to its cost."""
    cost, terms = roofline.schedule_time(
        flops=flops, mem_bytes=mem_bytes, comm_bytes=comm_bytes,
        backend=backend, compute_penalty=compute_penalty,
    )
    if grid_steps is not None:
        terms["grid_steps"] = grid_steps
        if backend == "tpu":
            cost += grid_steps * GRID_STEP_S
    return Candidate(schedule, cost, tuple(sorted(terms.items())))


def _kernel_penalty(backend: str) -> float:
    return 1.0 if backend == "tpu" else roofline.INTERPRET_PENALTY


# ---------------------------------------------------------------------------
# matmul: Pallas tiled kernel candidates vs the XLA dot
# ---------------------------------------------------------------------------


def plan_matmul(
    m: int, k: int, n: int,
    dtype=jnp.float32,
    *,
    backend: Optional[str] = None,
    use_hlo: bool = False,
    op_name: str = "matmul",
) -> List[Candidate]:
    """Candidates for ``C[M,N] = A[M,K] @ B[K,N]``.

    Kernel traffic model (per §3.4 tiling): each (i, j) output tile
    re-reads a row-panel of A per N-block and a column-panel of B per
    M-block, so HBM bytes fall as the tiles grow — exactly what the
    autotuner observes on TPU. Each candidate also counts its grid steps
    (``grid_steps`` in its terms), which the TPU charges
    :data:`GRID_STEP_S` each: K blocks grow up to the whole K and column
    blocks to :data:`MATMUL_BN_PREFER` while the step fits VMEM
    (``core.blockspec.matmul_k_blocks``); row blocks keep
    ``candidate_tilings``' default sizes. The XLA dot is modeled at its
    default 128³ tiling, steps included. Off-TPU, kernels carry the
    interpret-mode penalty so the compiled XLA schedule always ranks
    first.
    """
    backend = backend or _backend()
    item = _itemsize(dtype)
    flops = 2.0 * m * k * n

    def gemm_bytes(bm: int, bn: int) -> float:
        a_reads = m * k * max(1, n // bn)
        b_reads = k * n * max(1, m // bm)
        return float((a_reads + b_reads + m * n) * item)

    def grid_steps(bm: int, bn: int, bk: int) -> int:
        return -(-m // bm) * -(-n // bn) * -(-k // bk)

    out: List[Candidate] = []

    # XLA dot candidate (always valid — no divisibility constraints)
    xla_tile = (min(128, m), min(128, n), min(128, k))
    xla_bytes = gemm_bytes(*xla_tile[:2])
    if use_hlo:
        try:
            from repro.launch import hlo_cost

            a = jax.ShapeDtypeStruct((m, k), dtype)
            b = jax.ShapeDtypeStruct((k, n), dtype)
            c = hlo_cost.analyze_jit(lambda a, b: a @ b, a, b)
            xla_bytes = c.bytes or xla_bytes
        except Exception:
            pass
    out.append(_mk(Schedule(op_name, "xla"), flops, xla_bytes, backend=backend,
                   grid_steps=grid_steps(*xla_tile)))

    # Pallas kernel candidates: Axe-validated (M,N) tilings × K blocks
    penalty = _kernel_penalty(backend)
    row_blocks = candidate_blocks(m, minimum=MXU_TILE[0])
    for d in candidate_tilings((m, n), dtype, mxu=True, prefer=MATMUL_BN_PREFER):
        bm, bn = d.tile
        if bm not in row_blocks:
            continue
        for bk in matmul_k_blocks(k, bm, bn, dtype):
            try:
                derive_tiling((m, k), (bm, bk), dtype)
                derive_tiling((k, n), (bk, bn), dtype)
            except Exception:
                continue
            sched = Schedule(op_name, "kernel",
                             (("bm", bm), ("bn", bn), ("bk", bk)))
            cp = penalty if d.mxu_aligned else penalty * 4.0
            out.append(_mk(sched, flops, gemm_bytes(bm, bn), backend=backend,
                           compute_penalty=cp, grid_steps=grid_steps(bm, bn, bk)))

    out.sort(key=lambda c: (c.cost_s, c.schedule.describe()))
    return out


# ---------------------------------------------------------------------------
# flash attention: (block_q, block_kv) for the online-softmax kernel
# ---------------------------------------------------------------------------


def plan_flash_attention(
    b: int, h: int, sq: int, skv: int, d: int,
    dtype=jnp.float32,
    *,
    backend: Optional[str] = None,
    op_name: str = "flash_attention",
) -> List[Candidate]:
    """Candidates for the Pallas flash-attention kernel (§4.3 workload).

    K/V panels are re-read once per q block, so bytes fall with
    ``block_q``; VMEM must hold the q tile, both kv tiles, the f32
    accumulator, and the [block_q, block_kv] logits tile.
    """
    backend = backend or _backend()
    item = _itemsize(dtype)
    flops = 4.0 * b * h * sq * skv * d
    penalty = _kernel_penalty(backend)
    sub, _lane = vreg_atom(dtype)

    out: List[Candidate] = []
    seen = set()
    for bq in (512, 256, 128, 64):
        bq = min(bq, sq)
        if sq % bq or bq % sub:
            continue
        for bkv in (512, 256, 128, 64):
            bkv = min(bkv, skv)
            if skv % bkv or bkv % sub or (bq, bkv) in seen:
                continue
            seen.add((bq, bkv))
            vmem = (bq * d + 2 * bkv * d) * item + (bq * d + bq * bkv) * 4
            if vmem > 12 * 1024 * 1024:
                continue
            kv_rereads = max(1, sq // bq)
            mem = float(b * h * (2 * sq * d + 2 * skv * d * kv_rereads) * item)
            sched = Schedule(op_name, "kernel",
                             (("bq", bq), ("bkv", bkv)))
            out.append(_mk(sched, flops, mem, backend=backend, compute_penalty=penalty))

    out.sort(key=lambda c: (c.cost_s, c.schedule.describe()))
    return out


# ---------------------------------------------------------------------------
# blocked-softmax attention at MESH scope: the XLA chunk size
# ---------------------------------------------------------------------------


def plan_mha_blocked(
    b: int, s: int, h: int, d: int,
    dtype=jnp.float32,
    *,
    backend: Optional[str] = None,
    op_name: str = "mha_blocked",
) -> List[Candidate]:
    """Chunk-size candidates for the blocked online-softmax attention
    (``models.attention._gqa_blocked`` — same math as the Pallas kernel,
    expressed in XLA). Total logit traffic is chunk-independent; the
    cost difference is per-chunk dispatch overhead, so bigger chunks
    rank first until the autotuner's measurements say otherwise."""
    backend = backend or _backend()
    item = _itemsize(dtype)
    flops = 4.0 * b * h * s * s * d
    mem = float(b * h * (4 * s * d + 2 * s * s) * item)

    out: List[Candidate] = []
    seen = set()
    # s itself (one chunk) is always a valid schedule, so the plan is
    # never empty even when no preferred size divides s
    for chunk in (512, 256, 128, 64, s):
        chunk = min(chunk, s)
        if s % chunk or chunk in seen:
            continue
        seen.add(chunk)
        base, terms = roofline.schedule_time(flops=flops, mem_bytes=mem, backend=backend)
        cost = base + (s // chunk) * MHA_CHUNK_OVERHEAD_S
        out.append(Candidate(
            Schedule(op_name, "xla", (("chunk", chunk),)),
            cost, tuple(sorted(terms.items())),
        ))
    out.sort(key=lambda c: (c.cost_s, c.schedule.describe()))
    return out


# ---------------------------------------------------------------------------
# grouped MoE GEMM: (block_c, block_f, block_d) per expert
# ---------------------------------------------------------------------------


def plan_moe_gemm(
    e: int, c: int, d: int, f: int,
    dtype=jnp.float32,
    *,
    backend: Optional[str] = None,
    op_name: str = "moe_gemm",
) -> List[Candidate]:
    """Candidates for the per-expert batched GEMM [E,C,d]·[E,d,f], with
    ``plan_matmul``'s traffic model per expert: each (C, f) tile
    re-reads a row-panel of X per f-block and a panel of W per C-block;
    the XLA batched dot is modeled at its default 128³ tiling. Of
    tilings that cost the same, the one with fewest grid steps ranks
    first (each step of a Pallas kernel has a fixed cost)."""
    backend = backend or _backend()
    item = _itemsize(dtype)
    flops = 2.0 * e * c * d * f
    penalty = _kernel_penalty(backend)

    def gemm_bytes(bc: int, bf: int) -> float:
        x_reads = c * d * max(1, f // bf)
        w_reads = d * f * max(1, c // bc)
        return float(e * (x_reads + w_reads + c * f) * item)

    out: List[Candidate] = [
        _mk(Schedule(op_name, "xla"), flops, gemm_bytes(min(128, c), min(128, f)),
            backend=backend)
    ]
    steps = {out[0].schedule: math.inf}
    for td in candidate_tilings((c, f), dtype, mxu=True):
        bc, bf = td.tile
        for bd in (1024, 512, 256, 128):
            if bd > d or d % bd:
                continue
            if (bc * bd + bd * bf) * item + bc * bf * 4 > 12 * 1024 * 1024:
                continue
            try:
                derive_tiling((c, d), (bc, bd), dtype)
                derive_tiling((d, f), (bd, bf), dtype)
            except Exception:
                continue
            cp = penalty if td.mxu_aligned else penalty * 4.0
            sched = Schedule(op_name, "kernel",
                             (("bc", bc), ("bf", bf), ("bd", bd)))
            out.append(_mk(sched, flops, gemm_bytes(bc, bf), backend=backend,
                           compute_penalty=cp))
            steps[sched] = (c // bc) * (f // bf) * (d // bd)

    out.sort(key=lambda c_: (c_.cost_s, steps[c_.schedule], c_.schedule.describe()))
    return out


# ---------------------------------------------------------------------------
# mesh-scope collective matmul: overlapped ring vs GEMM + psum_scatter
# ---------------------------------------------------------------------------


def plan_collective_matmul(
    m: int, k_local: int, n: int, p: int,
    dtype=jnp.float32,
    *,
    backend: Optional[str] = None,
    op_name: str = "collective_matmul",
) -> List[Candidate]:
    """Rank the two §4.2 schedules for the K-sharded GEMM over ``p``
    devices: the baseline (full local GEMM, then reduce-scatter) pays
    compute *then* collective; the ring overlaps them, so its cost is
    the max of the two terms plus one un-overlappable chunk step."""
    backend = backend or _backend()
    item = _itemsize(dtype)
    flops = 2.0 * m * k_local * n
    mem = float((m * k_local + k_local * n + (m // max(p, 1)) * n) * item)
    comm = float(m * n * 4 * (p - 1) / max(p, 1))  # f32 partials on the wire

    base_cost, base_terms = roofline.schedule_time(
        flops=flops, mem_bytes=mem, backend=backend)
    _, comm_terms = roofline.schedule_time(
        flops=0.0, mem_bytes=0.0, comm_bytes=comm, backend=backend)

    out: List[Candidate] = []
    # unfused: compute + communicate, serialized
    seq = base_terms["compute"] + base_terms["memory"] + comm_terms["collective"]
    out.append(Candidate(
        Schedule(op_name, "psum_scatter"), seq,
        tuple(sorted({**base_terms, "collective": comm_terms["collective"]}.items())),
    ))
    if p > 1 and m % p == 0:
        # ring: per-chunk GEMM overlaps the permute of the previous chunk
        chunk_compute = (base_terms["compute"] + base_terms["memory"]) / p
        ring = max(base_terms["compute"] + base_terms["memory"],
                   comm_terms["collective"]) + chunk_compute
        out.append(Candidate(
            Schedule(op_name, "ring"), ring,
            tuple(sorted({**base_terms, "collective": comm_terms["collective"]}.items())),
        ))
    out.sort(key=lambda c: (c.cost_s, c.schedule.describe()))
    return out


# ---------------------------------------------------------------------------
# fused rmsnorm: row-block candidates (memory-bound fusion)
# ---------------------------------------------------------------------------


def plan_rmsnorm(
    rows: int, d: int,
    dtype=jnp.float32,
    *,
    backend: Optional[str] = None,
    op_name: str = "rmsnorm",
) -> List[Candidate]:
    """Candidates for the fused row-blocked RMSNorm. The op is
    memory-bound (one read + one write of x); candidates differ only in
    grid-dispatch overhead, so larger row blocks rank first. Rows are
    padded to the block by the kernel, so any VREG-aligned block is
    admissible — validation only checks the (block, d) tile itself."""
    backend = backend or _backend()
    item = _itemsize(dtype)
    flops = 4.0 * rows * d
    mem = float((2 * rows * d + d) * item)
    penalty = _kernel_penalty(backend)

    out: List[Candidate] = [
        _mk(Schedule(op_name, "xla"), flops, mem, backend=backend)
    ]
    seen = set()
    for br in (1024, 512, 256, 128, 64):
        br = min(br, rows)
        if br <= 0 or br in seen:
            continue
        seen.add(br)
        padded = -(-rows // br) * br
        try:
            derive_tiling((padded, d), (br, d), dtype)
        except Exception:
            continue
        out.append(_mk(
            Schedule(op_name, "kernel", (("brows", br),)),
            flops, mem, backend=backend, compute_penalty=penalty,
        ))
    out.sort(key=lambda c: (c.cost_s, c.schedule.describe()))
    return out


# ---------------------------------------------------------------------------
# uniform entry point
# ---------------------------------------------------------------------------


def plan(
    op: str,
    *,
    shapes: Sequence[Sequence[int]],
    dtypes: Sequence,
    backend: Optional[str] = None,
    use_hlo: bool = False,
    impl: Optional[str] = None,
    top_k: Optional[int] = None,
) -> List[Candidate]:
    """Enumerate + rank schedules for ``op`` on operands of ``shapes``.

    ``op`` is a legacy bare name (``"matmul"``) or an ``axe.program``
    stage key (``"matmul/tile"``): the part before the ``/`` selects
    the planning family, and every emitted ``Schedule`` carries the
    full key, so the one planner covers both in-kernel block choice and
    cross-device schedule choice for program stages.

    ``impl`` filters the candidate list (e.g. ``"kernel"`` when the
    caller has already committed to a Pallas launch and only needs block
    sizes). Raises ValueError for unknown ops.
    """
    base = op.split("/", 1)[0]
    dtype = jnp.dtype(dtypes[0]) if dtypes else jnp.float32
    if base == "matmul":
        (m, k), (_k2, n) = shapes[0], shapes[1]
        cands = plan_matmul(m, k, n, dtype, backend=backend, use_hlo=use_hlo,
                            op_name=op)
    elif base == "flash_attention":
        b, h, sq, d = shapes[0]
        skv = shapes[1][2]
        cands = plan_flash_attention(b, h, sq, skv, d, dtype, backend=backend,
                                     op_name=op)
    elif base == "mha_blocked":
        b, s, h, d_ = shapes[0]
        cands = plan_mha_blocked(b, s, h, d_, dtype, backend=backend, op_name=op)
    elif base == "moe_gemm":
        (e, c, d_), (_e2, _d2, f) = shapes[0], shapes[1]
        cands = plan_moe_gemm(e, c, d_, f, dtype, backend=backend, op_name=op)
    elif base == "rmsnorm":
        x_shape = shapes[0]
        rows = 1
        for s_ in x_shape[:-1]:
            rows *= int(s_)
        cands = plan_rmsnorm(rows, int(x_shape[-1]), dtype, backend=backend,
                             op_name=op)
    elif base == "collective_matmul":
        (m, k_local), (_kl, n) = shapes[0], shapes[1]
        p = shapes[2][0] if len(shapes) > 2 else 1
        cands = plan_collective_matmul(m, k_local, n, p, dtype, backend=backend,
                                       op_name=op)
    else:
        # a stage of a user-defined program: no planning family yet, but
        # its declared default (registered at stage declaration) is a
        # valid single-candidate plan — dispatch, forcing, caching, and
        # autotune measurement all work; ranking needs a plan_* family
        from repro.tune.schedule import STAGE_DEFAULTS

        default = STAGE_DEFAULTS.get(op)
        if default is None:
            raise ValueError(f"planner does not know op {op!r}")
        cands = [Candidate(default, 0.0, ())]
    if impl is not None:
        cands = [c for c in cands if c.schedule.impl == impl]
    return cands[:top_k] if top_k else cands


def best_schedule(op: str, **kwargs) -> Optional[Schedule]:
    """Top-ranked schedule, or None when nothing is admissible (e.g.
    kernel-only request on an un-tileable shape)."""
    cands = plan(op, **kwargs)
    return cands[0].schedule if cands else None


# ---------------------------------------------------------------------------
# planning keyed on solved AxeSpecs (repro.axe.solve / axe.compile)
# ---------------------------------------------------------------------------

#: layout-graph op kind → the planning family its local problem maps to
_SPEC_FAMILIES = {
    "matmul": "matmul",
    "attention": "flash_attention",
    "norm": "rmsnorm",
}

#: planning family → the ``program/stage`` key the op-backend binding
#: (``axe.compile``) dispatches under. Schedules planned for a solved
#: graph node are cached under the SAME key the program stage resolves
#: at trace time, so autotuned winners flow into compiled executables.
_STAGE_KEYS = {
    "matmul": "matmul/tile",
    "flash_attention": "flash_attention/attend",
    "moe_gemm": "moe_gemm/expert_gemm",
    "rmsnorm": "rmsnorm/rows",
}


def stage_key_for(kind: str, in_specs: Sequence) -> Optional[str]:
    """The backend-stage schedule key one graph node dispatches under
    (None for kinds with no tunable backend stage)."""
    family = _SPEC_FAMILIES.get(kind)
    if family is None:
        return None
    if kind == "matmul" and len(in_specs) > 1 and len(in_specs[1].shape) == 3:
        family = "moe_gemm"
    return _STAGE_KEYS[family]


def spec_key_parts(
    kind: str, in_specs: Sequence
) -> Optional[Tuple[str, Tuple[Tuple[int, ...], ...], Tuple[str, ...], str]]:
    """``(op, local_shapes, dtypes, layout_sig)`` — the schedule-cache
    key parts one graph node's solved layouts induce, *without*
    enumerating candidates. This is the key space ``plan_from_specs``
    plans under and ``tune.feedback.CostModel`` looks measurements up
    in; keeping it one function guarantees the two agree. None for op
    kinds with no tunable backend stage."""
    op = stage_key_for(kind, in_specs)
    if op is None:
        return None
    from repro.tune.schedule import layout_signature

    locals_ = [tuple(s.local_shape()) for s in in_specs]
    dtypes = tuple(s.dtype for s in in_specs)
    if op == _STAGE_KEYS["matmul"] and len(locals_[0]) > 2:
        # flatten leading batch dims into M for the 2D tiled kernel
        m = 1
        for d in locals_[0][:-1]:
            m *= d
        locals_ = [(m, locals_[0][-1])] + locals_[1:]
    sig = layout_signature(*in_specs)
    return op, tuple(locals_), dtypes, sig


@dataclasses.dataclass(frozen=True)
class SpecPlan:
    """Ranked schedules for the per-device problem one solved layout
    induces, plus the exact ``get_schedule`` key that retrieves a tuned
    winner for it from the cache."""

    op: str
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    layout_sig: str
    candidates: Tuple[Candidate, ...]

    @property
    def schedule(self) -> Optional[Schedule]:
        return self.candidates[0].schedule if self.candidates else None


def plan_from_specs(
    kind: str,
    in_specs: Sequence,
    *,
    backend: Optional[str] = None,
    top_k: Optional[int] = None,
) -> Optional[SpecPlan]:
    """Plan schedules for the *local* (per-device) problem a solved
    layout assignment leaves one op with.

    ``in_specs`` are the operand AxeSpecs the layout solver (or the
    propagation pass) settled on; their ``local_shape()`` is the problem
    the kernel actually runs, and their canonical signatures become the
    schedule-cache layout key — so a schedule tuned for a solved layout
    is keyed by that layout, not by the global shapes. Candidates are
    keyed per (graph node kind → backend stage): the emitted op is the
    ``program/stage`` key the compiled executable's program dispatch
    resolves (``matmul/tile``, ``flash_attention/attend``, ...), so
    autotuning through ``tune.autotune_program`` lands exactly where
    ``axe.compile`` looks. Returns None for op kinds with no planning
    family (elementwise, reshape, ...)."""
    parts = spec_key_parts(kind, in_specs)
    if parts is None:
        return None
    op, locals_, dtypes, sig = parts
    cands = plan(
        op, shapes=list(locals_), dtypes=dtypes, backend=backend, top_k=top_k
    )
    return SpecPlan(op, locals_, dtypes, sig, tuple(cands))


def schedule_from_specs(
    kind: str,
    in_specs: Sequence,
    *,
    backend: Optional[str] = None,
) -> Optional[Schedule]:
    """The dispatch-ready schedule for one solved-layout op: resolves
    through ``tune.get_schedule`` (forced → cached → planned), keyed on
    the solved specs' canonical layout signature."""
    sp = plan_from_specs(kind, in_specs, backend=backend)
    if sp is None:
        return None
    from repro import tune

    return tune.get_schedule(
        sp.op, shapes=sp.shapes, dtypes=sp.dtypes,
        layout_sig=sp.layout_sig, backend=backend,
    )
