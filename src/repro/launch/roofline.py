"""Roofline-term derivation from a compiled dry-run artifact.

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = Σ_op operand_bytes / effective link bw (ICI; pod axis → DCI)

``cost_analysis`` runs on the SPMD-partitioned per-device module, so its
flops/bytes are already per-chip. Collective bytes are NOT in
cost_analysis — we parse the optimized HLO text and sum operand sizes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Tuple


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """bytes of 'bf16[128,4096]{1,0}'-style shape strings."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_of_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum *output* shape bytes of every collective op, by op kind.

    HLO line form:  %name = bf16[...] all-gather(%operand), ...
    Output bytes ≈ communicated payload for gather-like ops; for
    all-reduce the payload is the (same-sized) operand. Lines inside
    fusions/computation bodies are included (they appear once each).
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w\.\-]+\s*=\s*(\([^)]*\)|[^=(]+?)\s*([\w\-]+)\(", s)
        if not m:
            continue
        op = m.group(2)
        if op.endswith("-start"):
            op = op[: -len("-start")]
        if op not in _COLLECTIVES:
            continue
        out[op] += _shape_bytes(m.group(1))
        counts[op] += 1
    return {"bytes": out, "counts": counts}


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float      # FLOP/s
    hbm_bw: float          # B/s
    hbm_bytes: float       # device memory
    ici_bw_per_link: float  # B/s per chip-to-chip link
    ici_links: int


#: Per-chip peaks keyed by ``jax.Device.device_kind``. Source: Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GiB HBM at
#: 819 GB/s, 1,600 Gbit/s of ICI over 4 links (2D torus).
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 1024**3,
        ici_bw_per_link=50e9, ici_links=4,
    ),
}

#: the chip the solver's cost model and the deviceless dry-run price for
TARGET_KIND = "TPU v5 lite"


def device_peaks(kind: str) -> DevicePeaks:
    """The peak table row for ``kind``; a kind not in the table is an
    error, never a default."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add its published peaks to "
            f"launch.roofline.DEVICE_PEAKS"
        ) from None


#: host-CPU (peak FLOP/s, memory B/s) for the schedule planner's
#: analytic ranking off the chip, where kernels run in interpret mode.
#: Rough on purpose: the planner only needs relative order there, and
#: no device metric is ever derived from it.
CPU_PEAKS = (200e9, 50e9)


def _peaks(backend: str) -> Tuple[float, float]:
    """(peak_flops, mem_bw) the planner ranks with. The accelerator
    reads the *active* device-class table (repro.axe.hetero), whose
    default is the :data:`TARGET_KIND` row; tests flip the table to flip
    relative costs. Any backend other than ``tpu``/``cpu`` raises."""
    if backend == "tpu":
        from repro.axe import hetero

        return hetero.default_peaks()
    if backend == "cpu":
        return CPU_PEAKS
    raise ValueError(f"no planner peaks for backend {backend!r}")


def _link_bw() -> float:
    """The default class' link bandwidth — the v5e ICI under the
    default table (repro.axe.hetero)."""
    from repro.axe import hetero

    return hetero.default_link_bw()

# Pallas kernels execute in interpret mode (Python per grid step) off
# TPU; the planner multiplies their compute term by this so an
# interpreted kernel never out-ranks a compiled XLA schedule.
INTERPRET_PENALTY = 1e4


def schedule_time(
    *,
    flops: float,
    mem_bytes: float,
    comm_bytes: float = 0.0,
    backend: str = "tpu",
    compute_penalty: float = 1.0,
) -> Tuple[float, Dict[str, float]]:
    """Three-term roofline estimate for one candidate schedule.

    Returns ``(seconds, terms)`` where seconds is the max of the terms —
    the same model ``derive_terms`` applies to whole compiled programs,
    reduced to a single operator so the planner can rank candidates.
    """
    peak_flops, mem_bw = _peaks(backend)
    ici_bw = _link_bw()
    terms = {
        "compute": compute_penalty * flops / peak_flops,
        "memory": mem_bytes / mem_bw,
        "collective": comm_bytes / ici_bw,
    }
    return max(terms.values()), terms


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    collective_breakdown: Dict[str, int]
    model_flops: float
    useful_ratio: float           # MODEL_FLOPS / (HLO_FLOPs × chips)
    bottleneck: str
    step_time_s: float            # max of the three terms
    roofline_fraction: float      # dominant-term-bound "usefulness": model-flops time / step time

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


def derive_terms(
    *,
    hlo_text: str,
    n_chips: int,
    model_flops_total: float,
    kind: str,
    pod_axis: bool = False,
) -> RooflineTerms:
    """Three-term roofline from the optimized per-device HLO, using the
    loop-aware analyzer (XLA's cost_analysis counts scan bodies once),
    priced with the peaks of device ``kind``."""
    from repro.launch import hlo_cost

    c = hlo_cost.analyze(hlo_text, total_devices=n_chips)

    pk = device_peaks(kind)
    peak_flops, mem_bw = pk.flops_bf16, pk.hbm_bw
    ici_bw = pk.ici_bw_per_link * pk.ici_links
    compute_s = c.flops / peak_flops
    memory_s = c.bytes / mem_bw
    collective_s = c.comm_bytes / ici_bw

    ideal_s = model_flops_total / (n_chips * peak_flops)
    step_s = max(compute_s, memory_s, collective_s)
    terms = {
        "compute": compute_s, "memory": memory_s, "collective": collective_s,
    }
    bottleneck = max(terms, key=terms.get)
    return RooflineTerms(
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        flops_per_device=c.flops,
        bytes_per_device=c.bytes,
        collective_bytes=c.comm_bytes,
        collective_breakdown={k: int(v) for k, v in c.comm_by_op.items()},
        model_flops=model_flops_total,
        useful_ratio=(model_flops_total / (c.flops * n_chips)) if c.flops else 0.0,
        bottleneck=bottleneck,
        step_time_s=step_s,
        roofline_fraction=(ideal_s / step_s) if step_s else 0.0,
    )


def model_flops(cfg, kind: str, batch: int, seq: int) -> float:
    """MODEL_FLOPS = 6·N_active·tokens (train) / 2·N_active·tokens (fwd)."""
    n_active = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n_active * batch * seq
    if kind == "prefill":
        return 2.0 * n_active * batch * seq
    return 2.0 * n_active * batch  # decode: one token per slot
