"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s. A device kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float   # FLOP/s
    hbm_bw: float       # B/s
    hbm_bytes: float    # B


PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks_for(kind: str) -> Peaks:
    """The table row for ``kind``; raises ``KeyError`` for any other kind."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]
