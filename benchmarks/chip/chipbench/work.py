"""Operations and bytes the algorithm needs, from the configuration's
sizes and the live sequence lengths.

It counts the work required, not what the program's kernels happen to
move: decode attention reads K/V up to each slot's live position, not
the whole cache window; a prefill computes the output head for the last
position only; weights are read once per step. So a roofline share
reads the same work whatever implements it. Weights and activations are
bfloat16 (2 bytes).

What depends on the architecture comes from the configuration's
reference module (``reference/<name>.py``), so a new architecture
brings its counts with its reference:

    weight_products(sz) -> (body, head)   (K, N) of every per-token
                                          weight product, all layers;
                                          the output head's (K, N)
    mixer_flops(sz, context) -> float     one token's sequence mixer,
                                          all layers, over ``context``
                                          positions
    decode_attention(sz, positions)       optional: (FLOPs, bytes) of a
                                          decode step's attention
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

BF16 = 2


class Work:
    def __init__(self, ref, sz: dict):
        self.ref, self.sz = ref, sz

    def products(self) -> List[Tuple[int, int]]:
        """(K, N) of one step's weight products, the output head last."""
        body, head = self.ref.weight_products(self.sz)
        return list(body) + [head]

    def matmul_flops(self, m: int) -> float:
        return float(sum(2 * m * k * n for k, n in self.products()))

    def matmul_least_s(self, m: int, peak_flops: float, hbm_bw: float) -> float:
        """Least time of one step's weight products at ``m`` rows: each
        product bound by its operations or by reading its weight and its
        bfloat16 input and output once, whichever is longer."""
        return sum(max(2 * m * k * n / peak_flops, BF16 * (k * n + m * k + m * n) / hbm_bw)
                   for k, n in self.products())

    def decode_attention(self, positions: Sequence[int]) -> Tuple[float, float]:
        """(FLOPs, bytes) of one decode step's attention; (0, 0) for an
        architecture that does not attend."""
        fn = getattr(self.ref, "decode_attention", None)
        return fn(self.sz, positions) if fn else (0.0, 0.0)

    def decode_attention_least_s(self, positions: Sequence[int], peak_flops: float,
                                 hbm_bw: float) -> float:
        """Least time of one step's decode attention: one kernel call per
        layer, each bound by its operations or its bytes."""
        flops, nbytes = self.decode_attention(positions)
        n = self.sz["layers"]
        return n * max(flops / n / peak_flops, nbytes / n / hbm_bw) if n else 0.0

    def decode_flops(self, positions: Sequence[int]) -> float:
        """Model FLOPs of one decode step over the live slots."""
        return self.matmul_flops(len(positions)) + sum(
            self.ref.mixer_flops(self.sz, p + 1) for p in positions)

    def prefill_flops(self, s: int) -> float:
        """Model FLOPs of one batch-1 prefill of ``s`` tokens: the weight
        products for every token, the output head for the last one, and
        the mixer of token ``i`` over its ``i`` positions."""
        body, (k, n) = self.ref.weight_products(self.sz)
        return (float(sum(2 * s * bk * bn for bk, bn in body)) + 2.0 * k * n
                + sum(self.ref.mixer_flops(self.sz, i) for i in range(1, s + 1)))

    def weight_bytes(self) -> float:
        return float(BF16 * sum(k * n for k, n in self.products()))
