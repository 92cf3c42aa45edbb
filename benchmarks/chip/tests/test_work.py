"""``work.py`` against counts made by hand from the published sizes."""
from __future__ import annotations

import pytest

from chipbench import cells, work


def _work(name):
    doc = cells.load_config(name)
    ref = cells.load_reference(doc)
    return work.Work(ref, ref.sizes(doc))


def test_qwen3_4b_counts():
    w = _work("qwen3-4b")
    # per layer: q 2560*4096, k and v 2560*1024, o 4096*2560, mlp 3*2560*9728
    per_layer = 2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728
    assert per_layer == 100_925_440
    params = 36 * per_layer + 2560 * 151_936          # tied head still read
    assert params == 4_022_272_000
    assert w.matmul_flops(1) == 2 * params
    assert w.weight_bytes() == 2 * params
    # positions 0 and 511: 1 + 512 live K/V rows
    flops, nbytes = w.decode_attention([0, 511])
    assert flops == 4 * 32 * 128 * 513 * 36
    assert nbytes == 2 * (2 * 8 * 128 * 513 + 2 * 32 * 128 * 2) * 36
    assert w.decode_flops([0, 511]) == 2 * 2 * params + flops
    s = 512
    assert w.prefill_flops(s) == \
        2 * s * 36 * per_layer + 2 * 2560 * 151_936 + 4 * 32 * 128 * 36 * s * (s + 1) / 2


def test_mamba2_2_7b_counts():
    w = _work("mamba2-2.7b")
    sz = w.sz
    assert sz["vocab"] == 50_288 and sz["ssm_heads"] == 80 and sz["d_inner"] == 5120
    # per layer: x and z 2560*5120, B and C 2560*128, dt 2560*80, out 5120*2560
    per_layer = 2 * 2560 * 5120 + 2 * 2560 * 128 + 2560 * 80 + 5120 * 2560
    assert per_layer == 40_181_760
    params = 64 * per_layer + 2560 * 50_288
    assert w.matmul_flops(3) == 2 * 3 * params
    assert w.decode_attention([5, 6]) == (0.0, 0.0)
    mixer = 64 * (2 * 4 * (5120 + 2 * 128) + 6 * 80 * 128 * 64)
    assert w.decode_flops([5, 6]) == 2 * 2 * params + 2 * mixer
    assert w.prefill_flops(64) == \
        2 * 64 * 64 * per_layer + 2 * 2560 * 50_288 + 64 * mixer


@pytest.mark.parametrize("name", ["qwen3-4b", "mamba2-2.7b"])
def test_decode_matmuls_are_bound_by_reading_the_weights(name):
    w = _work(name)
    peak, bw = 197e12, 819e9
    least = w.matmul_least_s(16, peak, bw)
    assert least >= w.weight_bytes() / bw
    assert least < 1.02 * w.weight_bytes() / bw   # plus activations
