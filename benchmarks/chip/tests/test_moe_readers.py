"""The readers of the expert-share cell's metrics, on a hand-made run: the
program's expert-load counter read for the traced steps only, the work
it implies, and the expert layer's ops told by their graph node."""
from __future__ import annotations

import collections
import types

import numpy as np
import pytest

from chipbench import cells, expert_load, harness, reduce_trace, serve_loop, work
from chipbench.peaks import peaks_for

D, F, LAYERS = 4096, 1536, 16


def _run(log, kernel_ns=3e7):
    doc = cells.load_config("qwen3-moe-235b-a22b")
    ref = cells.load_reference(doc)
    sz = ref.sizes(doc)
    rec = serve_loop.Recorder(traced=[True, True, False],
                              decodes=[(0, [70] * 64), (1, [71] * 64), (2, [72] * 64)],
                              admits=[(0, 7, 64, 0.01), (2, 8, 128, 0.01)])
    ev = reduce_trace.Event
    trace = reduce_trace.Summary(
        window=(0.0, 1e9),
        ops=[ev("%moe_gemm_expert_gemm.1 = bf16[8,64,1536] custom-call()", 0.0, kernel_ns / 2),
             ev("%moe_gemm_expert_gemm.2 = bf16[8,64,4096] custom-call()", 1e7, 1e7 + kernel_ns / 2),
             ev("%matmul_tile.3 = bf16[64,8192] custom-call()", 2e7, 3e7)],
        modules=[ev("jit_step(1)", 0.0, 4e7)], spans=[], devices=1, busy_ns=4e7)
    engine = types.SimpleNamespace(bench_batcher=types.SimpleNamespace(expert_load=log))
    return harness.Run(cell="qwen3-moe-235b-a22b.decode-batch-64", sizes=sz, work=work.Work(ref, sz), mix={}, peaks=peaks_for("TPU v5 lite"),
                       engine=engine, rec=rec, served={}, setup_s=0.0, solve_s=0.0,
                       window_s=1.0, tokens=0, gaps=[], trace=trace)


def _log():
    def load(pairs, active):
        return np.tile(np.asarray([[pairs, active]], np.int32), (LAYERS, 1))

    return collections.deque([(0, "prefill", load(32, 8)), (0, "decode", load(256, 8)),
                              (1, "decode", load(250, 7)), (2, "prefill", load(64, 8)),
                              (2, "decode", load(256, 8))])


def test_counter_is_read_for_traced_steps_only():
    run = _run(_log())
    assert [int(x[0, 0]) for x in expert_load.traced(run)] == [32, 256, 250]
    assert [int(x[0, 0]) for x in expert_load.traced(run, ("decode",))] == [256, 250]
    assert expert_load.traced(_run(collections.deque())) == []
    parent = _run(None)                  # a program with no counter
    assert expert_load.traced(parent) == []
    for name in ("moe_gemm_roofline", "mfu.moe"):
        assert cells.load_metric(name)(parent) is None


def test_moe_gemm_roofline_counts_each_active_expert_once():
    run = _run(_log())
    # per layer and decode step: bound by reading the active experts'
    # three d x f matrices once, and each pair's row in and out
    least = LAYERS * sum(2 * (3 * D * F * a + 2 * D * p) / 819e9 for p, a in ((256, 8), (250, 7)))
    got = cells.load_metric("moe_gemm_roofline")(run)
    assert got == pytest.approx(100 * least / 3e-2)
    assert 0 < got < 100


def test_mfu_moe_adds_the_counted_expert_work_to_the_dense_work():
    run = _run(_log())
    dense = run.work.decode_flops([70] * 64) + run.work.decode_flops([71] * 64) \
        + run.work.prefill_flops(64)
    experts = LAYERS * 6 * D * F * (32 + 256 + 250)
    got = cells.load_metric("mfu.moe")(run)
    assert got == pytest.approx(100 * (dense + experts) / 197e12)
    # the dense count holds attention, router and head, no expert
    body, head = run.work.ref.weight_products(run.sizes)
    assert (D, 128) in body and head == (D, 151936) and (D, F) not in body


def test_expert_layer_ops_are_told_by_their_graph_node():
    mod = cells._module(cells.BENCH_DIR / "metrics" / "moe_ms.batch.py")
    assert mod._expert_layer("jit(step)/matmul/L3.moe_ffn_g/jit(launch)")
    assert mod._expert_layer("jit(step)/moe_dispatch/L15.moe_dispatch")
    assert mod._expert_layer("jit(step)/elementwise/L0.moe_act")
    assert not mod._expert_layer("jit(step)/matmul/L3.q_proj/jit(launch)")
    assert not mod._expert_layer("jit(step)/bind")
