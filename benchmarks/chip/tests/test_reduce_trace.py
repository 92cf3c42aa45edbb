"""``reduce_trace`` on a small trace recorded on a TPU v5e
(``testdata/``, made by ``record_trace.py``: a quarter of a second of
``qwen3-4b.decode-batch`` at full size), and on hand-made planes."""
from __future__ import annotations

import json
import types

import pytest

from chipbench import cells, reduce_trace, serve_loop

CELL = "qwen3-4b.decode-batch"
TRACE = cells.BENCH_DIR / "testdata" / f"{CELL}.xplane.pb"


@pytest.fixture(scope="module")
def chip_trace():
    return reduce_trace.reduce_file(str(TRACE), serve_loop.SPANS)


def test_chip_trace_window_and_busy_time(chip_trace):
    s = chip_trace
    assert s.devices == 1
    assert 0.2 < s.window_s < 1.0
    assert 0 < s.busy_s <= s.window_s
    idle = sum(sec for _, sec in reduce_trace.idle_gaps(s))
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-9, abs=1e-9)


def test_chip_trace_finds_the_kernels_and_the_decode_program(chip_trace):
    s = chip_trace
    ns, n = reduce_trace.program_ns(s, "step")
    assert n >= 2 and ns > 0
    mm = reduce_trace.kernel_ns(s, "matmul_tile")
    fa = reduce_trace.kernel_ns(s, "flash_attention_decode")
    assert 0 < fa < mm < ns
    top = reduce_trace.top_ops(s)
    assert len(top) == 10 and top[0][0] == "matmul_tile"
    assert top[0][1] == pytest.approx(mm * 1e-9)


def test_chip_trace_reduces_as_recorded(chip_trace):
    """The reduction gives what the run that recorded it reported."""
    with open(TRACE.with_suffix("").with_suffix(".json")) as f:
        rec = json.load(f)
    assert chip_trace.busy_s == pytest.approx(rec["device"]["busy_s"])
    assert chip_trace.window_s == pytest.approx(rec["device"]["window_s"])


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur, stats=stats)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def test_hand_made_planes():
    host = _plane("/host:CPU", {"python": [
        _ev("bench_window", 100, 1000), _ev("step", 100, 500), _ev("sample", 400, 200),
        _ev("step", 600, 500), _ev("admit", 650, 100)]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [_ev("matmul_tile.3", 50, 250), _ev("fusion.1", 200, 150),
                    _ev("%flash_attention_decode.2 = bf16[4] custom-call(%matmul_tile.3)", 700, 100),
                    _ev("matmul_tile.9", 900, 50)],
        "XLA Modules": [_ev("jit_step(7)", 50, 300), _ev("jit_step(7)", 700, 250)]})
    s = reduce_trace.reduce_planes([host, dev], serve_loop.SPANS)
    assert s.window == (100, 1100)
    # busy: [100, 350) + [700, 800) + [900, 950)
    assert s.busy_ns == 250 + 100 + 50
    assert reduce_trace.kernel_ns(s, "matmul_tile") == 200 + 50
    assert reduce_trace.program_ns(s, "step") == (250 + 250, 2)
    # gaps [350, 700) in sample (latest start covering 525), [800, 900)
    # and [950, 1100) in the second step
    assert reduce_trace.idle_gaps(s) == [("sample", pytest.approx(350e-9)),
                                         ("step", pytest.approx(100e-9)),
                                         ("step", pytest.approx(150e-9))]
    assert reduce_trace.idle_by_span(s) == [["sample", pytest.approx(350e-9)],
                                            ["step", pytest.approx(250e-9)]]
    assert reduce_trace.same_program("jit__lambda(3)", "<lambda>")
