"""``chipbench.scopes``: device time by the program's scopes and the
program's host spans, on hand-made planes, on the committed chip traces
(``testdata/``), and on a compiled program's HLO text."""
from __future__ import annotations

import json
import re
import types

import pytest

from chipbench import cells, reduce_trace, scopes

TESTDATA = cells.BENCH_DIR / "testdata"
QWEN3 = TESTDATA / "qwen3-4b.decode-batch.xplane.pb"
MAMBA2 = TESTDATA / "mamba2-2.7b.decode-batch.xplane.pb"


@pytest.mark.parametrize("tf_op,path", [
    ("jit(step)/bind/squeeze:", "jit(step)/bind"),
    ("jit(step)/matmul/L3.q_proj/jit(launch)/matmul_tile/pallas_call:",
     "jit(step)/matmul/L3.q_proj/jit(launch)/matmul_tile"),
    # fused from several stacks: the scope they share ...
    ("jit(step)/bind/reshape;jit(step)/bind/squeeze:", "jit(step)/bind"),
    ("jit(step)/matmul/L0.q_proj/add;jit(step)/matmul/L1.q_proj/mul",
     "jit(step)/matmul"),
    # ... else the first one's
    ("jit(step)/restack/concatenate;jit(step)/cache_update/L3.k_cache_write/select_n:",
     "jit(step)/restack"),
    ("jit(step)/reshape;jit(step)/squeeze:", "jit(step)"),
    ("", ""),
])
def test_scope_path(tf_op, path):
    assert scopes.scope_path(tf_op) == path


def test_in_scope_matches_whole_names_not_primitives():
    assert scopes.in_scope("jit(step)/matmul/L3.q_proj/jit(launch)/matmul_tile", "matmul")
    assert scopes.in_scope("jit(step)/matmul/L3.q_proj", "matmul/L3.q_proj")
    assert not scopes.in_scope("jit(step)/jit(launch)/matmul_tile", "matmul")
    # a node kind named like a primitive is matched only as a scope
    assert scopes.in_scope(scopes.scope_path("jit(step)/reshape/L0.attn_flat/reshape:"),
                           "reshape")
    assert not scopes.in_scope(scopes.scope_path("jit(step)/bind/reshape:"), "reshape")
    assert scopes.top_scope("jit(step)/cache_update/L0.k_cache_write") == "cache_update"
    assert scopes.top_scope("jit(step)/jit(launch)/matmul_tile") == ""


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur, stats=stats)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k, events=v) for k, v in lines.items()])


def _planes():
    """One decode step [100, 400) and one prefill [600, 700) on the
    device; the batcher's spans on the host, the harness's ``step`` and
    ``admit`` wrapped around the program's."""
    host = _plane("/host:CPU", {"python": [
        _ev("bench_window", 0, 1000),
        _ev("step", 0, 1000), _ev("step", 0, 1000, (("step", 4), ("live", 1), ("queued", 2))),
        _ev("inputs", 50, 40), _ev("decode", 90, 10),
        _ev("sample", 400, 100), _ev("sample", 410, 80),
        _ev("admit", 500, 400), _ev("admit", 500, 400, (("uid", 7),)),
        _ev("prefill", 510, 100, (("uid", 7), ("prompt_len", 64))),
        _ev("first_token", 700, 100, (("uid", 7),)),
        _ev("slot_write", 800, 50, (("uid", 7),))]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [
            _ev("%fusion.1 = bf16[8] fusion(...)", 100, 50),
            _ev("%matmul_tile.2 = bf16[8] custom-call(...)", 150, 100),
            _ev("%fusion.3 = bf16[8] fusion(...)", 250, 50),
            _ev("%select.4 = bf16[8] select(...)", 300, 80),
            _ev("%copy.5 = bf16[8]{0} copy(bf16[8]{0} %cache__l0____k__.1)", 380, 20),
            _ev("%fusion.1 = bf16[8] fusion(...)", 600, 100)],
        "XLA Modules": [_ev("jit_step(11)", 100, 300), _ev("jit_prefill(12)", 600, 100)]})
    return [host, dev]


SCOPE_MAP = {
    "fusion.1": "jit(step)/bind/squeeze:",
    "matmul_tile.2": "jit(step)/matmul/L0.q_proj/jit(launch)/matmul_tile/pallas_call:",
    "fusion.3": "jit(step)/restack/concatenate;jit(step)/cache_update/L0.k_cache_write/select_n:",
    "select.4": "jit(step)/cache_update/L0.k_cache_write/select_n:",
}


def test_hand_made_planes_with_a_scope_map():
    s = reduce_trace.reduce_planes(_planes(), scopes.PROGRAM_SPANS)
    ops = scopes.tag(s, SCOPE_MAP)
    # the prefill's fusion.1 is not the step's, whatever its name
    assert [op.scope for op in ops] == ["jit(step)/bind",
                                        "jit(step)/matmul/L0.q_proj/jit(launch)/matmul_tile",
                                        "jit(step)/restack", "jit(step)/cache_update/L0.k_cache_write",
                                        # XLA's copy of a step argument: binding
                                        "jit(step)/bind"]
    assert scopes.scope_ns(ops, "bind") == 50 + 20
    assert scopes.scope_ns(ops, "bind", "restack") == 120
    assert scopes.scope_ns(ops, "matmul") == 100
    assert scopes.scope_ns(ops, "cache_update") == 80
    assert scopes.scope_ns(ops, "cache_update/L0.k_cache_write") == 80
    assert dict(scopes.by_scope(ops)) == pytest.approx(
        {"bind": 70e-9, "matmul": 100e-9, "restack": 50e-9, "cache_update": 80e-9})


def test_idle_goes_to_the_innermost_program_span():
    s = reduce_trace.reduce_planes(_planes(), scopes.PROGRAM_SPANS)
    # idle [0, 100), [400, 600) and [700, 1000): their midpoints 50, 500
    # and 850 lie in inputs, in admit (started later than sample) and in
    # slot_write (inside admit)
    gaps = reduce_trace.idle_gaps(s)
    assert [g[0] for g in gaps] == ["inputs", "admit", "slot_write"]
    # nested same-name spans (a caller's and the program's) count once
    assert len(scopes.outermost(s.spans, "admit")) == 1
    assert len(scopes.outermost(s.spans, "step")) == 1
    assert scopes.idle_ms_per_span(s, scopes.ADMIT_SPANS) == pytest.approx((200 + 300) * 1e-6)
    assert scopes.idle_ms_per_span(s, ("sample",)) == 0.0
    assert scopes.idle_ms_per_span(s, ("no_such_span",)) is None


def test_host_spans_keep_their_arguments():
    planes = _planes()
    s = reduce_trace.reduce_planes(planes, scopes.PROGRAM_SPANS)
    spans = scopes.host_spans(planes, scopes.PROGRAM_SPANS, s.window)
    args = {sp.name: sp.args for sp in spans if sp.args}
    assert args["step"] == {"step": 4, "live": 1, "queued": 2}
    assert args["prefill"] == {"uid": 7, "prompt_len": 64}
    assert {sp.args.get("uid") for sp in spans if sp.name in scopes.ADMIT_SPANS} == {7, None}


def test_op_scopes_reads_the_committed_chip_trace():
    m = scopes.op_scopes(str(QWEN3))
    mm = [v for k, v in m.items() if k.startswith("matmul_tile.")]
    assert mm and all(v == "jit(step)/jit(launch)/matmul_tile/pallas_call:" for v in mm)
    assert scopes.op_scopes(str(QWEN3), program="no_such_program") == {}
    # recorded before the program named its scopes: every op carries
    # primitive names only, and XLA's copies of the step's arguments
    # (the tied head's weight, the donated K and V caches) none
    s = reduce_trace.reduce_file(str(QWEN3), scopes.PROGRAM_SPANS)
    ops = scopes.tag(s, m)
    assert ops and {scopes.top_scope(op.scope) for op in ops} == {"", "bind"}
    moved = {re.search(r" copy\([^%]*%([\w.]+)\)", op.name).group(1)
             for op in ops if op.scope == "jit(step)/bind"
             and reduce_trace.base_name(op.name) == "copy"}
    assert moved == {"params__embed__.1", "cache__l0____k__.1", "cache__l0____v__.1"}


def test_hlo_scopes_reads_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def step(x, w):
        with jax.named_scope("bind"):
            w = jnp.sin(w)
        with jax.named_scope("matmul"), jax.named_scope("L0.q_proj"):
            return jnp.tanh(x @ w)

    x = jnp.ones((4, 8))
    text = jax.jit(step).lower(x, jnp.ones((8, 8))).compile().as_text()
    m = scopes.hlo_scopes(text)
    paths = {scopes.scope_path(v) for v in m.values()}
    assert "jit(step)/bind" in paths and "jit(step)/matmul/L0.q_proj" in paths


def _recorded_run(cell, path, source):
    """A run record rebuilt from a committed trace and the record the run
    that made it wrote beside it (``trace_report.py --keep``), its scope
    map from the trace's ``tf_op`` stats or from the compiled step's
    HLO ``op_name``s that the run recorded."""
    from chipbench import work
    from chipbench.peaks import peaks_for

    with open(path.with_suffix("").with_suffix(".json")) as f:
        rec = json.load(f)
    doc = cells.load_config(cells.find_cell(cells.load_benchmark(), cell).config)
    ref = cells.load_reference(doc)

    def step():
        pass

    return rec, types.SimpleNamespace(
        trace=reduce_trace.reduce_file(str(path), scopes.PROGRAM_SPANS),
        scope_map=scopes.op_scopes(str(path)) if source == "trace" else rec["hlo_scopes"],
        engine=types.SimpleNamespace(decode_fn=lambda **_: step),
        work=work.Work(ref, ref.sizes(doc)), peaks=peaks_for(rec["device"]["kind"]),
        traced_decodes=lambda: rec["traced_decodes"])


NEW = ("bind_ms.batch", "ssm_decode_ms.batch", "matmul_roofline", "sample_idle_ms.batch",
       "admit_idle_ms.batch")


@pytest.mark.parametrize("metric", NEW)
def test_scoped_chip_trace_reads_as_recorded(metric):
    """On the committed scoped mamba2 trace each new metric is non-null,
    and reads the same through the trace's ``tf_op`` stats, through the
    compiled step's ``op_name``s, and as the run that recorded it read
    it."""
    rec, by_trace = _recorded_run("mamba2-2.7b.decode-batch", MAMBA2, "trace")
    _, by_hlo = _recorded_run("mamba2-2.7b.decode-batch", MAMBA2, "hlo")
    read = cells.load_metric(metric)
    v = read(by_trace)
    assert v is not None and v >= 0
    if metric.endswith("roofline"):
        assert 0 < v <= 100
    assert read(by_hlo) == pytest.approx(v, rel=1e-9)
    assert rec["metrics"][metric]["value"] == pytest.approx(v, rel=1e-6)


def test_scoped_chip_trace_covers_the_step():
    """Ops with a program scope cover nearly all of the step's device
    time, the kernels keep their instruction names, and every program
    span is there with its arguments."""
    t = scopes.read(str(MAMBA2))
    total = sum(op.end - op.start for op in t.ops)
    scoped = sum(op.end - op.start for op in t.ops if scopes.top_scope(op.scope))
    assert t.steps >= 2 and scoped >= 0.95 * total
    assert any(reduce_trace.base_name(op.name) == "matmul_tile" and
               scopes.in_scope(op.scope, "matmul") for op in t.ops)
    assert {sp.name for sp in t.spans} == set(scopes.PROGRAM_SPANS)
    # the admission in the window: its spans share its uid
    uids = {sp.name: sp.args["uid"] for sp in t.spans
            if sp.name in scopes.ADMIT_SPANS and "uid" in sp.args}
    assert set(uids) == set(scopes.ADMIT_SPANS) and len(set(uids.values())) == 1
    assert all({"step", "live", "queued"} <= set(sp.args) for sp in t.spans
               if sp.name == "step" and sp.args)
