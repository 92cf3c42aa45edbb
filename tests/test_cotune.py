"""Cotune (docs/cotune.md): the solve <-> tune fixed-point loop, the
measured-cost feedback table it runs on, and the mergeable schedule
service artifact underneath.

Four layers under test. (1) The seam: ``solve(..., cost_model=None)``
and an *empty* :class:`~repro.tune.feedback.CostModel` are bit-identical
to the plain analytic solve, and ``cotune`` with a table that never
fires degenerates to exactly one solve. (2) The loop: on every model-zoo
config the iterate terminates within ``max_iters`` with a monotonically
non-increasing corrected objective and a final cost no worse than the
one-shot solve's. (3) The flip: a constructed cost table that penalizes
the one-shot layout's local matmul provably changes the solver's
decision — the whole point of closing the loop. (4) The service:
artifact merging is associative / commutative / idempotent and corrupt
entries are quarantined, never fatal.
"""
import json

import pytest

from repro import axe, compat
from repro.axe.cotune import cotune
from repro.axe.solve import op_seconds, solve
from repro.axe.spec import PhysicalSpace
from repro.configs import ARCH_IDS, get_config, smoke_variant
from repro.tune import use_cache
from repro.tune.cache import CacheEntry, ScheduleCache
from repro.tune.feedback import CostModel, _analytic_stage_seconds, parse_key
from repro.tune.planner import spec_key_parts
from repro.tune.schedule import Schedule, schedule_key
from repro.tune.service import (
    ServiceArtifact,
    load_into,
    merge_artifacts,
    merge_entry,
)

_SPACE = PhysicalSpace.from_mesh_shape({"data": 16, "model": 16})


def _cfg(arch):
    cfg = smoke_variant(get_config(arch))
    return cfg


def _graph(arch, batch=8, seq=512, space=_SPACE):
    return axe.model_graph(_cfg(arch), batch, seq, space, layers=2)


def _sig(res):
    return res.plan.signature()


def _matmul_locals(res):
    """The distinct 2-operand ``matmul/tile`` local problems a solved
    plan induces — the keys the in-loop tune step would measure."""
    out = []
    seen = set()
    for e in res.plan.entries:
        if e.op.kind != "matmul" or len(e.op.inputs) != 2:
            continue
        parts = spec_key_parts("matmul", e.input_specs(res.plan.env))
        if parts is None or parts[0] != "matmul/tile" or parts in seen:
            continue
        seen.add(parts)
        out.append(parts)
    return out


# ---------------------------------------------------------------------------
# the cost_model= seam: analytic fallback is bit-identical
# ---------------------------------------------------------------------------


def test_empty_cost_model_is_bit_identical_to_analytic():
    gs = _graph("qwen3-4b")
    cm = CostModel()
    plain = solve(gs)
    seamed = solve(gs, cost_model=cm)
    assert _sig(plain) == _sig(seamed)
    assert plain.objective_s == seamed.objective_s
    assert plain.comm_bytes == seamed.comm_bytes
    # every lookup fell through to the analytic roofline
    assert cm.lookups["analytic"] > 0
    assert cm.lookups["measured"] == cm.lookups["calibrated"] == 0


def test_op_seconds_delegates_to_cost_model():
    gs = _graph("qwen3-4b")
    res = solve(gs)
    e = next(e for e in res.plan.entries
             if e.op.kind == "matmul" and len(e.op.inputs) == 2)
    specs = e.input_specs(res.plan.env)
    out_spec = res.plan.env[e.op.out]
    base = op_seconds("matmul", specs, out_spec)

    class Pinned:
        def op_seconds(self, kind, operands, out_spec, backend="tpu", *,
                       epilogue=()):
            return 42.0

    assert op_seconds("matmul", specs, out_spec, cost_model=Pinned()) == 42.0
    assert op_seconds("matmul", specs, out_spec, cost_model=None) == base
    # an empty table's CostModel answer equals the analytic one exactly
    assert CostModel().op_seconds("matmul", specs, out_spec) == base


# ---------------------------------------------------------------------------
# fixed point on every zoo config
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_cotune_fixed_point_all_configs(arch):
    gs = _graph(arch, batch=2, seq=64)
    cm = CostModel()
    # seed a real calibration point: the one-shot plan's first matmul
    # local problem, measured (synthetically) at 3x its roofline — a
    # plausible "the model is optimistic" table every config can hit
    base = solve(gs)
    locals_ = _matmul_locals(base)
    if locals_:
        op, shapes, dtypes, sig = locals_[0]
        ana = _analytic_stage_seconds(op, shapes, dtypes, "tpu")
        cm.add_measurement(op, shapes, dtypes, ana * 3.0 * 1e6,
                           layout_sig=sig, backend="tpu")
    ct = cotune(gs, cost_model=cm, max_iters=4)
    assert ct.converged
    assert 1 <= len(ct.iterations) <= 4
    objs = [it.objective_s for it in ct.iterations]
    for prev, cur in zip(objs, objs[1:]):
        assert cur <= prev * (1.0 + 1e-12), (arch, objs)
    assert ct.objective_s <= ct.iter0_objective_s * (1.0 + 1e-12)
    d = ct.to_dict()
    assert d["iters"] == len(ct.iterations)
    assert d["final_objective_s"] == ct.objective_s
    assert "cotune iters=" in ct.describe()


def test_cotune_empty_table_degenerates_to_one_solve():
    gs = _graph("qwen3-4b")
    cm = CostModel()
    ct = cotune(gs, cost_model=cm, max_iters=4)
    plain = solve(gs)
    assert len(ct.iterations) == 1 and ct.converged and not ct.flipped
    assert _sig(ct.result) == _sig(plain)
    assert ct.result.objective_s == plain.objective_s
    assert {k: s.signature() for k, s in ct.assignment.items()} == \
        {k: s.signature() for k, s in plain.assignment.items()}


def test_model_executable_cotune_false_parity(tmp_path):
    """``cotune=True`` with an empty measurement table ships the same
    plan as ``cotune=False`` (PR-9 behavior), and the report says one
    solve ran."""
    use_cache(tmp_path / "schedules.json")  # empty ambient cache
    try:
        cfg = _cfg("qwen3-4b")
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        exe_a = axe.model_executable(cfg, mesh, 2, 32, layers=2,
                                     dtype=cfg.dtype)
        exe_c = axe.model_executable(cfg, mesh, 2, 32, layers=2,
                                     dtype=cfg.dtype, cotune=True)
        assert exe_a.cotune_report is None
        ct = exe_c.cotune_report
        assert ct is not None and len(ct.iterations) == 1 and ct.converged
        assert _sig(exe_a.solve_result) == _sig(exe_c.solve_result)
        assert exe_a.solve_result.objective_s == exe_c.solve_result.objective_s
    finally:
        use_cache(None)


# ---------------------------------------------------------------------------
# a constructed table flips the solver's layout
# ---------------------------------------------------------------------------


def test_constructed_table_flips_layout():
    """Penalize the one-shot layout's local matmul at 50x its roofline:
    the re-solve must walk away from that layout (different plan
    signature) and the corrected objective must strictly improve over
    shipping the one-shot plan."""
    gs = _graph("qwen3-4b")
    base = solve(gs, compare_seeded=False)
    locals_ = _matmul_locals(base)
    assert locals_, "qwen3-4b plan has no matmul locals to penalize"
    op, shapes, dtypes, sig = locals_[0]
    ana = _analytic_stage_seconds(op, shapes, dtypes, "tpu")
    assert ana is not None and ana > 0.0
    cm = CostModel()
    cm.add_measurement(op, shapes, dtypes, ana * 50.0 * 1e6,
                       layout_sig=sig, backend="tpu")
    ct = cotune(gs, cost_model=cm, max_iters=4, compare_seeded=False)
    assert ct.flipped, ct.describe()
    assert len(ct.iterations) > 1 and ct.converged
    assert ct.objective_s < ct.iter0_objective_s  # strictly better
    # the table actually fired on the queries that moved the decision
    assert cm.lookups["measured"] > 0 or cm.lookups["calibrated"] > 0
    # iteration rows carry the provenance counts the flip came from
    assert any(it.measured_hits + it.calibrated_hits > 0
               for it in ct.iterations)


def test_cost_model_lookup_ladder():
    """measured > calibrated > analytic, with provenance reported."""
    gs = _graph("qwen3-4b")
    res = solve(gs)
    e = next(e for e in res.plan.entries
             if e.op.kind == "matmul" and len(e.op.inputs) == 2)
    specs = e.input_specs(res.plan.env)
    out_spec = res.plan.env[e.op.out]
    parts = spec_key_parts("matmul", specs)
    assert parts is not None
    op, shapes, dtypes, sig = parts
    ana = _analytic_stage_seconds(op, shapes, dtypes, "tpu")

    cm = CostModel()
    assert cm.lookup("matmul", specs, out_spec).provenance == "analytic"
    # a same-family neighbor (different shapes) -> calibrated
    other = tuple((d * 2 for d in s) for s in shapes)
    cm.add_measurement(op, other, dtypes, 1e6, backend="tpu")
    lk = cm.lookup("matmul", specs, out_spec)
    assert lk.provenance == "calibrated" and lk.neighbor is not None
    # the exact key -> measured, charging the measured stage seconds
    cm.add_measurement(op, shapes, dtypes, ana * 7.0 * 1e6,
                       layout_sig=sig, backend="tpu")
    lk = cm.lookup("matmul", specs, out_spec)
    assert lk.provenance == "measured"
    assert lk.seconds == pytest.approx(ana * 7.0, rel=1e-9)
    # cross-backend exact measurements still satisfy the solver's query
    cm2 = CostModel()
    cm2.add_measurement(op, shapes, dtypes, ana * 5.0 * 1e6,
                        layout_sig=sig, backend="cpu")
    assert cm2.lookup("matmul", specs, out_spec,
                      backend="tpu").provenance == "measured"


# ---------------------------------------------------------------------------
# service artifact: merge laws + quarantine
# ---------------------------------------------------------------------------

_SCHED_A = Schedule("matmul", "kernel", (("bm", 128), ("bn", 128), ("bk", 256)))
_SCHED_B = Schedule("matmul", "xla")
_KEY = schedule_key("matmul/tile", ((64, 64), (64, 64)),
                    ("float32", "float32"), "dense", "cpu")
_KEY2 = schedule_key("matmul/tile", ((128, 64), (64, 32)),
                     ("float32", "float32"), "dense", "cpu")


def _art(entries):
    a = ServiceArtifact()
    a.entries.update(entries)
    return a


def _mk(schedule, us, ts, source="measured", measurements=()):
    return CacheEntry(schedule, us, source, tuple(measurements),
                      {"backend": "cpu"}, ts)


def test_service_merge_laws():
    a = _art({_KEY: _mk(_SCHED_A, 100.0, 10.0,
                        measurements=(("kernel", 100.0), ("xla", 130.0)))})
    b = _art({_KEY: _mk(_SCHED_B, 90.0, 20.0,
                        measurements=(("xla", 90.0),)),
              _KEY2: _mk(_SCHED_A, 55.0, 5.0)})
    c = _art({_KEY: _mk(_SCHED_A, 80.0, 15.0,
                        measurements=(("kernel", 80.0),)),
              _KEY2: _mk(_SCHED_B, None, None, source="planned")})

    def pay(art):
        return json.dumps(art.payload(), sort_keys=True)

    # associative, commutative, idempotent
    assert pay(merge_artifacts(merge_artifacts(a, b), c)) == \
        pay(merge_artifacts(a, merge_artifacts(b, c)))
    assert pay(merge_artifacts(a, b, c)) == pay(merge_artifacts(c, b, a))
    assert pay(merge_artifacts(a, a)) == pay(merge_artifacts(a))
    merged = merge_artifacts(a, b, c)
    # newest measurement wins (b's ts=20 beats a's 10 and c's 15) ...
    assert merged.entries[_KEY].schedule.impl == "xla"
    assert merged.entries[_KEY].us == 90.0
    # ... but per-candidate measurements union, fastest per candidate
    assert dict(merged.entries[_KEY].measurements) == \
        {"kernel": 80.0, "xla": 90.0}
    # measured beats planned regardless of timestamps
    assert merged.entries[_KEY2].source == "measured"
    e = _mk(_SCHED_A, 100.0, 10.0, measurements=(("kernel", 100.0),))
    assert merge_entry(e, e).to_dict() == merge_entry(
        merge_entry(e, e), e).to_dict()


def test_service_quarantine_and_roundtrip(tmp_path):
    good = _mk(_SCHED_A, 100.0, 10.0, measurements=(("kernel", 100.0),))
    p = tmp_path / "svc.json"
    p.write_text(json.dumps({
        "version": 2,
        "entries": {
            _KEY: good.to_dict(),
            "broken|key": {"us": 1.0},                 # no schedule
            "worse|key": {"schedule": {"op": "matmul", "impl": "nope"}},
        },
    }))
    art = ServiceArtifact.load(p)
    assert set(art.entries) == {_KEY}
    assert set(art.quarantined) == {"broken|key", "worse|key"}
    # quarantined entries are scrubbed on save, healthy ones round-trip
    out = tmp_path / "clean.json"
    art.save(out)
    art2 = ServiceArtifact.load(out)
    assert not art2.quarantined
    assert art2.entries[_KEY].to_dict() == merge_entry(good, good).to_dict()
    # a corrupt *file* is an empty artifact with one quarantine note
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    broken = ServiceArtifact.load(bad)
    assert not broken.entries and "<file>" in broken.quarantined
    # and the merge CLI path survives it
    merged = merge_artifacts(art2, broken)
    assert set(merged.entries) == {_KEY} and "<file>" in merged.quarantined


def test_service_cli_and_load_into(tmp_path, capsys):
    from repro.tune.service import main as service_main

    a, b = tmp_path / "a.json", tmp_path / "b.json"
    _art({_KEY: _mk(_SCHED_A, 100.0, 10.0)}).save(a)
    _art({_KEY: _mk(_SCHED_B, 90.0, 20.0),
          _KEY2: _mk(_SCHED_A, 55.0, 5.0)}).save(b)
    out = tmp_path / "merged.json"
    assert service_main(["merge", str(out), str(a), str(b)]) == 0
    assert service_main(["show", str(out)]) == 0
    text = capsys.readouterr().out
    assert "2 entries" in text and _KEY in text
    # merging again with OUT existing is idempotent (OUT is an input)
    assert service_main(["merge", str(out), str(a)]) == 0
    art = ServiceArtifact.load(out)
    assert art.entries[_KEY].us == 90.0 and len(art) == 2

    cache = ScheduleCache()  # memory-only
    assert load_into(cache, out) == 2
    assert cache.get(_KEY).schedule.impl == "xla"
    # re-loading adopts nothing new; a weaker artifact never downgrades
    assert load_into(cache, out) == 0
    _art({_KEY: _mk(_SCHED_A, 100.0, 10.0)}).save(a)
    assert load_into(cache, a) == 0
    assert cache.get(_KEY).us == 90.0

    assert service_main(["prune", str(out), "--older-than-days", "0"]) == 0
    assert len(ServiceArtifact.load(out)) == 0


def test_cost_model_from_cache_and_parse_key():
    cache = ScheduleCache()
    cache.put(_KEY, _SCHED_A, us=123.0, source="measured",
              measurements=(("kernel", 123.0),), updated_at=1.0)
    cache.put(_KEY2, _SCHED_B, source="planned", persist=False)
    cm = CostModel.from_cache(cache)
    assert len(cm) == 1  # planned entries carry no measured truth
    (e,) = cm.entries()
    assert e.op == "matmul/tile" and e.us == 123.0 and e.backend == "cpu"
    assert parse_key(_KEY) == ("matmul/tile", ((64, 64), (64, 64)),
                               ("float32", "float32"), "dense", "cpu")
    assert parse_key("garbage") is None
