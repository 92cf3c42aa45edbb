#!/usr/bin/env python3
"""One traced run of a cell, told by the program's own scopes and spans.

    python3 benchmarks/chip/trace_report.py --workload <cell> --seed <n> \\
        [--seconds 10] [--trace-from 3] [--trace-seconds 5] \\
        [--keep <dir>] [--hlo <file>]

Runs the cell as ``run.py --trace 1`` does (the same harness, the same
metrics), keeps the trace, and prints one JSON line: the run's result
line, and from the trace (``chipbench.scopes``) the decode step's device
milliseconds per step by scope, the share of its device time that has a
program scope, the ops that have none, idle time by the innermost
program span, and the spans' arguments (admitted uids and prompt
lengths, live and queued requests per step). It also checks that the
trace's ``tf_op`` stats and the compiled step's HLO text name each op
alike. ``--hlo <file>`` writes the compiled step's HLO without its
metadata, to compare two commits' programs.

With ``--keep <dir>`` the trace is kept there as ``<cell>.xplane.pb``
(without the host metadata plane, which holds the programs' HLO) beside
``<cell>.json`` (the run's device record and metrics, the traced decode
positions, and the compiled step's ``op_name`` of each op the trace
ran): ``testdata/mamba2-2.7b.decode-batch.*`` is made so. Run on a TPU
v5e.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import re
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def _summary(vals):
    return {"n": len(vals), "min": min(vals), "max": max(vals)} if vals else None


def strip_metadata(text: str) -> str:
    """An HLO module's computations without metadata, module header or
    stack-frame tables: what two commits' programs share if their scopes
    are all that differs."""
    body = [ln for ln in text.splitlines() if ln.startswith(("%", "ENTRY", " ", "}"))]
    return re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(body)) + "\n"


def report(path: str) -> dict:
    """What the trace at ``path`` says, by the program's scopes and spans."""
    from chipbench import reduce_trace, scopes

    t = scopes.read(path)
    total = sum(op.end - op.start for op in t.ops)
    scoped = sum(op.end - op.start for op in t.ops if scopes.top_scope(op.scope))
    unscoped: dict = {}
    for op in t.ops:
        if not scopes.top_scope(op.scope):
            k = reduce_trace.base_name(op.name)
            unscoped[k] = unscoped.get(k, 0.0) + (op.end - op.start) * 1e-9
    per_step = 1e3 / t.steps if t.steps else 0.0
    idle = reduce_trace.idle_by_span(t.summary, n=20)
    idle_total = sum(v for _, v in idle)
    args = {name: [sp.args for sp in t.spans if sp.name == name and sp.args]
            for name in ("step", "admit", "prefill")}
    return {
        "steps": t.steps,
        "step_ms_by_scope": [[k, v * per_step] for k, v in scopes.by_scope(t.ops)],
        "scoped_share": scoped / total if total else None,
        "unscoped_s": sorted(unscoped.items(), key=lambda kv: -kv[1])[:8],
        "idle_by_span_s": idle,
        "idle_none_share": (dict(idle).get("none", 0.0) / idle_total) if idle_total else None,
        "spans": {name: sum(1 for sp in t.spans if sp.name == name)
                  for name in scopes.PROGRAM_SPANS},
        "args": {
            "live": _summary([a["live"] for a in args["step"]]),
            "queued": _summary([a["queued"] for a in args["step"]]),
            "admitted_uids": [a["uid"] for a in args["admit"]],
            "prompt_len": _summary([a["prompt_len"] for a in args["prefill"]]),
        },
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-from", type=float, default=None)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--keep", default=None, help="directory to keep the trace in")
    ap.add_argument("--hlo", default=None,
                    help="file to write the compiled decode step's HLO to, without metadata")
    args = ap.parse_args(argv)

    import run

    cache_dir = run.enable_compile_cache()
    from chipbench import cells, harness, reduce_trace, scopes

    mix = cells.load_traffic(cells.find_cell(cells.load_benchmark(), args.workload).traffic)
    if args.trace_from is not None:
        mix["trace_from"] = args.trace_from
    if args.trace_seconds is not None:
        mix["trace_seconds"] = args.trace_seconds
    seen: dict = {}
    read_metrics = cells.read_metrics

    def read_and_note(entries, run_rec, *a, **kw):
        # what the run record holds that the result line does not: the
        # traced steps' live positions, the compiled step's scope map
        seen["traced_decodes"] = [[int(x) for x in p] for p in run_rec.traced_decodes()]
        seen["hlo"] = scopes.compiled_step_text(run_rec.engine) or ""
        return read_metrics(entries, run_rec, *a, **kw)

    cells.read_metrics = read_and_note
    tdir = tempfile.mkdtemp(prefix="trace_report_")
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds, True,
                                t_start=T_START, cache_dir=cache_dir, mix=mix,
                                keep_trace=tdir)
        path = glob.glob(os.path.join(tdir, "*.xplane.pb"))[0]
        out = {"line": line, "report": report(path)}
        xplane = scopes.op_scopes(path)
        hlo = scopes.hlo_scopes(seen["hlo"])
        xplane_ops = {scopes.instruction(op.name) for op in
                      scopes.program_ops(reduce_trace.reduce_file(path, ()), "step")}
        out["report"]["scope_sources_agree"] = {
            "ops": len(xplane),
            "differ": sum(1 for k, v in xplane.items()
                          if scopes.scope_path(v) != scopes.scope_path(hlo.get(k, "")))}
        if args.hlo:
            with open(args.hlo, "w") as f:
                f.write(strip_metadata(seen["hlo"]))
        if args.keep:
            keep = pathlib.Path(args.keep)
            keep.mkdir(parents=True, exist_ok=True)
            # the host metadata plane holds the programs' HLO, which
            # nothing here reads: the kept trace leaves it out
            space = scopes.xspace(path)
            planes = [p for p in space.planes if p.name != "/host:metadata"]
            del space.planes[:]
            space.planes.extend(planes)
            with open(keep / f"{args.workload}.xplane.pb", "wb") as f:
                f.write(space.SerializeToString())
            with open(keep / f"{args.workload}.json", "w") as f:
                json.dump({"device": line["device"], "metrics": line["metrics"],
                           "traced_decodes": seen["traced_decodes"],
                           "hlo_scopes": {k: v for k, v in hlo.items() if k in xplane_ops}},
                          f, indent=1)
    except harness.NoChip as e:
        print(f"trace_report.py: {e}", file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
