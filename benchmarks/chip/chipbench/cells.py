"""Cell discovery: everything that belongs to one configuration, one
traffic mix or one metric sits in a file of its own, found by the name
``BENCHMARK.json`` gives it.

- ``configs/<config>.json``: the configuration as it is run;
- ``reference/<reference>.py``: its plain float32 reference, named by
  the configuration file's ``reference`` key;
- ``traffic/<traffic>.json``: the parameters of a traffic mix, read by
  the generator in ``chipbench.traffic``, or by ``traffic/<name>.py``
  where the mix names ``"generator": "<name>"``;
- ``metrics/<metric>.py``: a reader with ``value(run) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

#: benchmarks/chip/chipbench/cells.py -> benchmarks/chip
BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
#: the checkout root, where BENCHMARK.json lives
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    end_to_end: List[dict]   # the cell's end-to-end metric entries
    per_layer: List[dict]    # the cell's per-layer metric entries


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: dict, name: str) -> Cell:
    for w in bench["workloads"]:
        if w["name"] == name:
            return Cell(
                name=name, config=w["config"], traffic=w["traffic"],
                chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
            )
    known = [w["name"] for w in bench["workloads"]]
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "configs" / f"{name}.json")


def load_traffic(name: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    return _json(bench_dir / "traffic" / f"{name}.json")


def _module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_generator(mix: dict, bench_dir: pathlib.Path = BENCH_DIR):
    """The module whose ``feed(mix, seed, vocab)`` makes the mix's
    requests: ``traffic/<generator>.py`` where the mix names one, else
    ``chipbench.traffic``."""
    if "generator" in mix:
        return _module(bench_dir / "traffic" / f"{mix['generator']}.py")
    from chipbench import traffic
    return traffic


def load_reference(config: dict, bench_dir: pathlib.Path = BENCH_DIR):
    """The reference module the configuration file names."""
    return _module(bench_dir / "reference" / f"{config['reference']}.py")


def load_metric(name: str, bench_dir: pathlib.Path = BENCH_DIR
                ) -> Callable[[Any], Optional[float]]:
    """The reader of metric ``name``: ``value(run) -> float | None``."""
    return _module(bench_dir / "metrics" / f"{name}.py").value


def read_metrics(entries: List[dict], run: Any,
                 bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, dict]:
    """Each metric's reader applied to ``run``; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in entries:
        v = load_metric(m["name"], bench_dir)(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def load_limits(cell: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    """The cell's comparison limits (``limits/<cell>.json``)."""
    return _json(bench_dir / "limits" / f"{cell}.json")
