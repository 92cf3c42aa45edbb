"""A new configuration, traffic mix, metric and cell are picked up by
adding files and entries, without editing a file that is there."""
from __future__ import annotations

import json
import shutil

from chipbench import cells


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(cells.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    shutil.copy(cells.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    (bench_dir / "configs" / "toy-1b.json").write_text(json.dumps(
        {**cells.load_config("qwen3-4b"), "name": "toy-1b"}))
    # a new arrival shape: a mix that names a generator of its own
    (bench_dir / "traffic" / "chat-burst.json").write_text(json.dumps(
        {**cells.load_traffic("decode-batch"), "generator": "bursts", "burst": 8}))
    (bench_dir / "traffic" / "bursts.py").write_text(
        "def feed(mix, seed, vocab):\n    return ('bursts', mix['burst'], seed)\n")
    (bench_dir / "metrics" / "queue_ms.chat.py").write_text(
        "def value(run):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-1b", "source": "x", "file": "f",
                             "reduced": [], "why": "y"})
    bench["workloads"].append({"name": "toy-1b.chat-burst", "config": "toy-1b",
                               "traffic": "chat-burst", "chips": 1, "why": "z"})
    bench["per_layer"].append({"name": "queue_ms.chat", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "batcher admission",
                               "moves": "itl_p95_ms", "workloads": ["toy-1b.chat-burst"]})

    cell = cells.find_cell(bench, "toy-1b.chat-burst")
    assert cells.load_config(cell.config, bench_dir)["name"] == "toy-1b"
    mix = cells.load_traffic(cell.traffic, bench_dir)
    assert cells.load_generator(mix, bench_dir).feed(mix, 3, 100) == ("bursts", 8, 3)
    assert [m["name"] for m in cell.per_layer] == ["queue_ms.chat"]
    got = cells.read_metrics(cell.per_layer, run=None, bench_dir=bench_dir)
    assert got == {"queue_ms.chat": {"value": 42.0, "unit": "ms"}}
    assert cells.load_reference(cells.load_config("toy-1b", bench_dir), bench_dir).sizes
    # nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_reader_that_finds_nothing_leaves_its_metric_out(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "silent.py").write_text("def value(run):\n    return None\n")
    entries = [{"name": "silent", "unit": "%"}]
    assert cells.read_metrics(entries, run=None, bench_dir=tmp_path) == {}


def test_a_mix_without_a_generator_gets_the_default():
    from chipbench import traffic

    assert cells.load_generator(cells.load_traffic("decode-batch")) is traffic
