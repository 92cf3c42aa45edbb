"""``BENCHMARK.json`` keeps to the benchmark's contract: names and units
of the allowed characters, every configuration used, every metric's
cells and files there."""
from __future__ import annotations

import json
import re

from chipbench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    return cells.load_benchmark()


def test_top_level_keys_and_command():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert b["command"][1].startswith("benchmarks/chip/")
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    # a full check at 24 cells: 2 + 14 * 24 runs, each run_seconds + 60 s,
    # 2 * 90 s of compile per cell and 1200 s spare, within 43200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(b)) < 64 * 1024


def test_names_and_units_use_the_allowed_characters():
    b = _bench()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in b["configs"]:
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert "\n" not in m["layer"] and "\t" not in m["layer"]


def test_every_configuration_has_a_cell_and_every_cell_its_files():
    b = _bench()
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        doc = cells.load_config(c["name"])
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert doc["reduced"] == c["reduced"] and doc["source"] == c["source"]
        cells.load_reference(doc)
    for w in b["workloads"]:
        cells.load_traffic(w["traffic"])
        assert cells.load_limits(w["name"])["max_logit_gap"]["limit"] > 0


def test_every_metric_has_a_reader_and_its_cells_exist():
    b = _bench()
    cell_names = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(cells.load_metric(m["name"]))
        assert set(m.get("workloads", cell_names)) <= cell_names, m["name"]
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cell_names))
        assert set(m.get("workloads", cell_names)) <= moved, m["name"]
    for name in cell_names:
        cell = cells.find_cell(b, name)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2 and cell.per_layer


def test_configuration_files_match_the_program_settings():
    """What the program is given is what the file says was published."""
    q = cells.load_config("qwen3-4b")
    pub, prog = q["published"], q["program"]["set"]
    assert (prog["num_layers"], prog["d_model"], prog["num_heads"], prog["num_kv_heads"],
            prog["head_dim"], prog["d_ff"], prog["vocab_size"]) == (
        pub["num_hidden_layers"], pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"], pub["intermediate_size"],
        pub["vocab_size"])
    assert prog["rope_theta"] == pub["rope_theta"]
    assert prog["tie_embeddings"] == pub["tie_word_embeddings"]
    m = cells.load_config("mamba2-2.7b")
    sz = cells.load_reference(m).sizes(m)
    prog = m["program"]["set"]
    assert (prog["num_layers"], prog["d_model"], prog["vocab_size"], prog["ssm_state"],
            prog["ssm_headdim"], prog["ssm_expand"]) == (
        sz["layers"], sz["d_model"], sz["vocab"], sz["state"], sz["ssm_head_dim"],
        m["assumed"]["ssm_cfg"]["expand"])
    assert prog["tie_embeddings"] == m["published"]["tie_embeddings"]
