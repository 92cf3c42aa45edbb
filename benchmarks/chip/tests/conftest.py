"""Shared pieces of the benchmark's own tests (CPU, small sizes)."""
from __future__ import annotations

import copy
import pathlib
import sys

import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
for p in (str(BENCH_DIR), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def smoke(cell_name: str):
    """(doc, mix) of a cell cut to a size the CPU runs in seconds: the
    configuration's widths and the traffic's lengths made small, the
    structure kept."""
    from chipbench import cells

    cell = cells.find_cell(cells.load_benchmark(), cell_name)
    doc = copy.deepcopy(cells.load_config(cell.config))
    mix = copy.deepcopy(cells.load_traffic(cell.traffic))
    if doc["reference"] == "dense_gqa":
        doc["published"].update(hidden_size=256, num_attention_heads=4,
                                num_key_value_heads=2, head_dim=64,
                                intermediate_size=512, vocab_size=512,
                                num_hidden_layers=2)
        doc["program"]["set"].update(d_model=256, num_heads=4, num_kv_heads=2,
                                     head_dim=64, d_ff=512, vocab_size=512,
                                     num_layers=2)
    else:
        # 16 layers: fewer let the float8 control's error stay under the
        # cell's limit, which the full 64 layers exceed threefold
        doc["published"].update(d_model=256, vocab_size=4096, n_layer=16)
        doc["assumed"]["ssm_cfg"].update(d_state=16, headdim=32)
        doc["program"]["set"].update(d_model=256, vocab_size=4096, num_layers=16,
                                     ssm_state=16, ssm_headdim=32)
    mix.update(slots=4, backlog=4, requests=20000, max_seq=128)
    mix["prompt"]["buckets"] = [16, 32, 64]
    mix["output"].update(min=8, max=40)
    mix["check"]["min_tokens"] = 40
    return doc, mix


@pytest.fixture
def smoke_cell():
    return smoke
