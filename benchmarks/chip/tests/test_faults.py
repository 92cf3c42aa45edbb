"""A whole run on the CPU at a small size, past the look for a chip,
under the cell's committed limits: sound, it comes out correct and its
float8 control, put through the same comparison, does not; with the
timed path broken underneath, ``correct`` comes out false."""
from __future__ import annotations

import time

import jax
import pytest

from chipbench import cells, harness

CELLS = ["qwen3-4b.decode-batch", "mamba2-2.7b.decode-batch"]


def _run(cell, smoke_cell, tmp_path, **kw):
    doc, mix = smoke_cell(cell)
    return harness.run_cell(cell, 2**31 + 99, 3.0, False, t_start=time.perf_counter(),
                            cache_dir=str(tmp_path), require_chip=False, doc=doc, mix=mix,
                            limits=cells.load_limits(cell), **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_its_control_is_not(cell, smoke_cell, tmp_path):
    line = _run(cell, smoke_cell, tmp_path, control=True)
    c = line["compared"]
    assert line["correct"], c
    assert line["metrics"]["out_tok_s"]["value"] > 0
    assert list(line)[-1] == "compared"
    # the control, put through the same comparison under the committed limit
    assert not line["control_correct"], c
    assert c["control_max_logit_gap"]["value"] > c["control_max_logit_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_caught(cell, smoke_cell, tmp_path, monkeypatch):
    from repro.serve.batcher import ContinuousBatcher

    sample = ContinuousBatcher._sample_batch

    def altered(self, uids, pos, logits):
        toks = sample(self, uids, pos, logits)
        return (toks + 1) % logits.shape[-1]

    monkeypatch.setattr(ContinuousBatcher, "_sample_batch", altered)
    line = _run(cell, smoke_cell, tmp_path)
    assert not line["correct"], line["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged_is_caught(cell, smoke_cell, tmp_path,
                                                         monkeypatch):
    from repro.serve.engine import ServeEngine

    step = ServeEngine.decode_step

    def stale(self, tok, cache, pos):
        logits, _ = step(self, tok, jax.tree.map(lambda a: a.copy(), cache), pos)
        return logits, cache

    monkeypatch.setattr(ServeEngine, "decode_step", stale)
    line = _run(cell, smoke_cell, tmp_path)
    assert not line["correct"], line["compared"]
