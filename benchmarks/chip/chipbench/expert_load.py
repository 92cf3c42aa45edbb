"""The program's expert-load counter, per traced run of the expert layer.

``ContinuousBatcher.expert_load`` keeps, on the device, one entry per
prefill and per decode step: ``(step, kind, [layers, 2])``, each row the
(token, held expert) pairs routed in that layer and the held experts
that received a token. It is read here after the window, for the steps
the trace covered. A program without the counter, or a model without
experts, gives nothing to read.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def traced(run, kinds: Sequence[str] = ("prefill", "decode")) -> List[np.ndarray]:
    """``[layers, 2]`` per run of the expert layer of ``kinds`` in a
    traced step of the window batcher; [] where there is none."""
    batcher = getattr(run.engine, "bench_batcher", None)
    log = getattr(batcher, "expert_load", None)
    if run.trace is None or not log:
        return []
    traced = run.rec.traced
    return [np.asarray(load) for step, kind, load in log
            if kind in kinds and step < len(traced) and traced[step]]

