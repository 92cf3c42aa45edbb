"""The default traffic generator: a mix file's parameters and a seed in,
a feed of requests out.

Every seed gets the same request sizes, drawn once from a fixed stream
(``SIZE_SEED``); the seed decides the prompt tokens and the order of
the sizes within consecutive blocks of ``block`` requests. A window
that takes the first few blocks then holds the same sizes whatever the
seed: runs with different seeds do the same work in another order.

A mix that names ``"generator": "<name>"`` is fed by the module
``traffic/<name>.py`` instead (``cells.load_generator``), which gives
the same ``feed(mix, seed, vocab)``: a new arrival shape is a new file.

Mix keys of this generator (a closed loop):
    slots, max_seq  the serving engine's batch slots and cache length
    backlog       requests kept waiting for a slot
    requests      schedule length; a run that exhausts it is an error
    block         requests per block whose order the seed draws
    prompt        {"buckets": [...], "weights": [...]}: prompt lengths
    output        {"dist": "pareto", "alpha", "min", "max"} (bounded
                  Pareto) or {"dist": "uniform", "min", "max"}; each
                  capped so that prompt + output <= max_seq
    trace_from, trace_seconds  the part of the window (start, length) a
                  ``--trace 1`` run profiles
    check         {"min_tokens": n}: served tokens the comparison reads
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

#: the fixed stream the sizes come from
SIZE_SEED = 20250101


@dataclasses.dataclass(frozen=True)
class Req:
    uid: int
    prompt: np.ndarray      # [S] int32
    max_new_tokens: int
    #: seconds after the window opens at which the request is due; None
    #: for a request due when it is submitted (a closed loop)
    due_s: Optional[float] = None


class Feed:
    """A closed loop: keeps ``backlog`` requests waiting for a slot.

    A feed's ``take(now, batcher)`` returns the requests to submit
    before the step at ``now`` (seconds since the window opened); the
    window calls it before every step."""

    def __init__(self, requests: List[Req], backlog: int):
        self.requests = requests
        self.backlog = backlog
        self._next = 0

    def take(self, now: float, batcher) -> List[Req]:
        n = self.backlog - len(batcher.queue) - len(batcher.pending)
        if n <= 0:
            return []
        if self._next + n > len(self.requests):
            raise RuntimeError("the schedule ran out inside the window")
        out = self.requests[self._next:self._next + n]
        self._next += n
        return out


def _outputs(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, size=n)
    if spec["dist"] == "pareto":
        # bounded Pareto on [lo, hi] by inverse transform
        a = float(spec["alpha"])
        u = rng.random(n)
        x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
        return np.minimum(np.floor(x).astype(np.int64), hi)
    raise ValueError(f"unknown output distribution {spec['dist']!r}")


def sizes(mix: dict):
    """(prompt lengths, output lengths) of the whole schedule, before
    the seed orders them."""
    n = int(mix["requests"])
    rng = np.random.default_rng(SIZE_SEED)
    p = mix["prompt"]
    prompts = rng.choice(np.asarray(p["buckets"], np.int64), size=n,
                         p=np.asarray(p["weights"], np.float64))
    outs = np.minimum(_outputs(mix["output"], rng, n), int(mix["max_seq"]) - prompts)
    if (outs < 1).any():
        raise ValueError("a prompt bucket leaves no room for output tokens")
    return prompts, outs


def schedule(mix: dict, seed: int, vocab: int) -> List[Req]:
    """The run's requests, in submission order."""
    prompts, outs = sizes(mix)
    rng = np.random.default_rng(seed)
    n, block = len(prompts), int(mix["block"])
    o = np.concatenate([i + rng.permutation(min(block, n - i)) for i in range(0, n, block)])
    prompts, outs = prompts[o], outs[o]
    toks = rng.integers(0, vocab, size=int(prompts.sum()), dtype=np.int32)
    cuts = np.cumsum(prompts)[:-1]
    return [Req(uid=i + 1, prompt=t, max_new_tokens=int(k))
            for i, (t, k) in enumerate(zip(np.split(toks, cuts), outs))]


def feed(mix: dict, seed: int, vocab: int) -> Feed:
    return Feed(schedule(mix, seed, vocab), int(mix["backlog"]))
