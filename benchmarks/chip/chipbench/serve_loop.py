"""Drives the program's serving path as its users build it: a
``ServeEngine`` with the compiled decode step, under a
``ContinuousBatcher`` whose ``step()`` the window calls.

The wall-clock bookkeeping is the harness's: it stamps the end of every
``step()``, and reads when each request's tokens came out from the step
indices the batcher keeps (a request admitted in step ``a`` emits its
``j``-th token in step ``a + j``). Host spans (``TraceAnnotation``) go
around the harness's own calls: ``step``, ``admit`` (a subclass wraps
``_admit``), ``decode`` and ``sample``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

SPANS = ("step", "admit", "decode", "sample")


@dataclasses.dataclass
class Recorder:
    """What the window saw, in step indices and seconds since it opened."""

    tick_end: List[float] = dataclasses.field(default_factory=list)
    traced: List[bool] = dataclasses.field(default_factory=list)
    #: per decode call: (step index, live slots' positions)
    decodes: List[tuple] = dataclasses.field(default_factory=list)
    #: per admission: (step index, uid, prompt length, host seconds)
    admits: List[tuple] = dataclasses.field(default_factory=list)
    #: per submitted request: the request, and when it was due
    requests: Dict[int, object] = dataclasses.field(default_factory=dict)
    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    window_s: float = 0.0
    window_ticks: int = 0


def build_engine(doc: dict, mix: dict, params):
    """The program's engine for configuration ``doc`` and mix ``mix``,
    built as its users build it."""
    from repro.configs import get_config
    from repro.models.model_zoo import build_model
    from repro.serve.engine import ServeEngine

    prog = doc["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["set"])
    engine = ServeEngine(build_model(cfg), batch_size=int(mix["slots"]),
                         max_seq=int(mix["max_seq"]))
    engine.load(params)
    return engine


def instrument(engine) -> None:
    """Wrap the engine's decode step in a ``decode`` span that records
    the live slots' positions in the recorder of the batcher driving it
    (``engine.bench_batcher``)."""
    from jax.profiler import TraceAnnotation

    inner = engine.decode_step

    def decode_step(tok, cache, pos):
        b = engine.bench_batcher
        b.rec.decodes.append((b.step_count, [s.pos for s in b.slots if s.uid is not None]))
        with TraceAnnotation("decode"):
            return inner(tok, cache, pos)

    engine.decode_step = decode_step


def batcher_class():
    from jax.profiler import TraceAnnotation

    from repro.serve.batcher import ContinuousBatcher

    class TimedBatcher(ContinuousBatcher):
        """``ContinuousBatcher`` with host spans around admission and
        sampling. ``sync_admit`` waits for the slot write to finish
        inside the span (traced runs), so the span times the whole
        admission."""

        def __init__(self, engine, rec: Recorder, *, sync_admit: bool = False):
            super().__init__(engine)
            self.rec = rec
            self.sync_admit = sync_admit
            engine.bench_batcher = self

        def _admit(self, req, slot):
            import jax

            t0 = time.perf_counter()
            with TraceAnnotation("admit"):
                super()._admit(req, slot)
                if self.sync_admit:
                    jax.block_until_ready(self.cache)
            self.rec.admits.append((self.step_count, req.uid, len(req.prompt),
                                    time.perf_counter() - t0))

        def _sample_batch(self, uids, pos, logits):
            with TraceAnnotation("sample"):
                return super()._sample_batch(uids, pos, logits)

    return TimedBatcher


def warm_up(engine, lengths, vocab: int, seed: int) -> None:
    """Serve one request per prompt length in ``lengths``, then as many
    again into the slots they free, through a batcher of its own that
    is dropped afterwards: every program the window runs is compiled
    (prefill per length, slot write, first-token sampling, decode step,
    batch sampling, cache creation)."""
    from repro.serve.batcher import Request

    rec = Recorder()
    b = batcher_class()(engine, rec)
    rng = np.random.default_rng(seed)
    uid = 0
    for rnd in range(2):
        for s in lengths:
            uid += 1
            b.submit(Request(uid=uid, prompt=rng.integers(0, vocab, int(s), dtype=np.int32),
                             max_new_tokens=3, arrival=rnd * 4))
    while b.step():
        pass
    b.cache = None
    engine.bench_batcher = None


def serve_window(batcher, feed, seconds: float, rec: Recorder, *,
                 trace_from: float = 0.0, trace_to: float = 0.0,
                 on_open=None, on_close=None) -> None:
    """Submit what ``feed.take`` gives and call ``step()`` until
    ``seconds`` have passed. Steps whose start lies in
    ``[trace_from, trace_to)`` run inside the ``bench_window`` span;
    ``on_open`` runs just before it opens (to start the profiler) and
    ``on_close`` just after it closes (to stop it)."""
    from jax.profiler import TraceAnnotation

    from repro.serve.batcher import Request

    window = None
    t0 = time.perf_counter()

    def close():
        nonlocal window
        window.__exit__(None, None, None)
        window = False
        if on_close:
            on_close()

    while (now := time.perf_counter() - t0) < seconds:
        for r in feed.take(now, batcher):
            batcher.submit(Request(uid=r.uid, prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens))
            rec.requests[r.uid] = r
            rec.due[r.uid] = now if r.due_s is None else r.due_s
        if trace_from <= now < trace_to and window is None:
            if on_open:
                on_open()
            window = TraceAnnotation("bench_window")
            window.__enter__()
        elif window and now >= trace_to:
            close()
        with TraceAnnotation("step"):
            batcher.step()
        rec.tick_end.append(time.perf_counter() - t0)
        rec.traced.append(bool(window))
    if window:
        close()
    rec.window_s = time.perf_counter() - t0
    rec.window_ticks = len(rec.tick_end)


def served(batcher) -> Dict[int, dict]:
    """Every admitted request: its admission step and the tokens it has
    emitted so far (finished or still in a slot)."""
    out = {}
    for uid, r in batcher.results.items():
        out[uid] = {"admitted": r.admitted, "tokens": np.asarray(r.tokens),
                    "finished": True}
    for s in batcher.slots:
        if s.uid is not None:
            out[s.uid] = {"admitted": s.result.admitted,
                          "tokens": np.asarray(s.tokens), "finished": False}
    return out


def timings(rec: Recorder, reqs: Dict[int, dict]):
    """(output tokens emitted in the window, every gap between two
    consecutive output tokens of a request, both in the window,
    requests admitted in the window)."""
    end = rec.tick_end
    n = rec.window_ticks
    tokens, gaps = 0, []
    for r in reqs.values():
        a, k = r["admitted"], len(r["tokens"])
        tokens += max(0, min(k, n - a))
        gaps += [end[a + j] - end[a + j - 1] for j in range(1, k) if a + j < n]
    return tokens, gaps, sum(1 for r in reqs.values() if r["admitted"] < n)
