from __future__ import annotations

import numpy as np

from chipbench import serve_loop, stats


def test_percentile_is_over_all_samples():
    xs = list(np.random.default_rng(0).random(1001))
    assert stats.percentile(xs, 95) == np.percentile(xs, 95)
    assert stats.percentile(xs, 50) == np.median(xs)


def _window(stall_at=None, ticks=200, tick_s=0.05, stall_s=2.0):
    """A recorder of ``ticks`` steps of ``tick_s``, one of them stalled,
    with 4 requests decoding all the way through."""
    rec = serve_loop.Recorder()
    t = 0.0
    for i in range(ticks):
        t += stall_s if i == stall_at else tick_s
        rec.tick_end.append(t)
        rec.traced.append(False)
    rec.window_s = t
    rec.window_ticks = ticks
    reqs = {u: {"admitted": 0, "tokens": np.zeros(ticks, np.int32), "finished": False}
            for u in range(4)}
    rec.due = {u: 0.0 for u in range(4)}
    return rec, reqs


def test_a_planted_stall_moves_the_rate_and_the_tail():
    rec, reqs = _window()
    tokens, gaps, attempted = serve_loop.timings(rec, reqs)
    assert tokens == 800 and len(gaps) == 4 * 199 and attempted == 4
    base_rate = stats.rate(tokens, rec.window_s)
    base_p = stats.percentile(gaps, 99.9)

    rec, reqs = _window(stall_at=100)
    tokens, gaps, _ = serve_loop.timings(rec, reqs)
    assert tokens == 800
    assert stats.rate(tokens, rec.window_s) < 0.9 * base_rate
    assert max(gaps) == 2.0
    assert stats.percentile(gaps, 99.9) > 10 * base_p


def test_only_tokens_and_gaps_inside_the_window_count():
    rec, reqs = _window()
    reqs[0]["admitted"] = 190               # 200 tokens from tick 190: 10 in the window
    reqs[1]["admitted"] = 200               # admitted after the window closed
    tokens, gaps, attempted = serve_loop.timings(rec, reqs)
    assert tokens == 2 * 200 + 10 and attempted == 3
    assert len(gaps) == 2 * 199 + 9
