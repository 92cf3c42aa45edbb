from __future__ import annotations

import collections
import types

from chipbench import cells, traffic

MIX = "decode-batch"


def test_schedule_repeats_for_a_seed():
    mix = cells.load_traffic(MIX)
    a = traffic.schedule(mix, 2**31 + 7, 50000)
    b = traffic.schedule(mix, 2**31 + 7, 50000)
    assert [(r.uid, r.max_new_tokens) for r in a] == [(r.uid, r.max_new_tokens) for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_schedules_differ_across_seeds_but_hold_the_same_sizes():
    mix = cells.load_traffic(MIX)
    a = traffic.schedule(mix, 1, 50000)
    b = traffic.schedule(mix, 2, 50000)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not all((x.prompt[:8] == y.prompt[:8]).all() for x, y in zip(a, b))

    def sizes(s):
        return collections.Counter((len(r.prompt), r.max_new_tokens) for r in s)
    # every block of requests holds the same sizes, whatever the seed
    k = mix["block"]
    for i in range(0, len(a), k):
        assert sizes(a[i:i + k]) == sizes(b[i:i + k])


def test_schedule_fits_the_cache_and_the_buckets():
    mix = cells.load_traffic(MIX)
    s = traffic.schedule(mix, 3, 50000)
    assert len(s) == mix["requests"]
    assert {len(r.prompt) for r in s} <= set(mix["prompt"]["buckets"])
    assert all(len(r.prompt) + r.max_new_tokens <= mix["max_seq"] for r in s)
    assert all(mix["output"]["min"] <= r.max_new_tokens <= mix["output"]["max"] for r in s)
    assert all(r.due_s is None for r in s)


def test_closed_loop_keeps_its_backlog_and_runs_out_loudly():
    mix = {**cells.load_traffic(MIX), "requests": 40}
    f = traffic.feed(mix, 5, 50000)
    b = types.SimpleNamespace(queue=[], pending=[])
    first = f.take(0.0, b)
    assert [r.uid for r in first] == list(range(1, mix["backlog"] + 1))
    b.queue = first[:-3]
    assert [r.uid for r in f.take(0.1, b)] == [17, 18, 19]
    b.queue = first
    assert f.take(0.2, b) == []
    b.queue = []
    f.take(0.3, b)
    try:
        f.take(0.4, b)
    except RuntimeError as e:
        assert "ran out" in str(e)
    else:
        raise AssertionError("a feed that ran out gave no error")
