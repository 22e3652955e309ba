"""The load generator: every seed offers the same work in another order."""
import collections
import json
import os
import random

import pytest

from harness.traffic import (Traffic, balanced_order, lognormal_strata,
                             round_prompt)

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def spec(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


def window(t, seconds, ramp_s=0.0, until=None):
    """(due, size) of every arrival before `until` (the window's close)."""
    out = []
    for due, size in t.arrivals(seconds, ramp_s):
        if due >= (seconds if until is None else until):
            return out
        out.append((due, size))


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_same_work_in_another_order(name):
    s = spec(name)
    a = Traffic(s, 1, 131072)
    b = Traffic(s, 2**33 + 5, 131072)
    assert a.plan_lengths() == b.plan_lengths()
    if not a.open:
        n = s["strata"]
        sa = [a.next_size() for _ in range(n)]
        sb = [b.next_size() for _ in range(n)]
        assert collections.Counter(sa) == collections.Counter(sb)
        assert collections.Counter(sa) == collections.Counter(a.pairs)
        assert sa != sb
        return
    for seconds in (51, 20):
        wa = [x for x in window(a, seconds, 15) if x[0] >= 0]
        wb = [x for x in window(b, seconds, 15) if x[0] >= 0]
        n = round(s["rate_per_s"] * seconds)
        assert len(wa) == len(wb) == n
        assert wa[0][0] == wb[0][0] == 0.0
        assert (collections.Counter(size for _, size in wa)
                == collections.Counter(size for _, size in wb))
        assert [size for _, size in wa] != [size for _, size in wb]

        def cycle(w):
            """(size, gap to the next arrival), the last gap closing the
            window."""
            dues = [d for d, _ in w] + [seconds]
            return [(size, round(y - x, 9))
                    for (x, size), y in zip(w, dues[1:])]
        ca, cb = cycle(wa), cycle(wb)
        assert sorted(g for _, g in ca) == sorted(g for _, g in cb)
        assert set(size for _, size in wa) <= set(a.pairs)
        # one fixed cycle, entered at another place: the same neighbours
        k = next(k for k in range(n) if ca[k:] + ca[:k] == cb)
        assert 0 < k < n


def test_ramp_and_drain_repeat_the_window_set():
    s = spec("chat")
    t = Traffic(s, 2**31 + 3, 131072)
    seconds = 30
    arr = window(t, seconds, 40, until=2 * seconds)
    assert arr[0][0] >= -40
    assert all(x[0] < y[0] for x, y in zip(arr, arr[1:]))
    periods = collections.defaultdict(list)
    for due, size in arr:
        periods[int((due + 3 * seconds) // seconds)].append(size)
    full = [collections.Counter(v) for k, v in sorted(periods.items())][1:]
    assert len(full) == 3 and full[0] == full[1] == full[2]


def test_balanced_order_takes_one_from_each_band():
    rng = random.Random(5)
    sizes = [rng.random() for _ in range(41)]
    order = balanced_order(sizes, 4, rng)
    assert sorted(order) == list(range(41))
    by_size = sorted(range(41), key=lambda i: sizes[i])
    edges = [0, 11, 21, 31, 41]          # bands of 11, 10, 10, 10
    band = {i: next(j for j in range(4) if r < edges[j + 1])
            for r, i in enumerate(by_size)}
    for k in range(0, 41, 4):
        got = sorted(band[i] for i in order[k:k + 4])
        assert got == list(range(len(got)))


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_lengths_keep_to_the_file(name):
    s = spec(name)
    t = Traffic(s, 3, 131072)
    short = set(s["short_prompt_round_up"])
    for p, o in t.pairs:
        assert s["output"]["min"] <= o <= s["output"]["max"]
        assert p in short or p % s["long_prompt_multiple"] == 0
        assert p <= max(s["prompt"]["max"], max(short))
    med = sorted(lognormal_strata(s["prompt"], 255))[127]
    assert abs(med - s["prompt"]["median"]) <= 1


def test_rounding():
    assert round_prompt(100, [128, 256], 128) == 128
    assert round_prompt(129, [128, 256], 128) == 256
    assert round_prompt(257, [128, 256], 128) == 384
    assert round_prompt(384, [128, 256], 128) == 384


def test_tokens_follow_the_seed():
    s = spec("chat")
    a, b = Traffic(s, 2**31 + 7, 1000), Traffic(s, 2**31 + 7, 1000)
    assert a.tokens(5, 64) == b.tokens(5, 64)
    assert a.tokens(5, 64) != Traffic(s, 8, 1000).tokens(5, 64)
    assert all(0 <= t < 1000 for t in a.tokens(9, 300))
