"""The generator: what a seed changes and what it must not."""

from collections import Counter
from itertools import islice

import numpy as np
import pytest

import spec
import traffic

BIG = 2**31 + 12345           # seeds are larger than 32 signed bits hold


def _cell(mix):
    return spec.load_cell({"chat": "stablelm-1.6b.chat",
                           "code": "granite-8b.code",
                           "longgen": "granite-8b.longgen"}[mix])


def _window(mix, seed, seconds):
    out = []
    for r in traffic.open_loop(mix, seed, seconds, 1000):
        if r.arrival >= seconds:
            return out
        if r.arrival >= 0:
            out.append(r)


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_open_loop_is_a_function_of_the_seed(seed):
    mix = _cell("chat").traffic
    a, b = _window(mix, seed, 40.0), _window(mix, seed, 40.0)
    assert [(r.arrival, r.prompt_len, r.out_len) for r in a] == \
        [(r.arrival, r.prompt_len, r.out_len) for r in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_open_loop_window_holds_the_same_set_for_every_seed():
    mix = _cell("chat").traffic
    n = round(mix["rate_per_s"] * 40.0)
    sets, orders = [], []
    for seed in (1, 2, BIG):
        w = _window(mix, seed, 40.0)
        assert len(w) == n
        sets.append(Counter((r.prompt_len, r.out_len) for r in w))
        orders.append([r.arrival for r in w])
    assert sets[0] == sets[1] == sets[2]
    assert orders[0] != orders[1]
    assert all(0 <= a < 40.0 for a in orders[2])


def test_open_loop_starts_lead_seconds_early_and_keeps_going():
    mix = _cell("chat").traffic
    rs = list(islice(traffic.open_loop(mix, 3, 40.0, 1000), 400))
    assert -mix["lead_s"] <= rs[0].arrival < -mix["lead_s"] + 5
    assert all(a.arrival <= b.arrival for a, b in zip(rs, rs[1:]))
    assert rs[-1].arrival > 40.0
    assert len({r.key for r in rs}) == len(rs)


@pytest.mark.parametrize("mix", ["chat", "code", "longgen"])
def test_lengths_are_clipped_and_bucketed(mix):
    t = _cell(mix).traffic
    x = traffic.draw_lengths(t["prompt"], 5000, np.random.default_rng(0))
    assert set(x) <= set(t["prompt"]["buckets"])
    y = traffic.draw_lengths(t["output"], 5000, np.random.default_rng(0))
    assert y.min() >= t["output"]["min"] and y.max() <= t["output"]["max"]
    med = np.median(traffic.draw_lengths(
        dict(t["output"], min=1, max=10**6), 20000,
        np.random.default_rng(1)))
    assert med == pytest.approx(t["output"]["median"], rel=0.05)
    # every request fits one sequence's cap
    assert max(t["prompt"]["buckets"]) + t["output"]["max"] - 1 \
        <= t["max_seq_len"]


@pytest.mark.parametrize("mix", ["code", "longgen"])
def test_offline_queue_staggers_the_first_fill_and_reorders_by_seed(mix):
    t = _cell(mix).traffic
    slots = t["slots"]
    a = list(islice(traffic.offline_queue(t, BIG, 1000), 2 * slots))
    b = list(islice(traffic.offline_queue(t, BIG, 1000), 2 * slots))
    assert [(r.prompt_len, r.out_len) for r in a] == \
        [(r.prompt_len, r.out_len) for r in b]
    n = t["base_requests"]
    full = [list(islice(traffic.offline_queue(t, s, 1000), slots, slots + n))
            for s in (1, 2)]
    # past the staggered fill, one epoch is a reordering of one fixed set
    # (the first ``slots`` of it were the staggered ones)
    c1 = Counter(r.prompt_len for r in full[0])
    c2 = Counter(r.prompt_len for r in full[1])
    assert sum((c1 - c2).values()) <= slots
    assert [r.prompt_len for r in full[0]] != [r.prompt_len for r in full[1]]
    # the i-th of the first fill keeps (i + 1) / slots of its output
    assert a[0].out_len <= -(-t["output"]["max"] // slots)
    assert all(r.out_len >= 1 for r in a[:slots])


@pytest.mark.parametrize("mix", ["chat", "code", "longgen"])
def test_warm_up_covers_every_prompt_length_a_run_sends(mix):
    t = _cell(mix).traffic
    want = set(traffic.prompt_lengths(t, 51.0))
    if t["loop"] == "open":
        sent = {r.prompt_len for r in islice(
            traffic.open_loop(t, BIG, 51.0, 1000), 200)}
    else:
        sent = {r.prompt_len for r in islice(
            traffic.offline_queue(t, BIG, 1000), 200)}
    assert sent == want


def test_offline_window_opens_when_the_pool_is_full():
    """With a pool too small for every slot at once, the window opens
    once the head of the queue waits for pages (it used to wait for
    every slot to be busy, without end)."""
    import run
    from conftest import smoke_cell

    cell = smoke_cell("granite-8b.code")
    cell.traffic["pool_pages"] = 16
    res = run.run_cell(cell, BIG, 0.5, False,
                       session=run.Session(cell, tuner=False), t_start=0.0,
                       check=False)
    lg = res["log"]
    assert lg.w1 > lg.w0 and lg.tokens_in_window() > 0
