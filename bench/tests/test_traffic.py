"""The traffic generator: the same work for every seed, in an order of its own."""

import itertools

import numpy as np
import pytest

import traffic

MIX = {
    "arrival": {"kind": "poisson", "rate": 4.0},
    "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.6, "min": 16, "max": 256},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2, "max": 32},
    "block": 32,
}


def _block(seed, mix=MIX, vocab=1000):
    return list(itertools.islice(traffic.arrivals(mix, vocab, seed), mix["block"]))


def test_every_seed_gets_the_same_work_in_its_own_order():
    a, b = _block(7), _block(2**33 + 5)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == sorted(x.max_new_tokens for x in b)
    gaps = [np.diff([0.0] + [x.due_s for x in xs]).round(12) for xs in (a, b)]
    assert sorted(gaps[0]) == sorted(gaps[1])
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_block_holds_the_same_sizes_and_gaps_in_another_order():
    xs = list(itertools.islice(traffic.arrivals(MIX, 1000, 7), 2 * MIX["block"]))
    first, second = xs[: MIX["block"]], xs[MIX["block"]:]
    assert sorted(len(x.prompt) for x in first) == sorted(len(x.prompt) for x in second)
    assert sorted(x.max_new_tokens for x in first) == sorted(x.max_new_tokens for x in second)
    gaps = np.diff([0.0] + [x.due_s for x in xs])
    assert sorted(gaps[: MIX["block"]].round(12)) == sorted(gaps[MIX["block"]:].round(12))
    assert [len(x.prompt) for x in first] != [len(x.prompt) for x in second]


def test_same_seed_same_requests():
    a, b = _block(11), _block(11)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))


def test_lengths_are_clipped_quantiles_with_the_stated_median():
    n = 101
    xs = traffic.quantile_lengths(MIX["prompt_len"], n)
    assert xs == sorted(xs) and xs[n // 2] == 64
    assert min(xs) >= 16 and max(xs) <= 256


def test_backlog_is_all_due_at_once_and_ids_are_in_range():
    mix = dict(MIX, arrival={"kind": "backlog"})
    xs = list(itertools.islice(traffic.arrivals(mix, 50, 3), 100))
    assert all(x.due_s == 0.0 for x in xs)
    assert [x.rid for x in xs] == list(range(100))
    assert all(x.prompt.min() >= 1 and x.prompt.max() < 50 for x in xs)


def test_poisson_mean_rate_per_block():
    xs = _block(5)
    assert xs[-1].due_s == pytest.approx(np.sum(traffic.quantile_gaps(4.0, 32)))
    assert abs(xs[-1].due_s - 32 / 4.0) < 0.1 * 32 / 4.0


def test_unknown_kinds_are_refused():
    with pytest.raises(ValueError):
        next(traffic.arrivals(dict(MIX, arrival={"kind": "gamma"}), 10, 0))
    with pytest.raises(ValueError):
        traffic.quantile_lengths({"dist": "uniform"}, 4)
