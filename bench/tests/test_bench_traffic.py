"""The traffic generator: determined by its seed, inside its stated ranges,
and the same multiset of sizes and gaps for every seed."""

import numpy as np
import pytest

from bench import traffic

MIXES = ("chat", "decode")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = traffic.load(name)
    a = traffic.generate(mix, 2 ** 31 + 77, 40.0, 50_000)
    b = traffic.generate(mix, 2 ** 31 + 77, 40.0, 50_000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.client == y.client
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_stated_ranges(name):
    mix = traffic.load(name)
    items = traffic.generate(mix, 3, 40.0, 50_000)
    p = [len(it.prompt) for it in items]
    o = [it.max_new_tokens for it in items]
    assert mix["prompt"]["min"] <= min(p) and max(p) <= mix["prompt"]["max"]
    assert mix["output"]["min"] <= min(o) and max(o) <= mix["output"]["max"]
    r = mix["prompt"].get("round_to", 1)
    assert all(n % r == 0 for n in p)
    assert all(0 <= int(t) < 50_000 for it in items for t in it.prompt)
    # the median, up to the rounding to whole multiples of round_to
    med = sorted(p)[len(p) // 2]
    assert 0.75 * mix["prompt"]["median"] <= med \
        <= 1.35 * mix["prompt"]["median"] + r


@pytest.mark.parametrize("name", MIXES)
def test_seeds_permute_one_multiset(name):
    mix = traffic.load(name)
    a = traffic.generate(mix, 1, 40.0, 1000)
    b = traffic.generate(mix, 2, 40.0, 1000)
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in b)
    assert sorted(i.max_new_tokens for i in a) == \
        sorted(i.max_new_tokens for i in b)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]
    if mix["kind"] == "open":
        ga = np.diff([i.due_s for i in a])
        gb = np.diff([i.due_s for i in b])
        assert abs(a[-1].due_s - b[-1].due_s) < 0.1 * a[-1].due_s
        assert ga.min() >= 0 and gb.min() >= 0


def test_open_rate():
    mix = traffic.load("chat")
    items = traffic.generate(mix, 5, 40.0, 1000)
    due = np.array([i.due_s for i in items])
    # the window's requests fill it, at the stated mean rate
    inside = due[due < 40.0]
    assert len(inside) == round(mix["rate_rps"] * 40.0)
    assert inside[-1] < 40.0 <= due[len(inside)]


def test_closed_clients_share_the_requests():
    mix = traffic.load("decode")
    items = traffic.generate(mix, 9, 40.0, 1000)
    counts = np.bincount([i.client for i in items])
    assert len(counts) == mix["clients"]
    assert (counts == mix["per_client"]).all()


@pytest.mark.parametrize("name", MIXES)
def test_mix_names_its_source_and_cuts(name):
    """A mix says which public trace its lengths follow and how they were
    cut to fit the cell."""
    mix = traffic.load(name)
    assert "arXiv:" in mix["source"] and mix["cuts"]
