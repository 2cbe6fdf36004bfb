"""The generator: one seed, one set of inputs; every seed, the same work
at the same times."""
import json
from collections import Counter

import numpy as np
import pytest

import traffic
from conftest import CHIP


@pytest.fixture(scope="module")
def mix():
    with open(CHIP / "traffic" / "chat.json") as f:
        return json.load(f)


def test_same_seed_same_requests(mix):
    a = traffic.make_requests(mix, 2**33 + 1, 1000, 50)
    b = traffic.make_requests(mix, 2**33 + 1, 1000, 50)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_change_tokens_and_not_the_schedule(mix):
    a = traffic.make_requests(mix, 1, 1000, 50)
    b = traffic.make_requests(mix, 2**31 + 7, 1000, 50)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == [
        (r.due_s, len(r.prompt), r.max_new) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_each_block_holds_the_same_work(mix):
    n = mix["block"]
    a = traffic.make_requests(mix, 1, 1000, 2 * n)
    assert Counter(len(r.prompt) for r in a[:n]) == Counter(
        len(r.prompt) for r in a[n:])
    assert Counter(r.max_new for r in a[:n]) == Counter(
        r.max_new for r in a[n:])
    assert [len(r.prompt) for r in a[:n]] != [len(r.prompt) for r in a[n:]]
    # the first arrival of a block is the sum of the block's gaps later
    span = sum(traffic.block_gaps(mix["arrivals"]["rate_per_s"], n))
    assert a[n].due_s == pytest.approx(span)


def test_lengths_fit_the_cache(mix):
    assert (max(mix["prompt"]["grid"]) + traffic.max_output(mix)
            <= mix["max_seq_len"])
    assert all(g % 128 for g in mix["prompt"]["grid"])
