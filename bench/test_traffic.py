"""The traffic generator: same seed, same requests; every seed the same work."""
from collections import Counter

from pathlib import Path

import numpy as np
import pytest

from bench import traffic

TESTDATA = Path(__file__).resolve().parent / "testdata"
MIXES = [("code_completion_buckets", traffic.HERE / "traffic"),
         ("chat_batch_buckets", traffic.HERE / "traffic"),
         ("tiny_open", TESTDATA), ("tiny_closed", TESTDATA)]


@pytest.mark.parametrize("name,directory", MIXES)
def test_same_seed_same_requests(name, directory):
    mix = traffic.load(name, directory)
    seed = 2**31 + 12345
    a = traffic.prompt_lengths(mix, seed, 100)
    assert np.array_equal(a, traffic.prompt_lengths(mix, seed, 100))
    assert np.array_equal(traffic.prompt_tokens(seed, 7, 50, 32256),
                          traffic.prompt_tokens(seed, 7, 50, 32256))
    if mix.loop == "open":
        t = traffic.arrival_times(mix, seed, 30.0)
        assert np.array_equal(t, traffic.arrival_times(mix, seed, 30.0))


@pytest.mark.parametrize("name,directory", MIXES)
def test_every_seed_same_work_other_order(name, directory):
    mix = traffic.load(name, directory)
    n = 3 * mix.block
    a = traffic.prompt_lengths(mix, 1, n)
    b = traffic.prompt_lengths(mix, 2, n)
    assert Counter(a.tolist()) == Counter(b.tolist())
    assert not np.array_equal(a, b)
    assert a.min() >= mix.prompt["min"] and a.max() <= mix.prompt["max"]


def test_bucketed_mixes_send_powers_of_two():
    for name in ("code_completion_buckets", "chat_batch_buckets"):
        n = traffic.prompt_lengths(traffic.load(name), 5, 64)
        assert all(x & (x - 1) == 0 for x in n.tolist())


def test_open_loop_rate_and_gaps():
    mix = traffic.load("code_completion_buckets")
    t1 = traffic.arrival_times(mix, 1, 40.0)
    t2 = traffic.arrival_times(mix, 2, 40.0)
    gaps1 = np.diff(np.concatenate([[0.0], t1]))[: mix.block]
    gaps2 = np.diff(np.concatenate([[0.0], t2]))[: mix.block]
    assert np.allclose(np.sort(gaps1), np.sort(gaps2))
    assert np.isclose(gaps1.mean(), 1.0 / mix.rate_per_s, rtol=0.03)
    assert abs(len(t1) - 40.0 * mix.rate_per_s) <= mix.block


def test_token_ids_stay_in_vocabulary():
    t = traffic.prompt_tokens(3, 0, 10000, 32256)
    assert t.min() >= 2 and t.max() < 32256 and t.dtype == np.int32
