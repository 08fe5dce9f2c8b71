"""Inputs are a function of the seed alone; every seed gets the same load."""

import numpy as np
import pytest

from bench import generate as gen

BIG = 2**33 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_schedule_repeats_from_seed(seed):
    a = gen.open_loop_schedule(seed, 5.0, 40.0)
    b = gen.open_loop_schedule(seed, 5.0, 40.0)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 200 and a[0] == 0.0 and a[-1] < 40.0
    assert np.all(np.diff(a) > 0)


def test_seeds_share_the_gaps_in_another_order():
    a = np.diff(gen.open_loop_schedule(1, 5.0, 40.0))
    b = np.diff(gen.open_loop_schedule(2, 5.0, 40.0))
    assert not np.allclose(a, b)
    # the same multiset of gaps; the one left out closes the window
    np.testing.assert_allclose(np.sort(np.append(a, 40.0 - a.sum())),
                               np.sort(np.append(b, 40.0 - b.sum())), atol=1e-9)


def test_gaps_follow_the_rate():
    gaps = np.diff(gen.open_loop_schedule(3, 8.0, 50.0))
    assert abs(gaps.mean() - 1 / 8.0) < 0.01
    assert abs(np.median(gaps) - np.log(2) / 8.0) < 0.01   # exponential median


@pytest.mark.parametrize("seed", [0, BIG])
def test_prompts_and_tokens_repeat_from_seed(seed):
    np.testing.assert_array_equal(gen.prompts(seed, 4, 16, 100), gen.prompts(seed, 4, 16, 100))
    assert not np.array_equal(gen.prompts(seed, 4, 16, 100), gen.prompts(seed + 1, 4, 16, 100))
    t = gen.zipf_tokens(seed, 3, 2, 64, 1000)
    np.testing.assert_array_equal(t, gen.zipf_tokens(seed, 3, 2, 64, 1000))
    assert not np.array_equal(t, gen.zipf_tokens(seed, 4, 2, 64, 1000))
    assert t.dtype == np.int32 and t.min() >= 0 and t.max() < 1000


def test_zipf_law():
    t = gen.zipf_tokens(5, 0, 64, 512, 1000).ravel()
    counts = np.bincount(t, minlength=1000)
    assert counts[0] > 1.5 * counts[1] > counts[3]


def test_jax_key_keeps_high_bits():
    assert not np.array_equal(gen.jax_key(5), gen.jax_key(5 + 2**32))
    np.testing.assert_array_equal(gen.jax_key(BIG), gen.jax_key(BIG))


def test_sample_is_seeded():
    s = gen.sample(9, 50, 6)
    assert s == gen.sample(9, 50, 6) and s != gen.sample(10, 50, 6) and len(set(s)) == 6
    assert gen.sample(9, 3, 6) == [0, 1, 2]
