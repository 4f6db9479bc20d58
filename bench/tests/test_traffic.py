"""The seeded generators reproduce exactly, and every seed of a mix asks
for the same work."""
import numpy as np
import pytest

from bench import traffic as gen
from bench.tests import small

BIG_SEED = 2**31 + 12345


def test_open_loop_same_seed_same_schedule():
    tr = small.load("traffic", "chat-steady")
    a = gen.open_loop(tr, 151936, BIG_SEED, 10.0)
    b = gen.open_loop(tr, 151936, BIG_SEED, 10.0)
    assert np.array_equal(a.due_s, b.due_s)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.new_tokens, b.new_tokens)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def test_open_loop_seeds_share_the_work():
    tr = small.load("traffic", "chat-steady")
    a = gen.open_loop(tr, 151936, 1, 10.0)
    b = gen.open_loop(tr, 151936, BIG_SEED, 10.0)
    assert len(a) == len(b) == int(tr["rate_per_s"] * 10.0)
    # the same requests at the same times ...
    assert np.array_equal(a.due_s, b.due_s)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.new_tokens, b.new_tokens)
    # ... with other token ids
    assert not any(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert 0.0 < a.due_s[0] and a.due_s[-1] < 10.0


@pytest.mark.parametrize("seconds", [10.0, 51.0])
def test_open_loop_order_is_fixed_not_seeded(seconds, monkeypatch):
    tr = small.load("traffic", "chat-steady")
    a = gen.open_loop(tr, 151936, BIG_SEED, seconds)
    n = len(a)
    sizes = gen.lognormal_sizes(tr["prompt"], n)
    assert np.array_equal(np.sort(a.prompt_len), np.sort(sizes))
    assert not np.array_equal(a.prompt_len, np.sort(a.prompt_len))  # shuffled
    monkeypatch.setattr(gen, "ORDER", gen.ORDER + 1)
    other = gen.open_loop(tr, 151936, BIG_SEED, seconds)
    assert not np.array_equal(a.prompt_len, other.prompt_len)  # another order ...
    assert np.array_equal(np.sort(a.prompt_len), np.sort(other.prompt_len))  # ... same sizes
    assert np.array_equal(np.sort(a.new_tokens), np.sort(other.new_tokens))
    gaps = lambda s: np.sort(np.diff(np.concatenate([[0.0], s.due_s])))  # noqa: E731
    assert gaps(a) == pytest.approx(gaps(other))
    assert a.due_s[-1] == pytest.approx(other.due_s[-1]) and a.due_s[-1] < seconds


def test_open_loop_sizes_follow_the_mix():
    tr = small.load("traffic", "chat-steady")
    s = gen.open_loop(tr, 1000, 3, 40.0)
    p, o = tr["prompt"], tr["output"]
    assert s.prompt_len.min() >= p["min"] and s.prompt_len.max() <= p["max"]
    assert s.new_tokens.min() >= o["min"] and s.new_tokens.max() <= o["max"]
    assert abs(np.median(s.prompt_len) - p["median"]) <= 0.05 * p["median"]
    assert abs(np.median(s.new_tokens) - o["median"]) <= 0.05 * o["median"]
    assert [len(x) for x in s.prompts] == s.prompt_len.tolist()
    assert all(x.min() >= 0 and x.max() < 1000 for x in s.prompts)
    rate = len(s) / s.due_s[-1]
    assert rate == pytest.approx(tr["rate_per_s"], rel=0.05)


def test_chain_starts_reproduce_inside_the_box():
    tr = small.load("traffic", "mlda-resident-64chains")
    a = gen.chain_starts(tr, [-200, -200], [200, 200], BIG_SEED)
    b = gen.chain_starts(tr, [-200, -200], [200, 200], BIG_SEED)
    c = gen.chain_starts(tr, [-200, -200], [200, 200], BIG_SEED + 1)
    assert a.shape == (64, 2) and a.dtype == np.float32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(np.abs(a) <= 200 * tr["start_scale"])


def test_int31_fits_and_reproduces():
    vals = [gen.int31(s) for s in (0, 1, 2**31, 2**40 + 7)]
    assert all(0 <= v < 2**31 for v in vals)
    assert gen.int31(2**40 + 7) == vals[-1]
    assert len(set(vals)) == len(vals)
