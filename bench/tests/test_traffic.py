"""The traffic generator reproduces exactly from a seed, and every seed
gets the same work in another order."""
import numpy as np

from bench import traffic
from bench.harness import BENCH, load_json

CHAT = load_json(BENCH / "traffic/w8a8-chat-poisson.json")
BIG = 2**31 + 12345            # seeds may exceed 32 bits


def test_lm_requests_reproduce():
    a = traffic.lm_requests(CHAT, 151_936, 20.0, BIG)
    b = traffic.lm_requests(CHAT, 151_936, 20.0, BIG)
    assert np.array_equal(a.due, b.due)
    assert np.array_equal(a.max_new, b.max_new)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def test_seeds_permute_the_same_work():
    a = traffic.lm_requests(CHAT, 151_936, 20.0, BIG)
    b = traffic.lm_requests(CHAT, 151_936, 20.0, 7)
    assert not np.array_equal(a.max_new, b.max_new)
    assert sorted(a.max_new) == sorted(b.max_new)
    assert sorted(map(len, a.prompts)) == sorted(map(len, b.prompts))
    assert len(a.due) == len(b.due) == CHAT["rate"] * 20


def test_lengths_follow_the_mix():
    r = traffic.lm_requests(CHAT, 151_936, 100.0, 3)
    n = np.array([len(p) for p in r.prompts])
    assert n.min() >= 32 and n.max() <= 1536
    assert abs(np.median(n) - 512) <= 8
    assert r.max_new.min() >= 16 and r.max_new.max() <= 512
    assert abs(np.median(r.max_new) - 128) <= 4
    assert all(p.max() < 151_936 and p.min() >= 0 for p in r.prompts)
    assert abs(len(r.due) - CHAT["rate"] * 100) <= 2


def test_arrivals_and_images_reproduce():
    a = traffic.arrival_times(800.0, 5.0, BIG)
    assert np.array_equal(a, traffic.arrival_times(800.0, 5.0, BIG))
    assert a[0] == 0.0 and a[-1] < 5.0 and np.all(np.diff(a) > 0)
    p = traffic.image_pool(4, 8, BIG)
    assert p.shape == (4, 8, 8, 3) and p.dtype == np.float32
    assert np.array_equal(p, traffic.image_pool(4, 8, BIG))
    assert np.array_equal(traffic.image_choice(10, 4, BIG),
                          traffic.image_choice(10, 4, BIG))
