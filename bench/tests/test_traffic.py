"""Seeded traffic: determinism, the same work for every seed, wide seeds."""

from collections import Counter

import numpy as np
import pytest

from bench import harness, traffic

SPEC = harness.load_workload("smollm-135m.serve-batch")["traffic"]
BIG = 2**31 + 12345


def _take(stream, n):
    return [next(stream) for _ in range(n)]


def test_same_seed_same_requests():
    a = _take(traffic.RequestStream(SPEC, 49152, BIG), 130)
    b = _take(traffic.RequestStream(SPEC, 49152, BIG), 130)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1] for x, y in zip(a, b))


def test_every_seed_gets_the_same_sizes_in_each_block():
    block = SPEC["block"]
    sizes = {}
    for seed in (1, 2, BIG):
        reqs = _take(traffic.RequestStream(SPEC, 49152, seed), 2 * block)
        sizes[seed] = [Counter((len(t), m) for t, m in reqs[i:i + block])
                       for i in (0, block)]
        orders = [(len(t), m) for t, m in reqs]
        assert all(t.max() < 49152 and t.min() >= 0 for t, _ in reqs)
    assert sizes[1] == sizes[2] == sizes[BIG]
    assert orders != [(len(t), m) for t, m in _take(traffic.RequestStream(SPEC, 49152, 1), 2 * block)]


def test_lengths_follow_the_lognormal_quantiles():
    pairs = traffic.length_pairs(SPEC)
    prompts = sorted(p for p, _ in pairs)
    outputs = sorted(o for _, o in pairs)
    assert prompts[len(prompts) // 2] == pytest.approx(256, rel=0.05)
    assert outputs[len(outputs) // 2] == pytest.approx(96, rel=0.05)
    assert min(prompts) >= 16 and max(prompts) == 1536
    assert min(outputs) >= 8 and max(outputs) == 512


def test_open_loop_schedule_keeps_its_rate():
    s = traffic.ArrivalSchedule(12.0, 64, BIG)
    due = [s.next_due() for _ in range(640)]
    assert all(b > a for a, b in zip(due, due[1:]))
    assert due[-1] == pytest.approx(640 / 12.0, rel=1e-9)
    t = traffic.ArrivalSchedule(12.0, 64, 7)
    assert [t.next_due() for _ in range(640)][-1] == pytest.approx(due[-1], rel=1e-9)
    with pytest.raises(ValueError):
        traffic.ArrivalSchedule(0.0, 64, 1)


def test_sub_seeds_fit_31_bits_and_differ_by_tag():
    a, b = traffic.sub_seed(BIG, "weights"), traffic.sub_seed(BIG, "data")
    assert 0 <= a < 2**31 and 0 <= b < 2**31 and a != b
    assert a == traffic.sub_seed(BIG, "weights")
    assert traffic.sub_seed(2**40 + 1, "weights") != traffic.sub_seed(1, "weights")


def test_percentile():
    assert traffic.percentile([1, 2, 3, 4, 5], 50) == 3
    with pytest.raises(ValueError):
        traffic.percentile([], 95)
