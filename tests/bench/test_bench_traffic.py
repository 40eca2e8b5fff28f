"""The traffic generator: every seed draws the same multiset of sizes and
gaps in another order, and the same seed draws the same requests."""

import bench_testroot  # noqa: F401  (puts the repository root on the path)
import numpy as np
import pytest

from bench import harness, traffic

MIXES = ("chat", "offline")


def _mix(name):
    return harness.Bench(bench_testroot.REPO).traffic(name)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.generate(_mix(name), 2 ** 31 + 11, 30.0, 92544)
    b = traffic.generate(_mix(name), 2 ** 31 + 11, 30.0, 92544)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_one_multiset(name):
    a = traffic.generate(_mix(name), 1, 30.0, 92544)
    b = traffic.generate(_mix(name), 2, 30.0, 92544)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert not np.array_equal(a[0].prompt[:16], b[0].prompt[:16])


def test_open_loop_schedule_fills_the_window():
    mix = _mix("chat")
    reqs = traffic.generate(mix, 5, 30.0, 92544)
    arr = np.array([r.arrival_s for r in reqs])
    assert len(reqs) == round(mix["rate_per_s"] * 30.0)
    assert np.all(np.diff(arr) > 0) and 0 < arr[0] and arr[-1] < 30.0
    lens = {len(r.prompt) for r in reqs}
    assert lens == set(traffic.prompt_lengths(mix, 30.0))
    assert all(n % mix["prompt"]["grid"] == 0 for n in lens)
    assert all(mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
               for r in reqs)


def test_closed_pool_blocks_are_whole_sets():
    mix = _mix("offline")
    reqs = traffic.generate(mix, 9, 30.0, 92544)
    block = mix["block"]
    first = sorted(len(r.prompt) for r in reqs[:block])
    assert all(sorted(len(r.prompt) for r in reqs[i:i + block]) == first
               for i in range(0, len(reqs), block))
    assert set(first) == set(range(128, 1025, 128))


def test_quantiles_follow_the_distribution():
    d = {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 64,
         "max": 1536, "grid": 128}
    assert traffic.quantile(d, 0.5) == 384
    assert traffic.quantile(d, 0.001) == 128       # 64, rounded up
    assert traffic.quantile(d, 0.999) == 1536      # clipped
    u = {"dist": "uniform", "min": 256, "max": 1024}
    assert traffic.quantile(u, 0.0) == 256 and traffic.quantile(u, 0.5) == 640


def test_open_loop_order_is_uniform():
    """Each of lengths, outputs and gaps is permuted on its own, uniformly:
    over many seeds the longest gap lands in every quarter of the window,
    and a prompt's length says nothing of its gap."""
    mix = _mix("chat")
    where, corr = [], []
    for seed in range(200):
        reqs = traffic.generate(mix, 2 ** 31 + seed, 51.0, 100)
        lens = np.array([len(r.prompt) for r in reqs], float)
        gaps = np.diff([0.0] + [r.arrival_s for r in reqs])
        where.append(int(np.argmax(gaps)) * 4 // len(reqs))
        corr.append(np.corrcoef(lens, gaps)[0, 1])
    assert np.bincount(where, minlength=4).min() >= 30
    assert abs(np.mean(corr)) < 0.05
    assert "balance" not in mix
