"""Traffic sketches: HLL accuracy envelope + Space-Saving guarantees."""

import numpy as np
import pytest

from gubernator_tpu.core.hashing import slot_hash_batch
from gubernator_tpu.core.sketches import (
    HyperLogLog,
    SpaceSaving,
    TrafficStats,
)


def _hashes(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**64, n, dtype=np.uint64)


@pytest.mark.parametrize("n", [100, 10_000, 200_000])
def test_hll_estimate_within_envelope(n):
    h = HyperLogLog(p=14)
    h.add_hashes(_hashes(n))
    est = h.estimate()
    # 1.04/sqrt(2^14) ~ 0.8% typical error; allow 5 sigma
    assert abs(est - n) <= max(0.05 * n, 10), (est, n)


def test_hll_duplicates_do_not_inflate():
    h = HyperLogLog(p=14)
    hashes = _hashes(1000)
    for _ in range(50):
        h.add_hashes(hashes)
    assert abs(h.estimate() - 1000) <= 60


def test_hll_merge_matches_union():
    a, b = HyperLogLog(p=12), HyperLogLog(p=12)
    ha, hb = _hashes(5000, seed=1), _hashes(5000, seed=2)
    a.add_hashes(ha)
    b.add_hashes(hb)
    a.merge(b)
    u = HyperLogLog(p=12)
    u.add_hashes(np.concatenate([ha, hb]))
    assert a.estimate() == u.estimate()


def test_hll_real_key_hashes():
    h = HyperLogLog(p=14)
    keys = [f"svc_{i}:acct_{i % 997}" for i in range(30_000)]
    h.add_hashes(slot_hash_batch(keys))
    distinct = len(set(keys))
    assert abs(h.estimate() - distinct) <= 0.05 * distinct


# The three summary tests run against a TrafficStats on either
# implementation (PR 40): "python" holds SpaceSaving itself, "native"
# the summary behind libguberhash.so that must keep its guarantees.
IMPLEMENTATIONS = ["python", "native"]


def _traffic(native, implementation, **kw):
    ts = TrafficStats(native=implementation == "native", **kw)
    assert ts.implementation == implementation
    assert isinstance(ts.hot, SpaceSaving) == (implementation == "python")
    return ts


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_space_saving_finds_heavy_hitters(native, implementation):
    rng = np.random.default_rng(3)
    # zipf stream over 10k keys: the top keys dominate
    stream = [f"key_{z}" for z in rng.zipf(1.3, 50_000) % 10_000]
    ts = _traffic(native, implementation, top_capacity=128)
    ss = ts.hot
    for i in range(0, len(stream), 500):
        keys = stream[i : i + 500]
        ts.observe(keys, slot_hash_batch(keys))

    true_counts = {}
    for k in stream:
        true_counts[k] = true_counts.get(k, 0) + 1
    true_top = sorted(true_counts, key=true_counts.get, reverse=True)[:5]

    reported = [k for k, _, _ in ss.top(20)]
    for k in true_top:
        assert k in reported, f"missed heavy hitter {k}"
    # count-err is a valid lower bound; count an upper-ish estimate
    for k, c, e in ss.top(20):
        if k in true_counts:
            assert c - e <= true_counts[k] <= c, (k, c, e, true_counts[k])


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_space_saving_capacity_bound(native, implementation):
    ts = _traffic(native, implementation, top_capacity=16)
    keys = [f"k{i}" for i in range(1000)]
    ts.observe(keys, slot_hash_batch(keys))
    assert len(ts.hot.top(100)) <= 16
    assert ts.hot.total == 1000


@pytest.mark.parametrize("implementation", IMPLEMENTATIONS)
def test_traffic_stats_snapshot(native, implementation):
    ts = _traffic(native, implementation)
    keys = ["a_1", "a_1", "b_2"]
    ts.observe(keys, slot_hash_batch(keys))
    snap = ts.snapshot()
    assert snap["observed_total"] == 3
    assert snap["hot_keys"][0]["key"] == "a_1"
    assert snap["hot_keys"][0]["count"] == 2
    assert 1 <= snap["distinct_keys_estimate"] <= 3
