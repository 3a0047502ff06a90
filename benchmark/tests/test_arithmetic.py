"""The arithmetic the metrics stand on: percentiles, the open-loop
schedule and its lateness, the seeded order, the tallies' bounds, the
bytes of a decide step."""

import json
import os

import numpy as np
import pytest

import check
import kernel_bytes
from generators import open_loop_calls
from harness import keyspace, stats
from reference import LEAKY, TOKEN

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 99) == 99
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_failed_calls_count_as_beyond_the_tail():
    lat = [1.0] * 98  # ascending
    assert stats.latency_percentiles(lat, 0, 10_000.0)[99] == 1.0
    p = stats.latency_percentiles(lat, 2, 10_000.0)  # 2 of 100 failed
    assert p[50] == 1.0 and p[99] == 10_000.0  # the timeout, not an answer


def spec(seed, seconds=5.0, rate=2000):
    return {"seed": seed, "seconds": seconds, "worker": 1, "tag": f"s{seed}",
            "cell": {"rate": rate}, "config": {"key_population": 100_000},
            "traffic": load("traffic", "grpc-pairs")}


def test_open_loop_schedule_same_work_for_every_seed():
    a_off, a_ids, a_warm = open_loop_calls.build(spec(1))
    b_off, b_ids, b_warm = open_loop_calls.build(spec(2**31 + 5))
    t = spec(1)["traffic"]
    per_worker = 2000 / t["workers"]
    assert a_warm == b_warm == round(per_worker * t["warmup_s"])
    assert len(a_off) == len(b_off) == a_warm + round(per_worker * 5.0)
    # due instants rise, the warm-up fills [0, warmup_s), the window
    # [warmup_s, warmup_s + seconds)
    assert np.all(np.diff(a_off) > 0)
    assert a_off[a_warm - 1] < t["warmup_s"] <= a_off[a_warm]
    assert a_off[-1] < t["warmup_s"] + 5.0
    # another seed: another order of the same gaps and the same keys
    assert not np.array_equal(a_ids, b_ids)
    assert np.array_equal(np.sort(a_ids[a_warm:], axis=None),
                          np.sort(b_ids[b_warm:], axis=None))
    ga, gb = np.diff(a_off[a_warm:]), np.diff(b_off[b_warm:])
    assert abs(ga.sum() - gb.sum()) < 0.01
    # and the same seed gives the same run
    c_off, c_ids, _ = open_loop_calls.build(spec(1))
    assert np.array_equal(a_off, c_off) and np.array_equal(a_ids, c_ids)


def test_open_loop_summary_counts_lateness_and_failures():
    results = [{
        "latency_ms": np.array([4.0, 2.0, 3.0, 1.0]),  # done - DUE
        "due_s": np.array([0.1, 0.3, 0.7, 0.9]),
        "late_ms": np.array([0.0, 0.1, 0.2, 5.0, 0.0]),  # sent - due
        "attempted": 5, "failed": 1, "late_events": [],
    }]
    spec = {"seconds": 1.0, "traffic": {"call_timeout_s": 10.0}}
    s = open_loop_calls.summarize(results, spec)
    assert s["attempted"] == 5 and s["failed"] == 1
    assert s["end_to_end"]["call_p50_ms"] == (3.0, "ms")
    assert set(s["end_to_end"]) == {"call_p50_ms"}
    assert s["generator"]["call_p90_ms"] == 10_000.0  # the failed call
    assert s["generator"]["late_p99_ms"] == 5.0
    assert s["generator"]["completed_per_s"] == 4.0
    # a traced run reads the far tail before its capture begins
    s = open_loop_calls.summarize(results, dict(spec, read_share=0.5))
    assert s["generator"]["calls_in_tail_sample"] == 2
    assert s["end_to_end"]["call_p50_ms"] == (3.0, "ms")  # all the window


def test_key_rules_shares_and_independence():
    rules = keyspace.KeyRules(load("traffic", "geb-frames"))
    ids = np.arange(100_000)
    limit, dur, algo = rules.of(ids)
    assert np.mean(limit == 100) == pytest.approx(0.7)
    assert np.mean(dur == 1000) == pytest.approx(0.2)
    assert np.mean(dur == 3_600_000) == pytest.approx(0.1)
    assert np.mean(algo == LEAKY) == pytest.approx(0.25)
    # the algorithm does not follow the class
    assert np.mean(algo[limit == 1000] == LEAKY) == pytest.approx(0.25)
    assert tuple(x[0] for x in rules.of(np.array([8]))) == (1000, 3_600_000, TOKEN)


def test_zipf_recipe_is_the_programs():
    from gubernator_tpu.cli import keystreams

    a = keyspace.zipf_ids(10_000_000, 5000, np.random.default_rng(7))
    b = keystreams.zipf_ids(10_000_000, 5000, np.random.default_rng(7))
    assert np.array_equal(a, b)


def test_tally_bounds():
    ids = np.arange(7)
    limit = np.array([1000, 1000, 100, 100, 10, 1000, 10])
    duration = np.array([3_600_000, 3_600_000, 60_000, 60_000, 1000, 3_600_000, 1000])
    algo = np.array([TOKEN, TOKEN, TOKEN, TOKEN, TOKEN, LEAKY, LEAKY])
    offered = np.array([5000, 5000, 500, 50, 900, 5000, 5000])
    zero = np.zeros(7, np.int64)
    span = 40_000.0  # ms: 1 h keys see one window, 60 s keys one, 1 s keys 41
    # the 1 h leaky key is created once and 40 s / 3.6 s a hit = 11 leak, + 1;
    # the 1 s one is created 41 times and 40 s / 100 ms a hit = 400 leak, + 1
    ok = np.array([1000, 1000, 100, 50, 410, 1012, 10 * 41 + 400 + 1])
    n, _, exact, _ = check.tally_faults(ids, offered, ok, zero, limit, duration, algo, span)
    assert n == 0 and exact == 3  # the two 1 h token keys and the 60 s key
    one_over = ok.copy(); one_over[0] += 1  # under-admission by a single hit
    assert check.tally_faults(ids, offered, one_over, zero, limit, duration, algo, span)[0] == 1
    one_short = ok.copy(); one_short[1] -= 1  # a hit refused that was due
    assert check.tally_faults(ids, offered, one_short, zero, limit, duration, algo, span)[0] == 1
    lost = zero.copy(); lost[1] = 1  # ... unless its answer was lost
    assert check.tally_faults(ids, offered, one_short, lost, limit, duration, algo, span)[0] == 0
    for key in (5, 6):  # a leaky key AT its bound is sound, one over is a fault
        leaky_over = ok.copy(); leaky_over[key] += 1
        n, first, _, _ = check.tally_faults(
            ids, offered, leaky_over, zero, limit, duration, algo, span)
        assert n == 1 and first[0]["id"] == key and first[0]["upper"] == ok[key]


def test_tally_reports_the_old_leaky_form_and_the_closest_keys():
    """Reported, not judged: keys above the form before PR 39
    (limit + span // rate + 1) and, by algorithm and class, the key
    nearest the bound that judged it."""
    limit = np.array([10, 10, 10, 10, 1000, 100, 10, 10, 100])
    duration = np.array([1000] * 4 + [3_600_000, 60_000, 1000, 1000, 60_000])
    algo = np.array([LEAKY] * 6 + [TOKEN] * 3)
    ids = np.arange(11, 20)
    offered = np.array([5000, 5000, 5000, 300, 5000, 90, 5000, 5000, 5000])
    #          old form 10 + 400 + 1 = 411; bound 10 x 41 + 401 = 811
    admitted = np.array([411, 412, 600, 300, 1000, 90, 380, 409, 100])
    n, first, exact, seen = check.tally_faults(
        ids, offered, admitted, np.zeros(9, np.int64), limit, duration, algo,
        40_000.0)
    assert (n, first, exact) == (0, [], 1)
    assert seen["leaky_over_steady"] == 2  # keys 12 and 13; key 14's offer is under it
    assert seen["closest"] == [
        # token, 10 a second: 41 windows of 10; key 18 came nearest
        {"algo": TOKEN, "limit": 10, "duration_ms": 1000, "keys": 2, "id": 18,
         "offered": 5000, "admitted": 409, "upper": 410},
        # (the 60 s token key is held exactly: it says nothing here)
        # leaky, 10 a second: three keys driven over the bound, 13 nearest
        {"algo": LEAKY, "limit": 10, "duration_ms": 1000, "keys": 3, "id": 13,
         "offered": 5000, "admitted": 600, "upper": 811, "steady": 411},
        # (the 60 s leaky key was offered 90 of 100: its offer is its bound)
        {"algo": LEAKY, "limit": 1000, "duration_ms": 3_600_000, "keys": 1,
         "id": 15, "offered": 5000, "admitted": 1000, "upper": 1012,
         "steady": 1012},
    ]
    # a key over the old form and under the new is sound; over the new, a fault
    admitted[2] = 812
    n, first, _, seen = check.tally_faults(
        ids, offered, admitted, np.zeros(9, np.int64), limit, duration, algo,
        40_000.0)
    assert n == 1 and first[0]["id"] == 13 and seen["leaky_over_steady"] == 2


@pytest.mark.parametrize("lead_ms,admitted,faults", [
    (5_000.0, 100, 0), (5_000.0, 101, 1),  # preload + run inside one 60 s window
    (25_000.0, 200, 0), (25_000.0, 201, 1),  # the preload's window ends in the run
])
def test_tally_bounds_count_the_window_the_preload_opened(lead_ms, admitted, faults):
    one = lambda v: np.array([v])
    n, _, exact, _ = check.tally_faults(
        one(1), one(500), one(admitted), one(0), one(100), one(60_000),
        one(TOKEN), 40_000.0, lead_ms)
    assert n == faults and exact == (lead_ms < 20_000)


def test_canary_verdict():
    key, limit = "c", 3
    good = [(0, 3, 2), (0, 3, 0), (0, 3, 1), (1, 3, 0)]  # reply order is free
    assert check.canary_verdict(key, limit, TOKEN, good, (1, 3, 0), 0) is None
    dup = [(0, 3, 2), (0, 3, 2), (0, 3, 1), (0, 3, 0)]  # a hit not charged
    assert check.canary_verdict(key, limit, TOKEN, dup, (1, 3, 0), 0)
    assert check.canary_verdict(key, limit, TOKEN, good, (0, 3, 1), 0)  # peek


def test_decide_step_bytes_by_hand():
    store = {"ways": 16, "entry_bytes": 32}
    # 1000 items: 1000 rows x 512 B read + written, 36 B in + 28 B out each
    assert kernel_bytes.decide_step_bytes(1000, store) == 1000 * 512 * 2 + 1000 * 64
    assert kernel_bytes.decide_step_bytes(1000, store, sketched_items=10) == (
        1000 * 512 * 2 + 10 * 2 * 4 * 2 + 1000 * 64
    )
    # 1,088,000 B at 819 GB/s = 1.3284 us; a 1 ms step is 0.13284 %
    assert kernel_bytes.roofline_share_pct(1_088_000, 1e-3, 819e9) == pytest.approx(0.13284, rel=1e-4)
