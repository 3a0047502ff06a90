"""The plain reference against the program's oracle (core/oracle.py):
the same requests at the same clock, the same answers -- expiry, leak,
peeks, over-limit, and a key found under the other algorithm."""

import random

import pytest

from reference import Limiter


@pytest.mark.parametrize("wild", [False, True])
@pytest.mark.parametrize("seed", range(8))
def test_reference_equals_oracle(seed, wild):
    from gubernator_tpu.api.types import Algorithm, RateLimitReq
    from gubernator_tpu.core import oracle
    from gubernator_tpu.core.cache import LRUCache

    rng = random.Random(seed)
    lim, cache = Limiter(), LRUCache(100_000)
    now = 1_700_000_000_000
    for _ in range(3000):
        now += rng.choice((0, 0, 1, 3, 50, 400, 2500))
        kid = rng.randrange(12)
        algo, limit, dur = kid % 2, 10, (1000, 5000)[kid % 3 == 0]
        if wild and rng.random() < 0.1:  # parameters that change under a key
            algo, limit = rng.choice((0, 1)), rng.choice((5, 10))
            dur = rng.choice((1000, 5000))
        hits = rng.choice((0, 1, 1, 1, 2, 7, 12))
        o = oracle.get_rate_limit(cache, RateLimitReq(
            name="n", unique_key=f"k{kid}", hits=hits, limit=limit,
            duration=dur, algorithm=Algorithm(algo)), now)
        got = lim.decide(f"k{kid}", hits, limit, dur, algo, now)
        assert got == (int(o.status), o.limit, o.remaining, o.reset_time)


# -- the tally's leaky bound against the reference itself (PR 39) ------------
#
# `check.tally_faults` holds a leaky key to limit x creations that fit +
# ticks that fit + 1. The reference is driven here as a run drives the
# daemon: the preload creates the key with hits = 0, `lead` ms later the
# generators send hits = 1 at the schedule's instants, and what the
# reference admitted is judged by the check.

import json
import os

import numpy as np

import check
from reference import LEAKY, UNDER

T0 = 1_700_000_000_000
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "traffic", "geb-frames.json")) as f:
    CLASSES = [(c["limit"], c["duration_ms"]) for c in json.load(f)["key_classes"]]
SPAN = 33_400  # ms: a 30 s window, its warm-up and its drain (PERF.md section 2)
KINDS = ("hammered", "one_a_tick", "clumps", "pauses", "bursts_and_ticks", "mix")


def drive(limit, duration, lead, times):
    """Hits admitted by the reference: preload at T0, hits = 1 at
    T0 + lead + t for t in `times` (ms, ascending)."""
    lim = Limiter()
    assert lim.decide("k", 0, limit, duration, LEAKY, T0)[:3] == (UNDER, limit, limit)
    return sum(
        lim.decide("k", 1, limit, duration, LEAKY, T0 + lead + t)[0] == UNDER
        for t in times
    )


def judge(limit, duration, lead, times, admitted):
    """(keys outside, the bound, the old form) as the run's check has them."""
    one = lambda v: np.array([v])  # noqa: E731
    span_ms = float(times[-1] - times[0])
    n, faults, _, seen = check.tally_faults(
        one(7), one(len(times)), one(admitted), one(0), one(limit),
        one(duration), one(LEAKY), span_ms, float(lead + times[0]))
    # one more hit than any key could take, so that the bound itself shows
    _, _, _, top = check.tally_faults(
        one(7), one(10**9), one(admitted), one(0), one(limit), one(duration),
        one(LEAKY), span_ms, float(lead + times[0]))
    return n, top["closest"][0]["upper"], top["closest"][0]["steady"], faults


def schedule(kind, rng, limit, duration):
    """Arrival instants in [0, SPAN]: what a one-node cell sends a hot key
    (hammered), what a ring's owner sees of it (about one a tick, clumps
    behind pauses), pauses of a tick to two durations, a burst about
    every duration over one hit a tick (the adaptive schedule below,
    played blind), and mixes."""
    rate = max(duration // limit, 1)
    if kind == "mix":
        cuts = sorted(rng.sample(range(1, SPAN), 3))
        parts = []
        for lo, hi in zip([0, *cuts], [*cuts, SPAN]):
            part = schedule(rng.choice(KINDS[:-1]), rng, limit, duration)
            parts += [t for t in part if lo <= t < hi]
        return sorted(parts) or [0]
    t, times = rng.randrange(0, rate + 1), []
    if kind == "bursts_and_ticks":
        step = duration + rng.choice((1, 1, 2, rate // 2, rate))
        times = [b + rng.randrange(0, 2) for b in range(t, SPAN, step)
                 for _ in range(limit + 2)]
        times += range(t, SPAN, rate + rng.choice((0, 0, 1)))
        return sorted(times)
    while t <= SPAN:
        if kind == "hammered":
            times.append(t)
            t += rng.choice((0, 1, 2, 5, 5, 9))
        elif kind == "one_a_tick":
            times.append(t)
            t += rate + rng.choice((-1, 0, 0, 1, 2, rate // 3))
        elif kind == "clumps":  # a forwarded clump, then the door node's pause
            times += [t + rng.randrange(0, 3) for _ in range(rng.randrange(1, limit + 3))]
            t += rng.choice((rate, 2 * rate, duration, duration + 1, duration + rate))
        else:  # pauses of a tick to two durations, a few hits between
            times += [t] * rng.randrange(1, 4)
            t += rng.randrange(rate, 2 * duration + 2)
    return sorted(x for x in times if x <= SPAN) or [0]


@pytest.mark.parametrize("limit,duration", CLASSES + [(1, 1000)])
@pytest.mark.parametrize("kind", KINDS)
def test_the_reference_never_passes_the_leaky_bound(kind, limit, duration):
    """50 seeded schedules a case, 24 cases: 1,200 (900 on the three key
    classes of geb-frames.json, 300 on a limit of 1, where the bucket the
    preload left is a grant of its own)."""
    for seed in range(50):
        rng = random.Random(f"{kind}:{limit}:{duration}:{seed}")
        times = schedule(kind, rng, limit, duration)
        lead = rng.choice((0, 1, duration // 2, duration - 1, duration,
                           duration + 1, 7_400, 26_000, 61_000))
        admitted = drive(limit, duration, lead, times)
        n, upper, _, faults = judge(limit, duration, lead, times, admitted)
        assert n == 0 and admitted <= upper, (seed, lead, admitted, upper, faults)


def adaptive(limit, duration, lead):
    """The schedule that follows the bucket's real expiries: a burst of
    `limit` whenever it has expired, one hit a tick between. Played
    against a reference of its own, then returned as instants."""
    rate = max(duration // limit, 1)
    lim, times, t, next_tick = Limiter(), [], 0, 0
    lim.decide("k", 0, limit, duration, LEAKY, T0)
    while t <= SPAN:
        now = T0 + lead + t
        e = lim._s["k"]
        if e[5] < now:  # expired: it will be created full
            burst = limit
        elif t >= next_tick:
            burst = limit if not times else 1
        else:
            burst = 0
        for _ in range(burst):
            lim.decide("k", 1, limit, duration, LEAKY, now)
            times.append(t)
        if burst:
            next_tick = t + rate
        t += 1
    return times


@pytest.mark.parametrize("limit,duration", CLASSES)
def test_the_adaptive_schedule_comes_within_a_tenth_of_the_bound(limit, duration):
    """Why the bound is not looser: it is the reference's own reach."""
    for lead in (7_400, 26_000, 61_000):  # one node's preload, a ring's, a slow one
        times = adaptive(limit, duration, lead)
        admitted = drive(limit, duration, lead, times)
        n, upper, steady, _ = judge(limit, duration, lead, times, admitted)
        assert n == 0 and admitted <= upper
        assert admitted >= 0.9 * upper, (lead, admitted, upper)
        if duration < SPAN:  # a bucket that expires in the run passes the old form
            assert admitted > steady


def test_a_key_seen_once_a_tick_passes_the_old_form():
    """Why PR 39 exists: a 10-per-second key whose owner sees it once a
    tick, and a clump each second (a ring's forwarded stream), is
    admitted far more than `limit + span // rate + 1` by the reference."""
    limit, duration = 10, 1000
    times = sorted([*range(0, SPAN, 100)]
                   + [s + 30 for s in range(0, SPAN, 1001) for _ in range(12)])
    admitted = drive(limit, duration, 7_400, times)
    n, upper, steady, _ = judge(limit, duration, 7_400, times, admitted)
    assert (upper, steady) == (10 * 34 + 333 + 1, 10 + 333 + 1)  # 33.3 s sent
    assert admitted > steady + 100 and n == 0 and admitted <= upper
    # the same key hammered (a one-node cell's hot key) stays under it:
    # a request faster than the tick moves the stamp and leaks nothing
    hammered = list(range(0, SPAN, 5))
    assert drive(limit, duration, 7_400, hammered) <= steady - 10
