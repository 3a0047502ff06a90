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
