"""The plain reference: token and leaky bucket after upstream
gubernator's algorithms.go, in pure Python over a dict. No JAX, and
nothing of `gubernator_tpu`: the same requests at the same clock give
the answers a correct limiter must give.

Observable behaviour kept from algorithms.go: an OVER_LIMIT answer for
"more hits than remain" changes nothing; a drained token bucket answers
OVER_LIMIT with remaining 0 until it expires; hits == 0 peeks (a leaky
bucket that is empty answers OVER_LIMIT to a peek too); a leaky bucket
moves its timestamp on every request that carries hits; a key found
under the other algorithm is recreated as a fresh TOKEN bucket. Two
repairs upstream needs and the program documents: a leaky bucket
expires `duration` after its last admitted hit, and its rate is
max(duration // limit, 1).
"""

from __future__ import annotations

TOKEN, LEAKY = 0, 1
UNDER, OVER = 0, 1


class Limiter:
    """decide() answers (status, limit, remaining, reset_time_ms)."""

    def __init__(self):
        self._s = {}  # key -> [algo, limit, duration, remaining, stamp, expire, sticky]

    def decide(self, key, hits, limit, duration, algo, now):
        e = self._s.get(key)
        if e is not None and e[5] < now:
            e = None
        if e is not None and e[0] != algo:
            e, algo = None, TOKEN
        if e is None:
            return self._create(key, hits, limit, duration, algo, now)
        if algo == TOKEN:
            return self._token(e, hits)
        return self._leaky(e, hits, limit, now)

    def _create(self, key, hits, limit, duration, algo, now):
        over = hits > limit
        if algo == TOKEN:
            remaining = limit if over else limit - hits
            self._s[key] = [TOKEN, limit, duration, remaining, now,
                            now + duration, over]
            return (OVER if over else UNDER, limit, remaining, now + duration)
        remaining = 0 if over else limit - hits
        self._s[key] = [LEAKY, limit, duration, remaining, now,
                        now + duration, False]
        return (OVER if over else UNDER, limit, remaining, 0)

    @staticmethod
    def _token(e, hits):
        _, limit, _, remaining, _, expire, sticky = e
        if remaining == 0:
            e[6] = True
            return (OVER, limit, 0, expire)
        status = OVER if sticky else UNDER
        if hits == 0:
            return (status, limit, remaining, expire)
        if hits > remaining:
            return (OVER, limit, remaining, expire)
        e[3] = remaining - hits
        return (status, limit, e[3], expire)

    @staticmethod
    def _leaky(e, hits, req_limit, now):
        _, limit, duration, remaining, stamp, _, _ = e
        if req_limit <= 0:
            return (OVER, req_limit, 0, now + duration)
        rate = max(duration // req_limit, 1)
        remaining = min(remaining + (now - stamp) // rate, limit)
        e[3] = remaining
        if hits != 0:
            e[4] = now
        if remaining == 0:
            return (OVER, limit, 0, now + rate)
        if hits == remaining:
            e[3] = 0
            return (UNDER, limit, 0, 0)
        if hits > remaining:
            return (OVER, limit, remaining, now + rate)
        if hits == 0:
            return (UNDER, limit, remaining, 0)
        e[3] = remaining - hits
        e[5] = now + duration
        return (UNDER, limit, e[3], 0)
