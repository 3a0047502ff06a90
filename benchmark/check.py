"""What decides `correct`: every comparison between what the daemon
answered and what the plain reference (reference.py) says it must.

All limits here are exact (limit 0): a rate limiter's answers are
integers, and the configurations state "zero under-admission, every
acknowledged hit read back". The checks:

  pre-window   a seeded sequence through the cell's door, item by item
  canaries     seeded 1-day keys hit all through the window: the replies
               of each key, as a multiset, equal the reference's, and a
               peek after the window reads limit - admitted
  tallies      per key over everything the generators sent: a token key
               whose window outlasts the run (preload included) admitted
               exactly min(offered, limit); no key admitted more than
               the windows that fit in the run allow, or fewer than one
               window's worth
  well-formed  every reply echoes its limit, 0 <= remaining <= limit
  counters     no eviction, no dropped create, no compile in the window
"""

from __future__ import annotations

import random
from collections import Counter

import numpy as np

from reference import OVER, TOKEN, UNDER, Limiter

DAY_MS = 86_400_000
CHECK_LIMIT = 5


def checked_sequence(seed: int, algos, n_calls: int = 24, items: int = 12):
    """[[(key, hits, limit, duration, algo)]]: every algorithm the cell
    sends, in-batch duplicates, keys driven over their limit, peeks.
    Day-long windows, so no answer depends on the wall clock. Within one
    call a key always carries the same hits: for same-key items of one
    batch the program documents a cumulative-attempt rule
    (core/kernels.py, "Intra-batch duplicate keys") that equals
    one-by-one service exactly when their hits are equal and is more
    restrictive otherwise, as upstream's own order is the scheduler's."""
    rng = random.Random(seed)
    calls = []
    for c in range(n_calls):
        hits_of = {}
        call = []
        for _ in range(items):
            algo = rng.choice(algos)
            key = f"chk{seed}:{algo}:{rng.randrange(30)}"
            hits = hits_of.setdefault(key, rng.choice((1, 1, 1, 2, 0)))
            call.append((key, hits, CHECK_LIMIT, DAY_MS, algo))
        if c % 2 == 0:  # an in-batch duplicate of the first item
            call.append(call[0])
        calls.append(call)
    return calls


def reference_answers(calls, now_ms: int):
    """(status, limit, remaining) per item, from the plain reference."""
    lim = Limiter()
    return [
        [lim.decide(k, h, li, d, a, now_ms)[:3] for k, h, li, d, a in call]
        for call in calls
    ]


def compare_sequence(calls, got, want):
    """Number compared, number that differ, the first difference."""
    n = bad = 0
    first = None
    for call, g, w in zip(calls, got, want):
        for item, a, b in zip(call, g, w):
            n += 1
            if tuple(a) != tuple(b):
                bad += 1
                first = first or {"item": item, "got": list(a), "want": list(b)}
    return n, bad, first


# -- canaries ----------------------------------------------------------------


def canary_keys(seed: int, worker: int, n: int, algos):
    """n (key, limit, algo) canaries owned by one generator worker: a
    day-long window and a limit small enough that a run drives it over."""
    rng = random.Random((seed << 8) ^ (worker + 1))
    return [
        (f"canary{seed}:{worker}:{j}", rng.randrange(20, 120),
         algos[j % len(algos)])
        for j in range(n)
    ]


def canary_verdict(key, limit, algo, replies, peek, now_ms: int):
    """replies: [(status, limit, remaining)] of every hits=1 request the
    worker sent for `key` (reply order is free: calls overlap on one
    connection); peek: the hits=0 answer after the window. The
    reference replays as many hits and must give the same multiset and
    the same peek. Returns None or a description of the fault."""
    lim = Limiter()
    want = [
        lim.decide(key, 1, limit, DAY_MS, algo, now_ms)[:3]
        for _ in replies
    ]
    if Counter(map(tuple, replies)) != Counter(want):
        return {"key": key, "fault": "replies differ from the reference",
                "got_admitted": sum(r[0] == UNDER for r in replies),
                "want_admitted": sum(w[0] == UNDER for w in want)}
    want_peek = lim.decide(key, 0, limit, DAY_MS, algo, now_ms)[:3]
    if tuple(peek) != want_peek:
        return {"key": key, "fault": "peek after the window",
                "got": list(peek), "want": list(want_peek)}
    return None


# -- tallies -----------------------------------------------------------------


def tally_faults(ids, offered, admitted, in_doubt, limit, duration, algo,
                 span_ms: float, lead_ms: float = 0.0):
    """Arrays over the keys the run touched (hits = 1 everywhere).
    Returns the count of keys outside their bounds and the first few.

    `span_ms` runs from the generators' first send to their last reply,
    `lead_ms` from the start of the preload (which creates the keys, so
    a window may already be open at the first send) to that first send.
    upper: a token window admits `limit`, and the windows that can be
    open in the span are one that the preload left open plus those that
    open in it, and never more than fit between the preload's start and
    the last reply; a leaky bucket holds at most `limit` at the first
    send and gains one hit per `duration // limit` ms.
    lower: one window's worth, less hits whose answer was lost.
    A token key whose window outlasts preload and span has upper == lower."""
    span = int(span_ms) + 1
    whole = span + int(lead_ms) + 1
    windows = np.minimum(2 + span // duration, 1 + whole // duration)
    upper = np.where(
        algo == TOKEN, limit * windows,
        limit + span // np.maximum(duration // np.maximum(limit, 1), 1) + 1,
    )
    upper = np.minimum(offered, upper)
    lower = np.minimum(offered, np.maximum(limit - in_doubt, 0))
    bad = (admitted > upper) | (admitted < lower)
    exact = int(np.sum((algo == TOKEN) & (windows == 1) & (offered > limit)))
    faults = [
        {"id": int(ids[i]), "offered": int(offered[i]),
         "admitted": int(admitted[i]), "lower": int(lower[i]),
         "upper": int(upper[i]), "algo": int(algo[i]),
         "duration_ms": int(duration[i])}
        for i in np.flatnonzero(bad)[:5]
    ]
    return int(bad.sum()), faults, exact


def malformed(status, limit, remaining, want_limit) -> int:
    """Replies that are not well-formed: a status outside {0, 1}, a limit
    not echoed, a remaining outside [0, limit]."""
    status, limit, remaining = map(np.asarray, (status, limit, remaining))
    ok = (
        ((status == UNDER) | (status == OVER))
        & (limit == want_limit) & (remaining >= 0) & (remaining <= limit)
    )
    return int((~ok).sum())
