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
    Returns the count of keys outside their bounds, the first few, the
    token keys held exactly, and what is reported beside them and judges
    nothing (`leaky_over_steady`, `closest`: below).

    `span_ms` runs from the generators' first send to their last reply,
    `lead_ms` from the start of the preload (which creates the keys, so
    a window may already be open at the first send) to that first send.

    upper, token: a window admits `limit`, and the windows that can be
    open in the span are one that the preload left open plus those that
    open in it, and never more than fit between the preload's start and
    the last reply (`windows`).

    upper, leaky: limit x creations that fit + ticks that fit + 1, the
    most reference.py's `_leaky` can admit, line by line:
    (i) a request admits only tokens the bucket holds, and it holds what
        a creation granted and what leaked since. A leak is
        `(now - stamp) // rate` at a request that carries hits, which
        then moves `stamp` to `now`: floors over disjoint gaps between
        such requests, so all the leaks of a span sum to at most
        `span // rate` (a key hammered faster than its tick leaks
        nothing: every gap floors to 0). The gap that ends at the first
        send began at the preload and is lost to `min(., limit)`: the
        preload left the bucket full.
    (ii) a creation grants `limit`. A bucket is created when the request
        finds `expire < now`, and `expire` is `duration` after the
        creation or a later admit, so creations lie more than `duration`
        apart: 1 + span // duration fit in the span. The bucket the
        preload left is no extra grant when limit >= 2: its first admit
        takes the `hits < remaining` branch, which moves `expire` to
        now + duration, so from there it stands for the span's first
        creation. With limit == 1 that admit is `hits == remaining`,
        `expire` stays, and the bucket may expire and be created again
        at once: `windows`, as for a token key.
    (iii) the two ADD, because the admit through `hits == remaining`
        (the bucket's last token) leaves `expire` where it was. The
        schedule that shows it, 10 a second (rate 100 ms): t = 0 created,
        ten hits admitted, the ninth left expire = 1000; t = 100, 200,
        ... 1000: one token leaked, admitted as the last, expire still
        1000; t = 1001: expired, created FULL, ten admitted. 20 a
        second where a leaky bucket would give 10, all through the run.
    No schedule does better (benchmark/tests/test_reference.py: 1,200
    seeded schedules never pass it, the adaptive one above reaches 99%).
    The form before PR 39, `limit + span // rate + 1` (a bucket that
    never expires), is what the reference itself passes on a key it sees
    about once a tick: a door node that thins the stream, a pause in the
    served path. What this costs the check's sight: PERF.md section 2.

    lower: one window's worth, less hits whose answer was lost.
    A token key whose window outlasts preload and span has upper == lower.

    Reported, not judged: `leaky_over_steady`, the leaky keys above the
    old form (0 while every leaky key is hammered; a later change that
    moves it shows here), and `closest`, for each algorithm and key
    class the key that came nearest the bound it was judged by (keys
    whose offer is the bound, and token keys held exactly, say nothing
    and are left out), a leaky key with the old form beside it."""
    span = int(span_ms) + 1
    whole = span + int(lead_ms) + 1
    windows = np.minimum(2 + span // duration, 1 + whole // duration)
    ticks = span // np.maximum(duration // np.maximum(limit, 1), 1) + 1
    creations = np.where(limit > 1, 1 + span // duration, windows)
    steady = limit + ticks
    bound = np.where(algo == TOKEN, limit * windows, limit * creations + ticks)
    upper = np.minimum(offered, bound)
    lower = np.minimum(offered, np.maximum(limit - in_doubt, 0))
    bad = (admitted > upper) | (admitted < lower)
    held = (algo == TOKEN) & (windows == 1)
    exact = int(np.sum(held & (offered > limit)))
    faults = [
        {"id": int(ids[i]), "offered": int(offered[i]),
         "admitted": int(admitted[i]), "lower": int(lower[i]),
         "upper": int(upper[i]), "algo": int(algo[i]),
         "duration_ms": int(duration[i])}
        for i in np.flatnonzero(bad)[:5]
    ]
    closest = []
    judged = (offered > bound) & ~held
    for a, li, d in sorted(set(zip(algo[judged].tolist(), limit[judged].tolist(),
                                   duration[judged].tolist()))):
        of = np.flatnonzero(judged & (algo == a) & (limit == li) & (duration == d))
        i = of[np.argmin(bound[of] - admitted[of])]
        closest.append({
            "algo": a, "limit": li, "duration_ms": d, "keys": int(len(of)),
            "id": int(ids[i]), "offered": int(offered[i]),
            "admitted": int(admitted[i]), "upper": int(bound[i]),
            **({} if a == TOKEN else {"steady": int(steady[i])}),
        })
    seen = {
        "leaky_over_steady": int(np.sum((algo != TOKEN) & (admitted > steady))),
        "closest": closest,
    }
    return int(bad.sum()), faults, exact, seen


def malformed(status, limit, remaining, want_limit) -> int:
    """Replies that are not well-formed: a status outside {0, 1}, a limit
    not echoed, a remaining outside [0, limit]."""
    status, limit, remaining = map(np.asarray, (status, limit, remaining))
    ok = (
        ((status == UNDER) | (status == OVER))
        & (limit == want_limit) & (remaining >= 0) & (remaining <= limit)
    )
    return int((~ok).sum())
