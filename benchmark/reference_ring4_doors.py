"""The plain reference for a ring asked at EVERY door at once, after
upstream gubernator v0.5.0 (cluster/cluster.go:56-58 GetPeer and
client.go:66-82 RandomPeer: a client dials any node;
architecture.md:13-17: any peer forwards to the one owner). Pure Python
over reference.Limiter, reference_ring.owner_of and
reference_ring4.Ring; no JAX, and nothing of `gubernator_tpu`.

Behind a load balancer each door sees its own sequence of calls, and
nobody fixes the order in which calls of different doors reach the
owners: the ring serves SOME interleaving of the doors' sequences, each
door's calls in that door's order. What this file states, and what
check.py relies on when it sums tallies over workers at four doors:

1. `same_as_one_limiter`: for ANY interleaving (`interleave`), the
   ring's answers — every item answered by the limiter of the one node
   that owns its key, whichever door the call came through — equal ONE
   `reference.Limiter`'s over the interleaved sequence. Doors add where
   the work runs, never an answer.

2. `key_summaries` / `same_for_every_order`: for items that carry ONE
   hit, at a standing clock inside a key's window, the multiset of a
   key's answers, the count admitted (`min(hits offered, limit)`) and
   the peek after them are the same for EVERY interleaving: a key's
   single hits are interchangeable, so the order the doors' calls
   reach its owner decides which call sees which `remaining`, never
   which values are seen or how many are admitted. That is why
   tallies summed over workers per key (offered, admitted) and
   per-worker canaries compared as multisets (each canary key belongs
   to one worker, so to one door, in that door's order) decide
   `correct` exactly with four doors as they do with one.
"""

from __future__ import annotations

from collections import Counter

import reference_ring4
from reference import UNDER
from reference_ring4 import Ring


def interleave(calls_by_door: dict, order) -> list:
    """[(door, call)]: the doors' call sequences merged as `order` says
    — a sequence of door addresses in which each door stands once for
    each of its calls; every door's calls keep their own order."""
    at = dict.fromkeys(calls_by_door, 0)
    merged = []
    for door in order:
        calls = calls_by_door[door]
        if at[door] >= len(calls):
            raise ValueError(f"the order names door '{door}' more often "
                             f"than it has calls ({len(calls)})")
        merged.append((door, calls[at[door]]))
        at[door] += 1
    left = {d: len(c) - at[d] for d, c in calls_by_door.items() if at[d] < len(c)}
    if left:
        raise ValueError(f"the order leaves calls unsent: {left}")
    return merged


def round_robin(calls_by_door: dict) -> list:
    """One order among many: the doors in turn until each has run out."""
    longest = max((len(c) for c in calls_by_door.values()), default=0)
    return [d for i in range(longest)
            for d, c in calls_by_door.items() if i < len(c)]


def _calls_and_doors(calls_by_door: dict, order):
    merged = interleave(calls_by_door, order)
    return [call for _, call in merged], [door for door, _ in merged]


def ring_answers(calls_by_door: dict, peers, order, now_ms: int,
                 name: str = "bench") -> list:
    """[[(status, limit, remaining)]] in the interleaved order: each
    call served by a `reference_ring4.Ring` at the door it came through."""
    calls, doors = _calls_and_doors(calls_by_door, order)
    return reference_ring4.ring_answers(calls, peers, now_ms, name, asked=doors)


def one_limiter_answers(calls_by_door: dict, order, now_ms: int) -> list:
    """The same interleaved sequence through ONE reference.Limiter."""
    calls, _ = _calls_and_doors(calls_by_door, order)
    return reference_ring4.one_limiter_answers(calls, now_ms)


def same_as_one_limiter(calls_by_door: dict, peers, order, now_ms: int,
                        name: str = "bench") -> bool:
    """Whatever the interleaving, the ring answers as one limiter."""
    return (ring_answers(calls_by_door, peers, order, now_ms, name)
            == one_limiter_answers(calls_by_door, order, now_ms))


def key_summaries(calls_by_door: dict, peers, order, now_ms: int,
                  name: str = "bench") -> dict:
    """{key: (multiset of its answers, hits admitted, the peek after
    them)} for one interleaving; the peek is (status, limit, remaining)
    of a zero-hit item through any door."""
    ring = Ring(peers)
    answers, admitted, shape = {}, Counter(), {}
    for door, call in interleave(calls_by_door, order):
        for (k, h, li, d, a), got in zip(call, ring.call(call, now_ms, name, door)):
            answers.setdefault(k, Counter())[got] += 1
            admitted[k] += h if got[0] == UNDER else 0
            shape[k] = (li, d, a)
    door = next(iter(calls_by_door))
    return {
        k: (answers[k], admitted[k],
            ring.call([(k, 0, *shape[k])], now_ms, name, door)[0])
        for k in answers
    }


def same_for_every_order(calls_by_door: dict, peers, orders, now_ms: int,
                         name: str = "bench") -> bool:
    """Single-hit items alone: every interleaving of `orders` gives each
    key the same multiset of answers, the same admitted count =
    min(offered, limit), and the same final peek."""
    offered, limit = Counter(), {}
    for calls in calls_by_door.values():
        for call in calls:
            for k, h, li, _d, _a in call:
                if h != 1:
                    raise ValueError("stated for items that carry one hit")
                offered[k] += 1
                limit[k] = li
    first = None
    for order in orders:
        got = key_summaries(calls_by_door, peers, order, now_ms, name)
        if any(got[k][1] != min(offered[k], limit[k]) for k in got):
            return False
        if first is None:
            first = got
        elif got != first:
            return False
    return first is not None
