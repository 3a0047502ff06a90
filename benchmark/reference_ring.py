"""The plain reference for ONE member of a ring as its peers see it,
after upstream gubernator v0.5.0 (gubernator.go:210-227 GetPeerRateLimits;
hash.go:62-96 the ring). Pure Python over reference.Limiter; no JAX, and
nothing of `gubernator_tpu`.

A client's call lands on any node of the ring. That node keeps the
items whose keys it owns and forwards every other item to the key's
owner, in micro-batches of up to `BatchLimit` items
(peers.go:143-172). The owner's `GetPeerRateLimits` applies whatever
arrives, item by item and in order, and checks no ownership
(gubernator.go:210-227): a forwarded batch is answered exactly as the
same items through `GetRateLimits` on a node that owns them all. So
the owner side has no semantics of its own; `owner_answers` is
reference.Limiter over the batches in the order they were served.

`owner_of` states what "this node's share of the key stream" means:
each peer sits at the crc32 of its address, a key at the crc32 of its
hash key (`<name>_<unique_key>`), and a key belongs to the first peer
at or after its point, the lowest point if there is none.
"""

from __future__ import annotations

import bisect
import zlib

from reference import Limiter


def owner_answers(batches, now_ms: int):
    """[[(status, limit, remaining)]]: what the owner answers to
    `batches` = [[(key, hits, limit, duration, algo)]], served one
    after another at `now_ms`; same-key items of one batch are applied
    in order like any others."""
    lim = Limiter()
    return [
        [lim.decide(k, h, li, d, a, now_ms)[:3] for k, h, li, d, a in batch]
        for batch in batches
    ]


def ring_point(text: str) -> int:
    """hash.go:40-42: crc32 (IEEE) of the UTF-8 bytes."""
    return zlib.crc32(text.encode("utf-8")) & 0xFFFFFFFF


def owner_of(key: str, peers):
    """The peer of `peers` (addresses) that owns `key` (hash.go:80-96)."""
    ring = sorted((ring_point(p), p) for p in peers)
    if not ring:
        raise ValueError("a ring of no peers owns nothing")
    i = bisect.bisect_left(ring, (ring_point(key), ""))
    return ring[i % len(ring)][1]
