"""The plain reference for Behavior GLOBAL on ONE node that owns every
key, after upstream gubernator v0.5.0 (gubernator.go:133-140, 240-242;
global.go). Pure Python over reference.Limiter; no JAX, and nothing of
`gubernator_tpu`.

What upstream does with a GLOBAL item depends on who owns its key. A
non-owner answers from its replica and queues the hits for the owner
(gubernator.go:133-140). The OWNER decides the item exactly as it
decides a BATCHING one, and marks the key for broadcast
(gubernator.go:240-242, global.go QueueUpdate). Its broadcast loop
(global.go:158-232) wakes after `GlobalSyncWait`, takes the marked keys,
reads each one's status with a zero-hit request, and sends the statuses
to every OTHER peer.

A node that owns every key, the `global-mesh4` deployment, is always the
owner: no replica answer, no queued hits, and a broadcast that peeks and
then finds nobody to send to. So for what a client can observe, GLOBAL
equals BATCHING there, provided the peek moves nothing: a zero-hit
request to a token bucket changes no counter (it may set the sticky
OVER flag of a bucket that is already at 0, which answers OVER_LIMIT
either way), and to a leaky bucket it applies the leak every request
applies (nothing, while the clock stands still or the last hit is less
than one `duration // limit` old).
"""

from __future__ import annotations

from reference import Limiter

BATCHING, GLOBAL = 0, 2


class OwnerNode:
    """One node of a ring of `peers` + 1 that owns every key it is
    asked about. `decide` answers like reference.Limiter.decide;
    `broadcast` is one flush of the owner's broadcast loop."""

    def __init__(self, peers: int = 0):
        self.limiter = Limiter()
        self.peers = peers
        self.marked = {}  # key -> (limit, duration, algo), in mark order
        self.sent = []  # (peer, key, (status, limit, remaining))
        self.peeks = 0

    def decide(self, key, hits, limit, duration, algo, behavior, now):
        answer = self.limiter.decide(key, hits, limit, duration, algo, now)
        if behavior == GLOBAL:
            self.marked[key] = (limit, duration, algo)
        return answer

    def broadcast(self, now) -> dict:
        """Peek every marked key (hits 0) and send its status to every
        other peer; returns {key: (status, limit, remaining)}."""
        marked, self.marked = self.marked, {}
        statuses = {}
        for key, (limit, duration, algo) in marked.items():
            statuses[key] = self.limiter.decide(
                key, 0, limit, duration, algo, now
            )[:3]
            self.peeks += 1
        for peer in range(self.peers):
            for key, status in statuses.items():
                self.sent.append((peer, key, status))
        return statuses

    def windows(self) -> dict:
        """{key: (algo, limit, duration, remaining)}: every counter the
        node holds, for "did a peek move one"."""
        return {k: tuple(e[:4]) for k, e in self.limiter._s.items()}
