"""The plain reference for a whole ring, after upstream gubernator
v0.5.0 (architecture.md:13-17: a key has one owner, any peer forwards
to it; gubernator.go:114-170 the split of a call into owned and foreign
items; hash.go:62-96 the ring). Pure Python over reference.Limiter and
reference_ring.owner_of; no JAX, and nothing of `gubernator_tpu`.

`Ring` is N limiters, one a node. A call lands on any node; every item
is answered by the limiter of the node that owns its key
(`reference_ring.owner_of`), wherever the call landed, and the answers
come back in the caller's order. Items of one call for one owner are
applied in their order in the call; items for different owners touch
different keys, so their order among each other changes nothing.

`same_as_one_limiter` is the statement check.py relies on when it sends
every check through node 0: a key has exactly one owner, so one key's
sequence of answers is the same whichever node is asked, and the ring's
answers to any sequence of calls equal ONE Limiter's over the same
sequence. (What a ring adds is where the work runs and what it costs,
not an answer.)
"""

from __future__ import annotations

from reference import Limiter
from reference_ring import owner_of


class Ring:
    """`peers`: the nodes' addresses (what stands on the crc32 circle)."""

    def __init__(self, peers):
        self.peers = list(peers)
        if not self.peers:
            raise ValueError("a ring of no peers answers nothing")
        self.limiters = {p: Limiter() for p in self.peers}

    def owner(self, name: str, key: str) -> str:
        """The node that owns the item: its place is the crc32 of the
        hash key `<name>_<unique_key>`."""
        return owner_of(f"{name}_{key}", self.peers)

    def call(self, items, now_ms: int, name: str = "bench", asked=None):
        """[(status, limit, remaining)] in the caller's order for
        `items` = [(key, hits, limit, duration, algo)] sent to node
        `asked` (an address; any, and it changes no answer)."""
        if asked is not None and asked not in self.limiters:
            raise ValueError(f"the ring has no node '{asked}'")
        return [
            self.limiters[self.owner(name, k)].decide(k, h, li, d, a, now_ms)[:3]
            for k, h, li, d, a in items
        ]

    def forwarded(self, items, asked: str, name: str = "bench") -> int:
        """How many of `items` node `asked` does not own."""
        return sum(self.owner(name, k) != asked for k, *_ in items)


def ring_answers(calls, peers, now_ms: int, name: str = "bench", asked=None):
    """[[(status, limit, remaining)]]: the ring's answers to `calls`,
    served one after another at `now_ms`; `asked` = one address, or a
    list with the node each call lands on, or None."""
    ring = Ring(peers)
    if asked is None or isinstance(asked, str):
        asked = [asked] * len(calls)
    return [ring.call(c, now_ms, name, a) for c, a in zip(calls, asked)]


def one_limiter_answers(calls, now_ms: int):
    """The same sequence through ONE reference.Limiter."""
    lim = Limiter()
    return [
        [lim.decide(k, h, li, d, a, now_ms)[:3] for k, h, li, d, a in call]
        for call in calls
    ]


def same_as_one_limiter(calls, peers, now_ms: int, name: str = "bench",
                        asked=None) -> bool:
    """Whichever nodes are asked, the ring answers as one limiter."""
    return (ring_answers(calls, peers, now_ms, name, asked)
            == one_limiter_answers(calls, now_ms))
