"""Keys, their limits and their algorithms, all as functions of a key id.

The zipf recipe is a copy of `gubernator_tpu/cli/keystreams.py`
(a = 1.2, `rng.zipf % population`): the yardstick keeps its own so a
later change to the program's copy cannot move the traffic.
"""

from __future__ import annotations

import numpy as np

ZIPF_A = 1.2
ALGORITHMS = {"TOKEN_BUCKET": 0, "LEAKY_BUCKET": 1}
NAME = "bench"  # RateLimitReq.name of every benchmark request


def zipf_ids(population: int, size, rng: np.random.Generator) -> np.ndarray:
    """Zipf(a=1.2) ranks folded into `population` ids (int64)."""
    return (rng.zipf(ZIPF_A, size=size) % population).astype(np.int64)


def seeded_order(base: np.ndarray, seed: int, stream: int) -> np.ndarray:
    """The same multiset of draws for every seed, in an order the seed
    picks: a run's work does not change with its seed, only its order."""
    rng = np.random.default_rng([int(seed), int(stream)])
    return base[rng.permutation(len(base))]


def _pick(ids: np.ndarray, shares, slot: np.ndarray) -> np.ndarray:
    """Index of the share each id falls in: `slot` is id-derived, in
    [0, 100), and shares are cumulated in whole percent."""
    edges = np.cumsum([round(s * 100) for s in shares])
    if edges[-1] != 100:
        raise ValueError(f"shares {shares} do not sum to 1 in whole percent")
    return np.searchsorted(edges, slot, side="right")


class KeyRules:
    """limit, duration and algorithm of a key id, as the traffic file's
    `key_classes` and `algorithms` give them. Shares are exact over any
    100 consecutive ids; the multipliers scatter the classes over the
    hottest ids (1: 100/60 s, 2: 10/1 s, 8: 1000/1 h), and the second
    term turns the algorithm against the class from one hundred ids to
    the next, so neither follows the other."""

    def __init__(self, traffic: dict):
        self.classes = traffic["key_classes"]
        self.algos = traffic["algorithms"]
        self._limit = np.array([c["limit"] for c in self.classes], np.int64)
        self._dur = np.array([c["duration_ms"] for c in self.classes], np.int64)
        self._algo = np.array(
            [ALGORITHMS[a["algorithm"]] for a in self.algos], np.int64
        )

    def of(self, ids: np.ndarray):
        """(limit, duration_ms, algorithm) arrays for `ids`."""
        ids = np.asarray(ids, np.int64)
        c = _pick(ids, [c["share"] for c in self.classes], ids * 37 % 100)
        a = _pick(ids, [a["share"] for a in self.algos],
                  (ids * 61 + ids // 100 * 7 + 17) % 100)
        return self._limit[c], self._dur[c], self._algo[a]


def req(key: str, hits: int, limit: int, duration: int, algo: int):
    """One RateLimitReq (the program's wire type) under the benchmark's name."""
    from gubernator_tpu.api.types import Algorithm, RateLimitReq

    return RateLimitReq(
        name=NAME, unique_key=key, hits=hits, limit=limit, duration=duration,
        algorithm=Algorithm(algo),
    )


def make_reqs(tag: str, ids, rules: KeyRules, hits: int):
    """One request per id, under the key name `<tag>:<id>`."""
    limit, dur, algo = rules.of(ids)
    return [
        req(f"{tag}:{k}", hits, li, d, a)
        for k, li, d, a in zip(
            np.asarray(ids).tolist(), limit.tolist(), dur.tolist(),
            algo.tolist(),
        )
    ]
