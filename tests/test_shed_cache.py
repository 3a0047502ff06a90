"""Over-limit shed cache (r10): differential identity + invalidation.

The shed contract under test (serve/shedcache.py): with the cache ON,
every response is byte-identical to the cache-OFF pipeline — the shed
only answers requests whose verdict is a frozen token-bucket refusal
the device would echo verbatim. The suites here pin:

- randomized differential identity ON vs OFF over the exact backend
  AND the device (tpu-on-cpu) backend: mixed token/leaky algorithms,
  duplicate keys per batch, peeks, oversized hits, mid-window
  limit/duration changes, and clock advances across reset boundaries
  (a shared fake clock drives both pipelines so reset_time compares
  exactly);
- peeks (hits=0) bypass the shed entirely;
- the reset_time expiry boundary: the first post-reset hit reaches the
  device (and recreates the window there);
- GLOBAL-update invalidation: an UpdatePeerGlobals install purges its
  keys so a replica reset is never shadowed by a stale verdict;
- owned-GLOBAL sheds preserve the broadcast side effect (queue_update);
- bridge-tier shed under windowed multi-frame load (GEB7): shed items
  never reach the batcher, responses stitch back in frame order, and
  the `shed` stage keeps the frame-coverage contract;
- the engine reset-generation clears the cache.
"""

import asyncio
import struct
from collections import OrderedDict

import numpy as np
import pytest

from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    PeerInfo,
    RateLimitReq,
    RateLimitResp,
    Status,
    millisecond_now,
)
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.serve.backends import ExactBackend, TpuBackend
from gubernator_tpu.serve.config import ServerConfig
from gubernator_tpu.serve.instance import Instance
from gubernator_tpu.serve.shedcache import ShedCache

T0 = 1_700_000_000_000
ADDR = "127.0.0.1:7971"


class FakeClock:
    def __init__(self, t=T0):
        self.t = t

    def __call__(self) -> int:
        return self.t


# -- ShedCache unit gates ---------------------------------------------------


def _run_body(monkeypatch, body: str) -> None:
    """Make ShedCache's array methods run their native call or, with
    the library hidden from the module, their numpy twin."""
    from gubernator_tpu.serve import shedcache

    if body == "numpy":
        monkeypatch.setattr(shedcache, "_hn", None)
    elif shedcache._hn is None:
        pytest.skip("libguberhash.so is absent: the numpy body is the "
                    "one there is (make -C gubernator_tpu/native)")



def test_lookup_gates_and_expiry():
    clock = FakeClock()
    c = ShedCache(8, now_fn=clock)
    c._observe_one(42, 1, 10, 1000, 0, int(Status.OVER_LIMIT), 10, 0,
                   clock.t + 500, clock.t)
    assert len(c) == 1
    r = RateLimitReq(name="n", unique_key="k", hits=1, limit=10,
                     duration=1000)
    assert c.lookup_resp(42, r).reset_time == clock.t + 500
    # param mismatch is a miss, not a drop
    r2 = RateLimitReq(name="n", unique_key="k", hits=1, limit=11,
                      duration=1000)
    assert c.lookup_resp(42, r2) is None and len(c) == 1
    # peek and leaky bypass (not even a lookup)
    lk = c.lookups
    assert c.lookup_resp(
        42, RateLimitReq(name="n", unique_key="k", hits=0, limit=10,
                         duration=1000)
    ) is None
    assert c.lookup_resp(
        42, RateLimitReq(name="n", unique_key="k", hits=1, limit=10,
                         duration=1000,
                         algorithm=Algorithm.LEAKY_BUCKET)
    ) is None
    assert c.lookups == lk
    # expiry boundary: at now == reset_time the entry is dead (the
    # first post-reset hit must reach the device)
    clock.t += 500
    assert c.lookup_resp(42, r) is None
    assert len(c) == 0


def test_r15_algorithms_never_shed():
    """r15 interplay audit (core/algorithms.py SHEDDABLE_ALGOS): a
    sliding-window blend decays and a GCRA TAT drains every
    millisecond, so their OVER verdicts are never provably current —
    they must neither consult nor populate the shed cache, and a
    stale token entry must never answer one of their requests."""
    from gubernator_tpu.core.algorithms import sheddable

    assert not sheddable(int(Algorithm.SLIDING_WINDOW))
    assert not sheddable(int(Algorithm.GCRA))

    clock = FakeClock()
    c = ShedCache(8, now_fn=clock)
    c._observe_one(42, 1, 10, 1000, 0, int(Status.OVER_LIMIT), 10, 0,
                   clock.t + 500, clock.t)
    assert len(c) == 1
    # a cached token verdict never answers a sliding/GCRA request for
    # the same fingerprint (not even a lookup)
    lk = c.lookups
    for algo in (Algorithm.SLIDING_WINDOW, Algorithm.GCRA):
        assert c.lookup_resp(
            42, RateLimitReq(name="n", unique_key="k", hits=1,
                             limit=10, duration=1000, algorithm=algo)
        ) is None
    assert c.lookups == lk
    # the bridge array screen is equally gated: a GCRA row over a
    # cached fingerprint does not shed
    for algo in (2, 3):
        fields = dict(
            key_hash=np.array([42], np.uint64),
            hits=np.array([1], np.int64),
            limit=np.array([10], np.int64),
            duration=np.array([1000], np.int64),
            algo=np.array([algo], np.int32),
        )
        assert c.screen_fields(fields, clock.t) is None
    # observing a sliding/GCRA response DROPS the stale token entry
    # (algorithm switch recreates the window, like leaky)...
    c._observe_one(42, 1, 10, 1000, int(Algorithm.GCRA),
                   int(Status.OVER_LIMIT), 10, 0, clock.t + 500,
                   clock.t)
    assert 42 not in c
    # ...and never populates one of its own
    for algo in (2, 3):
        c._observe_one(7, 1, 10, 1000, algo, int(Status.OVER_LIMIT),
                       10, 0, clock.t + 500, clock.t)
        assert 7 not in c


def test_lru_bound_and_observe_drop():
    clock = FakeClock()
    c = ShedCache(4, now_fn=clock)
    for h in range(6):
        c._observe_one(h, 1, 5, 1000, 0, int(Status.OVER_LIMIT), 5, 0,
                       clock.t + 9999, clock.t)
    assert len(c) == 4  # bounded; oldest evicted
    assert 0 not in c and 5 in c
    # an under-limit response for a cached fingerprint drops it
    c._observe_one(5, 1, 5, 1000, 0, int(Status.UNDER_LIMIT), 5, 3,
                   clock.t + 9999, clock.t)
    assert 5 not in c
    # a leaky request for a cached fingerprint drops it (algo switch)
    c._observe_one(4, 1, 5, 1000, 1, int(Status.UNDER_LIMIT), 5, 4, 0,
                   clock.t)
    assert 4 not in c


def test_observe_confirmation_vs_contradiction():
    """The device answers an existing window's hits with the STORED
    limit, so a param-mismatched request's response ECHOES the cached
    window — it must confirm the entry, not drop it (mixed-param
    traffic would otherwise thrash the cache on the hottest keys).
    Only a response contradicting the cached window drops it."""
    clock = FakeClock()
    c = ShedCache(8, now_fn=clock)
    reset = clock.t + 9999
    c._observe_one(9, 1, 10, 1000, 0, int(Status.OVER_LIMIT), 10, 0,
                   reset, clock.t)
    # req_limit=20 mismatches, but the response echoes the stored
    # window (limit 10, same reset): keep
    c._observe_one(9, 1, 20, 1000, 0, int(Status.OVER_LIMIT), 10, 0,
                   reset, clock.t)
    assert c.get(9) == (10, 1000, reset)
    # a different reset means the window was recreated: drop
    c._observe_one(9, 1, 20, 1000, 0, int(Status.OVER_LIMIT), 10, 0,
                   reset + 5, clock.t)
    assert 9 not in c


def test_generation_clears():
    gen = [0]
    c = ShedCache(8, now_fn=FakeClock(), generation_fn=lambda: gen[0])
    c._observe_one(1, 1, 5, 1000, 0, int(Status.OVER_LIMIT), 5, 0,
                   T0 + 9999, T0)
    c.refresh_generation()
    assert len(c) == 1
    gen[0] += 1  # engine store wiped
    c.refresh_generation()
    assert len(c) == 0


# -- the slot table against a plain-dict model of the rules -----------------


class _DictModel:
    """The cache's rules, one dict probe a row (the structure this
    module had before its lookup table lived in slots): the reference
    the vectorized table must agree with after every operation."""

    def __init__(self, capacity, insert_cap):
        self.capacity = capacity
        self.insert_cap = insert_cap
        self.entries = OrderedDict()

    def store(self, h, limit, duration, reset):
        self.entries[h] = (limit, duration, reset)
        self.entries.move_to_end(h)
        if len(self.entries) > self.capacity:
            self.entries.popitem(last=False)

    def seed(self, h, limit, duration, reset, now):
        if now < reset:
            self.store(h, limit, duration, reset)

    def lookup(self, h, limit, duration, now):
        e = self.entries.get(h)
        if e is None:
            return None
        if now >= e[2]:
            del self.entries[h]
            return None
        if e[0] != limit or e[1] != duration:
            return None
        self.entries.move_to_end(h)
        return e[2]

    def sheds(self, h, hits, limit, duration, algo, gnp, now):
        """screen_fields' rule for one row: no recency refresh, an
        expired entry skipped and not deleted."""
        e = self.entries.get(h)
        if e is None or algo != 0 or hits <= 0 or gnp:
            return None
        if e[0] != limit or e[1] != duration or now >= e[2]:
            return None
        return e[2]

    def observe_one(self, h, limit, duration, algo, status, r_limit,
                    remaining, r_reset, now):
        if algo != 0:
            self.entries.pop(h, None)
            return
        frozen = status == int(Status.OVER_LIMIT) and remaining == 0
        if frozen and r_limit == limit and now < r_reset:
            self.store(h, limit, duration, r_reset)
            return
        e = self.entries.get(h)
        if e is None:
            return
        if frozen and r_limit == e[0] and r_reset == e[2]:
            return
        del self.entries[h]

    def observe_rows(self, rows, now):
        cached = [r[0] in self.entries for r in rows]
        must = [r for r, c in zip(rows, cached) if c]
        fresh = [
            r for r, c in zip(rows, cached)
            if not c and r[4] == int(Status.OVER_LIMIT) and r[6] == 0
        ]
        # a row of another algorithm can only drop, and only what a
        # token row of this call may have stored before the walk
        # reaches it
        storable = {r[0] for r in fresh if r[3] == 0}
        ins = [
            r for r in fresh if r[3] == 0 or r[0] in storable
        ][: self.insert_cap]
        for r in must + ins:
            self.observe_one(*r, now)


@pytest.mark.parametrize("body", ["native", "numpy"])
@pytest.mark.parametrize(
    "seed,capacity,overlay,insert_cap",
    [(1, 40, 6, 5), (2, 40, 256, 512), (3, 4096, 16, 512)],
)
def test_slot_table_equals_dict_model(
    monkeypatch, seed, capacity, overlay, insert_cap, body
):
    """A few thousand seeded seed / observe_fields / observe_resps /
    lookup / purge / refresh_generation operations under a moving
    clock: after EVERY one, screen_fields over every key ever used
    sheds exactly the rows the model's per-row rule sheds, with the
    model's reset_time — so a dropped, expired, purged, evicted or
    recycled fingerprint never sheds — and len() stays inside the
    capacity. The small overlay and capacity make folds, slot
    recycling and LRU eviction happen every few operations."""
    from gubernator_tpu.serve import shedcache

    _run_body(monkeypatch, body)
    monkeypatch.setattr(shedcache, "OVERLAY_MAX", overlay)
    monkeypatch.setattr(shedcache, "OBSERVE_INSERT_CAP", insert_cap)
    rng = np.random.default_rng(seed)
    clock = FakeClock()
    gen = [0]
    c = ShedCache(capacity, now_fn=clock, generation_fn=lambda: gen[0])
    model = _DictModel(capacity, insert_cap)
    pool = rng.integers(
        0, 1 << 64, size=min(3 * capacity, 300), dtype=np.uint64
    )
    pool[0] = 0  # a fingerprint of 0 is a key like any other
    pool[1] = (1 << 64) - 1
    params = [(10, 1000), (100, 60_000), (1000, 3_600_000)]

    def param_of(h):
        return params[int(h) % 3]

    def random_rows(n):
        rows = []
        for h in rng.choice(pool, n).tolist():
            limit, duration = param_of(h)
            if rng.random() < 0.1:
                limit += 1  # a request under other params
            algo = int(rng.choice([0, 0, 0, 0, 1, 2, 3]))
            over = rng.random() < 0.6
            r_limit = param_of(h)[0] if rng.random() < 0.9 else limit
            r_reset = clock.t + int(rng.choice([-5, 1, 300, 800, 5000]))
            if rng.random() < 0.3 and h in model.entries:
                r_reset = model.entries[h][2]  # echo the cached window
            rows.append((
                h, limit, duration, algo,
                int(Status.OVER_LIMIT if over else Status.UNDER_LIMIT),
                r_limit, 0 if over else int(rng.integers(0, 5)), r_reset,
            ))
        return rows

    def fields_of(rows, hits=None):
        n = len(rows)
        return dict(
            key_hash=np.array([r[0] for r in rows], np.uint64),
            hits=np.ones(n, np.int64) if hits is None else hits,
            limit=np.array([r[1] for r in rows], np.int64),
            duration=np.array([r[2] for r in rows], np.int64),
            algo=np.array([r[3] for r in rows], np.int32),
        )

    def check_screen():
        assert len(c) == len(model.entries) <= capacity
        n = pool.shape[0]
        limit = np.array([param_of(h)[0] for h in pool.tolist()], np.int64)
        duration = np.array(
            [param_of(h)[1] for h in pool.tolist()], np.int64
        )
        odd = rng.random(n) < 0.05
        limit[odd] += 1
        fields = dict(
            key_hash=pool,
            hits=(rng.random(n) < 0.9).astype(np.int64),
            limit=limit,
            duration=duration,
            algo=rng.choice([0, 0, 0, 0, 0, 1, 3], n).astype(np.int32),
            gnp=rng.random(n) < 0.05,
        )
        want = [
            model.sheds(
                int(pool[i]), int(fields["hits"][i]), int(limit[i]),
                int(duration[i]), int(fields["algo"][i]),
                bool(fields["gnp"][i]), clock.t,
            )
            for i in range(n)
        ]
        got = c.screen_fields(fields)
        if got is None:
            assert not any(w is not None for w in want)
            return
        mask, (status, limit_out, remaining, reset), keep, residue = got
        assert mask.tolist() == [w is not None for w in want]
        assert keep.tolist() == np.flatnonzero(~mask).tolist()
        assert sorted(residue) == sorted(fields)
        for k, col in fields.items():
            assert residue[k].dtype == col.dtype
            assert residue[k].tolist() == col[~mask].tolist()
        assert reset.tolist() == [w or 0 for w in want]
        assert status.tolist() == [
            int(Status.OVER_LIMIT) if w is not None else 0 for w in want
        ]
        assert (limit_out == np.where(mask, limit, 0)).all()
        assert not remaining.any()

    for step in range(2500):
        op = rng.random()
        if op < 0.35:
            rows = random_rows(int(rng.integers(1, 60)))
            c.observe_fields(
                fields_of(rows),
                tuple(
                    np.array([r[k] for r in rows], np.int64)
                    for k in (4, 5, 6, 7)
                ),
            )
            model.observe_rows(rows, clock.t)
        elif op < 0.5:
            rows = random_rows(int(rng.integers(1, 8)))
            reqs = [
                RateLimitReq(name="n", unique_key="k", hits=1,
                             limit=r[1], duration=r[2],
                             algorithm=Algorithm(r[3]))
                for r in rows
            ]
            resps = [
                RateLimitResp(status=Status(r[4]), limit=r[5],
                              remaining=r[6], reset_time=r[7])
                for r in rows
            ]
            c.observe_resps([r[0] for r in rows], reqs, resps)
            for r in rows:
                model.observe_one(*r, clock.t)
        elif op < 0.65:
            for h in rng.choice(pool, 4).tolist():
                limit, duration = param_of(h)
                assert c.lookup(h, limit, duration) == model.lookup(
                    h, limit, duration, clock.t
                )
        elif op < 0.8:
            for h in rng.choice(pool, 3).tolist():
                limit, duration = param_of(h)
                reset = clock.t + int(rng.choice([-1, 0, 400, 9000]))
                c.seed(h, limit, duration, reset)
                model.seed(h, limit, duration, reset, clock.t)
        elif op < 0.9:
            gone = rng.choice(pool, int(rng.integers(1, 10)))
            c.purge(gone)
            for h in gone.tolist():
                model.entries.pop(h, None)
        elif op < 0.98:
            clock.t += int(rng.choice([1, 50, 400, 1000]))
        else:
            wiped = rng.random() < 0.5  # the engine reset its store
            gen[0] += wiped
            c.refresh_generation()
            if wiped:
                model.entries.clear()
        check_screen()
        assert [h for h in model.entries] == list(c._entries)
    assert c.index_rebuilds < c.index_uses
    served = dict(native=c.native_consults, numpy=c.numpy_consults)
    assert served.pop(body) == c.index_uses and served.popitem()[1] == 0


def test_value_changes_never_resort_the_index():
    """Counts, not times: with 4,000 verdicts cached, 1,000 changes of
    VALUE — a new reset_time in place, a confirmation, a drop by a
    contradicting response, by expiry on lookup, by purge — leave the
    sorted index and the overlay as they were, consults in between
    included, and every consult still reads the changed values. Only
    a fingerprint that holds no slot goes to the overlay, and the
    overlay folds once when it is past OVERLAY_MAX."""
    from gubernator_tpu.serve.shedcache import OVERLAY_MAX

    clock = FakeClock()
    c = ShedCache(1 << 16, now_fn=clock)
    rng = np.random.default_rng(7)
    fps = np.unique(rng.integers(0, 1 << 64, size=4000, dtype=np.uint64))
    m = fps.shape[0]
    over = int(Status.OVER_LIMIT)
    for h in fps.tolist():
        c._observe_one(h, 1, 100, 60_000, 0, over, 100, 0,
                       clock.t + 60_000, clock.t)
    fields = dict(
        key_hash=fps,
        hits=np.ones(m, np.int64),
        limit=np.full(m, 100, np.int64),
        duration=np.full(m, 60_000, np.int64),
        algo=np.zeros(m, np.int32),
    )
    assert c.screen_fields(fields)[0].all()  # folds what was inserted
    rebuilds, uses = c.index_rebuilds, c.index_uses
    assert c._new == 0

    want_reset = {h: clock.t + 60_000 for h in fps.tolist()}
    for i in range(1000):
        h = int(fps[(i * 37) % m])
        kind = i % 5
        if kind == 0 and h in want_reset:  # the window moved: in place
            want_reset[h] = clock.t + 70_000 + i
            c._observe_one(h, 1, 100, 60_000, 0, over, 100, 0,
                           want_reset[h], clock.t)
        elif kind == 1 and h in want_reset:  # confirmed
            c._observe_one(h, 1, 100, 60_000, 0, over, 100, 0,
                           want_reset[h], clock.t)
        elif kind == 2:  # contradicted: under limit again
            c._observe_one(h, 1, 100, 60_000, 0,
                           int(Status.UNDER_LIMIT), 100, 7,
                           clock.t + 60_000, clock.t)
            want_reset.pop(h, None)
        elif kind == 3:  # expired on lookup
            c.lookup(h, 100, 60_000, now=clock.t + 10**9)
            want_reset.pop(h, None)
        elif kind == 4:
            c.purge(np.array([h], np.uint64))
            want_reset.pop(h, None)
        if i % 10 == 9:
            got = c.screen_fields(fields)
            assert got[0].tolist() == [h in want_reset for h in fps.tolist()]
            assert got[1][3].tolist() == [
                want_reset.get(h, 0) for h in fps.tolist()
            ]
            c.observe_fields(
                {k: v[:50] for k, v in fields.items()},
                tuple(np.zeros(50, np.int64) for _ in range(4)),
            )  # 50 under-limit rows: cached ones drop, in place
            for h in fps[:50].tolist():
                want_reset.pop(h, None)
    assert len(c) == len(want_reset) < m
    assert c.index_uses == uses + 200
    assert c.index_rebuilds == rebuilds and c._new == 0

    # a fingerprint with no slot waits in the overlay (and sheds from
    # there); one past OVERLAY_MAX folds it, once
    fresh = np.setdiff1d(
        rng.integers(0, 1 << 64, size=OVERLAY_MAX + 8, dtype=np.uint64), fps
    )[: OVERLAY_MAX + 1]
    for h in fresh[:OVERLAY_MAX].tolist():
        c._observe_one(h, 1, 100, 60_000, 0, over, 100, 0,
                       clock.t + 60_000, clock.t)
    probe = dict(fields, key_hash=np.resize(fresh, m))
    assert c.screen_fields(probe)[0][:OVERLAY_MAX].all()
    assert c.index_rebuilds == rebuilds and c._new == OVERLAY_MAX
    c._observe_one(int(fresh[OVERLAY_MAX]), 1, 100, 60_000, 0, over, 100,
                   0, clock.t + 60_000, clock.t)
    assert c.screen_fields(probe)[0].all()
    assert c.screen_fields(probe)[0].all()
    assert c.index_rebuilds == rebuilds + 1 and c._new == 0


# -- the native call against its numpy twin ----------------------------------

#: what each case of the identity fuzz puts in front of the two bodies
TWIN_CASES = [
    "empty_cache", "first_consult", "index_only", "index_and_overlay",
    "rebound_in_overlay", "dropped_slots", "expired_entries", "gnp_rows",
    "leaky_on_cached", "param_mismatch", "duplicate_keys", "one_row",
    "thousand_rows", "past_the_insert_cap",
]


def _twin_state(case, rng, clock):
    """(build, pool): `build(cache)` brings a fresh ShedCache to the
    case's state by the same calls whoever it is handed, `pool` the
    fingerprints it cached (a frame draws its hot rows from them)."""
    from gubernator_tpu.serve.shedcache import OVERLAY_MAX

    over = int(Status.OVER_LIMIT)
    pool = np.unique(rng.integers(0, 1 << 64, size=600, dtype=np.uint64))
    pool[0], pool[-1] = 0, (1 << 64) - 1
    resets = clock.t + rng.integers(200, 90_000, size=pool.shape[0])
    late = rng.integers(0, 1 << 64, size=40, dtype=np.uint64)

    def store(c, h, reset):
        c._observe_one(int(h), 1, 100, 60_000, 0, over, 100, 0,
                       int(reset), clock.t)

    def fold(c):
        probe = dict(
            key_hash=pool[:1], hits=np.ones(1, np.int64),
            limit=np.ones(1, np.int64), duration=np.ones(1, np.int64),
            algo=np.zeros(1, np.int32),
        )
        c.screen_fields(probe)  # a consult: builds or folds the index
        assert c._new == 0 and c._ix_fp.shape[0]

    def build(c):
        if case == "empty_cache":
            return
        for h, reset in zip(pool.tolist(), resets.tolist()):
            store(c, h, reset)
        if case == "first_consult":
            assert not c._ix_fp.shape[0] and c._new > OVERLAY_MAX
            return
        fold(c)
        if case == "index_only":
            return
        for h in late.tolist():
            store(c, h, clock.t + 5000)  # these wait in the overlay
        if case == "rebound_in_overlay":
            # late[0] loses its slot to late[1]'s second binding and is
            # bound again to another: two overlay rows of one
            # fingerprint, the later one in force
            a, b = int(late[0]), int(late[1])
            c.purge([a, b])
            store(c, b, clock.t + 7000)
            store(c, a, clock.t + 9000)
            assert (c._new_fp[:c._new] == np.uint64(a)).sum() == 2
        if case == "dropped_slots":
            c.purge(pool[::3])  # indexed rows whose slots read reset 0
            c.purge(late[::4])

    return build, np.concatenate([pool, late])


def _twin_frame(case, rng, pool, clock):
    """One frame for the case: cached and uncached fingerprints, a few
    rows each of every gate's other side, and the case's own stress."""
    n = {"one_row": 1, "thousand_rows": 1000}.get(
        case, int(rng.integers(2, 400))
    )
    if case == "past_the_insert_cap":
        n = 1000
    cold = rng.integers(0, 1 << 64, size=n, dtype=np.uint64)
    hot_share = 0.1 if case == "past_the_insert_cap" else 0.55
    kh = np.where(rng.random(n) < hot_share, rng.choice(pool, n), cold)
    if case == "duplicate_keys":
        kh = rng.choice(kh[: max(1, n // 8)], n)
    limit = np.full(n, 100, np.int64)
    duration = np.full(n, 60_000, np.int64)
    odds = 0.4 if case == "param_mismatch" else 0.05
    limit[rng.random(n) < odds] += 1
    duration[rng.random(n) < odds] -= 1
    leaky = 0.4 if case == "leaky_on_cached" else 0.08
    algo = np.where(
        rng.random(n) < leaky, rng.integers(1, 4, size=n), 0
    ).astype(np.int32)
    fields = dict(
        key_hash=kh, hits=(rng.random(n) < 0.92).astype(np.int64),
        limit=limit, duration=duration, algo=algo,
    )
    if case == "gnp_rows" or rng.random() < 0.2:
        fields["gnp"] = rng.random(n) < 0.3
    return fields


def _twin_results(case, rng, fields, cache, clock, peer_reply):
    """What a device (int32 status / limit / remaining beside an int64
    reset_time) or a peer's reply (four int64) could say of `fields`:
    frozen and under-limit answers, echoes of the cached window,
    answers under other stored params, expired resets."""
    n = fields["key_hash"].shape[0]
    frozen = rng.random(n) < (0.9 if case == "past_the_insert_cap" else 0.5)
    status = np.where(frozen, int(Status.OVER_LIMIT), 0)
    remaining = np.where(frozen, 0, rng.integers(0, 50, size=n))
    limit = np.where(rng.random(n) < 0.9, fields["limit"], 100)
    reset = clock.t + rng.choice([-5, 1, 300, 800, 70_000], n)
    for i, h in enumerate(fields["key_hash"].tolist()):
        e = cache.get(h)
        if e is not None and rng.random() < 0.4:
            reset[i] = e[2]  # echo the cached window
    small = np.int64 if peer_reply else np.int32
    return (
        status.astype(small), limit.astype(small), remaining.astype(small),
        reset.astype(np.int64),
    )


def _recorded(c, rows):
    """c._observe_one that also appends each call's arguments to rows."""
    def observe_one(*args):
        rows.append(args)
        ShedCache._observe_one(c, *args)

    return observe_one


def _cache_state(c):
    top = c._top
    return dict(
        entries=list(c._entries.items()), free=list(c._free), top=top,
        new=c._new, new_fp=c._new_fp[:c._new].tolist(),
        new_slot=c._new_slot[:c._new].tolist(),
        fp=c._fp[:top].tolist(), lim=c._lim[:top].tolist(),
        dur=c._dur[:top].tolist(), reset=c._reset[:top].tolist(),
        ix_fp=c._ix_fp.tolist(), ix_slot=c._ix_slot.tolist(),
        counters=(c.hits, c.lookups, c.index_uses, c.index_rebuilds),
    )


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
@pytest.mark.parametrize("case", TWIN_CASES)
def test_native_calls_are_their_numpy_twins(monkeypatch, case, seed):
    """guber_shed_screen / guber_shed_observe against _screen_numpy /
    _observe_numpy from ONE cache state each step: the same mask, the
    same four answer columns, the same kept rows and residue columns
    (names, dtypes, values), the same rows walked in the same order
    with the same values, the same stitched answers, the same counters
    and, after the walk, the same entries in the same LRU order in the
    same slots."""
    from gubernator_tpu.serve import shedcache

    if shedcache._hn is None:
        pytest.skip("libguberhash.so is absent: the numpy body has no "
                    "twin to be held to (make -C gubernator_tpu/native)")
    native_lib = shedcache._hn
    rng = np.random.default_rng([seed, TWIN_CASES.index(case)])
    clock = FakeClock()
    build, pool = _twin_state(case, rng, clock)
    caches = {}
    walked = {}
    for body in ("numpy", "native"):
        c = caches[body] = ShedCache(1 << 12, now_fn=clock)
        build(c)
        c.reset_counters()
        walked[body] = []
        c._observe_one = _recorded(c, walked[body])
    assert _cache_state(caches["numpy"]) == _cache_state(caches["native"])

    def on(body, call):
        monkeypatch.setattr(
            shedcache, "_hn", native_lib if body == "native" else None
        )
        return call(caches[body])

    for step in range(6):
        if case == "expired_entries" or step % 3 == 2:
            clock.t += int(rng.choice([1, 250, 5000, 61_000]))
        fields = _twin_frame(case, rng, pool, clock)
        n = fields["key_hash"].shape[0]
        got = {
            body: on(body, lambda c: c.screen_fields(fields))
            for body in ("numpy", "native")
        }
        assert (got["numpy"] is None) == (got["native"] is None)
        if got["numpy"] is None:
            rows, into = {b: fields for b in got}, {b: None for b in got}
        else:
            rows, into = {}, {}
            for body, (mask, answers, keep, residue) in got.items():
                assert mask.dtype == bool and mask.shape == (n,)
                assert keep.dtype == np.int64
                assert all(
                    a.dtype == np.int64 and a.shape == (n,) for a in answers
                )
                rows[body], into[body] = residue, (answers, keep)
            a, b = got["numpy"], got["native"]
            assert a[0].tolist() == b[0].tolist()
            assert a[0].any()
            for col_a, col_b in zip(a[1], b[1]):
                assert col_a.tolist() == col_b.tolist()
            assert a[2].tolist() == b[2].tolist()
            assert list(a[3]) == list(b[3])
            for k in a[3]:
                assert a[3][k].dtype == b[3][k].dtype == fields[k].dtype
                assert a[3][k].tolist() == b[3][k].tolist()
        assert _cache_state(caches["numpy"]) == _cache_state(caches["native"])

        if rows["numpy"]["key_hash"].shape[0]:
            results = _twin_results(
                case, rng, rows["numpy"], caches["numpy"], clock,
                peer_reply=bool(step % 2),
            )
            for body in ("numpy", "native"):
                on(body, lambda c: c.observe_fields(
                    rows[body], results, into=into[body]
                ))
            assert walked["numpy"] == walked["native"]
            if case == "past_the_insert_cap":
                assert len(walked["numpy"]) >= shedcache.OBSERVE_INSERT_CAP
            for body in walked:
                walked[body].clear()
            if into["numpy"] is not None:
                for col_a, col_b in zip(into["numpy"][0], into["native"][0]):
                    assert col_a.tolist() == col_b.tolist()
        assert _cache_state(caches["numpy"]) == _cache_state(caches["native"])
    c = caches["native"]
    assert c.native_consults == c.index_uses and c.numpy_consults == 0
    c = caches["numpy"]
    assert c.numpy_consults == c.index_uses and c.native_consults == 0
    if case != "empty_cache":
        assert c.index_uses and c.hits


@pytest.mark.parametrize("body", ["native", "numpy"])
@pytest.mark.parametrize("seed", [5, 6, 7])
def test_rows_left_out_of_the_walk_would_have_changed_nothing(
    monkeypatch, seed, body
):
    """observe_fields leaves a frozen row of another algorithm out of
    the walk where its fingerprint holds no slot and no token-bucket
    row of the call could give it one. Walking EVERY frozen row of an
    uncached fingerprint, as the cache did before, ends in the same
    entries, in the same order, in the same slots — under frames where
    a key's rows switch algorithm mid-frame, on both sides of a token
    row that stores it."""
    from gubernator_tpu.serve import shedcache

    _run_body(monkeypatch, body)
    monkeypatch.setattr(shedcache, "OBSERVE_INSERT_CAP", 10_000)
    rng = np.random.default_rng(seed)
    clock = FakeClock()
    over = int(Status.OVER_LIMIT)
    pool = rng.integers(0, 1 << 64, size=120, dtype=np.uint64)
    new, old = ShedCache(256, now_fn=clock), ShedCache(256, now_fn=clock)
    skipped = 0
    for step in range(40):
        clock.t += int(rng.choice([0, 1, 400, 1100]))
        n = int(rng.integers(1, 300))
        kh = rng.choice(pool, n)
        algo = np.where(
            rng.random(n) < 0.45, rng.integers(1, 4, size=n), 0
        ).astype(np.int32)
        fields = dict(
            key_hash=kh, hits=np.ones(n, np.int64),
            limit=np.full(n, 100, np.int64),
            duration=np.full(n, 1000, np.int64), algo=algo,
        )
        frozen = rng.random(n) < 0.7
        results = (
            np.where(frozen, over, 0).astype(np.int32),
            np.full(n, 100, np.int32),
            np.where(frozen, 0, 7).astype(np.int32),
            clock.t + rng.choice([-3, 500, 1000], n),
        )
        # the old rule, by hand: the cached rows, then every frozen row
        # of an uncached fingerprint, each in row order
        cached = np.array([int(h) in old for h in kh.tolist()])
        rows = np.concatenate([
            np.flatnonzero(cached), np.flatnonzero(frozen & ~cached)
        ])
        for i in rows.tolist():
            old._observe_one(
                int(kh[i]), 1, 100, 1000, int(algo[i]),
                *(int(c[i]) for c in results), clock.t,
            )
        walked = []
        new._observe_one = _recorded(new, walked)
        new.observe_fields(fields, results)
        skipped += rows.shape[0] - len(walked)
        # (the cache walked by hand never consulted its index)
        held = ("entries", "free", "top", "fp", "lim", "dur", "reset")
        a, b = _cache_state(new), _cache_state(old)
        assert {k: a[k] for k in held} == {k: b[k] for k in held}
    assert skipped > 100  # the rule left rows out, and it did not matter


def test_a_stitch_outside_the_answers_is_refused():
    """Both bodies refuse to write a result past the answer columns,
    and write nothing."""
    from gubernator_tpu.serve import shedcache

    fields = dict(key_hash=np.array([1, 2], np.uint64))
    results = tuple(np.full(2, 7, np.int64) for _ in range(4))
    bodies = [None] if shedcache._hn is None else [shedcache._hn, None]
    for lib in bodies:
        answers = tuple(np.zeros(2, np.int64) for _ in range(4))
        c = ShedCache(8)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(shedcache, "_hn", lib)
            with pytest.raises(IndexError):
                c.observe_fields(
                    fields, results,
                    into=(answers, np.array([0, 2], np.int64)),
                )
        if lib is not None:
            assert not any(col.any() for col in answers)


# -- instance harness -------------------------------------------------------


async def _mk_instance(backend, shed: bool) -> Instance:
    conf = ServerConfig(
        grpc_address=ADDR, advertise_address=ADDR, shed_cache=shed
    )
    inst = Instance(conf, backend)
    inst.start()
    await inst.set_peers([PeerInfo(address=ADDR, is_owner=True)])
    return inst


def _pin_clock(monkeypatch, clock):
    """Route every now() the serving pipeline reads through the fake
    clock: the oracle (exact backend), the engine module (device
    backends' module-level import), and api.types (the backends'
    call-time local imports)."""
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod
    import gubernator_tpu.core.oracle as oracle_mod

    monkeypatch.setattr(types_mod, "millisecond_now", clock)
    monkeypatch.setattr(engine_mod, "millisecond_now", clock)
    monkeypatch.setattr(oracle_mod, "millisecond_now", clock)


def _assert_same(a, b, ctx):
    assert (
        a.status, a.limit, a.remaining, a.reset_time, a.error
    ) == (
        b.status, b.limit, b.remaining, b.reset_time, b.error
    ), (ctx, a, b)


def _fuzz_stream(rng, keys, steps):
    """Random request batches: mixed algorithms (pinned per key so the
    streams stay meaningful), duplicate keys, peeks, oversized hits,
    mid-window limit/duration changes, clock advances across resets."""
    for step in range(steps):
        n = int(rng.integers(1, 7))
        batch = []
        for _ in range(n):
            k = int(rng.integers(len(keys)))
            batch.append(
                RateLimitReq(
                    name="shedfuzz",
                    unique_key=keys[k],
                    hits=int(rng.choice([0, 1, 1, 1, 2, 9])),
                    limit=int(rng.choice([1, 1, 2, 3, 50])),
                    duration=int(rng.choice([400, 2000, 60_000])),
                    algorithm=Algorithm(k % 2),
                )
            )
        yield step, batch, int(rng.choice([0, 0, 1, 7, 150, 500, 2500]))


@pytest.mark.parametrize("seed", [3, 11])
def test_differential_identity_fuzz_exact(monkeypatch, seed):
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    async def run():
        on = await _mk_instance(ExactBackend(10_000), shed=True)
        off = await _mk_instance(ExactBackend(10_000), shed=False)
        on.shed.now_fn = clock
        try:
            rng = np.random.default_rng(seed)
            keys = [f"k{i}" for i in range(14)]
            for step, batch, dt in _fuzz_stream(rng, keys, 350):
                clock.t += dt
                a = await on.get_rate_limits(batch)
                b = await off.get_rate_limits(batch)
                for x, y, r in zip(a, b, batch):
                    _assert_same(x, y, (step, r))
            assert on.shed.hits > 0, "fuzz never exercised a shed"
        finally:
            await on.stop()
            await off.stop()

    asyncio.run(run())


def test_differential_identity_fuzz_device(monkeypatch):
    """Same identity contract across the DEVICE pipeline (tpu backend
    on cpu): instance -> batcher -> arrival prep -> merged submit ->
    kernel, shed ON vs OFF."""
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    def be():
        return TpuBackend(
            StoreConfig(rows=16, slots=1 << 10), buckets=(16, 64)
        )

    async def run():
        on = await _mk_instance(be(), shed=True)
        off = await _mk_instance(be(), shed=False)
        on.shed.now_fn = clock
        try:
            rng = np.random.default_rng(5)
            keys = [f"d{i}" for i in range(12)]
            for step, batch, dt in _fuzz_stream(rng, keys, 120):
                clock.t += dt
                a = await on.get_rate_limits(batch)
                b = await off.get_rate_limits(batch)
                for x, y, r in zip(a, b, batch):
                    _assert_same(x, y, (step, r))
            assert on.shed.hits > 0, "fuzz never exercised a shed"
        finally:
            await on.stop()
            await off.stop()

    asyncio.run(run())


def test_peek_bypass_and_post_reset_hit_reaches_device(monkeypatch):
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    async def run():
        inst = await _mk_instance(ExactBackend(1000), shed=True)
        inst.shed.now_fn = clock
        try:
            def req(hits=1):
                return RateLimitReq(
                    name="pb", unique_key="x", hits=hits, limit=1,
                    duration=1000,
                )

            r1 = (await inst.get_rate_limits([req()]))[0]
            assert r1.status == Status.UNDER_LIMIT  # creation, rem 0
            r2 = (await inst.get_rate_limits([req()]))[0]
            assert r2.status == Status.OVER_LIMIT  # frozen; now cached
            assert len(inst.shed) == 1
            r3 = (await inst.get_rate_limits([req()]))[0]
            assert inst.shed.hits == 1  # shed
            _assert_same(r2, r3, "frozen verdict")
            # a peek bypasses the shed but gets the same frozen answer
            lk = inst.shed.lookups
            r4 = (await inst.get_rate_limits([req(hits=0)]))[0]
            assert inst.shed.lookups == lk
            _assert_same(r2, r4, "peek")
            # cross the reset boundary: the next hit must reach the
            # device and recreate the window there
            clock.t = r2.reset_time + 1
            hits_before = inst.shed.hits
            r5 = (await inst.get_rate_limits([req()]))[0]
            assert inst.shed.hits == hits_before  # not shed
            assert r5.status == Status.UNDER_LIMIT  # fresh window
            assert r5.reset_time == clock.t + 1000
        finally:
            await inst.stop()

    asyncio.run(run())


def test_global_update_purges_cached_verdict(monkeypatch):
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    async def run():
        on = await _mk_instance(ExactBackend(1000), shed=True)
        off = await _mk_instance(ExactBackend(1000), shed=False)
        on.shed.now_fn = clock
        try:
            def req():
                return RateLimitReq(
                    name="g", unique_key="y", hits=1, limit=1,
                    duration=60_000,
                )

            for inst in (on, off):
                await inst.get_rate_limits([req(), req()])
            assert len(on.shed) == 1
            # owner-side reset arrives as a replica install: the shed
            # entry must die with it, or GLOBAL mode would keep
            # serving the stale refusal
            key = req().hash_key()

            def fresh():
                # one object per install: the exact backend stores the
                # replica object itself and mutates it in place
                return RateLimitResp(
                    status=Status.UNDER_LIMIT, limit=1, remaining=1,
                    reset_time=clock.t + 60_000,
                )

            for inst in (on, off):
                await inst.update_peer_globals([(key, fresh())])
            assert len(on.shed) == 0
            a = (await on.get_rate_limits([req()]))[0]
            b = (await off.get_rate_limits([req()]))[0]
            _assert_same(a, b, "post-install identity")
        finally:
            await on.stop()
            await off.stop()

    asyncio.run(run())


def test_owned_global_shed_preserves_broadcast_side_effect(monkeypatch):
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    async def run():
        inst = await _mk_instance(ExactBackend(1000), shed=True)
        inst.shed.now_fn = clock
        queued = []
        inst.global_mgr.queue_update = lambda r: queued.append(
            r.hash_key()
        )
        try:
            def req():
                return RateLimitReq(
                    name="gb", unique_key="z", hits=1, limit=1,
                    duration=60_000, behavior=Behavior.GLOBAL,
                )

            await inst.get_rate_limits([req(), req()])
            n_before = len(queued)
            assert n_before > 0
            r = (await inst.get_rate_limits([req()]))[0]
            assert inst.shed.hits >= 1 and r.status == Status.OVER_LIMIT
            # the shed answer still queued the owner's status broadcast
            assert len(queued) == n_before + 1
        finally:
            await inst.stop()

    asyncio.run(run())


def test_peer_serve_screen_identity(monkeypatch):
    """Owner-side forwarded batches (get_peer_rate_limits) screen the
    same cache: identity with the unscreened pipeline, shed hits
    recorded."""
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    async def run():
        on = await _mk_instance(ExactBackend(1000), shed=True)
        off = await _mk_instance(ExactBackend(1000), shed=False)
        on.shed.now_fn = clock
        try:
            reqs = [
                RateLimitReq(name="ps", unique_key="w", hits=1,
                             limit=1, duration=60_000)
                for _ in range(3)
            ]
            for inst in (on, off):
                await inst.get_peer_rate_limits(reqs)
            a = await on.get_peer_rate_limits(reqs)
            b = await off.get_peer_rate_limits(reqs)
            for x, y in zip(a, b):
                _assert_same(x, y, "peer serve")
            assert on.shed.hits >= 3
        finally:
            await on.stop()
            await off.stop()

    asyncio.run(run())


# -- bridge tier ------------------------------------------------------------


def _wfast(fid, rec, ring_hash):
    from gubernator_tpu.serve.edge_bridge import MAGIC_WFAST_REQ

    payload = rec.tobytes()
    return (
        struct.pack("<II", MAGIC_WFAST_REQ, len(rec))
        + struct.pack("<IIQ", fid, ring_hash, 0)
        + struct.pack("<I", len(payload))
        + payload
    )


async def _read_wfast_resp(reader):
    from gubernator_tpu.serve.edge_bridge import (
        MAGIC_WFAST_RESP,
        _fast_dtypes,
    )

    magic, n = struct.unpack("<II", await reader.readexactly(8))
    assert magic == MAGIC_WFAST_RESP, hex(magic)
    (fid,) = struct.unpack("<I", await reader.readexactly(4))
    _, resp_dt = _fast_dtypes()
    rec = np.frombuffer(
        await reader.readexactly(n * resp_dt.itemsize), dtype=resp_dt
    )
    return fid, rec


def test_bridge_tier_shed_windowed_frames():
    """GEB7 frames screen the shed cache before the batcher: a frame of
    frozen refusals is answered without a device trip, mixed frames
    stitch shed + device rows in order, multiple frames stay in flight,
    and the `shed` stage appears in the clock."""
    from gubernator_tpu.serve.edge_bridge import EdgeBridge
    from gubernator_tpu.serve.stages import STAGES

    path = "/tmp/guber-shed-bridge-test.sock"

    async def run():
        backend = TpuBackend(
            StoreConfig(rows=16, slots=1 << 10), buckets=(16, 64)
        )
        inst = await _mk_instance(backend, shed=True)
        bridge = EdgeBridge(inst, path)
        await bridge.start()
        try:
            from tests.test_edge_bridge import _read_hello

            reader, writer = await asyncio.open_unix_connection(path)
            _flags, rhash, _nodes = await _read_hello(reader)

            from gubernator_tpu.serve.edge_bridge import _fast_dtypes

            req_dt, _ = _fast_dtypes()

            def recs(key_hashes, limit=1):
                rec = np.zeros(len(key_hashes), req_dt)
                rec["key_hash"] = key_hashes
                rec["hits"] = 1
                rec["limit"] = limit
                rec["duration"] = 60_000
                return rec

            # frame 1: duplicate key drains the window; follower rows
            # come back (OVER, remaining 0) and populate the cache
            writer.write(_wfast(1, recs([7, 7, 7, 7]), rhash))
            await writer.drain()
            fid, rec = await asyncio.wait_for(_read_wfast_resp(reader), 30)
            assert fid == 1
            assert rec["status"].tolist() == [0, 1, 1, 1]
            frozen_reset = int(rec["reset_time"][3])
            assert len(inst.shed) == 1

            # frame 2: fully shed — no device batch happens
            batches_before = backend.stats()["batches"]
            hits_before = inst.shed.hits
            writer.write(_wfast(2, recs([7, 7, 7]), rhash))
            await writer.drain()
            fid, rec = await asyncio.wait_for(_read_wfast_resp(reader), 30)
            assert fid == 2
            assert rec["status"].tolist() == [1, 1, 1]
            assert rec["remaining"].tolist() == [0, 0, 0]
            assert rec["reset_time"].tolist() == [frozen_reset] * 3
            assert inst.shed.hits == hits_before + 3
            assert backend.stats()["batches"] == batches_before

            # frame 3: mixed shed + residue rows stitch back in order
            writer.write(_wfast(3, recs([7, 8, 7, 8]), rhash))
            await writer.drain()
            fid, rec = await asyncio.wait_for(_read_wfast_resp(reader), 30)
            assert fid == 3
            # key 7 rows frozen; key 8 rows are a fresh creation group
            # (leader UNDER rem 0, follower OVER rem 0)
            assert rec["status"].tolist() == [1, 0, 1, 1]
            assert rec["reset_time"][0] == frozen_reset
            assert rec["reset_time"][2] == frozen_reset
            assert backend.stats()["batches"] == batches_before + 1

            # two frames in flight, fully shed: ids match out of the
            # window regardless of completion order
            writer.write(_wfast(4, recs([7, 7]), rhash))
            writer.write(_wfast(5, recs([8, 8]), rhash))
            await writer.drain()
            got = {}
            for _ in range(2):
                fid, rec = await asyncio.wait_for(
                    _read_wfast_resp(reader), 30
                )
                got[fid] = rec["status"].tolist()
            assert got[4] == [1, 1] and got[5] == [1, 1]

            snap = STAGES.snapshot()
            assert "shed" in snap["stages"]
            assert "shed" in snap["per_frame_stages"]
            writer.close()
        finally:
            await bridge.stop()
            await inst.stop()

    asyncio.run(run())


def test_bridge_string_frame_fold_shed():
    """The GEB1 string fold rides the same screen: the second frame for
    a frozen key sheds, and the response stays a well-formed GEB3."""
    from gubernator_tpu.serve.edge_bridge import MAGIC_RESP, EdgeBridge

    path = "/tmp/guber-shed-fold-test.sock"

    async def run():
        backend = TpuBackend(
            StoreConfig(rows=16, slots=1 << 10), buckets=(16, 64)
        )
        inst = await _mk_instance(backend, shed=True)
        bridge = EdgeBridge(inst, path)
        await bridge.start()
        try:
            from tests.test_edge_bridge import (
                _frame,
                _item,
                _read_hello,
            )

            reader, writer = await asyncio.open_unix_connection(path)
            await _read_hello(reader)

            async def roundtrip():
                writer.write(_frame([
                    _item(b"fold", b"hot", hits=1, limit=1,
                          duration=60_000),
                    _item(b"fold", b"hot", hits=1, limit=1,
                          duration=60_000),
                ]))
                await writer.drain()
                magic, n = struct.unpack(
                    "<II", await reader.readexactly(8)
                )
                assert magic == MAGIC_RESP and n == 2
                out = []
                for _ in range(n):
                    status, limit, remaining, reset = struct.unpack(
                        "<Bqqq", await reader.readexactly(25)
                    )
                    (elen,) = struct.unpack(
                        "<H", await reader.readexactly(2)
                    )
                    await reader.readexactly(elen)
                    (olen,) = struct.unpack(
                        "<H", await reader.readexactly(2)
                    )
                    await reader.readexactly(olen)
                    out.append((status, limit, remaining, reset))
                return out

            first = await asyncio.wait_for(roundtrip(), 30)
            assert [s for s, *_ in first] == [0, 1]
            assert len(inst.shed) == 1
            hits = inst.shed.hits
            second = await asyncio.wait_for(roundtrip(), 30)
            assert [s for s, *_ in second] == [1, 1]
            assert [r for *_, r in second] == [first[1][3]] * 2
            assert inst.shed.hits == hits + 2
            writer.close()
        finally:
            await bridge.stop()
            await inst.stop()

    asyncio.run(run())


def test_engine_reset_generation_clears_instance_cache(monkeypatch):
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    async def run():
        backend = TpuBackend(
            StoreConfig(rows=16, slots=1 << 10), buckets=(16, 64)
        )
        inst = await _mk_instance(backend, shed=True)
        inst.shed.now_fn = clock
        try:
            def req():
                return RateLimitReq(
                    name="rg", unique_key="q", hits=1, limit=1,
                    duration=60_000,
                )

            await inst.get_rate_limits([req(), req()])
            assert len(inst.shed) == 1
            backend.engine.reset()  # store wiped (clock-jump path)
            r = (await inst.get_rate_limits([req()]))[0]
            # fresh store: the request recreated the window instead of
            # being answered from a stale cached refusal
            assert r.status == Status.UNDER_LIMIT
        finally:
            await inst.stop()

    asyncio.run(run())
