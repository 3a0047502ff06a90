"""Functional tests against a real in-process cluster.

The port of the reference's integration suite (reference
functional_test.go): a 6-node cluster on localhost gRPC sockets in one
process (discovery bypassed, static full-mesh peers), driven through real
clients — including the GLOBAL stale-then-synced convergence contract.
Nodes here run the TPU backend (slot store + decide kernel) so the whole
flagship path gRPC -> batcher -> device kernel is exercised end to end.
"""

import time

import grpc
import pytest

from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    Status,
    MILLISECOND,
    SECOND,
)
from gubernator_tpu.client import V1Client
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core.hashing import ring_hash
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.serve import metrics
from gubernator_tpu.serve.backends import TpuBackend

ADDRESSES = [f"127.0.0.1:{p}" for p in range(19990, 19996)]


def _tpu_backend():
    return TpuBackend(
        StoreConfig(rows=4, slots=1 << 12), buckets=(64, 256, 1024)
    )


@pytest.fixture(scope="module")
def cluster():
    c = LocalCluster(ADDRESSES, backend_factory=_tpu_backend)
    c.start()
    yield c
    c.stop()


def _hist_count(h) -> float:
    """Observation count of a prometheus Histogram."""
    for metric in h.collect():
        for s in metric.samples:
            if s.name.endswith("_count"):
                return s.value
    return 0.0


def owner_index(key: str, addresses=None) -> int:
    """Which cluster node owns this ring key (hash.go successor rule)."""
    addresses = ADDRESSES if addresses is None else addresses
    points = sorted((ring_hash(a), a) for a in addresses)
    h = ring_hash(key)
    for point, addr in points:
        if point >= h:
            return addresses.index(addr)
    return addresses.index(points[0][1])


def test_health_check(cluster):
    with V1Client(cluster.get_peer()) as client:
        h = client.health_check(timeout=5)
    assert h.status == "healthy"
    assert h.peer_count == 6


def test_over_the_limit(cluster):
    # reference functional_test.go:51-95
    with V1Client(cluster.get_peer()) as client:
        expects = [
            (1, Status.UNDER_LIMIT),
            (0, Status.UNDER_LIMIT),
            (0, Status.OVER_LIMIT),
        ]
        for remaining, status in expects:
            resp = client.get_rate_limits(
                [
                    RateLimitReq(
                        name="test_over_limit",
                        unique_key="account:1234",
                        algorithm=Algorithm.TOKEN_BUCKET,
                        duration=SECOND,
                        limit=2,
                        hits=1,
                    )
                ],
                timeout=10,
            )
            rl = resp[0]
            assert rl.error == ""
            assert rl.status == status
            assert rl.remaining == remaining
            assert rl.limit == 2
            assert rl.reset_time != 0


def test_token_bucket_window_reset(cluster):
    # reference functional_test.go:97-146. A 500 ms window: the two
    # hits must land inside ONE window of the wall clock, and at the
    # reference's 5 ms (or 25 ms here until PR 41) a client thread
    # preempted between them under six xdist workers saw the second
    # hit open a new window (remaining 1 again: the driver's one
    # failure at PR 40) — the treatment test_leaky_bucket_drain got.
    # What is asserted is the same: 1 -> 0 inside the window, then a
    # sleep past its end and a fresh window (1 again, reset_time set).
    window = 500 * MILLISECOND
    with V1Client(cluster.get_peer()) as client:
        def hit():
            return client.get_rate_limits(
                [
                    RateLimitReq(
                        name="test_token_bucket",
                        unique_key="account:1234",
                        algorithm=Algorithm.TOKEN_BUCKET,
                        duration=window,
                        limit=2,
                        hits=1,
                    )
                ],
                timeout=10,
            )[0]

        rl = hit()
        assert (rl.remaining, rl.status) == (1, Status.UNDER_LIMIT)
        first_reset = rl.reset_time
        rl = hit()
        assert (rl.remaining, rl.status) == (0, Status.UNDER_LIMIT)
        assert rl.reset_time == first_reset  # the same window
        time.sleep(window / 1000 + 0.1)  # past its end
        rl = hit()
        assert (rl.remaining, rl.status) == (1, Status.UNDER_LIMIT)
        assert rl.reset_time != 0 and rl.reset_time > first_reset


def test_leaky_bucket_drain(cluster):
    # reference functional_test.go:148-206. Token period 400ms: the
    # assertions tolerate ±~350ms of scheduling delay between hits —
    # at the reference's 10ms (or r2's 40ms) period this test flaked
    # under full-suite load on a one-core box, where a preempted client
    # thread lets an extra token leak between two hits.
    with V1Client(cluster.get_peer()) as client:
        def hit(hits):
            return client.get_rate_limits(
                [
                    RateLimitReq(
                        name="test_leaky_bucket",
                        unique_key="account:1234",
                        algorithm=Algorithm.LEAKY_BUCKET,
                        duration=2000 * MILLISECOND,  # rate = 400ms/token
                        limit=5,
                        hits=hits,
                    )
                ],
                timeout=10,
            )[0]

        rl = hit(5)
        assert (rl.status, rl.remaining) == (Status.UNDER_LIMIT, 0)
        rl = hit(1)
        assert (rl.status, rl.remaining) == (Status.OVER_LIMIT, 0)
        time.sleep(0.45)  # one token leaks back
        rl = hit(1)
        assert (rl.status, rl.remaining) == (Status.UNDER_LIMIT, 0)
        time.sleep(0.85)  # two more
        rl = hit(1)
        assert (rl.status, rl.remaining) == (Status.UNDER_LIMIT, 1)


def test_missing_fields(cluster):
    # reference functional_test.go:208-269
    cases = [
        (
            RateLimitReq(
                name="test_missing_fields",
                unique_key="account:1234",
                hits=1,
                limit=10,
                duration=0,
            ),
            "",
            Status.UNDER_LIMIT,
        ),
        (
            RateLimitReq(
                name="test_missing_fields",
                unique_key="account:12345",
                hits=1,
                duration=10_000,
                limit=0,
            ),
            "",
            Status.OVER_LIMIT,
        ),
        (
            RateLimitReq(
                unique_key="account:1234", hits=1, duration=10_000, limit=5
            ),
            "field 'namespace' cannot be empty",
            Status.UNDER_LIMIT,
        ),
        (
            RateLimitReq(
                name="test_missing_fields", hits=1, duration=10_000, limit=5
            ),
            "field 'unique_key' cannot be empty",
            Status.UNDER_LIMIT,
        ),
    ]
    with V1Client(cluster.get_peer()) as client:
        for i, (req, want_err, want_status) in enumerate(cases):
            rl = client.get_rate_limits([req], timeout=10)[0]
            assert rl.error == want_err, i
            assert rl.status == want_status, i


def test_batch_too_large(cluster):
    with V1Client(cluster.get_peer()) as client:
        reqs = [
            RateLimitReq(
                name="too_big", unique_key=f"k{i}", hits=1, limit=10,
                duration=1000,
            )
            for i in range(1001)
        ]
        with pytest.raises(grpc.RpcError) as exc:
            client.get_rate_limits(reqs, timeout=10)
        assert exc.value.code() == grpc.StatusCode.OUT_OF_RANGE


def test_forwarding_sets_owner_metadata(cluster):
    # pick a key NOT owned by node 0, send it to node 0, expect the
    # response to name the owner (gubernator.go:151)
    key = next(
        f"account:{i}"
        for i in range(1000)
        if owner_index("test_forward_" + f"account:{i}") != 0
    )
    own = owner_index("test_forward_" + key)
    with V1Client(cluster.peer_at(0)) as client:
        rl = client.get_rate_limits(
            [
                RateLimitReq(
                    name="test_forward",
                    unique_key=key,
                    hits=1,
                    limit=10,
                    duration=SECOND,
                    behavior=Behavior.BATCHING,
                )
            ],
            timeout=10,
        )[0]
    assert rl.error == ""
    assert rl.remaining == 9
    assert rl.metadata.get("owner") == ADDRESSES[own]


def test_no_batching_forwarding(cluster):
    key = next(
        f"account:{i}"
        for i in range(1000)
        if owner_index("test_nobatch_" + f"account:{i}") != 0
    )
    with V1Client(cluster.peer_at(0)) as client:
        rl = client.get_rate_limits(
            [
                RateLimitReq(
                    name="test_nobatch",
                    unique_key=key,
                    hits=1,
                    limit=10,
                    duration=SECOND,
                    behavior=Behavior.NO_BATCHING,
                )
            ],
            timeout=10,
        )[0]
    assert rl.error == ""
    assert rl.remaining == 9


def test_global_rate_limits(cluster):
    # reference functional_test.go:271-331: connect to a non-owner, first
    # two hits see the same stale remaining from the local replica (created
    # on first miss), after the gossip interval the third hit sees the
    # owner's accurate count.
    key = next(
        f"account:{i}"
        for i in range(1000)
        if owner_index("test_global_" + f"account:{i}") != 0
    )

    async_before = _hist_count(metrics.GLOBAL_ASYNC_DURATIONS)
    bcast_before = _hist_count(metrics.GLOBAL_BROADCAST_DURATIONS)

    with V1Client(cluster.peer_at(0)) as client:
        def send_hit(status, remaining):
            rl = client.get_rate_limits(
                [
                    RateLimitReq(
                        name="test_global",
                        unique_key=key,
                        algorithm=Algorithm.TOKEN_BUCKET,
                        behavior=Behavior.GLOBAL,
                        duration=3 * SECOND,
                        hits=1,
                        limit=5,
                    )
                ],
                timeout=10,
            )[0]
            assert rl.error == ""
            assert rl.status == status
            assert rl.remaining == remaining
            assert rl.limit == 5

        # first hit misses the replica: processed locally (remaining 4) and
        # the hit is queued to the owner
        send_hit(Status.UNDER_LIMIT, 4)
        # second hit reads the same locally-created entry, still 4 because
        # replica reads don't charge
        send_hit(Status.UNDER_LIMIT, 4)
        # after gossip: owner has absorbed 2 async hits (3 remaining), its
        # broadcast overwrote our local replica
        time.sleep(0.5)
        send_hit(Status.UNDER_LIMIT, 3)

    # the gossip loops actually ran (adaptation of the reference's
    # per-instance histogram asserts; metrics are process-global here).
    # The replica update lands mid-broadcast while observe() fires at the
    # end of the peer loop, so poll briefly.
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:
        if (
            _hist_count(metrics.GLOBAL_ASYNC_DURATIONS) > async_before
            and _hist_count(metrics.GLOBAL_BROADCAST_DURATIONS) > bcast_before
        ):
            break
        time.sleep(0.05)
    assert _hist_count(metrics.GLOBAL_ASYNC_DURATIONS) > async_before
    assert _hist_count(metrics.GLOBAL_BROADCAST_DURATIONS) > bcast_before


def test_traffic_stats_observability(cluster):
    """Every served request feeds the HLL + heavy-hitter sketches
    (core/sketches.py; surfaced at /v1/debug/stats)."""
    client = V1Client(cluster.peer_at(0))
    for i in range(5):
        client.get_rate_limits(
            [
                RateLimitReq(
                    name="test_traffic",
                    unique_key="hot" if i % 2 == 0 else f"cold{i}",
                    hits=1,
                    limit=100,
                    duration=10 * SECOND,
                )
            ]
        )
    snap = cluster.instance_at(0).traffic.snapshot()
    assert snap["observed_total"] >= 5
    keys = {h["key"] for h in snap["hot_keys"]}
    assert "test_traffic_hot" in keys
    assert snap["distinct_keys_estimate"] >= 2


def test_health_unhealthy_on_bad_peer(cluster):
    """A peer that cannot even be dialed (malformed address) makes the
    node report unhealthy with the failed peer named, and recovers once
    the peer list is fixed (reference gubernator.go:260-291)."""
    from gubernator_tpu.api.types import PeerInfo

    server = cluster.servers[0]
    inst = server.instance
    good = [
        PeerInfo(address=a, is_owner=(a == ADDRESSES[0]))
        for a in ADDRESSES
    ]
    bad = good + [PeerInfo(address="not-an-address:-1")]

    try:
        cluster.run(inst.set_peers(bad))
        h = inst.health_check()
        assert h.status == "unhealthy"
        assert "not-an-address:-1" in h.message
        # healthy peers still serve
        assert h.peer_count == len(ADDRESSES)
    finally:
        # restore for any test running after this one (module-scoped
        # cluster fixture)
        cluster.run(inst.set_peers(good))
    assert inst.health_check().status == "healthy"


def test_device_and_cache_metrics_observed(cluster):
    """The serving flusher must populate device_batch_size,
    device_launch_milliseconds and cache_access_count{hit|miss} — the
    reference exports cache hit/miss counts on every access
    (cache/lru.go:164-176), and r1 shipped these declared-but-dead
    (VERDICT weak #4/#5)."""
    batch_before = _hist_count(metrics.DEVICE_BATCH_SIZE)
    launch_before = _hist_count(metrics.DEVICE_LAUNCH_MS)

    def counter(label):
        for m in metrics.CACHE_ACCESS_COUNT.collect():
            for s in m.samples:
                if s.name.endswith("_total") and s.labels.get("type") == label:
                    return s.value
        return 0.0

    miss_before = counter("miss")
    hit_before = counter("hit")

    with V1Client(cluster.peer_at(0)) as client:
        req = RateLimitReq(
            name="test_metrics", unique_key="m1", hits=1, limit=10,
            duration=SECOND,
        )
        client.get_rate_limits([req])  # miss (creation)
        client.get_rate_limits([req])  # hit

    assert _hist_count(metrics.DEVICE_BATCH_SIZE) > batch_before
    assert _hist_count(metrics.DEVICE_LAUNCH_MS) > launch_before
    assert counter("miss") > miss_before
    assert counter("hit") > hit_before
    # the generic interceptor must have metered the RPCs by full method
    # name (reference prometheus.go:104-127 meters every method)
    found = {
        s.labels["method"]
        for m in metrics.GRPC_REQUEST_COUNTS.collect()
        for s in m.samples
        if s.name.endswith("_total") and s.value > 0
    }
    assert "/pb.gubernator.V1/GetRateLimits" in found, found


def test_dead_owner_forward_fails_per_item():
    """A forwarded request whose owner peer has DIED (accepted into the
    ring at set_peers time, gone at RPC time) must come back as a
    per-item error response; co-batched keys owned by live nodes decide
    normally. The reference fans a batch send error back to every
    waiting request the same way (peers.go:183-195)."""
    from _util import free_ports

    addresses = [f"127.0.0.1:{p}" for p in free_ports(3)]
    c = LocalCluster(addresses)  # exact backend: fast start/stop
    c.start()
    try:
        def owned_by(node_i):
            for n in range(10_000):
                k = f"deadfwd_{n}"
                hk = RateLimitReq(name="dead", unique_key=k).hash_key()
                if owner_index(hk, addresses) == node_i:
                    return k
            raise AssertionError("no key found")

        dead_key = owned_by(2)
        live_key = owned_by(0)
        # kill node 2's server; nodes 0/1 still list it as a peer
        c.run(c.servers[2].stop())

        with V1Client(c.peer_at(0)) as client:
            resps = client.get_rate_limits(
                [
                    RateLimitReq(name="dead", unique_key=dead_key,
                                 hits=1, limit=5, duration=SECOND),
                    RateLimitReq(name="dead", unique_key=live_key,
                                 hits=1, limit=5, duration=SECOND),
                ],
                timeout=15,
            )
        assert "while fetching rate limit" in resps[0].error, resps[0]
        assert resps[1].error == ""
        assert resps[1].status == Status.UNDER_LIMIT
        assert resps[1].remaining == 4
    finally:
        c.stop()
