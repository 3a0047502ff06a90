"""Client-side per-owner fast routing (r18): sharding, GEBR healing,
downgrade accounting, decision identity, and the rolling-membership
soak.

The router moves the compiled edge's placement logic into
client_geb._RingRouter: crc32 ring points over the hello's membership,
fast-eligible items sharded per owner across per-node GEB connections,
every child pinned to the ROUTER's ring fingerprint so a moved ring
refuses with GEBR (never silently serves a mis-routed frame), and the
refusal heals by re-fetching the hello and retrying the refused shards
only.

- sharding + healing against fake listeners with deterministic
  10.99.* ring addresses: exact per-node item counts from an
  independent crc32 mirror, exactly one refresh per membership flip;
- mixed batches: string-only items (NO_BATCHING, chained) ride the
  primary connection concurrently with the fast shards, results land
  in caller order;
- auto-mode downgrade accounting (r18 satellite): no peer door and
  no ring_route each count + record their reason, silently serving
  over string frames;
- decision identity: a 3-node routed client against a 1-node string
  reference under the r10 fake-clock fuzz — byte-equal decisions;
- the r17 rolling-deploy soak through the ROUTING client: membership
  churn with rescale handoff, a sticky-over canary peeked after every
  flip — ZERO under-admissions, and the router heals (refreshes) on
  every change.
"""

import asyncio
import bisect
import zlib

import numpy as np
import pytest

from _util import free_ports
from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    ChainLevel,
    PeerInfo,
    RateLimitReq,
    RateLimitResp,
    Status,
)
from gubernator_tpu.client_geb import AsyncGebClient
from gubernator_tpu.serve.edge_bridge import GebListener

T0 = 1_700_000_000_000

NODE_A = "10.99.0.1:81"
NODE_B = "10.99.0.2:81"
NODE_C = "10.99.0.3:81"


class FakeClock:
    def __init__(self):
        self.t = T0

    def __call__(self):
        return self.t


def _pin_clock(monkeypatch, clock):
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod
    import gubernator_tpu.core.oracle as oracle_mod

    monkeypatch.setattr(types_mod, "millisecond_now", clock)
    monkeypatch.setattr(engine_mod, "millisecond_now", clock)
    monkeypatch.setattr(oracle_mod, "millisecond_now", clock)


def _be():
    from gubernator_tpu.core.store import StoreConfig
    from gubernator_tpu.serve.backends import TpuBackend

    return TpuBackend(
        StoreConfig(rows=16, slots=1 << 10), buckets=(16, 64)
    )


# -- independent placement mirror -------------------------------------------


def _owner_of(hosts, hash_key: str) -> str:
    """crc32 successor placement, written independently of
    client_geb._RingRouter so the test cross-checks the
    implementation instead of echoing it."""
    points = sorted(
        (zlib.crc32(h.encode("utf-8")) & 0xFFFFFFFF, h) for h in hosts
    )
    p = zlib.crc32(hash_key.encode("utf-8")) & 0xFFFFFFFF
    i = bisect.bisect_left([q for q, _ in points], p)
    if i == len(points):
        i = 0
    return points[i][1]


def _expected_counts(hosts, reqs):
    counts = {h: 0 for h in hosts}
    for r in reqs:
        counts[_owner_of(hosts, r.hash_key())] += 1
    return counts


# -- fake listener harness (the test_edge_ring_change pattern) ---------------


class FakeBackend:
    decide_submit_merged = object()  # a device backend


class FakePicker:
    def __init__(self, hosts_self):
        self._peers = [
            type("P", (), {"host": h, "is_owner": mine})()
            for h, mine in hosts_self
        ]

    def peers(self):
        return self._peers


class CountingInstance:
    """Array fast path counting items and echoing limit-hits as
    remaining, plus a string echo path (for NO_BATCHING/chained
    items the router keeps on the primary connection)."""

    def __init__(self, self_host, hosts):
        self.backend = FakeBackend()
        self.picker = FakePicker([(h, h == self_host) for h in hosts])
        self.fast_items = 0
        inst = self

        class B:
            async def decide_arrays(self, fields, frame=True):
                n = fields["key_hash"].shape[0]
                inst.fast_items += n
                return (
                    np.zeros(n, np.int64),
                    fields["limit"],
                    fields["limit"] - fields["hits"],
                    np.zeros(n, np.int64),
                )

        class T:
            def observe_hashes(self, h):
                pass

        self.batcher = B()
        self.traffic = T()

    async def get_rate_limits(self, reqs, stage_frame=False):
        return [
            RateLimitResp(
                status=Status.UNDER_LIMIT, limit=r.limit,
                remaining=r.limit - r.hits, reset_time=1,
            )
            for r in reqs
        ]


def _rr_req(i, name="rr"):
    return RateLimitReq(
        name=name, unique_key=f"k{i}", hits=1, limit=10 + i,
        duration=60_000,
    )


def test_router_shards_per_owner_and_heals_on_membership_change():
    """40 fast-eligible items shard to EXACTLY the ring's owners (the
    independent crc32 mirror's counts, per node); a picker swap makes
    every in-flight fingerprint stale -> GEBR -> ONE hello re-fetch ->
    the same 40 items land on the 3-node split."""
    pa, pb, pc = free_ports(3)
    doors = {
        NODE_A: f"127.0.0.1:{pa}",
        NODE_B: f"127.0.0.1:{pb}",
        NODE_C: f"127.0.0.1:{pc}",
    }

    async def run():
        inst_a = CountingInstance(NODE_A, [NODE_A, NODE_B])
        inst_b = CountingInstance(NODE_B, [NODE_A, NODE_B])
        inst_c = CountingInstance(NODE_C, [NODE_A, NODE_B, NODE_C])
        listeners = [
            GebListener(inst, doors[node], peer_bridges=doors)
            for inst, node in (
                (inst_a, NODE_A), (inst_b, NODE_B), (inst_c, NODE_C)
            )
        ]
        for ln in listeners:
            await ln.start()
        client = AsyncGebClient(doors[NODE_A], ring_route=True)
        try:
            hello = await client.connect()
            assert len(hello.nodes) == 2
            st = client.stats()
            assert st["ring_routed"] is True
            assert st["downgrades"] == 0

            reqs = [_rr_req(i) for i in range(40)]
            want2 = _expected_counts([NODE_A, NODE_B], reqs)
            # the split must be non-trivial or the test proves nothing
            assert want2[NODE_A] > 0 and want2[NODE_B] > 0

            resps = await client.get_rate_limits(reqs)
            for i, r in enumerate(resps):
                assert r.status == Status.UNDER_LIMIT
                assert r.remaining == (10 + i) - 1, (i, r)
            assert inst_a.fast_items == want2[NODE_A]
            assert inst_b.fast_items == want2[NODE_B]
            assert inst_c.fast_items == 0
            assert client._router.refreshes == 0

            # membership change: C joins. The router's next frames
            # carry the 2-ring fingerprint -> every shard refused
            # (GEBR), ONE refresh, full re-route on the 3-ring.
            ring3 = [NODE_A, NODE_B, NODE_C]
            inst_a.picker = FakePicker(
                [(h, h == NODE_A) for h in ring3]
            )
            inst_b.picker = FakePicker(
                [(h, h == NODE_B) for h in ring3]
            )
            want3 = _expected_counts(ring3, reqs)
            assert want3[NODE_C] > 0

            resps = await client.get_rate_limits(reqs)
            for i, r in enumerate(resps):
                assert r.status == Status.UNDER_LIMIT
                assert r.remaining == (10 + i) - 1, (i, r)
            assert client._router.refreshes == 1
            assert inst_a.fast_items == want2[NODE_A] + want3[NODE_A]
            assert inst_b.fast_items == want2[NODE_B] + want3[NODE_B]
            assert inst_c.fast_items == want3[NODE_C]
            assert client.stats()["downgrades"] == 0
        finally:
            await client.close()
            for ln in listeners:
                await ln.stop()

    asyncio.run(run())


def test_router_mixed_batch_lands_in_caller_order():
    """A batch mixing fast-eligible, NO_BATCHING, and chained items:
    the ineligible ones ride the primary's string frames concurrently
    with the fast shards; every response lands at its request's
    index."""
    pa, pb = free_ports(2)
    doors = {NODE_A: f"127.0.0.1:{pa}", NODE_B: f"127.0.0.1:{pb}"}

    async def run():
        inst_a = CountingInstance(NODE_A, [NODE_A, NODE_B])
        inst_b = CountingInstance(NODE_B, [NODE_A, NODE_B])
        listeners = [
            GebListener(inst_a, doors[NODE_A], peer_bridges=doors),
            GebListener(inst_b, doors[NODE_B], peer_bridges=doors),
        ]
        for ln in listeners:
            await ln.start()
        client = AsyncGebClient(doors[NODE_A], ring_route=True)
        try:
            await client.connect()
            reqs = []
            for i in range(12):
                kw = {}
                if i % 3 == 0:
                    kw["behavior"] = Behavior.NO_BATCHING
                elif i % 3 == 2:
                    kw["chain"] = [ChainLevel("cg:mix", 1 << 30, 0)]
                reqs.append(
                    RateLimitReq(
                        name="mx", unique_key=f"m{i}", hits=1,
                        limit=20 + i, duration=60_000, **kw,
                    )
                )
            resps = await client.get_rate_limits(reqs)
            assert len(resps) == 12
            for i, r in enumerate(resps):
                assert r.status == Status.UNDER_LIMIT
                assert r.remaining == (20 + i) - 1, (i, r)
            # only the i%3==1 third was fast-eligible; the rest went
            # down the string/instance path (counted nowhere)
            fast = [r for i, r in enumerate(reqs) if i % 3 == 1]
            want = _expected_counts([NODE_A, NODE_B], fast)
            assert inst_a.fast_items == want[NODE_A]
            assert inst_b.fast_items == want[NODE_B]
        finally:
            await client.close()
            for ln in listeners:
                await ln.stop()

    asyncio.run(run())


def test_downgrade_reason_peer_door_unknown():
    """ring_route=True on a multi-node ring whose hello can't name a
    peer's frame door (no GUBER_GEB_PEER_DOORS, host without the
    symmetric port shape): the downgrade is COUNTED with its reason
    and the client silently keeps serving over string frames."""
    (pa,) = free_ports(1)

    async def run():
        # "nodeB" has no port: the symmetric-port door derivation
        # yields nothing and no peer_bridges override exists
        inst = CountingInstance(NODE_A, [NODE_A, "nodeB"])
        listener = GebListener(inst, f"127.0.0.1:{pa}")
        await listener.start()
        client = AsyncGebClient(f"127.0.0.1:{pa}", ring_route=True)
        try:
            await client.connect()
            st = client.stats()
            assert st["ring_routed"] is False
            assert st["use_fast"] is False
            assert st["downgrades"] == 1
            assert st["downgrade_reason"].startswith(
                "peer door unknown"
            )
            resps = await client.get_rate_limits(
                [_rr_req(0, name="dg")]
            )
            assert resps[0].status == Status.UNDER_LIMIT
            assert inst.fast_items == 0  # string path served it
        finally:
            await client.close()
            await listener.stop()

    asyncio.run(run())


def test_downgrade_reason_multi_node_without_ring_route():
    """The pre-r18 shape: auto mode on a multi-node ring WITHOUT
    ring_route downgrades to string frames — now counted + reasoned
    instead of silent."""
    pa, pb = free_ports(2)
    doors = {NODE_A: f"127.0.0.1:{pa}", NODE_B: f"127.0.0.1:{pb}"}

    async def run():
        inst = CountingInstance(NODE_A, [NODE_A, NODE_B])
        listener = GebListener(inst, doors[NODE_A], peer_bridges=doors)
        await listener.start()
        client = AsyncGebClient(doors[NODE_A])  # ring_route off
        try:
            await client.connect()
            st = client.stats()
            assert st["ring_routed"] is False
            assert st["use_fast"] is False
            assert st["downgrades"] == 1
            assert st["downgrade_reason"].startswith("multi-node ring")
            resps = await client.get_rate_limits(
                [_rr_req(1, name="dg2")]
            )
            assert resps[0].status == Status.UNDER_LIMIT
            assert inst.fast_items == 0
        finally:
            await client.close()
            await listener.stop()

    asyncio.run(run())


# -- decision identity ------------------------------------------------------


def _fuzz_stream(rng, keys, steps):
    for step in range(steps):
        n = int(rng.integers(1, 7))
        batch = []
        for _ in range(n):
            k = int(rng.integers(len(keys)))
            batch.append(
                RateLimitReq(
                    name="ringdoor",
                    unique_key=keys[k],
                    hits=int(rng.choice([0, 1, 1, 1, 2, 9])),
                    limit=int(rng.choice([1, 2, 3, 50])),
                    duration=int(rng.choice([400, 2000, 60_000])),
                    algorithm=Algorithm(k % 2),
                )
            )
        yield step, batch, int(rng.choice([0, 0, 1, 7, 150, 500, 2500]))


def test_ring_routed_vs_single_node_string_identity_fuzz(monkeypatch):
    """A ring-routed client over a REAL 3-node cluster decides
    byte-identically to a 1-node string reference under the r10
    fake-clock fuzz: every key lands on exactly one store in both
    topologies, so (status, limit, remaining, reset_time, error) match
    item for item."""
    from gubernator_tpu.cluster import LocalCluster

    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    ports = free_ports(8)
    routed_cluster = LocalCluster(
        [f"127.0.0.1:{p}" for p in ports[:3]],
        backend_factory=_be,
        geb_ports=list(ports[3:6]),
    )
    ref_cluster = LocalCluster(
        [f"127.0.0.1:{ports[6]}"],
        backend_factory=_be,
        geb_ports=[ports[7]],
    )
    for c in (routed_cluster, ref_cluster):
        c.start()
        for s in c.servers:
            if s.instance.shed is not None:
                s.instance.shed.now_fn = clock
    try:

        async def run():
            routed = AsyncGebClient(
                f"127.0.0.1:{ports[3]}", ring_route=True
            )
            ref = AsyncGebClient(
                f"127.0.0.1:{ports[7]}", mode="string", shm="off"
            )
            rng = np.random.default_rng(53)
            keys = [f"rk{i}" for i in range(12)]
            try:
                hello = await routed.connect()
                assert len(hello.nodes) == 3
                st = routed.stats()
                assert st["ring_routed"] is True
                assert st["downgrades"] == 0
                for step, batch, dt in _fuzz_stream(rng, keys, 70):
                    clock.t += dt
                    a = await ref.get_rate_limits(batch)
                    b = await routed.get_rate_limits(batch)
                    for i, (x, y) in enumerate(zip(a, b)):
                        tx = (int(x.status), x.limit, x.remaining,
                              x.reset_time, x.error)
                        ty = (int(y.status), y.limit, y.remaining,
                              y.reset_time, y.error)
                        assert tx == ty, (step, i, batch[i], tx, ty)
            finally:
                await ref.close()
                await routed.close()

        asyncio.run(run())
    finally:
        routed_cluster.stop()
        ref_cluster.stop()


# -- rolling-membership soak (r17's deploy replay, routed client) -----------


def test_rolling_membership_soak_zero_canary_under_admissions():
    """The r18 acceptance soak: a 3-node ring with elastic rescale,
    membership churned leave/rejoin through the CANARY OWNER twice,
    all traffic through the ring-routing client. The sticky-over
    canary (created-over window, r17 semantics) is peeked after every
    flip: ZERO under-admissions, ever. Fast background batches after
    each flip prove the router heals (GEBR -> refresh -> served) on
    every single change."""
    from gubernator_tpu.serve.config import BehaviorConfig, ServerConfig
    from gubernator_tpu.serve.server import Server

    ports = free_ports(6)
    addrs = [f"127.0.0.1:{p}" for p in ports[:3]]
    gebs = ports[3:]
    doors = ",".join(
        f"{a}=127.0.0.1:{g}" for a, g in zip(addrs, gebs)
    )

    async def run():
        servers = []
        for a, g in zip(addrs, gebs):
            conf = ServerConfig(
                grpc_address=a,
                http_address="",  # default is localhost:80
                advertise_address=a,
                backend="exact",
                behaviors=BehaviorConfig(global_sync_wait=0.05),
                rescale=True,
                replication_sync_wait=60.0,  # background flusher quiet
                geb_port=g,
                geb_peer_doors=doors,
            )
            conf.peers = list(addrs)
            s = Server(conf, backend=_be())
            await s.start()
            servers.append(s)

        async def set_ring(members):
            """One membership flip, everywhere: new pickers, then the
            rescale handoff (movers ship their owned windows), then
            the double-serve windows closed so the NEW owner serves —
            the deterministic deploy step (test_rescale's pattern)."""
            for s in servers:
                me = s.conf.grpc_address
                await s.instance.set_peers([
                    PeerInfo(address=h, is_owner=(h == me))
                    for h in members
                ])
            for s in servers:
                await s.instance.rescale.flush_once()
            for s in servers:
                s.instance.rescale._transition = None

        # deterministic handoffs: the server's background flusher
        # would pop a queued ring change before the test's manual
        # flush_once (which then sees an empty queue and returns while
        # the real handoff RPC is still in flight) — stop it and drive
        # every flush by hand (stop() is idempotent; Server.stop
        # re-calls it). Startup transitions (initial set_peers) must
        # not leak into the soak's windows either.
        for s in servers:
            await s.instance.rescale.stop()
            s.instance.rescale._transition = None

        client = AsyncGebClient(
            f"127.0.0.1:{gebs[0]}", ring_route=True, timeout=30.0
        )
        under_admissions = 0
        try:
            hello = await client.connect()
            assert len(hello.nodes) == 3
            assert client.stats()["ring_routed"] is True

            # a canary whose owner is NOT the client's primary, so the
            # owner itself can leave the ring (the interesting case)
            ck = next(
                f"c{i}" for i in range(512)
                if _owner_of(addrs, f"soak_c{i}") != addrs[0]
            )

            def canary(hits):
                return RateLimitReq(
                    name="soak", unique_key=ck, hits=hits, limit=1,
                    duration=600_000, behavior=Behavior.NO_BATCHING,
                )

            def bg(tag):
                return [
                    RateLimitReq(
                        name="soakbg", unique_key=f"{tag}{i}", hits=1,
                        limit=1 << 30, duration=600_000,
                    )
                    for i in range(8)
                ]

            # hits > limit on a fresh key: a created-over window —
            # sticky OVER_LIMIT for the whole duration (r17 semantics)
            r = (await client.get_rate_limits([canary(2)]))[0]
            assert r.error == "" and r.status == Status.OVER_LIMIT

            owner = _owner_of(addrs, f"soak_{ck}")
            other = next(
                a for a in addrs[1:] if a != owner
            )
            flips = 0
            for leaver in (owner, other, owner):
                for members in (
                    [a for a in addrs if a != leaver],  # leave
                    list(addrs),                        # rejoin
                ):
                    await set_ring(members)
                    flips += 1
                    outs = await client.get_rate_limits(
                        bg(f"f{flips}_")
                    )
                    assert all(
                        o.status == Status.UNDER_LIMIT and not o.error
                        for o in outs
                    )
                    r = (await client.get_rate_limits([canary(0)]))[0]
                    assert r.error == ""
                    if r.status != Status.OVER_LIMIT:
                        under_admissions += 1
            assert under_admissions == 0, (
                f"quota amnesia: {under_admissions} canary peeks "
                f"under-admitted across {flips} membership flips"
            )
            # the router healed on every flip: each post-flip batch
            # hit a stale fingerprint (GEBR) and re-fetched the ring
            assert client._router.refreshes >= flips, (
                client._router.refreshes, flips
            )
        finally:
            await client.close()
            for s in servers:
                await s.stop()

    asyncio.run(run())
