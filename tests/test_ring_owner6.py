"""The `ring-owner6` deployment's owner side on the CPU (PR 32): one
member of a six-node ring as its five peers see it, through the SERVED
`PeersV1/GetPeerRateLimits` door of a device backend sized by the
deployment's environment, cut to a store the CPU holds
(`GUBER_BACKEND=tpu GUBER_STORE_TARGET_KEYS=20000`: the same 16 ways,
64/256/1024 ladder and sketch tier).

- a seeded stream (both algorithms, duplicates inside a batch, keys over
  their limit, peeks, batches of 1, 12 and 1000 items, two batches in
  flight at once) through the peer door equals
  `benchmark/reference_ring.owner_answers` item by item, and equals the
  same stream through the V1 door of a second, fresh daemon;
- `reference_ring.owner_of` (which imports nothing of the program)
  places 10,000 seeded keys over six peers where `ConsistentHashPicker`
  does;
- the `peer_serve` stage and the three `peer_serve_*_total` counters
  grow by exactly the calls and items sent, the shed hits by the items
  answered over-limit from the cache; a peer call's tiles cover >= 0.9
  of its `call_e2e`, and it records no `instance_route`;
- the benchmark generator's build gives every seed the same multiset of
  batches in another order, and a batch joined from one-item messages is
  the batch's own serialisation.

The clock stands still (tests/test_global_mesh4_served.py's FakeClock),
so every answer is exact whatever the windows' lengths. Counters are
compared by GROWTH over a test: the registry and the stage clock are
the process's.
"""

import asyncio
import os
import random
import sys

import grpc
import numpy as np
import pytest

from _util import free_ports
from gubernator_tpu.api import convert
from gubernator_tpu.api.grpc_glue import PeersV1Stub, V1Stub
from gubernator_tpu.api.proto.gen import gubernator_pb2, peers_pb2
from gubernator_tpu.api.types import Algorithm, RateLimitReq
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core import oracle
from gubernator_tpu.serve.config import config_from_env
from gubernator_tpu.serve.metrics import REGISTRY
from gubernator_tpu.serve.peers import ConsistentHashPicker
from gubernator_tpu.serve.server import make_backend
from gubernator_tpu.serve.stages import CALL_TILES, STAGES
from test_exact100m_served import CLASSES  # the traffic's limit classes
from test_global_mesh4_served import FakeClock, T0

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
)
import reference_ring  # noqa: E402  (the configuration's plain reference)
from generators import closed_loop_peer_batches as gen  # noqa: E402

#: benchmark/configs/ring-owner6.json `env`, the key budget cut 500-fold
ENV = {"GUBER_BACKEND": "tpu", "GUBER_STORE_TARGET_KEYS": "20000"}
DAY = 86_400_000
COUNTERS = ("peer_serve_batches_total", "peer_serve_items_total",
            "peer_serve_shed_hits_total")


def item(i: int, hits: int, tag: str = "k"):
    """(key, hits, limit, duration, algo) as the references take it:
    limit class 70 / 20 / 10% and algorithm 75 / 25% by key id."""
    limit, duration = CLASSES[0 if i % 10 < 7 else 1 if i % 10 < 9 else 2]
    return (f"{tag}{i}", hits, limit, duration, 1 if i % 4 == 3 else 0)


def to_req(it) -> RateLimitReq:
    key, hits, limit, duration, algo = it
    return RateLimitReq(name="ring", unique_key=key, hits=hits, limit=limit,
                        duration=duration, algorithm=Algorithm(algo))


def stream(seed: int):
    """[[batch, ...]]: rounds of batches; the batches of one round are
    in flight together and share no key, so their order is free. Sizes
    1, 12, 1000 and what a forwarder's BatchWait would cut between
    them. Within a batch a key always carries the same hits
    (benchmark/check.py checked_sequence: the program's rule for
    same-key items of one batch equals one-by-one service then)."""
    rng = random.Random(seed)
    hot = list(range(80))
    driven = [i for i in hot if i % 10 in (7, 8)][:8]  # 10-per-window ids
    fresh = 10_000

    def batch(size: int, lane: int):
        nonlocal fresh
        hits_of, out = {}, []
        for n in range(size - (size > 1)):
            if size >= 100 and n % 3 == 0:  # keys never seen before
                i, fresh = fresh, fresh + 1
            else:
                i = rng.choice(driven) if rng.random() < 0.4 else rng.choice(hot)
            hits = hits_of.setdefault(i, rng.choice((1, 1, 1, 2, 0)))
            out.append(item(i, hits, tag=f"l{lane}-"))
        if size > 1:
            out.append(out[0])  # an in-batch duplicate for certain
        return out

    rounds = []
    for size in (1, 12, 1000, 1, 12, 300, 1000, 12):
        rounds.append([batch(size, 0)])
    for _ in range(6):  # two forwarders at once, each its own keys
        rounds.append([batch(1000, 1), batch(rng.choice((12, 1000)), 2)])
    for _ in range(20):
        rounds.append([batch(12, 0)])
    return rounds


def through_the_door(addr: str, door: str, rounds):
    """Every round's batches in flight together over one keep-alive
    channel; answers as [[(status, limit, remaining, error)]] a round."""

    async def run():
        channel = grpc.aio.insecure_channel(addr)
        await channel.channel_ready()
        if door == "peers":
            call, wrap = PeersV1Stub(channel).GetPeerRateLimits, (
                lambda pbs: peers_pb2.GetPeerRateLimitsReq(requests=pbs))
            unwrap = lambda resp: resp.rate_limits  # noqa: E731
        else:
            call, wrap = V1Stub(channel).GetRateLimits, (
                lambda pbs: gubernator_pb2.GetRateLimitsReq(requests=pbs))
            unwrap = lambda resp: resp.responses  # noqa: E731

        async def one(batch):
            pbs = [convert.req_to_pb(to_req(it)) for it in batch]
            resp = await call(wrap(pbs), timeout=120)
            return [(r.status, r.limit, r.remaining, r.error)
                    for r in unwrap(resp)]

        try:
            return [await asyncio.gather(*map(one, rnd)) for rnd in rounds]
        finally:
            await channel.close()

    return _loop().run_until_complete(run())


def _loop(made=[]):
    """One event loop for the file's client calls: grpc.aio's poller
    stays with the loop that first used it."""
    if not made:
        made.append(asyncio.new_event_loop())
    return made[0]


@pytest.fixture(scope="module")
def clock():
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    fake = FakeClock()
    mp = pytest.MonkeyPatch()
    for mod in (types_mod, engine_mod, oracle):
        mp.setattr(mod, "millisecond_now", fake)
    yield fake
    mp.undo()


def _node(clock):
    conf = config_from_env(dict(ENV))
    backend = make_backend(conf)
    (port,) = free_ports(1)
    cluster = LocalCluster(
        [f"127.0.0.1:{port}"], backend_factory=lambda: backend,
        device_batch_limit=conf.device_batch_limit,
    )
    cluster.start(timeout=600.0)
    inst = cluster.servers[0].instance
    assert inst.shed is not None  # the daemon's default: the screen is on
    inst.shed.now_fn = clock
    return cluster, f"127.0.0.1:{port}"


@pytest.fixture(scope="module")
def owner(clock):
    """The ring member whose peer door is driven."""
    cluster, addr = _node(clock)
    try:
        yield cluster, addr
    finally:
        cluster.stop()


def stage_counts():
    snap = STAGES.snapshot()["stages"]
    return {name: (s["count"], s["total_s"]) for name, s in snap.items()}


def peer_counters(cluster):
    cluster.servers[0]._refresh_store_metrics()
    return {c: REGISTRY.get_sample_value(c) for c in COUNTERS}


def test_seeded_stream_equals_reference_and_the_v1_door(owner, clock):
    cluster, addr = owner
    rounds = stream(32)
    batches = [b for rnd in rounds for b in rnd]
    sizes = {len(b) for b in batches}
    assert {1, 12, 1000} <= sizes and any(len(r) == 2 for r in rounds)
    before, counted = stage_counts(), peer_counters(cluster)
    got = through_the_door(addr, "peers", rounds)

    want = reference_ring.owner_answers(batches, T0)
    flat_got = [g for rnd in got for g in rnd]
    differ = [
        (b, j, it, g, w)
        for b, (batch, gs, ws) in enumerate(zip(batches, flat_got, want))
        for j, (it, g, w) in enumerate(zip(batch, gs, ws))
        if g != (*w, "")
    ]
    assert not differ, differ[:5]
    flat = [a for g in flat_got for a in g]
    items = [it for b in batches for it in b]
    assert len(flat) == len(items) >= 10_000
    assert sum(a[0] == 1 for a in flat) > 500  # keys driven over their limit
    assert sum(it[1] == 0 for it in items) > 1000  # peeks
    assert sum(it[4] == 1 for it in items) > 2000  # leaky buckets
    assert sum(len(b) - len({it[0] for it in b}) for b in batches) > 500

    # one peer_serve sample a call, no instance_route, tiles that tile
    after, counted_after = stage_counts(), peer_counters(cluster)

    def grew(name, field=0):
        return after.get(name, (0, 0.0))[field] - before.get(name, (0, 0.0))[field]

    assert grew("peer_serve") == grew("call_e2e") == len(batches)
    assert grew("grpc_decode") == grew("grpc_encode") == len(batches)
    assert grew("instance_route") == 0
    # a batch answered whole by the shed cache has no batcher tiles
    assert 0 < grew("call_queue") == grew("call_device") <= len(batches)
    tiled = sum(grew(name, 1) for name in CALL_TILES)
    assert 0.9 <= tiled / grew("call_e2e", 1) <= 1.0
    assert counted_after[COUNTERS[0]] - counted[COUNTERS[0]] == len(batches)
    assert counted_after[COUNTERS[1]] - counted[COUNTERS[1]] == len(items)
    shed = cluster.servers[0].instance.shed
    assert 0 < counted_after[COUNTERS[2]] - counted[COUNTERS[2]] <= shed.hits

    # the same stream through V1 on a second, fresh daemon
    second, addr2 = _node(clock)
    try:
        v1 = through_the_door(addr2, "grpc", rounds)
    finally:
        second.stop()
    assert v1 == got
    assert stage_counts().get("peer_serve", (0,))[0] == after["peer_serve"][0]


def test_counters_grow_by_what_was_sent_and_shed(owner):
    cluster, addr = owner
    key = ("shed-me", 1, 3, DAY, 0)  # token, limit 3, a day-long window
    before, counted = stage_counts(), peer_counters(cluster)
    # four hits: 2, 1, 0 remaining, then the frozen over-limit answer,
    # which the owner-side screen caches from the device's reply
    got = through_the_door(addr, "peers", [[[key]] for _ in range(4)])
    assert [g[0][0][:3] for g in got] == [(0, 3, 2), (0, 3, 1), (0, 3, 0), (1, 3, 0)]
    mid = peer_counters(cluster)
    assert mid[COUNTERS[0]] - counted[COUNTERS[0]] == 4
    assert mid[COUNTERS[1]] - counted[COUNTERS[1]] == 4
    assert mid[COUNTERS[2]] - counted[COUNTERS[2]] == 0
    # seven more calls and one batch of five: all answered by the screen
    got = through_the_door(
        addr, "peers", [[[key]] for _ in range(7)] + [[[key] * 5]])
    assert all(a[:3] == (1, 3, 0) for rnd in got for g in rnd for a in g)
    end = peer_counters(cluster)
    assert end[COUNTERS[0]] - mid[COUNTERS[0]] == 8
    assert end[COUNTERS[1]] - mid[COUNTERS[1]] == 12
    assert end[COUNTERS[2]] - mid[COUNTERS[2]] == 12
    after = stage_counts()
    assert after["peer_serve"][0] - before.get("peer_serve", (0,))[0] == 12
    # the shed batches reached no batcher: four calls have its tiles
    assert after["call_queue"][0] - before.get("call_queue", (0,))[0] == 4
    # a peek is never answered by the screen, and reads the same window
    ((peek,),), = through_the_door(addr, "peers", [[[("shed-me", 0, 3, DAY, 0)]]])
    assert peek[:3] == (1, 3, 0)
    assert peer_counters(cluster)[COUNTERS[2]] == end[COUNTERS[2]]


def test_too_large_a_batch_is_refused_and_not_counted(owner):
    cluster, addr = owner
    counted = peer_counters(cluster)
    with pytest.raises(grpc.aio.AioRpcError) as e:
        through_the_door(addr, "peers", [[[item(i, 1, "big") for i in range(1001)]]])
    assert e.value.code() == grpc.StatusCode.OUT_OF_RANGE
    assert peer_counters(cluster) == counted


def test_reference_ring_places_keys_where_the_picker_does():
    class Peer:
        def __init__(self, host):
            self.host = host

    # upstream's functional-test cluster: six nodes on one host
    peers = [f"127.0.0.1:{9990 + i}" for i in range(6)]
    picker = ConsistentHashPicker()
    for p in peers:
        picker.add(Peer(p))
    rng = random.Random(32)
    keys = [f"bench_s{rng.randrange(1 << 31)}:{rng.randrange(10_000_000)}"
            for _ in range(10_000)]
    owners = [reference_ring.owner_of(k, peers) for k in keys]
    assert owners == [picker.get(k).host for k in keys]
    assert set(owners) == set(peers)  # every node owns a share
    # a key at a peer's own point is that peer's; past the last point, the first's
    assert all(reference_ring.owner_of(p, peers) == p for p in peers)
    last = max(peers, key=reference_ring.ring_point)
    first = min(peers, key=reference_ring.ring_point)
    assert reference_ring.owner_of(last, [first, last]) == last
    hi = next(k for k in keys
              if reference_ring.ring_point(k) > reference_ring.ring_point(last))
    assert reference_ring.owner_of(hi, peers) == first
    with pytest.raises(ValueError):
        reference_ring.owner_of("k", [])


def test_reference_ring_imports_nothing_of_the_program():
    import subprocess

    bench = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import reference_ring; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('gubernator_tpu', 'jax', 'jaxlib', 'numpy')]; print(bad)")
    out = subprocess.run([sys.executable, "-c", code, bench], text=True,
                         capture_output=True, timeout=60, check=True)
    assert out.stdout.strip() == "[]"


def _spec(seed: int, worker: int, seconds: float = 2.0):
    traffic = {"prebuilt_batches_per_s": 8, "warmup_s": 1.0,
               "items_per_batch": 1000, "base_seed": 320006}
    return {"traffic": traffic, "worker": worker, "seed": seed,
            "seconds": seconds, "config": {"key_population": 10_000_000}}


def test_every_seed_sends_the_same_batches_in_another_order():
    a, b = gen.build(_spec(1, 0)), gen.build(_spec(2**31 + 5, 0))
    assert a.shape == b.shape == (24, 1000)
    rows = lambda x: sorted(map(tuple, x.tolist()))  # noqa: E731
    assert rows(a) == rows(b) and a.tolist() != b.tolist()
    assert rows(gen.build(_spec(1, 1))) != rows(a)  # a worker's own draws
    assert np.array_equal(a, gen.build(_spec(1, 0)))  # the seed decides


def test_a_batch_joined_from_one_item_messages_is_the_batch():
    reqs = [to_req(item(i, 1)) for i in range(50)]
    joined = b"".join(map(gen.one_item, reqs))
    whole = peers_pb2.GetPeerRateLimitsReq(
        requests=[convert.req_to_pb(r) for r in reqs])
    assert joined == whole.SerializeToString()
    parsed = peers_pb2.GetPeerRateLimitsReq.FromString(joined)
    assert [convert.req_from_pb(p) for p in parsed.requests] == reqs
    resp = peers_pb2.GetPeerRateLimitsResp(rate_limits=[
        gubernator_pb2.RateLimitResp(status=i % 2, limit=10, remaining=i)
        for i in range(5)] + [gubernator_pb2.RateLimitResp(error="x")])
    status, limit, remaining, error = gen.answers(resp.SerializeToString())
    assert (status, limit, remaining, error) == (
        [0, 1, 0, 1, 0, 0], [10] * 5 + [0], [0, 1, 2, 3, 4, 0], True)
