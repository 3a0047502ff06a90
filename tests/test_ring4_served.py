"""The `ring4` deployment on the CPU (PR 41): FOUR device-backend
daemons in a ring, each sized by the deployment's environment with the
key budget cut to a store the CPU holds (`GUBER_BACKEND=tpu
GUBER_STORE_TARGET_KEYS=20000`: the same 16 ways, 64/256/1024 ladder and
sketch tier), clients at node 0's GEB door with 1000-item string frames
that the client does not route.

- a seeded stream of such frames (both algorithms, duplicates inside a
  frame, keys over their limit, peeks, every frame holding keys of all
  four owners) through node 0 equals `benchmark/reference_ring4.py`
  item by item and equals ONE `reference.Limiter`;
- the same keys asked at node 0 and at node 2 in turn give one
  limiter's sequence;
- `reference_ring.owner_of` places the frames' keys where the four-node
  `ConsistentHashPicker` does;
- the forwarder's stages and counters (serve/peers.py, PR 41) grow by
  exactly the batches, groups and items forwarded;
  `peer_forward_items_total` at node 0 = the sum of
  `peer_serve_items_total` at nodes 1-3; the four forward stages cover
  >= 0.9 of the sampled `peer_forward` spans;
- a forward made to miss its deadline (`GUBER_FAULT_SPEC
  peer_rpc:hang`) yields error items that say which deadline passed,
  counts under `reason="deadline"`, logs ONE WARNING, and is not sent
  again: the owner never saw the hits.

The clock stands still (tests/test_global_mesh4_served.py's FakeClock),
so every answer is exact whatever the windows' lengths. Counters and
stages are compared by GROWTH over a test: the registry and the stage
clock are the process's, and the four nodes share them here.
"""

import asyncio
import contextlib
import json
import logging
import os
import random
import sys
import time

import grpc
import pytest

from _util import WireDoor, free_ports
from gubernator_tpu.api.types import Algorithm, RateLimitReq
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core import oracle
from gubernator_tpu.serve import peers as peers_mod
from gubernator_tpu.serve.breaker import BreakerOpenError
from gubernator_tpu.serve.config import BehaviorConfig, config_from_env
from gubernator_tpu.serve.faults import FAULTS, FaultError
from gubernator_tpu.serve.metrics import REGISTRY
from gubernator_tpu.serve.server import make_backend
from gubernator_tpu.serve.stages import PER_FORWARD, STAGES
from test_exact100m_served import CLASSES  # the traffic's limit classes
from test_global_mesh4_served import FakeClock, T0

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
sys.path.insert(0, BENCH)
import reference  # noqa: E402  (the benchmark's plain reference)
import reference_ring  # noqa: E402
import reference_ring4  # noqa: E402  (the configuration's: a whole ring)

NAME = "ring4"
DAY = 86_400_000
NODES = 4
FRAME = 1000  # upstream's cap, the cell's frame
DEADLINE_S = 0.5  # upstream's BatchTimeout, the program's default


def deployment_env() -> dict:
    """benchmark/configs/ring4.json `env`, the key budget cut 500-fold."""
    with open(os.path.join(BENCH, "configs", "ring4.json")) as f:
        env = dict(json.load(f)["env"])
    assert env["GUBER_STORE_TARGET_KEYS"] == "10000000"
    env["GUBER_STORE_TARGET_KEYS"] = "20000"
    return env


def item(i: int, hits: int, tag: str = "k"):
    """(key, hits, limit, duration, algo) as the references take it:
    limit class 70 / 20 / 10% and algorithm 75 / 25% by key id."""
    limit, duration = CLASSES[0 if i % 10 < 7 else 1 if i % 10 < 9 else 2]
    return (f"{tag}{i}", hits, limit, duration, 1 if i % 4 == 3 else 0)


def to_req(it) -> RateLimitReq:
    key, hits, limit, duration, algo = it
    return RateLimitReq(name=NAME, unique_key=key, hits=hits, limit=limit,
                        duration=duration, algorithm=Algorithm(algo))


def frames_of(seed: int, n_frames: int, tag: str):
    """[[item]]: 1000-item frames. A third of a frame is keys never
    seen before, the rest a hot set of 80 with the 10-per-window ids
    driven over their limit; within a frame a key always carries the
    same hits (benchmark/check.py checked_sequence: the program's rule
    for same-key items of one batch equals one-by-one service then)."""
    rng = random.Random(seed)
    hot = list(range(80))
    driven = [i for i in hot if i % 10 in (7, 8)][:8]
    fresh = 10_000
    out = []
    for _ in range(n_frames):
        hits_of, frame = {}, []
        for n in range(FRAME - 1):
            if n % 3 == 0:
                i, fresh = fresh, fresh + 1
            else:
                i = rng.choice(driven) if rng.random() < 0.4 else rng.choice(hot)
            hits = hits_of.setdefault(i, rng.choice((1, 1, 1, 2, 0)))
            frame.append(item(i, hits, tag))
        frame.append(frame[0])  # an in-batch duplicate for certain
        out.append(frame)
    return out


def through_the_doors(doors, frames, together: int = 1):
    """Frame n over a GEB connection to doors[n % len(doors)], as
    STRING frames (the client does not route), `together` in flight at
    once; answers as [(status, limit, remaining, error)] a frame."""

    async def run():
        from gubernator_tpu.client_geb import AsyncGebClient

        clients = [AsyncGebClient(d, mode="string") for d in doors]
        for c in clients:
            await c.connect()

        async def one(n, frame):
            resps = await clients[n % len(clients)].get_rate_limits(
                [to_req(it) for it in frame], timeout=120.0)
            return [(int(r.status), r.limit, r.remaining, r.error) for r in resps]

        try:
            out = []
            for at in range(0, len(frames), together):
                out += await asyncio.gather(*(
                    one(n, frames[n])
                    for n in range(at, min(at + together, len(frames)))))
            return out
        finally:
            for c in clients:
                await c.close()

    return asyncio.run(run())


def ring_ports(per_node: int):
    """`per_node` x NODES free ports, the first NODES (the gRPC ones,
    whose addresses are the ring's points) drawn again until every node
    holds at least a tenth of the crc32 circle: free ports cut random
    arcs, and a sliver of an arc leaves a frame — or 60 keys — without
    one of the four owners (the harness chooses its ports for the same
    reason: benchmark/harness/daemon.py even_points)."""
    for _ in range(200):
        ports = free_ports(per_node * NODES)
        addresses = [f"127.0.0.1:{p}" for p in ports[:NODES]]
        share = [0] * NODES
        for i in range(2000):
            owner = reference_ring.owner_of(f"{NAME}_probe{i}", addresses)
            share[addresses.index(owner)] += 1
        if min(share) >= 200:
            return ports
    raise RuntimeError("no even ring in 200 draws of free ports")


@contextlib.contextmanager
def served_ring(**cluster_options):
    """Four daemons' worth of serving stack in a ring, each backend
    sized by the daemon's own rule from the deployment's environment,
    every GEB door open, the clock pinned at T0: (the cluster, its GEB
    doors)."""
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    clock = FakeClock()
    mp = pytest.MonkeyPatch()
    for mod in (types_mod, engine_mod, oracle):
        mp.setattr(mod, "millisecond_now", clock)
    conf = config_from_env(deployment_env())
    ports = ring_ports(2)
    cluster = LocalCluster(
        [f"127.0.0.1:{p}" for p in ports[:NODES]],
        backend_factory=lambda: make_backend(conf),
        geb_ports=ports[NODES:], device_batch_limit=conf.device_batch_limit,
        **cluster_options,
    )
    cluster.start(timeout=900.0)
    for server in cluster.servers:
        assert server.instance.shed is not None  # the daemon's default
        server.instance.shed.now_fn = clock
        # four daemons share ONE interpreter here, beside five other
        # test workers: a forwarded 1000-item batch can take longer than
        # upstream's 0.5 s for no fault of the path, so the answers'
        # tests give it room; the deadline's own test sets it back
        assert server.instance.conf.behaviors.batch_timeout == DEADLINE_S
        server.instance.conf.behaviors.batch_timeout = 20.0
    try:
        yield cluster, [f"127.0.0.1:{p}" for p in ports[NODES:]]
    finally:
        cluster.stop()
        mp.undo()


@pytest.fixture(scope="module")
def ring():
    """served_ring with every request traced."""
    with served_ring(trace_sample=1.0) as ring:
        yield ring


def stage_counts():
    snap = STAGES.snapshot()["stages"]
    return {name: (s["count"], s["total_s"]) for name, s in snap.items()}


def counts_of(cluster):
    """(node 0's forward counts, the owners' served batches and items)."""
    fwd = cluster.instance_at(0).peer_forward
    owners = [cluster.instance_at(i) for i in range(1, NODES)]
    return {
        "batches": fwd.batches, "items": fwd.items, "failed": dict(fwd.failed),
        "served_batches": sum(o.peer_serve_batches for o in owners),
        "served_items": sum(o.peer_serve_items for o in owners),
        "folded_items": sum(o.peer_serve_folded_items for o in owners),
        "node0_served": cluster.instance_at(0).peer_serve_items,
    }


def test_the_environment_sizes_four_nodes_in_one_ring(ring):
    cluster, _ = ring
    conf = config_from_env(deployment_env())
    assert (conf.behaviors.batch_limit, conf.behaviors.effective_peer_timeout()) == (
        1000, 0.5)  # upstream's limit and deadline: the file sets neither
    for i in range(NODES):
        inst = cluster.instance_at(i)
        assert tuple(inst.backend.engine.buckets) == (64, 256, 1024)
        assert inst.backend.engine.config.rows == 16
        assert inst.picker.size() == NODES
        assert sum(p.is_owner for p in inst.peer_list()) == 1
        assert all(p.counts is inst.peer_forward for p in inst.peer_list())


@pytest.mark.parametrize("seed", [41, 2**31 + 41])
def test_seeded_frames_through_node0_equal_the_ring_and_one_limiter(ring, seed):
    cluster, doors = ring
    frames = frames_of(seed, 8, tag=f"a{seed}-")
    owners_of = [{reference_ring.owner_of(f"{NAME}_{it[0]}", cluster.addresses)
                  for it in frame} for frame in frames]
    assert all(o == set(cluster.addresses) for o in owners_of)  # all four, every frame
    before = counts_of(cluster)
    got = through_the_doors(doors[:1], frames)

    model, one = reference_ring4.Ring(cluster.addresses), reference.Limiter()
    differ, over, peeks, leaky = [], 0, 0, 0
    for f, (frame, answers) in enumerate(zip(frames, got)):
        want = model.call(frame, T0, name=NAME, asked=cluster.addresses[0])
        for j, (it, a, w) in enumerate(zip(frame, answers, want)):
            assert w == one.decide(*it, T0)[:3]  # the ring is one limiter
            over += a[0] == 1
            peeks += it[1] == 0
            leaky += it[4] == 1
            if a != (*w, ""):
                differ.append((f, j, it, a, w))
    assert not differ, differ[:5]
    assert over > 500 and peeks > 500 and leaky > 1000
    assert sum(len(fr) - len({it[0] for it in fr}) for fr in frames) > 1000
    after = counts_of(cluster)
    crossed = after["items"] - before["items"]
    foreign = sum(model.forwarded(fr, cluster.addresses[0], NAME) for fr in frames)
    # over-limit items of foreign keys are refused at the door from the
    # shed cache, so fewer cross than are foreign-owned; what crosses
    # is what the owners served, all of it as columns
    assert 0 < crossed <= foreign < 8 * FRAME
    assert after["served_items"] - before["served_items"] == crossed
    assert after["folded_items"] - before["folded_items"] == crossed
    assert after["failed"] == before["failed"]


@pytest.mark.parametrize("seed", [7, 8])
def test_node0_and_node2_give_one_keys_sequence(ring, seed):
    """Frames alternate between node 0's door and node 2's: every key
    is asked at both, and the answers are one limiter's."""
    cluster, doors = ring
    frames = frames_of(seed, 6, tag=f"b{seed}-")
    got = through_the_doors([doors[0], doors[2]], frames)
    asked = [cluster.addresses[0 if n % 2 == 0 else 2] for n in range(len(frames))]
    want = reference_ring4.ring_answers(frames, cluster.addresses, T0, NAME, asked)
    assert [[a[:3] for a in fr] for fr in got] == want
    assert all(a[3] == "" for fr in got for a in fr)
    assert want == reference_ring4.one_limiter_answers(frames, T0)
    keys = [{it[0] for it in fr} for fr in frames]
    assert len(set.intersection(*keys)) >= 60  # the hot set, at both doors


def test_owner_of_places_the_frames_keys_where_the_picker_does(ring):
    cluster, _ = ring
    keys = sorted({f"{NAME}_{it[0]}" for fr in frames_of(3, 4, "c-") for it in fr})
    assert len(keys) > 1000
    for i in (0, 2):  # every node's picker agrees
        picker = cluster.instance_at(i).picker
        assert [picker.get(k).host for k in keys] == [
            reference_ring.owner_of(k, cluster.addresses) for k in keys]
    picker = cluster.instance_at(0).picker
    owned = picker.ring()[2][picker.owner_column(keys)]
    assert [bool(x) for x in owned] == [
        reference_ring.owner_of(k, cluster.addresses) == cluster.addresses[0]
        for k in keys]


@pytest.mark.parametrize("together", [1, 4])
def test_stages_and_counters_grow_by_what_was_forwarded(ring, together):
    """Keys never driven over a limit, so every foreign item crosses:
    the counts are the reference's to the item. One frame at a time a
    group is an RPC; four in flight share RPCs (one a peer at a time)."""
    cluster, doors = ring
    rng = random.Random(together)
    frames = [[item(100_000 + 7 * rng.randrange(10**6), 1, f"d{together}-")
               for _ in range(FRAME)] for _ in range(8)]
    model = reference_ring4.Ring(cluster.addresses)
    me = cluster.addresses[0]
    foreign = sum(model.forwarded(fr, me, NAME) for fr in frames)
    groups = sum(len({model.owner(NAME, it[0]) for it in fr} - {me}) for fr in frames)
    assert groups == 8 * (NODES - 1) and 0 < foreign < 8 * FRAME
    stages0, counts0 = stage_counts(), counts_of(cluster)
    recorder = cluster.instance_at(0).tracer.recorder
    seen = {t["trace_id"] for t in recorder.snapshot()["traces"]}
    got = through_the_doors(doors[:1], frames, together)
    assert all(a[3] == "" and a[0] == 0 for fr in got for a in fr)
    stages1, counts1 = stage_counts(), counts_of(cluster)

    def grew(name, field=0):
        return stages1.get(name, (0, 0.0))[field] - stages0.get(name, (0, 0.0))[field]

    batches = counts1["batches"] - counts0["batches"]
    assert counts1["items"] - counts0["items"] == foreign
    assert counts1["served_items"] - counts0["served_items"] == foreign
    assert counts1["served_batches"] - counts0["served_batches"] == batches
    assert counts1["node0_served"] == counts0["node0_served"]  # sent no batch
    assert counts1["failed"] == counts0["failed"]
    assert batches == groups if together == 1 else NODES - 1 <= batches <= groups
    assert grew("forward_queue") == groups
    assert grew("forward_encode") == grew("forward_rpc") == grew(
        "forward_decode") == batches
    assert grew("forward_wait") == len(frames)  # one a frame that forwarded
    # scrape: the gauges read the same ints, the failed series all four
    cluster.servers[0]._refresh_store_metrics()
    assert REGISTRY.get_sample_value("peer_forward_items_total") == counts1["items"]
    assert REGISTRY.get_sample_value("peer_forward_batches_total") == counts1["batches"]
    for reason in peers_mod.FORWARD_FAIL_REASONS:
        assert REGISTRY.get_sample_value(
            "peer_forward_failed_items_total", {"reason": reason}
        ) == counts1["failed"][reason]
    if together > 1:
        return
    # the four stages tile the sampled peer_forward spans (one a group)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        spans = [s for t in recorder.snapshot()["traces"]
                 if t["trace_id"] not in seen and t["door"] == "geb"
                 for s in t["spans"] if s["name"] == "peer_forward"]
        if len(spans) >= groups:
            break
        time.sleep(0.05)
    assert len(spans) == groups
    assert sum(s["annotations"]["items"] for s in spans) == foreign
    spanned = sum(s["duration_ms"] for s in spans) / 1e3
    tiled = sum(grew(name, 1) for name in PER_FORWARD)
    assert 0.9 <= tiled / spanned <= 1.0, (tiled, spanned)


def test_a_missed_deadline_is_an_error_item_a_warning_and_no_resend(ring, caplog):
    """`peer_rpc:hang:n=1`: the first forward of the frame never leaves
    (whichever owner's it is: all four nodes are 127.0.0.1 here, so the
    rule cannot name a host) and its caller's deadline must save it."""
    cluster, doors = ring
    model = reference_ring4.Ring(cluster.addresses)
    frame = [item(500_000 + i, 1, "e-") for i in range(60)]
    counts0 = counts_of(cluster)
    served0 = [cluster.instance_at(i).peer_serve_batches for i in range(NODES)]
    retries0 = [REGISTRY.get_sample_value("peer_rpc_retries_total", {"peer": a})
                or 0.0 for a in cluster.addresses]
    door_conf = cluster.instance_at(0).conf.behaviors
    room, door_conf.batch_timeout = door_conf.batch_timeout, DEADLINE_S
    FAULTS.configure("peer_rpc:hang:n=1")
    try:
        with caplog.at_level(logging.WARNING, logger="gubernator_tpu.peers"):
            t = time.monotonic()
            (got,) = through_the_doors(doors[:1], [frame])
            waited = time.monotonic() - t
    finally:
        FAULTS.clear()
        door_conf.batch_timeout = room
    assert DEADLINE_S <= waited < 10.0  # the deadline, upstream's 500 ms
    failed = {model.owner(NAME, it[0]) for it, a in zip(frame, got) if a[3]}
    assert len(failed) == 1 and cluster.addresses[0] not in failed
    (victim,) = failed
    hers = [it for it in frame if model.owner(NAME, it[0]) == victim]
    assert 0 < len(hers) < len(frame)
    for it, a in zip(frame, got):
        if model.owner(NAME, it[0]) == victim:
            assert "from peer" in a[3] and "GUBER_BATCH_TIMEOUT_MS = 500 ms" in a[3]
            assert victim in a[3] and "not sent again" in a[3]
        else:
            assert a == (0, it[2], it[2] - 1, "")  # the other owners answered
    counts1 = counts_of(cluster)
    assert counts1["failed"]["deadline"] - counts0["failed"]["deadline"] == len(hers)
    assert all(counts1["failed"][r] == counts0["failed"][r]
               for r in ("breaker_open", "transport", "closed"))
    # one RPC an owner, the victim's counted as sent once and never again
    assert counts1["batches"] - counts0["batches"] == NODES - 1
    v = cluster.addresses.index(victim)
    assert cluster.instance_at(v).peer_serve_batches == served0[v]
    assert [REGISTRY.get_sample_value("peer_rpc_retries_total", {"peer": a})
            or 0.0 for a in cluster.addresses] == retries0
    warned = [r for r in caplog.records if "forward to peer" in r.getMessage()]
    assert len(warned) == 1 and warned[0].levelno == logging.WARNING
    text = warned[0].getMessage()
    assert victim in text and "(deadline)" in text and f"{len(hers)} item(s)" in text
    assert "GUBER_BATCH_TIMEOUT_MS" in text
    # the hits were applied nowhere: a peek through node 0 reads full windows
    (peek,) = through_the_doors(
        doors[:1], [[(k, 0, li, d, a) for k, _, li, d, a in hers]])
    assert [p[:3] for p in peek] == [(0, it[2], it[2]) for it in hers]


def test_the_promoters_programs_are_built_where_the_table_is(caplog):
    """R-A9's repair: the top-K table's two device programs are traced
    and built at its construction (before Ready), not by the first
    batch that carries hits; and the warm-up leaves the table empty."""
    import jax
    import numpy as np

    from gubernator_tpu.serve import promoter

    table = promoter.DeviceTopK(2048)
    assert table.top_with_payload(8) == [] and not table._dirty
    built = promoter._topk_update_jit()._cache_size()
    assert built >= 1
    with jax.log_compiles(), caplog.at_level(logging.WARNING, logger="jax"):
        table.observe_arrays(np.array([7, 9], np.uint64),
                             np.array([2, 1], np.int64), {7: (10, 1000)})
        table.decay(0)
        top = table.top_with_payload(2)
    assert [(k, c, p) for k, c, _, p in top] == [(7, 2, (10, 1000)), (9, 1, None)]
    assert promoter._topk_update_jit()._cache_size() == built
    assert not [r for r in caplog.records if "Finished tracing" in r.getMessage()
                or "Compiling" in r.getMessage()]


class _Rpc(grpc.RpcError):
    def __init__(self, code):
        self._code = code

    def code(self):
        return self._code


@pytest.mark.parametrize("exc,reason", [
    (asyncio.TimeoutError(), "deadline"),
    (peers_mod.PeerDeadlineError("past the deadline"), "deadline"),
    (_Rpc(grpc.StatusCode.DEADLINE_EXCEEDED), "deadline"),
    (BreakerOpenError("open"), "breaker_open"),
    (_Rpc(grpc.StatusCode.UNAVAILABLE), "transport"),
    (FaultError("injected"), "transport"),
    (RuntimeError("peer responded with mismatched rate limit list size"), "transport"),
])
def test_a_failed_forward_is_counted_under_its_reason(exc, reason):
    assert reason in peers_mod.FORWARD_FAIL_REASONS
    assert peers_mod.fail_reason(exc) == reason


def test_a_client_closed_under_its_callers_counts_them_as_closed():
    class _Never(WireDoor):
        async def GetPeerRateLimits(self, pb_req, timeout=None):
            await asyncio.Event().wait()

    async def run():
        counts = peers_mod.ForwardCounts()
        c = peers_mod.PeerClient(BehaviorConfig(), "10.0.0.9:81", counts=counts)
        c.stub = _Never()
        c._flusher = asyncio.ensure_future(c._run())
        reqs = [to_req(item(i, 1)) for i in range(5)]
        first = asyncio.ensure_future(c.get_peer_rate_limits_grouped(reqs[:2]))
        await asyncio.sleep(0.05)  # its RPC is out
        queued = asyncio.ensure_future(c.get_peer_rate_limits_grouped(reqs[2:]))
        await asyncio.sleep(0.05)
        await c.close()
        for fut in (first, queued):
            with pytest.raises(RuntimeError, match="closed"):
                await fut
        with pytest.raises(RuntimeError, match="is closed"):
            await c.get_peer_rate_limits_grouped(reqs[:1])
        return counts

    counts = asyncio.run(run())
    assert (counts.batches, counts.items) == (1, 2)
    assert counts.failed == {"deadline": 0, "breaker_open": 0, "transport": 0,
                             "closed": 6}


def test_one_daemon_records_no_forward_and_exports_zeroes():
    """A node that owns every key (the six one-daemon cells): no
    forward_* sample, and the three families are exported at 0."""
    (port,) = free_ports(1)
    cluster = LocalCluster([f"127.0.0.1:{port}"])
    cluster.start(timeout=120.0)
    try:
        before = stage_counts()
        from gubernator_tpu.client import V1Client

        client = V1Client(f"127.0.0.1:{port}")
        try:
            resps = client.get_rate_limits(
                [to_req(item(i, 1, "solo-")) for i in range(50)], timeout=30.0)
        finally:
            client.close()
        assert all(not r.error for r in resps)
        after = stage_counts()
        for name in (*PER_FORWARD, "forward_wait"):
            assert after.get(name, (0,))[0] == before.get(name, (0,))[0]
        inst = cluster.instance_at(0)
        assert (inst.peer_forward.batches, inst.peer_forward.items) == (0, 0)
        cluster.servers[0]._refresh_store_metrics()
        assert REGISTRY.get_sample_value("peer_forward_batches_total") == 0
        assert REGISTRY.get_sample_value("peer_forward_items_total") == 0
        for reason in peers_mod.FORWARD_FAIL_REASONS:
            assert REGISTRY.get_sample_value(
                "peer_forward_failed_items_total", {"reason": reason}) == 0
    finally:
        cluster.stop()


def test_the_ring_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; sys.path.insert(0, sys.argv[1]); import reference_ring4; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('gubernator_tpu', 'jax', 'jaxlib', 'numpy')]; print(bad)")
    out = subprocess.run([sys.executable, "-c", code, BENCH], text=True,
                         capture_output=True, check=True)
    assert out.stdout.strip() == "[]"
