"""The `ring4-lb` deployment on the CPU (PR 46): the four device-backend
daemons of `tests/test_ring4_served.py` (the deployment's environment,
the key budget cut to a store the CPU holds) asked at ALL FOUR GEB doors
at once, as behind a load balancer: every node a door for a quarter of
the clients AND the owner of a quarter of the keys.

- seeded 1000-item string frames at the four doors CONCURRENTLY
  (several in flight a door, both algorithms, duplicates inside a
  frame, keys driven over their limit, every frame holding keys of all
  four owners), then peeks at every door: per key, the multiset of
  answers, the hits admitted and the peek equal
  `benchmark/reference_ring4_doors.py` = ONE `reference.Limiter`,
  whatever order the doors' frames reached the owners in; no error item;
- every node BOTH forwarded and served peers' batches, and over the
  ring the items forwarded are the items served;
- the batcher's rows by source (`device_batch_rows_total{source}`,
  PR 46): on every node `peer` is what its peers' batches sent to the
  device and `door` what its own door's frames did, over the ring the
  two sum to `device_batch_size_sum`, and a batch carried both;
- every frame was split by owner as columns: no frame declined, no
  item on the object path;
- an owner whose own door has 12 frames queued answers a peer's batch
  in turn, before that backlog has drained (PR 47: the batcher keeps a
  lane a source), and the answers are still one limiter's;
- with every forward TO one owner hung past the deadline
  (`GUBER_FAULT_SPEC peer_rpc:hang:host=…`) while that owner is asked
  at its own door too: error items at the other three doors for its
  keys alone, its own door answers every row, nothing is sent again,
  and the pause counter does not take a peer's wait for a loop's pause;
- `ProcessProbes` counts a held loop and a long collection once a
  tick, a wait never, and the programs built while it runs.

The clock stands still (FakeClock), so a leaky bucket leaks nothing and
every single-hit item of a key is interchangeable: that is what makes
answers under an unfixed interleaving checkable exactly. Counters are
compared by GROWTH: the registry and the stage clock are the process's.
"""

import asyncio
import json
import os
import random
import sys
import time
from collections import Counter

import pytest

from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.serve.faults import FAULTS
from gubernator_tpu.serve.metrics import REGISTRY
from gubernator_tpu.serve.stages import ProcessProbes, StageStats, bucket_of
from test_global_mesh4_served import T0
from test_ring4_served import (
    DEADLINE_S, FRAME, NAME, NODES, deployment_env, item, served_ring, to_req,
)

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
sys.path.insert(0, BENCH)
import reference_ring  # noqa: E402
import reference_ring4_doors  # noqa: E402  (the configuration's plain reference)

IN_FLIGHT = 3  # frames a door has outstanding at once


@pytest.fixture(scope="module")
def ring():
    """test_ring4_served's ring, with the process probes a daemon starts
    at Ready running on the cluster's loop and handed to every server
    for its scrape: (the cluster, its GEB doors, the probes)."""
    with open(os.path.join(BENCH, "configs", "ring4-lb.json")) as f:
        lb = json.load(f)
    assert dict(lb["env"], GUBER_STORE_TARGET_KEYS="20000") == deployment_env()
    with served_ring() as (cluster, doors):
        # half the deployment's own deadline, as run_daemon sets it
        # (served_ring has given the forwards of this process room)
        # (on a clock of their own: the process's STAGES would carry
        # loop_lag / gc_pause into every later scrape of this worker)
        probes = ProcessProbes(StageStats(), DEADLINE_S / 2)

        async def on_loop(fn):
            fn()

        cluster.run(on_loop(probes.start))
        for server in cluster.servers:
            server.probes = probes
        try:
            yield cluster, doors, probes
        finally:
            cluster.run(on_loop(probes.stop))


def frames_by_door(seed: int, per_door: int, tag: str, size: int = FRAME):
    """{door index: [[item]]}: `per_door` frames of `size` items (the
    cell's 1000 unless said) a door, every item ONE hit. A third of a frame is keys nobody else sends, the rest
    a hot set of 80 that ALL doors share, the 10-per-window ids of it
    driven over their limit; the last item repeats the first."""
    rng = random.Random(seed)
    hot = list(range(80))
    driven = [i for i in hot if i % 10 in (7, 8)][:8]
    fresh = 20_000
    out = {}
    for door in range(NODES):
        out[door] = []
        for _ in range(per_door):
            frame = []
            for n in range(size - 1):
                if n % 3 == 0:
                    i, fresh = fresh, fresh + 1
                else:
                    i = rng.choice(driven) if rng.random() < 0.4 else rng.choice(hot)
                frame.append(item(i, 1, tag))
            frame.append(frame[0])
            out[door].append(frame)
    return out


def at_all_doors(doors, frames_of_door, late=(), window=IN_FLIGHT, done_at=None):
    """Every door's frames sent at once (the doors in `late` 60 ms
    after the others), each door's over its own GEB connection as
    STRING frames, `window` of them outstanding a door; {door index:
    [[(status, limit, remaining, error)]]} in each door's own order.
    `done_at`, a dict, is handed the instant each frame's reply was
    read: {(door, frame index): monotonic seconds}."""

    async def run():
        from gubernator_tpu.client_geb import AsyncGebClient

        clients = {d: AsyncGebClient(doors[d], mode="string", window=window)
                   for d in frames_of_door}
        for c in clients.values():
            await c.connect()

        async def one(d, n, frame):
            if d in late:
                await asyncio.sleep(0.06)
            resps = await clients[d].get_rate_limits(
                [to_req(it) for it in frame], timeout=120.0)
            if done_at is not None:
                done_at[d, n] = time.monotonic()
            return [(int(r.status), r.limit, r.remaining, r.error) for r in resps]

        try:
            flat = [(d, fr) for d, frames in frames_of_door.items() for fr in frames]
            got = await asyncio.gather(*(
                one(d, n, fr) for d, frames in frames_of_door.items()
                for n, fr in enumerate(frames)))
        finally:
            for c in clients.values():
                await c.close()
        out = {d: [] for d in frames_of_door}
        for (d, _), answers in zip(flat, got):
            out[d].append(answers)
        return out

    return asyncio.run(run())


def node_counts(cluster):
    """Per node: what it forwarded, what it served for peers, its
    door's split lanes and its batcher's rows by source."""
    out = []
    for i in range(NODES):
        inst = cluster.instance_at(i)
        out.append({
            "forwarded": inst.peer_forward.items,
            "failed": dict(inst.peer_forward.failed),
            "served": inst.peer_serve_items,
            "served_batches": inst.peer_serve_batches,
            "served_shed": inst.peer_serve_shed_hits,
            "split_frames": inst.edge_split.frames,
            "owned_lane": inst.edge_split.items["owned"],
            "declined": dict(inst.edge_split.declined),
            "door_rows": inst.batcher.rows_by_source["door"],
            "peer_rows": inst.batcher.rows_by_source["peer"],
            "mixed": inst.batcher.mixed_batches,
            "overtaking": inst.batcher.groups_overtaking,
        })
    return out


def grew(after, before, key):
    return [a[key] - b[key] for a, b in zip(after, before)]


def sample(name, labels=None):
    return REGISTRY.get_sample_value(name, labels or {}) or 0.0


def one_limiter_answers(by_door, got, want):
    """Per key, the multiset of answers and the hits admitted over
    every door's replies equal the reference's summaries `want`, and
    no item is an error: (the answers by key, a key's item)."""
    answers, admitted, shape = {}, Counter(), {}
    for d, frames in by_door.items():
        for frame, replies in zip(frames, got[d]):
            assert len(replies) == len(frame)
            for it, a in zip(frame, replies):
                assert a[3] == "", (d, it, a)  # no error item
                answers.setdefault(it[0], Counter())[a[:3]] += 1
                admitted[it[0]] += a[0] == 0
                shape[it[0]] = it
    assert set(answers) == set(want)
    differ = [(k, answers[k], want[k][0]) for k in want
              if answers[k] != want[k][0] or admitted[k] != want[k][1]]
    assert not differ, differ[:3]
    return answers, shape


@pytest.mark.parametrize("seed", [46, 2**31 + 46])
def test_frames_at_four_doors_at_once_are_one_limiter_and_every_node_both(ring, seed):
    cluster, doors, _ = ring
    tag = f"lb{seed}-"
    by_door = frames_by_door(seed, IN_FLIGHT, tag)
    peers = cluster.addresses
    for frames in by_door.values():  # all four owners in every frame
        assert all({reference_ring.owner_of(f"{NAME}_{it[0]}", peers)
                    for it in fr} == set(peers) for fr in frames)
    calls = {peers[d]: frames for d, frames in by_door.items()}
    rng = random.Random(seed)
    orders = [reference_ring4_doors.round_robin(calls)]
    for _ in range(2):
        order = list(orders[0])
        rng.shuffle(order)
        orders.append(order)
    # the reference's own statement, on this very traffic: whatever the
    # interleaving, one limiter; and per key one summary for all orders
    assert all(reference_ring4_doors.same_as_one_limiter(calls, peers, o, T0, NAME)
               for o in orders)
    assert reference_ring4_doors.same_for_every_order(calls, peers, orders, T0, NAME)
    want = reference_ring4_doors.key_summaries(calls, peers, orders[0], T0, NAME)

    before, size0 = node_counts(cluster), sample("device_batch_size_sum")
    object0 = sample("edge_object_items_total")
    got = at_all_doors(doors, by_door)
    after, size1 = node_counts(cluster), sample("device_batch_size_sum")

    # (a) per key: the multiset of answers and the hits admitted
    answers, shape = one_limiter_answers(by_door, got, want)
    over = sum(n for c in answers.values() for a, n in c.items() if a[0] == 1)
    leaky = sum(it[4] == 1 for it in shape.values())
    shared = sum(len({d for d, frames in by_door.items()
                      for fr in frames for it in fr if it[0] == k}) == NODES
                 for k in answers)
    assert over > 1000 and leaky > 500 and shared >= 60
    # ... and the peek after them, asked at every door at once
    keys = sorted(answers)
    peeks = {d: [] for d in range(NODES)}
    for n, at in enumerate(range(0, len(keys), FRAME)):
        peeks[n % NODES].append(
            [(k, 0, *shape[k][2:]) for k in keys[at:at + FRAME]])
    peeked = at_all_doors(doors, peeks)
    for d in range(NODES):
        for frame, replies in zip(peeks[d], peeked[d]):
            for it, a in zip(frame, replies):
                assert a == (*want[it[0]][2], ""), (it, a, want[it[0]])

    # (b) every node forwarded AND served; the ring's sums are equal
    forwarded, served = grew(after, before, "forwarded"), grew(after, before, "served")
    assert min(forwarded) > 0 and min(served) > 0
    assert sum(forwarded) == sum(served)
    assert all(a["failed"] == b["failed"] for a, b in zip(after, before))

    # (c) rows by source: a node's peer rows are what its peers' batches
    # sent to the device, its door rows its own frames' owned lane; the
    # ring's sum is the registry's device_batch_size_sum
    peer_rows, door_rows = grew(after, before, "peer_rows"), grew(after, before, "door_rows")
    to_device = [s - h for s, h in zip(served, grew(after, before, "served_shed"))]
    assert peer_rows == to_device and min(peer_rows) > 0
    assert door_rows == grew(after, before, "owned_lane") and min(door_rows) > 0
    assert sum(peer_rows) + sum(door_rows) == size1 - size0

    # (d) every frame split by owner as columns, none declined
    assert grew(after, before, "split_frames") == [IN_FLIGHT] * NODES
    assert all(a["declined"] == b["declined"] for a, b in zip(after, before))
    assert sample("edge_object_items_total") == object0


def test_a_batch_carries_both_sources_and_the_scrape_exports_the_counts(ring):
    """The four counters through every node's scrape, and device
    batches that merged a door's rows with a peer's. Which rows share a
    launch is the scheduler's, and a group is never split: on one
    shared loop a burst's door rows are all launched before the first
    forward arrives, and a 1000-item frame's groups fill a launch with
    two. So every flush is held for 0.15 s (`device_submit:delay`, a
    slow device), the frames are 200 items, and two of the four doors
    are asked 60 ms after the others: their nodes' flushers are then
    holding a first batch of peers' rows, and what queues behind it is
    the node's own frames' rows beside more of its peers'."""
    cluster, doors, probes = ring
    before = node_counts(cluster)
    FAULTS.configure("device_submit:delay=150ms")
    try:
        for attempt in range(4):
            got = at_all_doors(doors, frames_by_door(
                900 + attempt, IN_FLIGHT, f"mix{attempt}-", size=200), late=(1, 3))
            assert all(a[3] == "" for frames in got.values()
                       for fr in frames for a in fr)
            after = node_counts(cluster)
            if sum(grew(after, before, "mixed")) >= 1:
                break
    finally:
        FAULTS.clear()
    assert sum(grew(after, before, "mixed")) >= 1
    batches = sample("device_batch_size_count")
    for i, server in enumerate(cluster.servers):
        server._refresh_store_metrics()
        mine = node_counts(cluster)[i]
        for source in ("door", "peer"):
            assert REGISTRY.get_sample_value(
                "device_batch_rows_total", {"source": source}
            ) == mine[f"{source}_rows"] > 0
        assert REGISTRY.get_sample_value("device_batches_mixed_total") == mine["mixed"]
        assert mine["mixed"] <= batches
        assert REGISTRY.get_sample_value(
            "loop_pauses_over_half_deadline_total") == probes.pauses_over
        assert REGISTRY.get_sample_value(
            "programs_built_after_ready_total") == probes.programs_built
        text = cluster.run(_stages_body(server))
        assert REGISTRY.get_sample_value(
            "device_groups_overtaking_total") == mine["overtaking"]
        assert text["batch_rows"] == {"door": mine["door_rows"],
                                      "peer": mine["peer_rows"],
                                      "mixed_batches": mine["mixed"],
                                      "groups_overtaking": mine["overtaking"]}
        assert text["process"]["pause_threshold_s"] == DEADLINE_S / 2
    # the warm-up built every program the traffic above needed
    assert probes.programs_built == 0


@pytest.mark.parametrize("seed", [46, 2**31 + 46])
def test_a_busy_door_answers_a_peers_batch_before_its_own_backlog_drains(ring, seed):
    """One node's own door holds 12 frames in flight, every flush held
    for 0.15 s (`device_submit:delay`, a slow device) so that their
    owned rows queue on its batcher; 60 ms later the three other doors
    send one frame each, a quarter of it that node's keys. Their
    forwarded batches ride the busy node's next launches in turn — the
    frames at the other doors are answered before the busy door's
    last-queued frame is — and per key the answers are still ONE
    limiter's, whatever order the owners saw."""
    cluster, doors, _ = ring
    busy, tag = seed % NODES, f"turn{seed}-"
    backlog = frames_by_door(seed, 12, tag)[busy]
    others = [d for d in range(NODES) if d != busy]
    single = frames_by_door(seed + 1, 1, tag)
    by_door = {busy: backlog, **{d: single[d] for d in others}}
    peers = cluster.addresses
    calls = {peers[d]: frames for d, frames in by_door.items()}
    order = reference_ring4_doors.round_robin(calls)
    assert reference_ring4_doors.same_as_one_limiter(calls, peers, order, T0, NAME)
    want = reference_ring4_doors.key_summaries(calls, peers, order, T0, NAME)

    before, done_at = node_counts(cluster), {}
    FAULTS.configure("device_submit:delay=150ms")
    try:
        got = at_all_doors(doors, by_door, late=others, window=12, done_at=done_at)
    finally:
        FAULTS.clear()
    after = node_counts(cluster)
    one_limiter_answers(by_door, got, want)
    # every other door's frame needed the busy owner's answer, and had
    # it before the busy door's own backlog was through
    assert grew(after, before, "served_batches")[busy] >= len(others)
    last_own = max(t for (d, _), t in done_at.items() if d == busy)
    assert all(done_at[d, 0] < last_own for d in others), done_at
    # on the busy node the peers' groups were launched while older
    # groups of its own door stayed queued; arrival order alone has
    # nothing to overtake
    assert grew(after, before, "overtaking")[busy] >= 1
    assert all(a["failed"] == b["failed"] for a, b in zip(after, before))


async def _stages_body(server):
    class _Req:
        query = {}

    return json.loads((await server._http_debug_stages(_Req())).body)


def _late_samples(snap0, snap1, at_least_s: float) -> int:
    """loop_lag and gc_pause samples between two stage snapshots that
    fell in a bucket whose LOWER edge is `at_least_s` or more."""
    first = bucket_of(at_least_s) + 1
    n = 0
    for stage in ("loop_lag", "gc_pause"):
        b1 = snap1["stages"].get(stage, {}).get("buckets")
        if not b1:
            continue
        b0 = snap0["stages"].get(stage, {}).get("buckets") or [0] * len(b1)
        n += sum(b1[first:]) - sum(b0[first:])
    return n


def test_an_owner_hung_for_its_peers_still_answers_its_own_door(ring):
    """Every forward TO the victim hangs (`host=` its port); the victim
    is asked at its own door in the same instant."""
    cluster, doors, probes = ring
    peers = cluster.addresses
    victim = 2
    port = peers[victim].rsplit(":", 1)[1]
    assert sum(port in p for p in peers) == 1
    by_door = {d: [[item(700_000 + 100 * d + i, 1, "hang-") for i in range(80)]]
               for d in range(NODES)}
    owner = {it[0]: peers.index(reference_ring.owner_of(f"{NAME}_{it[0]}", peers))
             for frames in by_door.values() for it in frames[0]}
    assert all({owner[it[0]] for it in by_door[d][0]} == set(range(NODES))
               for d in range(NODES))
    before = node_counts(cluster)
    retries0 = [sample("peer_rpc_retries_total", {"peer": a}) for a in peers]
    snap0, pauses0 = probes._stats.snapshot(), probes.pauses_over
    confs = [cluster.instance_at(i).conf.behaviors for i in range(NODES)]
    room = [c.batch_timeout for c in confs]
    for c in confs:
        c.batch_timeout = DEADLINE_S
    FAULTS.configure(f"peer_rpc:hang:host={port}")
    try:
        t = time.monotonic()
        got = at_all_doors(doors, by_door)
        waited = time.monotonic() - t
    finally:
        FAULTS.clear()
        for c, r in zip(confs, room):
            c.batch_timeout = r
    assert DEADLINE_S <= waited < 15.0
    for d in range(NODES):
        for it, a in zip(by_door[d][0], got[d][0]):
            if owner[it[0]] == victim and d != victim:
                # its peers' deadline saved them: an error item that
                # says which deadline, and that nothing is sent again
                assert "GUBER_BATCH_TIMEOUT_MS = 500 ms" in a[3], (d, it, a)
                assert peers[victim] in a[3] and "not sent again" in a[3]
            else:
                # every other owner's rows, and ALL rows at the victim's
                # own door: its owned lane and its forwards to the rest
                assert a == (0, it[2], it[2] - 1, ""), (d, it, a)
    after = node_counts(cluster)
    hers_elsewhere = [sum(owner[it[0]] == victim for it in by_door[d][0])
                      for d in range(NODES)]
    for d in range(NODES):
        want = 0 if d == victim else hers_elsewhere[d]
        assert after[d]["failed"]["deadline"] - before[d]["failed"]["deadline"] == want
        assert all(after[d]["failed"][r] == before[d]["failed"][r]
                   for r in ("breaker_open", "transport", "closed"))
    # no batch ever reached the victim as an owner, none was sent again
    assert after[victim]["served_batches"] == before[victim]["served_batches"]
    assert [sample("peer_rpc_retries_total", {"peer": a}) for a in peers] == retries0
    # the victim's keys asked elsewhere were applied nowhere; asked at
    # her own door they were, once
    hers = [it for d in range(NODES) for it in by_door[d][0] if owner[it[0]] == victim]
    (peek,) = at_all_doors(doors, {0: [[(k, 0, li, du, al) for k, _, li, du, al in hers]]})[0]
    asked_at_home = {it[0] for it in by_door[victim][0]}
    assert [p[:3] for p in peek] == [
        (0, it[2], it[2] - (it[0] in asked_at_home)) for it in hers]
    # a peer that waits is not a loop that is held: unless a tick or a
    # collection of this very window WAS that late (the shared
    # interpreter's, under load), the pause counter stands
    snap1 = probes._stats.snapshot()
    if _late_samples(snap0, snap1, 0.18) == 0:
        assert probes.pauses_over == pauses0
    if _late_samples(snap0, snap1, 0.27) > 0:
        assert probes.pauses_over > pauses0


def test_the_probes_count_a_held_loop_once_a_tick_and_never_a_wait():
    async def run():
        stats = StageStats()
        probes = ProcessProbes(stats, pause_s=0.3)
        probes.start()
        try:
            await asyncio.sleep(0.45)  # a wait: the loop runs its timers
            waited = probes.pauses_over
            time.sleep(0.7)  # the loop HELD: the next tick is 0.65 s late
            await asyncio.sleep(0.12)
            held = probes.pauses_over
            # a collection that long, on whichever thread: the same tick
            probes._on_gc("start", {})
            time.sleep(0.45)
            probes._on_gc("stop", {})
            await asyncio.sleep(0.12)
            collected = probes.pauses_over
            built = probes.programs_built
            import jax
            import jax.numpy as jnp

            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
            return waited, held, collected, probes.programs_built - built, stats
        finally:
            probes.stop()

    waited, held, collected, built, stats = asyncio.run(run())
    assert (waited, held, collected) == (0, 1, 2)
    assert built >= 1  # a program first used after start() is counted
    snap = stats.snapshot()["stages"]
    # the staged collection, and any real one the interpreter ran meanwhile
    assert snap["gc_pause"]["count"] >= 1 and snap["loop_lag"]["count"] >= 8


def test_peer_rows_ride_the_queue_entry_not_the_rows():
    """The mark is the context's at enqueue: set inside peer_rows(),
    clear outside, and gone with the block."""
    from gubernator_tpu.serve import batcher

    assert batcher._QMeta(False).peer is None
    with batcher.peer_rows():
        # a caller that cannot name its peer: the one shared source
        assert batcher._QMeta(False).peer == batcher._QMeta(True).peer == "peer"
    with batcher.peer_rows("ipv4:10.0.0.7:4242"):
        # the PeersV1 door names the sender, and the instance's own
        # peer_rows() inside it keeps that name
        with batcher.peer_rows():
            assert batcher._QMeta(False).peer == "ipv4:10.0.0.7:4242"
        assert batcher._QMeta(True).peer == "ipv4:10.0.0.7:4242"
    assert batcher._QMeta(True).peer is None


@pytest.mark.parametrize("door,source", [("v1", "door"), ("peers", "peer")])
def test_one_daemon_counts_a_calls_rows_by_the_door_it_came_through(door, source):
    """A host backend (the default in-process node, decided inline or
    by one blocking call: neither launch goes through _finish_arrays):
    a client's call is `door` rows, the same items sent as a peer would
    forward them (PeersV1/GetPeerRateLimits) are `peer` rows, and no
    batch is mixed."""
    from _util import free_ports
    from gubernator_tpu.api import convert
    from gubernator_tpu.api.grpc_glue import PeersV1Stub
    from gubernator_tpu.api.proto.gen import peers_pb2
    from gubernator_tpu.client import V1Client

    (port,) = free_ports(1)
    cluster = LocalCluster([f"127.0.0.1:{port}"])
    cluster.start(timeout=120.0)
    client = V1Client(f"127.0.0.1:{port}")
    try:
        rows = cluster.instance_at(0).batcher.rows_by_source
        size0 = sample("device_batch_size_sum")
        reqs = [to_req(item(i, 1, f"solo-{door}-")) for i in range(40)]
        if door == "v1":
            resps = client.get_rate_limits(reqs, timeout=30.0)
        else:
            resps = PeersV1Stub(client.channel).GetPeerRateLimits(
                peers_pb2.GetPeerRateLimitsReq(
                    requests=[convert.req_to_pb(r) for r in reqs]),
                timeout=30.0).rate_limits
        assert len(resps) == 40 and not any(r.error for r in resps)
        other = "peer" if source == "door" else "door"
        assert (rows[source], rows[other]) == (40, 0)
        assert cluster.instance_at(0).batcher.mixed_batches == 0
        assert sample("device_batch_size_sum") - size0 == 40
    finally:
        client.close()
        cluster.stop()


def test_the_doors_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; sys.path.insert(0, sys.argv[1]); import reference_ring4_doors; "
            "bad = [m for m in sys.modules if m.split('.')[0] in "
            "('gubernator_tpu', 'jax', 'jaxlib', 'numpy')]; print(bad)")
    out = subprocess.run([sys.executable, "-c", code, BENCH], text=True,
                         capture_output=True, check=True)
    assert out.stdout.strip() == "[]"
