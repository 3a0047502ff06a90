"""The `global-mesh4` deployment's served path on the CPU (PR 26): a
seeded stream through the real GEB door -> Instance -> DeviceBatcher ->
MeshBackend on 4 simulated devices, the GLOBAL broadcast loop running,
item by item equal to core/oracle.py AND to the benchmark's plain
reference of one-node GLOBAL semantics (benchmark/reference_global.py).

The stream has what the cell sends: both algorithms, the three limit
classes of benchmark/traffic/geb-frames-global.json, in-batch
duplicates, keys driven over their limit, peeks, 10% of the key ids
Behavior GLOBAL, and frames of three kinds — mixed (plain + GLOBAL) and
GLOBAL only (string frames: since PR 27 the string->array fold serves
them, the node owning every key) and plain only (pre-hashed fast frames).
The clock stands still (the r10 fake-clock pattern), so every answer is
exact whatever the windows' lengths. After the stream every key's window
is read back and equals the reference's: no broadcast peek moved a
counter. The counters PRs 26 and 27 added are held to hand-counted
values on one crafted frame.
"""

import asyncio
import os
import random
import sys
import time

import jax
import pytest

from _util import free_ports
from gubernator_tpu.api.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core import oracle
from gubernator_tpu.core.cache import LRUCache
from gubernator_tpu.core.hashing import slot_hash_batch
from gubernator_tpu.core.sketches import SketchConfig
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.parallel.sharded import owner_of_np
from gubernator_tpu.serve.backends import MeshBackend
from gubernator_tpu.serve.metrics import REGISTRY
from gubernator_tpu.serve.stages import STAGES

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
)
import reference_global  # noqa: E402  (the benchmark's plain reference)

T0 = 1_700_000_000_000
SHARDS = 4
LADDER = (64, 256)
SYNC_WAIT = 0.01
N_IDS = 60
CLASSES = ((100, 60_000), (10, 1_000), (1000, 3_600_000))  # the traffic's
COUNTERS = (
    "mesh_shard_rows_total", "mesh_shard_slots_total",
    "mesh_shard_max_rows_total", "edge_object_items_total",
    "edge_fast_items_total", "edge_folded_items_total",
    "edge_folded_global_items_total",
    "global_peek_rows_total", "global_broadcast_keys_total",
)


def is_global(i: int) -> bool:
    return i % 10 == 3  # 10% of the key ids, whatever class or algorithm


def req(i: int, hits: int, name: str = "mesh4") -> RateLimitReq:
    limit, duration = CLASSES[i % 7 % 3]
    return RateLimitReq(
        name=name, unique_key=f"k{i}", hits=hits, limit=limit,
        duration=duration, algorithm=Algorithm(i % 2),
        behavior=Behavior.GLOBAL if is_global(i) else Behavior.BATCHING,
    )


def stream(seed: int, frames: int = 230):
    """[[RateLimitReq]]: frame kind by turn (mixed, plain only, GLOBAL
    only); within a frame a key always carries the same hits (the
    program's rule for same-key items of one batch equals one-by-one
    service exactly then: benchmark/check.py checked_sequence)."""
    rng = random.Random(seed)
    pools = (
        list(range(N_IDS)),
        [i for i in range(N_IDS) if not is_global(i)],
        [i for i in range(N_IDS) if is_global(i)],
    )
    out = []
    for f in range(frames):
        pool = pools[f % 3]
        hits_of = {}
        frame = []
        for _ in range(rng.randrange(12, 33)):
            # the 10/1 s class and a few hot ids are driven over
            i = rng.choice(pool[:8]) if rng.random() < 0.4 else rng.choice(pool)
            hits = hits_of.setdefault(i, rng.choice((1, 1, 1, 2, 0)))
            frame.append(req(i, hits))
        if f % 2 == 0:
            frame.append(frame[0])  # an in-batch duplicate
        out.append(frame)
    return out


def counters() -> dict:
    return {c: REGISTRY.get_sample_value(c) or 0.0 for c in COUNTERS}


def grown(before: dict) -> dict:
    return {c: v - before[c] for c, v in counters().items()}


def flushed_bytes() -> dict:
    """`global_flush_bytes_total` by path so far. The registry is the
    process's: under `--dist loadfile` another file's flushes (which
    file shares this worker varies with the worker count) stand in it
    already, so a test compares growth, never the level (PR 30: this
    is what failed here in the driver's six-worker run)."""
    return {path: REGISTRY.get_sample_value(
        "global_flush_bytes_total", {"path": path}) or 0.0
        for path in ("mesh", "rpc")}


def settled(before: dict, done, timeout: float = 30.0) -> dict:
    """`grown(before)` once `done(it)` holds, or as it stands at the
    deadline (the caller's assertions then say what is missing). The
    broadcast loop moves `global_peek_rows_total` BEFORE it awaits the
    peek and `global_broadcast_keys_total` after it, so a fixed sleep
    reads the pair apart whenever a flush is in flight — under six
    xdist workers a flush outlasts 10 sync waits (PR 30)."""
    deadline = time.monotonic() + timeout
    while True:
        grew = grown(before)
        if done(grew) or time.monotonic() > deadline:
            return grew
        time.sleep(SYNC_WAIT)


class FakeClock:
    def __call__(self):
        return T0


@pytest.fixture(scope="module")
def node():
    """One daemon's worth of serving stack over a 4-shard mesh, its GEB
    door open, the clock pinned at T0."""
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    clock = FakeClock()
    mp = pytest.MonkeyPatch()
    for mod in (types_mod, engine_mod, oracle):
        mp.setattr(mod, "millisecond_now", clock)
    grpc_port, geb_port = free_ports(2)
    cluster = LocalCluster(
        [f"127.0.0.1:{grpc_port}"],
        backend_factory=lambda: MeshBackend(
            StoreConfig(rows=16, slots=1 << 10),
            devices=jax.devices()[:SHARDS], buckets=LADDER,
            sketch=SketchConfig(rows=2, width=1 << 12, counter_bytes=4),
        ),
        geb_ports=[geb_port], global_sync_wait=SYNC_WAIT,
    )
    cluster.start(timeout=600.0)
    inst = cluster.servers[0].instance
    if inst.shed is not None:
        inst.shed.now_fn = clock
    try:
        yield cluster, f"127.0.0.1:{geb_port}"
    finally:
        cluster.stop()
        mp.undo()


def through_the_door(addr: str, frames):
    """Every frame over one GEB connection, one at a time; answers as
    [(status, limit, remaining, error)]."""

    async def run():
        from gubernator_tpu.client_geb import AsyncGebClient

        client = AsyncGebClient(addr)
        await client.connect()
        try:
            return [
                [(int(r.status), r.limit, r.remaining, r.error)
                 for r in await client.get_rate_limits(frame)]
                for frame in frames
            ]
        finally:
            await client.close()

    return asyncio.run(run())


def test_seeded_stream_equals_oracle_and_reference(node):
    cluster, addr = node
    frames = stream(26)
    assert sum(map(len, frames)) >= 4000
    before = counters()
    flushed_before = flushed_bytes()
    peeks_before = STAGES.snapshot()["stages"].get(
        "global_peek", {"count": 0})["count"]
    got = through_the_door(addr, frames)

    cache, ref = LRUCache(), reference_global.OwnerNode(peers=0)
    differ = []
    over = 0
    for f, (frame, answers) in enumerate(zip(frames, got)):
        for j, (r, a) in enumerate(zip(frame, answers)):
            o = oracle.get_rate_limit(cache, r, now=T0)
            p = ref.decide(r.unique_key, r.hits, r.limit, r.duration,
                           int(r.algorithm), int(r.behavior), T0)
            want = (int(o.status), o.limit, o.remaining, "")
            assert want[:3] == p[:3], (f, j, r, want, p)  # the two references
            over += a[0] == 1
            if a != want:
                differ.append((f, j, r, a, want))
        if f % 5 == 0:
            # the reference's broadcast loop flushes; nothing it peeks moves
            held = ref.windows()
            ref.broadcast(T0)
            assert ref.windows() == held and ref.sent == []
    assert not differ, differ[:5]
    assert over > 200  # keys were driven over their limit

    # the real broadcast loop ran beside the stream and peeked
    grew = settled(before, lambda g: (
        g["global_peek_rows_total"] > 0
        and g["global_broadcast_keys_total"] == g["global_peek_rows_total"]
    ))
    n_global = sum(r.behavior == Behavior.GLOBAL for fr in frames for r in fr)
    n_mixed = sum(
        len(fr) for fr in frames
        if any(r.behavior == Behavior.GLOBAL for r in fr)
    )
    assert n_global > 400
    # no frame holds a key the node does not own: none rides the object path
    assert grew["edge_object_items_total"] == 0
    assert grew["edge_folded_items_total"] == n_mixed
    assert grew["edge_folded_global_items_total"] == n_global
    assert grew["edge_fast_items_total"] == sum(map(len, frames)) - n_mixed
    assert grew["global_peek_rows_total"] > 0
    assert grew["global_broadcast_keys_total"] == grew["global_peek_rows_total"]
    assert STAGES.snapshot()["stages"]["global_peek"]["count"] > peeks_before
    # ...and found no peer: nothing was flushed, and the in-mesh psum
    # (queue_hit -> apply_global_hits) is a non-owner's path, which a
    # node that owns every key never takes
    assert flushed_bytes() == flushed_before

    # every key's window read back: no peek moved a counter
    ids = sorted({int(r.unique_key[1:]) for fr in frames for r in fr})
    peeks = [req(i, 0) for i in ids]
    got_peek = through_the_door(addr, [peeks])[0]
    want_peek = [
        ref.decide(r.unique_key, 0, r.limit, r.duration, int(r.algorithm),
                   int(r.behavior), T0)[:3] + ("",)
        for r in peeks
    ]
    assert got_peek == want_peek


def test_counters_on_one_crafted_frame(node):
    """Seven fresh keys in one frame, one of them GLOBAL: the frame folds
    into the array path as ONE device batch, and the owner's broadcast
    then peeks the one GLOBAL key in a batch of its own."""
    cluster, addr = node
    # earlier broadcasts have flushed: nothing queued, none in flight
    mgr = cluster.servers[0].instance.global_mgr
    settled(counters(), lambda g: (
        not mgr.backlog_sizes()["updates"]
        and REGISTRY.get_sample_value("global_broadcast_keys_total")
        == REGISTRY.get_sample_value("global_peek_rows_total")
    ))
    frame = [req(i, 1, name="crafted") for i in (100, 101, 102, 103, 104,
                                                 105, 106)]
    assert [r.behavior == Behavior.GLOBAL for r in frame].count(True) == 1
    owners = owner_of_np(
        slot_hash_batch([r.hash_key() for r in frame]), SHARDS)
    fullest = max(int((owners == s).sum()) for s in range(SHARDS))
    sub_rung = cluster.servers[0].instance.backend.engine.sub_buckets[0]
    before = counters()
    stack_before = STAGES.snapshot()["stages"].get(
        "shard_stack", {"count": 0})["count"]
    answers = through_the_door(addr, [frame])[0]
    assert [a[0] for a in answers] == [0] * 7
    assert settled(
        before, lambda g: g["global_broadcast_keys_total"] >= 1.0
    ) == {
        "edge_object_items_total": 0.0,
        "edge_fast_items_total": 0.0,
        "edge_folded_items_total": 7.0,
        "edge_folded_global_items_total": 1.0,
        "global_peek_rows_total": 1.0,
        "global_broadcast_keys_total": 1.0,
        # two device batches: the frame's seven rows, then the one peek
        "mesh_shard_rows_total": 7.0 + 1.0,
        "mesh_shard_slots_total": 2.0 * SHARDS * sub_rung,
        "mesh_shard_max_rows_total": fullest + 1.0,
    }
    stacks = STAGES.snapshot()["stages"]["shard_stack"]["count"]
    assert stacks - stack_before == 2


def test_merged_batches_count_who_stacked_them(node, monkeypatch):
    """PR 44: every merged device batch of the mesh is laid out per
    shard by the native merge in its one call (`mesh_native_stacks_total`
    = batches, `mesh_numpy_stacks_total` stands still); with the
    library hidden from the engine's module numpy lays them out and the
    counters trade places — the answers are the same frame for frame,
    and equal the reference."""
    import gubernator_tpu.parallel.sharded as sharded_mod

    if sharded_mod._hn is None:
        pytest.skip("libguberhash.so is absent")
    cluster, addr = node
    server = cluster.servers[0]
    engine = server.instance.backend.engine
    names = ("mesh_native_stacks_total", "mesh_numpy_stacks_total")

    def read():
        server._refresh_store_metrics()
        return [REGISTRY.get_sample_value(c) for c in names] + [
            STAGES.snapshot()["stages"].get(
                "shard_stack", {"count": 0})["count"]
        ]

    def serve(tag):
        # plain keys only: no broadcast peek adds a batch of its own
        ids = [i for i in range(N_IDS) if not is_global(i)]
        frames = [
            [req(i, 1 + (i + f) % 2, name=tag) for i in ids[f:f + 20]] * 2
            for f in range(12)
        ]
        ref = reference_global.OwnerNode(peers=0)
        want = [
            [ref.decide(r.unique_key, r.hits, r.limit, r.duration,
                        int(r.algorithm), int(r.behavior), T0)[:3] + ("",)
             for r in frame]
            for frame in frames
        ]
        before = read()
        got = through_the_door(addr, frames)
        assert got == want
        return got, [a - b for a, b in zip(read(), before)]

    assert engine.stack_implementation == "native"
    with_library, (native, numpy_, stacks) = serve("stacked-native")
    assert stacks >= 12 and (native, numpy_) == (stacks, 0)

    monkeypatch.setattr(sharded_mod, "_hn", None)
    assert engine.stack_implementation == "numpy"
    hidden, (native, numpy_, stacks) = serve("stacked-numpy")
    assert stacks >= 12 and (native, numpy_) == (0, stacks)
    assert hidden == with_library

