"""The `exact100m` deployment's served path on the CPU (PR 30): the
daemon's own sizing from the deployment's environment, cut to a store
the CPU holds — `GUBER_BACKEND=tpu GUBER_STORE_TARGET_KEYS=20000`
instead of 100000000: the same 16 ways, the same 64/256/1024 ladder,
the same default 16 MiB sketch, 2^11 bucket rows instead of 2^24 — and
a seeded stream through the real GEB door -> Instance -> DeviceBatcher
-> TpuBackend, item by item equal to core/oracle.py AND to the
benchmark's plain reference (benchmark/reference.py).

The stream has what the cell `exact100m.geb-frames` sends: token and
leaky bucket by key id (75% / 25%), the three limit classes of
benchmark/traffic/geb-frames.json by key id, in-batch duplicates, keys
driven over their limit, peeks, and keys never seen before in every
frame. The clock stands still (the r10 fake-clock pattern of
tests/test_global_mesh4_served.py, whose door helper this file
borrows), so every answer is exact whatever the windows' lengths. After
the stream every key's window is read back and equals the reference's;
nothing was evicted and no create was dropped. What the chip adds — the
table at 2^24 rows — is held by tests/test_exact100m_store.py and, for
the real chip, tests/test_tpu_compile.py.
"""

import os
import random
import sys

import pytest

from _util import free_ports
from gubernator_tpu.api.types import Algorithm, RateLimitReq
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core import oracle
from gubernator_tpu.core.cache import LRUCache
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.serve.config import config_from_env
from gubernator_tpu.serve.metrics import REGISTRY
from gubernator_tpu.serve.server import make_backend
from test_global_mesh4_served import FakeClock, T0, through_the_door

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
)
import reference  # noqa: E402  (the benchmark's plain reference)

#: benchmark/configs/exact100m.json `env`, the key budget cut 5000-fold
ENV = {"GUBER_BACKEND": "tpu", "GUBER_STORE_TARGET_KEYS": "20000"}
N_HOT = 60
FRESH_A_FRAME = 3
CLASSES = ((100, 60_000), (10, 1_000), (1000, 3_600_000))  # the traffic's


def req(i: int, hits: int) -> RateLimitReq:
    """Limit class 70 / 20 / 10% and algorithm 75 / 25% by key id, as
    benchmark/harness/keyspace.py deals them."""
    limit, duration = CLASSES[0 if i % 10 < 7 else 1 if i % 10 < 9 else 2]
    return RateLimitReq(
        name="x100m", unique_key=f"k{i}", hits=hits, limit=limit,
        duration=duration, algorithm=Algorithm(1 if i % 4 == 3 else 0),
    )


def stream(seed: int, frames: int = 200):
    """[[RateLimitReq]]; within a frame a key always carries the same
    hits (the program's rule for same-key items of one batch equals
    one-by-one service exactly then: benchmark/check.py
    checked_sequence)."""
    rng = random.Random(seed)
    hot = list(range(N_HOT))
    # the 10-per-second ids are the ones a frozen clock drives over
    driven = [i for i in hot if i % 10 in (7, 8)][:8]
    fresh = N_HOT
    out = []
    for f in range(frames):
        hits_of = {}
        frame = []
        for _ in range(rng.randrange(12, 33)):
            i = rng.choice(driven) if rng.random() < 0.4 else rng.choice(hot)
            hits = hits_of.setdefault(i, rng.choice((1, 1, 1, 2, 0)))
            frame.append(req(i, hits))
        for _ in range(FRESH_A_FRAME):  # creates in every frame
            frame.insert(rng.randrange(len(frame)), req(fresh, 1))
            fresh += 1
        if f % 2 == 0:
            frame.append(frame[0])  # an in-batch duplicate
        out.append(frame)
    return out


def lost_state() -> dict:
    """Evictions and dropped creates so far (the registry is the
    process's: other files' tests in this worker move it too)."""
    return {c: REGISTRY.get_sample_value(c) or 0.0 for c in (
        "store_evictions_total", "store_dropped_creates_total")}


@pytest.fixture(scope="module")
def node():
    """One daemon's worth of serving stack, its backend sized by the
    daemon's own rule from the deployment's environment, its GEB door
    open, the clock pinned at T0."""
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    clock = FakeClock()
    mp = pytest.MonkeyPatch()
    for mod in (types_mod, engine_mod, oracle):
        mp.setattr(mod, "millisecond_now", clock)
    conf = config_from_env(dict(ENV))
    backend = make_backend(conf)
    grpc_port, geb_port = free_ports(2)
    cluster = LocalCluster(
        [f"127.0.0.1:{grpc_port}"], backend_factory=lambda: backend,
        geb_ports=[geb_port], device_batch_limit=conf.device_batch_limit,
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


def test_the_environment_sizes_the_deployments_shape(node):
    cluster, _ = node
    engine = cluster.servers[0].instance.backend.engine
    assert engine.config == StoreConfig(rows=16, slots=1 << 11)
    assert tuple(engine.buckets) == (64, 256, 1024)
    assert engine.sketch_config is not None
    assert engine.flat


def test_seeded_stream_equals_oracle_and_reference(node):
    cluster, addr = node
    frames = stream(30)
    assert sum(map(len, frames)) >= 4000
    lost_before = lost_state()
    got = through_the_door(addr, frames)

    cache, ref = LRUCache(), reference.Limiter()
    differ = []
    over = peeks = leaky = duplicates = 0
    for f, (frame, answers) in enumerate(zip(frames, got)):
        duplicates += len(frame) - len({r.unique_key for r in frame})
        for j, (r, a) in enumerate(zip(frame, answers)):
            o = oracle.get_rate_limit(cache, r, now=T0)
            p = ref.decide(r.unique_key, r.hits, r.limit, r.duration,
                           int(r.algorithm), T0)
            want = (int(o.status), o.limit, o.remaining, "")
            assert want[:3] == p[:3], (f, j, r, want, p)  # the two references
            over += a[0] == 1
            peeks += r.hits == 0
            leaky += r.algorithm == Algorithm.LEAKY_BUCKET
            if a != want:
                differ.append((f, j, r, a, want))
    assert not differ, differ[:5]
    assert over > 200  # keys were driven over their limit
    assert peeks > 300 and leaky > 500 and duplicates > 500

    # every key's window read back, the fresh ones included
    ids = sorted({int(r.unique_key[1:]) for fr in frames for r in fr})
    assert len(ids) >= N_HOT + FRESH_A_FRAME * len(frames) - 1
    peek_reqs = [req(i, 0) for i in ids]
    got_peek = [a for part in through_the_door(
        addr, [peek_reqs[i:i + 500] for i in range(0, len(peek_reqs), 500)])
        for a in part]
    want_peek = [
        ref.decide(r.unique_key, 0, r.limit, r.duration, int(r.algorithm),
                   T0)[:3] + ("",)
        for r in peek_reqs
    ]
    assert got_peek == want_peek
    # the guarantees' counters: every create found a way
    assert lost_state() == lost_before


def test_device_memory_gauges_at_scrape(node):
    """`store_state_bytes` is the exact table + the sketch, from the
    arrays' own shapes; the allocator's two gauges read 0 on the CPU,
    which keeps no such statistic (the benchmark's `gauge` reader then
    reports nothing rather than a ratio)."""
    cluster, _ = node
    server = cluster.servers[0]
    server._refresh_store_metrics()
    table = (1 << 11) * 16 * 32
    assert REGISTRY.get_sample_value("store_state_bytes") == table + (16 << 20)
    assert REGISTRY.get_sample_value("device_memory_peak_bytes") == 0
    assert REGISTRY.get_sample_value("device_memory_limit_bytes") == 0
    per = server.device_report()["device"]["devices"]
    assert {"bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "state_bytes"} <= set(per[0])
