"""The one partitioned engine (r14): policy degenerate case, 1-vs-N
serving-pipeline identity, the sharded sketch tier's error bound, and
the in-mesh GLOBAL psum prototype.

What's pinned here:

- the sharding policy object (parallel/policy.py): the single-device
  policy is the DEGENERATE case of the same engine class, and a
  1-device mesh policy is decision-identical to it even under
  eviction pressure (same table, same kernel — only the dispatch
  wrapper differs);
- shard-count 1 vs N differential fuzz through the REAL serving
  pipeline (instance -> batcher -> arrival prep -> merged submit ->
  kernel) under the r10 fake clock: byte-identical decisions on
  exact-tier keys (no tier pressure, so sharding the table cannot
  change bucket occupancy);
- the sharded sketch tier: per-shard sub-sketches charge only their
  owner's keys, estimates never under-count, and the max overestimate
  stays within the per-shard e*N_s/width bound (N_s = that shard's
  charged total <= the global N — sharding tightens the classic
  count-min bound, never loosens it);
- apply_global_hits: the owner-charge + psum-replicate + install
  collective equals the sequential owner decide, flat == mesh.
"""

import asyncio
import math

import numpy as np
import pytest

import gubernator_tpu.core  # noqa: F401  (x64)
from gubernator_tpu.api.types import (
    Algorithm,
    PeerInfo,
    RateLimitReq,
)
from gubernator_tpu.core.sketches import SketchConfig
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.parallel.policy import ShardingPolicy
from gubernator_tpu.parallel.sharded import (
    MeshEngine,
    PartitionedEngine,
    TpuEngine,
    owner_of_np,
)
from gubernator_tpu.serve.backends import MeshBackend, TpuBackend
from gubernator_tpu.serve.config import ServerConfig
from gubernator_tpu.serve.instance import Instance

T0 = 1_700_000_000_000
ADDR = "127.0.0.1:7975"


class FakeClock:
    def __init__(self, t=T0):
        self.t = t

    def __call__(self) -> int:
        return self.t


def _pin_clock(monkeypatch, clock):
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod
    import gubernator_tpu.core.oracle as oracle_mod

    monkeypatch.setattr(types_mod, "millisecond_now", clock)
    monkeypatch.setattr(engine_mod, "millisecond_now", clock)
    monkeypatch.setattr(oracle_mod, "millisecond_now", clock)


# -- policy ------------------------------------------------------------------


def test_policy_factories_and_degenerate_shape():
    import jax

    single = ShardingPolicy.single()
    assert single.flat and single.n_shards == 1 and single.mesh is None
    assert "degenerate" in single.describe()

    mesh = ShardingPolicy.over_mesh()
    assert not mesh.flat
    assert mesh.n_shards == len(jax.devices()) == 8
    assert mesh.axes == ("shard",) and not mesh.spans_processes
    assert mesh.store_spec() == mesh.request_spec()

    two_d = ShardingPolicy.over_mesh(mesh_shape=(4, 2))
    assert two_d.axes == ("host", "chip") and two_d.hierarchical
    with pytest.raises(ValueError):
        ShardingPolicy.over_mesh(mesh_shape=(3, 2))


def test_engine_classes_are_one_implementation():
    """TpuEngine and MeshEngine are constructor shims over ONE class —
    the no-drift property the r14 unification is for."""
    assert issubclass(TpuEngine, PartitionedEngine)
    assert issubclass(MeshEngine, PartitionedEngine)
    flat = TpuEngine(StoreConfig(rows=4, slots=256), buckets=(64,))
    mesh = MeshEngine(StoreConfig(rows=4, slots=256), buckets=(64,))
    for name in (
        "decide_submit", "decide_wait", "prep_run", "merge_prepped",
        "decide_submit_merged", "decide_submit_presorted",
        "snapshot_read", "live_mask", "install_windows",
        "update_globals", "sync_globals", "apply_global_hits",
        "sketch_estimates", "promote_from_sketch", "warmup",
    ):
        assert (
            getattr(type(flat), name, None)
            is getattr(PartitionedEngine, name)
        ), f"{name} forked on TpuEngine"
        assert (
            getattr(type(mesh), name, None)
            is getattr(PartitionedEngine, name)
        ), f"{name} forked on MeshEngine"


@pytest.mark.parametrize("slots,want", [
    # 2^12 rows: rung 64's group rung and rung 256's low ones sit under
    # rows / 32, 128 rows and up reach it
    (1 << 12, {64: ["scatter"], 256: ["scatter", "scatter_sorted"]}),
    (1 << 14, {64: ["scatter"], 256: ["scatter"]}),
])
def test_writeback_forms_reads_one_shards_shape(slots, want):
    """The boot log's `form by rung`: kernels.writeback_form at the
    table shape the kernel sees — the whole table flat, ONE shard's
    rows on a mesh (`slots` sizes a shard) — over each rung's group
    rungs; the mesh lists its sub-rung ladder."""
    config = StoreConfig(rows=16, slots=slots)
    flat = TpuEngine(config, buckets=(64, 256))
    assert flat.writeback_forms() == want
    mesh = MeshEngine(config, buckets=(64, 256))
    forms = mesh.writeback_forms()
    assert tuple(forms) == mesh.sub_buckets
    assert {b: forms[b] for b in want} == want


def test_single_vs_one_shard_mesh_identical_under_pressure():
    """A 1-device mesh policy IS the degenerate case: same table
    geometry, same kernel — decisions stay byte-identical even under
    way-exhaustion pressure where an N-shard split would change bucket
    occupancy."""
    import jax

    flat = TpuEngine(StoreConfig(rows=1, slots=16), buckets=(64, 256))
    mesh1 = MeshEngine(
        StoreConfig(rows=1, slots=16),
        devices=jax.devices()[:1],
        buckets=(64, 256),
    )
    assert mesh1.n == 1
    rng = np.random.default_rng(3)
    for step in range(12):
        n = int(rng.integers(1, 120))
        kh = rng.integers(1, 1 << 63, n).astype(np.uint64)
        hits = rng.integers(0, 4, n).astype(np.int64)
        lim = np.full(n, 5, np.int64)
        dur = np.full(n, 60_000, np.int64)
        algo = rng.integers(0, 2, n).astype(np.int32)
        gnp = np.zeros(n, bool)
        a = flat.decide_arrays(kh, hits, lim, dur, algo, gnp, T0 + step)
        b = mesh1.decide_arrays(kh, hits, lim, dur, algo, gnp, T0 + step)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # pressure actually happened (1-way 16-bucket table, ~700 keys)
    assert flat.stats.snapshot()["evictions"] + flat.stats.snapshot()[
        "dropped"
    ] > 0


# -- 1-vs-N serving-pipeline differential fuzz -------------------------------


def test_shard_count_identity_through_serving_pipeline(monkeypatch):
    """Shard-count 1 vs N, byte-identical through the REAL pipeline
    (instance -> batcher -> arrival prep -> merged submit -> shard_map
    kernel) under the r10 fake clock, exact-tier keys (roomy store, so
    the N-way table split cannot change occupancy). The sketch tier is
    ON for both sides — the r14 mesh tier must keep the no-pressure
    byte-identity the flat tier has."""
    import jax

    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    def be(n_shards: int):
        store = StoreConfig(rows=16, slots=1 << 10)
        sketch = SketchConfig(rows=4, width=1 << 12)
        if n_shards == 1:
            return TpuBackend(store, buckets=(16, 64), sketch=sketch)
        return MeshBackend(
            store,
            devices=jax.devices()[:n_shards],
            buckets=(16, 64),
            sketch=sketch,
        )

    async def mk(n_shards: int):
        conf = ServerConfig(
            grpc_address=ADDR, advertise_address=ADDR,
            backend="tpu" if n_shards == 1 else "mesh",
            sketch_sync_wait=600.0,  # no promoter flush mid-fuzz
        )
        inst = Instance(conf, be(n_shards))
        inst.start()
        await inst.set_peers([PeerInfo(address=ADDR, is_owner=True)])
        return inst

    async def run():
        one = await mk(1)
        eight = await mk(8)
        assert eight.backend.engine.n == 8
        if one.shed is not None:
            one.shed.now_fn = clock
        if eight.shed is not None:
            eight.shed.now_fn = clock
        try:
            rng = np.random.default_rng(17)
            keys = [f"p{i}" for i in range(40)]
            for step in range(120):
                clock.t += int(rng.choice([0, 1, 9, 200, 2500]))
                n = int(rng.integers(1, 9))
                batch = [
                    RateLimitReq(
                        name="shardfuzz",
                        unique_key=keys[int(rng.integers(len(keys)))],
                        hits=int(rng.choice([0, 1, 1, 2, 7])),
                        limit=int(rng.choice([1, 2, 3, 50])),
                        duration=int(rng.choice([400, 2000, 60_000])),
                        algorithm=Algorithm(int(rng.integers(2))),
                    )
                    for _ in range(n)
                ]
                a = await one.get_rate_limits(batch)
                b = await eight.get_rate_limits(batch)
                for x, y, r in zip(a, b, batch):
                    assert (
                        x.status, x.limit, x.remaining, x.reset_time,
                        x.error,
                    ) == (
                        y.status, y.limit, y.remaining, y.reset_time,
                        y.error,
                    ), (step, r, x, y)
            # no tier pressure on either side: identity was exact-tier
            assert one.backend.stats()["dropped"] == 0
            assert eight.backend.stats()["dropped"] == 0
        finally:
            await one.stop()
            await eight.stop()

    asyncio.run(run())


# -- sharded sketch tier ------------------------------------------------------


def _cover_all_buckets(n_shards: int, slots: int) -> np.ndarray:
    """One immortal filler key per (shard, bucket) pair — the mesh twin
    of cli/bench_serving._filler_hashes: with every way pinned live,
    later creates are provably sketch-served (live-victim protection)."""
    from gubernator_tpu.core import hashing
    from gubernator_tpu.core.store import _BUCKET_SALT

    need = {(s, b) for s in range(n_shards) for b in range(slots)}
    out = []
    v = 1
    while need:
        kh = np.uint64((v << 32) | 9)
        arr = np.asarray([kh], np.uint64)
        s = int(owner_of_np(arr, n_shards)[0])
        b = int(
            hashing.mix64(arr ^ _BUCKET_SALT)[0] & np.uint64(slots - 1)
        )
        if (s, b) in need:
            need.remove((s, b))
            out.append(kh)
        v += 1
    return np.asarray(out, np.uint64)


def test_sharded_sketch_error_bound_zero_undercount():
    """The acceptance property on the MESH tier: every bucket of every
    shard pinned live, measured keys all sketch-served; estimates
    never under-count and the max overestimate stays within the
    per-shard e*N_s/width bound."""
    slots, width = 16, 1 << 12
    eng = MeshEngine(
        StoreConfig(rows=1, slots=slots), buckets=(64, 256, 1024),
        sketch=SketchConfig(rows=4, width=width),
    )
    fillers = _cover_all_buckets(eng.n, slots)
    nf = fillers.shape[0]
    ones_f = np.ones(nf, np.int64)
    eng.decide_arrays(
        fillers, ones_f, ones_f * 1000, ones_f * 1_000_000_000,
        np.zeros(nf, np.int32), np.zeros(nf, bool), T0,
    )
    assert eng.stats.snapshot()["dropped"] == 0

    D, LIM = 600_000, 1_000_000
    n_keys = 300
    # fingerprint range disjoint from the fillers' (high-32 bits are
    # the tag): a tag collision inside a bucket would alias a measured
    # key onto a filler's entry and decide it exactly
    meas = (
        (np.arange(1, n_keys + 1, dtype=np.uint64) + np.uint64(10_000_000))
        << np.uint64(32)
    ) | np.uint64(3)
    true = np.zeros(n_keys, np.int64)
    rng = np.random.default_rng(23)
    for step in range(6):
        hits_m = rng.integers(1, 5, n_keys).astype(np.int64)
        true += hits_m
        kh = np.concatenate([fillers, meas])
        hits = np.concatenate([np.zeros(nf, np.int64), hits_m])
        n = kh.shape[0]
        s, _, r, _ = eng.decide_arrays(
            kh, hits, np.full(n, LIM, np.int64),
            np.full(n, D, np.int64), np.zeros(n, np.int32),
            np.zeros(n, bool), T0 + 1 + step,
        )
    st = eng.stats.snapshot()
    assert st["dropped"] >= 6 * n_keys, st  # every measured decide hit the sketch
    assert st["evictions"] == 0, st  # live fillers never churned

    est = eng.sketch_estimates(meas, np.full(n_keys, D, np.int64), T0 + 50)
    under = int((est < true).sum())
    assert under == 0, f"{under} under-counts"
    # per-shard charged totals: the bound each shard's sub-sketch obeys
    owners = owner_of_np(meas, eng.n)
    over = (est - true).astype(np.int64)
    for s_i in range(eng.n):
        m = owners == s_i
        if not m.any():
            continue
        n_s = int(true[m].sum())
        bound = math.e * n_s / width
        assert over[m].max() <= max(bound, 0), (
            s_i, int(over[m].max()), bound
        )
    # and trivially within the global-N bound the flat tier documents
    assert over.max() <= math.e * int(true.sum()) / width


@pytest.mark.parametrize("algo", [2, 3], ids=["sliding", "gcra"])
def test_mesh_window_ring_pressure_is_fail_closed(algo):
    """The r21 window-ring on the 8-shard MESH tier: sliding/GCRA
    creates dropped to way exhaustion are served from the per-shard
    sub-rings and every served row is AT-LEAST-AS-RESTRICTIVE than the
    r15 bypass (the OFF engine answers each dropped create as a
    phantom-fresh window — maximally permissive). Every (shard,
    bucket) way is pinned with an immortal filler found-writer so
    measured creates provably drop in BOTH engines; the dt pool
    crosses rotation boundaries and multi-window jumps. Mirrors
    tests/test_sketch_tier.py::test_window_ring_pressure_is_fail_closed
    on the flat engine — sharding the ring (owner-charged sub-sketches,
    r14 layout) must not re-open the one-sidedness."""
    slots = 16
    mk = lambda sk: MeshEngine(  # noqa: E731
        StoreConfig(rows=1, slots=slots), buckets=(64, 256, 1024),
        sketch=SketchConfig(rows=4, width=1 << 12) if sk else None,
    )
    on, off = mk(True), mk(False)
    fillers = _cover_all_buckets(on.n, slots)
    nf = fillers.shape[0]
    ones_f = np.ones(nf, np.int64)
    for eng in (on, off):
        eng.decide_arrays(
            fillers, ones_f, ones_f * 1000, ones_f * 1_000_000_000,
            np.zeros(nf, np.int32), np.zeros(nf, bool), T0,
        )
        assert eng.stats.snapshot()["dropped"] == 0
    rng = np.random.default_rng(31)
    keyspace = 48
    pool = (
        (np.arange(1, keyspace + 1, dtype=np.uint64) + np.uint64(5_000_000))
        << np.uint64(32)
    ) | np.uint64(3)  # tag-disjoint from the fillers
    DUR, LIM = 10_000, 6
    t = T0
    diverged = 0
    for step in range(50):
        n = int(rng.integers(1, 24))
        kh_m = pool[rng.integers(0, keyspace, n)]
        hits_m = rng.choice((0, 1, 1, 1), n).astype(np.int64)
        t += int(rng.choice((0, 1, 7, 500, 2500, 12_000, 21_000)))
        kh = np.concatenate([fillers, kh_m])
        hits = np.concatenate([np.zeros(nf, np.int64), hits_m])
        lim = np.full(nf + n, LIM, np.int64)
        lim[:nf] = 1000
        dur = np.full(nf + n, DUR, np.int64)
        dur[:nf] = 1_000_000_000
        al = np.full(nf + n, algo, np.int32)
        al[:nf] = 0
        gnp = np.zeros(nf + n, bool)
        sa, _, ra, _ = on.decide_arrays(kh, hits, lim, dur, al, gnp, t)
        sb, _, rb, _ = off.decide_arrays(kh, hits, lim, dur, al, gnp, t)
        differ = (sa[nf:] != sb[nf:]) | (ra[nf:] != rb[nf:])
        diverged += int(differ.sum())
        assert (sa[nf:] >= sb[nf:]).all(), f"fail-open status @{step}"
        assert (ra[nf:] <= rb[nf:]).all(), f"fail-open remaining @{step}"
    assert diverged > 0, "mesh pressure fuzz never engaged the ring"
    st = on.stats.snapshot()
    assert st["dropped"] > 0
    assert st["evictions"] == 0, st  # live fillers never churned
    # the OFF engine (r15 bypass) never persisted a measured key
    assert not off.live_mask(pool, t).any()


def test_mesh_sketch_promoter_end_to_end():
    """Instance-level: the promoter runs on the MESH backend (fed by
    the all-shards estimate gather), promotes hot sketch keys into
    exact buckets on their owner shards, and GUBER_SKETCH=1 boots on
    GUBER_BACKEND=mesh."""
    conf = ServerConfig(
        grpc_address=ADDR, advertise_address=ADDR, backend="mesh",
        sketch_sync_wait=600.0, sketch_topk=64,
    )
    assert conf.sketch_config() is not None  # mesh carries the tier now
    backend = MeshBackend(
        StoreConfig(rows=1, slots=16), buckets=(64, 256),
        sketch=SketchConfig(rows=4, width=1 << 12),
    )
    assert backend.sketch_enabled

    async def run():
        inst = Instance(conf, backend)
        inst.start()
        await inst.set_peers([PeerInfo(address=ADDR, is_owner=True)])
        try:
            assert inst.promoter is not None
            inst.promoter.tracker._next = 0.0
            import gubernator_tpu.serve.promoter as prom_mod

            orig = prom_mod.OBSERVE_MIN_INTERVAL_S
            prom_mod.OBSERVE_MIN_INTERVAL_S = 0.0
            try:
                reqs = [
                    RateLimitReq(
                        name="mp", unique_key=f"mk{j}", hits=1,
                        limit=2, duration=600_000,
                    )
                    for j in range(160)
                ]
                for _ in range(4):
                    await inst.get_rate_limits(reqs)
            finally:
                prom_mod.OBSERVE_MIN_INTERVAL_S = orig
            assert backend.stats()["dropped"] > 0
            await inst.promoter.flush_once()
            st = inst.promoter.stats()
            assert st["promotions"] > 0, st
            promoted = np.array(
                sorted(inst.promoter._promoted), np.uint64
            )
            assert backend.engine.live_mask(promoted).any()
        finally:
            await inst.stop()

    asyncio.run(run())


# -- in-mesh GLOBAL psum prototype -------------------------------------------


def test_apply_global_hits_matches_sequential_and_installs_replicas():
    """One collective = charge aggregated GLOBAL hits on each key's
    owner shard + psum-replicate the post-charge status + install
    replicas: results equal the flat engine's sequential decide, and
    every shard answers subsequent non-owner (gnp) reads from its
    replica without re-deciding."""
    flat = TpuEngine(StoreConfig(rows=4, slots=1 << 10), buckets=(64,))
    mesh = MeshEngine(StoreConfig(rows=4, slots=1 << 10), buckets=(64,))
    n = 24
    kh = (np.arange(1, n + 1, dtype=np.uint64) << np.uint64(32)) | (
        np.uint64(11)
    )
    # keys span several shards (the point of the psum)
    assert len(set(owner_of_np(kh, mesh.n).tolist())) > 2
    hits = (np.arange(n, dtype=np.int64) % 5) + 1
    lim = np.full(n, 10, np.int64)
    dur = np.full(n, 60_000, np.int64)

    rf = flat.apply_global_hits(kh, hits, lim, dur, T0)
    rm = mesh.apply_global_hits(kh, hits, lim, dur, T0)
    for a, b in zip(rf, rm):
        np.testing.assert_array_equal(
            np.asarray(a, np.int64), np.asarray(b, np.int64)
        )
    # second application keeps charging the SAME windows (owner state
    # is authoritative, not the replicas)
    rf2 = flat.apply_global_hits(kh, hits, lim, dur, T0 + 5)
    rm2 = mesh.apply_global_hits(kh, hits, lim, dur, T0 + 5)
    for a, b in zip(rf2, rm2):
        np.testing.assert_array_equal(
            np.asarray(a, np.int64), np.asarray(b, np.int64)
        )
    np.testing.assert_array_equal(
        np.asarray(rm2[2]), np.maximum(10 - 2 * hits, 0)
    )
    # replicas: gnp peeks answer the stored status on EVERY shard
    s, l, r, t = mesh.decide_arrays(
        kh, np.zeros(n, np.int64), lim, dur, np.zeros(n, np.int32),
        np.ones(n, bool), T0 + 6,
    )
    np.testing.assert_array_equal(
        np.asarray(r, np.int64), np.asarray(rm2[2], np.int64)
    )


def test_replication_snapshot_surface_on_mesh():
    """The r11 replication snapshot read works on the mesh backend now
    (it was gated off pre-r14): token windows snapshot identically to
    the flat engine's."""
    import jax

    flat = TpuBackend(StoreConfig(rows=4, slots=256), buckets=(64,))
    mesh = MeshBackend(
        StoreConfig(rows=4, slots=256),
        devices=jax.devices(),
        buckets=(64,),
    )
    assert mesh.snapshot_read is not None
    reqs = [
        RateLimitReq(
            name="snap", unique_key=f"s{i}", hits=2, limit=9,
            duration=60_000,
        )
        for i in range(12)
    ]
    flat.decide(reqs, [False] * 12, now=T0)
    mesh.decide(reqs, [False] * 12, now=T0)
    keys = [r.hash_key() for r in reqs] + ["never-seen"]
    a = flat.snapshot_read(keys, now=T0 + 1)
    b = mesh.snapshot_read(keys, now=T0 + 1)
    assert a == b
    assert a[-1] is None and a[0] == (9, 60_000, 7, T0 + 60_000, False)


def test_flat_sync_chunks_above_ladder_top():
    """Gossip batches above max(buckets) on the FLAT policy chunk
    through the decide ladder instead of refusing (the mesh branch of
    the same method extends its ladder — one class, no behavior fork),
    and the two policies stay decision-identical across the chunk
    boundary."""
    flat = TpuEngine(StoreConfig(rows=8, slots=1 << 11), buckets=(64,))
    mesh = MeshEngine(StoreConfig(rows=8, slots=1 << 11), buckets=(64,))
    rng = np.random.default_rng(0xC0DE)
    n = 150  # > max(buckets): two full chunks + a remainder on flat
    kh = rng.integers(1, 2**63, n, np.int64).astype(np.uint64)
    ones = np.ones(n, np.int64)
    lim, dur = ones * 5, ones * 60_000
    rf = flat.apply_global_hits(kh, ones, lim, dur, T0)
    rm = mesh.apply_global_hits(kh, ones, lim, dur, T0)
    for a, b in zip(rf, rm):
        np.testing.assert_array_equal(
            np.asarray(a, np.int64), np.asarray(b, np.int64)
        )
    np.testing.assert_array_equal(
        np.asarray(rf[2], np.int64), np.full(n, 4)
    )
    # the hits=0 gossip peek path chunks through the same funnel
    flat.sync_globals(kh, lim, dur, T0 + 5)
