"""One host->device transfer per batch (ISSUE 25).

`kernels.pack_inputs` lays a batch's thirteen host arrays out as one
int32 buffer and `kernels.unpack_inputs` takes it apart inside the
jitted program. Pinned here: the round trip is bit for bit at every
(request rung, group rung) the ladders can produce, flat and with a
leading shard axis; `PartitionedEngine._dispatch` hands its program
exactly ONE host array a batch on every layout while the hot-key
observer still sees the numpy request; and a seeded stream through the
engine answers as the oracle does and leaves the store bytes a second
store gets from the kernel fed the thirteen arrays directly.
"""

import functools

import numpy as np
import pytest

import jax

import gubernator_tpu.core  # noqa: F401  (enables x64)
from gubernator_tpu.api.types import Algorithm, RateLimitReq
from gubernator_tpu.core import engine as engine_mod
from gubernator_tpu.core import kernels
from gubernator_tpu.core.cache import LRUCache
from gubernator_tpu.core.engine import (
    DEEP_BUCKETS,
    DEFAULT_BUCKETS,
    buckets_for_limit,
    group_rungs,
)
from gubernator_tpu.core.kernels import (
    BatchGroups,
    BatchRequest,
    pack_inputs,
    packed_inputs_width,
    unpack_inputs,
)
from gubernator_tpu.core.oracle import get_rate_limit
from gubernator_tpu.core.sketches import derive_sketch_config
from gubernator_tpu.core.store import COUNTER_MAX, StoreConfig, new_store
from gubernator_tpu.parallel.sharded import (
    MeshEngine,
    TpuEngine,
    sub_batch_ladder,
)

T0 = 1_700_000_000_000
I32 = np.iinfo(np.int32)

# every rung the daemon's default ladder (limit 1000), the library's
# default ladder and the deep (throughput-mode) ladder can produce
FLAT_RUNGS = sorted(
    set(buckets_for_limit(1000)) | set(DEFAULT_BUCKETS) | set(DEEP_BUCKETS)
)
FLAT_PAIRS = [(b, g) for b in FLAT_RUNGS for g in group_rungs(b)]
SHARD_PAIRS = [
    (b, g)
    for b in sub_batch_ladder(buckets_for_limit(1000))
    for g in group_rungs(b)
]

_unpack = jax.jit(unpack_inputs, static_argnums=(1, 2))


def _awkward_batch(seed, lead, B, G):
    """A (req, groups, now) whose every field would show a lost bit:
    hashes with bit 63 and bit 31 set (alone and together), negative
    and saturated counters, both bool values next to each other, and a
    padding tail (valid False)."""
    rng = np.random.default_rng(seed)

    def hashes(n):
        h = rng.integers(0, 2**64, lead + (n,), np.uint64)
        special = np.array(
            [1 << 63, 1 << 31, (1 << 63) | (1 << 31), 2**64 - 1, 0,
             0xFFFFFFFF, 0xFFFFFFFF00000000, 0x7FFFFFFF80000000],
            np.uint64,
        )
        k = min(n, special.shape[0])
        h[..., :k] = special[:k]
        return h

    def counters(n):
        c = rng.integers(I32.min, I32.max, lead + (n,), np.int64)
        edge = np.array(
            [-1, I32.min, I32.max, -COUNTER_MAX, COUNTER_MAX, 0], np.int64
        )
        k = min(n, edge.shape[0])
        c[..., -k:] = edge[:k]
        return c.astype(np.int32)

    def bools(n, phase):
        b = (np.arange(n) + phase) % 2 == 0
        b[n - n // 4 :] = False  # the padding tail
        return np.broadcast_to(b, lead + (n,)).copy()

    req = BatchRequest(
        key_hash=hashes(B),
        hits=counters(B),
        limit=counters(B),
        duration=counters(B),
        algo=rng.integers(0, 4, lead + (B,)).astype(np.int32),
        gnp=bools(B, 1),
        valid=bools(B, 0),
    )
    groups = BatchGroups(
        key_hash=hashes(G),
        leader_pos=rng.integers(0, B + 1, lead + (G,)).astype(np.int32),
        end_pos=rng.integers(0, B, lead + (G,)).astype(np.int32),
        valid=bools(G, 0),
        group_id=rng.integers(0, G, lead + (B,)).astype(np.int32),
    )
    return req, groups, np.int32(-(seed % 9973 + 1) * 77_777)


def _assert_same(got, want):
    for name, g, w in zip(want._fields, got, want):
        g = np.asarray(g)
        assert g.dtype == w.dtype, name
        assert g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)


def _round_trip(lead, B, G):
    req, groups, now = _awkward_batch(B * 131 + G, lead, B, G)
    packed = pack_inputs(req, groups, now)
    assert type(packed) is np.ndarray and packed.dtype == np.int32
    assert packed.shape == lead + (packed_inputs_width(B, G),)
    assert packed.flags.c_contiguous
    got_req, got_groups, got_now = _unpack(packed, B, G)
    _assert_same(got_req, req)
    _assert_same(got_groups, groups)
    got_now = np.asarray(got_now)
    assert got_now.dtype == np.int32 and got_now.shape == lead
    assert (got_now == now).all()


@pytest.mark.parametrize("B,G", FLAT_PAIRS)
def test_round_trip_flat(B, G):
    _round_trip((), B, G)


@pytest.mark.parametrize("B,G", SHARD_PAIRS)
def test_round_trip_with_shard_axis(B, G):
    _round_trip((4,), B, G)


def test_appended_column_follows_the_fixed_layout():
    """The chain program's chain ids ride behind `now`, so the fixed
    part of the layout does not move when a column is appended."""
    B, G = 64, 64
    req, groups, now = _awkward_batch(5, (), B, G)
    chain = np.arange(B, dtype=np.int32)[::-1].copy()
    plain = pack_inputs(req, groups, now)
    packed = pack_inputs(req, groups, now, chain)
    w = packed_inputs_width(B, G)
    assert packed.shape == (w + B,)
    np.testing.assert_array_equal(packed[:w], plain)
    np.testing.assert_array_equal(packed[w:], chain)


def test_each_batch_gets_a_fresh_buffer():
    """Two batches are in flight (fetch_depth): the second pack must not
    write into memory the first transfer may still read."""
    req, groups, now = _awkward_batch(9, (), 64, 64)
    first = pack_inputs(req, groups, now)
    second = pack_inputs(req, groups, now)
    assert not np.shares_memory(first, second)
    assert not np.shares_memory(first, req.key_hash)


# -- what _dispatch hands the jitted programs -------------------------------


def _host_arrays(args):
    return [a for a in jax.tree.leaves(args) if isinstance(a, np.ndarray)]


def _spy(monkeypatch, owner, name, seen):
    orig = getattr(owner, name)

    @functools.wraps(orig)
    def spy(*args):
        seen.append(args)
        return orig(*args)

    monkeypatch.setattr(owner, name, spy)


def _stream_batch(rng, n):
    keys = rng.integers(1, 2**63, n, np.int64).astype(np.uint64)
    keys[n // 2 :] = keys[: n - n // 2]  # duplicates inside the batch
    ones = np.ones(n, np.int64)
    return dict(
        key_hash=keys, hits=ones, limit=ones * 3, duration=ones * 60_000,
        algo=(np.arange(n) % 2).astype(np.int32),
        gnp=np.zeros(n, bool),
    )


LAYOUTS = {
    "flat-exact": lambda: (
        TpuEngine(StoreConfig(rows=16, slots=1 << 8), buckets=(16, 64)),
        engine_mod, "_decide_packed_jit",
    ),
    "flat-sketch": lambda: (
        TpuEngine(
            StoreConfig(rows=16, slots=1 << 8), buckets=(16, 64),
            sketch=derive_sketch_config(mib=1, rows=0, derivation="v2"),
        ),
        engine_mod, "_decide_packed_sketch_jit",
    ),
    "mesh4-exact": lambda: (
        MeshEngine(
            StoreConfig(rows=16, slots=1 << 8),
            devices=jax.devices()[:4], buckets=(64, 256),
        ),
        None, "_step",
    ),
    "mesh4-sketch": lambda: (
        MeshEngine(
            StoreConfig(rows=16, slots=1 << 8),
            devices=jax.devices()[:4], buckets=(64, 256),
            sketch=derive_sketch_config(mib=1, rows=0, derivation="v2"),
        ),
        None, "_step_sketch",
    ),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_dispatch_hands_the_program_one_host_array(layout, monkeypatch):
    eng, owner, name = LAYOUTS[layout]()
    calls, observed = [], []
    _spy(monkeypatch, owner or eng, name, calls)
    eng.observe_hook = observed.append
    rng = np.random.default_rng(3)
    for step, n in enumerate((5, 40, 17)):
        eng.decide_arrays(**_stream_batch(rng, n), now=T0 + step)
    assert len(calls) == len(observed) == 3
    n_state = 2 if "sketch" in layout else 1
    for args, req in zip(calls, observed):
        host = _host_arrays(args)
        assert len(host) == 1, [type(a) for a in args]
        packed = host[0]
        assert packed is args[n_state] and packed.dtype == np.int32
        B, G = args[n_state + 1 :]
        lead = () if eng.flat else (eng.n,)
        assert packed.shape == lead + (packed_inputs_width(B, G),)
        # state stays on the device; the rungs are plain (static) ints
        assert all(
            isinstance(a, jax.Array) for a in jax.tree.leaves(args[:n_state])
        )
        assert type(B) is int and type(G) is int
        # the observer keeps its numpy BatchRequest, laid out like the batch
        assert isinstance(req, BatchRequest)
        assert all(type(f) is np.ndarray for f in req)
        assert req.key_hash.shape == lead + (B,)
        assert req.key_hash.dtype == np.uint64 and req.valid.dtype == bool


def test_chain_dispatch_hands_the_program_one_host_array(monkeypatch):
    eng = TpuEngine(StoreConfig(rows=16, slots=1 << 8), buckets=(16, 64))
    calls = []
    _spy(monkeypatch, engine_mod, "_decide_packed_chain_jit", calls)
    keys = np.array([11, 12, 21, 22], np.uint64) << np.uint64(32)
    ones = np.ones(4, np.int64)
    status, *_ = eng.decide_chain_arrays(
        keys, ones, ones * 2, ones * 60_000, np.zeros(4, np.int32),
        np.array([0, 0, 1, 1], np.int32), keys[[0, 0, 2, 2]], T0,
    )
    assert status.shape == (4,)
    (args,) = calls
    (packed,) = _host_arrays(args)
    B, G = args[2:]
    assert packed.shape == (packed_inputs_width(B, G) + B,)


def test_jitted_programs_keep_their_names():
    """The benchmark's trace reduction and PERF.md's idle-gap tables
    find the programs by these names."""
    assert engine_mod._decide_packed_jit.__name__ == "_decide_packed_jit"
    assert (
        engine_mod._decide_packed_sketch_jit.__name__
        == "_decide_packed_sketch_jit"
    )
    mesh, _, _ = LAYOUTS["mesh4-sketch"]()
    assert mesh._step.__name__ == "_local_decide"
    assert mesh._step_sketch.__name__ == "_local_decide_sketch"


# -- same answers, same store bytes -----------------------------------------


def _seeded_stream(seed, steps=120):
    """Batches with in-batch duplicates, keys driven over their limit,
    peeks (hits 0), both bucket algorithms and GLOBAL replica reads, in
    the shape the oracle can follow one request at a time (one hits
    draw per key and batch, an algorithm pinned per key: see
    tests/test_fuzz_differential.py)."""
    rng = np.random.default_rng(seed)
    keys = [f"k:{i}" for i in range(20)]
    now = T0
    for _ in range(steps):
        now += int(rng.choice([0, 1, 7, 50, 400, 5000]))
        per_key, batch = {}, []
        for k in rng.choice(len(keys), size=int(rng.integers(1, 14))):
            if k not in per_key:
                per_key[k] = (
                    int(rng.choice([0, 1, 1, 2, 5, 40])),
                    int(rng.choice([1, 3, 8, 30])),
                    int(rng.choice([100, 1000, 60_000])),
                )
            elif per_key[k][0] == 0:
                continue  # at most one peek of a key per batch
            hits, limit, duration = per_key[k]
            batch.append(RateLimitReq(
                name="pack", unique_key=keys[k], hits=hits, limit=limit,
                duration=duration, algorithm=Algorithm(int(k) % 2),
            ))
        yield now, batch


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_stream_matches_oracle_and_the_thirteen_argument_kernel(seed):
    eng = TpuEngine(StoreConfig(rows=16, slots=1 << 10), buckets=(16, 64))
    batches = []
    dispatch = eng._dispatch

    def record(req, groups, e_now):
        # copies: the native prep hands out views of a buffer ring
        batches.append(jax.tree.map(np.copy, (req, groups, e_now)))
        return dispatch(req, groups, e_now)

    eng._dispatch = record
    cache = LRUCache()
    for step, (now, batch) in enumerate(_seeded_stream(seed)):
        got = eng.get_rate_limits(batch, now=now)
        want = [get_rate_limit(cache, r, now=now) for r in batch]
        for i, (g, w) in enumerate(zip(got, want)):
            ctx = f"seed={seed} step={step} i={i} req={batch[i]}"
            assert (g.status, g.limit, g.remaining, g.reset_time) == (
                w.status, w.limit, w.remaining, w.reset_time
            ), ctx
    # GLOBAL non-owner replica reads (gnp rows) ride the same buffer
    gnp_keys = np.array([7, 7, 9], np.uint64) << np.uint64(33)
    ones = np.ones(3, np.int64)
    eng.decide_arrays(
        gnp_keys, ones, ones * 5, ones * 1000, np.zeros(3, np.int32),
        np.array([True, True, False]), now + 1,
    )
    assert any(b[0].gnp.any() for b in batches)

    # a second store, fed every batch's thirteen arrays through the
    # kernel directly, ends up with the same bytes
    direct = jax.jit(kernels.decide_presorted)
    store = new_store(eng.config)
    for req, groups, e_now in batches:
        store, _, _ = direct(store, req, e_now, groups)
    np.testing.assert_array_equal(
        np.asarray(eng.store.data), np.asarray(store.data)
    )
