"""Arrival-time host-prep pipeline (r9): merge-combine equivalence.

The serving contract under test: a device batch built by MERGING the
caller groups' pre-sorted runs (arrival-time prep, serve/prep.py +
engine decide_submit_presorted) is byte-identical — padded request
fields, duplicate-key group structure, and response permutation — to
the engines' concat + full-argsort reference (decide_submit), across
mixed request-object/array groups, duplicate keys, GNP flags, saturating
values, empty groups, and carry overflow; and that a group prepped at
arrival and one prepped at the flush (no prep future) produce identical
decisions, responses slice back to the right callers, and stop()
mid-prep strands no futures.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from gubernator_tpu.api.types import Algorithm, RateLimitReq
from gubernator_tpu.core.hashing import native_lib
from gubernator_tpu.core.engine import (
    build_presorted_request,
    pad_request_sorted,
    prep_run_single,
)
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.parallel.sharded import (
    build_presorted_sharded,
    pad_request_sharded,
    prep_run_sharded,
    sub_batch_ladder,
)
from gubernator_tpu.serve.backends import MeshBackend, TpuBackend
from gubernator_tpu.serve.batcher import DeviceBatcher
from gubernator_tpu.serve.prep import merge_runs, merge_sorted_runs
from gubernator_tpu.serve.stages import STAGES

BUCKETS = (64, 256, 1024)
SLOTS = 1 << 10


def _rand_group(rng, n, dup_pool=None):
    """One caller group's array fields: duplicate-heavy keys, values
    spanning the int32 saturation boundaries, random GNP flags."""
    if dup_pool is None:
        dup_pool = rng.integers(1, 2**63, max(2 * n, 4), np.int64).astype(
            np.uint64
        )
    return dict(
        key_hash=rng.choice(dup_pool, n),
        hits=rng.integers(-(2**40), 2**40, n),
        limit=rng.integers(0, 2**40, n),
        duration=rng.integers(-5, 2**40, n),
        algo=rng.integers(0, 2, n).astype(np.int32),
        gnp=rng.random(n) < 0.3,
    )


def _concat(groups):
    return {
        k: np.concatenate([g[k] for g in groups])
        for k in ("key_hash", "hits", "limit", "duration", "algo", "gnp")
    }


def _assert_same(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype, (what, a.dtype, b.dtype)
    assert a.shape == b.shape, (what, a.shape, b.shape)
    assert np.array_equal(a, b), what


def test_merge_take_equals_stable_argsort():
    """The k-way merge's take permutation IS np.argsort(concat,
    kind='stable') of the pre-sorted runs — including empty runs and
    heavy cross-run ties."""
    rng = np.random.default_rng(0xA11)
    for trial in range(50):
        k = int(rng.integers(1, 9))
        runs = [
            np.sort(
                rng.integers(
                    0, 30, int(rng.integers(0, 40)), dtype=np.uint64
                )
            )
            for _ in range(k)
        ]
        skey, take = merge_sorted_runs(runs)
        cat = np.concatenate(runs) if runs else np.empty(0, np.uint64)
        _assert_same(take, np.argsort(cat, kind="stable"), "take")
        _assert_same(skey, cat[take], "skey")


def _merged_single(groups, force_numpy=False):
    runs = [prep_run_single(g, SLOTS) for g in groups]
    if force_numpy:
        import gubernator_tpu.serve.prep as prep_mod

        real = prep_mod._hn
        prep_mod._hn = None
        try:
            m = merge_runs(runs)
        finally:
            prep_mod._hn = real
    else:
        m = merge_runs(runs)
    n = int(sum(g["key_hash"].shape[0] for g in groups))
    req, grp, B = build_presorted_request(
        sorted(BUCKETS), m["fields"], m["skey"], n
    )
    return m, req, grp, B, n


@pytest.mark.parametrize("force_numpy", [False, True])
def test_merged_fields_byte_identical_single_device(force_numpy):
    """Merge-combined batches produce byte-identical padded request
    fields, groups, and order vs pad_request_sorted's concat+argsort
    path, across randomized mixed group counts/sizes — on BOTH the
    fused native merge (guber_merge_runs) and the numpy searchsorted
    fallback. Also pins the engine-level fused path (merge_prepped,
    which pads + derives groups natively in the same pass)."""
    from gubernator_tpu.core.engine import TpuEngine

    eng = TpuEngine(StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS)
    rng = np.random.default_rng(0xBEEF)
    for trial in range(15):
        k = int(rng.integers(1, 7))
        pool = rng.integers(1, 2**63, 64, np.int64).astype(np.uint64)
        groups = [
            _rand_group(rng, int(rng.integers(1, 200)), pool)
            for _ in range(k)
        ]
        cat = _concat(groups)
        req_ref, order_ref, grp_ref = pad_request_sorted(
            sorted(BUCKETS), SLOTS, cat["key_hash"], cat["hits"],
            cat["limit"], cat["duration"], cat["algo"], cat["gnp"],
            with_groups=True,
        )
        m, req, grp, B, n = _merged_single(groups, force_numpy)
        for f in req._fields:
            _assert_same(
                getattr(req, f), getattr(req_ref, f), f"req.{f}"
            )
        for f in grp._fields:
            _assert_same(
                getattr(grp, f), getattr(grp_ref, f), f"groups.{f}"
            )
        _assert_same(m["order"], order_ref[:n], "order")
        if not force_numpy:
            merged = eng.merge_prepped(
                [prep_run_single(g, SLOTS) for g in groups]
            )
            for f in req_ref._fields:
                _assert_same(
                    getattr(merged["req"], f), getattr(req_ref, f),
                    f"merge_prepped req.{f}",
                )
            for f in grp_ref._fields:
                _assert_same(
                    getattr(merged["groups"], f), getattr(grp_ref, f),
                    f"merge_prepped groups.{f}",
                )
            _assert_same(merged["order"], order_ref, "merge_prepped order")


def test_merged_fields_byte_identical_sharded():
    """Mesh sibling: merged runs through build_presorted_sharded match
    pad_request_sharded's output exactly (per-shard padded fields,
    local group structure, take_idx, order)."""
    rng = np.random.default_rng(0xFACE)
    sub = sub_batch_ladder(BUCKETS)
    for n_shards in (1, 3, 4):
        for trial in range(10):
            k = int(rng.integers(1, 6))
            pool = rng.integers(1, 2**63, 48, np.int64).astype(np.uint64)
            groups = [
                _rand_group(rng, int(rng.integers(1, 150)), pool)
                for _ in range(k)
            ]
            cat = _concat(groups)
            req_ref, order_ref, take_ref, grp_ref = pad_request_sharded(
                sub, SLOTS, n_shards, cat["key_hash"], cat["hits"],
                cat["limit"], cat["duration"], cat["algo"], cat["gnp"],
                with_groups=True,
            )
            runs = [
                prep_run_sharded(g, SLOTS, n_shards) for g in groups
            ]
            m = merge_runs(runs)
            req, take, grp, B_sub = build_presorted_sharded(
                sub, SLOTS, n_shards, m["fields"], m["skey"],
                m["counts"],
            )
            for f in req._fields:
                _assert_same(
                    getattr(req, f), getattr(req_ref, f), f"req.{f}"
                )
            for f in grp._fields:
                _assert_same(
                    getattr(grp, f), getattr(grp_ref, f), f"groups.{f}"
                )
            _assert_same(m["order"], order_ref, "order")
            _assert_same(take, take_ref, "take_idx")


# -- the native sharded merge against its numpy twin (PR 44) ----------------

SUB = sub_batch_ladder(BUCKETS)


def _keys_owned_by(rng, n_shards, per_shard):
    """{shard: uint64 key hashes it owns}, `per_shard` distinct each."""
    from gubernator_tpu.parallel.sharded import owner_of_np

    kh = rng.integers(1, 2**63, 64 * n_shards * per_shard, np.int64).astype(
        np.uint64
    )
    kh = np.unique(kh)
    rng.shuffle(kh)
    owner = owner_of_np(kh, n_shards)
    owned = {s: kh[owner == s][:per_shard] for s in range(n_shards)}
    assert all(v.shape[0] == per_shard for v in owned.values())
    return owned


def _group_of(rng, key_hash):
    """A caller group over the given key hashes, other fields random."""
    n = key_hash.shape[0]
    g = _rand_group(rng, n)
    g["key_hash"] = np.asarray(key_hash, np.uint64)
    return g


def _numpy_twin(runs, n_shards, sub=SUB):
    """(merged, req, take_idx, groups, B_sub): the flat merge, then the
    layout per shard in numpy (stack_shard_groups inside it)."""
    m = merge_runs(runs)
    return (m, *build_presorted_sharded(
        sub, SLOTS, n_shards, m["fields"], m["skey"], m["counts"]
    ))


def _assert_native_stack_is_the_twin(groups, n_shards, sub=SUB):
    """guber_merge_runs_sharded == the numpy twin, byte for byte in
    every output; returns the native call's result."""
    hn = native_lib()
    runs = [prep_run_sharded(g, SLOTS, n_shards) for g in groups]
    m, req, take, grp, B_sub = _numpy_twin(runs, n_shards, sub)
    got = hn.merge_runs_sharded_native(runs, n_shards, SLOTS, sub)
    assert got["B_sub"] == B_sub
    assert got["G_sub"] == grp.key_hash.shape[1]
    assert got["n"] == m["order"].shape[0]
    _assert_same(got["order"], m["order"], "order")
    _assert_same(got["take_idx"], take, "take_idx")
    _assert_same(got["counts"], np.asarray(m["counts"], np.int64), "counts")
    assert set(got["fields"]) == set(req._fields)
    for f in req._fields:
        _assert_same(got["fields"][f], getattr(req, f), f"req.{f}")
        assert got["fields"][f].flags.c_contiguous, f
    assert set(got["groups"]) == set(grp._fields)
    for f in grp._fields:
        _assert_same(got["groups"][f], getattr(grp, f), f"groups.{f}")
        assert got["groups"][f].flags.c_contiguous, f
    return got


needs_sharded_merge = pytest.mark.skipif(
    native_lib() is None,
    reason="libguberhash.so is absent (make -C gubernator_tpu/native)",
)


@needs_sharded_merge
@pytest.mark.parametrize("n_shards", [2, 4, 8])
@pytest.mark.parametrize("n_runs", [1, 2, 7, 64])
def test_native_sharded_merge_is_the_numpy_twin(n_runs, n_shards):
    """Random duplicate-heavy runs with saturating values and GNP flags,
    for 1, 2, 7 and 64 runs over 2, 4 and 8 shards."""
    rng = np.random.default_rng(0x5AD + 97 * n_runs + n_shards)
    for trial in range(6):
        pool = rng.integers(1, 2**63, 40 + 8 * trial, np.int64).astype(
            np.uint64
        )
        top = max(2, 1000 // n_runs)
        groups = [
            _rand_group(rng, int(rng.integers(1, top)), pool)
            for _ in range(n_runs)
        ]
        _assert_native_stack_is_the_twin(groups, n_shards)


def _case_empty_shard(rng, n_shards):
    owned = _keys_owned_by(rng, n_shards, 5)
    # shard 1 (a middle one where there is one) and the last draw nothing
    full = [s for s in range(n_shards) if s not in (1, n_shards - 1)]
    keys = np.concatenate([owned[s] for s in full] or [owned[0]])
    return [_group_of(rng, rng.choice(keys, 40)) for _ in range(3)]


def _case_all_but_one_shard_empty(rng, n_shards):
    owned = _keys_owned_by(rng, n_shards, 5)
    return [_group_of(rng, rng.choice(owned[n_shards - 1], 30))]


def _case_one_row_shard(rng, n_shards):
    owned = _keys_owned_by(rng, n_shards, 6)
    keys = np.concatenate(
        [owned[0][:1]] + [owned[s] for s in range(1, n_shards)]
    )
    lone = np.array([owned[0][0]], np.uint64)  # shard 0's one row
    rest = rng.choice(keys[1:], 50)
    return [_group_of(rng, rest[:25]), _group_of(rng, lone),
            _group_of(rng, rest[25:])]


def _case_hot_key_fills_a_shard(rng, n_shards):
    """The cell's LEAKY hot key: one key repeated until its shard alone
    fills a whole sub-rung, the other shards a few rows each."""
    owned = _keys_owned_by(rng, n_shards, 4)
    hot = owned[n_shards // 2][0]
    other = np.concatenate(
        [owned[s] for s in range(n_shards) if s != n_shards // 2]
    )
    rung = SUB[3]
    return [
        _group_of(rng, np.concatenate(
            [np.full(rung // 2, hot, np.uint64), rng.choice(other, 9)]
        )),
        _group_of(rng, np.full(rung - rung // 2, hot, np.uint64)),
    ]


def _fullest_shard_draws(rng, n_shards, rows):
    """Runs whose fullest shard draws exactly `rows` (distinct keys and
    repeats mixed), the others fewer."""
    owned = _keys_owned_by(rng, n_shards, 12)
    fullest = rng.choice(owned[0], rows)
    others = np.concatenate([
        rng.choice(owned[s], rows // 3) for s in range(1, n_shards)
    ])
    keys = np.concatenate([fullest, others])
    rng.shuffle(keys)
    cut = keys.shape[0] // 2
    return [_group_of(rng, keys[:cut]), _group_of(rng, keys[cut:])]


def _case_exactly_on_a_sub_rung(rng, n_shards):
    return _fullest_shard_draws(rng, n_shards, SUB[2])


def _case_one_past_a_sub_rung(rng, n_shards):
    return _fullest_shard_draws(rng, n_shards, SUB[2] + 1)


def _case_exactly_on_the_top_rung(rng, n_shards):
    return _fullest_shard_draws(rng, n_shards, SUB[-1])


def _case_duplicates_straddle_runs(rng, n_shards):
    """The same few keys in every run, each row's other fields its own:
    equal sort keys must resolve in run order, then in caller order."""
    owned = _keys_owned_by(rng, n_shards, 2)
    keys = np.concatenate([owned[s] for s in range(n_shards)])
    return [_group_of(rng, rng.choice(keys, 17)) for _ in range(5)]


def _case_single_rows(rng, n_shards):
    owned = _keys_owned_by(rng, n_shards, 1)
    return [_group_of(rng, owned[s]) for s in reversed(range(n_shards))]


def _case_empty_runs_between(rng, n_shards):
    pool = rng.integers(1, 2**63, 16, np.int64).astype(np.uint64)
    return [
        _rand_group(rng, n, pool) for n in (0, 31, 0, 0, 12, 0)
    ]


EDGE_CASES = [
    _case_empty_shard, _case_all_but_one_shard_empty, _case_one_row_shard,
    _case_hot_key_fills_a_shard, _case_exactly_on_a_sub_rung,
    _case_one_past_a_sub_rung, _case_exactly_on_the_top_rung,
    _case_duplicates_straddle_runs, _case_single_rows,
    _case_empty_runs_between,
]


@needs_sharded_merge
@pytest.mark.parametrize("n_shards", [2, 4, 8])
@pytest.mark.parametrize(
    "case", EDGE_CASES, ids=lambda c: c.__name__[len("_case_"):]
)
def test_native_sharded_merge_edge_layouts(case, n_shards):
    rng = np.random.default_rng(0xED6E + n_shards)
    groups = case(rng, n_shards)
    got = _assert_native_stack_is_the_twin(groups, n_shards)
    counts = got["counts"]
    if case is _case_empty_shard:
        assert counts[1] == 0 or n_shards == 2
        assert counts[n_shards - 1] == 0
        assert not got["fields"]["valid"][n_shards - 1].any()
    elif case is _case_all_but_one_shard_empty:
        assert (counts[:-1] == 0).all() and counts[-1] == 30
    elif case is _case_one_row_shard:
        assert counts[0] == 1
    elif case is _case_hot_key_fills_a_shard:
        assert counts.max() == got["B_sub"] == SUB[3]
        assert got["fields"]["valid"][n_shards // 2].all()
    elif case is _case_exactly_on_a_sub_rung:
        assert counts.max() == got["B_sub"] == SUB[2]
    elif case is _case_one_past_a_sub_rung:
        assert counts.max() == SUB[2] + 1 and got["B_sub"] == SUB[3]
    elif case is _case_exactly_on_the_top_rung:
        assert counts.max() == got["B_sub"] == SUB[-1]


@needs_sharded_merge
@pytest.mark.parametrize("hidden", [False, True], ids=["native", "hidden"])
def test_mesh_merge_prepped_follows_the_library(hidden, monkeypatch):
    """PartitionedEngine.merge_prepped on a mesh: with the library the
    native call lays the batch out, with it hidden the numpy twin does
    — the same dict either way — and the engine's two counters say
    which; `shard_counts` is told the same rows, slots and fullest
    shard by both."""
    import jax

    import gubernator_tpu.parallel.sharded as sharded_mod
    from gubernator_tpu.parallel.sharded import MeshEngine

    if hidden:
        # the handle the engine's module holds; the runs below are the
        # arrival-time prep's, whose sort keys the merge reads: they
        # come from the numpy twin then, bit for bit the native ones
        monkeypatch.setattr(sharded_mod, "_hn", None)
    eng = MeshEngine(
        StoreConfig(rows=4, slots=SLOTS), devices=jax.devices()[:4],
        buckets=BUCKETS,
    )
    assert eng.stack_implementation == ("numpy" if hidden else "native")
    told = []
    eng.shard_counts = lambda *a: told.append(a)
    rng = np.random.default_rng(0x44)
    batches = 5
    for _ in range(batches):
        pool = rng.integers(1, 2**63, 24, np.int64).astype(np.uint64)
        groups = [
            _rand_group(rng, int(rng.integers(1, 90)), pool)
            for _ in range(int(rng.integers(1, 5)))
        ]
        runs = [eng.prep_run(g) for g in groups]
        merged = eng.merge_prepped(runs)
        m, req, take, grp, B_sub = _numpy_twin(runs, 4, eng.sub_buckets)
        assert merged["B_sub"] == B_sub
        assert merged["n"] == m["order"].shape[0]
        _assert_same(merged["order"], m["order"], "order")
        _assert_same(merged["take_idx"], take, "take_idx")
        for f in req._fields:
            _assert_same(getattr(merged["req"], f), getattr(req, f), f)
        for f in grp._fields:
            _assert_same(getattr(merged["groups"], f), getattr(grp, f), f)
        assert told[-1] == (
            merged["n"], 4 * B_sub, int(np.max(m["counts"]))
        )
    assert (eng.native_stacks, eng.numpy_stacks) == (
        (0, batches) if hidden else (batches, 0)
    )
    assert len(told) == batches


@needs_sharded_merge
def test_batch_past_the_ladder_declines_to_the_twin_and_warns_once(
    monkeypatch, caplog
):
    """A fullest shard past the sub-rung ladder's top: the native call
    declines (None, nothing written), `merge_prepped` serves the batch
    from the numpy twin, which extends the ladder as it did and warns —
    once, however many such batches follow."""
    import logging

    import jax

    import gubernator_tpu.parallel.sharded as sharded_mod
    from gubernator_tpu.core.engine import extend_ladder
    from gubernator_tpu.parallel.sharded import MeshEngine

    monkeypatch.setattr(sharded_mod, "_warned_ladder_overflow", False)
    eng = MeshEngine(
        StoreConfig(rows=4, slots=SLOTS), devices=jax.devices()[:4],
        buckets=BUCKETS,
    )
    rng = np.random.default_rng(0x70F)
    top = max(eng.sub_buckets)
    groups = _fullest_shard_draws(rng, 4, top + 1)
    runs = [eng.prep_run(g) for g in groups]
    assert native_lib().merge_runs_sharded_native(
        runs, 4, SLOTS, eng.sub_buckets
    ) is None
    with caplog.at_level(logging.WARNING, logger="gubernator.sharded"):
        first = eng.merge_prepped(runs)
        second = eng.merge_prepped(runs)
    warned = [r for r in caplog.records if "exceeds the configured ladder"
              in r.getMessage()]
    assert len(warned) == 1
    want_rung = extend_ladder(eng.sub_buckets, top + 1)[-1]
    assert first["B_sub"] == second["B_sub"] == want_rung > top
    assert first["req"].key_hash.shape == (4, want_rung)
    assert (eng.native_stacks, eng.numpy_stacks) == (0, 2)
    # a batch that fits goes back to the native call
    eng.merge_prepped([eng.prep_run(_rand_group(rng, 50))])
    assert (eng.native_stacks, eng.numpy_stacks) == (1, 2)


def test_engine_presorted_matches_concat_argsort_end_to_end():
    """Twin engines, same batches, same clock: one decides via the
    engine's concat + argsort reference (decide_submit), the other via
    the served path, prep + merge (decide_submit_merged). Every
    response array — and therefore every store mutation — must be
    identical."""
    be_a = TpuBackend(StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS)
    be_b = TpuBackend(StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS)
    rng = np.random.default_rng(0xD0)
    now = 1_700_000_000_000
    for step in range(8):
        k = int(rng.integers(1, 5))
        pool = rng.integers(1, 2**63, 32, np.int64).astype(np.uint64)
        groups = [
            _rand_group(rng, int(rng.integers(1, 120)), pool)
            for _ in range(k)
        ]
        cat = _concat(groups)
        ra = be_a.engine.decide_wait(
            be_a.engine.decide_submit(now=now, **cat)
        )
        merged = be_b.merge_prepped(
            [be_b.prep_group(dict(g)) for g in groups]
        )
        rb = be_b.decide_wait_arrays(
            be_b.decide_submit_merged(merged, now=now)
        )
        for name, a, b in zip(
            ("status", "limit", "remaining", "reset"), ra, rb
        ):
            _assert_same(a, b, f"step {step} {name}")
        now += 1000


def _mk_reqs(tag, n, limit=1000):
    return [
        RateLimitReq(
            name="prep", unique_key=f"{tag}-{i}", hits=1,
            limit=limit + i, duration=60_000,
            algorithm=Algorithm.TOKEN_BUCKET,
        )
        for i in range(n)
    ]


def _array_group40():
    """One 40-row array group (no gnp) whose limit column, 5000..5039,
    echoes back, so a slicing error shows per row."""
    return dict(
        key_hash=np.arange(1, 41, dtype=np.uint64) << np.uint64(32),
        hits=np.ones(40, np.int64),
        limit=np.arange(5000, 5040, dtype=np.int64),
        duration=np.full(40, 60_000, np.int64),
        algo=np.zeros(40, np.int32),
    )


def _run(coro):
    return asyncio.run(coro)


def test_batcher_merged_slicing_mixed_groups():
    """One flush of mixed object/array groups through the merged path:
    every caller gets exactly its own rows back (limit echoes input,
    so slicing errors are visible per row). Also exercises carry
    overflow: the last group exceeds batch_limit and ships in a second
    batch."""

    async def scenario():
        be = TpuBackend(
            StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS
        )
        b = DeviceBatcher(be, batch_wait=0, batch_limit=256)
        assert b._device
        # enqueue BEFORE starting the flusher: one deterministic batch
        # composition (plus the carry group that overflows it)
        fields = _array_group40()
        tasks = [
            asyncio.ensure_future(b.decide(_mk_reqs("a", 30), [False] * 30)),
            asyncio.ensure_future(b.decide_arrays(dict(fields))),
            asyncio.ensure_future(
                b.decide(
                    _mk_reqs("g", 20, limit=77), [True] * 20
                )
            ),
            # 240 rows: pushes past batch_limit=256 -> parked (carry)
            asyncio.ensure_future(
                b.decide(_mk_reqs("c", 240, limit=9000), [False] * 240)
            ),
        ]
        await asyncio.sleep(0)  # everything enqueued
        b.start()
        r_obj, r_arr, r_gnp, r_carry = await asyncio.gather(*tasks)
        assert [r.limit for r in r_obj] == [1000 + i for i in range(30)]
        assert list(r_arr[1]) == list(range(5000, 5040))
        assert [r.limit for r in r_gnp] == [77 + i for i in range(20)]
        assert [r.limit for r in r_carry] == [
            9000 + i for i in range(240)
        ]
        await b.stop()

    _run(scenario())


@pytest.mark.parametrize("shape", ["objects", "arrays", "mixed"])
def test_every_batch_launches_through_the_merged_route(shape, monkeypatch):
    """One launch path: whatever a batch is made of, a device backend's
    batch is prepped, merged and dispatched — each span once on the
    stage clock, which is what the benchmark's submit_host /
    dispatch metrics read. The engine's argsort submit is the
    reference, never the served path, and the backend offers the
    batcher no second way to launch."""
    be = TpuBackend(StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS)
    assert not hasattr(be, "decide_submit")

    def reference_only(*a, **k):
        raise AssertionError("engine.decide_submit on the served path")

    monkeypatch.setattr(be.engine, "decide_submit", reference_only)
    fields = _array_group40()

    async def scenario():
        b = DeviceBatcher(be, batch_wait=0, batch_limit=256)
        tasks = []
        if shape != "arrays":
            tasks.append(b.decide(_mk_reqs("o", 30), [False] * 30))
        if shape != "objects":
            tasks.append(b.decide_arrays(fields))
        tasks = [asyncio.ensure_future(t) for t in tasks]
        await asyncio.sleep(0)  # everything enqueued: ONE batch
        b.start()
        out = await asyncio.gather(*tasks)
        await b.stop()
        return out

    def counts():
        st = STAGES.snapshot()["stages"]
        return [
            st.get(name, {"count": 0})["count"]
            for name in ("prep", "merge", "dispatch", "submit_host")
        ]

    before = counts()
    out = _run(scenario())
    assert [a - b for a, b in zip(counts(), before)] == [1, 1, 1, 1]
    if shape != "arrays":
        assert [r.limit for r in out[0]] == [1000 + i for i in range(30)]
    if shape != "objects":
        assert list(out[-1][1]) == list(range(5000, 5040))


def test_mesh_backend_refuses_engine_without_launch_surface():
    """Every in-tree engine carries prep_run / merge_prepped /
    decide_submit_merged; one that does not is an AttributeError when
    the backend is built, not a silently slower route."""

    class NoPrep:
        def decide_arrays(self, **kw):
            raise NotImplementedError

    with pytest.raises(AttributeError, match="prep_run"):
        MeshBackend(engine=NoPrep())


def test_arrival_vs_flush_prep_identical_decisions():
    """Same traffic, same pinned clock, twin backends: groups prepped
    at arrival vs groups that reach the flush with no prep future (what
    a stop() racing the enqueue leaves behind; the submit thread preps
    them) must produce identical responses — prepping earlier changes
    WHERE the work runs, never the result."""

    async def run_once(suppress_kick):
        be = TpuBackend(
            StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS
        )
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1024)
        if suppress_kick:
            b._kick_prep = lambda *a, **k: None
        tasks = [
            asyncio.ensure_future(
                b.decide(_mk_reqs(f"t{g}", 50), [g % 2 == 0] * 50)
            )
            for g in range(4)
        ]
        await asyncio.sleep(0)
        b.start()
        out = await asyncio.gather(*tasks)
        await b.stop()
        return [
            (r.status, r.limit, r.remaining) for rs in out for r in rs
        ]

    import gubernator_tpu.api.types as types

    real_now = types.millisecond_now
    types.millisecond_now = lambda: 1_700_000_000_000
    try:
        a = _run(run_once(False))
        f = _run(run_once(True))
    finally:
        types.millisecond_now = real_now
    assert a == f


def test_stop_mid_prep_strands_no_futures():
    """stop() while arrival preps are still running/queued: every
    caller future resolves (with an error), nothing hangs, and the
    prep pool is shut down."""

    async def scenario():
        be = TpuBackend(
            StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS
        )
        b = DeviceBatcher(
            be, batch_wait=0.05, batch_limit=1024, prep_threads=1,
        )
        real_prep = be.prep_group
        started = threading.Event()

        def slow_prep(fields):
            started.set()
            time.sleep(0.4)
            return real_prep(fields)

        be.prep_group = slow_prep
        b.start()
        fields = dict(
            key_hash=np.arange(1, 9, dtype=np.uint64) << np.uint64(32),
            hits=np.ones(8, np.int64),
            limit=np.full(8, 100, np.int64),
            duration=np.full(8, 60_000, np.int64),
            algo=np.zeros(8, np.int32),
        )
        tasks = [
            asyncio.ensure_future(b.decide_arrays(dict(fields)))
            for _ in range(4)
        ]
        await asyncio.sleep(0)
        assert started.wait(timeout=5)
        t0 = time.monotonic()
        await b.stop()
        done = await asyncio.gather(*tasks, return_exceptions=True)
        assert time.monotonic() - t0 < 5.0
        # every caller resolved; the batch the stop interrupted fails
        # with the batcher's stop error, none hang or leak
        for r in done:
            assert isinstance(r, (Exception, tuple)), r
        assert b._prep_pool._shutdown

    _run(scenario())


def test_decide_arrays_empty_group_dtype_contract():
    """The documented empty-group contract: four EMPTY int64 arrays,
    resolved synchronously, numpy imported at module level (not per
    call)."""

    async def scenario():
        be = TpuBackend(
            StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS
        )
        b = DeviceBatcher(be, batch_wait=0, batch_limit=64)
        empty = dict(
            key_hash=np.empty(0, np.uint64),
            hits=np.empty(0, np.int64),
            limit=np.empty(0, np.int64),
            duration=np.empty(0, np.int64),
            algo=np.empty(0, np.int32),
        )
        # resolves without the flusher even running (and after stop)
        out = await b.decide_arrays(empty)
        assert len(out) == 4
        for a in out:
            assert a.shape == (0,) and a.dtype == np.int64
        await b.stop()

    _run(scenario())
    import ast
    import inspect

    import gubernator_tpu.serve.batcher as batcher_mod

    # pin the hoist: no function-local numpy import left in batcher.py
    tree = ast.parse(inspect.getsource(batcher_mod))
    for node in ast.walk(tree):
        if isinstance(node, ast.Import) and node.col_offset > 0:
            assert not any(
                a.name == "numpy" for a in node.names
            ), "numpy must be imported at module level in batcher.py"


def test_merged_path_conversion_error_fails_batch_not_flusher():
    """A group whose arrival prep raises (out-of-int64 value) fails
    that batch's callers with per-item errors — and the flusher stays
    alive to serve the next batch."""

    async def scenario():
        be = TpuBackend(
            StoreConfig(rows=4, slots=SLOTS), buckets=BUCKETS
        )
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1024)
        b.start()
        bad = [
            RateLimitReq(
                name="x", unique_key="k", hits=2**200, limit=1,
                duration=1000,
            )
        ]
        with pytest.raises(Exception):
            await b.decide(bad, [False])
        # flusher survived: a good request still completes
        good = await b.decide(_mk_reqs("ok", 3), [False] * 3)
        assert [r.limit for r in good] == [1000, 1001, 1002]
        await b.stop()

    _run(scenario())
