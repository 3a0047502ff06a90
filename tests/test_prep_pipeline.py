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
