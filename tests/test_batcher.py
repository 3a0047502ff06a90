"""DeviceBatcher unit tests: the pipelined submit/wait flusher.

The serving claim under test: with a device backend (one offering
decide_submit_merged and the surface that goes with it), the flusher
submits batch N+1 while batch N's fetch is still in flight (throughput
tracks max(host, device) per batch, not the sum), submits stay strictly
serialized, a failed fetch fails only its own batch, and host backends
(a blocking decide only) still work unchanged.
"""

import asyncio
import random
import threading
import time

import numpy as np
import pytest

from gubernator_tpu.api.types import RateLimitReq, RateLimitResp
from gubernator_tpu.serve import metrics
from gubernator_tpu.serve.aio import SourceLanes, collect_batch
from gubernator_tpu.serve.batcher import (
    DeviceBatcher,
    _item_source,
    _item_weight,
    _QMeta,
    peer_rows,
)


def _req(i: int) -> RateLimitReq:
    return RateLimitReq(
        name="b", unique_key=f"k{i}", hits=1, limit=10, duration=1000
    )


class PipelinedFake:
    """A device backend as the batcher sees one — the launch surface of
    serve/backends.py _ArrayOps over plain lists: a group's "sorted
    run" is its request list, a merged batch their concatenation.
    Records submit/wait interleaving; waits block until released."""

    def __init__(self):
        self.submits = []
        self.waits = []
        self.releases = {}
        self.lock = threading.Lock()
        self.concurrent_submits = 0
        self.fail_wait_for = set()

    def prep_reqs(self, reqs, gnp):
        return list(reqs)

    def prep_group(self, fields):
        raise AssertionError("these tests enqueue no array groups")

    def merge_prepped(self, runs):
        return [r for run in runs for r in run]

    def decide_submit_merged(self, reqs, now=None):
        with self.lock:
            self.concurrent_submits += 1
            assert self.concurrent_submits == 1, "submits must serialize"
        try:
            idx = len(self.submits)
            self.submits.append([r.unique_key for r in reqs])
            self.releases[idx] = threading.Event()
            return (idx, list(reqs))
        finally:
            with self.lock:
                self.concurrent_submits -= 1

    def decide_wait_arrays(self, handle):
        idx, reqs = handle
        assert self.releases[idx].wait(timeout=30), (
            f"fetch {idx} never released"
        )
        self.waits.append(idx)
        if idx in self.fail_wait_for:
            raise RuntimeError(f"fetch {idx} failed")
        n = len(reqs)
        return [0] * n, [r.limit for r in reqs], [7] * n, [0] * n

    @staticmethod
    def resps_from_arrays(status, limit, remaining, reset):
        return [
            RateLimitResp(limit=lim, remaining=rem)
            for lim, rem in zip(limit, remaining)
        ]


def test_half_built_device_backend_refused_at_construction():
    """Offering decide_submit_merged makes a backend a device backend;
    one that then lacks part of the launch surface must fail loudly in
    DeviceBatcher.__init__ — not silently take some slower route, and
    not at its first flush."""

    class NoMerge(PipelinedFake):
        merge_prepped = None

    with pytest.raises(TypeError, match="merge_prepped"):
        DeviceBatcher(NoMerge(), batch_wait=0)
    assert DeviceBatcher(PipelinedFake(), batch_wait=0)._device
    assert not DeviceBatcher(BlockingFake(), batch_wait=0)._device


@pytest.fixture()
def loop_run():
    def run(coro):
        return asyncio.run(coro)

    return run


def test_pipelined_overlap_and_order(loop_run):
    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1)
        b.start()
        t1 = asyncio.ensure_future(b.decide([_req(1)], [False]))
        # first batch submitted; its fetch now blocks on releases[0]
        while len(be.submits) < 1:
            await asyncio.sleep(0.001)
        t2 = asyncio.ensure_future(b.decide([_req(2)], [False]))
        # the second batch must be SUBMITTED while fetch 0 is in flight —
        # this is the pipelining property
        while len(be.submits) < 2:
            await asyncio.sleep(0.001)
        assert be.waits == []  # nothing fetched yet
        be.releases[0].set()
        r1 = await t1
        be.releases[1].set()
        r2 = await t2
        assert [r.remaining for r in r1] == [7]
        assert [r.remaining for r in r2] == [7]
        # both fetches resolved (their completion order is the release
        # order here, but the contract no longer promises ordering:
        # fetch_depth-wide pools complete out of order by design)
        assert sorted(be.waits) == [0, 1]
        await b.stop()

    loop_run(scenario())


def test_fetch_depth_bounds_inflight_and_allows_overlap(loop_run):
    """fetch_depth=3: three batches submit back-to-back with none
    fetched; the fourth submit stalls until one fetch completes. Fetches
    completing out of order resolve their own batches independently."""

    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1, fetch_depth=3)
        b.start()
        tasks = [
            asyncio.ensure_future(b.decide([_req(i)], [False]))
            for i in range(4)
        ]
        while len(be.submits) < 3:
            await asyncio.sleep(0.001)
        # depth reached: the 4th submit must be parked
        await asyncio.sleep(0.05)
        assert len(be.submits) == 3
        assert be.waits == []
        # release the MIDDLE batch first: it resolves alone and frees a
        # slot for batch 3
        be.releases[1].set()
        r1 = await tasks[1]
        assert [r.remaining for r in r1] == [7]
        while len(be.submits) < 4:
            await asyncio.sleep(0.001)
        for i in (0, 2, 3):
            be.releases.setdefault(i, threading.Event()).set()
        for i in (0, 2, 3):
            assert [r.remaining for r in await tasks[i]] == [7]
        await b.stop()

    loop_run(scenario())


def test_batch_limit_never_overshoots_group_parked(loop_run):
    """A group that would push the batch past batch_limit is parked and
    ships in the NEXT batch: the flattened batch the backend sees never
    exceeds the limit (the engine's bucket ladder is sized to it)."""

    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(be, batch_wait=0.02, batch_limit=5, fetch_depth=4)
        b.start()
        t1 = asyncio.ensure_future(
            b.decide([_req(i) for i in range(3)], [False] * 3)
        )
        t2 = asyncio.ensure_future(
            b.decide([_req(10 + i) for i in range(4)], [False] * 4)
        )
        # 3 + 4 > 5: the second group must ship alone in batch 2
        while len(be.submits) < 2:
            await asyncio.sleep(0.001)
            for k, ev in list(be.releases.items()):
                ev.set()
        assert [len(s) for s in be.submits] == [3, 4]
        for k, ev in list(be.releases.items()):
            ev.set()
        r1, r2 = await t1, await t2
        assert len(r1) == 3 and len(r2) == 4
        await b.stop()

    loop_run(scenario())


def test_failed_fetch_fails_only_its_batch(loop_run):
    async def scenario():
        be = PipelinedFake()
        be.fail_wait_for.add(0)
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1)
        b.start()
        t1 = asyncio.ensure_future(b.decide([_req(1)], [False]))
        while len(be.submits) < 1:
            await asyncio.sleep(0.001)
        be.releases[0].set()
        with pytest.raises(RuntimeError, match="fetch 0 failed"):
            await t1
        # the flusher survives: the next batch decides normally
        t2 = asyncio.ensure_future(b.decide([_req(2)], [False]))
        while len(be.submits) < 2:
            await asyncio.sleep(0.001)
        be.releases[1].set()
        r2 = await t2
        assert [r.remaining for r in r2] == [7]
        await b.stop()

    loop_run(scenario())


def test_stop_with_two_batches_in_flight(loop_run):
    """stop() while batch N is fetching and batch N+1 is already
    submitted (the flusher parked awaiting the previous fetch) must
    resolve BOTH batches' callers and return cleanly — not strand
    futures or re-raise CancelledError out of stop()."""

    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1)
        b.start()
        t1 = asyncio.ensure_future(b.decide([_req(1)], [False]))
        # wait until batch 1 is OWNED by a fetch task (submits alone
        # can be observed before the flusher receives the handle, and a
        # stop() landing in that window legitimately fails the batch)
        while not b._pending:
            await asyncio.sleep(0.001)
        t2 = asyncio.ensure_future(b.decide([_req(2)], [False]))
        while len(b._pending) < 2:
            await asyncio.sleep(0.001)
        stop_task = asyncio.ensure_future(b.stop())
        await asyncio.sleep(0.01)  # let the cancel land mid-pipeline
        be.releases[0].set()
        be.releases[1].set()
        await stop_task  # must not raise
        r1, r2 = await t1, await t2
        assert [r.remaining for r in r1] == [7]
        assert [r.remaining for r in r2] == [7]
        assert sorted(be.waits) == [0, 1]

    loop_run(scenario())


def test_stop_drains_inflight_fetch(loop_run):
    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1)
        b.start()
        t1 = asyncio.ensure_future(b.decide([_req(1)], [False]))
        while not b._pending:  # a fetch task owns the batch
            await asyncio.sleep(0.001)
        be.releases[0].set()
        # stop() must await the in-flight fetch so t1 resolves, not hang
        await b.stop()
        r1 = await t1
        assert [r.remaining for r in r1] == [7]

    loop_run(scenario())


def test_stop_fails_requests_parked_in_collect_window(loop_run):
    """stop() while the flusher is still collecting (parked in the
    batch_wait window with one request already popped from the queue)
    must fail that caller with an error — not strand it forever."""

    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(be, batch_wait=5.0, batch_limit=100)
        b.start()
        t1 = asyncio.ensure_future(b.decide([_req(1)], [False]))
        await asyncio.sleep(0.05)  # flusher now parked in the window
        assert be.submits == []  # nothing flushed yet
        await b.stop()
        with pytest.raises(RuntimeError, match="stopped mid-batch"):
            await t1

    loop_run(scenario())


def test_deep_batch_accumulates_while_pipeline_full(loop_run):
    """Throughput mode (deep_batch=True): while every fetch_depth slot
    is occupied a flush could not submit anyway, so the collector keeps
    accumulating; the moment a slot frees, everything accumulated ships
    as ONE deep batch instead of a run of shallow ones."""

    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(
            be, batch_wait=0, batch_limit=100, fetch_depth=1,
            deep_batch=True,
        )
        b.start()
        t0 = asyncio.ensure_future(b.decide([_req(0)], [False]))
        # batch 0 submitted; the single pipeline slot is now occupied
        while not b._pending:
            await asyncio.sleep(0.001)
        t1 = asyncio.ensure_future(b.decide([_req(1)], [False]))
        t2 = asyncio.ensure_future(b.decide([_req(2)], [False]))
        # the flusher must HOLD these (pipeline full), not submit them
        await asyncio.sleep(0.05)
        assert len(be.submits) == 1, be.submits
        be.releases[0].set()
        await t0
        # slot freed -> the held groups flush together as one deep batch
        while len(be.submits) < 2:
            await asyncio.sleep(0.001)
        assert be.submits[1] == ["k1", "k2"], be.submits
        be.releases[1].set()
        r1, r2 = await t1, await t2
        assert [r.remaining for r in r1 + r2] == [7, 7]
        await b.stop()

    loop_run(scenario())


def test_deep_batch_idle_flush_semantics_unchanged(loop_run):
    """Deep mode must not change idle-path latency: with no batch in
    flight the hold predicate is False, so a solo request flushes after
    exactly the historical drain + batch_wait window — it is never held
    hostage to traffic that may not come."""

    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(
            be, batch_wait=0, batch_limit=100_000, fetch_depth=2,
            deep_batch=True,
        )
        b.start()
        t1 = asyncio.ensure_future(b.decide([_req(1)], [False]))
        # idle pipeline: the solo request must submit promptly
        for _ in range(200):
            if be.submits:
                break
            await asyncio.sleep(0.001)
        assert be.submits == [["k1"]]
        be.releases[0].set()
        assert [r.remaining for r in await t1] == [7]
        await b.stop()

    loop_run(scenario())


def test_deep_batch_respects_batch_limit(loop_run):
    """Accumulation stops at batch_limit: a group that would overshoot
    parks in carry and ships in the NEXT deep batch."""

    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(
            be, batch_wait=0, batch_limit=3, fetch_depth=1,
            deep_batch=True,
        )
        b.start()
        t0 = asyncio.ensure_future(b.decide([_req(0)], [False]))
        while not b._pending:
            await asyncio.sleep(0.001)
        tasks = [
            asyncio.ensure_future(b.decide([_req(10 + i)], [False]))
            for i in range(4)
        ]
        await asyncio.sleep(0.05)
        assert len(be.submits) == 1
        be.releases[0].set()
        await t0
        while len(be.submits) < 2:
            await asyncio.sleep(0.001)
        assert be.submits[1] == ["k10", "k11", "k12"]  # capped at 3
        be.releases[1].set()
        while len(be.submits) < 3:
            await asyncio.sleep(0.001)
            for k, ev in list(be.releases.items()):
                ev.set()
        for t in tasks:
            await t
        await b.stop()

    loop_run(scenario())


class BlockingFake:
    """A host backend: only the blocking decide()."""

    def __init__(self):
        self.calls = 0

    def decide(self, reqs, gnp, now=None):
        self.calls += 1
        return [RateLimitResp(limit=r.limit, remaining=3) for r in reqs]


def test_host_backend_blocking_decide(loop_run):
    async def scenario():
        be = BlockingFake()
        b = DeviceBatcher(be, batch_wait=0, batch_limit=8)
        b.start()
        out = await b.decide([_req(i) for i in range(5)], [False] * 5)
        assert [r.remaining for r in out] == [3] * 5
        assert be.calls == 1  # coalesced into one backend call
        await b.stop()

    loop_run(scenario())


def test_decide_after_stop_raises(loop_run):
    """A closed batcher fails fast instead of enqueueing into a queue no
    flusher reads (the caller would await a future that never resolves)."""

    async def scenario():
        be = PipelinedFake()
        b = DeviceBatcher(be, batch_wait=0)
        b.start()
        await b.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            await b.decide([_req(0)], [False])
        with pytest.raises(RuntimeError, match="stopped"):
            await b.update_globals([("k", RateLimitResp(limit=1))])

    loop_run(scenario())


def test_update_globals_coalesce_one_backend_call(loop_run):
    """r10 satellite: all `globals` groups of one flush batch land in
    ONE backend.update_globals call (one to_thread hop instead of N),
    in enqueue order, with per-caller futures still resolved
    individually — and failed individually when the coalesced call
    raises."""

    class Recorder:
        def __init__(self):
            self.calls = []
            self.fail_next = False

        def decide(self, reqs, gnp):
            return [RateLimitResp(limit=r.limit) for r in reqs]

        def update_globals(self, updates):
            self.calls.append([k for k, _ in updates])
            if self.fail_next:
                self.fail_next = False
                raise RuntimeError("install exploded")

    async def scenario():
        be = Recorder()
        b = DeviceBatcher(be, batch_wait=0.05, batch_limit=100)
        b.start()
        # three caller groups enqueue inside one straggler window ->
        # one flush batch -> ONE backend call with all six keys
        tasks = [
            asyncio.ensure_future(
                b.update_globals(
                    [
                        (f"g{i}a", RateLimitResp(limit=1)),
                        (f"g{i}b", RateLimitResp(limit=1)),
                    ]
                )
            )
            for i in range(3)
        ]
        await asyncio.gather(*tasks)
        assert len(be.calls) == 1, be.calls
        assert be.calls[0] == [
            "g0a", "g0b", "g1a", "g1b", "g2a", "g2b"
        ]
        # a coalesced-call failure fails EVERY caller group's future
        be.fail_next = True
        fails = [
            asyncio.ensure_future(
                b.update_globals([(f"f{i}", RateLimitResp(limit=1))])
            )
            for i in range(2)
        ]
        results = await asyncio.gather(*fails, return_exceptions=True)
        assert all(
            isinstance(r, RuntimeError) and "exploded" in str(r)
            for r in results
        ), results
        await b.stop()

    loop_run(scenario())


def test_inline_fast_path_never_overtakes_collected_items(loop_run):
    """An inline decide must not run ahead of work the flusher already
    drained into its batch while parked in a batch_wait straggler
    window (the queue looks empty then, but earlier work exists)."""

    class InlineRecorder:
        inline_decide = True

        def __init__(self):
            self.order = []

        def decide(self, reqs, gnp):
            self.order.append(("D", [r.unique_key for r in reqs]))
            return [RateLimitResp(limit=r.limit) for r in reqs]

        def update_globals(self, updates):
            self.order.append(("U", [k for k, _ in updates]))

    async def scenario():
        be = InlineRecorder()
        b = DeviceBatcher(be, batch_wait=0.2, batch_limit=100)
        b.start()
        # U enters the queue; the flusher drains it into its batch and
        # parks in the 200ms straggler window (queue now empty)
        u_task = asyncio.ensure_future(
            b.update_globals([("k", RateLimitResp(limit=1))])
        )
        await asyncio.sleep(0.05)
        assert b._live_batch, "flusher should hold U in its open batch"
        # D arrives mid-window: the fast path must refuse; D coalesces
        # into the same batch and executes AFTER U
        resps = await b.decide([_req(1)], [False])
        await u_task
        await b.stop()
        assert resps[0].limit == 10
        assert [kind for kind, _ in be.order] == ["U", "D"], be.order

    loop_run(scenario())


def test_inline_fast_path_concurrency_soak(loop_run):
    """Randomized soak of the inline fast path against the flusher: many
    concurrent decide/update_globals callers with a nonzero batch_wait,
    exercising every path (fast path, coalesced batches, straggler
    windows, interleaved global installs).

    What this pins: liveness (no deadlock/hang between the fast path
    and the flusher) and exactly-once application — 300 decides on one
    key yield the complete multiset of remaining values {100..399},
    so no hit is lost or double-applied under any interleaving, and
    every caller gets a real response through stop().

    What this deliberately does NOT pin: fast-path/flusher ORDERING.
    A sorted multiset is order-invariant, and no black-box soak can
    see the overtake hazard anyway — overtaking items whose callers
    are still awaiting is a legal concurrent serialization; the guard
    exists for FIFO fairness and is pinned white-box by
    test_inline_fast_path_never_overtakes_collected_items above."""

    from gubernator_tpu.serve.backends import ExactBackend

    async def scenario():
        rng = random.Random(7)
        be = ExactBackend(1000)
        b = DeviceBatcher(be, batch_wait=0.002, batch_limit=64)
        b.start()

        LIMIT = 400

        async def one_decide(i):
            await asyncio.sleep(rng.random() * 0.05)
            r = RateLimitReq(
                name="soak", unique_key="k", hits=1, limit=LIMIT,
                duration=60_000,
            )
            return (await b.decide([r], [False]))[0]

        async def one_update(i):
            await asyncio.sleep(rng.random() * 0.05)
            # replica install for an UNRELATED key: must never perturb
            # the soak key's countdown
            await b.update_globals(
                [(f"other:{i}", RateLimitResp(limit=5, remaining=2))]
            )

        tasks = []
        for i in range(300):
            tasks.append(one_decide(i))
            if i % 7 == 0:
                tasks.append(one_update(i))
        outs = await asyncio.gather(*tasks)
        await b.stop()

        remainings = sorted(
            r.remaining for r in outs if isinstance(r, RateLimitResp)
        )
        # 300 decides, limit 400: remaining values must be exactly
        # {100..399}, each consumed once — duplicates or gaps mean a
        # lost or double-applied hit
        assert remainings == list(range(LIMIT - 300, LIMIT)), (
            remainings[:10], remainings[-10:], len(remainings)
        )

    loop_run(scenario())


# -- one lane a source, collected in turn (PR 47) ---------------------------
#
# The batcher's queue keeps a FIFO lane for this node's own doors and one
# for each peer that forwards to it (serve/aio.py SourceLanes) and takes the
# head group of each in turn. Counts and orders on a fake device backend:
# nothing here is a device number.

class RecordingDevice:
    """A device backend over tagged rows: every row of a group carries
    the group's id (an array group in `key_hash`, an object or chain
    group in its keys' prefix, a globals group in its keys), and every
    backend call is recorded as (kind, [id of each row]). `gate`, when
    set, holds the FIRST submit until it is released, so a test can
    queue a backlog behind a launch in flight."""

    def __init__(self, gate: bool = False):
        self.calls = []
        self.gate = threading.Event()
        if not gate:
            self.gate.set()

    @staticmethod
    def _gid(key: str) -> int:
        return int(key.split(":")[0][1:])

    def prep_reqs(self, reqs, gnp):
        return [self._gid(r.unique_key) for r in reqs]

    def prep_group(self, fields):
        return [int(h) for h in fields["key_hash"]]

    def merge_prepped(self, runs):
        return [g for run in runs for g in run]

    def decide_submit_merged(self, rows, now=None):
        assert self.gate.wait(timeout=30), "the gate was never released"
        self.calls.append(("decide", list(rows)))
        return rows

    def decide_wait_arrays(self, rows):
        a = np.asarray(rows, np.int64)
        return a, a, a, a

    @staticmethod
    def resps_from_arrays(status, limit, remaining, reset):
        return [RateLimitResp(limit=int(v)) for v in limit]

    def decide_chain(self, reqs):
        self.calls.append(("chain", [self._gid(r.unique_key) for r in reqs]))
        return [RateLimitResp(limit=r.limit) for r in reqs]

    def update_globals(self, updates):
        self.calls.append(("globals", [self._gid(k) for k, _ in updates]))

    def launches(self, kind="decide"):
        """Each launch of `kind` as its groups' ids in row order, and
        every group's rows in one piece."""
        out = []
        for k, rows in self.calls:
            if k != kind:
                continue
            ids = [g for i, g in enumerate(rows) if i == 0 or rows[i - 1] != g]
            assert len(ids) == len(set(ids)), f"a group was split: {ids}"
            out.append(ids)
        return out


def _arrays(gid: int, rows: int) -> dict:
    col = np.zeros(rows, np.int64)
    return {
        "key_hash": np.full(rows, gid, np.int64),
        "hits": col, "limit": col, "duration": col, "algo": col,
    }


def _objs(gid: int, rows: int, chain=()):
    return [
        RateLimitReq(
            name="b", unique_key=f"g{gid}:{i}", hits=1, limit=gid,
            duration=1000, chain=list(chain),
        )
        for i in range(rows)
    ]


def _enqueue(b: DeviceBatcher, kind: str, gid: int, rows: int, sender=None):
    """One caller's group on its way into the batcher, as a task (a
    task copies the context: the sender mark rides with it)."""
    if kind == "arrays":
        call = b.decide_arrays(_arrays(gid, rows), frame=False)
    elif kind == "decide":
        call = b.decide(_objs(gid, rows), [False] * rows)
    elif kind == "chain":
        call = b.decide_chain(_objs(gid, rows))
    else:
        call = b.update_globals(
            [(f"g{gid}:{i}", RateLimitResp(limit=1)) for i in range(rows)]
        )
    if sender is None:
        return asyncio.ensure_future(call)
    with peer_rows(sender):
        return asyncio.ensure_future(call)


def _entry(gid: int, rows: int, sender=None):
    """A queue entry as DeviceBatcher.decide_arrays builds it."""
    if sender is None:
        meta = _QMeta(False)
    else:
        with peer_rows(sender):
            meta = _QMeta(False)
    return ("decide_arrays", _arrays(gid, rows), None, meta, None)


async def _fifo_launches(bursts, limit):
    """The PARENT's collector on a schedule of bursts (each a list of
    entries queued before one collect): one asyncio.Queue drained
    oldest first, the group that does not fit parked in `carry` and
    first of the next launch — DeviceBatcher._run's lines before
    PR 47, on aio.collect_batch as it stands."""
    q, carry, out = asyncio.Queue(), [], []
    for burst in bursts:
        for e in burst:
            q.put_nowait(e)
        if q.empty() and not carry:
            continue
        out.append(await collect_batch(
            q, limit, 0, [], weight=_item_weight, carry=carry))
    while not q.empty() or carry:
        out.append(await collect_batch(
            q, limit, 0, [], weight=_item_weight, carry=carry))
    return out


async def _lane_launches(bursts, limit):
    q, out = SourceLanes(limit, _item_weight, _item_source), []
    for burst in bursts:
        for e in burst:
            q.put_nowait(e)
        if not q.empty():
            out.append(await q.collect([], 0))
    while not q.empty():
        out.append(await q.collect([], 0))
    return out


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("limit", [1000, 64])
def test_one_source_collects_as_the_one_queue_did(loop_run, seed, limit):
    """(a) ONE source queued — this node's own doors, or one peer —
    launches entry for entry what the single FIFO launched: same
    groups, same order, same batch boundaries, the group that does not
    fit first of the next launch, an oversized group alone."""
    rng = random.Random(seed)
    sender = None if seed % 2 == 0 else "ipv4:10.0.0.7:4242"
    sizes = [1, 2, 7, 259, 259, 259, 500, 843, 1000, 1500, limit, limit + 1]
    gid = 0
    bursts = []
    for _ in range(40):
        burst = []
        for _ in range(rng.randrange(0, 7)):
            gid += 1
            burst.append(_entry(gid, rng.choice(sizes), sender))
        bursts.append(burst)

    async def scenario():
        was = await _fifo_launches(bursts, limit)
        now = await _lane_launches(bursts, limit)
        assert [[id(e) for e in b] for b in now] == [
            [id(e) for e in b] for b in was
        ]
        assert sum(len(b) for b in now) == gid

    loop_run(scenario())


def test_one_source_mix_of_kinds_launches_as_before(loop_run):
    """(a) through the batcher itself: a recorded mix of decide,
    decide_arrays, globals and chain groups from this node's own doors,
    queued behind a launch in flight, reaches the backend in the
    batches and the order the one queue gave — computed here by the
    parent's collector over the same weights."""
    mix = [
        ("arrays", 300), ("decide", 200), ("globals", 3), ("chain", 40),
        ("arrays", 259), ("arrays", 259), ("decide", 1), ("globals", 2),
        ("arrays", 843), ("chain", 5), ("decide", 157), ("arrays", 1000),
        ("arrays", 1), ("globals", 1), ("decide", 998), ("arrays", 2),
    ]

    async def scenario():
        be = RecordingDevice(gate=True)
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1000, fetch_depth=2)
        b.start()
        first = _enqueue(b, "arrays", 0, 10)
        while not b._flushing:
            await asyncio.sleep(0.001)
        tasks = [
            _enqueue(b, kind, gid, rows)
            for gid, (kind, rows) in enumerate(mix, start=1)
        ]
        await asyncio.sleep(0)
        entries = b._queue.queued()
        assert [_item_weight(e) for e in entries] == [r for _, r in mix]
        expect = await _fifo_launches([list(entries)], 1000)
        be.gate.set()
        await asyncio.gather(first, *tasks)
        await b.stop()
        kinds = {"decide": "decide", "decide_arrays": "decide",
                 "chain": "chain", "globals": "globals"}
        order = {id(e): gid for gid, e in enumerate(entries, start=1)}
        for kind in ("decide", "chain", "globals"):
            want = [
                [order[id(e)] for e in batch if kinds[e[0]] == kind]
                for batch in expect
            ]
            assert be.launches(kind) == (
                [[0]] if kind == "decide" else []
            ) + [w for w in want if w], kind
        assert b.groups_overtaking == 0

    loop_run(scenario())


def test_a_busy_door_answers_its_peers_in_turn(loop_run):
    """(b) + (e): 32 groups of 259 rows from the node's own door wait
    behind a launch in flight when three peers forward 843 rows each:
    each peer's group is launched within three launches, the door's
    groups keep their order and fill their launches (three a launch),
    no launch passes 1000 rows, no group is split — and the counters
    say so."""

    async def scenario():
        be = RecordingDevice(gate=True)
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1000, fetch_depth=2)
        size0 = _sample("device_batch_size_sum")
        count0 = _sample("device_batch_size_count")
        src0 = (_sample("device_batch_sources_sum"),
                _sample("device_batch_sources_count"))
        b.start()
        door = [_enqueue(b, "arrays", gid, 259) for gid in range(1, 33)]
        while not b._flushing:  # launch 0 = door groups 1-3, in submit
            await asyncio.sleep(0.001)
        peers = [
            _enqueue(b, "arrays", 100 + i, 843, f"ipv4:10.0.0.{i}:5000")
            for i in (1, 2, 3)
        ]
        # a peer's SECOND batch keeps its place behind its first
        again = _enqueue(b, "arrays", 201, 843, "ipv4:10.0.0.1:5000")
        await asyncio.sleep(0)
        assert b.queue_stats()["depth"] == 3 + 29 + 4
        be.gate.set()
        await asyncio.gather(*door, *peers, again)
        await b.stop()
        got = be.launches()
        assert got[0] == [1, 2, 3]
        assert got[1:4] == [[101], [102], [103]]
        flat = [g for launch in got for g in launch]
        assert [g for g in flat if g < 100] == list(range(1, 33))
        assert flat.index(201) > flat.index(101)
        # the peer's second batch took its lane's next turn: one launch
        # of the door's, then it
        assert got[4] == [4, 5, 6] and got[5] == [201]
        rows = {g: 259 for g in range(1, 33)}
        rows.update({101: 843, 102: 843, 103: 843, 201: 843})
        assert max(sum(rows[g] for g in launch) for launch in got) <= 1000
        # (e) the three peers' groups and the second batch went ahead
        # of older door groups; every launch held one source
        assert b.groups_overtaking == 4
        assert b.rows_by_source == {"door": 32 * 259, "peer": 4 * 843}
        assert b.mixed_batches == 0
        d_size = _sample("device_batch_size_sum") - size0
        assert d_size == 32 * 259 + 4 * 843
        d_count = _sample("device_batch_size_count") - count0
        assert d_count == len(got)
        assert _sample("device_batch_sources_count") - src0[1] == len(got)
        assert _sample("device_batch_sources_sum") - src0[0] == len(got)

    loop_run(scenario())


def _sample(name: str) -> float:
    return metrics.REGISTRY.get_sample_value(name) or 0.0


def test_small_groups_of_two_sources_share_a_launch(loop_run):
    """(e) where the groups are small a launch takes the head of each
    lane in turn and holds both sources: device_batch_sources reads
    over 1 a launch, the rows by source still add up to
    device_batch_size_sum, and a one-source node reads 1.0 and 0."""

    async def scenario(with_peer: bool):
        be = RecordingDevice(gate=True)
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1000, fetch_depth=2)
        size0 = _sample("device_batch_size_sum")
        src0 = (_sample("device_batch_sources_sum"),
                _sample("device_batch_sources_count"))
        b.start()
        first = _enqueue(b, "arrays", 0, 10)
        while not b._flushing:
            await asyncio.sleep(0.001)
        tasks = [_enqueue(b, "arrays", g, 100) for g in range(1, 13)]
        if with_peer:
            tasks += [
                _enqueue(b, "decide", 100 + g, 50, "ipv4:10.0.0.9:1")
                for g in range(1, 7)
            ]
        await asyncio.sleep(0)
        be.gate.set()
        await asyncio.gather(first, *tasks)
        await b.stop()
        got = be.launches()
        launches = _sample("device_batch_sources_count") - src0[1]
        sources = _sample("device_batch_sources_sum") - src0[0]
        assert launches == len(got)
        assert sum(b.rows_by_source.values()) == (
            _sample("device_batch_size_sum") - size0
        )
        return b, got, sources / launches

    b, got, mean = loop_run(scenario(False))
    assert got == [[0], list(range(1, 11)), [11, 12]]
    assert (mean, b.groups_overtaking, b.mixed_batches) == (1.0, 0, 0)
    b, got, mean = loop_run(scenario(True))
    # peer and door in turn (the door opened the launch in flight, so
    # the peer opens this one): 6 x (50 + 100) = 900, one more door group
    assert got[1] == [101, 1, 102, 2, 103, 3, 104, 4, 105, 5, 106, 6, 7]
    assert got[2] == [8, 9, 10, 11, 12]
    assert mean > 1.0 and b.mixed_batches == 1
    assert b.rows_by_source == {"door": 10 + 12 * 100, "peer": 6 * 50}
    # the six peer groups each went ahead of an older door group
    assert b.groups_overtaking == 6


@pytest.mark.parametrize(
    "flood", [False, True], ids=["three_peers_50_rounds", "one_peer_flood"]
)
def test_neither_the_door_nor_a_peer_is_starved(loop_run, flood):
    """(c) three peers that forward again the instant they are
    answered, 50 rounds each, beside a door backlog: the door's head
    group launches at least once in every four launches, and every
    peer batch within four launches of its enqueue; with 2,000 one-row
    groups from ONE peer queued the door still has every second
    launch."""

    async def scenario():
        be = RecordingDevice(gate=True)
        b = DeviceBatcher(be, batch_wait=0, batch_limit=1000, fetch_depth=2)
        b.start()
        n_door = 120 if not flood else 30
        door = [_enqueue(b, "arrays", g, 259) for g in range(1, n_door + 1)]
        while not b._flushing:
            await asyncio.sleep(0.001)
        queued_at = {}

        async def peer(i):
            with peer_rows(f"ipv4:10.0.0.{i}:5000"):
                for r in range(50):
                    gid = 1000 * i + r
                    queued_at[gid] = len(be.calls)
                    await b.decide_arrays(_arrays(gid, 843), frame=False)

        if flood:
            peers = [
                _enqueue(b, "arrays", 10_000 + g, 1, "ipv4:10.0.0.1:5000")
                for g in range(2000)
            ]
        else:
            peers = [asyncio.ensure_future(peer(i)) for i in (1, 2, 3)]
        await asyncio.sleep(0)
        be.gate.set()
        await asyncio.gather(*door, *peers)
        await b.stop()
        got = be.launches()
        has_door = [any(g <= n_door for g in launch) for launch in got]
        last_door = max(i for i, d in enumerate(has_door) if d)
        every = 2 if flood else 4
        for i in range(last_door - every + 1):
            assert any(has_door[i:i + every]), (i, got[i:i + every])
        flat = [g for launch in got for g in launch]
        assert [g for g in flat if g <= n_door] == list(range(1, n_door + 1))
        if flood:
            assert [g for g in flat if g > n_door] == [
                10_000 + g for g in range(2000)
            ]
            return
        at = {g: i for i, launch in enumerate(got) for g in launch}
        for gid, n in queued_at.items():
            # launches recorded before its enqueue, the one in flight
            # then, and at most the three other lanes' turns
            assert at[gid] - n <= 4, (gid, n, at[gid])
        for i in (1, 2, 3):
            mine = [g for g in flat if g // 1000 == i]
            assert mine == [1000 * i + r for r in range(50)]

    loop_run(scenario())


@pytest.mark.parametrize("where", ["wait_window", "launch_in_flight"])
def test_stop_fails_every_lane(loop_run, where):
    """(d) stop() while the collector sits in its straggler window
    with groups of two sources collected, or while a launch is in
    submit with three lanes queued behind it (one group handed back
    because it did not fit): every caller gets the error, none hangs;
    queue_stats counted every lane while they waited."""

    async def scenario():
        gated = where == "launch_in_flight"
        be = RecordingDevice(gate=gated)
        b = DeviceBatcher(be, batch_wait=30.0, batch_limit=1000)
        big = 843 if gated else 50
        t0 = time.monotonic()
        tasks = [
            _enqueue(b, "arrays", 1, 259),
            _enqueue(b, "decide", 2, 259),
            _enqueue(b, "arrays", 101, big, "ipv4:10.0.0.1:5000"),
            _enqueue(b, "arrays", 102, big, "ipv4:10.0.0.2:5000"),
            _enqueue(b, "globals", 3, 2),
            _enqueue(b, "chain", 4, 3),
        ]
        await asyncio.sleep(0.02)
        stats = b.queue_stats()
        assert stats["depth"] == 6
        assert 0.02 <= stats["oldest_age_s"] <= time.monotonic() - t0
        b.start()
        while not (b._flushing if gated else b._live_batch):
            await asyncio.sleep(0.001)
        late = _enqueue(b, "arrays", 103, big, "ipv4:10.0.0.3:5000")
        await asyncio.sleep(0.01)
        if gated:
            # the door's four groups are in submit (523 rows: a peer's
            # 843 did not fit and went back to its lane's head)
            assert len(b._live_batch) == 4
            assert len(b._queue.queued()) == 3
        else:
            # everything fits: collected, the collector waits out its
            # window for stragglers, and the late one joined it
            assert len(b._live_batch) == 7 and b._queue.empty()
        assert b.queue_stats()["depth"] == 7
        await b.stop()
        be.gate.set()
        done = await asyncio.wait_for(
            asyncio.gather(*tasks, late, return_exceptions=True), 5
        )
        if gated:
            # a flush installs globals and runs the chain lane before
            # the decide lanes' submit: those two were answered
            assert done[4] is None and [r.limit for r in done[5]] == [4] * 3
            del done[4:6]
        assert all(
            isinstance(r, RuntimeError) and "stopped" in str(r) for r in done
        ), done
        assert b._queue.empty() and b._queue.qsize() == 0

    loop_run(scenario())
