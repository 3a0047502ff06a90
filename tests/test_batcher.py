"""DeviceBatcher unit tests: the pipelined submit/wait flusher.

The serving claim under test: with a device backend (one offering
decide_submit_merged and the surface that goes with it), the flusher
submits batch N+1 while batch N's fetch is still in flight (throughput
tracks max(host, device) per batch, not the sum), submits stay strictly
serialized, a failed fetch fails only its own batch, and host backends
(a blocking decide only) still work unchanged.
"""

import asyncio
import threading

import pytest

from gubernator_tpu.api.types import RateLimitReq, RateLimitResp
from gubernator_tpu.serve.batcher import DeviceBatcher


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

    import random

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
