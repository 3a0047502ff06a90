"""Device micro-batcher: coalesces concurrent requests into device batches.

The reference fans each request out to a goroutine and serializes them on a
cache mutex (reference gubernator.go:90-160, 237). Here the inversion that
makes the TPU fast: requests from all in-flight RPCs are coalesced into one
dense batch (up to `batch_limit`, waiting at most `batch_wait` after the
first arrival) and decided in a single kernel launch. One flusher task owns
the backend, so no locks exist anywhere on the hot path.

The backend call itself runs in a worker thread (it blocks on the device);
the event loop keeps accepting requests for the *next* batch meanwhile,
giving natural double-buffering: batch N on device while batch N+1 fills.

A decide batch has exactly two routes, chosen by what the backend IS:

* a HOST backend (ExactBackend, the tests' fakes) is decided by one
  blocking `backend.decide` per batch — on the loop when the backend is
  marked `inline_decide` (microseconds of dict work), else on a worker
  thread;
* a DEVICE backend (one that offers `decide_submit_merged`; the rest of
  the surface is checked at construction) is launched by
  `_flush_merged`, below.

The device route is pipelined: the flusher submits batch N+1 (merge +
async dispatch) while batch N's device fetch is still in flight, so
sustained throughput tracks max(host work, device time) per batch
instead of their sum. Up to `fetch_depth` batches may be in flight
(default 2): submits stay strictly serialized on one thread, but
fetches run on a fetch_depth-wide pool and may complete out of order —
each batch's futures resolve independently, and the engines' stats land
through a lock (core/engine.py EngineStats). Depth 2 is the default:
the device is co-located (fetch is ~0.1ms over PCIe), so one batch in
fetch and one in submit keep it fed; a device behind a slower link
would need depth ~ fetch RTT / batch time to run at device rate rather
than at 1/RTT. The native prep's reusable buffer ring is sized to
depth+1 generations at construction (hashlib_native.set_prep_generations)
so no in-flight batch's host arrays are ever overwritten by a later
submit.

Deep-batch mode (GUBER_DEVICE_DEEP_BATCH, serve/config.py) additionally
accumulates toward batch_limit while every pipeline slot is occupied — a
flush could not submit anyway — building the deep batches that amortize
per-batch fixed device costs (the big-store full-table writeback pass).
Idle flush semantics are unchanged: the hold predicate is False whenever
a slot is free.

Arrival-time prep (r9): each caller group's host prep —
request->array conversion + batch hashing (object groups), device-dtype
clipping, and the ownership/bucket PRE-SORT — is kicked onto a small
prep pool the moment the group is enqueued, overlapping the queue wait
it was going to pay anyway (batch_queue measured 16.7ms mean at the r7
profile while submit_host burned 32.8ms serialized). By flush time the
batch is a set of sorted runs; the submit thread k-way MERGES them
(serve/prep.py, O(n log k)) and dispatches — the only serialized work
left. prep/merge/dispatch (serve/stages.py) are the whole submit-thread
interior; a group that reaches the flush without a prep future (stop()
raced its enqueue) is prepped there, inside the `prep` span.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import contextvars
import os
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from gubernator_tpu.api.types import RateLimitReq, RateLimitResp
from gubernator_tpu.core.hashing import native_lib
from gubernator_tpu.serve import metrics, tracing
from gubernator_tpu.serve.aio import SourceLanes
from gubernator_tpu.serve.faults import FAULTS, FaultError
from gubernator_tpu.serve.stages import STAGES, claim_call


class _QMeta:
    """Per-queue-entry mark, riding slot -2 of every queue tuple (was a
    bare enqueue stamp pre-r16): the enqueue time (always stamped now —
    the batcher_queue_oldest_age_seconds gauge needs it for every
    entry), the frame flag (per-frame stage attribution keeps its r7
    contract: only frame-flagged groups enter coverage), and the
    caller's active trace, captured at enqueue so the flusher — which
    runs outside the caller's context — can attribute batch_queue and
    device spans to it (serve/tracing.py). `call` marks the one group
    a gRPC call's handler enqueued first (stages.claim_call): it alone
    records the call_queue / call_device / call_wake tiles, under
    those names in the caller's trace too, so the tiles have their
    call_e2e and a JSON-door, peer-loop or internal group records
    none. `t_done` is the instant the flusher resolved the group's
    future: call_device ends there and call_wake begins. `peer` is the
    group's SOURCE: the peer whose forward it is (enqueued under
    peer_rows(sender), Instance.get_peer_rate_limits: the sender's
    address as its PeersV1 call gave it) or None for every other
    caller, this node's own doors' — a row's source rides the entry
    that queued it: a batch is counted by its groups' lengths, and the
    queue keeps one lane a source (aio.SourceLanes)."""

    __slots__ = ("t", "frame", "call", "trace", "t_done", "peer")

    def __init__(self, frame: bool):
        self.t = time.monotonic()
        self.frame = frame
        self.call = not frame and claim_call()
        self.trace = tracing.active()
        self.t_done = 0.0
        self.peer = _PEER_ROWS.get()


#: set around the owner side's serving of one forwarded batch, to who
#: sent it: what is enqueued under it is that PEER's — its lane of the
#: queue, and rows counted as device_batch_rows_total{source="peer"} —
#: everything else (None) this node's own doors'
_PEER_ROWS: "contextvars.ContextVar[Optional[str]]" = contextvars.ContextVar(
    "guber_batch_peer_rows", default=None
)


@contextlib.contextmanager
def peer_rows(sender: Optional[str] = None):
    """`sender` names the forwarding peer (the PeersV1 door holds this
    around a call, with the call's peer address); without one the
    sender already named stands, and where none is — a caller that
    cannot tell its peers apart — the one shared source "peer"."""
    token = _PEER_ROWS.set(sender or _PEER_ROWS.get() or "peer")
    try:
        yield
    finally:
        _PEER_ROWS.reset(token)


#: what a device backend owes the batcher besides decide_submit_merged
DEVICE_SURFACE = (
    "prep_reqs", "prep_group", "merge_prepped",
    "decide_wait_arrays", "resps_from_arrays",
)


def is_device_backend(backend) -> bool:
    """THE statement of which route a backend's batches take: one that
    offers decide_submit_merged is launched on the device route
    (DeviceBatcher._flush_merged, the edge bridge's array frames);
    anything else is a host backend, decided by its blocking decide."""
    return getattr(backend, "decide_submit_merged", None) is not None


def _prep_result(prep: "concurrent.futures.Future"):
    """Resolve an arrival-prep future on the submit thread. A pool
    shutdown (stop() racing a flush) surfaces as CancelledError, which
    is a BaseException the pipelined submit's failure guard would not
    convert to per-item errors — normalize it here."""
    try:
        return prep.result()
    except concurrent.futures.CancelledError:
        raise RuntimeError("prep cancelled (batcher stopping)") from None


def _group_rows(item) -> int:
    """The answers a decide or chain queue entry is owed: its array
    group's rows, or its requests."""
    if item[0] == "decide_arrays":
        return item[1]["key_hash"].shape[0]
    return len(item[1])


def _item_weight(item) -> int:
    """Queue items are whole groups; the batch limit counts underlying
    requests/updates, not queue entries."""
    if item[0] == "decide_arrays":
        return max(1, item[1]["key_hash"].shape[0])
    if item[0] == "chain":
        # a chained request expands to one device row per level
        return max(
            1,
            sum(1 + len(getattr(r, "chain", ()) or ()) for r in item[1]),
        )
    return max(1, len(item[1]))


def _item_source(item):
    return item[-2].peer


class DeviceBatcher:
    def __init__(
        self,
        backend,
        batch_wait: float = 0.0005,
        batch_limit: int = 1000,
        fetch_depth: int = 2,
        deep_batch: bool = False,
        prep_threads: int = 0,
    ):
        self.backend = backend
        self.batch_wait = batch_wait
        self.batch_limit = batch_limit
        # throughput mode (GUBER_DEVICE_DEEP_BATCH): while the submit
        # gate is saturated (every fetch_depth slot occupied — a flush
        # could not submit anyway), keep accumulating toward
        # batch_limit instead of parking a shallow batch at the
        # semaphore. Deep batches amortize per-batch fixed device costs
        # (the big-store full-table writeback); idle/light-load flush
        # semantics are byte-identical to deep_batch=False because the
        # hold predicate is False whenever a pipeline slot is free.
        self.deep_batch = bool(deep_batch)
        self.fetch_depth = max(1, int(fetch_depth))
        # one FIFO lane a source (this node's own doors, each peer that
        # forwards to it), collected in turn: with one source queued,
        # one arrival-order queue
        self._queue = SourceLanes(batch_limit, _item_weight, _item_source)
        self._task: Optional[asyncio.Task] = None
        # in-flight fetches of submitted batches (device backends
        # only); each task resolves its own batch's futures. The
        # semaphore admits a submit only while fewer than fetch_depth
        # batches are outstanding.
        self._pending: set = set()
        self._inflight = asyncio.Semaphore(self.fetch_depth)
        self._fetch_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.fetch_depth, thread_name_prefix="guber-fetch"
        )
        if self.fetch_depth > 1:
            # size the native prep's buffer ring to the pipeline depth
            # BEFORE any prep call (see hashlib_native._PrepBuffers)
            lib = native_lib()
            if lib is not None:
                lib.set_prep_generations(self.fetch_depth + 1)
        # ONE dedicated submit thread (not the shared to_thread pool):
        # the native prep keeps per-thread reusable buffers and scratch
        # (hashlib_native._PrepBuffersTL, C++ thread_locals), so letting
        # submits hop across the default executor's up-to-32 threads
        # would multiply resident warm buffers by the executor width for
        # a pipeline that never has more than two batches in flight
        self._submit_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="guber-submit"
        )
        # last backend stats snapshot, for cache_access_count /
        # store_dropped_creates / store_evictions deltas
        self._last_hits = 0
        self._last_misses = 0
        self._last_dropped = 0
        self._last_evictions = 0
        # every batch's rows by who sent them, and the batches that
        # held rows of both (plain ints, exported lazily at scrape:
        # device_batch_rows_total{source}, device_batches_mixed_total);
        # door + peer = device_batch_size_sum by construction
        self.rows_by_source = {"door": 0, "peer": 0}
        self.mixed_batches = 0
        # groups launched while an OLDER group of another source stayed
        # queued: the turn-taking between sources at work, 0 wherever
        # one source feeds the batcher (device_groups_overtaking_total)
        self.groups_overtaking = 0
        # set before the flusher is cancelled: a decide()/update_globals()
        # after stop() would otherwise enqueue into a queue no flusher
        # reads and await a future that never resolves (same guard as
        # PeerClient._closed)
        self._closed = False
        # inline backends (host-memory decide, microseconds of work) can
        # take a same-task fast path when nothing is queued, collected,
        # or flushing: the decide runs synchronously in the caller's
        # handler, skipping the queue + flusher-task round trip (~0.2ms
        # of single-request latency). Safe because the loop can't
        # interleave between the check and the call (no await), and all
        # three places earlier work can hide are checked: the queue
        # (not yet collected), _live_batch (collected by the flusher's
        # collect_batch — possibly parked in a batch_wait straggler
        # window — but not yet flushed), and _flushing (mid-flush).
        self._inline = bool(getattr(backend, "inline_decide", False))
        # a DEVICE backend is one that offers decide_submit_merged; it
        # then owes the rest of the launch surface, checked here so a
        # half-built backend fails at construction instead of at its
        # first flush
        self._device = is_device_backend(backend)
        if self._device:
            missing = [
                m for m in DEVICE_SURFACE
                if not callable(getattr(backend, m, None))
            ]
            if missing:
                raise TypeError(
                    f"{type(backend).__name__} offers decide_submit_merged"
                    f" but not {', '.join(missing)}: a device backend "
                    f"carries the whole launch surface"
                )
        if prep_threads <= 0:
            # auto: leave a core for the serving loop — a prep pool as
            # wide as the box measurably thrashes small hosts (2-core
            # A/B: pool=2 cost 6% decisions/s vs pool=1 at parity; a
            # group's prep budget is its whole batch_queue wait, so
            # narrow pools keep up easily)
            prep_threads = max(1, min(4, (os.cpu_count() or 2) - 1))
        self.prep_threads = prep_threads
        # workers spawn on first submit, so an idle prep pool costs no
        # threads; host backends get none
        self._prep_pool = (
            concurrent.futures.ThreadPoolExecutor(
                max_workers=self.prep_threads,
                thread_name_prefix="guber-prep",
            )
            if self._device
            else None
        )
        self._flushing = False
        self._live_batch: List = []

    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.ensure_future(self._run())

    async def stop(self) -> None:
        self._closed = True
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        for t in list(self._pending):
            await t  # drain every in-flight fetch gracefully
        self._pending.clear()
        self._submit_pool.shutdown(wait=False)
        self._fetch_pool.shutdown(wait=False)
        if self._prep_pool is not None:
            # cancel queued-but-unstarted arrival preps; running ones
            # finish on their own (their results are simply dropped —
            # every caller future was already failed above, so no
            # future is stranded waiting on a prep)
            self._prep_pool.shutdown(wait=False, cancel_futures=True)

    async def drain(self) -> None:
        """Graceful-drain wait: resolves when no queued, collected,
        parked, or in-flight work remains. Callers must have stopped
        feeding the batcher first (drain doesn't gate decide()); the
        server's drain path bounds this with the GUBER_DRAIN_TIMEOUT_MS
        budget."""
        while (
            not self._queue.empty()
            or self._live_batch
            or self._flushing
            or self._pending
        ):
            await asyncio.sleep(0.005)

    async def decide(
        self,
        reqs: Sequence[RateLimitReq],
        gnp: Sequence[bool],
        frame: bool = False,
    ) -> List[RateLimitResp]:
        """Submit requests; resolves when their device batch completes.
        `frame=True` marks the group as one edge frame's work for the
        per-frame stage clock (serve/stages.py)."""
        if not reqs:
            return []
        if self._closed:
            raise RuntimeError("DeviceBatcher is stopped")
        if (
            self._inline
            and not self._flushing
            and not self._live_batch
            and self._queue.empty()
            and self._task is not None
        ):
            t0 = time.monotonic()
            resps = self.backend.decide(list(reqs), [bool(g) for g in gnp])
            tr = tracing.active()
            if tr is not None:
                # inline fast path: no queue wait, the decide IS the
                # device span (r16)
                tr.add_span(
                    "device", start=t0, batch=len(resps),
                    rung=self._rung(len(resps)), inline=True,
                )
            self._observe_batch(
                len(resps), time.monotonic() - t0,
                len(resps) if _PEER_ROWS.get() is not None else 0,
            )
            return resps
        # one queue item + ONE future per caller (an RPC's whole request
        # list): per-item futures cost ~0.1-0.3ms of event-loop work per
        # request on a contended host, which at 1000-item batches was
        # 100-300ms of pure asyncio overhead per RPC — 10x the device
        # time. Groups are flattened at flush and responses sliced back.
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        reqs_l = list(reqs)
        gnp_l = [bool(g) for g in gnp]
        # the second-to-last slot of EVERY queue tuple is a _QMeta
        # mark: enqueue stamp (queue-age gauge), frame flag (per-frame
        # stages must count ONLY groups that belong to an edge frame,
        # or the coverage ratio's numerator outgrows its denominator
        # under direct gRPC/HTTP/peer traffic), and the caller's trace
        meta = _QMeta(frame)
        self._queue.put_nowait(
            ("decide", reqs_l, gnp_l,
             self._kick_prep("prep_reqs", reqs_l, gnp_l),
             meta, fut)
        )
        resps = await fut
        if meta.t_done:
            # the caller's coroutine runs again: a gRPC call's
            # call_wake tile (future resolved -> here; event-loop and
            # GIL wait), in the caller's context, so a traced call
            # gets the span too
            STAGES.add("call_wake", time.monotonic() - meta.t_done)
        return resps

    def _kick_prep(self, method: str, *args):
        """Arrival-time prep kick: schedule this group's conversion +
        presort on the prep pool NOW, so it overlaps the group's own
        queue wait. Returns the prep future to ride in the queue tuple,
        or None on a host backend and when stop() already shut the pool
        down (a group that still reaches a flush is prepped there, on
        the submit thread)."""
        if not self._device:
            return None
        try:
            return self._prep_pool.submit(
                getattr(self.backend, method), *args
            )
        except RuntimeError:  # pool shut down: stop() raced the caller
            return None

    async def decide_arrays(self, fields: dict, frame: bool = True):
        """Array-group decide — the edge bridge's pre-hashed fast path.
        `fields`: key_hash/hits/limit/duration/algo numpy arrays (gnp
        optional, default all-False; the edge routes GLOBAL items via the
        request-object path). Resolves to (status, limit, remaining,
        reset_time) arrays for exactly these rows, co-batched and
        pipelined with every other caller. Only valid on device
        backends (is_device_backend).
        `frame=False` keeps a group out of the per-frame stage clock —
        a chunked frame flags only its first chunk, so one frame
        contributes one batch_queue/device span, not one per chunk —
        and, enqueued first by a gRPC call's handler, makes it that
        call's group (stages.claim_call): call_queue / call_device /
        call_wake are its tiles then.

        Empty-group contract (pinned by tests/test_prep_pipeline.py):
        a zero-row `key_hash` resolves immediately to four EMPTY
        int64 arrays — the canonical wire dtype, regardless of the
        narrower dtypes a real device batch returns."""
        if fields["key_hash"].shape[0] == 0:
            z = np.empty(0, np.int64)
            return z, z, z, z
        if self._closed:
            raise RuntimeError("DeviceBatcher is stopped")
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        meta = _QMeta(frame)
        self._queue.put_nowait(
            ("decide_arrays", fields,
             self._kick_prep("prep_group", fields),
             meta, fut)
        )
        res = await fut
        if meta.t_done:
            # a gRPC call's array group (the PeersV1 door's folded
            # batch): its call_wake tile, as in decide()
            STAGES.add("call_wake", time.monotonic() - meta.t_done)
        return res

    async def decide_chain(
        self, reqs: Sequence[RateLimitReq], frame: bool = False
    ):
        """Hierarchical quota chains (r15): a dedicated, coalescing
        lane — chained caller groups in one flush window merge into ONE
        backend.decide_chain call, which expands levels and runs the
        chain-coupled kernel pass. The call runs on the single submit
        thread (it submits AND waits against the donated store), so a
        chain batch serializes with — never races — the pipelined
        plain-batch submits; plain traffic keeps its full pipeline.
        `frame=True` (bridge GEBC path) marks the group for the
        per-frame stage clock, exactly like decide() — the r16 audit
        found chain-lane batches silently diluting frame coverage."""
        if not reqs:
            return []
        if self._closed:
            raise RuntimeError("DeviceBatcher is stopped")
        if getattr(self.backend, "decide_chain", None) is None:
            raise RuntimeError(
                "backend does not support quota chains (r15): the "
                "multihost lockstep engine has no chain step message"
            )
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        meta = _QMeta(frame)
        self._queue.put_nowait(("chain", list(reqs), meta, fut))
        resps = await fut
        if meta.t_done:
            STAGES.add("call_wake", time.monotonic() - meta.t_done)
        return resps

    async def run_serialized(self, fn, *args):
        """Run `fn(*args)` on the single submit thread, serialized with
        every device dispatch. Bucket replication's snapshot reads
        (serve/replication.py) use this: the store gather is
        non-mutating but must not overlap a decide that DONATES the
        store buffer, and the one-wide submit pool is exactly that
        ordering guarantee."""
        if self._closed:
            raise RuntimeError("DeviceBatcher is stopped")
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(self._submit_pool, fn, *args)

    async def update_globals(self, updates) -> None:
        """Replica installs funnel through the same flusher queue so the
        backend stays single-threaded."""
        if self._closed:
            raise RuntimeError("DeviceBatcher is stopped")
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._queue.put_nowait(("globals", updates, _QMeta(False), fut))
        await fut

    # -- queue visibility (r16) ---------------------------------------------

    def queue_stats(self) -> dict:
        """Standing-work snapshot for the lazily-set scrape gauges
        (serve/metrics.py batcher_queue_*): depth counts caller groups
        queued in every source's lane + collected-but-unflushed; oldest
        age reads the _QMeta enqueue stamps. Runs on the serving loop
        (the /metrics handler), so the peek at the lanes cannot race an
        enqueue."""
        items = self._queue.queued() + self._live_batch
        oldest = min((it[-2].t for it in items), default=None)
        prep_backlog = 0
        if self._prep_pool is not None:
            q = getattr(self._prep_pool, "_work_queue", None)
            if q is not None:
                prep_backlog = q.qsize()
        return {
            "depth": len(items),
            "oldest_age_s": (
                time.monotonic() - oldest if oldest is not None else 0.0
            ),
            "prep_backlog": prep_backlog,
        }

    def _rung(self, n: int) -> int:
        """The padding-ladder rung a batch of n rows launches at — the
        device-span annotation (r16). Engines keep a sorted `buckets`
        ladder; backends without one (exact/inline) report the batch
        size itself."""
        buckets = getattr(
            getattr(self.backend, "engine", None), "buckets", None
        )
        if buckets:
            for b in buckets:
                if b >= n:
                    return int(b)
        return int(n)

    def _trace_device(
        self, items, t_collect: float, total: int, extra=None
    ) -> None:
        """Attach the device span (+ batch annotations) to every traced
        caller group of a flushed batch. Annotations are computed only
        when at least one group carries a trace — the untraced path
        pays one attribute check per group (r16)."""
        traced = [it for it in items if it[-2].trace is not None]
        if not traced:
            return
        algos: dict = {}
        for it in items:
            if it[0] == "decide_arrays":
                vals, counts = np.unique(
                    np.asarray(it[1]["algo"]), return_counts=True
                )
                for v, c in zip(vals.tolist(), counts.tolist()):
                    algos[int(v)] = algos.get(int(v), 0) + int(c)
            else:
                for r in it[1]:
                    a = int(r.algorithm)
                    algos[a] = algos.get(a, 0) + 1
        ann = dict(
            batch=int(total),
            rung=self._rung(int(total)),
            algo_mix={str(k): v for k, v in sorted(algos.items())},
        )
        if extra:
            ann.update(extra)
        now = time.monotonic()
        for it in traced:
            it[-2].trace.add_span(
                "call_device" if it[-2].call else "device",
                start=t_collect, end=now, **ann
            )

    async def _run(self) -> None:
        while True:
            batch: List[Tuple] = []
            # visible to the inline fast path: items drained into this
            # list during a batch_wait window are "earlier work" a fast
            # decide must not overtake
            self._live_batch = batch
            try:
                # Everything already enqueued rides this launch; while
                # the backend is busy in _flush, new arrivals accumulate
                # in the queue, so batches grow with load on their own
                # ("batch while busy") and a solo request only waits the
                # optional batch_wait window. The collect runs INSIDE
                # the try (and is cancellation-race-safe, serve/aio.py):
                # a cancel must reach the drain handler below with every
                # collected item visible, or a caller would hang.
                await self._queue.collect(
                    batch, self.batch_wait,
                    hold_while=(
                        self._inflight.locked if self.deep_batch else None
                    ),
                )
                self._note_overtaking(batch)
                self._flushing = True
                try:
                    await self._flush(batch)
                finally:
                    self._flushing = False
            except asyncio.CancelledError:
                # stop() anywhere in the collect/flush path: every caller
                # in this batch and still enqueued gets an error, never a
                # hang. Items a flush step already resolved, or handed to
                # the _pending fetch chain, were removed from `batch` (or
                # have done futures, which _fail skips).
                exc = RuntimeError("batcher stopped mid-batch")
                self._fail(batch, exc)
                while True:
                    try:
                        self._fail([self._queue.get_nowait()], exc)
                    except asyncio.QueueEmpty:
                        break
                raise

    def _note_overtaking(self, batch) -> None:
        """Count the groups of this launch that are YOUNGER than a
        group of another source still queued: what arrival order would
        have launched the other way round."""
        if self._queue.empty():
            return
        waiting = [(h[-2].peer, h[-2].t) for h in self._queue.heads()]
        for it in batch:
            m = it[-2]
            self.groups_overtaking += any(
                t < m.t and src != m.peer for src, t in waiting
            )

    async def _flush(self, batch) -> None:
        if FAULTS.enabled:
            # device_submit injection point (GUBER_FAULT_SPEC): an
            # error fails THIS batch's callers (per-item errors, the
            # same envelope as a real submit failure) and must never
            # kill the flusher task; delay/hang stall the submit path
            # like a wedged device would
            try:
                await FAULTS.inject("device_submit")
            except FaultError as e:
                self._fail(batch, e)
                return
        decide_items = [
            b for b in batch if b[0] in ("decide", "decide_arrays")
        ]
        global_items = [b for b in batch if b[0] == "globals"]
        chain_items = [b for b in batch if b[0] == "chain"]
        # batch_queue stage: enqueue -> collect, per frame-flagged
        # caller group (the chain lane participates since the r16
        # audit — chained frames used to dilute coverage), call_queue
        # per gRPC call's group; a traced group gets the span under
        # its family's name, flagged or not
        t_collect = time.monotonic()
        for it in decide_items + chain_items:
            m = it[-2]
            stage = "call_queue" if m.call else "batch_queue"
            if m.frame or m.call:
                STAGES.add(stage, t_collect - m.t)
            if m.trace is not None:
                m.trace.add_span(stage, start=m.t, end=t_collect)

        inline = self._inline
        if global_items:
            # coalesced install (r10): ONE backend call — and one
            # to_thread hop — per flush batch instead of one per caller
            # group. Safe to concatenate: installs are last-writer-wins
            # upserts applied in list order, identical to the former
            # sequential per-group calls; the total is bounded by
            # batch_limit (collect_batch weighs update rows like decide
            # rows), which config.validate pins under the engine's
            # bucket ladder. Per-caller futures still resolve/fail
            # individually.
            all_updates = [
                u for _, updates, _m, _fut in global_items
                for u in updates
            ]
            try:
                if inline:
                    self.backend.update_globals(all_updates)
                else:
                    await asyncio.to_thread(
                        self.backend.update_globals, all_updates
                    )
            except Exception as e:
                for _, _updates, _m, fut in global_items:
                    if not fut.done():
                        fut.set_exception(e)
            else:
                for _, _updates, _m, fut in global_items:
                    if not fut.done():
                        fut.set_result(None)
            # a cancel mid-call propagates to _run's handler, which fails
            # this and every remaining item in the batch

        if chain_items:
            # coalesced chain lane (r15): ONE backend call per flush
            # window; responses slice back per caller. Runs on the
            # single submit thread (never the shared to_thread pool):
            # decide_chain submits AND waits against the donated store,
            # and only the one-wide submit pool serializes that with
            # the pipelined plain submits. Inline (host) backends run
            # on the loop like their plain decide.
            all_chain = [
                r for _, reqs, _m, _fut in chain_items for r in reqs
            ]

            def chain_call():
                # the chain lane records the batch tiles like the
                # decide lanes (the r16 frame-coverage audit: it must
                # record PER_BATCH stages too). The chain call both
                # submits and waits, so its whole body is submit_call
                # and its batch has no fetch tile.
                with STAGES.span("submit_call") as sp:
                    return self.backend.decide_chain(all_chain), sp

            t0c = time.monotonic()
            sp = None  # an inline (host) backend's batch has no tiles
            try:
                if inline:
                    resps = self.backend.decide_chain(all_chain)
                else:
                    loop = asyncio.get_running_loop()
                    resps, sp = await loop.run_in_executor(
                        self._submit_pool, chain_call
                    )
            except Exception as e:
                for _, _reqs, _m, fut in chain_items:
                    if not fut.done():
                        fut.set_exception(e)
            else:
                if sp is not None:
                    self._stage_submit(t_collect, t0c, sp)
                k = 0
                for _, reqs_c, _m, fut in chain_items:
                    span = resps[k : k + len(reqs_c)]
                    k += len(reqs_c)
                    if not fut.done():
                        fut.set_result(span)
                # device stage per frame-flagged chain group (r16
                # audit fix): the same span the decide lanes record —
                # without it, a GEBC frame added e2e with no device
                # span and coverage silently diluted under chained
                # traffic
                resolved = self._stage_device(chain_items, t_collect)
                if sp is not None:
                    STAGES.add("batch_e2e", resolved - t_collect)
                rows = sum(
                    1 + len(getattr(r, "chain", ()) or ())
                    for r in all_chain
                )
                self._trace_device(
                    chain_items, t_collect, len(all_chain),
                    extra=dict(chain=True, rows=rows),
                )
                self._observe_batch(
                    len(resps), time.monotonic() - t0c,
                    *self._by_source(chain_items),
                )

        if not decide_items:
            return
        if self._device:
            await self._flush_merged(decide_items, t_collect)
            return
        # host backend: one blocking decide per batch (a cancel mid-call
        # is handled by _run; the worker thread finishes on its own and
        # to_thread discards its result). Host backends marked
        # inline_decide run right here on the loop — their decide is
        # microseconds of dict work and the to_thread handoff would
        # dominate the request latency.
        reqs = [r for it in decide_items for r in it[1]]
        gnp = [g for it in decide_items for g in it[2]]
        t0 = time.monotonic()
        try:
            if inline:
                resps = self.backend.decide(reqs, gnp)
            else:
                resps = await asyncio.to_thread(
                    self.backend.decide, reqs, gnp
                )
        except Exception as e:
            self._fail(decide_items, e)
            return
        self._resolve(decide_items, resps, time.monotonic() - t0)
        self._stage_device(decide_items, t_collect)
        self._trace_device(decide_items, t_collect, len(resps))

    async def _flush_merged(self, decide_items, t_collect) -> None:
        """The device route, and the only launch path: resolve every
        group's pre-sorted run (its arrival prep's result; a group that
        carries no prep future is prepped here), k-way merge the runs
        into one sorted batch, and dispatch — no concat + full argsort
        anywhere. The submit-thread interior is stage-attributed as
        prep (waiting out unfinished arrival preps), merge, and
        dispatch. All of it runs inside submit_call — off the event
        loop, and inside the failure guard, so a conversion error fails
        THIS batch's callers, never the flusher task. The fetch runs in
        a background task (_finish_arrays), so the flusher can collect
        and submit the NEXT batch while the device computes this one."""
        # group lengths are exception-free to read and needed for the
        # response slicing regardless of submit outcome
        lens = [_group_rows(it) for it in decide_items]

        def submit_call():
            # the span's own two stamps go back with the handle: the
            # loop tiles the hand-off's legs around them (_stage_submit)
            with STAGES.span("submit_call") as sp:
                runs = []
                with STAGES.span("prep"):
                    for it in decide_items:
                        # queue tuples carry their arrival-prep future
                        # at slot 3 (object group) or 2 (array group)
                        p = it[3] if it[0] == "decide" else it[2]
                        if p is not None:
                            runs.append(_prep_result(p))
                        elif it[0] == "decide":
                            runs.append(
                                self.backend.prep_reqs(it[1], it[2])
                            )
                        else:
                            runs.append(self.backend.prep_group(it[1]))
                with STAGES.span("merge"):
                    merged = self.backend.merge_prepped(runs)
                with STAGES.span("dispatch"):
                    return self.backend.decide_submit_merged(merged), sp

        # admission bounds outstanding batches at fetch_depth; a cancel
        # while waiting for a slot reaches _run's handler with nothing
        # submitted
        await self._inflight.acquire()
        # t0 AFTER admission: under a saturated pipeline the acquire
        # blocks for up to a batch period, which is queue wait, not
        # launch cost — DEVICE_LAUNCH_MS must not double-count it
        t0 = time.monotonic()
        # shield: a stop() mid-submit must not strand these futures —
        # the submit thread finishes either way (the store mutation has
        # already been dispatched), so fail the batch and propagate.
        loop = asyncio.get_running_loop()
        submit_fut = asyncio.ensure_future(
            loop.run_in_executor(self._submit_pool, submit_call)
        )
        try:
            handle, sp = await asyncio.shield(submit_fut)
        except asyncio.CancelledError:
            # consume the shielded submit's outcome so an exception is
            # not logged as unretrieved at GC; a returned handle is
            # abandoned — the dispatched batch's store mutation stands,
            # the same contract as a crash after dispatch. _run's handler
            # fails the batch's futures.
            self._inflight.release()
            submit_fut.add_done_callback(
                lambda t: t.cancelled() or t.exception()
            )
            raise
        except Exception as e:
            self._inflight.release()
            self._fail(decide_items, e)
            return
        t_submitted = self._stage_submit(t_collect, t0, sp)
        task = asyncio.ensure_future(
            self._finish_arrays(
                handle, decide_items, lens, t_submitted - t0, t_collect,
                t_submitted,
            )
        )
        # hold the reference until done (stop() drains the set); discard
        # on completion so an idle batcher doesn't pin the last batches'
        # requests/responses until the next flush
        self._pending.add(task)
        task.add_done_callback(self._pending.discard)
        # this batch now belongs to its fetch task (stop() awaits it): a
        # later cancel must not fail its futures from _run. _live_batch
        # is the same list object _run handed to _flush.
        self._live_batch.clear()

    async def _finish_arrays(
        self, handle, decide_items, lens, submit_s, t_collect, t_submitted
    ):
        t1 = time.monotonic()
        loop = asyncio.get_running_loop()
        try:
            (status, limit, remaining, reset), sp = (
                await loop.run_in_executor(
                    self._fetch_pool, self._fetch,
                    self.backend.decide_wait_arrays, handle,
                )
            )
        except Exception as e:
            self._fail(decide_items, e)
            return
        finally:
            self._inflight.release()
        # the fetch's two legs, around fetch_wait's own stamps: the hop
        # to guber-fetch (from the end of submit_host, the ensure_future
        # of this task included) and the answer's way back to the loop
        STAGES.add("fetch_wake", sp.t0 - t_submitted)
        STAGES.add("fetch_return", time.monotonic() - sp.t1)
        k = 0
        for it, n in zip(decide_items, lens):
            span = (
                status[k : k + n],
                limit[k : k + n],
                remaining[k : k + n],
                reset[k : k + n],
            )
            k += n
            fut = it[-1]
            if fut.done():
                continue
            if it[0] == "decide":
                fut.set_result(self.backend.resps_from_arrays(*span))
            else:
                fut.set_result(span)
        resolved = self._stage_device(decide_items, t_collect)
        STAGES.add("batch_e2e", resolved - t_collect)
        self._trace_device(
            decide_items, t_collect, k,
            extra=dict(
                submit_ms=round(submit_s * 1e3, 3),
                fetch_ms=round((time.monotonic() - t1) * 1e3, 3),
            ),
        )
        self._observe_batch(
            k, submit_s + (time.monotonic() - t1),
            *self._by_source(decide_items),
        )

    def _fail(self, items, exc: BaseException) -> None:
        # both queue item shapes carry their future last
        for it in items:
            fut = it[-1]
            if not fut.done():
                fut.set_exception(exc)

    def _resolve(self, decide_items, resps, launch_s: float) -> None:
        # resolve callers FIRST: metrics are best-effort and must never
        # be able to kill the flusher task (a dead flusher wedges every
        # future request with no error surfaced). Responses come back
        # flat in flatten order; slice one span per caller group.
        k = 0
        for it in decide_items:
            rs, fut = it[1], it[-1]
            span = resps[k : k + len(rs)]
            k += len(rs)
            if not fut.done():
                fut.set_result(span)
        self._observe_batch(
            len(resps), launch_s, *self._by_source(decide_items)
        )

    @staticmethod
    def _fetch(wait, handle):
        """decide_wait* on the fetch pool, as the fetch_wait stage; the
        span goes back with the answer, for its two stamps."""
        with STAGES.span("fetch_wait") as sp:
            return wait(handle), sp

    @staticmethod
    def _stage_submit(t_collect: float, t0: float, sp) -> float:
        """One device batch's tiles up to the loop running again after
        its submit: admit_wait (collect -> t0, the pipeline slot
        taken), then the hand-off's two legs around the submit
        thread's own span `sp` — submit_wake (t0 -> its first line)
        and submit_return (its last line -> now) — and submit_host,
        their sum with submit_call by construction. Returns now."""
        now = time.monotonic()
        STAGES.add("admit_wait", t0 - t_collect)
        STAGES.add("submit_wake", sp.t0 - t0)
        STAGES.add("submit_return", now - sp.t1)
        STAGES.add("submit_host", now - t0)
        return now

    @staticmethod
    def _stage_device(items, t_collect: float) -> float:
        """The span from the flusher's collect to this batch's futures
        resolved (submit + device execute + fetch + any wait behind
        earlier pipelined batches), once per caller group: `device`
        for frame-flagged groups, `call_device` for a gRPC call's
        group, whose call_wake tile starts at the same stamp. Call
        right after the futures are set, before the flusher yields.
        Returns that stamp: a device batch's batch_e2e ends there."""
        now = time.monotonic()
        span = now - t_collect
        frames = calls = 0
        for it in items:
            m = it[-2]
            if m.frame:
                frames += 1
            elif m.call:
                m.t_done = now
                calls += 1
        if frames:
            STAGES.add("device", span * frames, frames)
        if calls:
            STAGES.add("call_device", span * calls, calls)
        return now

    @staticmethod
    def _by_source(items) -> Tuple[int, int]:
        """Of one batch's rows, those its peers forwarded — the lengths
        of the groups whose queue entry names a peer (_QMeta.peer) —
        and the distinct sources its groups came from."""
        return (
            sum(_group_rows(it) for it in items if it[-2].peer is not None),
            len({it[-2].peer for it in items}),
        )

    def _observe_batch(
        self, n: int, launch_s: float, peer: int, sources: int = 1
    ) -> None:
        """One device batch of n rows from `sources` distinct sources,
        `peer` of the rows forwarded by peers and the rest from this
        node's own doors, launched at its padding rung: useful rows
        over attempted slots is device_batch_size_sum /
        device_batch_slots_total. Best-effort: metrics must never be
        able to kill the flusher task."""
        self.rows_by_source["peer"] += peer
        self.rows_by_source["door"] += n - peer
        self.mixed_batches += 0 < peer < n
        try:
            metrics.DEVICE_BATCH_SIZE.observe(n)
            metrics.DEVICE_BATCH_SOURCES.observe(sources)
            metrics.DEVICE_BATCH_SLOTS.inc(self._rung(n))
            metrics.DEVICE_LAUNCH_MS.observe(launch_s * 1e3)
            self._observe_cache_stats()
        except Exception:  # pragma: no cover - defensive
            pass

    def _observe_cache_stats(self) -> None:
        """Forward the backend's monotonic hit/miss counters into
        cache_access_count{type} (reference cache/lru.go:164-176) as
        deltas since the last flush. Backends are duck-typed: anything
        without a dict-shaped stats() is simply not metered."""
        stats_fn = getattr(self.backend, "stats", None)
        if stats_fn is None:
            return
        s = stats_fn()
        if not isinstance(s, dict):
            return
        hits = int(s.get("hits", s.get("hit", 0)))
        misses = int(s.get("misses", s.get("miss", 0)))
        if hits > self._last_hits:
            metrics.CACHE_ACCESS_COUNT.labels(type="hit").inc(
                hits - self._last_hits
            )
        if misses > self._last_misses:
            metrics.CACHE_ACCESS_COUNT.labels(type="miss").inc(
                misses - self._last_misses
            )
        self._last_hits, self._last_misses = hits, misses
        dropped = int(s.get("dropped", 0))
        evictions = int(s.get("evictions", 0))
        if dropped > self._last_dropped:
            metrics.STORE_DROPPED_CREATES.inc(dropped - self._last_dropped)
        if evictions > self._last_evictions:
            metrics.STORE_EVICTIONS.inc(evictions - self._last_evictions)
        self._last_dropped, self._last_evictions = dropped, evictions
