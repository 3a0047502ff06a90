"""Shared asyncio batching primitives for the serving tier.

Both micro-batchers — the device batcher (serve/batcher.py) and the peer
forwarding client (serve/peers.py) — coalesce queued work the same way:
the first item blocks, everything already enqueued drains immediately,
then an optional fixed window (the reference's BatchWait semantics,
peers.go:143-172) collects stragglers. The collect loop and its
cancellation-race handling live here so a fix lands in one place.
"""

from __future__ import annotations

import asyncio
import collections


async def pop_with_deadline(queue: "asyncio.Queue", timeout: float):
    """queue.get bounded by `timeout`; None on expiry. Race-safe where
    bare `wait_for(queue.get(), ...)` is not: when the window closes (or
    the caller is cancelled) just as an item arrives, the item is
    returned / handed back instead of silently dropped — a dropped
    item's caller would await its future forever. No await happens in the
    exception paths: while the getter is still PENDING, Queue.get keeps
    the item in the queue (it only pops at get_nowait after its waiter
    fires), so cancelling a pending getter loses nothing; only a DONE
    getter holds an item, and that is recovered synchronously.

    CAVEAT: the cancel-path hand-back uses put_nowait, which appends at
    the TAIL — the raced item loses its FIFO position behind later
    arrivals. Both current callers only cancel during teardown, where
    every queued item is failed regardless of order; a future caller
    that cancels mid-stream and cares about ordering must not reuse
    this helper as-is."""
    getter = asyncio.ensure_future(queue.get())
    try:
        return await asyncio.wait_for(asyncio.shield(getter), timeout)
    except asyncio.TimeoutError:
        if getter.done() and not getter.cancelled():
            return getter.result()  # raced: completed as the window shut
        getter.cancel()
        return None
    except asyncio.CancelledError:
        if getter.done() and not getter.cancelled():
            # hand the raced item back for the owner's cancel-drain loop
            queue.put_nowait(getter.result())
        else:
            getter.cancel()
        raise


#: no source at all (None is one: the batcher's own doors)
_NO_SOURCE = object()


class SourceLanes(asyncio.Queue):
    """The device batcher's queue: one FIFO lane a SOURCE (who enqueued
    the group: `source(item)`), collected in turn — the head group of
    each non-empty lane, least recently served lane first — so that
    what one source has queued never stands between another source's
    group and its launch. Upstream has no such wait — a peer's
    GetPeerRateLimits runs on its own goroutine beside the node's own
    callers (reference gubernator.go:210-225) — and in ONE
    arrival-order queue a ring member that is asked at its own door
    answers a peer's forwarded batch only behind its own door's whole
    backlog.

    Order inside a lane is arrival order. Within one launch (`collect`:
    collect_batch under this queue's `limit` and `weight`) a lane whose
    head no longer fits the room left is passed over while another
    lane's head fits, and is never starved by it: the lanes stand in
    the order they were last served, the front lane's head always rides
    the launch it opens, and serving a lane moves it to the back, so a
    group at the head of its lane launches within (lanes - 1) launches.
    When NO head fits, get hands out the front lane's: collect_batch
    parks it in its `carry`, which ends the launch, and `collect` puts
    it back where it was, first of the next launch.

    With ONE source queued this is asyncio.Queue, entry for entry: the
    front lane is the only lane."""

    def __init__(self, limit: int, weight, source):
        self._limit, self._weight, self._source = limit, weight, source
        super().__init__()

    def _init(self, maxsize):
        # source -> its groups, oldest first; the lanes in the order
        # they were last served (a lane that empties is dropped, one
        # that appears joins at the back)
        self._queue = collections.OrderedDict()
        # weight handed out in this launch: collect_batch's `total`
        self._taken = 0
        # the source whose lane opened the last launch
        self._opened = _NO_SOURCE

    def qsize(self) -> int:
        return sum(len(lane) for lane in self._queue.values())

    def queued(self) -> list:
        """Every queued item, lane by lane."""
        return [item for lane in self._queue.values() for item in lane]

    def heads(self) -> list:
        """Each non-empty lane's oldest item."""
        return [lane[0] for lane in self._queue.values()]

    def _lane(self, item):
        """(the item's source, its lane: made, at the back, if need be)"""
        src = self._source(item)
        lane = self._queue.get(src)
        if lane is None:
            lane = self._queue[src] = collections.deque()
        return src, lane

    def _pop(self, src):
        """The head of `src`'s lane; a lane that empties is dropped."""
        lane = self._queue[src]
        item = lane.popleft()
        if not lane:
            del self._queue[src]
        return item

    def _put(self, item):
        self._lane(item)[1].append(item)

    def _get(self):
        lanes = self._queue
        if self._taken:
            room = self._limit - self._taken
            for src, lane in lanes.items():
                if self._weight(lane[0]) <= room:
                    break
            else:
                # no head fits: the front lane's, for the collector to
                # park (collect puts it back; the lanes keep their order)
                return self._pop(next(iter(lanes)))
        else:
            # a launch opens with the front lane's head, whatever its
            # weight — not with the lane that opened the last one while
            # another waits (a lane that stood alone was served last
            # AND is the front)
            src = next(iter(lanes))
            if src == self._opened and len(lanes) > 1:
                lanes.move_to_end(src)
                src = next(iter(lanes))
            self._opened = src
        self._taken += self._weight(lanes[src][0])
        item = self._pop(src)
        if src in lanes:
            lanes.move_to_end(src)
        return item

    async def collect(self, into: list, wait: float, hold_while=None) -> list:
        """One launch collected INTO the caller's list: collect_batch's
        contract (first item blocks, what is queued drains, the `wait`
        and `hold_while` windows, a cancel leaves `into` visible), the
        lanes taken in turn. The group that did not fit goes back to
        the head of its lane, and the next launch's turn is that
        lane's; no await lies between its parking and here, so a
        cancel never finds it outside the queue."""
        self._taken = 0
        carry: list = []
        await collect_batch(
            self, self._limit, wait, into,
            weight=self._weight, carry=carry, hold_while=hold_while,
        )
        if carry:
            item = carry.pop()
            src, lane = self._lane(item)
            lane.appendleft(item)
            self._queue.move_to_end(src, last=False)
        return into


#: poll period of collect_batch's hold_while phase — how long after the
#: hold condition clears a deep batch may still sit unflushed. Device
#: batch periods in deep mode are milliseconds, so 0.2ms of flush slack
#: is noise there while keeping the idle-transition latency tight.
HOLD_POLL_S = 0.0002


async def collect_batch(
    queue: "asyncio.Queue",
    limit: int,
    wait: float,
    into: list,
    weight=None,
    carry: list = None,
    hold_while=None,
) -> list:
    """Collect one coalesced batch INTO the caller's list (so a cancel
    mid-collect leaves the partial batch visible to the caller's drain
    handler — a local list would be lost with the exception). Blocks for
    the first item, drains everything already enqueued, then waits out
    the optional `wait` window for stragglers.

    `weight` (item -> int) makes `limit` count underlying units instead
    of queue items — the device batcher enqueues whole request GROUPS
    (one per RPC) and its limit is in requests. Groups are never split;
    a group that would push the batch PAST the limit is parked in
    `carry` (a persistent caller-owned list, drained first next round)
    so batches never exceed the limit — except a single group bigger
    than the limit, which ships alone (progress over strictness; the
    engine's ladder covers MAX_BATCH_SIZE, the per-RPC cap). Callers
    passing `weight` must pass `carry` and must drain it on teardown.

    `hold_while` (-> bool) is the deep-accumulation hook: after the
    drain and straggler phases, keep collecting toward `limit` for as
    long as the predicate holds. The device batcher passes "the submit
    gate is saturated" — while every pipeline slot is occupied a flush
    could not submit anyway, so accumulating costs zero latency and
    builds the deep batches that amortize per-batch fixed costs (the
    big-store writeback pass). The predicate is re-polled every
    HOLD_POLL_S; when it clears (a slot freed — the device is about to
    go idle) the batch flushes immediately, preserving the submit/wait
    overlap of host marshalling with device execution. With the
    predicate never true (default None), behavior is exactly the
    historical drain + wait semantics."""
    if weight is None:
        weight = lambda _i: 1  # noqa: E731
    total = 0
    if carry:
        item = carry.pop()
        into.append(item)
        total = weight(item)
    if not into:
        into.append(await queue.get())
        total = weight(into[-1])

    def take(item) -> bool:
        nonlocal total
        w = weight(item)
        if into and total + w > limit:
            carry.append(item)
            return False
        into.append(item)
        total += w
        return True

    def drain_ready() -> bool:
        """True while the batch can keep growing from queued items."""
        while total < limit:
            try:
                item = queue.get_nowait()
            except asyncio.QueueEmpty:
                return True
            if not take(item):
                return False
        return False

    if not drain_ready():
        return into
    if wait > 0:
        loop = asyncio.get_running_loop()
        deadline = loop.time() + wait
        while total < limit:
            timeout = deadline - loop.time()
            if timeout <= 0:
                break
            item = await pop_with_deadline(queue, timeout)
            if item is None:
                break
            if not take(item):
                return into
    while (
        total < limit and hold_while is not None and hold_while()
    ):
        item = await pop_with_deadline(queue, HOLD_POLL_S)
        if item is not None and not take(item):
            return into
    return into
