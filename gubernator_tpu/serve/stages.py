"""Stage-attribution clock for the serving pipeline.

Round 5's verdict called the served front door "admitted blind": the
device trace sees inside a batch, Prometheus sees per-RPC totals, but
nothing said WHERE a served decision's wall time went between the edge
socket and the response write. This module is that decomposition: a
process-global accumulator of per-stage monotonic spans, recorded at
fixed points of the serving path (the tuples below name every one) and
exposed as `/v1/debug/stages` and, lazily at scrape, `/metrics`
(serve/server.py); `scripts/trace_study.py` reads one window of it on
the chip.

Stages form six families:

- **per-frame stages** (`PER_FRAME`): spans that tile one edge frame's
  end-to-end wall time, so their totals are directly comparable to the
  frame e2e total. Coverage = sum(per-frame stage seconds) / e2e
  seconds; the gap is unattributed time (event-loop scheduling, frame
  reads) and should stay under ~10%.

    edge_to_bridge   frame send stamp (edge, CLOCK_MONOTONIC us) ->
                     frame fully read by the bridge. Windowed frames
                     only; monotonic epochs differ across hosts, so
                     the bridge calibrates each connection against the
                     smallest delta it has seen (epoch offset + floor
                     transit) and attributes time spent ABOVE that
                     floor: window queueing + socket backlog.
    bridge_decode    frame payload -> numpy fields / request objects
    shed             over-limit shed-cache screen of the frame's items
                     (serve/shedcache.py, r10) — the host-side answer
                     path for frozen token-bucket refusals. Items it
                     sheds never enqueue; a fully-shed frame has no
                     batch_queue/device span at all, and this stage is
                     what tiles that part of its e2e (the frame-
                     coverage contract keeps no hole)
    batch_queue      batcher enqueue -> flusher collect (per group)
    device           flusher collect -> responses resolved (per group;
                     covers submit + device execute + fetch + any wait
                     behind earlier pipelined batches)
    encode           responses resolved -> response frame written
    forward_wait     a string frame on the object path with items
                     another node owns (Instance.get_rate_limits): its
                     local lane done (or, with no local item, its
                     routing done) -> every forwarded group answered.
                     The forward lane's excess over the local lane, so
                     such a frame tiles as bridge_decode +
                     max(local lane, forward lane) + encode; its
                     interior is PER_FORWARD's. A node that owns every
                     key records none

- **per-batch stages** (`PER_BATCH`): recorded once per device batch,
  never per call, frame or item. Seven of them, `BATCH_TILES`, tile
  one batch's life from the flusher's collect to its futures resolved
  the way PER_FRAME tiles a frame — each stamp is taken once and ends
  one tile and begins the next, across the three threads a batch
  visits (the serving loop, `guber-submit`, `guber-fetch`; the stamps
  travel in the closure and in the fetch's return value):

    batch_e2e = admit_wait + submit_wake + submit_call + submit_return
              + fetch_wake + fetch_wait + fetch_return + resolve

  `batch_coverage` = sum(tile seconds) / `batch_e2e` seconds; the gap
  is the resolve (slicing the answers back per caller group and
  setting the futures, on the loop). They do NOT tile frame e2e (one
  batch serves many frames): they are the interior of `device` and of
  `call_device`. `submit_host` is kept as the sum it always was:
  submit_host = submit_wake + submit_call + submit_return, by
  construction (the same three stamps), so what used to be read as
  "submit_host less prep, merge and dispatch" is now two measured
  legs.

    admit_wait       flusher collect -> a pipeline slot taken
                     (batcher._flush_merged: `await _inflight.acquire()`
                     returned): the wait for one of fetch_depth slots,
                     plus whatever the flush did before it (a GLOBAL
                     install, a chain call of the same flush window)
    submit_wake      slot taken -> submit_call's first line on
                     `guber-submit`: the OUTBOUND leg of the hand-off —
                     the executor's queue (a run_serialized read or an
                     earlier batch still on the thread), the thread's
                     wake-up, and taking the GIL from a loop that keeps
                     running frames
    submit_call      submit_call's first line -> its last, on the
                     submit thread's own clock (a `span`: one bar on
                     the profiler's clock during a capture) = prep +
                     merge + dispatch. What the thread was TAKEN, GIL
                     waits inside its numpy and native calls included;
                     what it RAN is thread_cpu_seconds_total{submit}
    submit_return    submit_call's last line -> _flush_merged runs
                     again after its await: the RETURN leg —
                     call_soon_threadsafe, the loop's wake-up, the
                     ready callbacks queued ahead of it, the GIL
    submit_host      slot taken -> _flush_merged runs again: the three
                     above, summed by construction
    fetch_wake       end of submit_host -> _fetch's first line on
                     `guber-fetch`: the ensure_future hop to
                     _finish_arrays, the executor's queue, the thread's
                     wake-up, the GIL
    fetch_wait       decide_wait* span on the fetch pool
    fetch_return     fetch_wait's end -> _finish_arrays runs again on
                     the loop: the answer's way back; it is inside
                     every call's call_device and every frame's device
    batch_e2e        flusher collect -> the batch's futures set: the
                     denominator, one sample a device batch

  The chain lane (r15) submits AND waits inside one call on the submit
  thread, so its batch records admit_wait, the three submit legs,
  submit_host and batch_e2e and no fetch tile (its coverage is whole
  without them). `run_serialized` reads (bucket replication) and a
  host backend's blocking decide are no device batches and record
  none of this; a run_serialized read that holds the submit thread
  shows as the next batch's submit_wake.

  The submit thread's interior, inside submit_call:

    prep             waiting out arrival preps that hadn't finished
                     (~0 when the prep pool keeps up), plus the
                     conversion/presort of a group that carries no
                     prep future
    merge            k-way merge of the groups' pre-sorted runs into
                     one sorted, padded batch with its duplicate-key
                     groups derived (serve/prep.py, merge_prepped)
    dispatch         backend decide_submit_merged call: epoch
                     bookkeeping + input pack + device dispatch
    jit_call         the jitted decide call alone, inside dispatch
                     (PartitionedEngine._dispatch): the transfer of
                     the batch's ONE packed input array + launch, the
                     host side of the program
    observe          the serve-tier observe hook on the batch's numpy
                     fields, inside dispatch; dispatch - jit_call -
                     observe is pad + group-derive + the input pack
                     (kernels.pack_inputs), by subtraction
    shard_stack      mesh backends only: the owner presort's
                     per-shard slices padded to one sub-rung and
                     stacked [n_shards, B_sub] with their group
                     structure — inside merge on the arrival-prep
                     path, where it IS the merge: one native call
                     merges the runs and writes the stack
                     (PartitionedEngine.merge_prepped; the numpy
                     twin, _stack_presorted, where the library lacks
                     it); inside dispatch on the flush-time path
                     (_shard_stack, where a native prep fuses the
                     presort into it)

- **per-flush stages** (`PER_FLUSH`): the GLOBAL gossip loops, one
  sample a flush.

    global_peek      one owner broadcast's status peek
                     (GlobalManager._update_peers): the zero-hit
                     decide_local of every queued key, queue + device
                     + fetch of the batcher included

- **per-forward stages** (`PER_FORWARD`): the forwarder side of the
  ring (serve/peers.py PeerClient), recorded by a node that sends
  items to the peer that owns them and by no other: a daemon that
  owns every key records none. The four tile one forward from the
  instance's enqueue to the answers back with their groups:

    forward_queue    a group's enqueue (get_peer_rate_limits_grouped,
                     or forward_columns for the GEB door's split)
                     -> the flusher has collected its batch and starts
                     to build the RPC: BatchWait, the RPC in flight
                     ahead of it (one a peer), the loop. One sample a
                     GROUP (one frame's items for one owner)
    forward_encode   convert.req_to_pb x items and the message's
                     build; a column group's bytes by one native call
                     (PeerClient._forward_wire). One sample an RPC,
                     like the next two
    forward_rpc      RPC sent -> reply or failure in hand, deadline,
                     breaker and retries included (bare stamps: it
                     crosses an await): the wire, the owner's whole
                     GetPeerRateLimits call and this node's loop
                     getting back to it
    forward_decode   resp_from_pb x items (the slice back to the
                     groups is a few list slices, uncounted); for an
                     RPC that carried a column group, the reply's one
                     native parse (its seconds taken out of
                     forward_rpc) and the slices

  Beside them the plain counts peer_forward_batches_total,
  peer_forward_items_total and peer_forward_failed_items_total{reason}
  (/metrics, scrape-lazy): items / batches is the forwarded batch's
  size, failed items are error items some client was handed.

- **per-call stages** (`PER_CALL`): the gRPC door's family. Six of the
  seven `CALL_TILES` tile one call from handler entry to handler
  return the way PER_FRAME tiles a frame: a GetRateLimits call records
  `instance_route` and no `peer_serve`, a GetPeerRateLimits call
  `peer_serve` and no `instance_route`, both the other five.
  `call_coverage` = sum(tile seconds) / `call_e2e` seconds, and the
  gap is event-loop scheduling between tiles. The batcher records
  its three tiles for the first group a gRPC handler enqueues
  (mark_call / claim_call) and for no other: the r7 frame-coverage
  contract is untouched, and the JSON door, peer loops and internal
  callers add no tile seconds that lack a call_e2e. A call answered
  whole by the shed cache, or by an inline host backend's fast
  path, has no batcher tiles at all.

    grpc_decode      handler entry -> the call's items in the form the
                     instance serves: joining the trace, then pb ->
                     RateLimitReq list (the runtime's bytes -> pb ran
                     before the handler), or on a GetPeerRateLimits
                     batch the wire fold serves (serve/server.py
                     _serve_folded) the one native parse, wire bytes ->
                     columns, all of it inside the span
    instance_route   instance-side validation/routing/assembly,
                     recorded once per Instance.get_rate_limits call
                     from ANY door that reaches the instance (the
                     fast path bypasses it), and once per string frame
                     the bridge's fold serves, for the same work on
                     arrays: ownership screen, key hashing, traffic
                     observers, the managers' notes, GLOBAL queueing
    peer_serve       the owner side of a forwarded batch, once per
                     Instance.get_peer_rate_limits call: everything
                     it does on the loop that is not waiting for the
                     batcher. On a folded batch (columns): GLOBAL
                     items' broadcast queueing, the shed screen over
                     the whole batch, the residue's row selection, the
                     cache's population from the answers, the stitch
                     in place (shedcache.screened_decide; the keys
                     were hashed by the parse, inside grpc_decode). On
                     the object path: key hashing, the shed screen
                     item by item, the residue's lists, the
                     population, the stitch
    call_queue       batcher enqueue -> flusher collect, for the
                     call's group (the twin of batch_queue)
    call_device      flusher collect -> the group's future resolved
                     (the twin of device)
    call_wake        future resolved -> the awaiting coroutine runs
                     again: event-loop and GIL wait
    grpc_encode      RateLimitResp list -> pb in the servicer, or a
                     folded batch's answer columns -> wire bytes (one
                     native call)
    call_e2e         handler entry -> return: the denominator

- **process stages** (`PER_PROCESS`): what stalls every call at once,
  recorded by serve/server.py's probes.

    loop_lag         how late a 50 ms timer on the serving loop fired:
                     how late the loop runs what is ready
    gc_pause         one cyclic-GC collection, gc.callbacks start ->
                     stop

Every span above is WALL time: a thread's work plus its waits for the
one GIL. What the serving threads actually RAN is read from the
kernel's per-thread CPU clocks, at scrape and nowhere else
(`thread_clocks`, below: /metrics thread_cpu_seconds_total{thread} and
`threads` in /v1/debug/stages) — no span reads a CPU clock, because
the chip host's (a gVisor sandbox) advance in 10 ms ticks and each read
is a syscall under the GIL.

Everything is a plain float accumulation into the recording thread's
own table, no lock — ~0.5us per record — so the clock can stay on in
production. `/metrics` exports
the same totals as gauges (serve/metrics.py stage_seconds_total).
Each record also bumps one of `BUCKET_EDGES_S`'s log-scale buckets
(two an octave, 1 us .. 134 s), cumulative like the totals: a reader
differences two snapshots' `buckets` for a window's quantiles.

Thread-bound spans go through `STAGES.span(name)`: one pair of
monotonic stamps, one `add`, and — while a profiler session records
in a process that has imported JAX — a `jax.profiler.TraceAnnotation`
around the body, so a /v1/debug/profile capture shows the same span
on the profiler's clock beside the device's XLA Ops. This module
never imports JAX itself (the JAX-free client tier imports
serve/tracing.py, and through it this). Spans that cross an `await`
or belong to no thread (batch_queue, device, call_queue, call_device,
call_wake, call_e2e, global_peek, forward_wait, forward_queue,
forward_rpc, and the batch tiles between
threads: admit_wait, submit_wake, submit_return, submit_host,
fetch_wake, fetch_return, batch_e2e),
and the per-call ones recorded from bare stamps on the serving loop
(grpc_decode, instance_route, grpc_encode: tens of microseconds
each, and a span object a call is not free there; peer_serve, which
crosses the batcher's await), stay on the stage clock only.

The chain lane (r15) participates in BOTH families like the decide
lanes (r16 audit fix): a frame-flagged chained group records
batch_queue and device spans, and the serialized chain call records
submit_host and its three legs — before this, chained traffic added
frame e2e with no per-frame stages and silently diluted coverage.

Tracing tie-in (r16, serve/tracing.py): when the caller's context
carries an active trace, `add` forwards the same span into it — the
distributed tracer reuses these timings instead of running a second
clock. One ContextVar read per record when tracing is idle.
"""

from __future__ import annotations

import asyncio
import contextvars
import gc
import math
import os
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from gubernator_tpu.serve import tracing

PER_FRAME = (
    "edge_to_bridge",
    "bridge_decode",
    "shed",
    "batch_queue",
    "device",
    "encode",
    "forward_wait",
)
#: what tiles one forward, enqueue to answers: serve/peers.py
PER_FORWARD = (
    "forward_queue",
    "forward_encode",
    "forward_rpc",
    "forward_decode",
)
#: what tiles one device batch from the flusher's collect to its
#: futures resolved; submit_host = the three submit_* of them
BATCH_TILES = (
    "admit_wait",
    "submit_wake",
    "submit_call",
    "submit_return",
    "fetch_wake",
    "fetch_wait",
    "fetch_return",
)
PER_BATCH = BATCH_TILES + (
    "batch_e2e",
    "submit_host",
    "prep",
    "merge",
    "dispatch",
    "jit_call",
    "observe",
    "shard_stack",
)
PER_FLUSH = ("global_peek",)
CALL_TILES = (
    "grpc_decode",
    "instance_route",  # a GetRateLimits call's; a peer call has none
    "peer_serve",  # a GetPeerRateLimits call's; a client call has none
    "call_queue",
    "call_device",
    "call_wake",
    "grpc_encode",
)
PER_CALL = CALL_TILES + ("call_e2e",)
PER_PROCESS = ("loop_lag", "gc_pause")

#: upper edges of the quantile buckets: bucket 0 is [0, 1 us), bucket k
#: is [edge k-1, edge k), and one more bucket past the last edge takes
#: everything longer
BUCKET_EDGES_S = tuple(1e-6 * 2.0 ** (k / 2) for k in range(55))
_N_BUCKETS = len(BUCKET_EDGES_S) + 1


def bucket_of(seconds: float) -> int:
    if seconds < 1e-6:
        return 0
    return min(int(2 * math.log2(seconds * 1e6)) + 1, _N_BUCKETS - 1)


#: a gRPC door's handler marks its context (mark_call); the batcher
#: gives the call tiles to the FIRST group enqueued under the mark
#: (claim_call), so one call records one call_queue / call_device /
#: call_wake however many lanes its items ride, and a group from any
#: other caller records none: the tiles never outgrow call_e2e
_CALL: "contextvars.ContextVar" = contextvars.ContextVar(
    "guber_stage_call", default=None
)


def mark_call():
    return _CALL.set([True])


def unmark_call(token) -> None:
    _CALL.reset(token)


def claim_call() -> bool:
    mark = _CALL.get()
    return bool(mark) and mark.pop()


def _capturing() -> bool:
    """A profiler session is recording in this process. JAX is picked
    up from a process that has it, never imported here."""
    profiler = sys.modules.get("jax.profiler")
    return profiler is not None and profiler.TraceAnnotation.is_enabled()


class _Span:
    """One thread-bound span: see StageStats.span. `t0` and `t1` are
    its two monotonic stamps, for a caller that tiles the time around
    the span from the same instants (the batcher's hand-off legs)."""

    __slots__ = ("_stats", "_stage", "_ann", "t0", "t1")

    def __init__(self, stats: "StageStats", stage: str):
        self._stats = stats
        self._stage = stage

    def __enter__(self):
        # the annotation only while a capture runs: made and dropped
        # for nobody it is the larger half of an idle span's cost
        self._ann = None
        if _capturing():
            self._ann = sys.modules["jax.profiler"].TraceAnnotation(
                self._stage
            )
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._stats.add(self._stage, self.t1 - self.t0)
        return False


class StageStats:
    """Cumulative per-stage spans + frame end-to-end totals."""

    def __init__(self):
        # Every thread records into a table of its own — stage ->
        # [total seconds, count, bucket counts] — and takes no lock:
        # the gRPC path serves ~700 calls/s on one GIL with its submit
        # thread ~95% busy, and what a record costs there measured as
        # milliseconds of call_p50_ms on the chip (PERF.md, PR 24).
        # snapshot() sums the tables; it may read one between two of
        # an add's three updates, which a cumulative debug clock can
        # bear.
        self._local = threading.local()
        self._lock = threading.Lock()  # the list of tables, the frames
        self._tables: List[Dict[str, List]] = []
        self._e2e_s = 0.0
        self._frames = 0
        self._started = time.monotonic()

    def _table(self) -> Dict[str, List]:
        table = self._local.table = {}
        with self._lock:
            self._tables.append(table)
        return table

    def add(self, stage: str, seconds: float, n: int = 1) -> None:
        """`n` samples that lasted `seconds` in total."""
        if seconds < 0:  # clock skew guard (edge stamp from the future)
            return
        tr = tracing.active()
        if tr is not None:
            # the span just ended and lasted `seconds`: the trace gets
            # the stage clock's own timing, not a parallel measurement
            tr.add_span(stage, duration_s=seconds)
        try:
            table = self._local.table
        except AttributeError:
            table = self._table()
        rec = table.get(stage)
        if rec is None:
            rec = table[stage] = [0.0, 0, [0] * _N_BUCKETS]
        rec[0] += seconds
        rec[1] += n
        rec[2][bucket_of(seconds / n)] += n

    def span(self, stage: str) -> _Span:
        """`with STAGES.span("dispatch"):` records the body's wall time
        as one sample of `stage` and, where JAX is loaded, shows the
        same span in a profiler capture's host plane. For code that
        stays on one thread between enter and exit (no `await`
        inside): the profiler's annotations nest per thread."""
        return _Span(self, stage)

    def add_frame(self, e2e_seconds: float) -> None:
        """One edge frame fully served (edge send stamp when the frame
        carried one, else bridge read start -> response written). The
        denominator of per-frame stage coverage."""
        if e2e_seconds < 0:
            return
        with self._lock:
            self._e2e_s += e2e_seconds
            self._frames += 1

    def reset(self) -> None:
        with self._lock:
            for table in self._tables:
                table.clear()
            self._e2e_s = 0.0
            self._frames = 0
            self._started = time.monotonic()

    def snapshot(self) -> dict:
        summed: Dict[str, List] = {}
        with self._lock:
            for table in self._tables:
                for name, (total, count, buckets) in list(table.items()):
                    rec = summed.setdefault(name, [0.0, 0, [0] * _N_BUCKETS])
                    rec[0] += total
                    rec[1] += count
                    rec[2] = [a + b for a, b in zip(rec[2], buckets)]
            e2e_s, frames = self._e2e_s, self._frames
            window_s = time.monotonic() - self._started
        stages = {
            name: {
                "total_s": round(total, 6),
                "count": count,
                "mean_ms": round(total / count * 1e3, 4) if count else 0.0,
                "buckets": buckets,
            }
            for name, (total, count, buckets) in sorted(summed.items())
        }

        def total(names) -> float:
            return sum(
                s["total_s"] for n, s in stages.items() if n in names
            )

        attributed = total(PER_FRAME)
        call = stages.get("call_e2e", {"total_s": 0.0, "count": 0})
        batch = stages.get("batch_e2e", {"total_s": 0.0, "count": 0})
        return {
            "stages": stages,
            "per_frame_stages": list(PER_FRAME),
            "per_batch_stages": list(PER_BATCH),
            "per_batch_tiles": list(BATCH_TILES),
            "per_call_stages": list(PER_CALL),
            "per_flush_stages": list(PER_FLUSH),
            "per_forward_stages": list(PER_FORWARD),
            "per_process_stages": list(PER_PROCESS),
            "bucket_edges_s": list(BUCKET_EDGES_S),
            "frames": frames,
            "frame_e2e_total_s": round(e2e_s, 6),
            "attributed_total_s": round(attributed, 6),
            "coverage": round(attributed / e2e_s, 4) if e2e_s else 0.0,
            "calls": call["count"],
            "call_coverage": round(
                total(CALL_TILES) / call["total_s"], 4
            )
            if call["total_s"]
            else 0.0,
            "batches": batch["count"],
            "batch_coverage": round(
                total(BATCH_TILES) / batch["total_s"], 4
            )
            if batch["total_s"]
            else 0.0,
            "window_s": round(window_s, 3),
        }


class ProcessProbes:
    """The PER_PROCESS stages of one serving process: a 50 ms timer on
    the serving loop records how late it fired as `loop_lag`, and
    gc.callbacks times every collection as `gc_pause`. One per process
    (serve/server.py run_daemon), started on the running loop.

    The GC callback only appends to a list, which the timer drains
    into the clock at its next tick: a collection runs on whichever
    thread allocated last, and the clock's tables belong to threads.

    Two plain ints beside the stages, exported lazily at scrape
    (serve/server.py): `pauses_over` counts the ticks that saw the
    serving loop held for `pause_s` or longer — the tick itself that
    late, or a collection that long since the tick before (a
    collection holds the interpreter whichever thread it runs on, so
    it is the same pause: one tick counts once). `pause_s` is half of
    what a peer gives a forwarded batch
    (BehaviorConfig.effective_peer_timeout): a loop held that long on
    an owner has spent half of every waiting forwarder's deadline, and
    twice that fails a hit-carrying batch that is never sent again
    (loop_pauses_over_half_deadline_total). `programs_built` counts the
    programs XLA was handed since start() — compiled, or loaded from
    the persistent cache: either way traced and lowered first, on a
    serving thread, while a peer waits; the warm-up exists so that
    none is (programs_built_after_ready_total). Counted by a
    jax.monitoring listener, in a process that has JAX."""

    #: 20 Hz, not 100: each tick wakes the serving loop, and on the
    #: gRPC path a loop wake-up is not free (PERF.md, PR 24). A stall
    #: longer than a tick is always seen, a shorter one in proportion
    TICK_S = 0.050

    #: the XLA hand-over of one program (jax._src.dispatch
    #: BACKEND_COMPILE_EVENT): the "Finished XLA compilation" line of
    #: JAX_LOG_COMPILES, cache hit or not
    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, stats: "StageStats", pause_s: float = 0.25):
        self._stats = stats
        self.pause_s = pause_s
        self.pauses_over = 0
        self.programs_built = 0
        self._loop = self._timer = None
        self._due = 0.0
        self._gc_t0 = 0.0
        self._gc_pauses: List[float] = []

    def start(self) -> None:
        gc.callbacks.append(self._on_gc)
        self._loop = asyncio.get_running_loop()
        self._due = self._loop.time() + self.TICK_S
        self._timer = self._loop.call_at(self._due, self._tick)
        monitoring = sys.modules.get("jax.monitoring")
        if monitoring is not None:
            monitoring.register_event_duration_secs_listener(self._on_event)

    def stop(self) -> None:
        gc.callbacks.remove(self._on_gc)
        self._timer.cancel()
        self._drain_gc()
        monitoring = sys.modules.get("jax.monitoring")
        if monitoring is not None:
            monitoring.unregister_event_duration_listener(self._on_event)

    def _tick(self) -> None:
        # a bare timer callback, not a task that sleeps: the serving
        # loop pays for this twenty times a second
        now = self._loop.time()
        lag = max(0.0, now - self._due)
        self._stats.add("loop_lag", lag)
        if max(lag, self._drain_gc()) >= self.pause_s:
            self.pauses_over += 1
        self._due = now + self.TICK_S
        self._timer = self._loop.call_at(self._due, self._tick)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self._gc_pauses.append(time.monotonic() - self._gc_t0)

    def _drain_gc(self) -> float:
        """The collections since the last tick into the clock; the
        longest of them in seconds."""
        pauses, self._gc_pauses = self._gc_pauses, []
        for seconds in pauses:
            self._stats.add("gc_pause", seconds)
        return max(pauses, default=0.0)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if event == self.COMPILE_EVENT:
            self.programs_built += 1


#: the serving threads by role. `loop` is the thread that reads (the
#: scrape and snapshot handlers run on the serving loop), the next three
#: are serve/batcher.py's pools by the names it gives them, and `other`
#: is the rest of the process by subtraction: the PJRT client's, gRPC
#: core's and the native pools' threads
THREAD_ROLES = ("loop", "submit", "fetch", "prep", "other")
_POOL_ROLES = (
    ("guber-submit", "submit"),
    ("guber-fetch", "fetch"),
    ("guber-prep", "prep"),
)


def _pthread_cpu_s(thread: threading.Thread) -> Optional[float]:
    """The thread's CPU clock (user + system seconds it was on a
    core), or None where the host has none to give."""
    try:
        return time.clock_gettime(time.pthread_getcpuclockid(thread.ident))
    except (AttributeError, OSError, OverflowError, TypeError):
        return None


def _proc_stat_cpu_s(thread: threading.Thread) -> Optional[float]:
    """The same from /proc/self/task/<tid>/stat: utime + stime, the
    14th and 15th fields, in clock ticks."""
    try:
        with open(f"/proc/self/task/{thread.native_id}/stat", "rb") as f:
            fields = f.read().rsplit(b")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
    except (AttributeError, OSError, ValueError, IndexError):
        return None


class ThreadClocks:
    """Every serving thread's on-CPU seconds, summed by role: what the
    threads RAN, beside the stage clock's wall spans, which say what
    they were TAKEN. Read by the /metrics and /v1/debug/stages
    handlers and by nothing else — no hot path reads a CPU clock — so
    a reader differences two scrapes, each of which carries the
    monotonic clock of the same instant (`wall_s`).

    The source is chosen once, by trying it on the constructing
    thread: `pthread` (clock_gettime on pthread_getcpuclockid), else
    `proc_stat`, else `none` — and then `cpu_s` is empty: a series
    that cannot be read is absent, not 0."""

    SOURCES = (("pthread", _pthread_cpu_s), ("proc_stat", _proc_stat_cpu_s))

    def __init__(self, sources=SOURCES):
        self.source = "none"
        self._read: Optional[Callable] = None
        #: the smallest step the clock was seen to take (measure_granularity)
        self.granularity_s: Optional[float] = None
        me = threading.current_thread()
        for name, read in sources:
            if read(me) is not None:
                self.source, self._read = name, read
                break

    def snapshot(self) -> dict:
        """Call on the serving loop: the calling thread is `loop`."""
        out = {
            "thread_clock": self.source,
            "granularity_s": self.granularity_s,
            "wall_s": time.monotonic(),
            "cpu_s": {},
        }
        if self._read is None:
            return out
        cpu = dict.fromkeys(THREAD_ROLES, 0.0)
        me = threading.current_thread()
        for t in threading.enumerate():
            role = "loop" if t is me else next(
                (r for prefix, r in _POOL_ROLES if t.name.startswith(prefix)),
                None,
            )
            if role is not None:
                # a thread that exited since enumerate() reads as None
                cpu[role] += self._read(t) or 0.0
        # the process's clock and the threads' tick separately
        cpu["other"] = max(0.0, time.process_time() - sum(cpu.values()))
        out["cpu_s"] = cpu
        return out

    def measure_granularity(self) -> Optional[float]:
        """Spin on the calling thread until its clock has stepped three
        times (or 0.1 s is spent) and keep the smallest step: ~1 us on
        a kernel that accounts by the nanosecond, the tick (10 ms under
        gVisor) on one that samples. Once, at boot."""
        if self._read is None:
            return None
        me = threading.current_thread()
        deadline = time.monotonic() + 0.1
        last, seen = self._read(me), []
        while len(seen) < 3 and time.monotonic() < deadline:
            now = self._read(me)
            if now is None:
                break
            if now > last:
                seen.append(now - last)
                last = now
        self.granularity_s = min(seen) if seen else None
        return self.granularity_s


#: process-global clock; the doors, batcher, instance and engine record
#: here
STAGES = StageStats()
