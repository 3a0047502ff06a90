"""Peer routing: consistent-hash ownership + batching peer RPC client.

The host-level ring is wire- and placement-compatible with the reference
(crc32 point per peer, sorted ring, binary-search successor with
wraparound — reference hash.go:62-96), so a mixed cluster of reference
nodes and gubernator-tpu nodes would agree on key ownership. Within one
host, keys further shard across TPU chips (parallel/sharded.py); this ring
only decides which *host* coordinates a key.

PeerClient mirrors the reference's forwarding semantics (peers.go):
BATCHING/GLOBAL requests coalesce into micro-batches flushed every
`batch_wait` or at `batch_limit`; NO_BATCHING goes out as a direct unary
call. Implemented on asyncio instead of goroutines+channels: one flusher
task per peer, futures instead of response channels.
"""

from __future__ import annotations

import asyncio
import bisect
import logging
import random
import time
from typing import Dict, List, Optional, Sequence, Tuple

import grpc

from gubernator_tpu.api import convert
from gubernator_tpu.api.columns import (
    ForwardAnswers,
    ForwardGroup,
    split_ready,
)
from gubernator_tpu.api.grpc_glue import PeersV1Stub
from gubernator_tpu.api.proto.gen import peers_pb2
from gubernator_tpu.api.types import Behavior, RateLimitReq, RateLimitResp
from gubernator_tpu.core.hashing import native_lib, ring_hash
from gubernator_tpu.serve import metrics, tracing
from gubernator_tpu.serve.aio import collect_batch
from gubernator_tpu.serve.breaker import (
    OPEN as BREAKER_OPEN,
    BreakerOpenError,
    CircuitBreaker,
)
from gubernator_tpu.serve.config import BehaviorConfig
from gubernator_tpu.serve.faults import FAULTS, FaultError
from gubernator_tpu.serve.stages import STAGES

log = logging.getLogger("gubernator_tpu.peers")

#: why a forwarded item came back as an error: the label values of
#: peer_forward_failed_items_total. `deadline` is a peer that did not
#: answer in time (the hits may have been applied: never re-sent),
#: `breaker_open` a forward that was never sent, `closed` a client
#: replaced under its caller (set_peers), `transport` everything else
#: (refused, reset, an application error, a reply of the wrong length)
FORWARD_FAIL_REASONS = ("deadline", "breaker_open", "transport", "closed")


class ForwardCounts:
    """The forwarder side of the ring in plain ints, one object an
    Instance, shared by its PeerClients and exported at scrape
    (peer_forward_*_total) like the owner side's peer_serve_*."""

    __slots__ = ("batches", "items", "failed")

    def __init__(self):
        self.batches = 0  # GetPeerRateLimits RPCs sent
        self.items = 0  # the items in them
        self.failed = dict.fromkeys(FORWARD_FAIL_REASONS, 0)  # items


#: why the GEB door served a string frame on a shared ring through
#: request objects and not as columns: the label values of
#: edge_split_declined_total (serve/edge_bridge.py _plan_split)
SPLIT_DECLINE_REASONS = (
    "invalid_item",  # the native parse declined the payload
    "chain",  # a GEBC chain frame
    "foreign_global",  # a GLOBAL item another node owns (replica path)
    "foreign_no_batching",  # a NO_BATCHING one (its own unary RPC)
    "rescale_transition",  # an open double-serve window reroutes items
    "too_many_items",  # a frame past the per-RPC cap
    "no_arrays",  # a backend that takes no arrays
    "no_native",  # libguberhash.so is absent (core/hashing.native_lib)
    "error",  # the split raised before a row was sent anywhere
)


class SplitCounts:
    """The GEB door's split by owner in plain ints, one object an
    Instance, shared by its doors and exported at scrape
    (edge_split_*_total)."""

    __slots__ = ("frames", "items", "declined")

    def __init__(self):
        self.frames = 0  # string frames served split by owner
        # their items by lane: decided here, sent to the peer that owns
        # them, answered from the shed cache (owned and foreign alike)
        self.items = dict.fromkeys(("owned", "forwarded", "shed"), 0)
        self.declined = dict.fromkeys(SPLIT_DECLINE_REASONS, 0)  # frames


class PeerDeadlineError(asyncio.TimeoutError):
    """A peer RPC past its deadline, with words: asyncio's own
    TimeoutError prints as '' and the item's error text said nothing."""


def fail_reason(exc: BaseException) -> str:
    if isinstance(exc, asyncio.TimeoutError):
        return "deadline"
    if isinstance(exc, BreakerOpenError):
        return "breaker_open"
    if isinstance(exc, grpc.RpcError):
        code = getattr(exc, "code", None)
        try:
            if callable(code) and code() == grpc.StatusCode.DEADLINE_EXCEEDED:
                return "deadline"
        except Exception:
            pass
    return "transport"


def is_retryable(exc: BaseException, all_peek: bool = False) -> bool:
    """Safe-to-resend classification for the peer retry policy.

    `all_peek=True` (every request in the batch carries hits=0) makes
    ANY failure retryable — re-running a peek is free. Otherwise only
    failures where the request never reached the peer's application
    layer qualify: gRPC UNAVAILABLE (connection refused / reset before
    dispatch), plain connection errors, and injected faults flagged
    retryable. DEADLINE_EXCEEDED and application errors are NOT safe —
    the peer may have already applied the hits, and a rate limiter that
    double-counts under partial failure is worse than one that errors.
    """
    if all_peek:
        return True
    if isinstance(exc, FaultError):
        return exc.retryable
    if isinstance(exc, ConnectionError):
        return True
    if isinstance(exc, grpc.RpcError):
        code = getattr(exc, "code", None)
        try:
            return callable(code) and code() == grpc.StatusCode.UNAVAILABLE
        except Exception:
            return False
    return False


class _WireReply:
    """One GetPeerRateLimits reply taken as BYTES (a flusher batch's
    RPC): four answer columns by one native parse, or, where
    the parser declines (an item with an error or metadata, odd wire)
    or the library is absent, the protobuf runtime's items. Raises
    what the message path raises: a reply that is not the message, or
    one of another length than the batch."""

    __slots__ = ("cols", "items")

    def __init__(self, wire: bytes, n: int):
        lib = native_lib()
        got, self.cols = -1, None
        if lib is not None:
            got, self.cols = lib.parse_peer_answers(wire, n)
        self.items = None
        if got < 0:
            self.items = peers_pb2.GetPeerRateLimitsResp.FromString(
                wire
            ).rate_limits
            got = len(self.items)
        if got != n:
            raise RuntimeError(
                "peer responded with mismatched rate limit list size"
            )

    def answers(self, a: int, b: int) -> ForwardAnswers:
        if self.items is not None:
            return ForwardAnswers.from_resps(self.items[a:b])
        return ForwardAnswers(*(c[a:b] for c in self.cols))

    def resps(self, a: int, b: int) -> List[RateLimitResp]:
        if self.items is not None:
            return [convert.resp_from_pb(p) for p in self.items[a:b]]
        return self.answers(a, b).resps()


class PeerClient:
    """Connection to one peer (possibly this server itself)."""

    def __init__(
        self,
        conf: BehaviorConfig,
        host: str,
        is_owner: bool = False,
        mesh_local: bool = False,
        counts: Optional[ForwardCounts] = None,
    ):
        self.conf = conf
        self.host = host
        self.counts = counts if counts is not None else ForwardCounts()
        self.is_owner = is_owner  # true when this peer is this server
        # true when this peer's replica state rides THIS node's mesh
        # (PeerInfo.mesh_local): broadcast installs for it short-circuit
        # to one local mesh install (r21, global_mgr._update_peers)
        self.mesh_local = mesh_local
        self.channel: Optional[grpc.aio.Channel] = None
        self.stub: Optional[PeersV1Stub] = None
        # queue items are GROUPS: (reqs list, future resolving to the
        # matching resps list) or (ForwardGroup, future resolving to
        # its ForwardAnswers: forward_columns). One future per group
        # (r7 owner batching): a request batch forwarding hundreds of
        # items to one owner costs one enqueue + one future, not one
        # per item.
        self._queue: "asyncio.Queue[Tuple[List[RateLimitReq], asyncio.Future]]" = (  # noqa: E501
            asyncio.Queue()
        )
        # one-slot park for a group that would overflow the previous
        # batch (aio.collect_batch carry contract)
        self._carry: List = []
        self._flusher: Optional[asyncio.Task] = None
        self._closed = False
        # per-peer circuit breaker (r8): failures on THIS peer's RPCs
        # trip it; while open every call fails fast (BreakerOpenError)
        # instead of paying a deadline. State survives set_peers churn
        # because existing clients are reused there.
        self.breaker = self._make_breaker()

    def _make_breaker(self) -> Optional[CircuitBreaker]:
        c = self.conf
        if getattr(c, "breaker_failures", 0) <= 0:
            return None  # GUBER_BREAKER_FAILURES=0 disables

        def on_transition(frm: str, to: str) -> None:
            from gubernator_tpu.serve.breaker import STATE_CODES

            log.warning(
                "peer '%s' circuit breaker: %s -> %s", self.host, frm, to
            )
            try:
                metrics.PEER_BREAKER_TRANSITIONS.labels(
                    peer=self.host, to=to
                ).inc()
                metrics.PEER_BREAKER_STATE.labels(peer=self.host).set(
                    STATE_CODES[to]
                )
            except Exception:  # pragma: no cover - defensive
                pass

        return CircuitBreaker(
            failures=c.breaker_failures,
            ratio=c.breaker_ratio,
            window=c.breaker_window,
            cooldown=c.breaker_cooldown,
            probes=c.breaker_probes,
            on_transition=on_transition,
        )

    def connect(self) -> None:
        self._closed = False  # (re)opening
        if self.channel is None:
            # grpc.aio dials lazily and accepts any string, so validate
            # the target's SYNTAX eagerly. This mirrors the reference,
            # whose non-blocking grpc.Dial also only fails fast on
            # unparsable targets (gubernator.go:260-291): health reports
            # unhealthy for malformed peers, while well-formed but
            # unreachable ones surface at request time, as there.
            host, _, port = self.host.rpartition(":")
            if not host or not port.isdigit() or not (
                0 < int(port) < 65536
            ):
                raise ValueError(f"invalid peer address {self.host!r}")
            self.channel = grpc.aio.insecure_channel(
                self.host,
                options=[
                    # bound gRPC's reconnect backoff to the breaker
                    # cooldown: during an outage the channel's redial
                    # backoff grows (default cap 120s!), so without
                    # this the half-open probe after a peer RETURNS
                    # fails against a still-backed-off channel and
                    # recovery stretches far past the breaker's
                    # contract (measured 4s vs the 2-cooldown bound in
                    # the chaos soak)
                    ("grpc.initial_reconnect_backoff_ms", 100),
                    (
                        "grpc.max_reconnect_backoff_ms",
                        max(
                            200,
                            int(
                                getattr(
                                    self.conf, "breaker_cooldown", 1.0
                                )
                                * 1000
                            ),
                        ),
                    ),
                ],
            )
            self.stub = PeersV1Stub(self.channel)
        if self._flusher is None:
            self._flusher = asyncio.ensure_future(self._run())

    async def close(self) -> None:
        # before cancelling the flusher: an enqueue AFTER its cancel-time
        # queue drain would land in a queue nothing reads — the flag makes
        # late forwards (a caller holding this peer across set_peers)
        # fail fast instead
        self._closed = True
        if self._flusher is not None:
            self._flusher.cancel()
            try:
                await self._flusher
            except asyncio.CancelledError:
                pass
            self._flusher = None
        if self.channel is not None:
            await self.channel.close()
            self.channel = None

    # -- forwarding ---------------------------------------------------------

    async def get_peer_rate_limit(self, r: RateLimitReq) -> RateLimitResp:
        """Forward one request; batches unless NO_BATCHING
        (reference peers.go:73-90)."""
        if r.behavior in (Behavior.BATCHING, Behavior.GLOBAL):
            resps = await self.get_peer_rate_limits_grouped([r])
            return resps[0]
        if self._closed:
            raise RuntimeError(
                f"peer client for '{self.host}' is closed"
            )
        resp = await self.get_peer_rate_limits([r])
        return resp[0]

    async def get_peer_rate_limits_grouped(
        self, reqs: Sequence[RateLimitReq]
    ) -> List[RateLimitResp]:
        """Forward a whole group through the micro-batch flusher with
        ONE queue entry and ONE future (r7 owner batching). The group
        still coalesces with other callers' groups up to batch_limit
        — same wire behavior as per-item enqueueing, a fraction of the
        event-loop cost."""
        if not reqs:
            self._refuse_closed(0)
            return []
        return await self._enqueue(list(reqs))

    async def forward_columns(self, group: ForwardGroup) -> ForwardAnswers:
        """get_peer_rate_limits_grouped for a door that holds columns
        and no request objects (the GEB door's split by owner): the
        same queue, flusher, batch limit, deadline, breaker and retry
        rule; the RPC's bytes are serialised from the group's columns
        and key bytes and its reply parsed back to columns, one native
        call each (_forward_wire). Raises what the grouped call raises;
        the caller then rebuilds request objects (ForwardGroup.requests)
        for the failure code that exists."""
        return await self._enqueue(group)

    def _refuse_closed(self, n: int) -> None:
        if self._closed:
            self.counts.failed["closed"] += n
            raise RuntimeError(
                f"peer client for '{self.host}' is closed"
            )

    async def _enqueue(self, group):
        self._refuse_closed(len(group))
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        # the caller's trace context rides the queue entry (r16): the
        # flusher task that sends the batched RPC runs outside the
        # caller's context, so the traceparent must be captured HERE —
        # one branch, None for unsampled/untraced callers. The enqueue
        # stamp rides it too: forward_queue ends where the flusher
        # starts to build the group's RPC
        self._queue.put_nowait(
            (group, fut, tracing.propagation_header(), time.monotonic())
        )
        return await fut

    async def get_peer_rate_limits(
        self,
        reqs: Sequence[RateLimitReq],
        traceparent: Optional[str] = None,
    ) -> List[RateLimitResp]:
        """One GetPeerRateLimits RPC as messages, for a caller that
        goes round the flusher (a NO_BATCHING forward, GLOBAL gossip),
        with the forwarder's stages (forward_encode, forward_rpc,
        forward_decode: serve/stages.py PER_FORWARD), its counters, and
        for a failure one WARNING line and an error that says which
        deadline passed."""
        t_enc = time.monotonic()
        pb_req = peers_pb2.GetPeerRateLimitsReq(
            requests=[convert.req_to_pb(r) for r in reqs]
        )

        async def send(timeout, kw):
            pb_resp = await self.stub.GetPeerRateLimits(
                pb_req, timeout=timeout, **kw
            )
            if len(pb_resp.rate_limits) != len(reqs):
                raise RuntimeError(
                    "peer responded with mismatched rate limit list size"
                )
            return pb_resp, 0.0

        # a batch of pure peeks (hits all 0) is idempotent end to end;
        # anything carrying hits only retries transport-level failures
        # (is_retryable) so a slow peer is never double-counted
        pb_resp, t_got = await self._forward_rpc(
            send, len(reqs), all(r.hits == 0 for r in reqs), t_enc,
            traceparent,
        )
        resps = [convert.resp_from_pb(p) for p in pb_resp.rate_limits]
        STAGES.add("forward_decode", time.monotonic() - t_got)
        return resps

    async def _forward_wire(self, batch, traceparent) -> _WireReply:
        """get_peer_rate_limits for a flusher batch: the request is the
        groups' serialised bytes end to end (a repeated field's
        serialisations concatenate) — a ForwardGroup by one native
        call, a group of request objects by the runtime — sent through
        the stub's pass-through method, and the reply's bytes are
        parsed to columns inside the same deadline, breaker and retry
        envelope that checks the message path's reply. The same three
        stages and counters; the parse's seconds are forward_decode's,
        not forward_rpc's."""
        t_enc = time.monotonic()
        parts, idempotent = [], True
        for group, *_ in batch:
            if isinstance(group, ForwardGroup):
                parts.append(group.to_wire())
                idempotent = idempotent and group.all_peeks()
            else:
                parts.append(
                    peers_pb2.GetPeerRateLimitsReq(
                        requests=[convert.req_to_pb(r) for r in group]
                    ).SerializeToString()
                )
                idempotent = idempotent and all(
                    r.hits == 0 for r in group
                )
        wire = b"".join(parts)
        n = sum(len(group) for group, *_ in batch)

        async def send(timeout, kw):
            raw = await self.stub.GetPeerRateLimitsWire(
                wire, timeout=timeout, **kw
            )
            t = time.monotonic()
            reply = _WireReply(raw, n)
            return reply, time.monotonic() - t

        reply, t_got = await self._forward_rpc(
            send, n, idempotent, t_enc, traceparent
        )
        STAGES.add("forward_decode", time.monotonic() - t_got)
        return reply

    async def _forward_rpc(
        self, send, n: int, idempotent: bool, t_enc: float,
        traceparent: Optional[str],
    ):
        """The part of a forward that does not depend on what its bytes
        were made from: `send(timeout, metadata kwargs)` -> (reply,
        the seconds it spent decoding the reply once it had it) inside
        the deadline, breaker and retry envelope, the RPC's counters,
        forward_encode (since `t_enc`) and forward_rpc (less those
        seconds, which are forward_decode's), and for a failure one
        WARNING line and an error that says which deadline passed.
        Returns (the reply, the stamp forward_decode starts from)."""
        timeout = self.conf.effective_peer_timeout()
        if traceparent is None:
            # direct callers (NO_BATCHING forwards, GLOBAL gossip) run
            # in their own context; batched callers pass the captured
            # header through _send_batch
            traceparent = tracing.propagation_header()
        # kwargs-style so the metadata key is ABSENT on untraced calls:
        # test fakes (and any stub-shaped embedder hook) predating r16
        # keep working untraced
        kw = (
            {"metadata": ((tracing.TRACEPARENT, traceparent),)}
            if traceparent
            else {}
        )
        counts = self.counts
        counts.batches += 1
        counts.items += n
        # bare stamps: forward_rpc crosses an await
        t_sent = time.monotonic()
        STAGES.add("forward_encode", t_sent - t_enc)
        try:
            reply, decoded_s = await self._call_resilient(
                lambda: send(timeout or None, kw), idempotent=idempotent,
                timeout=timeout,
            )
        except Exception as e:
            waited = time.monotonic() - t_sent
            STAGES.add("forward_rpc", waited)
            reason = fail_reason(e)
            counts.failed[reason] += n
            if reason == "deadline":
                knob = (
                    "GUBER_PEER_TIMEOUT_MS"
                    if self.conf.peer_timeout > 0
                    else "GUBER_BATCH_TIMEOUT_MS"
                )
                e = PeerDeadlineError(
                    f"no answer from peer '{self.host}' for {n} "
                    f"item(s) after {waited:.3f} s: past the deadline "
                    f"{knob} = {timeout * 1e3:g} ms "
                    f"(a batch that carries hits is not sent again)"
                )
            log.warning(
                "forward to peer '%s' failed (%s): %d item(s), waited "
                "%.3f s - %s",
                self.host, reason, n, waited,
                e if str(e) else type(e).__name__,
            )
            raise e
        t_got = time.monotonic() - decoded_s
        STAGES.add("forward_rpc", t_got - t_sent)
        return reply, t_got

    async def update_peer_globals(self, updates) -> None:
        """updates: sequence of (key, RateLimitResp). Installing a
        status replica is last-write-wins idempotent, so retries are
        always safe here."""
        pb_req = peers_pb2.UpdatePeerGlobalsReq(
            globals=[
                peers_pb2.UpdatePeerGlobal(
                    key=k, status=convert.resp_to_pb(s)
                )
                for k, s in updates
            ]
        )
        timeout = self.conf.global_timeout
        # originating context rides along when the install happens
        # inside a traced request (r16); the background gossip loops
        # have no context and send bare metadata
        tp = tracing.propagation_header()
        kw = {"metadata": ((tracing.TRACEPARENT, tp),)} if tp else {}

        async def call() -> None:
            await self.stub.UpdatePeerGlobals(
                pb_req, timeout=timeout or None, **kw
            )

        await self._call_resilient(call, idempotent=True, timeout=timeout)

    async def replicate_buckets(self, snaps, owner: str) -> None:
        """Ship owned-bucket snapshots to this peer (the key's ring
        successor, or — for a reconcile handback — its returned owner).
        `snaps`: sequence of serve/replication.Snapshot. Installs are
        last-write-wins by (reset_time, snapshot_ms), so retries and
        duplicate deliveries are always safe."""
        pb_req = peers_pb2.ReplicateBucketsReq(
            owner=owner,
            buckets=[
                peers_pb2.BucketSnapshot(
                    key=s.key,
                    algorithm=s.algorithm,
                    limit=s.limit,
                    duration=s.duration,
                    remaining=s.remaining,
                    reset_time=s.reset_time,
                    status=s.status,
                    snapshot_ms=s.snapshot_ms,
                )
                for s in snaps
            ],
        )
        timeout = self.conf.global_timeout
        tp = tracing.propagation_header()
        kw = {"metadata": ((tracing.TRACEPARENT, tp),)} if tp else {}

        async def call() -> None:
            await self.stub.ReplicateBuckets(
                pb_req, timeout=timeout or None, **kw
            )

        await self._call_resilient(call, idempotent=True, timeout=timeout)

    # -- resilience envelope (r8) -------------------------------------------

    async def _call_resilient(
        self, do_call, idempotent: bool, timeout: float
    ):
        """Deadline + circuit breaker + bounded retry around one peer
        RPC. The deadline wraps fault injection AND the RPC, so an
        injected hang (GUBER_FAULT_SPEC peer_rpc:hang) is bounded
        exactly like a wedged peer. Retries use exponential backoff
        with FULL jitter; only is_retryable failures re-send."""
        c = self.conf
        attempt = 0
        while True:
            b = self.breaker
            token = b.acquire() if b is not None else None
            if b is not None and not token:
                raise BreakerOpenError(
                    f"peer '{self.host}' circuit open (failing fast)"
                )
            try:
                result = await asyncio.wait_for(
                    self._guarded(do_call), timeout or None
                )
            except asyncio.CancelledError:
                # teardown, not peer health: release a half-open probe
                # slot without counting an outcome
                if b is not None:
                    b.record_cancel(token)
                raise
            except Exception as e:
                if b is not None:
                    b.record_failure(token)
                retries = getattr(c, "peer_retries", 0)
                if (
                    attempt < retries
                    and is_retryable(e, idempotent)
                    # when THIS failure tripped the breaker, don't
                    # sleep a backoff only to raise BreakerOpenError
                    # on re-acquire: fail fast with the root-cause
                    # error instead
                    and (b is None or b.state != BREAKER_OPEN)
                ):
                    attempt += 1
                    try:
                        metrics.PEER_RPC_RETRIES.labels(
                            peer=self.host
                        ).inc()
                    except Exception:  # pragma: no cover - defensive
                        pass
                    await asyncio.sleep(
                        random.uniform(
                            0.0,
                            min(
                                c.peer_backoff_max,
                                c.peer_backoff * (2 ** (attempt - 1)),
                            ),
                        )
                    )
                    continue
                raise
            if b is not None:
                b.record_success(token)
            return result

    async def _guarded(self, do_call):
        if FAULTS.enabled:
            await FAULTS.inject("peer_rpc", peer=self.host)
        return await do_call()

    # -- micro-batch flusher ------------------------------------------------

    async def _run(self) -> None:
        """Coalesce queued requests; flush at batch_limit or after
        batch_wait from the first enqueue (reference peers.go:143-172).
        Everything already enqueued is drained without waiting, so batches
        grow with in-flight RPC load while a lone request only waits the
        configured window (batch_wait=0 disables even that)."""
        while True:
            batch: List[Tuple[List[RateLimitReq], asyncio.Future]] = []
            try:
                await collect_batch(
                    self._queue,
                    self.conf.batch_limit,
                    self.conf.batch_wait,
                    batch,
                    weight=lambda g: max(1, len(g[0])),
                    carry=self._carry,
                )
                await self._send_batch(batch)
            except asyncio.CancelledError:
                # close() (e.g. set_peers replacing this peer) mid-collect
                # or mid-send: every caller parked on a queued future gets
                # an error, never a hang
                exc = RuntimeError(
                    f"peer client for '{self.host}' closed mid-batch"
                )

                def fail(group) -> None:
                    reqs, fut = group[0], group[1]
                    if not fut.done():
                        self.counts.failed["closed"] += len(reqs)
                        fut.set_exception(exc)

                for group in batch:
                    fail(group)
                for group in self._carry:
                    fail(group)
                self._carry.clear()
                while True:
                    try:
                        fail(self._queue.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                raise

    async def _send_batch(self, batch) -> None:
        # groups flatten into one peer RPC; responses slice back per
        # group (reference peers.go:143-172, group-granular here)
        t_collected = time.monotonic()
        for group in batch:
            STAGES.add("forward_queue", t_collected - group[3])
        # one traceparent per RPC: micro-batching can coalesce groups
        # from different traced callers, so the FIRST traced group's
        # context represents the wire hop (documented scope limit —
        # head sampling makes same-flush collisions rare)
        tp = next((g[2] for g in batch if g[2]), None)
        # every flusher batch goes out as bytes, whoever queued its
        # groups (_forward_wire); get_peer_rate_limits, the message
        # path, is the direct callers' (NO_BATCHING forwards, GLOBAL
        # gossip)
        try:
            reply = await self._forward_wire(batch, tp)
        except Exception as e:  # entire batch failed (peers.go:186-192)
            for _, fut, *_ in batch:
                if not fut.done():
                    fut.set_exception(
                        RuntimeError(f"while fetching from peer - '{e}'")
                    )
            return
        k = 0
        for g, fut, *_ in batch:
            a, k = k, k + len(g)
            if fut.done():
                continue
            if isinstance(g, ForwardGroup):
                fut.set_result(reply.answers(a, k))
            else:
                fut.set_result(reply.resps(a, k))


class ConsistentHashPicker:
    """Ring-placement-compatible peer picker (reference hash.go)."""

    def __init__(self, hash_fn=ring_hash):
        self._hash = hash_fn
        self._keys: List[int] = []
        self._by_point: Dict[int, PeerClient] = {}
        self._by_host: Dict[str, PeerClient] = {}
        # (ring points uint32, their peers) for owner_column: rebuilt
        # after an add, and a picker is never added to once it serves
        self._ring_cache = None

    def new(self) -> "ConsistentHashPicker":
        return ConsistentHashPicker(self._hash)

    def add(self, peer: PeerClient) -> None:
        point = self._hash(peer.host)
        existing = self._by_point.get(point)
        if existing is not None and existing.host != peer.host:
            # Two addresses colliding on one crc32 point (~2^-32 per
            # pair) would silently split ownership: this picker's
            # dict-overwrite (last add wins) disagrees with the edge's
            # sort-order tie-break, and the membership fingerprint
            # cannot catch it (same host set). Refuse loudly; set_peers
            # surfaces it through health (ADVICE r5 #3).
            raise ValueError(
                f"ring point collision: '{peer.host}' and "
                f"'{existing.host}' both hash to {point:#x}; rename one "
                f"peer address (placement would silently diverge "
                f"between pickers)"
            )
        if existing is None:
            bisect.insort(self._keys, point)
        self._by_point[point] = peer
        self._by_host[peer.host] = peer
        self._ring_cache = None

    def size(self) -> int:
        return len(self._keys)

    def peers(self) -> List[PeerClient]:
        return list(self._by_host.values())

    def get_peer_by_host(self, host: str) -> Optional[PeerClient]:
        return self._by_host.get(host)

    def get(self, key: str) -> PeerClient:
        """Successor peer on the ring for this key's point, wrapping
        (reference hash.go:80-96)."""
        if not self._keys:
            raise RuntimeError("unable to pick a peer; pool is empty")
        point = self._hash(key)
        i = bisect.bisect_left(self._keys, point)
        if i == len(self._keys):
            i = 0
        return self._by_point[self._keys[i]]

    def get_successor(self, key: str) -> Optional[PeerClient]:
        """The peer that would own `key` if its current owner left the
        ring: the next ring point after the key's, skipping points
        belonging to the owner itself (wraparound like get()). This is
        where the consistent hash routes the key on owner removal, so
        it is both the replication target (serve/replication.py) and
        the takeover route when the owner's breaker opens. None when
        the ring has fewer than two distinct hosts."""
        if not self._keys:
            return None
        point = self._hash(key)
        i = bisect.bisect_left(self._keys, point)
        if i == len(self._keys):
            i = 0
        owner = self._by_point[self._keys[i]]
        n = len(self._keys)
        for step in range(1, n):
            peer = self._by_point[self._keys[(i + step) % n]]
            if peer.host != owner.host:
                return peer
        return None

    def ownership_diff(
        self, new: "ConsistentHashPicker", keys: Sequence[str]
    ) -> Dict[str, Tuple["PeerClient", List[str]]]:
        """Keys THIS ring routes to this node (is_owner) that `new`
        routes to a DIFFERENT host, grouped by their new owner:
        {new_owner_host: (new_owner_client, [keys])}. This is the
        planned-handoff work list on a ring change (serve/rescale.py):
        call on the OLD picker with the new picker and the keys this
        node holds live windows for. Keys the old ring did not route
        here, and keys still owned here under `new`, contribute
        nothing; an empty old ring (never populated) diffs to nothing
        rather than raising."""
        out: Dict[str, Tuple[PeerClient, List[str]]] = {}
        if not self._keys or not new._keys:
            return out
        for key in keys:
            if not self.get(key).is_owner:
                continue
            owner = new.get(key)
            if owner.is_owner:
                continue
            entry = out.get(owner.host)
            if entry is None:
                out[owner.host] = (owner, [key])
            else:
                entry[1].append(key)
        return out

    def ring(self):
        """(points uint64[m] ascending, [the peer at each point],
        is_owner bool[m]): the ring as owner_column indexes it."""
        import numpy as np

        cached = self._ring_cache
        if cached is None:
            cached = self._ring_cache = (
                np.asarray(self._keys, dtype=np.uint64),
                [self._by_point[p] for p in self._keys],
            )
        points, peers = cached
        own = np.fromiter(
            (p.is_owner for p in peers), dtype=bool, count=len(peers)
        )
        return points, peers, own

    def owner_column(self, keys: Sequence[str], packed=None):
        """int32[len(keys)]: each key's position on ring() — the peer
        get() returns, key for key (bisect_left == lower bound,
        wraparound to 0). With `packed` (the keys' UTF-8 bytes joined
        by NUL, as the native string-frame parse leaves them) and the
        ring's own hash, one native call with the GIL released: crc32
        and the search a key (hashlib_native.ring_owners); otherwise
        one hash call per key and a single searchsorted."""
        import numpy as np

        if not self._keys:
            raise RuntimeError("unable to pick a peer; pool is empty")
        points = self.ring()[0]
        if packed is not None and self._hash is ring_hash and split_ready():
            # crc32 points: they fit the native call's uint32
            return native_lib().ring_owners(
                packed, len(keys), points.astype(np.uint32)
            )
        pts = np.fromiter(
            (self._hash(k) for k in keys), dtype=np.uint64, count=len(keys)
        )
        idx = np.searchsorted(points, pts, side="left")
        idx[idx == len(points)] = 0
        return idx.astype(np.int32)
