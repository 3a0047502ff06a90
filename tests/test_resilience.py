"""Peer resilience layer (r8): circuit breaker transitions, retry
policy + budget exhaustion, degraded mode, breaker-aware health, the
GlobalManager's supervised restarts, and graceful drain — the fast
in-process matrix behind the chaos soak (test_chaos_soak.py runs the
kill-a-real-node version, marked slow).
"""

import asyncio
import struct

import grpc
import pytest

from gubernator_tpu.api import convert
from gubernator_tpu.api.proto.gen import peers_pb2
from gubernator_tpu.api.types import (
    Behavior,
    PeerInfo,
    RateLimitReq,
    RateLimitResp,
)
from gubernator_tpu.serve.backends import ExactBackend
from gubernator_tpu.serve.breaker import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    BreakerOpenError,
    CircuitBreaker,
)
from gubernator_tpu.serve.config import BehaviorConfig, ServerConfig
from gubernator_tpu.serve.instance import Instance
from gubernator_tpu.serve.peers import PeerClient, is_retryable


def _req(key="k", hits=1, behavior=Behavior.BATCHING) -> RateLimitReq:
    return RateLimitReq(
        name="res", unique_key=key, hits=hits, limit=10, duration=60000,
        behavior=behavior,
    )


# -- circuit breaker state machine ----------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _tripped(b):
    for _ in range(b.failures):
        assert b.acquire()
        b.record_failure()
    return b


def test_breaker_trips_on_consecutive_failures_and_fails_fast():
    clk = _Clock()
    b = _tripped(CircuitBreaker(failures=3, cooldown=1.0, clock=clk))
    assert b.state == OPEN
    assert not b.acquire()  # fail fast, no probe before cooldown


def test_breaker_half_open_probe_closes_on_success():
    clk = _Clock()
    b = _tripped(CircuitBreaker(failures=3, cooldown=1.0, clock=clk))
    clk.t = 1.5  # past cooldown
    assert b.acquire()  # the half-open probe
    assert b.state == HALF_OPEN
    assert not b.acquire()  # probes bounded (probes=1)
    b.record_success()
    assert b.state == CLOSED
    assert b.acquire()


def test_breaker_probe_failure_reopens_and_restarts_cooldown():
    clk = _Clock()
    b = _tripped(CircuitBreaker(failures=3, cooldown=1.0, clock=clk))
    clk.t = 1.5
    assert b.acquire()
    b.record_failure()  # probe failed
    assert b.state == OPEN
    clk.t = 2.0  # cooldown restarted at 1.5 — still open
    assert not b.acquire()
    clk.t = 2.6
    assert b.acquire()
    b.record_success()
    assert b.state == CLOSED


def test_breaker_ratio_trip_without_consecutive_failures():
    # alternate ok/fail: never 3 consecutive, but 50% failures over a
    # full window must trip
    b = CircuitBreaker(failures=3, ratio=0.5, window=8, cooldown=1.0,
                       clock=_Clock())
    for i in range(8):
        assert b.acquire()
        (b.record_failure if i % 2 else b.record_success)()
    assert b.state == OPEN


def test_breaker_transition_callback():
    seen = []
    clk = _Clock()
    b = CircuitBreaker(failures=2, cooldown=1.0, clock=clk,
                       on_transition=lambda f, t: seen.append((f, t)))
    _tripped(b)
    clk.t = 2.0
    b.acquire()
    b.record_success()
    assert seen == [
        (CLOSED, OPEN), (OPEN, HALF_OPEN), (HALF_OPEN, CLOSED)
    ]


def test_breaker_effective_state_half_open_without_traffic():
    """An idle breaker past its cooldown must READ as half-open (code
    1), or health/metrics would report a recovered peer as down
    forever once traffic was routed away (no acquire -> the lazy
    OPEN->HALF_OPEN transition never runs)."""
    clk = _Clock()
    b = _tripped(CircuitBreaker(failures=3, cooldown=1.0, clock=clk))
    assert b.effective_state() == OPEN and b.state_code == 2
    clk.t = 1.5  # cooldown elapsed, NO acquire happened
    assert b.state == OPEN  # stored state unchanged (lazy)
    assert b.effective_state() == HALF_OPEN and b.state_code == 1


def test_breaker_stale_outcome_cannot_close_or_reopen():
    """A slow call admitted while CLOSED that resolves during a later
    half-open must not masquerade as a probe: its success must not
    close the breaker, its failure must not restart the cooldown
    (acquire/record straddle the RPC await, so this interleaving is
    real — a hung call outliving the trip)."""
    clk = _Clock()
    b = CircuitBreaker(failures=3, cooldown=1.0, probes=1, clock=clk)
    straggler = b.acquire()  # admitted while CLOSED, then hangs
    _tripped(b)  # meanwhile fast calls trip the breaker
    clk.t = 1.5
    probe = b.acquire()  # the real half-open probe, in flight
    assert b.state == HALF_OPEN
    b.record_success(straggler)  # straggler resolves late
    assert b.state == HALF_OPEN  # NOT closed by the stale success
    b.record_failure(straggler)
    assert b.state == HALF_OPEN  # NOT re-opened by the stale failure
    b.record_success(probe)  # only the true probe decides
    assert b.state == CLOSED


# -- retry policy ----------------------------------------------------------


class _UnavailableError(grpc.RpcError):
    def code(self):
        return grpc.StatusCode.UNAVAILABLE


def test_is_retryable_classification():
    assert is_retryable(_UnavailableError())
    assert is_retryable(ConnectionRefusedError())
    assert not is_retryable(asyncio.TimeoutError())  # may have applied
    assert not is_retryable(RuntimeError("boom"))
    # a pure-peek batch is idempotent: anything retries
    assert is_retryable(asyncio.TimeoutError(), all_peek=True)
    assert is_retryable(RuntimeError("boom"), all_peek=True)


class _FlakyStub:
    """Fails the first `fail_n` calls with UNAVAILABLE, then succeeds."""

    def __init__(self, fail_n):
        self.fail_n = fail_n
        self.calls = 0

    async def GetPeerRateLimits(self, pb_req, timeout=None):
        self.calls += 1
        if self.calls <= self.fail_n:
            raise _UnavailableError()
        return peers_pb2.GetPeerRateLimitsResp(
            rate_limits=[
                convert.resp_to_pb(RateLimitResp(limit=10, remaining=9))
                for _ in pb_req.requests
            ]
        )


def _client(stub, **kw) -> PeerClient:
    defaults = dict(peer_retries=2, peer_backoff=0.001,
                    peer_backoff_max=0.002)
    defaults.update(kw)
    c = PeerClient(BehaviorConfig(**defaults), "127.0.0.1:1")
    c.stub = stub
    return c


def test_retry_masks_transient_unavailable():
    async def run():
        stub = _FlakyStub(fail_n=2)
        c = _client(stub)
        resps = await c.get_peer_rate_limits([_req()])
        assert resps[0].remaining == 9
        assert stub.calls == 3  # 2 failures + 1 success

    asyncio.run(run())


def test_retry_budget_exhaustion_raises():
    async def run():
        stub = _FlakyStub(fail_n=100)
        c = _client(stub, peer_retries=2)
        with pytest.raises(grpc.RpcError):
            await c.get_peer_rate_limits([_req()])
        assert stub.calls == 3  # initial + 2 retries, then give up

    asyncio.run(run())


def test_no_retry_for_nonretryable_on_hit_batch():
    class _DeadlineStub:
        calls = 0

        async def GetPeerRateLimits(self, pb_req, timeout=None):
            self.calls += 1
            raise RuntimeError("application error")

    async def run():
        stub = _DeadlineStub()
        c = _client(stub)
        with pytest.raises(RuntimeError):
            await c.get_peer_rate_limits([_req(hits=1)])
        assert stub.calls == 1  # hits may have applied: never re-sent

    asyncio.run(run())


def test_peek_batch_retries_any_failure():
    class _FlakyAppStub:
        calls = 0

        async def GetPeerRateLimits(self, pb_req, timeout=None):
            self.calls += 1
            if self.calls == 1:
                raise RuntimeError("transient application error")
            return peers_pb2.GetPeerRateLimitsResp(
                rate_limits=[
                    convert.resp_to_pb(RateLimitResp(limit=10, remaining=10))
                    for _ in pb_req.requests
                ]
            )

    async def run():
        stub = _FlakyAppStub()
        c = _client(stub)
        resps = await c.get_peer_rate_limits([_req(hits=0)])
        assert resps[0].remaining == 10
        assert stub.calls == 2

    asyncio.run(run())


def test_deadline_bounds_hung_stub():
    class _HungStub:
        async def GetPeerRateLimits(self, pb_req, timeout=None):
            await asyncio.Event().wait()

    async def run():
        c = _client(_HungStub(), peer_timeout=0.05, peer_retries=0)
        t0 = asyncio.get_running_loop().time()
        with pytest.raises(asyncio.TimeoutError):
            await c.get_peer_rate_limits([_req()])
        assert asyncio.get_running_loop().time() - t0 < 1.0

    asyncio.run(run())


def test_breaker_fails_fast_after_trip():
    async def run():
        stub = _FlakyStub(fail_n=10**9)
        c = _client(stub, peer_retries=0, breaker_failures=3,
                    breaker_cooldown=60.0)
        for _ in range(3):
            with pytest.raises(grpc.RpcError):
                await c.get_peer_rate_limits([_req()])
        calls = stub.calls
        with pytest.raises(BreakerOpenError):
            await c.get_peer_rate_limits([_req()])
        assert stub.calls == calls  # no RPC attempted while open

    asyncio.run(run())


def test_trip_failure_raises_root_cause_not_breaker_error():
    """When the failure that trips the breaker is itself retryable,
    the caller must get THAT error immediately — not a backoff sleep
    followed by BreakerOpenError masking the root cause."""

    async def run():
        stub = _FlakyStub(fail_n=10**9)
        # breaker_failures=2, retries allowed: the 2nd attempt's
        # UNAVAILABLE trips the breaker mid-retry-loop
        c = _client(stub, peer_retries=5, breaker_failures=2,
                    breaker_cooldown=60.0)
        with pytest.raises(_UnavailableError):
            await c.get_peer_rate_limits([_req()])
        assert stub.calls == 2  # stopped at the trip, no wasted retries

    asyncio.run(run())


# -- instance-level: per-item errors, degraded mode, health ---------------


def _conf(**kw) -> ServerConfig:
    conf = ServerConfig(
        grpc_address="127.0.0.1:1",
        advertise_address="127.0.0.1:1",
        backend="exact",
        behaviors=BehaviorConfig(
            peer_timeout=0.2, peer_retries=1, peer_backoff=0.001,
            peer_backoff_max=0.002, breaker_failures=3,
            breaker_cooldown=60.0,
        ),
    )
    for k, v in kw.items():
        setattr(conf, k, v)
    return conf


async def _instance_with_dead_peer(conf):
    """Instance owning nothing: all keys route to a peer address
    nothing listens on (connect-refused surfaces at RPC time, like the
    reference)."""
    from tests._util import free_ports

    dead = f"127.0.0.1:{free_ports(1)[0]}"
    inst = Instance(conf, ExactBackend(1000))
    inst.start()
    await inst.set_peers([
        PeerInfo(address=conf.advertise_address, is_owner=True),
        PeerInfo(address=dead, is_owner=False),
    ])
    # find keys the DEAD peer owns
    keys = []
    for i in range(256):
        r = _req(key=f"k{i}")
        if inst.get_peer(r.hash_key()).host == dead:
            keys.append(r)
        if len(keys) >= 4:
            break
    assert keys, "no key landed on the dead peer in 256 tries"
    return inst, dead, keys


def test_retry_exhaustion_surfaces_per_item_errors_not_exceptions():
    async def run():
        inst, dead, keys = await _instance_with_dead_peer(_conf())
        try:
            resps = await inst.get_rate_limits(keys)
            for r in resps:
                assert "from peer" in r.error  # per-item, not a 503
        finally:
            await inst.stop()

    asyncio.run(run())


def test_degraded_mode_answers_locally_with_metadata():
    async def run():
        inst, dead, keys = await _instance_with_dead_peer(
            _conf(degraded_local=True)
        )
        try:
            resps = await inst.get_rate_limits(keys)
            for r in resps:
                assert r.error == ""
                assert r.metadata["degraded"] == "true"
                assert r.metadata["owner"] == dead
                assert r.remaining == 9  # decided by the LOCAL store
            # hits actually landed locally: a second round decrements
            resps = await inst.get_rate_limits(keys)
            for r in resps:
                assert r.remaining == 8
        finally:
            await inst.stop()

    asyncio.run(run())


def test_health_reports_open_breaker():
    async def run():
        inst, dead, keys = await _instance_with_dead_peer(_conf())
        try:
            assert inst.health_check().status == "healthy"
            # trip the dead peer's breaker (breaker_failures=3, retries
            # count too: 2 attempts/request)
            for _ in range(3):
                await inst.get_rate_limits(keys[:1])
            h = inst.health_check()
            assert h.status == "unhealthy"
            assert "circuit open" in h.message and dead in h.message
        finally:
            await inst.stop()

    asyncio.run(run())


# -- GlobalManager supervision --------------------------------------------


def test_global_loops_restart_with_metric():
    from gubernator_tpu.serve import metrics
    from gubernator_tpu.serve.global_mgr import GlobalManager

    async def run():
        class _Inst:
            def get_peer(self, key):
                raise RuntimeError("unused")

            def peer_list(self):
                return []

        mgr = GlobalManager(
            BehaviorConfig(global_sync_wait=0.001), _Inst()
        )
        sent = []
        killed = asyncio.Event()

        async def dying_send(hits):
            killed.set()
            raise RuntimeError("injected loop death")

        async def recording_send(hits):
            sent.append(hits)

        mgr._send_hits = dying_send
        before = metrics.GLOBAL_TASK_RESTARTS.labels(
            task="async_hits"
        )._value.get()
        mgr.start()
        try:
            mgr.queue_hit(_req(key="g1", behavior=Behavior.GLOBAL))
            await asyncio.wait_for(killed.wait(), 5)
            # loop died; the supervisor must restart it and the next
            # queued hit must flow
            mgr._send_hits = recording_send
            for _ in range(200):
                mgr.queue_hit(_req(key="g2", behavior=Behavior.GLOBAL))
                if sent:
                    break
                await asyncio.sleep(0.02)
            assert sent, "async-hits loop never came back"
            assert metrics.GLOBAL_TASK_RESTARTS.labels(
                task="async_hits"
            )._value.get() > before
        finally:
            await mgr.stop()

    asyncio.run(run())


# -- graceful drain --------------------------------------------------------


def test_batcher_drain_waits_for_inflight_work():
    from gubernator_tpu.serve.batcher import DeviceBatcher

    class _SlowBackend:
        decided = 0

        def decide(self, reqs, gnp):
            import time

            time.sleep(0.05)
            self.decided += len(reqs)
            return [RateLimitResp(limit=r.limit, remaining=1)
                    for r in reqs]

        def update_globals(self, updates):
            pass

    async def run():
        backend = _SlowBackend()
        b = DeviceBatcher(backend, batch_wait=0.0)
        b.start()
        futs = [asyncio.ensure_future(b.decide([_req(key=f"d{i}")],
                                               [False]))
                for i in range(4)]
        await asyncio.sleep(0)  # let them enqueue
        await asyncio.wait_for(b.drain(), 10)
        # drain returned: the backend has decided everything that was
        # in flight and every result is set. The callers' coroutines
        # resume on the loop's next turns (on a loaded host not yet:
        # this read False once in the driver's run of PR 26)
        assert backend.decided == 4
        _, pending = await asyncio.wait(futs, timeout=5)
        assert not pending
        for f in futs:
            assert (await f)[0].remaining == 1
        await b.stop()

    asyncio.run(run())


def test_global_mgr_drain_flushes_pending():
    from gubernator_tpu.serve.global_mgr import GlobalManager

    async def run():
        class _Inst:
            def get_peer(self, key):
                raise RuntimeError("unused")

            def peer_list(self):
                return []

        # LONG sync window: without drain() these hits would sit for 60s
        mgr = GlobalManager(BehaviorConfig(global_sync_wait=60.0), _Inst())
        sent = []

        async def recording_send(hits):
            sent.append(hits)

        mgr._send_hits = recording_send
        mgr.queue_hit(_req(key="g1", behavior=Behavior.GLOBAL))
        await mgr.drain()
        assert len(sent) == 1 and "res_g1" in sent[0]

    asyncio.run(run())


def test_edge_bridge_drain_answers_inflight_then_refuses():
    """Drain under load at the bridge: a frame in flight when drain
    begins is ANSWERED (no in-flight frame loss), the next frame gets
    the GEBR drain code, and new connections are refused."""
    from gubernator_tpu.serve.edge_bridge import (
        DRAIN_FRAME_ID,
        MAGIC_STALE,
        MAGIC_WREQ,
        MAGIC_WRESP,
        EdgeBridge,
    )

    release = asyncio.Event()

    class _SlowInstance:
        async def get_rate_limits(self, reqs, stage_frame=False):
            await release.wait()
            return [RateLimitResp(limit=r.limit, remaining=3)
                    for r in reqs]

    def _witem():
        name, key = b"res", b"dk"
        return (
            struct.pack("<H", len(name)) + name
            + struct.pack("<H", len(key)) + key
            + struct.pack("<qqqBB", 1, 9, 60000, 0, 0)
        )

    def _wframe(frame_id):
        payload = _witem()
        return (
            struct.pack("<II", MAGIC_WREQ, 1)
            + struct.pack("<IQ", frame_id, 0)
            + struct.pack("<I", len(payload))
            + payload
        )

    async def run():
        path = "/tmp/guber-bridge-drain-test.sock"
        bridge = EdgeBridge(_SlowInstance(), path)
        await bridge.start()
        reader, writer = await asyncio.open_unix_connection(path)
        # consume hello
        magic, flags, rhash, n = struct.unpack(
            "<IIII", await reader.readexactly(16)
        )
        assert n == 0
        # frame 7 starts serving, parked on `release`
        writer.write(_wframe(7))
        await writer.drain()
        while bridge._active_frames == 0:
            await asyncio.sleep(0.005)
        # drain begins with frame 7 in flight
        drain_task = asyncio.ensure_future(bridge.drain(5.0))
        await asyncio.sleep(0.02)
        # frame 8 arrives during the drain: must be refused AFTER 7
        # completes
        writer.write(_wframe(8))
        await writer.drain()
        await asyncio.sleep(0.02)
        release.set()
        # response for 7 first (it was in flight), then the drain GEBR
        magic, n = struct.unpack("<II", await reader.readexactly(8))
        assert magic == MAGIC_WRESP and n == 1
        (fid,) = struct.unpack("<I", await reader.readexactly(4))
        assert fid == 7
        body = await reader.readexactly(n * 29)
        status, limit, remaining, reset = struct.unpack_from(
            "<Bqqq", body
        )
        assert remaining == 3
        magic, fid = struct.unpack("<II", await reader.readexactly(8))
        assert magic == MAGIC_STALE and fid == DRAIN_FRAME_ID
        await asyncio.wait_for(drain_task, 5)
        # new connections are refused while draining
        with pytest.raises((ConnectionError, asyncio.IncompleteReadError)):
            r2, w2 = await asyncio.open_unix_connection(path)
            await r2.readexactly(16)
        await bridge.stop()

    asyncio.run(run())
