"""PeerClient unit tests: the micro-batch flusher's load-bearing
behaviors (reference peers.go:143-207) driven against a fake stub —
flush at batch_limit without waiting, flush at the batch_wait window,
whole-batch failure fan-back, response-count-mismatch rejection, and
close() failing (not stranding) queued callers.
"""

import asyncio

import pytest

from _util import WireDoor
from gubernator_tpu.api import convert
from gubernator_tpu.api.proto.gen import peers_pb2
from gubernator_tpu.api.types import Behavior, RateLimitReq, RateLimitResp
from gubernator_tpu.serve.config import BehaviorConfig
from gubernator_tpu.serve.peers import PeerClient


def _req(i: int) -> RateLimitReq:
    return RateLimitReq(
        name="pc", unique_key=f"k{i}", hits=1, limit=10, duration=1000,
        behavior=Behavior.BATCHING,
    )


class FakeStub(WireDoor):
    """Records each GetPeerRateLimits batch; echoes per-request answers."""

    def __init__(self):
        self.batches = []
        self.fail_next = None
        self.short_response = False
        self.release = asyncio.Event()
        self.release.set()

    async def GetPeerRateLimits(self, pb_req, timeout=None):
        # record on ENTRY so tests can wait for "flusher inside the RPC"
        self.batches.append([r.unique_key for r in pb_req.requests])
        await self.release.wait()
        if self.fail_next:
            e, self.fail_next = self.fail_next, None
            raise e
        n = len(pb_req.requests)
        if self.short_response:
            n -= 1
        return peers_pb2.GetPeerRateLimitsResp(
            rate_limits=[
                convert.resp_to_pb(RateLimitResp(limit=10, remaining=7))
                for _ in range(n)
            ]
        )


def _client(stub, **conf_kwargs) -> PeerClient:
    conf = BehaviorConfig(**conf_kwargs)
    c = PeerClient(conf, "127.0.0.1:1")
    c.stub = stub
    c._flusher = asyncio.ensure_future(c._run())
    return c


def test_flush_at_batch_limit_without_waiting():
    async def scenario():
        stub = FakeStub()
        # a long window that must NOT be waited out once limit hits
        c = _client(stub, batch_wait=5.0, batch_limit=3)
        stub.release.clear()  # hold the RPC so the queue accumulates
        futs = [
            asyncio.ensure_future(c.get_peer_rate_limit(_req(i)))
            for i in range(3)
        ]
        await asyncio.sleep(0.05)
        stub.release.set()
        resps = await asyncio.wait_for(asyncio.gather(*futs), timeout=2)
        assert [r.remaining for r in resps] == [7, 7, 7]
        assert stub.batches == [["k0", "k1", "k2"]]  # one coalesced RPC
        await c.close()

    asyncio.run(scenario())


def test_flush_at_window_for_partial_batch():
    async def scenario():
        stub = FakeStub()
        c = _client(stub, batch_wait=0.02, batch_limit=100)
        r = await asyncio.wait_for(
            c.get_peer_rate_limit(_req(0)), timeout=2
        )
        assert r.remaining == 7
        assert stub.batches == [["k0"]]
        await c.close()

    asyncio.run(scenario())


def test_batch_failure_fans_back_to_every_caller():
    async def scenario():
        stub = FakeStub()
        stub.release.clear()
        stub.fail_next = RuntimeError("owner exploded")
        c = _client(stub, batch_wait=0.005, batch_limit=10)
        futs = [
            asyncio.ensure_future(c.get_peer_rate_limit(_req(i)))
            for i in range(4)
        ]
        await asyncio.sleep(0.02)
        stub.release.set()
        for f in futs:
            with pytest.raises(RuntimeError, match="owner exploded"):
                await asyncio.wait_for(f, timeout=2)
        # the flusher survives a failed batch
        r = await asyncio.wait_for(
            c.get_peer_rate_limit(_req(9)), timeout=2
        )
        assert r.remaining == 7
        await c.close()

    asyncio.run(scenario())


def test_response_count_mismatch_rejected():
    async def scenario():
        stub = FakeStub()
        stub.short_response = True
        c = _client(stub, batch_wait=0, batch_limit=10)
        with pytest.raises(RuntimeError, match="mismatched"):
            await asyncio.wait_for(
                c.get_peer_rate_limit(_req(0)), timeout=2
            )
        await c.close()

    asyncio.run(scenario())


def test_enqueue_after_close_fails_fast():
    async def scenario():
        stub = FakeStub()
        c = _client(stub, batch_wait=0, batch_limit=10)
        await c.close()
        # a caller holding this peer object across set_peers must get an
        # immediate error, not enqueue into a queue nothing reads
        with pytest.raises(RuntimeError, match="is closed"):
            await asyncio.wait_for(c.get_peer_rate_limit(_req(0)), 2)

    asyncio.run(scenario())


def test_close_fails_queued_callers_instead_of_stranding():
    async def scenario():
        stub = FakeStub()
        stub.release.clear()  # first RPC parks the flusher mid-send
        c = _client(stub, batch_wait=0, batch_limit=1)
        f1 = asyncio.ensure_future(c.get_peer_rate_limit(_req(0)))
        while not stub.batches:  # flusher is now inside the held RPC
            await asyncio.sleep(0.001)
        f2 = asyncio.ensure_future(c.get_peer_rate_limit(_req(1)))
        await asyncio.sleep(0.01)
        await c.close()
        with pytest.raises(RuntimeError, match="closed mid-batch"):
            await asyncio.wait_for(f1, timeout=2)
        with pytest.raises(RuntimeError, match="closed mid-batch"):
            await asyncio.wait_for(f2, timeout=2)

    asyncio.run(scenario())
