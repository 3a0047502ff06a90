"""GlobalManager unit tests with a fake instance — direct coverage of
the gossip loops that e2e tests only exercise as a black box (reference
global.go:29-232 behaviors):

- per-key hit aggregation before a flush (one forwarded request carries
  the summed hits),
- immediate flush at global_batch_limit vs coalescing window below it,
- broadcast dedup (last queued state wins per key), owner-peer skip,
- one failing peer must not block the others or kill the loop.
"""

import asyncio
from dataclasses import dataclass, field

import pytest

from gubernator_tpu.api.types import Behavior, RateLimitReq, RateLimitResp
from gubernator_tpu.serve.config import BehaviorConfig
from gubernator_tpu.serve.global_mgr import GlobalManager


def _req(key: str, hits=1, behavior=Behavior.GLOBAL) -> RateLimitReq:
    return RateLimitReq(
        name="gm", unique_key=key, hits=hits, limit=10, duration=60_000,
        behavior=behavior,
    )


@dataclass
class FakePeer:
    host: str
    is_owner: bool = False
    mesh_local: bool = False
    fail: bool = False
    hit_batches: list = field(default_factory=list)
    update_batches: list = field(default_factory=list)

    async def get_peer_rate_limits(self, reqs):
        if self.fail:
            raise RuntimeError(f"{self.host} unreachable")
        self.hit_batches.append(list(reqs))
        return [RateLimitResp(limit=r.limit) for r in reqs]

    async def update_peer_globals(self, updates):
        if self.fail:
            raise RuntimeError(f"{self.host} unreachable")
        self.update_batches.append(list(updates))


class FakeInstance:
    """Key ownership by prefix: key 'a…' -> peer A, 'b…' -> peer B…"""

    def __init__(self, peers):
        self.peers = peers
        self.decided = []
        self.installed = []  # local replica installs (r21 mesh path)

    async def update_peer_globals(self, updates):
        self.installed.append(list(updates))

    def get_peer(self, hash_key):
        # hash_key is "name_uniquekey"; route on the unique key's first char
        first = hash_key.split("_", 1)[1][0]
        return self.peers[first]

    def peer_list(self):
        return list(self.peers.values())

    async def decide_local(self, reqs, gnp):
        self.decided.append(list(reqs))
        return [
            RateLimitResp(limit=r.limit, remaining=r.limit - 3)
            for r in reqs
        ]


def _conf(**kw):
    base = dict(
        global_sync_wait=0.02, global_batch_limit=1000, global_timeout=2.0
    )
    base.update(kw)
    return BehaviorConfig(**base)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=20))


def test_hits_aggregate_per_key_before_flush():
    peers = {"a": FakePeer("A"), "b": FakePeer("B", is_owner=True)}
    inst = FakeInstance(peers)

    async def main():
        gm = GlobalManager(_conf(), inst)
        gm.start()
        # three hits on one key + one on another, all owned by peer A —
        # queued within one window so they coalesce into ONE flush
        gm.queue_hit(_req("a1", hits=2))
        gm.queue_hit(_req("a1", hits=3))
        gm.queue_hit(_req("a2", hits=1))
        for _ in range(200):
            if peers["a"].hit_batches:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    assert len(peers["a"].hit_batches) == 1
    sent = {r.unique_key: r.hits for r in peers["a"].hit_batches[0]}
    assert sent == {"a1": 5, "a2": 1}  # summed per key (global.go:78-86)
    assert peers["b"].hit_batches == []  # nothing owned by B was queued


def test_batch_limit_flushes_without_window():
    peers = {"a": FakePeer("A"), "b": FakePeer("B", is_owner=True)}
    inst = FakeInstance(peers)

    async def main():
        # window absurdly long: only the batch-limit path can flush
        gm = GlobalManager(
            _conf(global_sync_wait=30.0, global_batch_limit=3), inst
        )
        gm.start()
        for i in range(3):
            gm.queue_hit(_req(f"a{i}"))
        for _ in range(200):
            if peers["a"].hit_batches:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    assert len(peers["a"].hit_batches) == 1
    assert len(peers["a"].hit_batches[0]) == 3


def test_broadcast_dedup_last_wins_and_skips_owner():
    peers = {
        "a": FakePeer("A"),
        "b": FakePeer("B", is_owner=True),
        "c": FakePeer("C"),
    }
    inst = FakeInstance(peers)

    async def main():
        gm = GlobalManager(_conf(), inst)
        gm.start()
        gm.queue_update(_req("a1", hits=1))
        gm.queue_update(_req("a1", hits=9))  # same key dedups
        gm.queue_update(_req("c7", hits=1))
        for _ in range(200):
            if peers["a"].update_batches and peers["c"].update_batches:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    # the owner peer (self) is never broadcast to (global.go:215-229)
    assert peers["b"].update_batches == []
    # both non-owners got ONE batch of the two deduped keys
    for p in ("a", "c"):
        assert len(peers[p].update_batches) == 1
        keys = sorted(k for k, _ in peers[p].update_batches[0])
        assert keys == ["gm_a1", "gm_c7"]
    # the peek decide was a zero-hit non-GLOBAL read (global.go:200-203)
    (peek_batch,) = inst.decided
    assert all(r.hits == 0 for r in peek_batch)
    assert all(r.behavior == Behavior.BATCHING for r in peek_batch)


@pytest.mark.parametrize("entry", ["queue_update", "queue_update_fields"])
def test_update_backlog_bound_drops_and_counts(entry):
    """Both entry points of the owner's broadcast queue hold the
    GUBER_GLOBAL_BACKLOG bound alike: past it a NEW key is dropped and
    counted, a key already queued is refreshed for free (last wins)."""
    import numpy as np

    from gubernator_tpu.serve.metrics import REGISTRY

    def dropped():
        return REGISTRY.get_sample_value(
            "global_backlog_dropped_total", {"queue": "updates"}
        ) or 0.0

    reqs = [_req("a1", hits=1), _req("a2", hits=2), _req("a3", hits=3),
            _req("a4", hits=4), _req("a1", hits=5)]

    async def main():
        gm = GlobalManager(_conf(global_backlog=2), FakeInstance({}))
        if entry == "queue_update":
            for r in reqs:
                gm.queue_update(r)
        else:
            gm.queue_update_fields(
                [r.hash_key() for r in reqs],
                [(i, r.name, r.unique_key) for i, r in enumerate(reqs)],
                dict(
                    hits=np.array([r.hits for r in reqs]),
                    limit=np.array([r.limit for r in reqs]),
                    duration=np.array([r.duration for r in reqs]),
                    algo=np.array([int(r.algorithm) for r in reqs]),
                ),
            )
        return gm

    before = dropped()
    gm = run(main())
    assert dropped() - before == 2  # a3 and a4
    assert gm._dropped["updates"] == 2
    assert gm._updates == {"gm_a1": reqs[4], "gm_a2": reqs[1]}
    assert gm._updates_event.is_set()
    assert gm.backlog_sizes() == {"hits": 0, "updates": 2}


def test_failing_peer_does_not_block_others_or_kill_loops():
    peers = {
        "a": FakePeer("A", fail=True),
        "b": FakePeer("B", is_owner=True),
        "c": FakePeer("C"),
    }
    inst = FakeInstance(peers)

    async def main():
        gm = GlobalManager(_conf(), inst)
        gm.start()
        gm.queue_hit(_req("a1"))  # flush to A raises
        gm.queue_hit(_req("c1"))  # must still reach C
        for _ in range(200):
            if peers["c"].hit_batches:
                break
            await asyncio.sleep(0.01)
        # broadcast path: A fails, C still receives
        gm.queue_update(_req("c2"))
        for _ in range(200):
            if peers["c"].update_batches:
                break
            await asyncio.sleep(0.01)
        # loops survived both errors: another hit still flushes
        gm.queue_hit(_req("c3"))
        for _ in range(200):
            if len(peers["c"].hit_batches) >= 2:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    assert len(peers["c"].hit_batches) >= 2
    assert peers["c"].update_batches, "broadcast blocked by failing peer"


# -- r20 mesh-native flush: per-destination path selection ------------------


def _flush_bytes(path: str) -> float:
    from gubernator_tpu.serve import metrics

    return metrics.GLOBAL_FLUSH_BYTES.labels(path=path)._value.get()


def test_self_owned_hits_short_circuit_local_apply():
    """r20 satellite pin: hits whose ring owner is THIS node must go
    through the local apply path (one in-mesh collective / local
    decide), never a loopback gossip RPC to our own door — and the
    flush trace span must carry the hop-count split proving it."""
    from gubernator_tpu.serve.tracing import Tracer

    peers = {"a": FakePeer("A"), "b": FakePeer("B", is_owner=True)}
    inst = FakeInstance(peers)
    inst.tracer = Tracer(sample=1.0)
    before_mesh = _flush_bytes("mesh")
    before_rpc = _flush_bytes("rpc")

    async def main():
        gm = GlobalManager(_conf(), inst)
        gm.start()
        gm.queue_hit(_req("b1", hits=2))
        gm.queue_hit(_req("b1", hits=3))  # aggregates with the first
        gm.queue_hit(_req("b2", hits=1))
        gm.queue_hit(_req("a1", hits=4))  # off-mesh peer: stays RPC
        for _ in range(200):
            if peers["a"].hit_batches and inst.decided:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    # self-destined keys NEVER loop back through our own gossip door
    assert peers["b"].hit_batches == []
    # they landed on the local apply path (decide_local fallback on the
    # fake), aggregated per key exactly like the RPC chunks
    (local_batch,) = inst.decided
    assert {r.unique_key: r.hits for r in local_batch} == {"b1": 5, "b2": 1}
    # the off-mesh peer still got its gossip send
    assert len(peers["a"].hit_batches) == 1
    assert {r.unique_key for r in peers["a"].hit_batches[0]} == {"a1"}
    # byte split is observable per path
    assert _flush_bytes("mesh") > before_mesh
    assert _flush_bytes("rpc") > before_rpc
    # trace-span evidence: one mesh hop (one collective) regardless of
    # how many self-owned keys flushed, one RPC hop for the one peer
    spans = [
        sp
        for tr in inst.tracer.recorder.snapshot()["traces"]
        if tr["door"] == "global_flush"
        for sp in tr["spans"]
        if sp["name"] == "global_flush_hits"
    ]
    assert spans, "flush produced no global_flush_hits span"
    ann = spans[0]["annotations"]
    assert ann["hops_mesh"] == 1
    assert ann["hops_rpc"] == 1
    assert ann["keys_mesh"] == 2
    assert ann["keys_rpc"] == 1


def test_global_mesh_off_restores_rpc_fanout():
    """GUBER_GLOBAL_MESH=0 escape hatch: self-destined hits go back
    through the gossip door like any other peer (pre-r20 behavior, and
    the perf gate's A side)."""
    peers = {"a": FakePeer("A"), "b": FakePeer("B", is_owner=True)}
    inst = FakeInstance(peers)

    async def main():
        gm = GlobalManager(_conf(global_mesh=False), inst)
        gm.start()
        gm.queue_hit(_req("b1", hits=2))
        for _ in range(200):
            if peers["b"].hit_batches:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    assert len(peers["b"].hit_batches) == 1
    assert inst.decided == []


def test_mesh_local_broadcast_short_circuits_install():
    """r21 satellite pin: the r20 per-destination split applied to the
    BROADCAST loop. Peers whose replicas ride this node's mesh
    (PeerInfo.mesh_local) must be covered by ONE local install of the
    whole batch — never a per-peer UpdatePeerGlobals RPC — while
    off-mesh peers keep the RPC fan-out; the flush trace span carries
    the hop split proving the collapse."""
    from gubernator_tpu.serve.tracing import Tracer

    peers = {
        "a": FakePeer("A"),
        "b": FakePeer("B", is_owner=True),
        "c": FakePeer("C", mesh_local=True),
        "d": FakePeer("D", mesh_local=True),
    }
    inst = FakeInstance(peers)
    inst.tracer = Tracer(sample=1.0)
    before_mesh = _flush_bytes("mesh")
    before_rpc = _flush_bytes("rpc")

    async def main():
        gm = GlobalManager(_conf(), inst)
        gm.start()
        gm.queue_update(_req("a1"))
        gm.queue_update(_req("c7"))
        for _ in range(200):
            if peers["a"].update_batches and inst.installed:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    # mesh-local peers NEVER got an RPC; the owner stays skipped
    assert peers["c"].update_batches == []
    assert peers["d"].update_batches == []
    assert peers["b"].update_batches == []
    # ONE local install of the whole deduped batch covers c AND d
    (installed,) = inst.installed
    assert sorted(k for k, _ in installed) == ["gm_a1", "gm_c7"]
    # the off-mesh peer still got its full broadcast over RPC
    assert len(peers["a"].update_batches) == 1
    assert sorted(k for k, _ in peers["a"].update_batches[0]) == [
        "gm_a1", "gm_c7",
    ]
    # byte split is observable per path
    assert _flush_bytes("mesh") > before_mesh
    assert _flush_bytes("rpc") > before_rpc
    # trace-span evidence: one mesh hop covers BOTH mesh-local peers;
    # the RPC path pays one hop for the one off-mesh peer
    spans = [
        sp
        for tr in inst.tracer.recorder.snapshot()["traces"]
        if tr["door"] == "global_broadcast"
        for sp in tr["spans"]
        if sp["name"] == "global_flush_updates"
    ]
    assert spans, "broadcast produced no global_flush_updates span"
    ann = spans[0]["annotations"]
    assert ann["hops_mesh"] == 1
    assert ann["hops_rpc"] == 1
    assert ann["keys_mesh"] == 2
    assert ann["keys_rpc"] == 2
    assert ann["peers_mesh"] == 2
    assert ann["peers_rpc"] == 1


def test_mesh_local_broadcast_off_restores_rpc_fanout():
    """GUBER_GLOBAL_MESH=0 escape hatch on the broadcast loop: a
    mesh_local peer is fanned out to over RPC like any other peer
    (pre-r21 behavior)."""
    peers = {
        "b": FakePeer("B", is_owner=True),
        "c": FakePeer("C", mesh_local=True),
    }
    inst = FakeInstance(peers)

    async def main():
        gm = GlobalManager(_conf(global_mesh=False), inst)
        gm.start()
        gm.queue_update(_req("c1"))
        for _ in range(200):
            if peers["c"].update_batches:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    assert len(peers["c"].update_batches) == 1
    assert inst.installed == []


def test_mesh_local_install_prefers_instance_hook():
    """When the instance exposes update_peer_globals_local, the
    mesh-local broadcast chunk must use it over update_peer_globals —
    that is where an embedder hangs a one-collective install."""
    peers = {
        "b": FakePeer("B", is_owner=True),
        "c": FakePeer("C", mesh_local=True),
    }
    inst = FakeInstance(peers)
    hooked = []

    async def hook(updates):
        hooked.append(list(updates))

    inst.update_peer_globals_local = hook

    async def main():
        gm = GlobalManager(_conf(), inst)
        gm.start()
        gm.queue_update(_req("c1"))
        for _ in range(200):
            if hooked:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    assert inst.installed == []
    (batch,) = hooked
    assert [k for k, _ in batch] == ["gm_c1"]


def test_local_apply_prefers_instance_hook():
    """When the instance exposes apply_global_hits_local (the real
    server does), the flush must call it instead of decide_local — that
    hook is where the one-collective apply lives."""
    peers = {"b": FakePeer("B", is_owner=True)}
    inst = FakeInstance(peers)
    applied = []

    async def hook(reqs):
        applied.append(list(reqs))

    inst.apply_global_hits_local = hook

    async def main():
        gm = GlobalManager(_conf(), inst)
        gm.start()
        gm.queue_hit(_req("b1", hits=7))
        for _ in range(200):
            if applied:
                break
            await asyncio.sleep(0.01)
        await gm.stop()

    run(main())
    assert inst.decided == []
    (batch,) = applied
    assert [(r.unique_key, r.hits) for r in batch] == [("b1", 7)]
