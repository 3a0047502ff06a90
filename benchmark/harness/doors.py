"""The daemons' doors as the checks use them (not the timed path, which
belongs to the generators): one blocking call -> [(status, limit,
remaining)], and bulk calls over GEB for the preload. Every call names
its node, 0 unless said: one key's answers are the same whichever node
of a ring is asked (reference_ring.py), so the checks go through node
0's doors and reach the others through its forwards.

Doors: "grpc" (V1/GetRateLimits), "geb" (a GEB frame), "peer"
(PeersV1/GetPeerRateLimits: the node answers as the keys' owner).
"""

from __future__ import annotations

CALL_TIMEOUT = 120.0


def _norm(resps, reqs):
    out = []
    for r, q in zip(resps, reqs):
        if r.error:
            raise RuntimeError(f"'{q.unique_key}' answered error {r.error!r}")
        out.append((int(r.status), int(r.limit), int(r.remaining)))
    if len(out) != len(reqs):
        raise RuntimeError(f"{len(resps)} answers for {len(reqs)} items")
    return out


class _Node:
    def __init__(self, daemon):
        from gubernator_tpu.api.grpc_glue import PeersV1Stub
        from gubernator_tpu.client import V1Client
        from gubernator_tpu.client_geb import GebClient

        self.v1 = V1Client(daemon.grpc)
        self.peers = PeersV1Stub(self.v1.channel)  # the same port, another service
        self.geb = GebClient(daemon.geb, timeout=CALL_TIMEOUT)
        self.geb.connect()

    def peer(self, reqs):
        from gubernator_tpu.api import convert
        from gubernator_tpu.api.proto.gen import peers_pb2

        resp = self.peers.GetPeerRateLimits(
            peers_pb2.GetPeerRateLimitsReq(
                requests=[convert.req_to_pb(r) for r in reqs]),
            timeout=CALL_TIMEOUT)
        return [convert.resp_from_pb(r) for r in resp.rate_limits]

    def close(self) -> None:
        self.geb.close()
        self.v1.close()


class Doors:
    """`ring`: harness.daemon.Ring. Node 0 is connected at once, another
    node when it is first called."""

    def __init__(self, ring):
        self._ring = ring
        self._nodes = {0: _Node(ring.nodes[0])}

    def _node(self, i: int) -> _Node:
        if i not in self._nodes:
            self._nodes[i] = _Node(self._ring.nodes[i])
        return self._nodes[i]

    def close(self) -> None:
        for n in self._nodes.values():
            n.close()

    def call(self, door: str, reqs, node: int = 0):
        n = self._node(node)
        if door == "grpc":
            return _norm(n.v1.get_rate_limits(reqs, timeout=CALL_TIMEOUT), reqs)
        if door == "geb":
            return _norm(n.geb.get_rate_limits(reqs), reqs)
        if door == "peer":
            return _norm(n.peer(reqs), reqs)
        raise ValueError(f"unknown door '{door}'")

    def bulk(self, batches, node: int = 0):
        """Many GEB frames, a credit window of them in flight."""
        return [
            _norm(resps, reqs)
            for reqs, resps in zip(
                batches, self._node(node).geb.get_rate_limits_pipelined(batches)
            )
        ]
