"""Hand-written gRPC service glue.

grpc_tools (the protoc gRPC python plugin) is not available in this image,
so the servicer registration and client stubs for the two services
(V1, PeersV1 — reference proto/gubernator.proto:27-45, proto/peers.proto:28-34)
are written out by hand against the generated message classes. Works with
both sync and asyncio grpc channels/servers.

One method is registered pass-through: PeersV1/GetPeerRateLimits hands
its servicer the request's serialised bytes and takes bytes or a message
back (add_peers_servicer), so the owner side of the ring can serve a
forwarded batch as arrays without a protobuf message per item; and the
client stub offers the same method a second time as bytes in, bytes out
(PeersV1Stub.GetPeerRateLimitsWire), for a forwarder that serialises a
batch from columns (serve/peers.py PeerClient.forward_columns). Every
other method, and every other client stub, goes through the generated
classes.
"""

from __future__ import annotations

import grpc

from gubernator_tpu.api.proto.gen import gubernator_pb2, peers_pb2

V1_SERVICE = "pb.gubernator.V1"
PEERS_SERVICE = "pb.gubernator.PeersV1"


def add_v1_servicer(server: grpc.Server, servicer) -> None:
    """servicer must expose GetRateLimits(req, ctx) and HealthCheck(req, ctx)
    (sync or async depending on the server flavor)."""
    handlers = {
        "GetRateLimits": grpc.unary_unary_rpc_method_handler(
            servicer.GetRateLimits,
            request_deserializer=gubernator_pb2.GetRateLimitsReq.FromString,
            response_serializer=gubernator_pb2.GetRateLimitsResp.SerializeToString,
        ),
        "HealthCheck": grpc.unary_unary_rpc_method_handler(
            servicer.HealthCheck,
            request_deserializer=gubernator_pb2.HealthCheckReq.FromString,
            response_serializer=gubernator_pb2.HealthCheckResp.SerializeToString,
        ),
    }
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(V1_SERVICE, handlers),)
    )


def _bytes_or_message(reply) -> bytes:
    return reply if isinstance(reply, bytes) else reply.SerializeToString()


def add_peers_servicer(server: grpc.Server, servicer) -> None:
    """servicer must expose GetPeerRateLimits(wire, ctx),
    UpdatePeerGlobals(req, ctx) and ReplicateBuckets(req, ctx).

    GetPeerRateLimits is registered pass-through: the handler is given
    the request's serialised bytes and returns bytes or a
    GetPeerRateLimitsResp, so a servicer that can serve a forwarded
    batch as arrays (serve/server.py: the wire fold) never has the
    runtime build a message per item; one that cannot parses the bytes
    itself (GetPeerRateLimitsReq.FromString)."""
    handlers = {
        "GetPeerRateLimits": grpc.unary_unary_rpc_method_handler(
            servicer.GetPeerRateLimits,
            request_deserializer=None,
            response_serializer=_bytes_or_message,
        ),
        "UpdatePeerGlobals": grpc.unary_unary_rpc_method_handler(
            servicer.UpdatePeerGlobals,
            request_deserializer=peers_pb2.UpdatePeerGlobalsReq.FromString,
            response_serializer=peers_pb2.UpdatePeerGlobalsResp.SerializeToString,
        ),
        "ReplicateBuckets": grpc.unary_unary_rpc_method_handler(
            servicer.ReplicateBuckets,
            request_deserializer=peers_pb2.ReplicateBucketsReq.FromString,
            response_serializer=peers_pb2.ReplicateBucketsResp.SerializeToString,
        ),
    }
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(PEERS_SERVICE, handlers),)
    )


class V1Stub:
    """Client stub for the public service."""

    def __init__(self, channel):
        self.GetRateLimits = channel.unary_unary(
            f"/{V1_SERVICE}/GetRateLimits",
            request_serializer=gubernator_pb2.GetRateLimitsReq.SerializeToString,
            response_deserializer=gubernator_pb2.GetRateLimitsResp.FromString,
        )
        self.HealthCheck = channel.unary_unary(
            f"/{V1_SERVICE}/HealthCheck",
            request_serializer=gubernator_pb2.HealthCheckReq.SerializeToString,
            response_deserializer=gubernator_pb2.HealthCheckResp.FromString,
        )


class PeersV1Stub:
    """Client stub for the peer-to-peer service."""

    def __init__(self, channel):
        self.GetPeerRateLimits = channel.unary_unary(
            f"/{PEERS_SERVICE}/GetPeerRateLimits",
            request_serializer=peers_pb2.GetPeerRateLimitsReq.SerializeToString,
            response_deserializer=peers_pb2.GetPeerRateLimitsResp.FromString,
        )
        # the same method pass-through: a serialised
        # GetPeerRateLimitsReq in, the reply's bytes out
        self.GetPeerRateLimitsWire = channel.unary_unary(
            f"/{PEERS_SERVICE}/GetPeerRateLimits",
            request_serializer=None,
            response_deserializer=None,
        )
        self.UpdatePeerGlobals = channel.unary_unary(
            f"/{PEERS_SERVICE}/UpdatePeerGlobals",
            request_serializer=peers_pb2.UpdatePeerGlobalsReq.SerializeToString,
            response_deserializer=peers_pb2.UpdatePeerGlobalsResp.FromString,
        )
        self.ReplicateBuckets = channel.unary_unary(
            f"/{PEERS_SERVICE}/ReplicateBuckets",
            request_serializer=peers_pb2.ReplicateBucketsReq.SerializeToString,
            response_deserializer=peers_pb2.ReplicateBucketsResp.FromString,
        )
