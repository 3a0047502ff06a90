"""Mesh backend through the FULL serving stack: the 8-device CPU mesh
engine behind a real gRPC server (instance + batcher + warmup), not just
the engine-level suite in test_sharded.py.

Covers the production wiring GUBER_BACKEND=mesh uses: warmup compiles
the sub-batch rung ladder through the public decide path, the batcher
launches through the pipelined merged route, GLOBAL owned
keys broadcast-and-install across the mesh shards, and the oracle
semantics hold over the wire.
"""

import time

import pytest

from _util import free_ports
from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    Status,
)
from gubernator_tpu.client import V1Client
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.serve.backends import MeshBackend


@pytest.fixture(scope="module")
def mesh_cluster():
    import jax

    assert len(jax.devices()) == 8, "conftest should provide 8 cpu devices"

    def factory():
        # tiny ladder + store: warmup compiles a handful of CPU programs
        return MeshBackend(
            StoreConfig(rows=16, slots=256), buckets=(64,)
        )

    cluster = LocalCluster(
        [f"127.0.0.1:{p}" for p in free_ports(1)],
        backend_factory=factory,
    )
    cluster.start()
    yield cluster
    cluster.stop()


def test_mesh_backend_served_transitions(mesh_cluster):
    """Token 2->1->0->OVER and a leaky drain, decided by the sharded
    mesh engine behind real gRPC."""
    with V1Client(mesh_cluster.get_peer()) as client:
        seq = []
        for _ in range(4):
            rl = client.get_rate_limits(
                [
                    RateLimitReq(
                        name="mesh-serve", unique_key="tok", hits=1,
                        limit=3, duration=60_000,
                    )
                ],
                timeout=20,
            )[0]
            seq.append((rl.status, rl.remaining))
        assert seq == [
            (Status.UNDER_LIMIT, 2),
            (Status.UNDER_LIMIT, 1),
            (Status.UNDER_LIMIT, 0),
            (Status.OVER_LIMIT, 0),
        ], seq

        leaky = client.get_rate_limits(
            [
                RateLimitReq(
                    name="mesh-serve", unique_key="lk", hits=2, limit=4,
                    duration=2_000, algorithm=Algorithm.LEAKY_BUCKET,
                )
            ],
            timeout=20,
        )[0]
        assert (leaky.status, leaky.remaining) == (Status.UNDER_LIMIT, 2)


def test_mesh_backend_served_global(mesh_cluster):
    """GLOBAL behavior on the mesh backend: the single node owns every
    key, so a GLOBAL decide charges locally and queues a broadcast; the
    replica-install path (update_globals through the batcher into the
    mesh _upsert collective) must keep the key's state consistent over
    repeated reads."""
    with V1Client(mesh_cluster.get_peer()) as client:
        def hit(hits):
            return client.get_rate_limits(
                [
                    RateLimitReq(
                        name="mesh-serve", unique_key="g", hits=hits,
                        limit=5, duration=60_000,
                        behavior=Behavior.GLOBAL,
                    )
                ],
                timeout=20,
            )[0]

        first = hit(1)
        assert (first.status, first.remaining) == (Status.UNDER_LIMIT, 4)
        time.sleep(0.3)  # let the broadcast loop run at least once
        second = hit(1)
        assert (second.status, second.remaining) == (
            Status.UNDER_LIMIT, 3,
        )
        peek = hit(0)
        assert peek.remaining == 3
