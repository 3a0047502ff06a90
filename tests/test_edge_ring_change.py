"""Membership change under a LIVE edge: GEBR refusal -> ring refresh
-> re-route (the r5 over-admission guard, end to end).

The bridge side is unit-tested in test_edge_bridge.py; this drives the
real C++ edge binary against in-process bridges whose membership is
swapped mid-run:

1. edge boots with a 1-node ring and fast-paths everything locally;
2. the picker is swapped to a 2-node ring (as etcd/k8s discovery does
   via set_peers) whose second node is ANOTHER in-process bridge on
   TCP;
3. the edge's next fast frame is refused (GEBR) — those items come
   back as per-item "membership changed; retry" errors, never decided
   under the stale view;
4. within the refresh period the edge re-reads the ring and
   subsequent requests reach BOTH bridges, split by the new ring.
"""

import asyncio
import json
import struct
import subprocess
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.serve.edge_bridge import EdgeBridge

from tests._util import edge_binary

EDGE_BIN = edge_binary()

pytestmark = pytest.mark.skipif(
    not EDGE_BIN.exists(),
    reason="edge binary not built (make -C gubernator_tpu/native/edge)",
)

NODE_A = "10.99.0.1:81"  # the edge's primary (unix socket)
NODE_B = "10.99.0.2:81"  # joins later, bridge on 127.0.0.1 TCP


class FakeBackend:
    decide_submit_merged = object()  # a device backend


class FakePicker:
    def __init__(self, hosts_self):
        self._peers = [
            type("P", (), {"host": h, "is_owner": mine})()
            for h, mine in hosts_self
        ]

    def peers(self):
        return self._peers


class CountingInstance:
    """Array fast path that counts items and echoes limit-hits as
    remaining (so decisions are checkable), plus a string path."""

    def __init__(self, self_host, hosts):
        self.backend = FakeBackend()
        self.picker = FakePicker(
            [(h, h == self_host) for h in hosts]
        )
        self.fast_items = 0
        inst = self

        class B:
            async def decide_arrays(self, fields, frame=True):
                n = fields["key_hash"].shape[0]
                inst.fast_items += n
                return (
                    np.zeros(n, np.int64),
                    fields["limit"],
                    fields["limit"] - fields["hits"],
                    np.zeros(n, np.int64),
                )

        class T:
            def observe_hashes(self, h):
                pass

        self.batcher = B()
        self.traffic = T()

    async def get_rate_limits(self, reqs, stage_frame=False):
        from gubernator_tpu.api.types import RateLimitResp, Status

        return [
            RateLimitResp(
                status=Status.UNDER_LIMIT, limit=r.limit,
                remaining=r.limit - r.hits, reset_time=1,
            )
            for r in reqs
        ]


def _post(port, n_keys, tag):
    # bounded 503 retry (r15 deflake; see tests/_util.post_json): a
    # just-(re)spawned edge can refuse the first frame un-served
    # under full-suite load
    from tests._util import post_json

    return post_json(
        f"http://127.0.0.1:{port}/v1/GetRateLimits",
        {
            "requests": [
                {"name": "rc", "uniqueKey": f"{tag}-{i}", "hits": 1,
                 "limit": 7, "duration": 60000}
                for i in range(n_keys)
            ]
        },
        timeout=15,
    )


def test_membership_change_refuses_then_reroutes():
    from tests._util import free_ports

    edge_http, bridge_b_tcp = free_ports(2)
    sock_a = "/tmp/guber-ring-change-a.sock"

    async def main():
        inst_a = CountingInstance(NODE_A, [NODE_A])
        inst_b = CountingInstance(NODE_B, [NODE_A, NODE_B])
        bridge_a = EdgeBridge(
            inst_a, sock_a,
            peer_bridges={NODE_B: f"127.0.0.1:{bridge_b_tcp}"},
        )
        bridge_b = EdgeBridge(
            inst_b, "", tcp_address=f"127.0.0.1:{bridge_b_tcp}"
        )
        import os

        try:
            os.unlink(sock_a)
        except FileNotFoundError:
            pass
        await bridge_a.start()
        await bridge_b.start()
        edge = subprocess.Popen(
            [str(EDGE_BIN), "--listen", str(edge_http),
             "--backend", sock_a, "--ring-refresh-ms", "100",
             "--batch-wait-us", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        try:
            deadline = time.monotonic() + 10
            import socket as sl

            while True:
                if edge.poll() is not None:
                    pytest.fail(f"edge died:\n{edge.stdout.read()}")
                try:
                    sl.create_connection(
                        ("127.0.0.1", edge_http), timeout=1
                    ).close()
                    break
                except OSError:
                    assert time.monotonic() < deadline
                    await asyncio.sleep(0.05)

            # phase 1: 1-node ring, everything fast + local
            out = await asyncio.to_thread(_post, edge_http, 20, "p1")
            assert all(
                r["remaining"] == "6" and not r["error"]
                for r in out["responses"]
            )
            assert inst_a.fast_items == 20 and inst_b.fast_items == 0

            # phase 2: membership grows (the discovery callback shape:
            # a NEW picker object swapped in, as set_peers does)
            inst_a.picker = FakePicker(
                [(NODE_A, True), (NODE_B, False)]
            )

            # the edge still has the old ring for up to refresh-ms; its
            # next fast frames are REFUSED, never decided locally under
            # the stale view. Items answer with retry errors until the
            # re-read lands; then both bridges serve their shares.
            saw_retry = False
            deadline = time.monotonic() + 8
            while time.monotonic() < deadline:
                out = await asyncio.to_thread(_post, edge_http, 30, "p2")
                errs = [r["error"] for r in out["responses"] if r["error"]]
                if errs:
                    assert all(
                        "membership changed" in e for e in errs
                    ), errs
                    saw_retry = True
                if inst_b.fast_items > 0 and not errs:
                    break
                await asyncio.sleep(0.1)
            assert inst_b.fast_items > 0, (
                "edge never re-routed to the new node "
                f"(a={inst_a.fast_items}, b={inst_b.fast_items}, "
                f"saw_retry={saw_retry})"
            )
            # under the stale view nothing may have been decided by A
            # for keys B owns: A's count can only have grown through
            # frames accepted AFTER its ring matched (post-change
            # acceptance implies the edge's fingerprint matched the
            # 2-node membership)
        finally:
            edge.kill()
            await bridge_a.stop()
            await bridge_b.stop()

    asyncio.run(main())


def test_degraded_cluster_folds_self_fast_into_slow_frame():
    """Cluster whose peers have NO reachable bridges (e.g. GUBER_EDGE_TCP
    unset fleet-wide): most items fold to the string path anyway, and
    splitting off a minority self-fast frame would cost a second backend
    round-trip per request for nothing (measured ~15% door throughput on
    the 6-node exact bench). The router must send ONE string frame:
    the bridge's fast counter stays 0 while decisions stay correct.
    Converse guard: the single-NODE ring (self-fast majority, no slow)
    must still use the fast path."""
    from tests._util import free_ports

    edge_http, = free_ports(1)
    sock_a = "/tmp/guber-fold-a.sock"
    # 3-node ring, peers WITHOUT bridge endpoints: self owns ~1/3
    nodes = [NODE_A, "10.99.0.3:81", "10.99.0.4:81"]

    async def main():
        import os

        inst = CountingInstance(NODE_A, nodes)  # no peer_bridges map
        bridge = EdgeBridge(inst, sock_a)
        try:
            os.unlink(sock_a)
        except FileNotFoundError:
            pass
        await bridge.start()
        edge = subprocess.Popen(
            [str(EDGE_BIN), "--listen", str(edge_http),
             "--backend", sock_a, "--ring-refresh-ms", "100",
             "--batch-wait-us", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        try:
            deadline = time.monotonic() + 10
            import socket as sl

            while True:
                if edge.poll() is not None:
                    pytest.fail(f"edge died:\n{edge.stdout.read()}")
                try:
                    sl.create_connection(
                        ("127.0.0.1", edge_http), timeout=1
                    ).close()
                    break
                except OSError:
                    assert time.monotonic() < deadline
                    await asyncio.sleep(0.05)

            out = await asyncio.to_thread(_post, edge_http, 60, "fold")
            assert all(
                r["remaining"] == "6" and not r["error"]
                for r in out["responses"]
            ), out["responses"][:3]
            # ONE string frame served everything: the pre-hashed path
            # was never used even for self-owned items
            assert inst.fast_items == 0, inst.fast_items

            # converse: shrink to a 1-node ring -> fast path again
            inst.picker = FakePicker([(NODE_A, True)])
            deadline = time.monotonic() + 8
            while time.monotonic() < deadline and inst.fast_items == 0:
                out = await asyncio.to_thread(
                    _post, edge_http, 10, f"f1-{time.monotonic_ns()}"
                )
                await asyncio.sleep(0.1)
            assert inst.fast_items > 0, "fast path never re-engaged"
        finally:
            edge.kill()
            await bridge.stop()

    asyncio.run(main())
