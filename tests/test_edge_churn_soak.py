"""Membership-churn soak for the cluster edge's riskiest machinery.

The r5 lane/ring code has three moving parts that only interleave
under churn: the refresher republishing rings, publish_ring evicting
lanes whose endpoint left (shutdown -> queued-shard failure -> detached
workers freeing the Lane), and in-flight execute() calls racing both.
This soak flaps the membership every ~60 ms for several seconds while
4 client threads hammer the edge, then asserts:

- the edge NEVER crashes or wedges (every request gets an HTTP
  response within timeout for the whole soak);
- every item answer is either a real decision or one of the two
  legitimate transient errors (stale-ring retry / bridge unreachable)
  — never garbage, never a protocol desync;
- after the flapping stops, the edge converges: requests succeed with
  no errors and BOTH bridges serve fast traffic again.
"""

import asyncio
import json
import pathlib
import subprocess
import threading
import time
import urllib.request

import numpy as np
import pytest

from gubernator_tpu.serve.edge_bridge import EdgeBridge
from tests._util import edge_binary, free_ports

ROOT = pathlib.Path(__file__).resolve().parent.parent
EDGE_BIN = edge_binary()

pytestmark = pytest.mark.skipif(
    not EDGE_BIN.exists(),
    reason="edge binary not built (make -C gubernator_tpu/native/edge)",
)

NODE_A = "10.98.0.1:81"
NODE_B = "10.98.0.2:81"


class FakePicker:
    def __init__(self, hosts_self):
        self._peers = [
            type("P", (), {"host": h, "is_owner": mine})()
            for h, mine in hosts_self
        ]

    def peers(self):
        return self._peers


class Inst:
    def __init__(self, self_host, hosts):
        class FakeBackend:
            decide_submit_merged = object()  # a device backend

        self.backend = FakeBackend()
        self.picker = FakePicker([(h, h == self_host) for h in hosts])
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
        self.fast_items = 0

    async def get_rate_limits(self, reqs, stage_frame=False):
        from gubernator_tpu.api.types import RateLimitResp, Status

        return [
            RateLimitResp(
                status=Status.UNDER_LIMIT, limit=r.limit,
                remaining=r.limit - r.hits, reset_time=1,
            )
            for r in reqs
        ]


OK_ERRORS = ("membership changed", "unreachable", "edge backend")


def _post(port, tag, n=8, timeout=30):
    body = json.dumps(
        {
            "requests": [
                {"name": "cs", "uniqueKey": f"{tag}-{i}", "hits": 1,
                 "limit": 7, "duration": 60000}
                for i in range(n)
            ]
        }
    ).encode()
    resp = urllib.request.urlopen(
        urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/GetRateLimits", data=body,
            headers={"Content-Type": "application/json"},
        ),
        timeout=timeout,
    )
    return json.loads(resp.read())


def test_membership_flapping_soak():
    edge_http, bridge_b_tcp = free_ports(2)
    sock_a = "/tmp/guber-churn-a.sock"

    async def main():
        import os

        inst_a = Inst(NODE_A, [NODE_A])
        inst_b = Inst(NODE_B, [NODE_A, NODE_B])
        bridge_a = EdgeBridge(
            inst_a, sock_a,
            peer_bridges={NODE_B: f"127.0.0.1:{bridge_b_tcp}"},
        )
        bridge_b = EdgeBridge(
            inst_b, "", tcp_address=f"127.0.0.1:{bridge_b_tcp}"
        )
        try:
            os.unlink(sock_a)
        except FileNotFoundError:
            pass
        await bridge_a.start()
        await bridge_b.start()
        edge = subprocess.Popen(
            [str(EDGE_BIN), "--listen", str(edge_http),
             "--backend", sock_a, "--ring-refresh-ms", "60",
             "--batch-wait-us", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        stats = {
            "calls": 0, "errors": 0, "unavail": 0, "bad": [], "fails": []
        }
        stop = threading.Event()

        def client(w):
            import urllib.error

            i = 0
            while not stop.is_set():
                i += 1
                try:
                    out = _post(edge_http, f"w{w}-{i}")
                except urllib.error.HTTPError as e:
                    # 503 mid-flap is a legitimate transient (a lane
                    # reconnect window on a churning ring); anything
                    # else is not
                    stats["calls"] += 1
                    if e.code == 503:
                        stats["unavail"] += 1
                    else:
                        stats["bad"].append(f"HTTP {e.code}")
                    continue
                except Exception as e:  # timeout/conn error = wedge
                    stats["fails"].append(repr(e))
                    return
                stats["calls"] += 1
                for r in out["responses"]:
                    if r["error"]:
                        stats["errors"] += 1
                        if not any(s in r["error"] for s in OK_ERRORS):
                            stats["bad"].append(r["error"])
                    elif r["remaining"] != "6":
                        stats["bad"].append(f"remaining={r['remaining']}")

        try:
            import socket as sl

            deadline = time.monotonic() + 10
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

            # 4 clients, not 16: the fake bridges share this box's ONE
            # core with the clients' GIL, and an over-dense soak mostly
            # measures starvation of the fake asyncio loop (5s lane
            # connect/hello timeouts pile into client-visible stalls)
            threads = [
                threading.Thread(target=client, args=(w,))
                for w in range(4)
            ]
            for t in threads:
                t.start()

            # flap the membership for ~4s: 1-node <-> 2-node ring
            one = FakePicker([(NODE_A, True)])
            two = FakePicker([(NODE_A, True), (NODE_B, False)])
            end = time.monotonic() + 4.0
            flip = False
            while time.monotonic() < end:
                inst_a.picker = two if flip else one
                flip = not flip
                await asyncio.sleep(0.06)
            inst_a.picker = two  # settle on the 2-node ring
            await asyncio.sleep(1.5)
            stop.set()
            # join OFF the loop thread: the fake bridges live on THIS
            # event loop, and a blocking join here deadlocks the
            # clients' final in-flight requests against their own
            # teardown (they stall until the edge's peer timeout
            # rescues them — the first version of this test diagnosed
            # exactly that as a spurious edge wedge)
            await asyncio.to_thread(
                lambda: [t.join(timeout=30) for t in threads]
            )
            assert not any(t.is_alive() for t in threads), "client wedged"
            assert edge.poll() is None, f"edge died:\n{edge.stdout.read()}"
            assert stats["fails"] == [], stats["fails"][:3]
            assert stats["bad"] == [], stats["bad"][:5]
            assert stats["calls"] > 100, stats

            # convergence: clean request, both bridges fast again (the
            # settled ring must also stop producing 503s)
            b_before = inst_b.fast_items
            deadline = time.monotonic() + 8
            clean = False
            import urllib.error

            while time.monotonic() < deadline:
                try:
                    out = await asyncio.to_thread(
                        _post, edge_http, f"conv-{time.monotonic_ns()}",
                        30,
                    )
                except urllib.error.HTTPError:
                    await asyncio.sleep(0.1)
                    continue
                if all(not r["error"] for r in out["responses"]):
                    if inst_b.fast_items > b_before:
                        clean = True
                        break
                await asyncio.sleep(0.1)
            assert clean, (
                f"no clean fast convergence (b fast {inst_b.fast_items})"
            )
        finally:
            stop.set()
            edge.kill()
            await bridge_a.stop()
            await bridge_b.stop()

    asyncio.run(main())
