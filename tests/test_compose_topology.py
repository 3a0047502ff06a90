"""deploy/docker-compose.yaml's topology, executed natively.

The compose file (r5) demos the reference's deployment shape: an etcd
service plus two nodes that discover each other through it (reference
docker-compose.yaml) via the vendored client. This image has no
docker, so the compose file itself can't boot here — instead this test
runs the SAME wiring with real processes: two daemons configured
exactly like the compose services (GUBER_ETCD_ENDPOINTS, no
GUBER_PEERS) against a protocol-real etcd (tests/_fake_etcd.py, real
gRPC + the vendored field-number-exact protos), and proves

- both nodes register and see each other (peerCount == 2 on both);
- the ring actually works: a request sent to the NON-owner node comes
  back with metadata.owner naming the other node (forwarded over
  gRPC), i.e. discovery produced a functioning cluster, not just a
  list.

When docker IS available, `docker compose up` in deploy/ runs the same
thing against real etcd; tests/test_etcd_vendored.py additionally runs
the client cycle against a live etcd when GUBER_TEST_ETCD is set.

Isolation (r8 deflake): ports are allocated per-run (the r5-r7 version
pinned 2971x/2972x, which collided with leftovers/TIME_WAIT under
full-suite runs), and each daemon's output goes to its own temp FILE —
the old stdout=PIPE was never read until failure, so a chatty daemon
could fill the 64 KiB pipe buffer, block on a log write, and miss the
discovery deadline only when the rest of the suite made it slow.
"""

import json
import os
import pathlib
import subprocess
import sys
import time
import urllib.request

import pytest

from _util import free_ports

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _daemon(grpc_port, http_port, etcd_port, log_dir, i):
    env = dict(
        os.environ,
        PYTHONPATH=str(ROOT),
        GUBER_BACKEND="exact",
        JAX_PLATFORMS="cpu",
        GUBER_GRPC_ADDRESS=f"127.0.0.1:{grpc_port}",
        GUBER_HTTP_ADDRESS=f"127.0.0.1:{http_port}",
        GUBER_ADVERTISE_ADDRESS=f"127.0.0.1:{grpc_port}",
        GUBER_ETCD_ENDPOINTS=f"127.0.0.1:{etcd_port}",
    )
    env.pop("GUBER_PEERS", None)
    out = open(os.path.join(log_dir, f"daemon{i}.log"), "w+")
    proc = subprocess.Popen(
        [sys.executable, "-m", "gubernator_tpu.cli.daemon"],
        stdout=out,
        stderr=subprocess.STDOUT,
        text=True,
        cwd=ROOT,
        env=env,
    )
    proc._log = out  # noqa: SLF001 - test-local teardown handle
    return proc


def _read_log(proc) -> str:
    proc._log.flush()
    proc._log.seek(0)
    return proc._log.read()


def _get(url):
    return json.loads(urllib.request.urlopen(url, timeout=5).read())


def _post(port, body):
    return json.loads(
        urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/GetRateLimits",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            ),
            timeout=10,
        ).read()
    )


def test_compose_topology_discovers_and_forwards(tmp_path):
    from tests._fake_etcd import FakeEtcd

    grpc_ports = free_ports(2)
    http_ports = free_ports(2)
    log_dir = str(tmp_path)  # pytest-managed: cleaned up, kept on failure
    etcd = FakeEtcd().start()
    daemons = [
        _daemon(grpc_ports[i], http_ports[i], etcd.port, log_dir, i)
        for i in range(2)
    ]
    try:
        # both nodes must discover each other through etcd
        deadline = time.monotonic() + 60
        counts = {}
        while time.monotonic() < deadline:
            for i in range(2):
                if daemons[i].poll() is not None:
                    pytest.fail(
                        f"daemon {i} died:\n{_read_log(daemons[i])}"
                    )
                try:
                    counts[i] = _get(
                        f"http://127.0.0.1:{http_ports[i]}/v1/HealthCheck"
                    )["peerCount"]
                except OSError:
                    counts[i] = 0
            if counts.get(0) == 2 and counts.get(1) == 2:
                break
            time.sleep(0.3)
        assert counts == {0: 2, 1: 2}, counts

        # the discovered ring must FUNCTION: find a key owned by node 1
        # (response through node 0 carries metadata.owner), then verify
        # coherence by reading it back through the owner
        # (one ring point a peer, as upstream: with random ports node
        # 1's arc can be a fraction of a percent of the ring, and 64
        # tries once found no key in 0.37% of it)
        owner_key = None
        for i in range(4096):
            out = _post(
                http_ports[0],
                {"requests": [{"name": "ct", "uniqueKey": f"k{i}",
                               "hits": 1, "limit": 9,
                               "duration": 60000}]},
            )
            resp = out["responses"][0]
            assert resp["error"] == "", resp
            owner = f"127.0.0.1:{grpc_ports[1]}"
            if resp["metadata"].get("owner") == owner:
                owner_key = f"k{i}"
                break
        assert owner_key is not None, "no key owned by node 1 in 4096 tries"
        out = _post(
            http_ports[1],
            {"requests": [{"name": "ct", "uniqueKey": owner_key,
                           "hits": 0, "limit": 9, "duration": 60000}]},
        )
        # node 1 owns it: local decide, consumed hit visible
        resp = out["responses"][0]
        assert resp["remaining"] == "8" and "owner" not in resp["metadata"]
    finally:
        for d in daemons:
            d.terminate()
        for d in daemons:
            d.wait(timeout=10)
            d._log.close()
        etcd.stop()
