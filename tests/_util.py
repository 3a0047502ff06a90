"""Shared test helpers."""

import itertools
import json
import os
import pathlib
import random
import socket
import time
import urllib.error
import urllib.request


def post_json(url, body, timeout=30.0, retries=8, backoff=0.25):
    """POST a JSON body and decode the JSON response, with BOUNDED
    retry on transient 503s (r15 deflake of the r14 note: the
    edge-cluster suites could 503-flake under full-suite load on one
    core while passing in isolation).

    Retrying a 503 is safe by protocol contract: the edge/daemon doors
    answer 503 only for frames REFUSED un-served (lane down, shard
    connect failure, conn cap — the HTTP face of the GEBR refusal,
    whose client contract is explicitly retry-safe), so no hit can be
    double-charged. Connection-refused/reset during setup is equally
    un-served and retried. TIMEOUTS ARE NOT RETRIED — an expired
    in-flight request's delivery is unknown and a retry could double
    charge; a wedged fixture should fail loudly, not double-count.
    """
    data = json.dumps(body).encode()
    last = None
    for attempt in range(retries + 1):
        try:
            return json.loads(
                urllib.request.urlopen(
                    urllib.request.Request(
                        url,
                        data=data,
                        headers={"Content-Type": "application/json"},
                    ),
                    timeout=timeout,
                ).read()
            )
        except urllib.error.HTTPError as e:
            if e.code != 503:
                raise
            last = e
        except urllib.error.URLError as e:
            if not isinstance(
                e.reason, (ConnectionRefusedError, ConnectionResetError)
            ):
                raise
            last = e
        except (ConnectionRefusedError, ConnectionResetError) as e:
            last = e
        time.sleep(backoff * (attempt + 1))
    raise last


def edge_binary() -> "pathlib.Path":
    """Path to the guber-edge binary the edge suites drive. Overridable
    via GUBER_EDGE_BIN so the same suites can run against the
    ASan/UBSan build (tests/test_edge_asan.py)."""
    override = os.environ.get("GUBER_EDGE_BIN")
    if override:
        return pathlib.Path(override)
    root = pathlib.Path(__file__).resolve().parent.parent
    return root / "gubernator_tpu" / "native" / "edge" / "guber-edge"


_PORT_LANES = 16
_port_lane = None  # this process's ports, in the order it hands them out


def free_ports(n):
    """n distinct localhost ports that nobody holds and the kernel
    hands to nobody: drawn below the range it serves bind(0) and
    connect() from, each proved free by binding it
    (benchmark/harness/daemon.py free_sockets does the same). A daemon
    binds its doors seconds after its ports were chosen; a port drawn
    with bind(0) and let go was meanwhile drawn by a neighbour's
    bind(0) under the driver's six workers (test_ring_route.py x 6 and
    test_shm_lane.py x 1 failed so in one run, all passing alone). The
    16,384 ports below the range are cut into lanes, one an xdist
    worker (by process id outside xdist), and a process walks its lane
    in a shuffled order without handing a port out twice before the
    lane is spent — so two tests, or two workers, cannot hold the same
    port between the draw and the bind either."""
    global _port_lane
    if _port_lane is None:
        try:
            with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
                low = int(f.read().split()[0])
        except (OSError, ValueError, IndexError):
            low = 32768
        first = max(1024, low - 16384)
        width = (low - first) // _PORT_LANES
        worker = os.environ.get("PYTEST_XDIST_WORKER", "")
        lane = (
            int(worker[2:]) if worker[2:].isdigit() else os.getpid()
        ) % _PORT_LANES
        ports = list(range(first + lane * width, first + (lane + 1) * width))
        random.shuffle(ports)
        # no room below the range: the kernel's own draw, as ever
        _port_lane = itertools.cycle(ports if width >= 64 else [0])
    got = []
    for port in itertools.islice(_port_lane, 4096):
        with socket.socket() as s:
            try:
                s.bind(("0.0.0.0", port))
            except OSError:
                continue
            got.append(s.getsockname()[1])
        if len(got) == n:
            return got
    raise RuntimeError(f"no {n} free ports in this worker's lane")


def spawn_daemon_edge(
    env_overrides: dict,
    sock_path: str,
    edge_http: int,
    edge_grpc: int = 0,
    daemon_boot_timeout: float = 180.0,
):
    """Spawn a daemon (edge socket enabled) plus a guber-edge fronting
    it, with HARD readiness checks: a dead or never-listening process
    fails with its captured output instead of leaking into the tests as
    opaque connection-refused noise. Returns (daemon, edge) Popens; the
    caller owns teardown (edge.kill(); daemon.terminate()).

    Shared across the daemon+edge e2e suites so spawn/teardown fixes
    land once (r4 review: three divergent copies had already drifted).
    """
    import os
    import pathlib
    import subprocess
    import sys
    import time

    import pytest

    root = pathlib.Path(__file__).resolve().parent.parent
    edge_bin = edge_binary()
    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass
    env = dict(os.environ, PYTHONPATH=str(root), **env_overrides)
    daemon = subprocess.Popen(
        [sys.executable, "-m", "gubernator_tpu.cli.daemon"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=root, env=env,
    )
    deadline = time.monotonic() + daemon_boot_timeout
    while time.monotonic() < deadline and not os.path.exists(sock_path):
        time.sleep(0.2)
        if daemon.poll() is not None:
            pytest.fail(f"daemon died:\n{daemon.stdout.read()}")
    if not os.path.exists(sock_path):
        daemon.kill()
        pytest.fail("daemon never created the edge socket")

    args = [str(edge_bin), "--listen", str(edge_http),
            "--backend", sock_path]
    if edge_grpc:
        args += ["--grpc-listen", str(edge_grpc)]
    edge = subprocess.Popen(
        args, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    probe_port = edge_grpc or edge_http
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if edge.poll() is not None:
            daemon.kill()
            pytest.fail(f"edge died:\n{edge.stdout.read()}")
        try:
            socket.create_connection(
                ("127.0.0.1", probe_port), timeout=1
            ).close()
            return daemon, edge
        except OSError:
            time.sleep(0.05)
    edge.kill()
    daemon.kill()
    pytest.fail("edge never started listening")


def native_lib_for_tests():
    """The package's own native module — the one handle,
    core/hashing.native_lib() — or skip with the import's reason.
    tests/conftest.py builds libguberhash.so before collection, so the
    skip is a box without a compiler."""
    import pytest

    from gubernator_tpu.core.hashing import native_lib

    pytest.importorskip("gubernator_tpu.native.hashlib_native")
    lib = native_lib()
    assert lib is not None, "the library appeared after this process asked"
    return lib


class WireDoor:
    """PeersV1Stub's pass-through door (GetPeerRateLimitsWire: bytes
    in, bytes out — what a PeerClient's flusher sends) for a stub fake
    that answers messages through GetPeerRateLimits."""

    async def GetPeerRateLimitsWire(self, wire, timeout=None, **kw):
        from gubernator_tpu.api.proto.gen import peers_pb2

        reply = await self.GetPeerRateLimits(
            peers_pb2.GetPeerRateLimitsReq.FromString(wire),
            timeout=timeout, **kw
        )
        return reply.SerializeToString()
