"""The system under test as child processes: a `Ring` of
`python -m gubernator_tpu.cli.daemon`, each with its ports, its log and
what it reports about itself over HTTP. (After chip_smoke.py's
`Daemon`; the yardstick keeps its own copy.)

A configuration is one daemon unless it says `"nodes"`: a list, one
object a daemon, `{"env": {...}, "chips": k}`. Node i's environment is
the configuration's `env` overlaid with `nodes[i].env` (where a
machine's chip pinning lives: the harness invents none), and for more
than one node the harness sets the static ring beside the addresses it
always sets: `GUBER_PEERS` (every node's gRPC address, the same list on
every node), `GUBER_ADVERTISE_ADDRESS` (the node's own) and
`GUBER_GEB_PEER_DOORS` (each gRPC address's GEB door). For one node it
sets none of the three.

Every port is drawn free, in every run, from below the range the
kernel hands out, and held until its daemon starts. A key belongs to the node whose gRPC ADDRESS hashes next after
it on the crc32 circle, so a ring's gRPC ports are the ones, of many
drawn, that cut the circle into the most even arcs: each node owns one
N-th of the keys whatever ports the machine had free. Which of them is
node 0 follows the run's key names: the node that owns least of the
zipf head (`head_owned`), so the door that clients dial is the same
kind of node in every run.

Node 0's addresses and reports stand at the top of the ring, so what
knew one daemon (generators, the checks' doors, scripts) reaches node 0
of any ring.

The parent that uses this never imports JAX: a chip belongs to one
process, and the daemons are the ones that hold them.
"""

from __future__ import annotations

import bisect
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request
import zlib

from harness import keyspace
from reference_ring import owner_of

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_ARGV = ["-m", "gubernator_tpu.cli.daemon"]


class BenchFailure(Exception):
    """The run cannot produce a result: no last line is printed."""


POOL = 64  # free ports drawn for each node of a ring, to choose its gRPC port from
HEAD_IDS = range(1, 17)  # the zipf head: id 1 alone is 18% of a stream at a = 1.2


def ring_point(addr: str) -> int:
    """Where an address stands on the ring (a copy of the program's
    `core.hashing.ring_hash`, upstream's hash.go:40-42)."""
    return zlib.crc32(addr.encode("utf-8")) & 0xFFFFFFFF


def even_points(points: list, n: int) -> list:
    """Indices of the n of `points` that stand most nearly 1/n of the
    circle apart: with every point as the first, the nearest to each
    n-th of the circle after it; the choice whose worst miss is least."""
    ring = sorted(zip(points, range(len(points))))
    best = None
    for first, _ in ring:
        picks = []
        for i in range(n):
            target = (first + i * 2**32 // n) % 2**32
            k = bisect.bisect_left(ring, (target,))
            picks.append(min(
                (min((p - target) % 2**32, (target - p) % 2**32), j)
                for p, j in (ring[k - 1], ring[k % len(ring)])))
        if len({j for _, j in picks}) == n and (best is None or max(picks) < best[0]):
            best = (max(picks), [j for _, j in picks])
    return best[1]


def head_owned(tag: str, grpc: list) -> list:
    """For each gRPC address of a ring, the key ids of the zipf head it
    owns under the run's key tag, by the plain reference's ring: a key
    stands at the crc32 of its hash key, `<name>_<tag>:<id>` as the
    generators send it."""
    owned = {a: [] for a in grpc}
    for k in HEAD_IDS:
        owned[owner_of(f"{keyspace.NAME}_{tag}:{k}", grpc)].append(k)
    return [owned[a] for a in grpc]


def free_sockets(k: int) -> list:
    """k sockets bound, on every address, to distinct ports that the
    kernel hands to nobody: the 16,384 below the range it draws from
    for bind(0) and connect(). A daemon binds its doors only after its
    warm-up, seconds to minutes after its ports were chosen, and a port
    drawn with bind(0) and let go was meanwhile drawn by a neighbour (a
    test run beside five others lost its GEB door that way)."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            low = int(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        low = 32768
    ports = range(max(1024, low - 16384), low)
    # no room below the range: the kernel's own draw, as ever
    ports = random.sample(ports, len(ports)) if len(ports) >= 8 * k else [0] * k
    socks = []
    for port in ports:
        s = socket.socket()
        try:
            s.bind(("0.0.0.0", port))
        except OSError:
            s.close()
            continue
        socks.append(s)
        if len(socks) == k:
            return socks
    for s in socks:
        s.close()
    raise BenchFailure(f"no {k} free ports for the daemons")


def draw_addresses(n: int, tag: str = None):
    """([{"grpc", "http", "geb"}, ...] for n nodes, the sockets that hold
    each node's three ports): every port free and distinct, all bound
    at once; the caller lets a node's go when it starts the node. A
    ring's gRPC ports are chosen from POOL a node for even arcs, in the
    circle's order. Given the run's key tag, the order starts at the
    node that owns none of the zipf head's ids, or where each of the n
    owns some, at the one that does not own id 1 and owns the fewest of
    the others: a run whose door node owns id 1 sits at another level
    (PERF.md section 2), and ports x seed would decide which run does."""
    socks = free_sockets(3 if n == 1 else POOL * n)
    addr = [f"127.0.0.1:{s.getsockname()[1]}" for s in socks]
    grpc = even_points([ring_point(a) for a in addr], n) if n > 1 else [0]
    if n > 1 and tag is not None:
        owned = head_owned(tag, [addr[g] for g in grpc])
        first = min(range(n), key=lambda i: (1 in owned[i], len(owned[i]), i))
        grpc = grpc[first:] + grpc[:first]
    rest = iter(i for i in range(len(socks)) if i not in grpc)
    mine = [{"grpc": g, "http": next(rest), "geb": next(rest)} for g in grpc]
    kept = {i for m in mine for i in m.values()}
    for i, s in enumerate(socks):
        if i not in kept:
            s.close()
    return ([{door: addr[i] for door, i in m.items()} for m in mine],
            [[socks[i] for i in m.values()] for m in mine])


def node_specs(config: dict) -> list:
    """[{"env", "chips"}, ...]: the configuration's nodes, each with its
    whole environment. No `nodes` is one node with the config's own."""
    nodes = config.get("nodes")
    if nodes is None:
        return [{"env": dict(config["env"]), "chips": config["chips"]}]
    if not nodes or sum(n["chips"] for n in nodes) != config["chips"]:
        raise BenchFailure(
            f"the configuration's nodes hold {sum(n['chips'] for n in nodes)} "
            f"chips, its 'chips' says {config['chips']}"
        )
    return [{"env": {**config["env"], **n.get("env", {})}, "chips": n["chips"]}
            for n in nodes]


def node_env(env: dict, addrs: list, i: int) -> dict:
    """What node i of the ring at `addrs` is given over the process's
    own environment: its configuration's, then what the harness sets."""
    me = addrs[i]
    out = dict(env)
    out.update(
        GUBER_GRPC_ADDRESS=me["grpc"],
        GUBER_HTTP_ADDRESS=me["http"],
        GUBER_GEB_PORT=me["geb"].rsplit(":", 1)[1],
        JAX_LOG_COMPILES="1",
        # every program goes to the compile cache, however short its
        # compile: a boot then finds all of them or none, never the
        # few that took about JAX's default threshold of 1 s
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    if len(addrs) > 1:
        out.update(
            GUBER_PEERS=",".join(a["grpc"] for a in addrs),
            GUBER_ADVERTISE_ADDRESS=me["grpc"],
            GUBER_GEB_PEER_DOORS=",".join(f"{a['grpc']}={a['geb']}" for a in addrs),
        )
    return out


def http_get(addr: str, path: str, timeout: float = 30.0) -> bytes:
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=timeout) as r:
        if r.status != 200:
            raise BenchFailure(f"GET {path} answered {r.status}")
        return r.read()


class Daemon:
    """One node, started on the addresses it is given."""

    def __init__(self, name: str, env: dict, log_dir: str, argv, addrs: list,
                 index: int):
        self.index = index
        self.grpc, self.http, self.geb = (
            addrs[index][door] for door in ("grpc", "http", "geb"))
        os.makedirs(log_dir, exist_ok=True)
        self.log_path = os.path.join(
            log_dir, f"{name}.daemon.{index}.log" if index else f"{name}.daemon.log")
        full = dict(os.environ)
        full.update(node_env(env, addrs, index))
        full["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
        self._log = open(self.log_path, "wb")
        self._asked = False
        self.proc = subprocess.Popen(
            [sys.executable, *(argv or DEFAULT_ARGV)],
            cwd=ROOT, env=full, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_text(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def check_alive(self, when: str = "before the run ended") -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise BenchFailure(
                f"daemon (node {self.index}) exited {rc} {when}; log tail:\n"
                f"{self.log_text()[-3000:]}"
            )

    def wait_ready(self, deadline: float, peers: int = 1) -> None:
        """Until /v1/HealthCheck answers; in a ring, until it answers
        healthy with every peer counted."""
        while True:
            self.check_alive("before Ready")
            try:
                health = http_get(self.http, "/v1/HealthCheck", 2.0)
                if peers == 1:
                    return
                h = json.loads(health)
                if h["status"] == "healthy" and h["peerCount"] == peers:
                    return
            except (OSError, BenchFailure):
                pass
            if time.monotonic() > deadline:
                raise BenchFailure(
                    f"daemon (node {self.index}) not Ready in time; log tail:\n"
                    f"{self.log_text()[-3000:]}"
                )
            time.sleep(0.25)

    def compiles(self) -> dict:
        """Programs XLA built so far (JAX_LOG_COMPILES lines): how many,
        how many came from the persistent cache, seconds in total, and
        `built`: whether this process compiled any itself. The daemon's
        two log handlers print every line twice, hits and finishes
        alike, so more finishes than hits is a compile whatever the
        handlers: the cache's own growth cannot say (one that evicts as
        it fills shrank under a boot that compiled for 254 s)."""
        text = self.log_text()
        built = re.findall(
            r"Finished XLA compilation of jit\((.+?)\) in ([0-9.]+) sec", text
        )
        hit_lines = re.findall(r"cache hit for '[^']+' with key '([^']+)'", text)
        return {
            "programs": len(built),
            "cache_hits": len(set(hit_lines)),
            "compile_seconds": sum(float(s) for _, s in built),
            "built": len(built) > len(hit_lines),
        }

    def get(self, path: str, timeout: float = 30.0) -> bytes:
        try:
            return http_get(self.http, path, timeout)
        except OSError:
            self.check_alive()
            raise

    def stages(self) -> dict:
        return json.loads(self.get("/v1/debug/stages"))

    def prom(self) -> dict:
        """/metrics as {name{labels}: value}."""
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out

    def terminate(self) -> None:
        """SIGTERM, once: a second one would cut the drain short."""
        if self.proc.poll() is None and not self._asked:
            self._asked = True
            self.proc.send_signal(signal.SIGTERM)

    def stop(self, timeout: float = 60.0) -> int:
        """SIGTERM, wait; kill if it does not go. Returns the exit code."""
        try:
            self.terminate()
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
            return self.proc.returncode
        finally:
            if not self._log.closed:
                self._log.close()


class Ring:
    """A configuration's daemons on addresses drawn before any boots
    (for the run's key tag, where one is given: `draw_addresses`),
    each node's ports held until it does. `start(i)` boots node i, or
    boots it afresh on the same addresses (its peers keep the list they
    were given)."""

    def __init__(self, name: str, config: dict, log_dir: str, argvs=None,
                 tag: str = None):
        self.name, self.log_dir = name, log_dir
        self.specs = node_specs(config)
        self.addrs, self._held = draw_addresses(len(self.specs), tag)
        self.argvs = argvs or {}
        self.nodes = [None] * len(self.specs)
        self.devices = [None] * len(self.specs)  # each node's own report
        self.exits = []

    def _let_go(self, i: int) -> None:
        for s in self._held[i]:
            s.close()
        self._held[i] = []

    def start(self, i: int) -> Daemon:
        self._let_go(i)
        self.nodes[i] = Daemon(self.name, self.specs[i]["env"], self.log_dir,
                               self.argvs.get(i), self.addrs, i)
        return self.nodes[i]

    # node 0 at the top
    grpc = property(lambda self: self.nodes[0].grpc)
    http = property(lambda self: self.nodes[0].http)
    geb = property(lambda self: self.nodes[0].geb)

    def prom(self) -> dict:
        return self.nodes[0].prom()

    def check_alive(self) -> None:
        for d in self.nodes:
            d.check_alive()

    def stop(self, timeout: float = 60.0) -> int:
        """Every node stopped, all asked before any is waited for; one
        that lingers is killed. Returns node 0's exit code; `exits`
        keeps them all."""
        for i in range(len(self.nodes)):
            self._let_go(i)
        started = [d for d in self.nodes if d is not None]
        for d in started:
            d.terminate()
        self.exits = [d.stop(timeout) for d in started]
        return self.exits[0] if self.exits else None
