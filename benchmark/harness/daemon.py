"""The system under test as a child process: one
`python -m gubernator_tpu.cli.daemon`, its ports, its log and what it
reports about itself over HTTP. (After chip_smoke.py's `Daemon`; the
yardstick keeps its own copy.)

The parent that uses this never imports JAX: a chip belongs to one
process, and the daemon is the one that holds it.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_ARGV = ["-m", "gubernator_tpu.cli.daemon"]


class BenchFailure(Exception):
    """The run cannot produce a result: no last line is printed."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(addr: str, path: str, timeout: float = 30.0) -> bytes:
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=timeout) as r:
        if r.status != 200:
            raise BenchFailure(f"GET {path} answered {r.status}")
        return r.read()


class Daemon:
    def __init__(self, name: str, env: dict, log_dir: str, argv=None):
        self.grpc = f"127.0.0.1:{free_port()}"
        self.http = f"127.0.0.1:{free_port()}"
        self.geb = f"127.0.0.1:{free_port()}"
        os.makedirs(log_dir, exist_ok=True)
        self.log_path = os.path.join(log_dir, f"{name}.daemon.log")
        full = dict(os.environ)
        full.update(env)
        full.update(
            GUBER_GRPC_ADDRESS=self.grpc,
            GUBER_HTTP_ADDRESS=self.http,
            GUBER_GEB_PORT=self.geb.rsplit(":", 1)[1],
            JAX_LOG_COMPILES="1",
            # every program goes to the compile cache, however short its
            # compile: a boot then finds all of them or none, never the
            # few that took about JAX's default threshold of 1 s
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, *(argv or DEFAULT_ARGV)],
            cwd=ROOT, env=full, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_text(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def wait_ready(self, deadline: float) -> None:
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise BenchFailure(
                    f"daemon exited {rc} before Ready; log tail:\n"
                    f"{self.log_text()[-3000:]}"
                )
            try:
                http_get(self.http, "/v1/HealthCheck", 2.0)
                return
            except (OSError, BenchFailure):
                pass
            if time.monotonic() > deadline:
                raise BenchFailure(
                    f"daemon not Ready in time; log tail:\n"
                    f"{self.log_text()[-3000:]}"
                )
            time.sleep(0.25)

    def compiles(self) -> dict:
        """Programs XLA built so far (JAX_LOG_COMPILES lines): how many,
        how many came from the persistent cache, seconds in total."""
        text = self.log_text()
        built = re.findall(
            r"Finished XLA compilation of jit\((.+?)\) in ([0-9.]+) sec", text
        )
        hits = len(set(
            re.findall(r"cache hit for '[^']+' with key '([^']+)'", text)
        ))
        return {
            "programs": len(built),
            "cache_hits": hits,
            "compile_seconds": sum(float(s) for _, s in built),
        }

    def stages(self) -> dict:
        return json.loads(http_get(self.http, "/v1/debug/stages"))

    def prom(self) -> dict:
        """/metrics as {name{labels}: value}."""
        out = {}
        for line in http_get(self.http, "/metrics").decode().splitlines():
            if line and not line.startswith("#"):
                name, _, val = line.rpartition(" ")
                try:
                    out[name] = float(val)
                except ValueError:
                    pass
        return out

    def stop(self, timeout: float = 60.0) -> int:
        """SIGTERM, wait; kill if it does not go. Returns the exit code."""
        try:
            if self.proc.poll() is None:
                self.proc.send_signal(signal.SIGTERM)
                try:
                    self.proc.wait(timeout)
                except subprocess.TimeoutExpired:
                    self.proc.kill()
                    self.proc.wait()
            return self.proc.returncode
        finally:
            if not self._log.closed:
                self._log.close()
