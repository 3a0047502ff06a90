#!/usr/bin/env python3
"""The quickest proof that the served path still starts on the chip.

`python3 chip_smoke.py` (one TPU chip) builds the native library, boots
`python -m gubernator_tpu.cli.daemon` as a child with GUBER_BACKEND=tpu
and a 10M-key store (the daemon an operator gets: default batch ladder,
default sketch tier, no other sizing option), loads 1,000,000 distinct
keys through the GEB door in 1000-item calls, sends a few hundred
checked requests through gRPC, HTTP JSON and GEB — token, leaky,
sliding, GCRA, a depth-2 chain, GLOBAL, peeks — and holds every answer
to a second child on GUBER_BACKEND=exact (core/oracle.py semantics, on
the CPU). Then health, /metrics, one profile capture, a SIGTERM drain,
and a second boot that shows the warm compile cache.

`--chips 4` runs the same daemon as GUBER_BACKEND=mesh GUBER_SHARDS=4
(one process driving four chips) against the same exact child, and no
other phase.

This parent never imports JAX: a chip belongs to one process, and the
daemon child must be the one that holds it. What device served is read
from the daemon's own report (/v1/debug/stages), not assumed.

Output: one JSON object per line; the last line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`
and is printed only when every phase passed AND the daemon served from
a TPU. Any failed phase raises: the exit code is non-zero and no
`ok: true` is printed. `--rehearse` runs every phase on whatever
platform JAX_PLATFORMS names (the CPU rehearsal, tests/test_chip_smoke
.py); it still ends non-zero there, because the device is not a TPU.
"""

import argparse
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
DAY_MS = 86_400_000  # windows long enough that wall-clock changes no answer
LOAD_LIMIT = 1000
DOORS = ("grpc", "http", "geb")
# a request may meet a program the warm-up does not build (the chain
# variant is jitted lazily) and then waits out a TPU compile: minutes
CALL_TIMEOUT = 600.0


class SmokeFailure(Exception):
    pass


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(addr: str, path: str, timeout: float = 30.0):
    with urllib.request.urlopen(
        f"http://{addr}{path}", timeout=timeout
    ) as r:
        return r.status, r.read()


# -- the daemon child --------------------------------------------------------


class Daemon:
    """One `python -m gubernator_tpu.cli.daemon` child, its ports and
    its log. The log goes to a file under `<checkout>/chiprun_out/`,
    which the chip tool brings back, so a failed run leaves it behind."""

    def __init__(self, name: str, env: dict, log_dir: str):
        self.name = name
        self.grpc = f"127.0.0.1:{free_port()}"
        self.http = f"127.0.0.1:{free_port()}"
        self.geb_port = free_port()
        self.log_path = os.path.join(log_dir, f"chip_smoke_{name}.log")
        full = dict(os.environ)
        full.update(
            GUBER_GRPC_ADDRESS=self.grpc,
            GUBER_HTTP_ADDRESS=self.http,
            GUBER_GEB_PORT=str(self.geb_port),
            PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
            **env,
        )
        self._log = open(self.log_path, "wb")
        self.t_exec = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "gubernator_tpu.cli.daemon"],
            cwd=ROOT, env=full, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def log_text(self) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return f.read()

    def wait_ready(self, deadline: float) -> float:
        """Seconds from exec to the first healthy answer on the HTTP
        door (the daemon logs `Ready` at the same moment)."""
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"daemon '{self.name}' exited {rc} before Ready; "
                    f"log tail:\n{self.log_text()[-3000:]}"
                )
            try:
                status, _ = http_get(self.http, "/v1/HealthCheck", 2.0)
                if status == 200:
                    return time.monotonic() - self.t_exec
            except OSError:
                pass
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"daemon '{self.name}' not Ready in time; log tail:\n"
                    f"{self.log_text()[-3000:]}"
                )
            time.sleep(0.5)

    def compiles(self) -> dict:
        """Programs the XLA compiler built (JAX_LOG_COMPILES lines),
        persistent-cache hits among them, and the slowest by name."""
        text = self.log_text()
        seen = set(
            re.findall(
                r"Finished XLA compilation of jit\((.+?)\) in ([0-9.]+) sec",
                text,
            )
        )
        by_name: dict = {}
        for name, secs in seen:
            n, total, worst = by_name.get(name, (0, 0.0, 0.0))
            s = float(secs)
            by_name[name] = (n + 1, total + s, max(worst, s))
        hits = len(
            set(re.findall(r"cache hit for '[^']+' with key '([^']+)'", text))
        )
        top = sorted(by_name.items(), key=lambda kv: -kv[1][1])[:8]
        return {
            "programs": len(seen),
            "cache_hits": hits,
            "compile_seconds_total": round(
                sum(v[1] for v in by_name.values()), 1
            ),
            "slowest": {
                k: {"n": n, "seconds_total": round(t, 1),
                    "seconds_max": round(w, 1)}
                for k, (n, t, w) in top
            },
        }

    def sigterm(self, timeout: float = 60.0) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout)
        finally:
            self._log.close()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if not self._log.closed:
            self._log.close()


# -- the three doors ---------------------------------------------------------


class Doors:
    """gRPC GetRateLimits, HTTP /v1/GetRateLimits and the GEB door of
    one daemon, each answering [(status, limit, remaining, error)]."""

    def __init__(self, d: Daemon):
        from gubernator_tpu.client import V1Client
        from gubernator_tpu.client_geb import GebClient

        self.d = d
        self.v1 = V1Client(d.grpc)
        self.geb = GebClient(f"127.0.0.1:{d.geb_port}", timeout=CALL_TIMEOUT)
        self.hello = self.geb.connect()

    def close(self) -> None:
        self.geb.close()
        self.v1.close()

    @staticmethod
    def _norm(resps):
        return [
            (int(r.status), int(r.limit), int(r.remaining), r.error or "")
            for r in resps
        ]

    def call(self, door: str, reqs):
        if door == "grpc":
            return self._norm(
                self.v1.get_rate_limits(reqs, timeout=CALL_TIMEOUT)
            )
        if door == "geb":
            return self._norm(self.geb.get_rate_limits(reqs))
        body = json.dumps({"requests": [
            {
                "name": r.name, "uniqueKey": r.unique_key,
                "hits": r.hits, "limit": r.limit, "duration": r.duration,
                "algorithm": int(r.algorithm), "behavior": int(r.behavior),
                "chain": [
                    {"uniqueKey": lv.unique_key, "limit": lv.limit,
                     "duration": lv.duration}
                    for lv in r.chain
                ],
            }
            for r in reqs
        ]}).encode()
        req = urllib.request.Request(
            f"http://{self.d.http}/v1/GetRateLimits", data=body,
            headers={"Content-Type": "application/json"},
        )
        from gubernator_tpu.api.types import Status

        with urllib.request.urlopen(req, timeout=CALL_TIMEOUT) as r:
            out = json.loads(r.read())["responses"]
        return [
            (int(Status[x["status"]]), int(x["limit"]),
             int(x["remaining"]), x.get("error") or "")
            for x in out
        ]


def checked_sequence(seed: int):
    """[(door, [RateLimitReq])] — the same list for the device daemon
    and the exact one. Every algorithm, in-batch duplicates, keys driven
    over their limit, a depth-2 chain and one GLOBAL key; doors rotate
    so each door carries every kind."""
    from gubernator_tpu.api.types import (
        Algorithm, Behavior, ChainLevel, RateLimitReq,
    )

    rng = random.Random(seed)
    calls = []
    for c in range(36):
        reqs = []
        for algo in Algorithm:
            for _ in range(2):
                reqs.append(RateLimitReq(
                    name="chk", unique_key=f"{algo.name}:{rng.randrange(6)}",
                    hits=rng.choice((1, 1, 2, 0)), limit=5, duration=DAY_MS,
                    algorithm=algo,
                ))
        if c % 3 == 0:  # an in-batch duplicate of the first item's key
            reqs.append(RateLimitReq(
                name="chk", unique_key=reqs[0].unique_key, hits=1, limit=5,
                duration=DAY_MS, algorithm=reqs[0].algorithm,
            ))
        if c % 2 == 0:
            reqs.append(RateLimitReq(
                name="chk", unique_key="global-key", hits=1, limit=7,
                duration=DAY_MS, behavior=Behavior.GLOBAL,
            ))
        calls.append((DOORS[c % 3], reqs))
    for c in range(6):  # depth-2 chains: org -> team -> leaf
        reqs = [
            RateLimitReq(
                name="chk", unique_key=f"leaf:{k}", hits=1, limit=4,
                duration=DAY_MS,
                algorithm=Algorithm.TOKEN_BUCKET if k % 2 else Algorithm.GCRA,
                chain=[
                    ChainLevel("org", 12, DAY_MS),
                    ChainLevel(f"team:{k % 2}", 7, DAY_MS),
                ],
            )
            for k in (rng.randrange(4) for _ in range(3))
        ]
        calls.append((DOORS[c % 3], reqs))
    return calls


def run_sequence(doors: Doors, calls):
    return [doors.call(door, reqs) for door, reqs in calls]


def load_keys(doors: Doors, n_keys: int):
    """Create n_keys distinct keys through the GEB door, 1000 items a
    call, a credit window of calls in flight; every answer is checked
    against what was charged."""
    from gubernator_tpu.api.types import RateLimitReq

    t0 = time.monotonic()
    wrong = calls = 0
    window = 16
    for base in range(0, n_keys, 1000 * window):
        batches = [
            [
                RateLimitReq(
                    name="smoke", unique_key=f"load:{i}", hits=1 + i % 3,
                    limit=LOAD_LIMIT, duration=DAY_MS,
                )
                for i in range(b, min(b + 1000, n_keys))
            ]
            for b in range(base, min(base + 1000 * window, n_keys), 1000)
        ]
        for batch, resps in zip(
            batches, doors.geb.get_rate_limits_pipelined(batches)
        ):
            calls += 1
            for r, resp in zip(batch, resps):
                if (
                    resp.error or int(resp.status) != 0
                    or resp.remaining != LOAD_LIMIT - r.hits
                ):
                    wrong += 1
    return calls, wrong, time.monotonic() - t0


def peek_loaded(doors: Doors, n_keys: int, seed: int):
    """hits=0 peeks of loaded keys over all three doors: remaining must
    be the limit less what the load charged."""
    from gubernator_tpu.api.types import RateLimitReq

    rng = random.Random(seed + 1)
    wrong = total = 0
    for door in DOORS:
        idx = [rng.randrange(n_keys) for _ in range(40)]
        reqs = [
            RateLimitReq(name="smoke", unique_key=f"load:{i}", hits=0,
                         limit=LOAD_LIMIT, duration=DAY_MS)
            for i in idx
        ]
        for i, (st, lim, rem, err) in zip(idx, doors.call(door, reqs)):
            total += 1
            if err or st != 0 or lim != LOAD_LIMIT or (
                rem != LOAD_LIMIT - (1 + i % 3)
            ):
                wrong += 1
    return total, wrong


def metric(text: str, name: str, labels: str = "") -> float:
    m = re.search(
        rf"^{re.escape(name)}{re.escape(labels)} ([0-9.e+-]+)$", text, re.M
    )
    return float(m.group(1)) if m else 0.0


def capture_profile(doors: Doors) -> dict:
    """One /v1/debug/profile capture while peeks keep the device busy;
    the daemon's own listing says what landed on disk."""
    from gubernator_tpu.api.types import RateLimitReq

    stop = threading.Event()

    def traffic():
        reqs = [
            RateLimitReq(name="smoke", unique_key=f"load:{i}", hits=0,
                         limit=LOAD_LIMIT, duration=DAY_MS)
            for i in range(256)
        ]
        while not stop.is_set():
            doors.call("grpc", reqs)

    t = threading.Thread(target=traffic, daemon=True)
    t.start()
    try:
        status, body = http_get(
            doors.d.http, "/v1/debug/profile?ms=300&name=chip_smoke", 180.0
        )
    finally:
        stop.set()
        t.join(30.0)
    check(status == 200, f"profile capture answered {status}: {body!r}")
    _, listing = http_get(doors.d.http, "/v1/debug/profile?list=1")
    mine = [
        p for p in json.loads(listing)["profiles"]
        if p["name"] == "chip_smoke"
    ]
    check(
        mine and mine[0]["files"] > 0 and mine[0]["bytes"] > 0,
        f"profile trace directory is empty: {listing!r}",
    )
    return {"files": mine[0]["files"], "bytes": mine[0]["bytes"]}


def device_report(d: Daemon) -> dict:
    _, body = http_get(d.http, "/v1/debug/stages")
    return json.loads(body)


# -- the run -----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the GUBER_BACKEND=mesh GUBER_SHARDS=4 daemon "
                    "and its exact reference, no other phase")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--target-keys", type=int, default=10_000_000,
                    help="GUBER_STORE_TARGET_KEYS of the daemon "
                    "(BASELINE config 4's key count)")
    ap.add_argument("--load-keys", type=int, default=1_000_000,
                    help="distinct keys created through the GEB door")
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase on a named non-TPU platform "
                    "(still exits non-zero: the device is not a TPU)")
    ap.add_argument("--boot-timeout", type=float, default=1000.0)
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    # the host half of the hot path: built here, from the committed
    # guberhash.cc only, or the smoke fails
    subprocess.run(
        ["make", "-C", os.path.join(ROOT, "gubernator_tpu", "native")],
        check=True, stdout=sys.stderr,
    )
    sys.path.insert(0, ROOT)
    from gubernator_tpu.client_geb import client_hash_is_native
    from gubernator_tpu.jaxenv import CACHE_ENV, compile_cache_dir

    check(client_hash_is_native(), "libguberhash.so built but did not load")

    cache_dir = compile_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    cache_was_empty = not os.listdir(cache_dir)
    log_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(log_dir, exist_ok=True)

    mesh = args.chips == 4
    # GUBER_STORE_TARGET_KEYS sizes ONE shard's store (the geometry is
    # replicated per shard, docs/operations.md), so the mesh daemon is
    # given a quarter of the key budget each: same total store
    device_env = {
        "GUBER_BACKEND": "mesh" if mesh else "tpu",
        "GUBER_STORE_TARGET_KEYS": str(
            args.target_keys // args.chips
        ),
        "JAX_LOG_COMPILES": "1",
        CACHE_ENV: cache_dir,
    }
    if mesh:
        device_env["GUBER_SHARDS"] = "4"
        # the mesh warm-up compiles 27 decide programs (9 sub-rungs x
        # group rungs) side by side since PR 26: 323 s exec -> Ready on
        # a cold cache with the default sketch tier (PERF.md, PR 26), so
        # the daemon runs as an operator gets it, nothing cut
    exact_env = {"GUBER_BACKEND": "exact", "JAX_PLATFORMS": "cpu"}

    children = []
    try:
        # both children boot at once: the exact one needs no chip
        dev = Daemon("mesh4" if mesh else "tpu", device_env, log_dir)
        children.append(dev)
        exact = Daemon("exact", exact_env, log_dir)
        children.append(exact)
        deadline = time.monotonic() + args.boot_timeout
        exact.wait_ready(deadline)
        ready_s = dev.wait_ready(deadline)
        boot_log = dev.log_text()
        booted = dev.compiles()
        # cold = no program came from the cache (the directory may hold
        # another deployment's programs and still be cold for this one)
        emit(phase="boot", backend=device_env["GUBER_BACKEND"],
             cold=booted["cache_hits"] == 0,
             cache_dir_was_empty=cache_was_empty,
             seconds_exec_to_ready=round(ready_s, 1), **booted)

        report = device_report(dev)
        device = report["device"]
        check(device is not None, "daemon reports no device")
        emit(phase="device", device=device, host_prep=report["host_prep"],
             hasher=report["hasher"])
        check(
            device["platform"] == "tpu" or args.rehearse,
            f"the daemon serves from '{device['platform']}', not a TPU",
        )
        check("serving from" in boot_log, "boot log does not name the device")
        check(
            report["host_prep"] == "native" and report["hasher"] == "native"
            and "native prep:" in boot_log and "native XXH64" in boot_log,
            "the numpy/blake2b twins are serving: native library not loaded "
            "by the daemon",
        )
        store_line = re.search(r"store tiers: .*", boot_log)
        check(store_line, "boot log has no store tiers line")
        emit(phase="store", line=store_line.group(0))
        if mesh:
            check("partitioned engine: 4-shard mesh" in boot_log,
                  "boot log lacks 'partitioned engine: 4-shard mesh'")
            check(device["count"] == 4, f"{device['count']} devices, not 4")
            per = [x["state_bytes"] for x in device["devices"]]
            check(
                min(per) > 0 and max(per) - min(per) <= 0.05 * max(per),
                f"state is not a quarter per device: {per}",
            )

        doors = Doors(dev)
        check(doors.hello.fast and doors.hello.xxh64,
              f"GEB hello flags {doors.hello.flags:#x}: fast/xxh64 not set")
        ncalls, wrong, secs = load_keys(doors, args.load_keys)
        emit(phase="load", door="geb", keys=args.load_keys, calls=ncalls,
             items_per_call=1000, wrong=wrong, seconds=round(secs, 1),
             geb_client=doors.geb.stats())
        check(wrong == 0, f"{wrong} load answers differ from what was charged")

        calls = checked_sequence(args.seed)
        got = run_sequence(doors, calls)
        exact_doors = Doors(exact)
        want = run_sequence(exact_doors, calls)
        exact_doors.close()
        n = mismatches = 0
        first = None
        for (door, reqs), g, w in zip(calls, got, want):
            check(len(g) == len(w) == len(reqs), "answer count differs")
            for r, a, b in zip(reqs, g, w):
                n += 1
                if a != b:
                    mismatches += 1
                    first = first or {
                        "door": door, "key": r.unique_key,
                        "algorithm": int(r.algorithm), "hits": r.hits,
                        "device": a, "exact": b,
                    }
        over = sum(1 for g in got for a in g if a[0] == 1)
        emit(phase="checked", requests=n, mismatches=mismatches,
             over_limit_answers=over, doors=list(DOORS),
             first_mismatch=first)
        check(mismatches == 0, f"{mismatches} answers differ from exact")
        check(over > 0, "no checked request was driven over its limit")

        peeks, wrong = peek_loaded(doors, args.load_keys, args.seed)
        emit(phase="peek", requests=peeks, wrong=wrong)
        check(wrong == 0, f"{wrong} peeks of loaded keys are wrong")

        hc = doors.v1.health_check(timeout=10.0)
        check(hc.status == "healthy", f"HealthCheck says {hc.status!r}")
        _, mtext = http_get(dev.http, "/metrics")
        mtext = mtext.decode()
        counters = {
            "device_batch_size_count": metric(mtext, "device_batch_size_count"),
            "device_batch_size_sum": metric(mtext, "device_batch_size_sum"),
            "cache_access_count_miss": metric(
                mtext, "cache_access_count_total", '{type="miss"}'),
            "cache_access_count_hit": metric(
                mtext, "cache_access_count_total", '{type="hit"}'),
            "edge_fast_items_total": metric(mtext, "edge_fast_items_total"),
            "store_evictions_total": metric(mtext, "store_evictions_total"),
            "store_dropped_creates_total": metric(
                mtext, "store_dropped_creates_total"),
        }
        emit(phase="metrics", health=hc.status, **counters)
        check(counters["device_batch_size_sum"] >= args.load_keys,
              "batcher counters did not move with the load")
        check(counters["cache_access_count_miss"] >= args.load_keys,
              "store miss counter did not move with the load")
        check(counters["store_evictions_total"] == 0,
              "the store evicted live keys")

        emit(phase="profile", **capture_profile(doors))
        after = device_report(dev)["device"]
        emit(phase="device_after_load", devices=after["devices"])
        doors.close()

        rc = dev.sigterm()
        check(rc == 0, f"SIGTERM: daemon exited {rc}")
        check("drained in" in dev.log_text(), "no drain line in the log")
        total = dev.compiles()
        emit(phase="drain", exit_code=rc,
             programs_compiled_after_ready=total["programs"] - booted["programs"],
             compile_seconds_after_ready=round(
                 total["compile_seconds_total"]
                 - booted["compile_seconds_total"], 1))

        if not mesh:
            # the same daemon again, same cache directory: the warm figure
            warm = Daemon("tpu_warm", device_env, log_dir)
            children.append(warm)
            warm_s = warm.wait_ready(time.monotonic() + args.boot_timeout)
            rebooted = warm.compiles()
            emit(phase="boot", backend="tpu",
                 cold=rebooted["cache_hits"] == 0, cache_dir_was_empty=False,
                 seconds_exec_to_ready=round(warm_s, 1), **rebooted)
            check(rebooted["cache_hits"] > 0,
                  "the second boot found nothing in the compile cache")
            check(warm.sigterm() == 0, "warm daemon did not exit 0")
        check(exact.sigterm() == 0, "exact daemon did not exit 0")
    finally:
        for c in children:
            c.kill()

    emit(phase="done", seconds=round(time.monotonic() - t_start, 1))
    check("jax" not in sys.modules, "the parent imported jax")
    check(
        device["platform"] == "tpu",
        f"every phase passed, but on '{device['platform']}': not a chip run",
    )
    emit(ok=True, device={k: device[k] for k in ("platform", "kind", "count")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
