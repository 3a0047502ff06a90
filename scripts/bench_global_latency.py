"""GLOBAL latency artifact: the BASELINE config-3 story, measured honestly.

The reference's claim is "most responses < 1ms" for batched/GLOBAL
behavior in production (reference README.md:99-100) — a per-response
latency on co-located hardware, not a saturated-tail number. This bench
produces the two measurements that bracket it here:

1. WIRE (this box, 1 core): single keep-alive client sending GLOBAL
   requests through the compiled edge into a live daemon (exact
   backend — the inline host path a replica read takes), at the edge's
   default 500us batch window AND at --batch-wait-us 0. Client, edge,
   bridge, instance, and response all inside the measurement.
2. DEVICE (default jax device — the real chip under the driver): the
   GLOBAL replica-read decide step (50% gnp rows) and the broadcast
   install step (upsert_globals) at serving batch sizes, as TRUE
   per-step percentiles (p50/p99/p999): >=1k individually dispatched
   steps run under a device profiler trace, and each step's duration
   is read from the trace's device-side timestamps ("XLA Modules"
   events on /device:TPU:*). Host wall-clock never touches the
   number, so host scheduling and dispatch jitter cannot contaminate
   it. The fused-loop mean is still computed as a cross-check row.

Prints one JSON document on stdout; chatter on stderr.
Usage: python scripts/bench_global_latency.py [--skip-wire] [--skip-device]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import socket
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


ROOT = pathlib.Path(__file__).resolve().parent.parent
EDGE_BIN = ROOT / "gubernator_tpu" / "native" / "edge" / "guber-edge"


def _percentiles(lat):
    lat = sorted(lat)
    n = len(lat)

    def p(q):
        return round(lat[min(n - 1, int(q * n))] * 1e3, 3)

    return {
        "p50_ms": p(0.50),
        "p90_ms": p(0.90),
        "p99_ms": p(0.99),
        "p999_ms": p(0.999),
        "sub_1ms_pct": round(
            100.0 * sum(1 for x in lat if x < 0.001) / n, 2
        ),
        "n": n,
    }


def bench_wire(batch_wait_us: int, n_calls: int = 5000) -> dict:
    """One keep-alive client, GLOBAL item per request, through the edge."""
    sock_path = f"/tmp/guber-glat-{batch_wait_us}.sock"
    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass
    grpc_port, http_port, edge_port = 29561, 29562, 29563
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        GUBER_BACKEND="exact",
        GUBER_GRPC_ADDRESS=f"127.0.0.1:{grpc_port}",
        GUBER_HTTP_ADDRESS=f"127.0.0.1:{http_port}",
        GUBER_EDGE_SOCKET=sock_path,
        PYTHONPATH=str(ROOT) + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    daemon = subprocess.Popen(
        [sys.executable, "-m", "gubernator_tpu.cli.daemon"],
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
        cwd=ROOT, env=env,
    )
    edge = None
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not os.path.exists(sock_path):
            time.sleep(0.2)
            if daemon.poll() is not None:
                raise RuntimeError("daemon died during startup")
        edge = subprocess.Popen(
            [str(EDGE_BIN), "--listen", str(edge_port), "--backend",
             sock_path, "--batch-wait-us", str(batch_wait_us)],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            try:
                socket.create_connection(
                    ("127.0.0.1", edge_port), timeout=1
                ).close()
                break
            except OSError:
                time.sleep(0.05)

        body = json.dumps(
            {"requests": [{"name": "g", "uniqueKey": "G", "hits": 1,
                           "limit": 1_000_000, "duration": 10_000,
                           "behavior": "GLOBAL"}]}
        ).encode()
        req = (
            f"POST /v1/GetRateLimits HTTP/1.1\r\nHost: x\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode() + body
        s = socket.create_connection(("127.0.0.1", edge_port))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

        def call():
            s.sendall(req)
            buf = b""
            while b"\r\n\r\n" not in buf:
                buf += s.recv(65536)
            head, _, rest = buf.partition(b"\r\n\r\n")
            cl = int(
                [ln for ln in head.split(b"\r\n")
                 if ln.lower().startswith(b"content-length")][0]
                .split(b":")[1]
            )
            while len(rest) < cl:
                rest += s.recv(65536)

        for _ in range(300):
            call()
        lat = []
        for _ in range(n_calls):
            t0 = time.perf_counter()
            call()
            lat.append(time.perf_counter() - t0)
        s.close()
        row = {
            "scenario": "global_1way_edge_keepalive",
            "batch_wait_us": batch_wait_us,
            "backend": "exact",
            **_percentiles(lat),
        }
        log(f"wire batch_wait={batch_wait_us}us: {row}")
        return row
    finally:
        if edge is not None:
            edge.kill()
        daemon.terminate()
        daemon.wait(timeout=10)


def _trace_step_percentiles(trace_dir: str, prefix: str) -> dict:
    """Per-step device durations from a jax profiler trace: the
    Chrome trace (*.trace.json.gz) carries one
    "XLA Modules" event per executable run on /device:TPU:* with
    device-clock timestamps and sub-us durations."""
    import glob
    import gzip

    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz"))
    )
    assert paths, f"no trace under {trace_dir}"
    with gzip.open(paths[-1]) as f:
        ev = json.load(f)["traceEvents"]
    device_pids = {
        e["pid"]
        for e in ev
        if e.get("ph") == "M"
        and e.get("name") == "process_name"
        and "/device:" in e["args"].get("name", "")
    }
    module_tids = {
        (e["pid"], e["tid"])
        for e in ev
        if e.get("ph") == "M"
        and e.get("name") == "thread_name"
        and e["args"].get("name") == "XLA Modules"
        and e["pid"] in device_pids
    }
    runs = [
        e
        for e in ev
        if e.get("ph") == "X"
        and (e.get("pid"), e.get("tid")) in module_tids
        and e.get("name", "").startswith(prefix)
    ]
    durs = sorted(e["dur"] for e in runs)  # already microseconds
    n = len(durs)
    assert n >= 1000, f"only {n} device step events for {prefix}"

    def p(q):
        return round(durs[min(n - 1, int(q * n))], 1)

    starts = sorted(e["ts"] for e in runs)
    gaps = sorted(
        max(0.0, b - a - d)
        for (a, b, d) in zip(starts, starts[1:], durs)
    )
    return {
        "n_steps": n,
        "p50_us": p(0.50),
        "p99_us": p(0.99),
        "p999_us": p(0.999),
        "max_us": round(durs[-1], 1),
        # device idle between consecutive steps (how long the host
        # takes to dispatch the next one) — occupancy honesty, not a
        # latency row
        "median_dispatch_gap_us": round(gaps[len(gaps) // 2], 1),
    }


def bench_device() -> list:
    """Per-step percentiles (trace method) + fused-mean cross-check of
    the GLOBAL device paths."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    import gubernator_tpu.core  # noqa: F401 (x64)
    from gubernator_tpu.core.engine import (
        _presort_grouped,
        build_groups,
        choose_bucket,
        group_rungs,
    )
    from gubernator_tpu.core.kernels import (
        BatchRequest,
        decide_presorted,
        upsert_globals,
    )
    from gubernator_tpu.core.store import StoreConfig, new_store

    dev = jax.devices()[0]
    log(f"device: {dev.platform} ({dev.device_kind})")
    ROWS, SLOTS = 16, 1 << 15
    S = 512
    rows = []
    rng = np.random.default_rng(7)
    for B in (1024, 4096):
        store = new_store(StoreConfig(rows=ROWS, slots=SLOTS))
        kh = (
            (rng.integers(0, 100_000, B).astype(np.uint64)
             * np.uint64(0x9E3779B97F4A7C15))
            ^ np.uint64(0xDEADBEEFCAFEF00D)
        )
        order, gid, lp, g_real = _presort_grouped(kh, SLOTS)
        kh = kh[order]
        G = choose_bucket(group_rungs(B), g_real)
        groups = jax.tree.map(
            jnp.asarray, build_groups(kh, gid, lp, g_real, B, B, G)
        )
        # 50% replica reads (gnp): the shape of a GLOBAL-heavy batch on
        # a non-owner node answering from its replica
        req = BatchRequest(
            key_hash=jnp.asarray(kh),
            hits=jnp.ones(B, jnp.int32),
            limit=jnp.full(B, 10_000, jnp.int32),
            duration=jnp.full(B, 60_000, jnp.int32),
            algo=jnp.zeros(B, jnp.int32),
            gnp=jnp.asarray(np.arange(B) % 2 == 0),
            valid=jnp.ones(B, bool),
        )

        def steps(store, req, groups):
            def body(i, carry):
                store, chk = carry
                store, resp, _ = decide_presorted(
                    store, req, jnp.int32(1000) + i, groups
                )
                chk = chk + jnp.sum(
                    resp.status ^ resp.remaining, dtype=jnp.int32
                )
                return store, chk

            return lax.fori_loop(
                0, S, body, (store, jnp.zeros((), jnp.int32))
            )

        stepped = jax.jit(steps, donate_argnums=(0,))
        store, chk = stepped(store, req, groups)
        int(chk)  # barrier
        best = None
        for _ in range(3):
            store = new_store(StoreConfig(rows=ROWS, slots=SLOTS))
            t0 = time.monotonic()
            store, chk = stepped(store, req, groups)
            int(chk)
            dt = (time.monotonic() - t0) / S * 1e6
            best = dt if best is None else min(best, dt)
        log(f"device decide fused-mean B={B}: {best:.0f} us/step")

        # TRUE per-step percentiles: >=1k individually dispatched steps
        # under a device trace; durations come from device timestamps
        def gstep_decide(store, req, groups, now):
            store, resp, _ = decide_presorted(store, req, now, groups)
            return store, resp.status

        one = jax.jit(gstep_decide, donate_argnums=(0,))
        store = new_store(StoreConfig(rows=ROWS, slots=SLOTS))
        store, st_out = one(store, req, groups, jnp.int32(999))
        jax.block_until_ready(st_out)  # compile before tracing
        N_STEPS = 1100
        trace_dir = f"/tmp/guber-glat-trace-decide-{B}"
        subprocess.run(["rm", "-rf", trace_dir])
        jax.profiler.start_trace(trace_dir)
        for i in range(N_STEPS):
            store, st_out = one(store, req, groups, jnp.int32(1000 + i))
        jax.block_until_ready(st_out)
        jax.profiler.stop_trace()
        pct = _trace_step_percentiles(trace_dir, "jit_gstep_decide")
        rows.append(
            {
                "scenario": "device_global_replica_decide_step",
                "batch": B,
                "gnp_fraction": 0.5,
                "method": "device-trace per-step percentiles",
                **pct,
                "fused_mean_us_crosscheck": round(best, 1),
                "device": dev.device_kind,
            }
        )
        log(f"device decide B={B}: {pct}")

    # broadcast install (UpdatePeerGlobals receive) at B=1024
    B = 1024
    store = new_store(StoreConfig(rows=ROWS, slots=SLOTS))
    kh = (
        (rng.integers(0, 100_000, B).astype(np.uint64)
         * np.uint64(0x9E3779B97F4A7C15))
        ^ np.uint64(0xDEADBEEFCAFEF00D)
    )
    args = (
        jnp.asarray(kh),
        jnp.full(B, 10_000, jnp.int32),
        jnp.full(B, 5_000, jnp.int32),
        jnp.full(B, 60_000, jnp.int32),
        jnp.zeros(B, bool),
        jnp.ones(B, bool),
    )

    def upsert_steps(store, kh, lim, rem, rst, over, valid):
        def body(i, store):
            return upsert_globals(store, kh, lim, rem, rst + i, over, valid)

        return lax.fori_loop(0, S, body, store)

    up = jax.jit(upsert_steps, donate_argnums=(0,))
    store = up(store, *args)
    jax.block_until_ready(store.data)
    float(np.asarray(store.data[0, 0]))  # barrier via tiny fetch
    best = None
    for _ in range(3):
        store = new_store(StoreConfig(rows=ROWS, slots=SLOTS))
        t0 = time.monotonic()
        store = up(store, *args)
        float(np.asarray(store.data[0, 0]))
        dt = (time.monotonic() - t0) / S * 1e6
        best = dt if best is None else min(best, dt)
    log(f"device upsert fused-mean B={B}: {best:.0f} us/step")

    def gstep_upsert(store, kh, lim, rem, rst, over, valid, now):
        return upsert_globals(store, kh, lim, rem, rst + now, over, valid)

    one_up = jax.jit(gstep_upsert, donate_argnums=(0,))
    store = new_store(StoreConfig(rows=ROWS, slots=SLOTS))
    store = one_up(store, *args, jnp.int32(0))
    jax.block_until_ready(store.data)
    N_STEPS = 1100
    trace_dir = "/tmp/guber-glat-trace-upsert"
    subprocess.run(["rm", "-rf", trace_dir])
    jax.profiler.start_trace(trace_dir)
    for i in range(N_STEPS):
        store = one_up(store, *args, jnp.int32(i))
    jax.block_until_ready(store.data)
    jax.profiler.stop_trace()
    pct = _trace_step_percentiles(trace_dir, "jit_gstep_upsert")
    rows.append(
        {
            "scenario": "device_global_broadcast_install_step",
            "batch": B,
            "method": "device-trace per-step percentiles",
            **pct,
            "fused_mean_us_crosscheck": round(best, 1),
            "device": dev.device_kind,
        }
    )
    log(f"device upsert B={B}: {pct}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-wire", action="store_true")
    ap.add_argument("--skip-device", action="store_true")
    args = ap.parse_args()
    doc = {"rows": []}
    if not args.skip_wire:
        if not EDGE_BIN.exists():
            log("edge binary missing; skipping wire rows")
        else:
            doc["rows"].append(bench_wire(0))
            doc["rows"].append(bench_wire(500))
    if not args.skip_device:
        doc["rows"].extend(bench_device())
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
