"""Served-mesh throughput bench: does the serving tier keep up with the
mesh? (r2 verdict item 1.)

NOT a device measurement: the served rate on the chip is measured
end to end through a daemon's doors (chip_smoke.py proves the path;
the benchmark of ROADMAP S1 will time it). This script remains as the
CPU-mesh projection model and the prep-path comparison harness.

Runs on the virtual 8-device CPU mesh (no TPU needed): sustained
decisions/s through MeshEngine for

  blocking+numpy  — r2's serving shape: blocking decide_arrays with the
                    numpy marshal (prep and device costs ADD)
  blocking+native — r3 prep, still blocking
  pipelined+native— r3: decide_submit/decide_wait two-in-flight (the
                    DeviceBatcher discipline; prep and device OVERLAP)

then prints the projected v5e-8 served ceiling per prep-thread count,
combining the measured host prep with the v5e device time an r2 chip
run recorded (873us/32k/chip) — a model, labeled as such, not a
measurement of a served mesh.

One JSON line per row to stdout; chatter to stderr.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a CPU check by construction: the platform and the virtual device count
# are named before jax initializes (see tests/conftest.py)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

N = 32768
STEPS = 30


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _traffic():
    rng = np.random.default_rng(42)
    zipf = rng.zipf(1.2, size=N) % 100_000
    kh = (
        zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    ) ^ np.uint64(0xDEADBEEFCAFEF00D)
    return dict(
        key_hash=kh,
        hits=np.ones(N, np.int64),
        limit=rng.integers(10, 10_000, N),
        duration=np.full(N, 60_000, np.int64),
        algo=(zipf % 2).astype(np.int32),
        gnp=np.zeros(N, bool),
    )


def main():
    import jax

    import gubernator_tpu.core  # noqa: F401
    import gubernator_tpu.parallel.sharded as sh
    from gubernator_tpu.core.store import StoreConfig

    devs = jax.devices()
    assert len(devs) == 8, devs
    eng = sh.MeshEngine(
        StoreConfig(rows=16, slots=1 << 15),
        devices=devs,
        buckets=(64, 256, 1024, 4096, 16384, 32768),
    )
    a = _traffic()
    now = 1_700_000_000_000

    def run_blocking(label):
        eng.decide_arrays(now=now, **a)  # compile + warm
        t0 = time.perf_counter()
        for i in range(STEPS):
            eng.decide_arrays(now=now + i, **a)
        dt = time.perf_counter() - t0
        rate = N * STEPS / dt
        print(
            json.dumps(
                {"mode": label, "decisions_per_sec": round(rate, 0),
                 "us_per_batch": round(dt / STEPS * 1e6, 1)}
            ),
            flush=True,
        )
        return rate

    def run_pipelined(label):
        eng.decide_wait(eng.decide_submit(now=now, **a))  # warm
        t0 = time.perf_counter()
        prev = None
        for i in range(STEPS):
            h = eng.decide_submit(now=now + i, **a)
            if prev is not None:
                eng.decide_wait(prev)
            prev = h
        eng.decide_wait(prev)
        dt = time.perf_counter() - t0
        rate = N * STEPS / dt
        print(
            json.dumps(
                {"mode": label, "decisions_per_sec": round(rate, 0),
                 "us_per_batch": round(dt / STEPS * 1e6, 1)}
            ),
            flush=True,
        )
        return rate

    saved = sh._prep_native
    sh._prep_native = None
    try:
        run_blocking("blocking+numpy(r2)")
    finally:
        sh._prep_native = saved
    run_blocking("blocking+native")
    if saved is not None:
        run_pipelined("pipelined+native")

    # projected v5e-8 ceiling: measured host prep vs measured device time
    import gubernator_tpu.parallel.sharded as _sh

    ts = []
    for _ in range(20):
        t0 = time.perf_counter()
        _sh.pad_request_sharded(
            eng.sub_buckets, eng.config.slots, 8, a["key_hash"],
            a["hits"], a["limit"], a["duration"], a["algo"], a["gnp"],
            with_groups=True,
        )
        ts.append(time.perf_counter() - t0)
    prep_us = min(ts) * 1e6
    # v5e decide time by sub-batch size, measured on the real chip
    # (zipf-1.2 traffic, grouped, 16x32k store — r3 session, same
    # harness as bench.py): each mesh chip runs ONE sub-batch of
    # ~B/n_chips rows padded to its rung, all chips in parallel, so the
    # mesh step costs the sub-batch time, not the full-batch time.
    V5E_DECIDE_US = {
        4096: 323.7, 8192: 426.1, 12288: 509.0,
        16384: 657.6, 32768: 880.5,
    }
    # the flagship 32k zipf batch shards to B_sub=12288 on 8 chips
    device_us = V5E_DECIDE_US[12288]
    for t in (1, 2, 4, 8):
        # pipelined: served = B / max(prep/t, device) — prep phases
        # parallelize across t cores (sort+marshal are per-shard)
        ceiling = N / max(prep_us / t, device_us) * 1e6
        print(
            json.dumps(
                {"mode": f"projected-v5e8-prep-threads-{t}",
                 "model": "B/max(prep/T, sub_batch_device)",
                 "prep_us": round(prep_us / t, 1),
                 "device_us": device_us,
                 "decisions_per_sec": round(ceiling, 0)}
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
