"""End-to-end engine bench: host presort + dispatch + fetch + unpermute.

bench.py measures the pure device loop (S fused steps, no host round
trips). This bench drives the PRODUCTION host path instead —
TpuEngine.decide_submit/decide_wait per batch — with a configurable
pipeline depth so host presort of batch i+1 overlaps device compute of
batch i, and reports:

- host-side cost per batch (presort + pad + unpermute, no device),
- e2e decisions/s at pipeline depth 1 (strict request/response) and
  depth N,
- the device-only reference rate for the same shapes.

With the chip co-located (PCIe fetch, ~0.1 ms) the depth-2 e2e rate is
the serving throughput ceiling; the host-side cost line is the half of
the claim that does not depend on the machine: host work per batch must
stay under the device's batch time.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    import jax

    import gubernator_tpu.core  # noqa: F401
    from gubernator_tpu.core.engine import TpuEngine
    from gubernator_tpu.core.store import StoreConfig

    B, KEYS, N_BATCHES = 16384, 100_000, 24
    eng = TpuEngine(
        StoreConfig(rows=16, slots=1 << 15), buckets=(B,)
    )
    rng = np.random.default_rng(42)
    zipf = rng.zipf(1.2, size=(N_BATCHES, B)) % KEYS
    key_hash = (
        (zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15))
        ^ np.uint64(0xDEADBEEFCAFEF00D)
    )
    hits = np.ones(B, np.int64)
    limit = rng.integers(10, 10_000, B)
    duration = np.full(B, 60_000, np.int64)
    gnp = np.zeros(B, bool)
    now0 = 1_700_000_000_000

    def submit(i):
        return eng.decide_submit(
            key_hash[i % N_BATCHES], hits, limit, duration,
            (zipf[i % N_BATCHES] % 2).astype(np.int32), gnp, now0 + i,
        )

    # warm: compile + first batches
    eng.decide_wait(submit(0))
    eng.decide_wait(submit(1))

    # host-side cost alone (presort+pad then unpermute), no device wait —
    # exactly the per-batch work decide_submit/decide_wait do around the
    # device call (native marshalling when built)
    from gubernator_tpu.core.engine import (
        _hn,
        pad_request_sorted,
        unpermute_responses,
    )

    t0 = time.monotonic()
    reps = 20
    from gubernator_tpu.core.kernels import PACKED_STATS

    fake_packed = np.zeros(4 * B + PACKED_STATS, np.int32)
    for i in range(reps):
        req, order = pad_request_sorted(
            (B,), eng.config.slots, key_hash[i % N_BATCHES], hits, limit,
            duration, (zipf[i % N_BATCHES] % 2).astype(np.int32), gnp,
        )
        if _hn is not None:
            _hn.unpermute_i32(
                fake_packed[: 4 * B].reshape(4, B), order, B
            )
        else:
            fake = np.zeros(B, np.int32)
            unpermute_responses(order, (fake, fake, fake, fake))
    host_us = (time.monotonic() - t0) / reps * 1e6
    log(f"host-side work: {host_us:.0f} us/batch (presort+pad+unpermute)")

    results = {"host_us_per_batch": round(host_us, 1)}
    for depth in (1, 2):
        t0 = time.monotonic()
        inflight = []
        done = 0
        for i in range(N_BATCHES):
            inflight.append(submit(i))
            if len(inflight) >= depth:
                eng.decide_wait(inflight.pop(0))
                done += 1
        while inflight:
            eng.decide_wait(inflight.pop(0))
            done += 1
        dt = time.monotonic() - t0
        rate = done * B / dt
        us = dt / done * 1e6
        log(f"e2e depth={depth}: {us:.0f} us/batch -> {rate/1e6:.2f} M/s")
        results[f"e2e_depth{depth}_Mps"] = round(rate / 1e6, 2)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
