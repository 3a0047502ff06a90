"""Benchmark: rate-limit decisions/sec on one chip.

Prints exactly ONE JSON line to stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "device": {"platform": ..., "kind": ..., "count": N}}
Everything else goes to stderr. Exits non-zero when JAX finds no TPU,
unless another platform was asked for by name (JAX_PLATFORMS=cpu): a
device number is never printed from a fallback.

Config mirrors BASELINE.md's flagship single-chip target (config 2: mixed
token+leaky traffic over 100k keys against the slot store in HBM). The
measured program is the production decide kernel (core/kernels.py) stepped
S times inside one lax.fori_loop — the store threads through the loop carry
exactly as it does batch-over-batch in serving, with zero host involvement,
so the number is pure device decision throughput. vs_baseline compares
against the reference's published single-node client-facing rate of
~2,000 req/s (reference README.md:94-99; BASELINE.md).

MEASUREMENT NOTES (r3):
- The accumulator reduces EVERY response field (status + a checksum of
  remaining/reset_time/limit). Hygiene, not a correction: a status-only
  reduction would let XLA dead-code-eliminate the other fields' math if
  it ever grew expensive; today the measured difference is ~0.3%
  (back-to-back A/B), far inside run variance.
- Conclusions about code changes need BACK-TO-BACK A/Bs in one run
  on one machine (parent, change, change, parent): the r3 sessions saw
  the same binary measure 34.1-40.7M across hours.
"""

import json
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    import gubernator_tpu.core  # noqa: F401  (enables x64)
    from gubernator_tpu.core.kernels import BatchRequest, decide_presorted
    from gubernator_tpu.core.store import (
        StoreConfig,
        group_sort_key_np,
        new_store,
    )

    from gubernator_tpu.jaxenv import (
        device_summary,
        enable_compile_cache,
        require_tpu,
    )

    enable_compile_cache()
    require_tpu("bench.py")
    device = device_summary()
    log(f"device: {device['platform']} ({device['kind']}) x {device['count']}")

    import os

    B = int(os.environ.get("GUBER_DEVICE_BATCH_LIMIT", "32768"))
    # requests per batch (reference hard cap is 1000/RPC; the
    # device batch coalesces many RPCs, serve/batcher.py). Larger batches
    # amortize the gather/scatter fixed costs: measured 37.5M @ 32k with
    # the b/4 group rung (~0.87ms/batch — inside the serving latency
    # envelope). 32k keeps the flagship number consistent with the p99
    # < 1ms serving story; the override rides the SAME env knob the
    # serving tier uses (GUBER_DEVICE_BATCH_LIMIT), so throughput-mode
    # configs (e.g. 131072 on a big store) bench at their serving depth.
    R = 8  # distinct pre-staged batches cycled through. The per-step
    # i%R dynamic-slice of the staged [R, B] arrays costs ~145us/batch
    # (measured r3: R=1 runs 716us/batch vs R=8's 861) — kept
    # DELIBERATELY: each step must consume a fresh input buffer the way
    # serving consumes each batch's host transfer, and with R=1 XLA can
    # hoist loop-invariant key-derived work (bucket/fingerprint of an
    # unchanging key array), overstating steady-state throughput.
    S = 1024  # decide steps fused into one device program: the loop
    # times the device alone, with no per-step host dispatch in it
    KEYS = 100_000
    # 16 ways x 32k buckets: 524k entries capacity, ~20% load at 100k
    # keys (the guidance ceiling is ~50%). ways=16 makes each bucket row
    # exactly 128 lanes (the native TPU vector width) — the fast path for
    # the whole-row gather and delta-add scatter; the 16 MiB store also
    # sweeps faster than wider geometries
    ROWS, SLOTS = 16, 1 << 15

    rng = np.random.default_rng(42)
    store = new_store(StoreConfig(rows=ROWS, slots=SLOTS))

    # mixed token+leaky traffic, zipf-ish key popularity over 100k keys.
    # Batches are presorted by (bucket, fingerprint) on the host — in
    # serving that is one numpy argsort per batch, pipelined with device
    # compute (engine.pad_request_sorted) — so the measured program is
    # the production decide_presorted kernel.
    zipf = rng.zipf(1.2, size=(R, B)) % KEYS
    key_hash = (
        (zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15))
        ^ np.uint64(0xDEADBEEFCAFEF00D)
    )
    limit = rng.integers(10, 10_000, (R, B))
    # presort + group structure with the SHIPPED fast path (native radix,
    # core/engine.py) — the same code serving runs per batch; numpy
    # argsort kept as the cross-check + fallback
    from gubernator_tpu.core.engine import (
        _np_presort,
        _presort,
        _presort_grouped,
        choose_bucket,
        group_rungs,
    )

    t_sort = time.monotonic()
    grouped = [_presort_grouped(key_hash[r], SLOTS) for r in range(R)]
    dt_native = (time.monotonic() - t_sort) / R * 1e6
    order = np.stack([g[0] for g in grouped])
    t_sort = time.monotonic()
    order_np = np.argsort(
        group_sort_key_np(key_hash, SLOTS), axis=1, kind="stable"
    )
    dt_np = (time.monotonic() - t_sort) / R * 1e6
    assert (order == order_np).all() or _presort is _np_presort
    key_hash = np.take_along_axis(key_hash, order, axis=1)
    zipf = np.take_along_axis(zipf, order, axis=1)
    limit = np.take_along_axis(limit, order, axis=1)
    log(
        f"host presort+groups: native {dt_native:.0f} us/batch (numpy "
        f"argsort alone {dt_np:.0f}) — pipelined with device compute in "
        "serving"
    )

    # group structure (store I/O runs at unique-key granularity): one
    # shared G rung across the staged batches, assembled per batch by the
    # same helper serving uses (engine.build_groups)
    from gubernator_tpu.core.engine import build_groups

    G_max = max(g[3] for g in grouped)
    G = choose_bucket(group_rungs(B), G_max)
    log(f"unique-key groups: max {G_max}/{B} per batch -> G rung {G}")
    per_batch = [
        build_groups(key_hash[r], gid, lp, g_real, B, B, G)
        for r, (_o, gid, lp, g_real) in enumerate(grouped)
    ]
    groups = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs)), *per_batch
    )

    reqs = BatchRequest(
        key_hash=jnp.asarray(key_hash),
        hits=jnp.ones((R, B), jnp.int32),
        limit=jnp.asarray(limit, jnp.int32),
        duration=jnp.full((R, B), 60_000, jnp.int32),
        algo=jnp.asarray(zipf % 2, jnp.int32),  # per-key stable algorithm
        gnp=jnp.zeros((R, B), bool),
        valid=jnp.ones((R, B), bool),
    )
    t0 = jnp.int32(1000)  # engine-ms (epoch-relative; see core.store)

    def steps(store, reqs, groups):
        def body(i, carry):
            store, over, chk = carry
            r = jax.tree.map(lambda x: x[i % R], reqs)
            g = jax.tree.map(lambda x: x[i % R], groups)
            now = t0 + i  # clock advances 1ms per batch
            store, resp, _ = decide_presorted(store, r, now, g)
            over = over + jnp.sum(resp.status, dtype=jnp.int32)
            # consume EVERY response field: a status-only reduction lets
            # XLA dead-code-eliminate the remaining/reset/limit math and
            # overstate serving throughput (wrap-safe int32 checksum)
            chk = chk + jnp.sum(
                resp.remaining ^ resp.reset_time ^ resp.limit,
                dtype=jnp.int32,
            )
            return store, over, chk

        return lax.fori_loop(
            0, S, body,
            (store, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)),
        )

    stepped = jax.jit(steps, donate_argnums=(0,))

    log("compiling...")
    t = time.monotonic()
    store, acc, chk = stepped(store, reqs, groups)
    int(acc), int(chk)  # fetch the loop-dependent scalars: the barrier
    log(f"compile+first run: {time.monotonic() - t:.1f}s")

    times = []
    for rep in range(5):
        t = time.monotonic()
        store, acc, chk = stepped(store, reqs, groups)
        # on the co-located chip block_until_ready IS a barrier (chip
        # check, PR 21: it returned 843.5 ms into a loop that a
        # fetch-first run timed at 844.0 ms). The scalars are fetched
        # as well, and the fetch after it logged, so every run shows it
        # held: fetch_after_ready is a couple of ms at most, not the
        # loop's time
        jax.block_until_ready((acc, chk))
        t_ready = time.monotonic() - t
        over, _ = int(acc), int(chk)
        dt = time.monotonic() - t
        times.append(dt)
        log(
            f"rep {rep}: ready after {t_ready*1000:.1f} ms, "
            f"fetch_after_ready {(dt - t_ready)*1e6:.0f} us"
        )
        log(
            f"rep {rep}: {dt*1000:.1f} ms for {S} batches of {B} "
            f"-> {S*B/dt/1e6:.2f} M decisions/s "
            f"(over_limit={over})"
        )

    best = min(times)
    value = S * B / best
    per_batch_us = best / S * 1e6
    log(f"best: {value/1e6:.2f} M decisions/s, {per_batch_us:.0f} us/batch")

    baseline = 2000.0  # reference production node: >2,000 req/s
    print(
        json.dumps(
            {
                "metric": "rate_limit_decisions_per_sec_per_chip",
                "value": round(value, 1),
                "unit": "decisions/s",
                "vs_baseline": round(value / baseline, 1),
                "device": device,
            }
        ),
        flush=True,
    )


if __name__ == "__main__":
    main()
