"""BASELINE.json benchmark scenarios (configs 1-5), one JSON line each.

The flagship single-chip number lives in bench.py (the driver runs it);
this suite reproduces the full baseline matrix on whatever devices are
visible:

  1 token_1k    Token-bucket, 1k unique keys, single chip
                (reference benchmark_test.go's CPU-baseline shape)
  2 leaky_100k  Leaky-bucket, 100k keys, single chip
  3 global_mesh GLOBAL behavior on a key-sharded device mesh: replica
                reads + one psum gossip step per interval
                (reference global.go's gossip -> collective)
  4 zipf_10m    Zipfian 10M-key heavy-hitter workload, 1 GiB store,
                single chip (HLL/topk observability runs host-side in
                serving and is benched in its own tests)
  5 mixed_shard Mixed token+leaky at v5e-32 scale: each chip owns
                100M/32 ~= 3.1M keys of a mesh-sharded store; this runs
                the per-chip slice, which is the number that multiplies
                by the mesh size (decisions combine with one psum,
                measured in scenario 3)

Run: python scripts/bench_scenarios.py [--scenario N] [--cpu-mesh M]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


R = 8  # distinct pre-staged batches cycled through every scenario
S_DEFAULT = 2048  # steps fused per device call: the loop times the
# device alone, with no per-step host dispatch in it (see bench.py)


def _zipf_key_hashes(key_space, B, rng=None):
    """(zipf ids [R,B], key hashes [R,B]) — the one zipf key recipe every
    scenario shares, now factored to cli/keystreams.py (r13) so this
    sweep and the serving benches cannot drift apart (bit-identical to
    the historical inline recipe for any (key_space, size, seed))."""
    from gubernator_tpu.cli import keystreams

    zipf = keystreams.zipf_ids(key_space, (R, B), rng)
    return zipf, keystreams.hash_ids(zipf)


def _scenario_steps():
    """Fused steps per device call: full depth on real chips, a short
    functional loop on the virtual CPU mesh."""
    import jax

    return S_DEFAULT if jax.devices()[0].platform == "tpu" else 32


def _zipf_batches(
    key_space, buckets, B, rng=None, gnp=False, algo_mode="mixed", limit=None
):
    """(BatchRequest [R,B], BatchGroups [R,...], sorted zipf ids):
    presorted zipf traffic + duplicate-key group structure — the one
    key/limit/sort recipe every scenario shares (same helpers serving
    uses: engine._presort_grouped / build_groups)."""
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.core.engine import (
        _presort_grouped,
        build_groups,
        choose_bucket,
        group_rungs,
    )
    from gubernator_tpu.core.kernels import BatchRequest

    rng = rng or np.random.default_rng(42)
    zipf, key_hash = _zipf_key_hashes(key_space, B, rng)
    limit = np.full((R, B), limit) if limit else rng.integers(
        10, 10_000, (R, B)
    )
    grouped = [_presort_grouped(key_hash[r], buckets) for r in range(R)]
    order = np.stack([g[0] for g in grouped])
    key_hash = np.take_along_axis(key_hash, order, axis=1)
    zipf_s = np.take_along_axis(zipf, order, axis=1)
    limit = np.take_along_axis(limit, order, axis=1)
    if algo_mode == "token":
        algo = np.zeros((R, B), np.int32)
    elif algo_mode == "leaky":
        algo = np.ones((R, B), np.int32)
    else:
        algo = (zipf_s % 2).astype(np.int32)
    G = choose_bucket(group_rungs(B), max(g[3] for g in grouped))
    groups = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs)),
        *[
            build_groups(key_hash[r], gid, lp, g_real, B, B, G)
            for r, (_o, gid, lp, g_real) in enumerate(grouped)
        ],
    )
    return BatchRequest(
        key_hash=jnp.asarray(key_hash),
        hits=jnp.ones((R, B), jnp.int32),
        limit=jnp.asarray(limit, jnp.int32),
        duration=jnp.full((R, B), 60_000, jnp.int32),
        algo=jnp.asarray(algo),
        gnp=jnp.full((R, B), gnp, bool),
        valid=jnp.ones((R, B), bool),
    ), groups, zipf_s


def _time_steps(stepped, store, reqs, groups, B, S, reps=3):
    """Best-of-reps decisions/s for a jitted S-step loop. The loop's
    scalar accumulator is FETCHED as the barrier (see bench.py)."""
    store, acc = stepped(store, reqs, groups)
    int(acc)
    best = float("inf")
    for _ in range(reps):
        t = time.monotonic()
        store, acc = stepped(store, reqs, groups)
        int(acc)  # hard barrier
        best = min(best, time.monotonic() - t)
    return S * B / best


def _measure_kernel(
    store_cfg, key_space, algo_mode, B=16384, S=None, reps=3
):
    """Decisions/s for the presorted kernel over `key_space` keys."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from gubernator_tpu.core.kernels import decide_presorted
    from gubernator_tpu.core.store import new_store

    S = S if S is not None else _scenario_steps()
    store = new_store(store_cfg)
    reqs, groups, _ = _zipf_batches(
        key_space, store_cfg.slots, B, algo_mode=algo_mode
    )
    t0 = jnp.int32(1000)

    def steps(store, reqs, groups):
        def body(i, carry):
            store, acc = carry
            r = jax.tree.map(lambda x: x[i % R], reqs)
            g = jax.tree.map(lambda x: x[i % R], groups)
            store, resp, _ = decide_presorted(store, r, t0 + i, g)
            # consume EVERY response field (status-only reductions let
            # XLA DCE the remaining/reset/limit math — measured ~10%
            # inflation; same fix as bench.py r3)
            acc = acc + jnp.sum(resp.status, dtype=jnp.int32) + jnp.sum(
                resp.remaining ^ resp.reset_time ^ resp.limit,
                dtype=jnp.int32,
            )
            return store, acc

        return lax.fori_loop(0, S, body, (store, jnp.zeros((), jnp.int32)))

    stepped = jax.jit(steps, donate_argnums=(0,))
    return _time_steps(stepped, store, reqs, groups, B, S, reps)


def scenario_token_1k():
    from gubernator_tpu.core.store import StoreConfig

    v = _measure_kernel(StoreConfig(rows=16, slots=1 << 12), 1_000, "token")
    return "token_bucket_1k_keys_single_chip", v


def scenario_leaky_100k():
    from gubernator_tpu.core.store import StoreConfig

    v = _measure_kernel(
        StoreConfig(rows=16, slots=1 << 15), 100_000, "leaky"
    )
    return "leaky_bucket_100k_keys_single_chip", v


def scenario_global_mesh():
    """GLOBAL over a key-sharded mesh, fused on-device: the host routes
    each batch's rows to their owner chips (batch-axis sharding — each
    chip evaluates only its ~B/n rows), every step answers
    replica/owner reads against the chip's store shard, and every 8th
    step runs the gossip collective (owner peek + psum broadcast +
    replica upsert), i.e. a sync interval of 8 batch windows (reference
    global.go's async aggregate -> owner -> broadcast loop as
    collectives)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from gubernator_tpu.core.engine import choose_bucket
    from gubernator_tpu.core.kernels import decide_presorted
    from gubernator_tpu.core.store import StoreConfig, new_store
    from gubernator_tpu.parallel.sharded import (
        _shard_sync_globals,
        owner_of_np,
        pad_request_sharded,
        sub_batch_ladder,
    )

    devs = jax.devices()
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("shard",))
    cfg = StoreConfig(rows=16, slots=1 << 13)

    B, KEYS = 16384, 100_000
    S = _scenario_steps()
    # token-only GLOBAL replica-read traffic, fixed limit=1000 keeps
    # this metric comparable across runs
    _zipf, key_hash = _zipf_key_hashes(KEYS, B)
    # one shared per-shard rung across the staged batches
    max_count = max(
        int(np.bincount(owner_of_np(key_hash[r], n), minlength=n).max())
        for r in range(R)
    )
    ladder = sub_batch_ladder((64, 256, 1024, 4096, 16384))
    rung = choose_bucket(ladder, max_count)
    ones = np.ones(B, np.int64)
    # with_groups: the serving mesh path (MeshEngine.decide_arrays) runs
    # all store I/O at unique-key granularity; the scenario must measure
    # the same kernel, not the 2x-slower ungrouped compat path. Stage
    # twice: the first pass learns each batch's natural G rung, the
    # second pins every batch to the shared max so the stacked
    # BatchGroups shapes line up (padding conventions stay inside
    # pad_request_sharded / engine.build_groups — the single source of
    # truth).
    def stage(r, G=None):
        return pad_request_sharded(
            (rung,), cfg.slots, n, key_hash[r], ones, ones * 1000,
            ones * 60_000, np.zeros(B, np.int32), np.ones(B, bool),
            with_groups=True, group_rung=G,
        )

    G_shared = max(stage(r)[3].leader_pos.shape[-1] for r in range(R))
    staged = [stage(r, G_shared) for r in range(R)]
    # [R, n, ...] -> [n, R, ...]: shard axis leads for P("shard")
    reqs = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs).swapaxes(0, 1)),
        *[s[0] for s in staged],
    )
    groups = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs).swapaxes(0, 1)),
        *[s[3] for s in staged],
    )
    # gossip keys must honor decide_presorted's (bucket, fp) sort
    # contract — raw-value np.sort would hand unsorted bucket streams to
    # indices_are_sorted gathers (silent corruption on TPU)
    from gubernator_tpu.core.store import group_sort_key_np

    g_pick = key_hash[0, :1024]
    g_kh = jnp.asarray(
        g_pick[np.argsort(group_sort_key_np(g_pick, cfg.slots),
                          kind="stable")]
    )
    t0 = jnp.int32(1000)

    def body_all(store, reqs, aux):
        groups, g_kh = aux

        def body(i, carry):
            store, acc = carry
            r = jax.tree.map(lambda x: x[0, i % R], reqs)
            g = jax.tree.map(lambda x: x[0, i % R], groups)
            st, resp, _ = decide_presorted(
                jax.tree.map(lambda x: x[0], store), r, t0 + i, g
            )
            store = jax.tree.map(lambda x: x[None], st)

            def do_sync(store):
                store2, _resp = _shard_sync_globals(
                    store,
                    g_kh,
                    jnp.full(1024, 1000, jnp.int32),
                    jnp.full(1024, 60_000, jnp.int32),
                    jnp.zeros(1024, jnp.int32),
                    jnp.ones(1024, bool),
                    t0 + i,
                    n_shards=n,
                )
                return store2

            store = lax.cond(i % 8 == 7, do_sync, lambda s: s, store)
            # full-consumption checksum (see single-device steps above)
            acc = acc + jnp.sum(resp.status, dtype=jnp.int32) + jnp.sum(
                resp.remaining ^ resp.reset_time ^ resp.limit,
                dtype=jnp.int32,
            )
            return store, acc

        store, acc = lax.fori_loop(
            0, S, body, (store, jnp.zeros((), jnp.int32))
        )
        return store, jax.lax.psum(acc, "shard")

    stepped = jax.jit(
        jax.shard_map(
            body_all,
            mesh=mesh,
            in_specs=(P("shard"), P("shard"), (P("shard"), P())),
            out_specs=(P("shard"), P()),
            check_vma=False,  # psum output IS replicated
        ),
        donate_argnums=(0,),
    )

    base = new_store(cfg)
    sharding = NamedSharding(mesh, P("shard"))
    store = jax.tree.map(
        lambda x: jax.device_put(
            jnp.broadcast_to(x[None], (n,) + x.shape), sharding
        ),
        base,
    )
    return (
        f"global_mesh_{n}dev_psum_gossip",
        _time_steps(stepped, store, reqs, (groups, g_kh), B, S),
    )


def scenario_zipf_10m():
    from gubernator_tpu.core.store import StoreConfig

    # 2^21 buckets x 16 ways = 33.5M entries (1 GiB), ~30% load at 10M
    # keys. Kernel-level row; the SERVING-path twin (env knobs, deep
    # ladder, store auto-sizing) is `cli/bench_serving.py --scenario
    # zipf10m` -> BENCH_SCENARIOS_r6.json
    v = _measure_kernel(
        StoreConfig(rows=16, slots=1 << 21), 10_000_000, "mixed"
    )
    return "zipf_10m_keys_single_chip_1gib_store", v


def scenario_mixed_shard():
    from gubernator_tpu.core.store import StoreConfig

    # per-chip slice of the v5e-32 config: 100M/32 keys against a
    # 2^19-bucket shard (8.4M entries, 256 MiB per chip)
    v = _measure_kernel(
        StoreConfig(rows=16, slots=1 << 19), 3_125_000, "mixed"
    )
    return "mixed_100m_keys_v5e32_per_chip_slice", v


def scenario_throughput_mode():
    """The flagship workload (bench.py: mixed token+leaky, 100k zipf
    keys) at B=131072 — trade batch latency (~3ms windows) for peak
    sustained throughput. Committed so the README's throughput-mode row
    traces to an artifact instead of a one-off run (r4 verdict weak #4)."""
    from gubernator_tpu.core.store import StoreConfig

    v = _measure_kernel(
        StoreConfig(rows=16, slots=1 << 15), 100_000, "mixed",
        B=131_072, S=max(1, _scenario_steps() // 8),
    )
    return "throughput_mode_100k_keys_b131072_single_chip", v


SCENARIOS = {
    1: scenario_token_1k,
    2: scenario_leaky_100k,
    3: scenario_global_mesh,
    4: scenario_zipf_10m,
    5: scenario_mixed_shard,
    6: scenario_throughput_mode,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", type=int, default=0, help="0 = all")
    ap.add_argument(
        "--cpu-mesh",
        type=int,
        default=0,
        help="force an N-virtual-device CPU mesh (functional check of the "
        "multi-chip path; perf numbers only mean anything on real chips)",
    )
    args = ap.parse_args()

    import jax

    if args.cpu_mesh:
        # both settings must land before the first device use
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_mesh)

    import gubernator_tpu.core  # noqa: F401
    from gubernator_tpu.jaxenv import (
        device_summary,
        enable_compile_cache,
        require_tpu,
    )

    enable_compile_cache()
    # --cpu-mesh names the CPU; otherwise no TPU is an error, not a
    # smaller loop on whatever JAX fell back to
    require_tpu(
        "scripts/bench_scenarios.py", "cpu" if args.cpu_mesh else ""
    )
    device = device_summary()

    todo = [args.scenario] if args.scenario else sorted(SCENARIOS)
    for n in todo:
        name, value = SCENARIOS[n]()
        print(
            json.dumps(
                {
                    "metric": name,
                    "value": round(value, 1),
                    "unit": "decisions/s",
                    "vs_baseline": round(value / 2000.0, 1),
                    "device": device,
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
