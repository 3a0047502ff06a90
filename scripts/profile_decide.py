"""Microprofile of decide-kernel cost structure on the real chip (dev tool).

Median-of-reps with S fused steps per dispatch (dispatch overhead <2% of
the measurement). Measures the FLAGSHIP bench configuration (B=32768 with
host-computed unique-key groups, 16 ways x 32k buckets — bench.py) and
decomposes it: full kernel, kernel with the writeback scatter DCE'd, the
isolated [G]-row gather/scatter shapes, and the same batch at alternative
G rungs (sizing the group-ladder padding waste).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

S, REPS = 512, 5


def bench(name, make_f, *args):
    import jax

    try:
        f = make_f(S)
        out = f(*args)
        jax.block_until_ready(out)
        times = []
        for _ in range(REPS):
            t = time.monotonic()
            out = f(*args)
            jax.block_until_ready(out)
            times.append(time.monotonic() - t)
        med = sorted(times)[len(times) // 2]
        print(f"{name:44s} {med/S*1e6:8.1f} us/step", file=sys.stderr)
        return med / S * 1e6
    except Exception as e:  # keep profiling the rest
        print(f"{name:44s} FAILED {type(e).__name__}: {str(e)[:90]}",
              file=sys.stderr)
        return None


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    import gubernator_tpu.core  # noqa: F401
    from gubernator_tpu.core.engine import _presort_grouped, build_groups
    from gubernator_tpu.core.kernels import BatchRequest, decide_presorted
    from gubernator_tpu.core.store import LANES, StoreConfig, new_store

    B = 32768
    WAYS, BUCKETS = 16, 1 << 15  # the flagship geometry (bench.py)
    rng = np.random.default_rng(42)
    store = new_store(StoreConfig(rows=WAYS, slots=BUCKETS))
    zipf = rng.zipf(1.2, size=B) % 100_000
    key_hash = (
        (zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15))
        ^ np.uint64(0xDEADBEEFCAFEF00D)
    )
    order, gid, lp, G_real = _presort_grouped(key_hash, BUCKETS)
    key_hash = key_hash[order]
    print(f"B={B} unique-key groups G_real={G_real}", file=sys.stderr)

    req = BatchRequest(
        key_hash=jnp.asarray(key_hash),
        hits=jnp.ones(B, jnp.int32),
        limit=jnp.full(B, 1000, jnp.int32),
        duration=jnp.full(B, 60_000, jnp.int32),
        algo=jnp.asarray(zipf[order] % 2, jnp.int32),
        gnp=jnp.zeros(B, bool),
        valid=jnp.ones(B, bool),
    )
    now0 = jnp.int32(1000)

    def mk_loop(body, groups):
        del groups  # passed through bench args

        def make_f(S):
            @jax.jit
            def f(store, req, groups):
                def b(i, c):
                    s, acc = c
                    return body(i, s, acc, req, groups)

                return lax.fori_loop(
                    0, S, b, (store, jnp.zeros((), jnp.int32))
                )

            return f

        return make_f

    def full_body(i, s, acc, req, groups):
        s2, r, _ = decide_presorted(s, req, now0 + i, groups)
        return s2, acc + r.status.sum().astype(jnp.int32)

    def dce_body(i, s, acc, req, groups):
        s2, r, _ = decide_presorted(s, req, now0 + i, groups)
        return s, acc + r.status.sum().astype(jnp.int32)

    for G in (12288, 8192, -(-G_real // 128) * 128):
        if G < G_real:
            continue
        groups = jax.tree.map(
            jnp.asarray, build_groups(key_hash, gid, lp, G_real, B, B, G)
        )
        bench(
            f"decide grouped G={G:5d} (full)", mk_loop(full_body, groups),
            store, req, groups,
        )
        bench(
            f"decide grouped G={G:5d} [writeback DCE'd]",
            mk_loop(dce_body, groups), store, req, groups,
        )

    # ungrouped compat path (G == B on device)
    def full_nog(i, s, acc, req, groups):
        s2, r, _ = decide_presorted(s, req, now0 + i, None)
        return s2, acc + r.status.sum().astype(jnp.int32)

    bench("decide ungrouped (device G==B)", mk_loop(full_nog, None),
          store, req, None)

    # isolated transfer shapes at the [G] granularity: the kernel's real
    # access pattern is the G_real unique group-leader rows spread over
    # all buckets (taking the first G of the sorted duplicated stream
    # would measure a denser, range-truncated pattern)
    G = -(-G_real // 128) * 128
    rows_np = np.sort(
        np.resize(key_hash[np.minimum(lp[:G_real], B - 1)] % BUCKETS, G)
    ).astype(np.int32)
    row_dup = jnp.asarray(rows_np)
    vals = jnp.ones((G, WAYS * LANES), jnp.int32)
    dense = jnp.zeros((BUCKETS, WAYS * LANES), jnp.int32)

    def mk2(body):
        def make_f(S):
            @jax.jit
            def f(d):
                return lax.fori_loop(0, S, body, d)

            return f

        return make_f

    def sc_add(i, d):
        return d.at[row_dup].add(
            vals + d[0, 0], mode="drop", indices_are_sorted=True
        )

    def g128(i, d):
        g = jnp.take(d, row_dup, axis=0, indices_are_sorted=True)
        return d.at[row_dup].add(g, mode="drop", indices_are_sorted=True)

    bench(f"[G={G},128] scatter-add sorted", mk2(sc_add), dense)
    bench(f"[G={G},128] gather + scatter-add", mk2(g128), dense)


if __name__ == "__main__":
    main()
