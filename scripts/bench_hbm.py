"""Device microbenchs with a scalar-fetch barrier (dev tool).

Every measured program returns a scalar data-dependent on the final
state and the harness fetches it (4-byte transfer): the fetch cannot
complete before the fused loop has, and it also defeats dead-code
elimination. On the co-located chip jax.block_until_ready is a barrier
too (chip check, PR 21: it returned 843.5 ms into a loop that a
fetch-first run timed at 844.0 ms); bench.py logs the same check on
every run.

Measures, at the production store geometry [32768, 128] int32 (16 MiB):
- XLA elementwise pass over the store            (HBM copy floor)
- pallas identity sweep at several tile sizes    (pallas pipeline floor)
- XLA scatter-add of B=16384 sorted delta rows   (the production writeback)
- pallas sweep writeback                          (GUBER_WRITEBACK=sweep)
- XLA row gather of the same index stream        (the production lookup)
"""

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


S, REPS = 512, 5


def timeit(name, steps_fn, *args):
    import jax

    out = steps_fn(*args)
    carry, chk = out[0], out[1]
    float(chk)  # barrier
    ts = []
    for _ in range(REPS):
        t0 = time.monotonic()
        carry, chk = steps_fn(carry, *args[1:])
        float(chk)  # barrier: 4-byte fetch forces the whole loop
        ts.append(time.monotonic() - t0)
    us = min(ts) / S * 1e6
    log(f"{name:40s} {us:8.1f} us/step")
    return round(us, 1)


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    import gubernator_tpu.core  # noqa: F401
    from gubernator_tpu.core.pallas_sweep import _apply_inline

    buckets, B = 1 << 15, 16384
    rng = np.random.default_rng(5)
    data0 = rng.integers(-1000, 1000, (buckets, 128)).astype(np.int32)
    bkt = np.sort(rng.integers(0, buckets, B)).astype(np.int32)
    drow = np.zeros((B, 128), np.int32)
    run = 0
    for i in range(B):
        run = run + 1 if i and bkt[i] == bkt[i - 1] else 0
        w = run % 16
        drow[i, w * 8:(w + 1) * 8] = rng.integers(-5, 5, 8)

    results = {}

    def fused(body):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def steps(x, *args):
            x = lax.fori_loop(0, S, lambda i, x: body(x, *args), x)
            return x, x[0, 0]
        return steps

    # XLA elementwise
    results["xla_elementwise"] = timeit(
        "XLA x+1 (16 MiB rw)", fused(lambda x: x + 1), jnp.asarray(data0))

    # pallas identity sweeps
    def ident(tile):
        def kern(data_ref, out_ref):
            out_ref[:] = data_ref[:] + 1

        def apply(x):
            with jax.enable_x64(False):
                return pl.pallas_call(
                    kern,
                    out_shape=jax.ShapeDtypeStruct((buckets, 128), jnp.int32),
                    grid=(buckets // tile,),
                    in_specs=[pl.BlockSpec((tile, 128), lambda t: (t, 0),
                                           memory_space=pltpu.VMEM)],
                    out_specs=pl.BlockSpec((tile, 128), lambda t: (t, 0),
                                           memory_space=pltpu.VMEM),
                    input_output_aliases={0: 0},
                    compiler_params=pltpu.CompilerParams(
                        dimension_semantics=("arbitrary",)),
                )(x)
        return apply

    for tile in (128, 512, 2048, 8192):
        results[f"pallas_ident_t{tile}"] = timeit(
            f"pallas identity sweep tile={tile}",
            fused(ident(tile)), jnp.asarray(data0))

    d_bkt = jnp.asarray(bkt)
    d_drow = jnp.asarray(drow)

    results["xla_scatter"] = timeit(
        "XLA scatter-add B=16k sorted",
        fused(lambda x, b, d: x.at[b].add(d, indices_are_sorted=True)),
        jnp.asarray(data0), d_bkt, d_drow)

    results["pallas_sweep"] = timeit(
        "pallas sweep writeback",
        fused(lambda x, b, d: _apply_inline(x, b, d)),
        jnp.asarray(data0), d_bkt, d_drow)

    # gather feeding a cheap reduce so it can't be DCE'd; carry stays the
    # store so donation shapes match
    def gath(x, b):
        g = jnp.take(x, b, axis=0, indices_are_sorted=True)
        return x + jnp.sum(g, dtype=jnp.int32)

    results["xla_gather"] = timeit(
        "XLA row gather B=16k sorted (+reduce)",
        fused(gath), jnp.asarray(data0), d_bkt)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
