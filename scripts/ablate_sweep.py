"""Ablation microbench for the pallas sweep writeback: isolate per-step
cost of (a) the bare store sweep (tile in->out copy), (b) + chunk DMA,
(c) + one-hot matmul compute, at several TILE_ROWS. Run on real TPU.
Diagnostic script — not part of the product surface.
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


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    import gubernator_tpu.core  # noqa: F401

    buckets, B, S = 1 << 15, 16384, 256
    rng = np.random.default_rng(5)
    data = rng.integers(-2**31, 2**31 - 1, (buckets, 128), dtype=np.int64
                        ).astype(np.int32)
    bkt = np.sort(rng.integers(0, buckets, B)).astype(np.int32)
    comb = np.concatenate(
        [rng.integers(-1000, 1000, (B, 128)).astype(np.int32),
         np.repeat(bkt[:, None], 128, axis=1)], axis=1)

    def make(tile_rows, chunk, mode):
        ntiles = buckets // tile_rows

        def kernel(bounds_ref, data_ref, comb_ref, out_ref, comb_s, sem):
            t = pl.program_id(0)
            nt = pl.num_programs(0)
            lo = bounds_ref[t]
            hi = bounds_ref[t + 1]
            tile_base = t * tile_rows
            slot = lax.rem(t, 2)
            acc0 = data_ref[:]
            if mode == "copy":
                out_ref[:] = acc0
                return

            def first_dma(tt, sl):
                lo8 = bounds_ref[tt] // 8
                s8 = jnp.minimum(lo8, (B - chunk) // 8)
                return pltpu.make_async_copy(
                    comb_ref.at[pl.ds(s8 * 8, chunk), :],
                    comb_s.at[sl], sem.at[sl])

            @pl.when(t == 0)
            def _():
                first_dma(0, 0).start()

            @pl.when(t + 1 < nt)
            def _():
                first_dma(t + 1, 1 - slot).start()

            first_dma(t, slot).wait()
            if mode == "dma":
                out_ref[:] = acc0 + comb_s[slot, 0, 0]
                return

            lo8 = lo // 8
            start = jnp.minimum(lo8, (B - chunk) // 8) * 8
            ch = comb_s[slot]
            d = ch[:, :128]
            gidx = start + lax.broadcasted_iota(jnp.int32, (chunk, 128), 0)
            fresh = gidx >= lo8 * 8
            row_ids = lax.broadcasted_iota(jnp.int32, (chunk, 128), 1)
            contract = (((0,), (0,)), ((), ()))
            nblk = tile_rows // 128
            parts = ((d & 0xFF, 0), ((d >> 8) & 0xFF, 8),
                     ((d >> 16) & 0xFF, 16), (d >> 24, 24))
            fparts = [(p.astype(jnp.float32), s) for p, s in parts]
            adds = []
            for blk in range(nblk):
                rel = ch[:, 128:] - (tile_base + blk * 128)
                onehot = ((rel == row_ids) & fresh).astype(jnp.float32)
                add = None
                for p, shift in fparts:
                    r = lax.dot_general(
                        onehot, p, contract,
                        preferred_element_type=jnp.float32,
                    ).astype(jnp.int32)
                    r = r << shift
                    add = r if add is None else add + r
                adds.append(add)
            total = adds[0] if nblk == 1 else jnp.concatenate(adds, axis=0)
            out_ref[:] = acc0 + total

        def apply(x, comb_arr):
            bounds = jnp.searchsorted(
                jnp.asarray(bkt),
                jnp.arange(ntiles + 1, dtype=jnp.int32) * tile_rows,
                side="left").astype(jnp.int32)
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(ntiles,),
                in_specs=[
                    pl.BlockSpec((tile_rows, 128), lambda t, b: (t, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tile_rows, 128), lambda t, b: (t, 0),
                                       memory_space=pltpu.VMEM),
                scratch_shapes=[pltpu.VMEM((3, chunk, 256), jnp.int32),
                                pltpu.SemaphoreType.DMA((3,))])
            with jax.enable_x64(False):
                return pl.pallas_call(
                    kernel, out_shape=jax.ShapeDtypeStruct(
                        (buckets, 128), jnp.int32),
                    grid_spec=grid_spec, input_output_aliases={1: 0},
                    compiler_params=pltpu.CompilerParams(
                        dimension_semantics=("arbitrary",)),
                )(bounds, x, comb_arr)
        return apply

    d_comb = jnp.asarray(comb)
    results = {}
    for tile_rows, chunk in ((128, 128), (256, 256), (512, 512),
                             (1024, 1024)):
        for mode in ("copy", "dma", "full"):
            if mode != "full" and tile_rows != 128:
                continue
            fn = make(tile_rows, chunk, mode)
            try:  # trace once outside the loop for a clean error site
                jax.jit(fn).lower(jnp.asarray(data), d_comb)
            except Exception as e:
                log(f"TILE_ROWS={tile_rows} {mode}: TRACE FAIL {e}")
                continue

            @functools.partial(jax.jit, donate_argnums=(0,))
            def steps(x, comb_arr, fn=fn):
                x = lax.fori_loop(
                    0, S, lambda i, x: fn(x, comb_arr), x)
                # loop-dependent scalar: fetching it is the barrier
                # (see bench.py)
                return x, x[0, 0]

            x = jnp.asarray(data)
            x, acc = steps(x, d_comb)
            int(acc)
            ts = []
            for _ in range(4):
                t0 = time.monotonic()
                x, acc = steps(x, d_comb)
                int(acc)
                ts.append(time.monotonic() - t0)
            us = min(ts) / S * 1e6
            results[f"T{tile_rows}_{mode}"] = round(us, 1)
            log(f"TILE_ROWS={tile_rows} {mode}: {us:.1f} us/step")
    print(json.dumps(results))


if __name__ == "__main__":
    main()
