"""Pallas store-sweep writeback: apply delta rows with DMA + MXU matmuls.

The XLA scatter that applies the decide kernel's delta rows costs ~300us
at B=16k on v5e — ~15x off the HBM bandwidth bound for the 16 MiB it
actually moves. (r5 device-trace finding: current XLA lowers this
scatter as a FULL-TABLE pass — 51us at 16 MiB, 3324us at 1 GiB, i.e.
read+write of the whole table at ~650 GB/s regardless of update count;
see scripts/profile_zipf10m.py; a hand-pipelined sparse pallas scatter
was a measured dead end — 565 ns/tile, scalar-core DMA-issue bound.)
This kernel instead SWEEPS the whole store once per batch:

  for each tile of TILE_ROWS bucket rows (grid):  [Mosaic pipelines tiles]
    for each chunk of up to CHUNK update rows whose (sorted) bucket falls
    in the tile (dynamic range via scalar-prefetched searchsorted bounds):
      DMA the chunk's combined rows HBM -> VMEM
      M[c, r] = 1.0 where chunk row c targets tile row r   (one-hot)
      tile += M^T @ chunk_deltas                            (MXU)

Update rows arrive as ONE combined int32[B, 256] array: lanes 0-127 are
the delta row, lanes 128-255 replicate the row's bucket id — Mosaic DMA
slices must be whole 128-lane groups, so shipping the bucket inside the
row sidesteps unaligned narrow copies and needs just one DMA per chunk.
TILE_ROWS == 128 keeps the one-hot comparison a pure [CHUNK, 128]
vector op against a lane iota (no sub-lane slicing anywhere).

Exactness of the matmuls: the writeback contract guarantees at most
ONE update row touches any (bucket, lane) cell (way-disjointness,
kernels._writeback_delta_add), so every output cell is a sum of one
value and zeros — no accumulation rounding. Values themselves exceed
the MXU's bf16 pass precision, so each int32 delta is split into four
8-bit halves (each exact in bf16), matmul'd separately, and recombined
in int32 (wrap-safe: the shifted sums reassemble delta mod 2^32).

STATUS (r3, measured on v5e — scripts/bench_sweep_regime.py): bit-exact,
cross-tile DMA prefetch in place, TILE_ROWS/CHUNK parameterized. The r2
lesson stands in spirit for the flagship regime: any full-store sweep
pays the ~260us streaming floor before doing work (XLA's elementwise
pass over the 16 MiB store runs at only ~180 GB/s effective), and below
density ~2 the two paths trade within noise (sweep +7% at density 0.5,
-23% at density 1.0 — regime-dependent, not a clean win either way).
The dense regime the r2 note hypothesized is REAL and now measured:

  buckets    B      density  scatter  sweep   speedup
  32768    16384     0.5     364us    341us   1.07x
  32768    32768     1.0     372us    486us   0.77x
   8192    16384     2       320us    315us   1.02x
   4096    16384     4       342us    299us   1.14x
   2048    16384     8       268us    209us   1.28x
   4096    32768     8       482us    361us   1.34x

Small-store / big-batch deployments (B >= ~4x bucket count) get
1.14-1.34x from the sweep, so GUBER_WRITEBACK=auto (the default,
kernels.writeback_form) selects it exactly there; =sweep/=scatter
force a path. "scatter" in this table is the scatter-add told its
indices are sorted, the form writeback_form still gives every one of
these shapes when it does not give the sweep.

Because the update stream is bucket-sorted, rows DMA'd beyond the tile's
[lo, hi) range map outside [0, TILE_ROWS) and one-hot to zero — the
sort does the range masking for free; only re-reads caused by clamping
a chunk's start against the end of the array need an explicit index
mask.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tile/chunk geometry (trace-time constants; env knobs for benchmarking).
# TILE_ROWS: bucket rows per grid step, a multiple of 128 — each 128-row
# block gets its own one-hot matmul (the lane-width trick, see docstring).
# CHUNK: update rows per DMA window.
TILE_ROWS = int(os.environ.get("GUBER_SWEEP_TILE", "128"))
CHUNK = int(os.environ.get("GUBER_SWEEP_CHUNK", "128"))


def _first_chunk_dma(bounds_ref, comb_ref, comb_s, sem, t, slot):
    """Async copy of tile t's FIRST update chunk into ping-pong slot
    `slot`. Issued one grid step EARLY (tile t-1 prefetches for tile t)
    so the wait at tile t is satisfied long before it's reached — per-tile
    DMA issue latency was the dominant cost of the serialized version."""
    B = comb_ref.shape[0]
    lo_al8 = bounds_ref[t] // 8
    start8 = jnp.minimum(lo_al8, (B - CHUNK) // 8)
    return pltpu.make_async_copy(
        comb_ref.at[pl.ds(start8 * 8, CHUNK), :],
        comb_s.at[slot],
        sem.at[slot],
    )


def _kernel(
    bounds_ref,  # SMEM int32[ntiles+1]: searchsorted tile ranges
    data_ref,  # VMEM int32[TILE_ROWS, 128] current tile (aliased out)
    comb_ref,  # ANY int32[B, 256]: delta lanes 0-127, bucket id 128-255
    out_ref,  # VMEM int32[TILE_ROWS, 128]
    comb_s,  # VMEM scratch int32[3, CHUNK, 256]: slots 0/1 ping-pong
    # prefetched first chunks across tiles; slot 2 serves the rare
    # second-and-later chunks of a dense tile
    sem,  # DMA semaphores (3,)
):
    t = pl.program_id(0)
    nt = pl.num_programs(0)
    B = comb_ref.shape[0]
    lo = bounds_ref[t]
    hi = bounds_ref[t + 1]
    tile_base = t * TILE_ROWS
    slot = lax.rem(t, 2)
    nonempty = hi > lo

    # t=0 has no predecessor to prefetch for it; issue inline
    @pl.when((t == 0) & nonempty)
    def _():
        _first_chunk_dma(bounds_ref, comb_ref, comb_s, sem, t, slot).start()

    # prefetch the NEXT tile's first chunk into the other slot while this
    # tile computes (skip empty tiles — both sites test the same bounds,
    # so every started DMA is waited exactly once)
    @pl.when((t + 1 < nt) & (bounds_ref[t + 1] < bounds_ref[t + 2]))
    def _():
        _first_chunk_dma(
            bounds_ref, comb_ref, comb_s, sem, t + 1, 1 - slot
        ).start()

    acc0 = data_ref[:]

    # chunk windows advance from an 8-aligned base so every dynamic DMA
    # start is provably sublane-aligned AND windows tile [lo_al, hi) with
    # no gaps. The re-read prefix [lo_al, lo) belongs to the previous
    # tile, whose buckets one-hot to zero here — the sort masks it free.
    lo_al8 = lo // 8

    def process(chunk, want8, start, acc):
        d = chunk[:, :128]
        buck = chunk[:, 128:]  # [CHUNK, 128], lanes identical (bucket id)
        gidx = start + lax.broadcasted_iota(jnp.int32, (CHUNK, 128), 0)
        # rows before this chunk's intended window were handled by the
        # previous chunk (re-read only happens under the end clamp)
        fresh = gidx >= want8 * 8
        row_ids = lax.broadcasted_iota(jnp.int32, (CHUNK, 128), 1)

        contract = (((0,), (0,)), ((), ()))  # sum over the CHUNK dim
        # int32 deltas split into four 8-bit halves: each is exactly
        # representable in bf16 (8 mantissa bits), so the MXU's default
        # single-pass bf16 matmul is exact — measured faster than two
        # 16-bit halves at 3-pass HIGHEST precision
        parts = (
            (d & 0xFF).astype(jnp.float32),
            ((d >> 8) & 0xFF).astype(jnp.float32),
            ((d >> 16) & 0xFF).astype(jnp.float32),
            (d >> 24).astype(jnp.float32),
        )
        # one [CHUNK, 128] one-hot + 4 matmuls per 128-row block of the
        # tile; blocks assemble with a concat (a .at[].add would lower to
        # an unsupported in-kernel scatter)
        adds = []
        for blk in range(TILE_ROWS // 128):
            rel = buck - (tile_base + blk * 128)
            onehot = ((rel == row_ids) & fresh).astype(jnp.float32)
            add = None
            for shift, p in enumerate(parts):
                r = lax.dot_general(
                    onehot,
                    p,
                    contract,
                    preferred_element_type=jnp.float32,
                ).astype(jnp.int32)
                r = r << (8 * shift)
                add = r if add is None else add + r
            adds.append(add)
        total = adds[0] if len(adds) == 1 else jnp.concatenate(adds, axis=0)
        return acc + total

    def chunk_body(c, acc):
        # rare path (a tile holding >CHUNK update rows): blocking DMA
        # through the dedicated slot 2 so the cross-tile ping-pong slots
        # stay untouched
        want8 = lo_al8 + c * (CHUNK // 8)
        start8 = jnp.minimum(want8, (B - CHUNK) // 8)  # end clamp
        cp = pltpu.make_async_copy(
            comb_ref.at[pl.ds(start8 * 8, CHUNK), :],
            comb_s.at[2],
            sem.at[2],
        )
        cp.start()
        cp.wait()
        return process(comb_s[2], want8, start8 * 8, acc)

    def with_updates():
        _first_chunk_dma(bounds_ref, comb_ref, comb_s, sem, t, slot).wait()
        start8_0 = jnp.minimum(lo_al8, (B - CHUNK) // 8)
        acc = process(comb_s[slot], lo_al8, start8_0 * 8, acc0)
        nchunks = (hi - lo_al8 * 8 + CHUNK - 1) // CHUNK
        return lax.fori_loop(1, nchunks, chunk_body, acc)

    out_ref[:] = lax.cond(nonempty, with_updates, lambda: acc0)


def _apply_inline(
    data: jax.Array,  # int32[buckets, 128]
    bkt: jax.Array,  # int32[B] sorted non-decreasing, in range
    drow: jax.Array,  # int32[B, 128] zero rows for non-writers
    interpret: bool = False,
) -> jax.Array:
    """data with every delta row added at its bucket; traceable inside a
    larger jit (kernels._writeback_delta_add's opt-in path). Requires
    buckets % TILE_ROWS == 0, 128 lanes, B >= CHUNK, and B % 8 == 0
    (the chunk windows advance in 8-row sublane steps; a ragged tail
    would fall outside every window and its updates would be lost)."""
    buckets, W = data.shape
    assert W == 128 and buckets % TILE_ROWS == 0
    B = bkt.shape[0]
    assert B >= CHUNK, "use the XLA scatter for small batches"
    assert B % 8 == 0, "B must be a multiple of the sublane tiling (8)"
    ntiles = buckets // TILE_ROWS

    bounds = jnp.searchsorted(
        bkt, jnp.arange(ntiles + 1, dtype=jnp.int32) * TILE_ROWS, side="left"
    ).astype(jnp.int32)
    comb = jnp.concatenate(
        [drow, jnp.broadcast_to(bkt[:, None], (B, 128))], axis=1
    )

    # The process runs with x64 enabled (uint64 key hashes); tracing
    # this kernel under x64 trips an astype recursion inside pallas
    # (jax 0.9). Every input here is int32, so trace the pallas_call
    # with x64 locally disabled — numerics are identical.
    with jax.enable_x64(False):
        return _call(data, bounds, comb, ntiles, buckets, interpret)


@functools.partial(jax.jit, donate_argnums=(0,))
def sweep_apply(data: jax.Array, bkt: jax.Array, drow: jax.Array):
    """Standalone jitted _apply_inline (tests, benchmarks)."""
    return _apply_inline(data, bkt, drow)


def _call(data, bounds, comb, ntiles, buckets, interpret=False):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec(
                (TILE_ROWS, 128), lambda t, bounds: (t, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (TILE_ROWS, 128), lambda t, bounds: (t, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((3, CHUNK, 256), jnp.int32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    kwargs = (
        dict(interpret=True)
        if interpret
        else dict(
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            )
        )
    )
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((buckets, 128), jnp.int32),
        grid_spec=grid_spec,
        input_output_aliases={1: 0},
        **kwargs,
    )(bounds, data, comb)
