"""Writeback regime grids: which form of the writeback wins where.

Default: XLA scatter (told its indices are sorted) vs pallas sweep across store density (B updates /
buckets rows). `--hint`: the XLA scatter WITH and WITHOUT
`indices_are_sorted=True` over the shapes the cells and the deep-batch
modes trace (kernels.writeback_form's threshold was placed from this
grid; PERF.md section 6, PR 31).

The sweep module's STATUS note claims the sweep "only pays off when
updates are dense relative to the store (B approaching the bucket
count)" — this script measures that claim instead of asserting it: for
each (buckets, B) the measured op is kernels._writeback_delta_add's
final step (way-disjoint delta-row add, sorted indices), same harness as
scripts/bench_writeback.py. Prints one JSON line per regime to stdout.
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


def run_regime(buckets: int, B: int, S: int = 512):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from gubernator_tpu.core.pallas_sweep import _apply_inline

    rng = np.random.default_rng(5)
    data = rng.integers(
        -(2**31), 2**31 - 1, (buckets, 128), dtype=np.int64
    ).astype(np.int32)
    # B sorted updates over the bucket space, way-disjoint within a bucket
    # (the writeback contract); cap way index at 16
    bkt = np.sort(rng.integers(0, buckets, B)).astype(np.int32)
    drow = np.zeros((B, 128), np.int32)
    run = 0
    vals = rng.integers(-1000, 1000, (B, 8)).astype(np.int32)
    for i in range(B):
        run = run + 1 if i and bkt[i] == bkt[i - 1] else 0
        w = run % 16
        drow[i, w * 8 : (w + 1) * 8] = vals[i]

    want = data.copy()
    np.add.at(want, bkt, drow)
    d_bkt = jnp.asarray(bkt)
    d_drow = jnp.asarray(drow)

    def scatter_apply(x, bkt, drow):
        return x.at[bkt].add(drow, indices_are_sorted=True)

    out = {"buckets": buckets, "B": B, "density": round(B / buckets, 3)}
    for name, fn in (
        ("scatter", scatter_apply),
        ("sweep", lambda x, bkt, drow: _apply_inline(x, bkt, drow)),
    ):
        got = jax.jit(fn)(jnp.asarray(data), d_bkt, d_drow)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=name)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def steps(x, bkt, drow, fn=fn):
            def body(i, x):
                return fn(x, bkt, drow)

            return lax.fori_loop(0, S, body, x)

        x = jnp.asarray(data)
        x = steps(x, d_bkt, d_drow)
        jax.block_until_ready(x)
        times = []
        for _ in range(3):
            t = time.monotonic()
            x = steps(x, d_bkt, d_drow)
            jax.block_until_ready(x)
            times.append(time.monotonic() - t)
        us = min(times) / S * 1e6
        out[name] = round(us, 1)
        log(f"  {name}: {us:.1f} us/step (B={B}, store {buckets}x128)")
    out["sweep_speedup"] = round(out["scatter"] / out["sweep"], 2)
    print(json.dumps(out), flush=True)
    return out


def _plan(rng, buckets: int, B: int):
    """B sorted bucket indices with duplicates and way-disjoint delta
    rows (the writeback contract), built without a Python loop."""
    bkt = np.sort(rng.integers(0, buckets, B)).astype(np.int32)
    ar = np.arange(B)
    first = np.r_[True, bkt[1:] != bkt[:-1]]
    run = ar - np.maximum.accumulate(np.where(first, ar, 0))
    drow = np.zeros((B, 16, 8), np.int32)
    live = run < 16  # a 17th item of one bucket adds a zero row
    drow[ar[live], run[live]] = rng.integers(
        -1000, 1000, (int(live.sum()), 8)
    ).astype(np.int32)
    return bkt, drow.reshape(B, 128)


def run_hint_regime(buckets: int, B: int, out_path: str):
    """ms a call of `x.at[bkt].add(drow)` with and without the sorted
    hint on an int32[buckets, 128] table made ON the device (8 GiB at
    2^24 rows: one table, donated through the timed loop). Both forms
    are checked against numpy on every touched row and by the table's
    wrapped sum."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(buckets * 31 + B)
    bkt, drow = _plan(rng, buckets, B)
    d_bkt, d_drow = jnp.asarray(bkt), jnp.asarray(drow)
    rows = np.unique(bkt)
    d_rows = jnp.asarray(rows)

    @jax.jit
    def make():
        r = lax.broadcasted_iota(jnp.int32, (buckets, 128), 0)
        c = lax.broadcasted_iota(jnp.int32, (buckets, 128), 1)
        return r * jnp.int32(1000003) + c

    want_rows = (
        rows[:, None].astype(np.int64) * 1000003 + np.arange(128)[None, :]
    ).astype(np.int32)
    np.add.at(want_rows, np.searchsorted(rows, bkt), drow)

    @jax.jit
    def digest(x):
        return jnp.take(x, d_rows, axis=0), jnp.sum(x, dtype=jnp.int32)

    out = {"rows": buckets, "log2_rows": buckets.bit_length() - 1, "B": B}
    sums = {}
    for name, hint in (("hint", True), ("no_hint", False)):

        @functools.partial(jax.jit, donate_argnums=(0,))
        def steps(x, bkt, drow, n, hint=hint):
            def body(i, x):
                return x.at[bkt].add(drow, indices_are_sorted=hint)

            return lax.fori_loop(0, n, body, x)

        x = steps(make(), d_bkt, d_drow, 1)
        got_rows, sums[name] = digest(x)
        np.testing.assert_array_equal(
            np.asarray(got_rows), want_rows, err_msg=name
        )
        t = time.monotonic()
        x = steps(x, d_bkt, d_drow, 4)
        jax.block_until_ready(x)
        est = (time.monotonic() - t) / 4
        S = int(min(2048, max(8, 0.25 / est)))
        times = []
        for _ in range(3):
            t = time.monotonic()
            x = steps(x, d_bkt, d_drow, S)
            jax.block_until_ready(x)
            times.append(time.monotonic() - t)
        del x
        out[name + "_ms"] = round(min(times) / S * 1e3, 4)
    assert int(sums["hint"]) == int(sums["no_hint"]), sums
    out["hint_over_no_hint"] = round(out["hint_ms"] / out["no_hint_ms"], 3)
    line = json.dumps(out)
    print(line, flush=True)
    with open(out_path, "a") as f:
        f.write(line + "\n")
    return out


def hint_grid():
    """The cells' ladder into every store the cells run, the mesh's
    sub-rungs into a shard, and the deep batches no cell runs."""
    grid = [
        (1 << r, B)
        for r in (15, 18, 20, 24)
        for B in (64, 256, 1024, 16384)
    ]
    grid += [(1 << 18, B) for B in (96, 128, 192, 384, 512, 768)]
    grid += [
        (1 << 15, B) for B in (2048, 4096, 8192, 32768, 65536, 131072)
    ]
    grid += [(1 << 12, 4096), (1 << 12, 16384), (1 << 18, 131072)]
    # around the crossover, B between rows / 64 and rows / 16
    grid += [
        (1 << 15, 512), (1 << 15, 768), (1 << 15, 1536),
        (1 << 16, 1024), (1 << 16, 2048), (1 << 16, 4096),
        (1 << 17, 2048), (1 << 17, 4096),
        (1 << 18, 4096), (1 << 18, 8192),
        (1 << 20, 32768), (1 << 20, 65536),
    ]
    return grid


def main():
    import jax

    import gubernator_tpu.core  # noqa: F401 (x64 on)

    dev = jax.devices()[0]
    log(f"device: {dev.platform} ({dev.device_kind})")
    if "--hint" in sys.argv[1:]:
        os.makedirs("chiprun_out", exist_ok=True)
        path = "chiprun_out/writeback_hint_grid.jsonl"
        with open(path, "w") as f:
            f.write(json.dumps({"device": dev.device_kind}) + "\n")
        small = "--small" in sys.argv[1:]  # a CPU rehearsal's size
        for buckets, B in hint_grid():
            if small and (buckets > 1 << 15 or B > 4096):
                continue
            log(f"hint regime rows={buckets} B={B}")
            try:
                run_hint_regime(buckets, B, path)
            except (RuntimeError, AssertionError) as e:
                # one regime the device refuses must not lose the grid
                log(f"  FAILED rows={buckets} B={B}: {e!r:.300}")
        return
    grid = [
        (1 << 15, 16384),  # flagship-ish: density 0.5 (STATUS regime)
        (1 << 15, 32768),  # density 1.0 at the flagship store
        (8192, 16384),  # density 2
        (4096, 16384),  # density 4
        (2048, 16384),  # density 8
        (4096, 32768),  # density 8, bigger batch
    ]
    for buckets, B in grid:
        log(f"regime buckets={buckets} B={B}")
        run_regime(buckets, B)


if __name__ == "__main__":
    main()
