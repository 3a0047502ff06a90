"""Host batch-prep bench: the r2 serving-tier bottleneck, re-measured.

The r2 verdict identified single-threaded host prep as the cap on served
mesh throughput. This bench measures the three generations of the mesh
prep path at the flagship batch (32k rows, 8 shards, 32k-bucket store):

  numpy    — r2's pad_request_sharded (native presort + numpy marshal +
             per-shard Python build_groups)
  native   — r3's one-call guber_prep_sharded at 1 thread
  native-T — the same call with GUBER_PREP_THREADS=T (subprocess per T,
             because the pool size is resolved once per process)

Prints one JSON line per variant. NOTE on this builder box: nproc == 1,
so thread counts above 1 CANNOT show wall-clock wins here — the threaded
rows document pool overhead on one core and the path is bit-identity
tested at every width (tests/test_prep_native.py); on a real serving
host the per-shard sort/marshal phases parallelize across cores.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N, NS, SLOTS = 32768, 8, 1 << 15
REPS = 40


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _traffic():
    rng = np.random.default_rng(42)
    zipf = rng.zipf(1.2, size=N) % 100_000
    kh = (
        zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    ) ^ np.uint64(0xDEADBEEFCAFEF00D)
    return (
        kh,
        np.ones(N, np.int64),
        rng.integers(10, 10_000, N),
        np.full(N, 60_000, np.int64),
        (zipf % 2).astype(np.int32),
        np.zeros(N, bool),
    )


def _emit(variant, us, extra=None):
    row = {
        "variant": variant,
        "us_per_batch": round(us, 1),
        "keys_per_sec": round(N / (us / 1e6), 0),
        "batch": N,
        "shards": NS,
    }
    if extra:
        row.update(extra)
    print(json.dumps(row), flush=True)


def bench_inproc():
    import gubernator_tpu.parallel.sharded as sh

    arrays = _traffic()
    sub = sh.sub_batch_ladder((64, 256, 1024, 4096))

    def run(label):
        sh.pad_request_sharded(sub, SLOTS, NS, *arrays, with_groups=True)
        ts = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            sh.pad_request_sharded(
                sub, SLOTS, NS, *arrays, with_groups=True
            )
            ts.append(time.perf_counter() - t0)
        _emit(label, min(ts) * 1e6)

    # r2 path: native presort + numpy marshal
    saved = sh._prep_native
    sh._prep_native = None
    try:
        run("numpy-marshal(r2)")
    finally:
        sh._prep_native = saved
    if saved is not None:
        run("native-onecall")


_CHILD = """
import json, time, numpy as np
import gubernator_tpu.parallel.sharded as sh
from gubernator_tpu.native import hashlib_native as hn
rng = np.random.default_rng(42)
N, NS, SLOTS = %d, %d, %d
zipf = rng.zipf(1.2, size=N) %% 100_000
kh = (zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) ^ np.uint64(0xDEADBEEFCAFEF00D)
arrays = (kh, np.ones(N, np.int64), rng.integers(10, 10_000, N),
          np.full(N, 60_000, np.int64), (zipf %% 2).astype(np.int32),
          np.zeros(N, bool))
sub = sh.sub_batch_ladder((64, 256, 1024, 4096))
sh.pad_request_sharded(sub, SLOTS, NS, *arrays, with_groups=True)
ts = []
for _ in range(%d):
    t0 = time.perf_counter()
    sh.pad_request_sharded(sub, SLOTS, NS, *arrays, with_groups=True)
    ts.append(time.perf_counter() - t0)
print(json.dumps({"us": min(ts) * 1e6, "threads": hn.prep_threads()}))
"""


def bench_threads():
    for t in (1, 2, 4, 8):
        env = dict(
            os.environ,
            GUBER_PREP_THREADS=str(t),
            # APPEND to PYTHONPATH, never replace it: the child needs
            # whatever the parent's interpreter was started with
            PYTHONPATH=os.getcwd()
            + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
        )
        out = subprocess.run(
            [sys.executable, "-c", _CHILD % (N, NS, SLOTS, REPS)],
            capture_output=True, text=True, env=env,
        )
        if out.returncode != 0:
            log(f"threads={t} failed: {out.stderr[-500:]}")
            continue
        row = json.loads(out.stdout.strip().splitlines()[-1])
        _emit(
            f"native-onecall-T{t}", row["us"],
            {"threads": row["threads"]},
        )


def main():
    log(f"host: {os.cpu_count()} core(s) visible")
    bench_inproc()
    bench_threads()


if __name__ == "__main__":
    main()
