"""Native one-call batch prep (guber_prep_sharded) differential tests.

The mesh serving hot path builds its per-shard device arrays through ONE
native call (presort + duplicate-key groups + clipped/padded marshal,
optionally thread-parallel — guberhash.cc). These tests pin it
bit-identical to the pure-numpy twin (parallel/sharded.py fallbacks /
engine.build_groups) across batch shapes, shard counts, store sizes, and
pool widths; the twin is itself pinned against the kernel's contract by
tests/test_sharded.py and tests/test_kernels.py.
"""

import logging
import os
import subprocess
import sys

import numpy as np
import pytest

from gubernator_tpu.core.engine import dense_ladder_extension
from gubernator_tpu.core.hashing import native_lib
from gubernator_tpu.core.store import (
    COUNTER_MAX,
    MAX_DURATION_MS,
    TIME_FLOOR,
)
import gubernator_tpu.parallel.sharded as sh

hn = native_lib()
if hn is None:
    pytest.skip(
        "libguberhash.so is absent (make -C gubernator_tpu/native)",
        allow_module_level=True,
    )


def _traffic(rng, n):
    zipf = rng.zipf(1.2, size=n) % 50_000
    kh = (
        zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    ) ^ np.uint64(0xABCD)
    return (
        kh,
        rng.integers(-(2**40), 2**40, n),  # hits: exercises clipping
        rng.integers(0, 2**40, n),
        rng.integers(-5, 2**40, n),  # duration: below TIME_FLOOR too
        rng.integers(0, 2, n).astype(np.int32),
        rng.integers(0, 2, n).astype(bool),
    )


def _numpy_twin(sub, slots, ns, arrays, group_rung=None):
    saved = sh._presort_sharded_grouped, sh._prep_native
    sh._presort_sharded_grouped = sh._np_presort_sharded_grouped
    sh._prep_native = None
    try:
        return sh.pad_request_sharded(
            sub, slots, ns, *arrays, with_groups=True,
            group_rung=group_rung,
        )
    finally:
        sh._presort_sharded_grouped, sh._prep_native = saved


CONFIGS = [
    (32768, 8, 1 << 15),  # flagship mesh shape
    (1000, 8, 1 << 15),
    (5000, 6, 1 << 12),  # non-power-of-two shards
    (64, 3, 256),
    (1, 8, 1 << 15),  # 7 empty shards
    (17, 2, 1024),
    (4096, 1, 1 << 15),  # single-device form
    (32768, 16, 1 << 15),
    (300, 8, 1 << 15),
    (8192, 4, 1 << 10),
    (2, 8, 64),  # mostly-empty tiny store
    (128, 128, 1 << 15),  # many shards, some empty
]


@pytest.mark.parametrize("n,ns,slots", CONFIGS)
def test_prep_matches_numpy_twin(n, ns, slots):
    logging.disable(logging.WARNING)  # ladder-overflow warning is expected
    try:
        rng = np.random.default_rng(hash((n, ns, slots)) % 2**32)
        arrays = _traffic(rng, n)
        sub = sh.sub_batch_ladder((64, 256, 1024, 4096))
        req_np, order_np, take_np, groups_np = _numpy_twin(
            sub, slots, ns, arrays
        )
        rungs = np.asarray(dense_ladder_extension(sub, n), np.int64)
        order, counts, take, fields, groups, B, G = hn.prep_sharded(
            *arrays, slots, ns, rungs, 0,
            -COUNTER_MAX, COUNTER_MAX, TIME_FLOOR, MAX_DURATION_MS,
        )
        assert B == req_np.key_hash.shape[1]
        assert G == groups_np.key_hash.shape[1]
        assert int(counts.sum()) == n
        np.testing.assert_array_equal(order, order_np[:n])
        np.testing.assert_array_equal(take, take_np)
        for f in (
            "key_hash", "hits", "limit", "duration", "algo", "gnp", "valid"
        ):
            np.testing.assert_array_equal(
                fields[f], getattr(req_np, f), err_msg=f
            )
        for f in ("key_hash", "leader_pos", "end_pos", "valid", "group_id"):
            np.testing.assert_array_equal(
                groups[f], getattr(groups_np, f), err_msg=f"groups.{f}"
            )
    finally:
        logging.disable(logging.NOTSET)


def test_prep_group_rung_override_and_error():
    rng = np.random.default_rng(7)
    n, ns, slots = 2048, 4, 1 << 12
    arrays = _traffic(rng, n)
    sub = sh.sub_batch_ladder((64, 256, 1024, 4096))
    rungs = np.asarray(dense_ladder_extension(sub, n), np.int64)
    clip = (-COUNTER_MAX, COUNTER_MAX, TIME_FLOOR, MAX_DURATION_MS)
    # a valid override is honored exactly
    *_, G = hn.prep_sharded(*arrays, slots, ns, rungs, 1024, *clip)
    assert G == 1024
    req_np, _o, _t, groups_np = _numpy_twin(
        sub, slots, ns, arrays, group_rung=1024
    )
    assert groups_np.key_hash.shape[1] == 1024
    # an override below a shard's group count raises like the numpy path
    with pytest.raises(ValueError, match="group_rung"):
        hn.prep_sharded(*arrays, slots, ns, rungs, 1, *clip)
    with pytest.raises(ValueError, match="group_rung"):
        _numpy_twin(sub, slots, ns, arrays, group_rung=1)


def test_prep_buffer_lifetime_two_generations():
    """Results stay intact across ONE further call (the pipelined
    engine's two-in-flight bound) and are recycled after two."""
    rng = np.random.default_rng(11)
    sub = sh.sub_batch_ladder((64, 256, 1024, 4096))
    clip = (-COUNTER_MAX, COUNTER_MAX, TIME_FLOOR, MAX_DURATION_MS)
    a1 = _traffic(rng, 500)
    a2 = _traffic(rng, 500)
    rungs = np.asarray(dense_ladder_extension(sub, 500), np.int64)
    r1 = hn.prep_sharded(*a1, 1 << 12, 4, rungs, 0, *clip)
    kh1 = r1[3]["key_hash"].copy()
    hn.prep_sharded(*a2, 1 << 12, 4, rungs, 0, *clip)  # generation flips
    np.testing.assert_array_equal(r1[3]["key_hash"], kh1)


@pytest.mark.parametrize("threads", ["2", "4", "7"])
def test_prep_thread_pool_bit_identity(threads):
    """GUBER_PREP_THREADS is resolved at pool creation, so the threaded
    runs execute in a subprocess; output must be bit-identical to the
    in-process single-thread result for a fixed seed."""
    code = """
import numpy as np, sys
from gubernator_tpu.native import hashlib_native as hn
from gubernator_tpu.core.engine import dense_ladder_extension
from gubernator_tpu.core.hashing import native_lib
from gubernator_tpu.core.store import COUNTER_MAX, MAX_DURATION_MS, TIME_FLOOR
import gubernator_tpu.parallel.sharded as sh
rng = np.random.default_rng(99)
n, ns, slots = 20000, 8, 1 << 15
zipf = rng.zipf(1.2, size=n) % 50_000
kh = (zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) ^ np.uint64(0xF00)
hits = rng.integers(-2**40, 2**40, n); limit = rng.integers(0, 2**40, n)
dur = rng.integers(-5, 2**40, n); algo = rng.integers(0, 2, n).astype(np.int32)
gnp = rng.integers(0, 2, n).astype(bool)
sub = sh.sub_batch_ladder((64, 256, 1024, 4096))
rungs = np.asarray(dense_ladder_extension(sub, n), np.int64)
r = hn.prep_sharded(kh, hits, limit, dur, algo, gnp, slots, ns, rungs, 0,
                    -COUNTER_MAX, COUNTER_MAX, TIME_FLOOR, MAX_DURATION_MS)
assert hn.prep_threads() == int(sys.argv[1]), hn.prep_threads()
import hashlib
d = hashlib.sha256()
for a in (r[0], r[2], r[3]["key_hash"], r[3]["hits"], r[3]["valid"],
          r[4]["leader_pos"], r[4]["end_pos"], r[4]["group_id"]):
    d.update(np.ascontiguousarray(a).tobytes())
print(d.hexdigest())
"""
    env = dict(os.environ, GUBER_PREP_THREADS="1", PYTHONPATH=".")
    base = subprocess.run(
        [sys.executable, "-c", code, "1"],
        capture_output=True, text=True, env=env, check=True,
    ).stdout.strip()
    env["GUBER_PREP_THREADS"] = threads
    got = subprocess.run(
        [sys.executable, "-c", code, threads],
        capture_output=True, text=True, env=env, check=True,
    ).stdout.strip()
    assert got == base, f"threads={threads} diverged"


def test_single_device_prep_matches_numpy_twin():
    """engine.pad_request_sorted's native gate (multi-thread hosts) must
    be bit-identical to its numpy/fused path regardless of the gate, so
    exercise the n_shards=1 native form directly."""
    import gubernator_tpu.core.engine as eng

    rng = np.random.default_rng(21)
    n, slots = 4096, 1 << 15
    arrays = _traffic(rng, n)
    saved = eng._hn
    eng._hn = None
    try:
        req_np, order_np, groups_np = eng.pad_request_sorted(
            (4096,), slots, *arrays, with_groups=True
        )
    finally:
        eng._hn = saved
    clip = (-COUNTER_MAX, COUNTER_MAX, TIME_FLOOR, MAX_DURATION_MS)
    order, _c, _t, fields, groups, B, G = hn.prep_sharded(
        *arrays, slots, 1, np.asarray([4096], np.int64), 0, *clip
    )
    assert B == 4096
    np.testing.assert_array_equal(order, order_np[:n])
    for f in ("key_hash", "hits", "limit", "duration", "algo", "gnp", "valid"):
        np.testing.assert_array_equal(
            fields[f][0], getattr(req_np, f), err_msg=f
        )
    for f in ("key_hash", "leader_pos", "end_pos", "valid", "group_id"):
        np.testing.assert_array_equal(
            groups[f][0], getattr(groups_np, f), err_msg=f"groups.{f}"
        )


def test_engine_native_gate_glue_multithread():
    """The pad_request_sorted native branch only runs when
    prep_threads() > 1 (never on this 1-core box in-process), so drive
    it in a subprocess with GUBER_PREP_THREADS=2 and assert its output
    equals the numpy path computed in the same process."""
    code = """
import numpy as np
import gubernator_tpu.core.engine as eng
from gubernator_tpu.native import hashlib_native as hn
assert hn.prep_threads() == 2
rng = np.random.default_rng(33)
n, slots = 5000, 1 << 14
zipf = rng.zipf(1.2, size=n) % 20_000
kh = (zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)) ^ np.uint64(0x77)
arrays = (kh, rng.integers(-2**40, 2**40, n), rng.integers(0, 2**40, n),
          rng.integers(-5, 2**40, n), rng.integers(0, 2, n).astype(np.int32),
          rng.integers(0, 2, n).astype(bool))
args = ((64, 256, 1024, 4096, 8192), slots) + arrays
req, order, groups = eng.pad_request_sorted(*args, with_groups=True)
# copy before the twin runs (twin path doesn't flip buffers, but be safe)
native = [np.array(x) for x in (order, *req, *groups)]
saved = eng._hn
eng._hn = None
req_np, order_np, groups_np = eng.pad_request_sorted(*args, with_groups=True)
eng._hn = saved
for got, want in zip(native, (order_np, *req_np, *groups_np)):
    np.testing.assert_array_equal(got, want)
print("GLUE-OK")
"""
    env = dict(os.environ, GUBER_PREP_THREADS="2", PYTHONPATH=".")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env,
    )
    assert out.returncode == 0, out.stderr[-800:]
    assert "GLUE-OK" in out.stdout


def test_prep_pool_fork_safety():
    """A forked child inherits a multi-lane pool with no worker threads;
    the atfork guard must make it run inline instead of hanging."""
    code = """
import os, sys
import numpy as np
from gubernator_tpu.native import hashlib_native as hn
from gubernator_tpu.core.engine import dense_ladder_extension
from gubernator_tpu.core.hashing import native_lib
from gubernator_tpu.core.store import COUNTER_MAX, MAX_DURATION_MS, TIME_FLOOR
import gubernator_tpu.parallel.sharded as sh
rng = np.random.default_rng(5)
n = 4000
kh = rng.integers(1, 2**63, n).astype(np.uint64)
arrays = (kh, np.ones(n, np.int64), np.ones(n, np.int64) * 10,
          np.ones(n, np.int64) * 1000, np.zeros(n, np.int32),
          np.zeros(n, bool))
sub = sh.sub_batch_ladder((64, 256, 1024, 4096))
rungs = np.asarray(dense_ladder_extension(sub, n), np.int64)
clip = (-COUNTER_MAX, COUNTER_MAX, TIME_FLOOR, MAX_DURATION_MS)
r_parent = hn.prep_sharded(*arrays, 1 << 12, 4, rungs, 0, *clip)
parent_order = r_parent[0].copy()
pid = os.fork()
if pid == 0:
    # child: pool threads are gone; this must complete inline
    r = hn.prep_sharded(*arrays, 1 << 12, 4, rungs, 0, *clip)
    ok = np.array_equal(r[0], parent_order)
    os._exit(0 if ok else 3)
_, status = os.waitpid(pid, 0)
assert os.WIFEXITED(status) and os.WEXITSTATUS(status) == 0, status
print("FORK-OK")
"""
    env = dict(os.environ, GUBER_PREP_THREADS="4", PYTHONPATH=".")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, env=env, timeout=60,
    )
    assert out.returncode == 0, out.stderr[-800:]
    assert "FORK-OK" in out.stdout
