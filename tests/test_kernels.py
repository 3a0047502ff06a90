"""Differential tests: the TPU decide kernel vs the exact oracle.

Every behavioral contract the oracle encodes must hold identically on the
device path (same status, remaining, reset_time), batch after batch, under
a synthetic clock. Intra-batch duplicate-key semantics follow the
documented cumulative-attempt rule (kernels.py module docstring) and match
sequential-greedy for uniform hits.
"""

import random

import pytest

from gubernator_tpu.api.types import (
    Algorithm,
    RateLimitReq,
    RateLimitResp,
    Status,
    SECOND,
)
from gubernator_tpu.core.cache import LRUCache
from gubernator_tpu.core.engine import TpuEngine
from gubernator_tpu.core.oracle import get_rate_limit
from gubernator_tpu.core.store import StoreConfig

T0 = 1_700_000_000_000


@pytest.fixture(scope="module")
def engine():
    return TpuEngine(StoreConfig(rows=4, slots=1 << 12), buckets=(16, 64, 256))


@pytest.fixture(autouse=True)
def _reset(engine):
    engine.reset()
    yield


def req(**kw):
    kw.setdefault("name", "test")
    kw.setdefault("unique_key", "account:1234")
    return RateLimitReq(**kw)


def one(engine, r, now, gnp=False):
    return engine.get_rate_limits([r], now=now, gnp=[gnp])[0]


def check_same(resp: RateLimitResp, want: RateLimitResp, ctx=""):
    assert resp.status == want.status, ctx
    assert resp.limit == want.limit, ctx
    assert resp.remaining == want.remaining, ctx
    assert resp.reset_time == want.reset_time, ctx


# ---------------------------------------------------------------- behavioral


def test_over_the_limit(engine):
    expects = [(1, Status.UNDER_LIMIT), (0, Status.UNDER_LIMIT), (0, Status.OVER_LIMIT)]
    for remaining, status in expects:
        rl = one(engine, req(hits=1, limit=2, duration=SECOND), now=T0)
        assert (rl.remaining, rl.status) == (remaining, status)
        assert rl.limit == 2
        assert rl.reset_time == T0 + SECOND


def test_token_window_reset(engine):
    r = req(hits=1, limit=2, duration=5)
    assert one(engine, r, now=T0).remaining == 1
    assert one(engine, r, now=T0).remaining == 0
    rl = one(engine, r, now=T0 + 6)
    assert (rl.remaining, rl.status) == (1, Status.UNDER_LIMIT)


def test_leaky_drain(engine):
    steps = [
        (5, 0, 0, Status.UNDER_LIMIT),
        (1, 0, 0, Status.OVER_LIMIT),
        (1, 10, 0, Status.UNDER_LIMIT),
        (1, 20, 1, Status.UNDER_LIMIT),
    ]
    t = T0
    for hits, advance, want_rem, want_status in steps:
        t += advance
        rl = one(
            engine,
            req(hits=hits, limit=5, duration=50, algorithm=Algorithm.LEAKY_BUCKET),
            now=t,
        )
        assert rl.status == want_status, (hits, advance)
        assert rl.remaining == want_rem, (hits, advance)
        assert rl.limit == 5


def test_sticky_over_on_oversized_creation(engine):
    rl = one(engine, req(hits=10, limit=5, duration=SECOND), now=T0)
    assert (rl.status, rl.remaining) == (Status.OVER_LIMIT, 5)
    rl = one(engine, req(hits=2, limit=5, duration=SECOND), now=T0)
    assert (rl.status, rl.remaining) == (Status.OVER_LIMIT, 3)


def test_leaky_peek_at_empty_reports_over(engine):
    lk = dict(limit=5, duration=SECOND, algorithm=Algorithm.LEAKY_BUCKET)
    one(engine, req(hits=5, **lk), now=T0)
    rl = one(engine, req(hits=0, **lk), now=T0)
    assert rl.status == Status.OVER_LIMIT
    assert rl.reset_time != 0


def test_algorithm_switch_recreates_as_token(engine):
    one(
        engine,
        req(hits=1, limit=5, duration=SECOND, algorithm=Algorithm.LEAKY_BUCKET),
        now=T0,
    )
    rl = one(engine, req(hits=1, limit=5, duration=SECOND), now=T0)
    assert rl.remaining == 4  # fresh token window

    engine.reset()
    one(engine, req(hits=3, limit=5, duration=SECOND), now=T0)
    rl = one(
        engine,
        req(hits=1, limit=5, duration=SECOND, algorithm=Algorithm.LEAKY_BUCKET),
        now=T0,
    )
    assert rl.remaining == 4  # recreated as fresh *token* bucket
    assert rl.reset_time == T0 + SECOND


def test_zero_limit_token(engine):
    rl = one(engine, req(hits=1, limit=0, duration=10_000), now=T0)
    assert rl.status == Status.OVER_LIMIT
    assert rl.remaining == 0


def test_global_replica_read(engine):
    # owner broadcast installs a replica; gnp reads serve it verbatim
    engine.update_globals(
        [
            (
                "test_account:g1",
                RateLimitResp(
                    status=Status.UNDER_LIMIT, limit=5, remaining=4,
                    reset_time=T0 + 3000,
                ),
            )
        ],
        now=T0,
    )
    r = RateLimitReq(
        name="test", unique_key="account:g1", hits=1, limit=5, duration=3000
    )
    r_key = r.hash_key()
    assert r_key == "test_account:g1"
    rl = one(engine, r, now=T0, gnp=True)
    check_same(
        rl,
        RateLimitResp(
            status=Status.UNDER_LIMIT, limit=5, remaining=4, reset_time=T0 + 3000
        ),
    )
    # replica unchanged by the read
    rl = one(engine, r, now=T0, gnp=True)
    assert rl.remaining == 4


def test_global_replica_miss_processes_locally(engine):
    r = req(unique_key="account:g2", hits=1, limit=5, duration=3000)
    rl = one(engine, r, now=T0, gnp=True)
    assert (rl.status, rl.remaining) == (Status.UNDER_LIMIT, 4)
    # the local (owned-style) entry now serves as the replica
    rl = one(engine, r, now=T0, gnp=True)
    assert rl.remaining == 4


# ------------------------------------------------------ intra-batch semantics


def test_batch_duplicate_uniform_hits(engine):
    rs = [req(hits=1, limit=3, duration=SECOND) for _ in range(5)]
    resp = engine.get_rate_limits(rs, now=T0)
    got = [(r.status, r.remaining) for r in resp]
    assert got == [
        (Status.UNDER_LIMIT, 2),
        (Status.UNDER_LIMIT, 1),
        (Status.UNDER_LIMIT, 0),
        (Status.OVER_LIMIT, 0),
        (Status.OVER_LIMIT, 0),
    ]


def test_batch_duplicate_oversized_does_not_starve(engine):
    rs = [
        req(hits=2, limit=5, duration=SECOND),
        req(hits=100, limit=5, duration=SECOND),  # refused outright
        req(hits=3, limit=5, duration=SECOND),  # still admitted
    ]
    resp = engine.get_rate_limits(rs, now=T0)
    assert resp[0].status == Status.UNDER_LIMIT
    assert resp[1].status == Status.OVER_LIMIT
    assert resp[2].status == Status.UNDER_LIMIT
    assert resp[2].remaining == 0


def test_batch_refused_duplicates_do_not_poison_sticky(engine):
    # Refused duplicates inflate the attempted prefix but consume nothing;
    # the persisted sticky-OVER flag must track *real* depletion only.
    rs = [req(hits=3, limit=5, duration=SECOND) for _ in range(3)]
    resp = engine.get_rate_limits(rs, now=T0)
    assert [r.status for r in resp] == [
        Status.UNDER_LIMIT, Status.OVER_LIMIT, Status.OVER_LIMIT,
    ]
    # Store remaining is 2; a later small request must succeed UNDER_LIMIT.
    rl = one(engine, req(hits=1, limit=5, duration=SECOND), now=T0 + 1)
    assert (rl.status, rl.remaining) == (Status.UNDER_LIMIT, 1)


def test_batch_leaky_refused_follower_reset_uses_request_duration(engine):
    lk = dict(limit=1, duration=60_000, algorithm=Algorithm.LEAKY_BUCKET)
    rs = [req(hits=1, **lk), req(hits=1, **lk)]
    resp = engine.get_rate_limits(rs, now=T0)
    assert resp[0].status == Status.UNDER_LIMIT
    assert resp[1].status == Status.OVER_LIMIT
    # retry hint is one leak interval (duration/limit), not a stale slot's
    assert resp[1].reset_time == T0 + 60_000


def test_batch_distinct_keys_independent(engine):
    rs = [
        req(unique_key=f"k{i}", hits=1, limit=2, duration=SECOND)
        for i in range(10)
    ]
    resp = engine.get_rate_limits(rs, now=T0)
    assert all(r.remaining == 1 for r in resp)
    resp = engine.get_rate_limits(rs, now=T0)
    assert all(r.remaining == 0 for r in resp)


# ------------------------------------------------------------- differential


def _random_req(rng, keys):
    algo = rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET])
    return RateLimitReq(
        name="fuzz",
        unique_key=rng.choice(keys),
        hits=rng.choice([0, 1, 1, 1, 2, 3, 7, 50]),
        limit=rng.choice([1, 2, 5, 20]),
        duration=rng.choice([10, 100, 1000]),
        algorithm=algo,
    )


def test_differential_fuzz_vs_oracle(engine):
    """Random single-key-per-batch workload over an advancing clock: the
    device path must match the oracle decision-for-decision."""
    rng = random.Random(1234)
    keys = [f"acct:{i}" for i in range(40)]
    cache = LRUCache()
    now = T0
    for step in range(400):
        now += rng.choice([0, 1, 3, 7, 15, 40, 200])
        # unique keys within the batch so oracle sequencing matches exactly
        batch_keys = rng.sample(keys, rng.randint(1, 12))
        rs = []
        for k in batch_keys:
            r = _random_req(rng, [k])
            rs.append(r)
        got = engine.get_rate_limits(rs, now=now)
        for r, g in zip(rs, got):
            want = get_rate_limit(cache, r, now=now)
            check_same(g, want, ctx=f"step={step} key={r.unique_key} req={r}")


def test_differential_sequential_same_key(engine):
    """Long same-key request sequences (one per batch) across both
    algorithms and window resets."""
    rng = random.Random(99)
    cache = LRUCache()
    now = T0
    for algo in (Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET):
        engine.reset()
        cache = LRUCache()
        for step in range(200):
            now += rng.choice([0, 1, 2, 5, 11, 37])
            r = RateLimitReq(
                name="seq",
                unique_key="only",
                hits=rng.choice([0, 1, 1, 2, 4, 9]),
                limit=8,
                duration=60,
                algorithm=algo,
            )
            got = one(engine, r, now=now)
            want = get_rate_limit(cache, r, now=now)
            check_same(got, want, ctx=f"algo={algo} step={step} now={now}")


def test_eviction_recreates_window(engine):
    """Overfilling the store evicts oldest-expiry entries; evicted keys are
    simply recreated (the reference's accepted over-admission contract)."""
    small = TpuEngine(StoreConfig(rows=2, slots=16), buckets=(64,))
    rs = [
        req(unique_key=f"spill:{i}", hits=1, limit=5, duration=SECOND)
        for i in range(32)
    ]
    resp = small.get_rate_limits(rs, now=T0)
    assert all(r.remaining == 4 for r in resp)
    # 32 keys in a 2x16 store: many were evicted; recreated windows give
    # remaining == 4 again instead of 3 (over-admission, never a crash)
    resp = small.get_rate_limits(rs, now=T0 + 1)
    assert all(r.remaining in (3, 4) for r in resp)
    assert any(r.remaining == 4 for r in resp)


# ------------------------------------------------------- presorted kernel


def test_presorted_equals_wrapper_with_interspersed_invalids():
    """decide_presorted under the caller contract (host-sorted rows,
    padding repeats the last key, invalid rows possibly interspersed as
    the mesh's ownership masking produces) matches the self-sorting
    decide() wrapper row for row, and writes the same store."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gubernator_tpu.core.kernels import (
        BatchRequest,
        decide,
        decide_presorted,
    )
    from gubernator_tpu.core.store import (
        StoreConfig,
        group_sort_key_np,
        new_store,
    )

    rng = np.random.default_rng(7)
    cfg = StoreConfig(rows=16, slots=1 << 10)
    B, n = 64, 50

    for trial in range(4):
        keys = rng.integers(1, 2**63, n, dtype=np.int64).astype(np.uint64)
        keys = keys[rng.integers(0, n, n)]  # force duplicate keys
        hits = rng.integers(0, 4, n).astype(np.int64)
        limit = rng.integers(1, 6, n).astype(np.int64)
        duration = np.full(n, 60_000, np.int64)
        algo = rng.integers(0, 2, n).astype(np.int32)
        # per-key validity mask (the mesh masks whole key groups)
        key_valid = {k: bool(rng.random() < 0.7) for k in set(keys.tolist())}
        valid_n = np.asarray([key_valid[k] for k in keys.tolist()])

        # --- host-sorted presorted request, padding repeats last row ----
        skey = group_sort_key_np(keys, cfg.slots)
        order = np.argsort(skey, kind="stable")

        def pad(x, fill_from_last=True):
            out = np.empty(B, x.dtype)
            out[:n] = x[order]
            out[n:] = out[n - 1]
            return out

        valid = np.zeros(B, bool)
        valid[:n] = valid_n[order]
        req_sorted = BatchRequest(
            key_hash=jnp.asarray(pad(keys)),
            hits=jnp.asarray(pad(hits.astype(np.int32))),
            limit=jnp.asarray(pad(limit.astype(np.int32))),
            duration=jnp.asarray(pad(duration.astype(np.int32))),
            algo=jnp.asarray(pad(algo)),
            gnp=jnp.zeros(B, bool),
            valid=jnp.asarray(valid),
        )

        # --- same batch, original order, through the wrapper ------------
        def pad0(x, dtype):
            out = np.zeros(B, dtype)
            out[:n] = x
            return out

        valid0 = np.zeros(B, bool)
        valid0[:n] = valid_n
        req_orig = BatchRequest(
            key_hash=jnp.asarray(pad0(keys, np.uint64)),
            hits=jnp.asarray(pad0(hits, np.int32)),
            limit=jnp.asarray(pad0(limit, np.int32)),
            duration=jnp.asarray(pad0(duration, np.int32)),
            algo=jnp.asarray(pad0(algo, np.int32)),
            gnp=jnp.zeros(B, bool),
            valid=jnp.asarray(valid0),
        )

        now = jnp.int32(1000 + trial)
        s1, r1, st1 = jax.jit(decide_presorted)(
            new_store(cfg), req_sorted, now
        )
        s2, r2, st2 = jax.jit(decide)(new_store(cfg), req_orig, now)

        # unpermute the presorted responses host-side
        for f in ("status", "limit", "remaining", "reset_time"):
            a = np.asarray(getattr(r1, f))[:n]
            u = np.empty_like(a)
            u[order] = a
            b = np.asarray(getattr(r2, f))[:n]
            np.testing.assert_array_equal(
                u[valid_n], b[valid_n], err_msg=f"{f} trial={trial}"
            )
        np.testing.assert_array_equal(
            np.asarray(s1.data), np.asarray(s2.data), err_msg="store"
        )
        assert int(st1.hits) == int(st2.hits)
        assert int(st1.misses) == int(st2.misses)


def test_pallas_sweep_matches_scatter():
    """The opt-in pallas store-sweep writeback must be bit-identical to
    the XLA scatter-add on way-disjoint delta rows (interpret mode on
    CPU; scripts run the same check compiled on real TPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gubernator_tpu.core import pallas_sweep as ps

    rng = np.random.default_rng(11)
    buckets, B = 1 << 10, 2048
    data = rng.integers(-2**31, 2**31 - 1, (buckets, 128), dtype=np.int64
                        ).astype(np.int32)
    bkt = np.sort(rng.integers(0, buckets, B)).astype(np.int32)
    # way-disjoint rows: each duplicate run takes a distinct 8-lane way
    drow = np.zeros((B, 128), np.int32)
    run = 0
    vals = rng.integers(-2**31, 2**31 - 1, (B, 8), dtype=np.int64
                        ).astype(np.int32)
    for i in range(B):
        run = run + 1 if i and bkt[i] == bkt[i - 1] else 0
        w = run % 16
        if rng.random() < 0.7:
            drow[i, w * 8 : (w + 1) * 8] = vals[i]
    want = data.copy()
    np.add.at(want, bkt, drow)

    got = ps._apply_inline(
        jnp.asarray(data), jnp.asarray(bkt), jnp.asarray(drow),
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), want)


def test_writeback_auto_selection(monkeypatch):
    """GUBER_WRITEBACK=auto (default) picks the pallas sweep exactly in
    its measured winning regime (B >= 4x bucket count, see
    scripts/bench_sweep_regime.py) and the scatter elsewhere; explicit
    values force a path."""
    import jax as jax_mod

    from gubernator_tpu.core.kernels import writeback_form

    def sweeps(buckets, W, B):
        return writeback_form(buckets, W, B) == "sweep"

    monkeypatch.delenv("GUBER_WRITEBACK", raising=False)
    # auto never picks the Mosaic TPU kernel on a non-TPU backend
    assert not sweeps(2048, 128, 16384)
    # ... the regime assertions below model a TPU host
    monkeypatch.setattr(jax_mod, "default_backend", lambda: "tpu")
    # flagship store (32k buckets, 32k batch): density 1 -> scatter
    assert not sweeps(1 << 15, 128, 1 << 15)
    # dense small-store regime: density >= 4 -> sweep
    assert sweeps(2048, 128, 16384)
    assert sweeps(4096, 128, 32768)
    # shape constraints still gate the sweep even in its regime
    assert not sweeps(2048, 64, 16384)  # W != 128
    assert not sweeps(100, 128, 16384)  # buckets % 128

    monkeypatch.setenv("GUBER_WRITEBACK", "scatter")
    assert not sweeps(2048, 128, 16384)
    monkeypatch.setenv("GUBER_WRITEBACK", "sweep")
    assert sweeps(1 << 15, 128, 16384)


# every (rows of the table a decide sees, rows of its writeback) a cell
# traces: the default ladder into the default, the 10M-key and the
# 100M-key store, and the mesh's sub-rungs into one shard's 2^18 rows.
# A group rung is never above its rung, so the rungs bound the
# writeback's rows. All the unhinted scatter but one: a full
# 1024-group batch into the default store's 2^15 rows sits AT rows / 32,
# where the hinted pass (61 us) still beats 1024 row touches (76 us).
_SERVED_SHAPES = [
    (1 << r, B) for r in (15, 20, 24) for B in (64, 256, 1024)
] + [(1 << 18, B) for B in (64, 96, 128, 192, 256, 384, 512, 768, 1024)]


_SERVED_FORMS = {
    shape: "scatter_sorted" if shape == (1 << 15, 1024) else "scatter"
    for shape in _SERVED_SHAPES
}


@pytest.mark.parametrize("rows,B,auto,scatter_mode", [
    *((*shape, form, form) for shape, form in _SERVED_FORMS.items()),
    # the 1024 rung's lower group rungs into the default store
    (1 << 15, 384, "scatter", "scatter"),
    (1 << 15, 768, "scatter", "scatter"),
    # deep batches (GUBER_DEVICE_DEEP_BATCH, cli/bench_serving.py; no
    # cell): the hint from B = rows / 32 up, the sweep from 4 x rows up
    (1 << 15, 16384, "scatter_sorted", "scatter_sorted"),
    (1 << 15, 131072, "sweep", "scatter_sorted"),
    (1 << 18, 16384, "scatter_sorted", "scatter_sorted"),
    (1 << 20, 16384, "scatter", "scatter"),
    (1 << 24, 16384, "scatter", "scatter"),
])
def test_writeback_form_at_every_traced_shape(
    monkeypatch, rows, B, auto, scatter_mode
):
    """The form is a function of the traced shapes alone: the same
    answer for a flat table and for one shard of a mesh's, whatever the
    deployment is called."""
    import jax as jax_mod

    from gubernator_tpu.core.kernels import writeback_form

    monkeypatch.setattr(jax_mod, "default_backend", lambda: "tpu")
    monkeypatch.delenv("GUBER_WRITEBACK", raising=False)
    assert writeback_form(rows, 128, B) == auto
    # =scatter keeps the sweep out and leaves the hint to the shapes
    monkeypatch.setenv("GUBER_WRITEBACK", "scatter")
    assert writeback_form(rows, 128, B) == scatter_mode
    # off the TPU auto is =scatter
    monkeypatch.delenv("GUBER_WRITEBACK")
    monkeypatch.setattr(jax_mod, "default_backend", lambda: "cpu")
    assert writeback_form(rows, 128, B) == scatter_mode


@pytest.mark.parametrize("rows,B,hinted", [
    (1 << 24, 1024, False),  # exact100m's top rung
    (1 << 15, 64, False),  # the default daemon's bottom rung
    (1 << 15, 1024, True),  # its top rung, every key its own group
    (1 << 15, 16384, True),  # a deep batch into the default store
])
def test_writeback_apply_lowers_the_form_it_chose(rows, B, hinted):
    """_writeback_apply traced at a served and at a deep shape (shapes
    only: no table is made): ONE scatter-add, and its
    indices_are_sorted is what writeback_form said."""
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.core import kernels as K
    from gubernator_tpu.core.store import LANES

    closed = jax.make_jaxpr(
        lambda d, b, r: K._writeback_apply(
            d, b, jnp.ones(b.shape, bool), jnp.zeros(b.shape, jnp.int32),
            r[:, :LANES], r.reshape(b.shape[0], 16, LANES))
    )(
        jax.ShapeDtypeStruct((rows, 128), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B, 128), jnp.int32),
    )
    scatters = [
        e for e in closed.jaxpr.eqns if e.primitive.name == "scatter-add"
    ]
    assert len(scatters) == 1
    assert scatters[0].params["indices_are_sorted"] is hinted
    assert hinted == (K.writeback_form(rows, 128, B) == "scatter_sorted")
    # duplicate buckets are the rule, under either form
    assert scatters[0].params["unique_indices"] is False
