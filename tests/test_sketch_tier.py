"""Sketch cold tier (r13/r21): identity, fail-closed error, promotion.

The two-tier contract under test (core/kernels.decide_presorted_sketch,
core/sketches.py, serve/promoter.py):

- exact-tier keys are BYTE-IDENTICAL with the tier on vs off — the
  sketch only changes the fate of creates the exact store DROPS to way
  exhaustion, and store contents evolve identically either way (the
  writeback plan is sketch-independent), so with no drop pressure the
  two pipelines are indistinguishable end to end for ALL FOUR
  algorithms (differential fuzz, exact-capacity stores, device
  tpu-on-cpu pipeline, r10 fake clock);
- under pressure, every divergent row is AT-LEAST-AS-RESTRICTIVE with
  the tier on (status >=, remaining <=): sketch estimates never
  under-count the hits they were charged with, so the error is
  one-sided — fail-closed, matching the shed cache's stance. Since
  r21 this covers sliding (window-ring blend) and GCRA (TAT-quantized
  reconstruction), each pinned bit-exact against its host twin
  (algorithms.sketch_sliding_budget / sketch_gcra_budget) on
  pinned-bucket single-key drives across rotations and clock jumps.
  NOTE the scoping: strict per-request dominance vs the EXACT oracle
  is impossible once refusal histories diverge (an early sketch
  refusal leaves budget an exact path would have consumed), so the
  row-wise property is asserted ON-vs-OFF — the OFF engine serves
  dropped creates as phantom-fresh windows, strictly more permissive;
- the measured tail error on a pinned zipf stream stays within the
  documented e*N/width bound with ZERO under-counts, and the v2
  derivation (saturating int32 counters, core/sketches.py) yields a
  strictly tighter bound than r13 at the same byte budget (the
  property the BENCH_SKETCH_r21.json acceptance commits);
- device and host sketch indexing are bit-identical twins;
- promotion migrates the estimate into an exact bucket (the window
  continues, then the key decides exactly), never clobbers live exact
  state, and feeds over-limit candidates to the shed cache.
"""

import asyncio

import numpy as np
import pytest

import gubernator_tpu.core  # noqa: F401  (x64)
from gubernator_tpu.api.types import (
    Algorithm,
    PeerInfo,
    RateLimitReq,
    Status,
)
from gubernator_tpu.core.engine import TpuEngine
from gubernator_tpu.core.sketches import (
    SketchConfig,
    derive_sketch_config,
    new_sketch,
    sketch_footprint_bytes,
    sketch_indices_np,
    window_id_np,
)
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.serve.backends import TpuBackend
from gubernator_tpu.serve.config import ServerConfig
from gubernator_tpu.serve.instance import Instance
from gubernator_tpu.serve.shedcache import ShedCache

T0 = 1_700_000_000_000
ADDR = "127.0.0.1:7973"


class FakeClock:
    def __init__(self, t=T0):
        self.t = t

    def __call__(self) -> int:
        return self.t


def _pin_clock(monkeypatch, clock):
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod
    import gubernator_tpu.core.oracle as oracle_mod

    monkeypatch.setattr(types_mod, "millisecond_now", clock)
    monkeypatch.setattr(engine_mod, "millisecond_now", clock)
    monkeypatch.setattr(oracle_mod, "millisecond_now", clock)


def _pressure_engine(sketch=True, width=1 << 12):
    """1-way 16-bucket store: trivial to saturate, so drops flow."""
    return TpuEngine(
        StoreConfig(rows=1, slots=16),
        buckets=(64, 256),
        sketch=SketchConfig(rows=4, width=width) if sketch else None,
    )


def _keys(n, tag=7):
    # distinct fingerprints (id << 32) spread over buckets
    return (np.arange(1, n + 1, dtype=np.uint64) << np.uint64(32)) | (
        np.uint64(tag)
    )


def _pin_buckets(eng, nf=16):
    """Fill EVERY bucket's single way with an immortal filler (one key
    per bucket, cli/bench_serving._filler_hashes) and return the filler
    hashes: included in each later batch as peeks, they are found-
    writers, so a rank-0 miss can never evict them — every measured
    key provably decides on the sketch tier."""
    from gubernator_tpu.cli.bench_serving import _filler_hashes

    fillers = _filler_hashes(eng.config.slots)
    ones = np.ones(fillers.shape[0], np.int64)
    eng.decide_arrays(
        fillers, ones, ones * 1000, ones * 1_000_000_000,
        np.zeros(fillers.shape[0], np.int32),
        np.zeros(fillers.shape[0], bool), T0,
    )
    return fillers


# -- config / sizing --------------------------------------------------------


def test_sketch_config_and_derivation():
    # r13 derivation: 4 rows of int64 — the committed r13 geometry
    c = derive_sketch_config(mib=16, rows=4, derivation="r13")
    assert c.width == 1 << 19 and c.counter_bytes == 8
    assert sketch_footprint_bytes(c) == 16 << 20
    assert derive_sketch_config(mib=8, derivation="r13").width == 1 << 18
    # v2 derivation (default): 2 rows of saturating int32 — 4x the
    # width at the same budget, so a 4x tighter additive error bound
    v = derive_sketch_config(mib=8)
    assert v.rows == 2 and v.counter_bytes == 4
    assert v.width == 1 << 20
    assert sketch_footprint_bytes(v) == 8 << 20
    # explicit rows keep the derivation's counter dtype
    assert derive_sketch_config(mib=16, rows=4).width == 1 << 20
    # direct construction stays r13-compatible (int64 default)
    assert SketchConfig(rows=4, width=1 << 12).counter_bytes == 8
    import jax.numpy as jnp

    assert new_sketch(v).data.dtype == jnp.int32
    assert new_sketch(c).data.dtype == jnp.int64
    with pytest.raises(AssertionError):
        SketchConfig(rows=4, width=1000)  # not a power of two
    with pytest.raises(AssertionError):
        SketchConfig(rows=9, width=1 << 10)  # more rows than salts
    with pytest.raises(AssertionError):
        SketchConfig(rows=2, width=1 << 10, counter_bytes=2)
    with pytest.raises(ValueError):
        derive_sketch_config(mib=0)
    with pytest.raises(ValueError):
        derive_sketch_config(mib=8, derivation="r12")


def test_store_mib_carve_out_and_host_budget():
    """GUBER_STORE_MIB covers BOTH tiers: the exact tier shrinks by the
    sketch's resolved footprint; an impossible split fails loudly; the
    whole-host lint flags shed/standby overflow."""
    from gubernator_tpu.core.store import (
        check_host_budget,
        check_store_budget,
        store_footprint_bytes,
    )

    full = ServerConfig(
        backend="tpu", store_mib=1024, sketch=False
    ).store_config()
    carved = ServerConfig(
        backend="tpu", store_mib=1024, sketch=True, sketch_mib=256
    ).store_config()
    assert store_footprint_bytes(carved) <= (1024 - 256) << 20
    assert store_footprint_bytes(carved) < store_footprint_bytes(full)
    # mesh carries the sharded sketch since r14: same carve-out as tpu;
    # multihost joins in r20 (promotion + estimate reads are lockstep
    # collectives), so its budget carves identically too
    mesh = ServerConfig(
        backend="mesh", store_mib=1024, sketch=True, sketch_mib=256
    ).store_config()
    assert store_footprint_bytes(mesh) == store_footprint_bytes(carved)
    mh = ServerConfig(
        backend="multihost", store_mib=1024, sketch=True, sketch_mib=256
    ).store_config()
    assert store_footprint_bytes(mh) == store_footprint_bytes(carved)
    with pytest.raises(ValueError):
        ServerConfig(
            backend="tpu", store_mib=16, sketch=True, sketch_mib=16
        ).store_config()
    # tiny budget + AUTO sketch: the tier auto-disables (pre-r13 tiny
    # configs keep booting); the hard refusal is reserved for an
    # EXPLICIT GUBER_SKETCH_MIB (review finding)
    tiny = ServerConfig(backend="tpu", store_mib=1, sketch=True)
    assert tiny.sketch_config() is None
    assert store_footprint_bytes(tiny.store_config()) == 1 << 20
    with pytest.raises(ValueError):
        ServerConfig(
            backend="tpu", store_mib=1, sketch=True, sketch_mib=1
        ).store_config()
    # cold_tier suppresses the undersize lint (tail overflow is the
    # sketch's job) but keeps the oversize lint
    small = ServerConfig(backend="tpu", store_mib=64, sketch=False)
    sc = small.store_config()
    assert check_store_budget(sc, 100_000_000) != ""
    assert check_store_budget(sc, 100_000_000, cold_tier=True) == ""
    assert check_store_budget(sc, 1000, cold_tier=True) != ""  # oversize
    # whole-host budget: parts must fit the declared MiB
    assert check_host_budget(1, {"a": 2 << 20}) != ""
    assert check_host_budget(4, {"a": 2 << 20, "b": 1 << 20}) == ""
    assert check_host_budget(0, {"a": 1 << 30}) == ""  # no budget


def test_install_windows_chunks_past_ladder_top():
    """A promotion batch larger than the bucket ladder's top rung is
    chunked, not refused — GUBER_SKETCH_TOPK has no relation to the
    ladder, and a choose_bucket refusal would wedge every promotion
    tick (review finding)."""
    eng = TpuEngine(
        StoreConfig(rows=16, slots=1 << 8), buckets=(64,),
        sketch=SketchConfig(rows=4, width=1 << 12),
    )
    n = 150  # > ladder top 64
    kh = _keys(n)
    eng.install_windows(
        kh, np.full(n, 10, np.int64), np.full(n, 5, np.int64),
        np.full(n, T0 + 60_000, np.int64), np.zeros(n, bool), T0,
    )
    assert eng.live_mask(kh, T0 + 1).all()


def test_host_budget_strict_gates_on_explicit_host_knobs(caplog):
    """STRICT + tiny budget + DEFAULT shed cache must still boot (the
    default shed alone overflows small budgets — failing would regress
    every pre-r13 strict config); an EXPLICITLY oversized host part
    under STRICT refuses (review finding)."""
    import logging

    from gubernator_tpu.serve.server import make_backend

    conf = ServerConfig(
        backend="tpu", store_mib=16, store_size_strict=True,
        device_batch_limit=1000,
    )
    with caplog.at_level(logging.WARNING):
        make_backend(conf)  # boots; the lint only warns
    assert any("exceeded" in r.message for r in caplog.records)
    with pytest.raises(ValueError, match="STRICT"):
        make_backend(
            ServerConfig(
                backend="tpu", store_mib=16, store_size_strict=True,
                shed_cache_keys=1_000_000, device_batch_limit=1000,
            )
        )


def test_sketch_knob_validation():
    # 0 rows = derivation default since r21 (v2: 2, r13: 4)
    ServerConfig(sketch_rows=0).validate()
    skc = ServerConfig(backend="tpu", sketch_rows=0).sketch_config()
    assert skc.rows == 2 and skc.counter_bytes == 4
    r13 = ServerConfig(
        backend="tpu", sketch_derivation="r13"
    ).sketch_config()
    assert r13.rows == 4 and r13.counter_bytes == 8
    with pytest.raises(ValueError):
        ServerConfig(sketch_rows=-1).validate()
    with pytest.raises(ValueError):
        ServerConfig(sketch_rows=9).validate()
    with pytest.raises(ValueError):
        ServerConfig(sketch_derivation="r12").validate()
    with pytest.raises(ValueError):
        ServerConfig(sketch_mib=-1).validate()
    with pytest.raises(ValueError):
        ServerConfig(sketch_topk=0).validate()


def test_algo_registry_pins():
    """The r21 registry audit: every eligibility gate derives from
    core/algorithms.ALGORITHMS, and the three gates intentionally
    DIFFER — widening one without auditing its consumers must fail
    here, loudly, instead of silently shipping the r15 assumption
    (sketch tier == token/leaky only) into a consumer.

    - SKETCH_SERVABLE: all four. The window-ring (r21) reconstructs
      sliding and GCRA budgets one-sidedly from per-window counts.
    - PROMOTABLE: token ONLY. install_windows fabricates the token
      fixed-window layout; promoting a sliding/GCRA key would reset
      its phase and under-restrict. Ring keys are served, not promoted.
    - SHEDDABLE: token ONLY. The shed cache freezes an OVER verdict to
      the window end; sliding/GCRA budgets refill continuously, so a
      frozen verdict would over-restrict for up to a full window —
      serve them from the sketch tier instead (fail-closed but live).
    """
    from gubernator_tpu.core.algorithms import (
        ALGO_TOKEN,
        PROMOTABLE_ALGOS,
        SHEDDABLE_ALGOS,
        SKETCH_SERVABLE_ALGOS,
    )

    assert SKETCH_SERVABLE_ALGOS == {0, 1, 2, 3}
    assert PROMOTABLE_ALGOS == {ALGO_TOKEN}
    assert SHEDDABLE_ALGOS == {ALGO_TOKEN}
    # the gates are registry-derived, not parallel hand-written sets
    from gubernator_tpu.core.algorithms import ALGORITHMS

    assert SKETCH_SERVABLE_ALGOS == {
        a for a, s in ALGORITHMS.items() if s.sketch_servable
    }
    assert SHEDDABLE_ALGOS == {
        a for a, s in ALGORITHMS.items() if s.sheddable
    }
    # consumers import the gates (grep-level pin: shedcache asserts at
    # import time, promoter builds its mask from PROMOTABLE_ALGOS)
    from gubernator_tpu.serve import promoter as promoter_mod

    assert set(promoter_mod._PROMOTABLE_IDS.tolist()) == PROMOTABLE_ALGOS


# -- indexing twins ---------------------------------------------------------


def test_device_host_index_twins():
    """The kernel's conservative update lands counts at EXACTLY the
    host-computed (row, index) positions: read the raw sketch array at
    sketch_indices_np positions and recover every charged count."""
    eng = _pressure_engine()
    n = 48
    kh = _keys(n)
    ones = np.ones(n, np.int64)
    dur = np.full(n, 10_000, np.int64)
    eng.decide_arrays(
        kh, ones, ones * 100, dur, np.zeros(n, np.int32),
        np.zeros(n, bool), T0,
    )
    dropped = eng.stats.snapshot()["dropped"]
    assert dropped > 0
    e_now = int(eng.clock.to_engine(T0))
    idx = sketch_indices_np(
        kh, window_id_np(e_now, dur), eng.sketch_config
    )
    data = np.asarray(eng.sketch.data)
    per_row = np.stack(
        [data[r, idx[r]] for r in range(idx.shape[0])]
    )
    est_host = per_row.min(axis=0)
    est_engine = eng.sketch_estimates(kh, dur, T0 + 1)
    np.testing.assert_array_equal(est_host, est_engine)
    # exactly the dropped keys carry charge 1, the rest 0
    assert int((est_engine == 1).sum()) == dropped
    assert int((est_engine == 0).sum()) == n - dropped


# -- tier semantics ---------------------------------------------------------


def test_sketch_tier_fixed_window_semantics():
    """A sketch-served key follows fixed-window token math: budget
    drains across batches, freezes OVER at the limit with reset = the
    window's end, and the next window starts fresh."""
    eng = _pressure_engine()
    fillers = _pin_buckets(eng)
    nf = fillers.shape[0]
    # one measured key + the fillers in every batch (found-writers
    # block rank-0 eviction, so the key always drops to the sketch)
    key = _keys(1, tag=9)[:1]
    DUR, LIM = 10_000, 3
    for i in range(5):
        kh = np.concatenate([fillers, key])
        hits = np.concatenate([np.zeros(nf, np.int64), [1]])
        s, l, r, t = eng.decide_arrays(
            kh, hits, np.full(nf + 1, LIM, np.int64),
            np.full(nf + 1, DUR, np.int64),
            np.zeros(nf + 1, np.int32), np.zeros(nf + 1, bool),
            T0 + i,
        )
        # engine-ms: the epoch pins ONE ms before first contact (r15,
        # core/engine.py EpochClock — engine 0 is the wire's no-reset
        # sentinel), so the fixed-window grid anchors at T0 - 1
        e_now = (T0 + i) - (T0 - 1)
        window_end_unix = (T0 - 1) + ((e_now // DUR) + 1) * DUR
        if i < LIM:
            assert s[-1] == int(Status.UNDER_LIMIT)
            assert r[-1] == LIM - (i + 1)
        else:
            assert s[-1] == int(Status.OVER_LIMIT)
            assert r[-1] == 0
        assert t[-1] == window_end_unix
    # cross the window boundary: fresh budget
    t_next = T0 + DUR + 1
    kh = np.concatenate([fillers, key])
    hits = np.concatenate([np.zeros(nf, np.int64), [1]])
    s, l, r, t = eng.decide_arrays(
        kh, hits, np.full(nf + 1, LIM, np.int64),
        np.full(nf + 1, DUR, np.int64), np.zeros(nf + 1, np.int32),
        np.zeros(nf + 1, bool), t_next,
    )
    assert s[-1] == int(Status.UNDER_LIMIT) and r[-1] == LIM - 1


def test_reset_and_rebase_clear_sketch():
    eng = _pressure_engine()
    n = 48
    kh = _keys(n)
    ones = np.ones(n, np.int64)
    dur = np.full(n, 10_000, np.int64)
    eng.decide_arrays(
        kh, ones, ones * 100, dur, np.zeros(n, np.int32),
        np.zeros(n, bool), T0,
    )
    assert int(eng.sketch_estimates(kh, dur, T0 + 1).sum()) > 0
    eng.reset()
    assert int(np.asarray(eng.sketch.data).sum()) == 0


# -- differential identity --------------------------------------------------


def _twin_arrays(seed, slots, rows, steps=60, keyspace=24,
                 hit_pool=(0, 1, 1, 2), limit_pool=(5, 8, 50),
                 dur_pool=(400, 2000, 60_000),
                 dt_pool=(0, 1, 7, 500, 2500), token_only=False,
                 algo_pool=None):
    """Drive identical random array batches through sketch-ON and
    sketch-OFF engines; returns the per-step response pairs.
    `algo_pool` pins the algorithm draw (r15 suite ids); default is
    the historical token/leaky mix (or token-only)."""
    rng = np.random.default_rng(seed)
    cfg = StoreConfig(rows=rows, slots=slots)
    on = TpuEngine(cfg, buckets=(64, 256),
                   sketch=SketchConfig(rows=4, width=1 << 12))
    off = TpuEngine(cfg, buckets=(64, 256))
    pool = _keys(keyspace)
    t = T0
    out = []
    for step in range(steps):
        n = int(rng.integers(1, 48))
        kh = pool[rng.integers(0, keyspace, n)]
        hits = rng.choice(hit_pool, n).astype(np.int64)
        limit = rng.choice(limit_pool, n).astype(np.int64)
        dur = rng.choice(dur_pool, n).astype(np.int64)
        if algo_pool is not None:
            algo = rng.choice(algo_pool, n).astype(np.int32)
        elif token_only:
            algo = np.zeros(n, np.int32)
        else:
            algo = rng.integers(0, 2, n).astype(np.int32)
        gnp = np.zeros(n, bool)
        t += int(rng.choice(dt_pool))
        a = on.decide_arrays(kh, hits, limit, dur, algo, gnp, t)
        b = off.decide_arrays(kh, hits, limit, dur, algo, gnp, t)
        out.append((step, a, b))
    return on, off, out


@pytest.mark.parametrize("seed", [2, 13])
def test_on_off_identity_no_pressure(seed):
    """With the exact tier under capacity (no dropped creates), sketch
    ON is byte-identical to OFF — responses AND store contents — for
    ALL FOUR algorithms (r21: sliding/GCRA are sketch-servable now, so
    the identity must keep holding with them in the stream)."""
    on, off, steps = _twin_arrays(
        seed, slots=1 << 10, rows=16, algo_pool=(0, 1, 2, 3)
    )
    for step, a, b in steps:
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y, err_msg=f"step {step}")
    assert on.stats.snapshot()["dropped"] == 0
    np.testing.assert_array_equal(
        np.asarray(on.store.data), np.asarray(off.store.data)
    )


def test_on_off_pressure_is_fail_closed():
    """Under tier pressure every divergent response row is
    at-least-as-restrictive with the tier on (status >=, remaining <=),
    and live-victim protection shows in the stats: the OFF engine
    churns resident windows (evictions), the ON engine serves those
    creates from the sketch instead (dropped == sketch-served) and
    evicts strictly less. One duration and sub-window clock advances
    keep every step inside one aligned window — across a window
    boundary the fixed-window tier legitimately forgives earlier than
    a creation-anchored window (the standard fixed-window artifact,
    bounded at 2x limit per duration and documented); hits <= limit
    keeps the oversized-hit creation corner (which reports
    remaining=limit by reference contract) to the no-pressure fuzz,
    and ONE limit keeps `remaining` comparable (a mixed-param stream
    answers from STORED params on the exact tier but request params on
    the sketch tier — both documented, not comparable row-wise). Unit
    hits make the row-wise claim airtight: with h in {0,1} the sketch
    estimate dominates the exact tier's current-window consumption for
    every key (both admit the same prefix until the sketch refuses
    first or the exact tier churns-and-forgets), so status can only
    tighten and remaining only shrink; variable hit sizes reorder
    refusals legitimately and belong to the admitted-count bound, not
    a row-wise one. Token-only for the same reason: an algorithm
    switch RECREATES a resident window (count reset), and residency
    differs between the engines under pressure, so mixed-algo streams
    reset counts at engine-dependent times (covered by the
    no-pressure and serving identity fuzzes instead)."""
    on, off, steps = _twin_arrays(
        7, slots=16, rows=1, steps=80, keyspace=64,
        hit_pool=(0, 1, 1, 1), limit_pool=(50,),
        dur_pool=(600_000,), dt_pool=(0, 1, 7, 150), token_only=True,
    )
    diverged = 0
    for step, a, b in steps:
        sa, la, ra, ta = a
        sb, lb, rb, tb = b
        differ = (sa != sb) | (ra != rb) | (ta != tb) | (la != lb)
        diverged += int(differ.sum())
        assert (sa >= sb).all(), f"fail-open status at step {step}"
        assert (ra <= rb).all(), f"fail-open remaining at step {step}"
    assert diverged > 0, "pressure fuzz never engaged the sketch"
    s_on, s_off = on.stats.snapshot(), off.stats.snapshot()
    assert s_on["dropped"] > 0
    # live-victim protection: resident windows survive the tail storm
    assert s_on["evictions"] < s_off["evictions"]


@pytest.mark.parametrize("algo", [2, 3], ids=["sliding", "gcra"])
def test_window_ring_pressure_is_fail_closed(algo):
    """r21 window-ring: sliding/GCRA creates dropped to way exhaustion
    are served from the ring (sliding blend / TAT-quantized GCRA) and
    every served row is AT-LEAST-AS-RESTRICTIVE than the r15 bypass
    behavior — the OFF engine serves each dropped create as a
    phantom-fresh window with the full budget, the most permissive
    answer possible, so ANY correct sketch serving must dominate it
    row-wise (status >=, remaining <=). All buckets are pinned with
    immortal filler found-writers so every measured create provably
    drops in BOTH engines (the OFF engine never persists a measured
    key — asserted), which keeps the comparison clean across rotation
    boundaries and clock jumps: the dt pool crosses single and
    multiple window advances. Unit hits and one limit keep `remaining`
    row-comparable (see test_on_off_pressure_is_fail_closed). Strict
    dominance vs the EXACT r15 oracle is deliberately not claimed —
    impossible once refusal histories diverge (module docstring); the
    bit-exact semantics are pinned against the host twins in
    test_window_ring_twin_oracle instead."""
    on = _pressure_engine()
    off = _pressure_engine(sketch=False)
    fillers = _pin_buckets(on)
    np.testing.assert_array_equal(fillers, _pin_buckets(off))
    nf = fillers.shape[0]
    rng = np.random.default_rng(11)
    keyspace = 40
    pool = _keys(keyspace, tag=3)
    DUR, LIM = 10_000, 6
    t = T0
    diverged = 0
    for step in range(60):
        n = int(rng.integers(1, 24))
        kh_m = pool[rng.integers(0, keyspace, n)]
        hits_m = rng.choice((0, 1, 1, 1), n).astype(np.int64)
        t += int(rng.choice((0, 1, 7, 500, 2500, 12_000, 21_000)))
        kh = np.concatenate([fillers, kh_m])
        hits = np.concatenate([np.zeros(nf, np.int64), hits_m])
        lim = np.full(nf + n, LIM, np.int64)
        lim[:nf] = 1000  # fillers keep their own params
        dur = np.full(nf + n, DUR, np.int64)
        dur[:nf] = 1_000_000_000
        al = np.full(nf + n, algo, np.int32)
        al[:nf] = 0
        gnp = np.zeros(nf + n, bool)
        a = on.decide_arrays(kh, hits, lim, dur, al, gnp, t)
        b = off.decide_arrays(kh, hits, lim, dur, al, gnp, t)
        sa, _, ra, _ = a
        sb, _, rb, _ = b
        differ = (sa[nf:] != sb[nf:]) | (ra[nf:] != rb[nf:])
        diverged += int(differ.sum())
        assert (sa[nf:] >= sb[nf:]).all(), f"fail-open status @{step}"
        assert (ra[nf:] <= rb[nf:]).all(), f"fail-open remaining @{step}"
    assert diverged > 0, "pressure fuzz never engaged the ring"
    assert on.stats.snapshot()["dropped"] > 0
    assert int(np.asarray(on.sketch.data).sum()) > 0, (
        "ring never charged: sliding/GCRA are sketch-servable in r21"
    )
    # the OFF engine (r15 bypass behavior) never persisted a measured
    # key — every step really was phantom-fresh over there
    assert not off.live_mask(pool, t).any()


@pytest.mark.parametrize(
    "skc",
    [
        SketchConfig(rows=4, width=1 << 12),
        SketchConfig(rows=2, width=1 << 12, counter_bytes=4),
    ],
    ids=["r13-int64", "v2-int32"],
)
@pytest.mark.parametrize("algo", [2, 3], ids=["sliding", "gcra"])
def test_window_ring_twin_oracle(algo, skc):
    """A sketch-served sliding/GCRA key is BIT-EXACT against its host
    twin (algorithms.sketch_sliding_budget / sketch_gcra_budget fed
    host-read ring estimates) on a pinned-bucket single-key drive
    whose clock crosses rotation boundaries, multi-window jumps and
    sub-window advances — and the ring never under-counts the true
    charge log (est_cur >= charges the engine admitted per window).
    Runs on both counter derivations: int64 (r13) and saturating
    int32 (v2)."""
    from gubernator_tpu.core.algorithms import (
        gcra_params,
        sketch_gcra_budget,
        sketch_sliding_budget,
    )

    I32_MAX = (1 << 31) - 1
    eng = TpuEngine(
        StoreConfig(rows=1, slots=16), buckets=(64, 256), sketch=skc
    )
    fillers = _pin_buckets(eng)
    nf = fillers.shape[0]
    key = _keys(1, tag=11)[:1]
    DUR, LIM = 10_000, 4
    epoch = T0 - 1  # EpochClock pins one ms before first contact
    true_charges: dict = {}

    def ring_est(wid):
        data = np.asarray(eng.sketch.data)
        idx = sketch_indices_np(
            key, np.array([wid], np.int64), skc
        )
        return int(
            min(data[r, idx[r][0]] for r in range(skc.rows))
        )

    t = T0
    for dt in (0, 1, 1, 1, 1, 1, 3000, 1, 1, 6000, 1, 1, 15_000,
               1, 1, 1, 1, 25_001, 1, 2, 3, 9_999, 1):
        t += dt
        e_now = t - epoch
        wid = e_now // DUR
        est_cur = ring_est(wid)
        est_prev = ring_est(wid - 1)
        if algo == 2:
            budget, wend = sketch_sliding_budget(
                est_cur, est_prev, e_now, LIM, DUR
            )
            exp_reset = epoch + wend
        else:
            budget, tatq = sketch_gcra_budget(
                est_cur, est_prev, e_now, LIM, DUR
            )
            T_, tau = gcra_params(LIM, DUR)
            tatq_c = min(tatq, I32_MAX)
            if budget >= 1:  # this row charges
                exp_reset = epoch + min(tatq_c + T_, I32_MAX)
            else:
                exp_reset = epoch + min(tatq_c + T_ - tau, I32_MAX)
        charged = budget >= 1
        exp_status = Status.UNDER_LIMIT if charged else Status.OVER_LIMIT
        exp_rem = budget - 1 if charged else 0
        kh = np.concatenate([fillers, key])
        hits = np.concatenate([np.zeros(nf, np.int64), [1]])
        lim = np.full(nf + 1, LIM, np.int64)
        lim[:nf] = 1000
        dur = np.full(nf + 1, DUR, np.int64)
        dur[:nf] = 1_000_000_000
        al = np.full(nf + 1, algo, np.int32)
        al[:nf] = 0
        s, l, r, ts = eng.decide_arrays(
            kh, hits, lim, dur, al, np.zeros(nf + 1, bool), t
        )
        assert s[-1] == int(exp_status), f"status @t={t}"
        assert r[-1] == exp_rem, f"remaining @t={t}"
        assert ts[-1] == exp_reset, f"reset @t={t}"
        assert l[-1] == LIM
        if charged:
            true_charges[wid] = true_charges.get(wid, 0) + 1
            # zero under-count: the ring re-read AFTER the charge
            # covers everything admitted this window
            assert ring_est(wid) >= true_charges[wid]
    assert len(true_charges) >= 3, "drive never crossed rotations"
    assert sum(true_charges.values()) > 0


def test_on_off_identity_serving_device(monkeypatch):
    """The serve-level mirror of the identity fuzz: GUBER_SKETCH on vs
    off through the REAL pipeline (instance -> batcher -> arrival prep
    -> merged submit -> kernel, tpu-on-cpu) under the r10 fake clock,
    with an under-capacity store — byte-identical responses, clock
    advances crossing reset boundaries mid-fuzz."""
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    def be(sk: bool):
        return TpuBackend(
            StoreConfig(rows=16, slots=1 << 10), buckets=(16, 64),
            sketch=SketchConfig(rows=4, width=1 << 12) if sk else None,
        )

    async def mk(sk: bool):
        conf = ServerConfig(
            grpc_address=ADDR, advertise_address=ADDR, sketch=sk,
            # a huge tick so no promoter flush fires mid-fuzz; the
            # promoter is inert anyway with zero drops
            sketch_sync_wait=600.0,
        )
        inst = Instance(conf, be(sk))
        inst.start()
        await inst.set_peers([PeerInfo(address=ADDR, is_owner=True)])
        return inst

    async def run():
        on = await mk(True)
        off = await mk(False)
        assert on.promoter is not None and off.promoter is None
        if on.shed is not None:
            on.shed.now_fn = clock
        if off.shed is not None:
            off.shed.now_fn = clock
        try:
            rng = np.random.default_rng(9)
            keys = [f"s{i}" for i in range(14)]
            for step in range(140):
                clock.t += int(rng.choice([0, 1, 7, 150, 2500]))
                n = int(rng.integers(1, 7))
                batch = [
                    RateLimitReq(
                        name="skfuzz",
                        unique_key=keys[int(rng.integers(len(keys)))],
                        hits=int(rng.choice([0, 1, 1, 2, 9])),
                        limit=int(rng.choice([1, 2, 3, 50])),
                        duration=int(rng.choice([400, 2000, 60_000])),
                        algorithm=Algorithm(int(rng.integers(2))),
                    )
                    for _ in range(n)
                ]
                a = await on.get_rate_limits(batch)
                b = await off.get_rate_limits(batch)
                for x, y, r in zip(a, b, batch):
                    assert (
                        x.status, x.limit, x.remaining, x.reset_time,
                        x.error,
                    ) == (
                        y.status, y.limit, y.remaining, y.reset_time,
                        y.error,
                    ), (step, r, x, y)
            assert on.backend.stats()["dropped"] == 0
        finally:
            await on.stop()
            await off.stop()

    asyncio.run(run())


# -- error bound property ---------------------------------------------------


def test_tail_error_bound_and_no_undercount():
    """The committed acceptance property on a pinned zipf stream
    (cli/bench_serving.measure_tail_error, the same code path the
    BENCH_SKETCH_r21.json artifact runs): zero under-counts and max
    overestimate within the documented e*N/width bound — on the v2
    default AND under the r21 window-ring arms (sliding/GCRA charge the
    same per-window cells, so the one-sided bound carries over)."""
    from gubernator_tpu.cli.bench_serving import measure_tail_error

    err = measure_tail_error(batches=16)
    assert err["derivation"] == "v2" and err["counter_bytes"] == 4
    assert err["under_counts"] == 0, err
    assert err["within_bound"], err
    assert err["charged_hits"] > 0 and err["distinct_keys"] > 100
    for arm in ("sliding", "gcra"):
        e = measure_tail_error(batches=8, algorithm=arm)
        assert e["under_counts"] == 0, (arm, e)
        assert e["within_bound"], (arm, e)
        assert e["charged_hits"] > 0


def test_tail_error_derivation_ab_is_strictly_tighter():
    """The r21 derivation A/B (measure_tail_error_ab): at the SAME byte
    budget v2's bound is 4x tighter than r13's (2 rows of int32 -> 4x
    width) and its measured max overestimate sits strictly below r13's
    THEORETICAL bound — the per-byte win the tentpole commits — with
    zero under-counts on both geometries."""
    from gubernator_tpu.cli.bench_serving import measure_tail_error_ab

    ab = measure_tail_error_ab(batches=16)
    assert ab["zero_under_counts"], ab
    assert ab["v2_max_below_r13_bound"], ab
    # 4x width = 4x tighter bound (ratio reported off the rounded
    # bounds, so pin the exact geometry instead of the float)
    assert abs(ab["v2_bound_over_r13_bound"] - 0.25) < 0.01
    assert ab["v2"]["sketch_width"] == 4 * ab["r13"]["sketch_width"]
    assert ab["v2"]["within_bound"] and ab["r13"]["within_bound"]


# -- eviction -> sketch migration (r14) -------------------------------------


def _same_bucket_keys(slots: int, n: int, start: int = 1):
    """n distinct-fingerprint uint64 hashes all mapping to bucket 0."""
    from gubernator_tpu.core import hashing
    from gubernator_tpu.core.store import _BUCKET_SALT

    out = []
    v = start
    while len(out) < n:
        kh = np.uint64(v << 32) | np.uint64(5)
        b = int(
            hashing.mix64(np.asarray([kh], np.uint64) ^ _BUCKET_SALT)[0]
            & np.uint64(slots - 1)
        )
        if b == 0:
            out.append(kh)
        v += 1
    return np.asarray(out, np.uint64)


def test_evicted_dead_entry_folds_into_sketch(monkeypatch):
    """A create recycling a DEAD victim's way folds the victim's
    consumed count into the victim key's current fixed window: the
    evicted-then-recreated key decides at-least-as-restrictively as
    the unevicted oracle, and the sketch estimate actually carries
    the folded count (without the fold it would be 0). Exactly
    window-aligned dead entries (no overlap with the current window)
    fold nothing."""

    def mk():
        eng = TpuEngine(
            StoreConfig(rows=1, slots=16), buckets=(64,),
            sketch=SketchConfig(rows=4, width=1 << 12),
        )
        # pin the epoch at T0 (engine-ms 0)
        z = np.zeros(1, np.int64)
        eng.decide_arrays(
            _keys(1, tag=250), z, z + 1, z + 1000,
            np.zeros(1, np.int32), np.zeros(1, bool), T0,
        )
        return eng

    D, LIM = 1000, 10
    K, L = _same_bucket_keys(16, 2)
    kk = np.asarray([K], np.uint64)
    ll = np.asarray([L], np.uint64)

    def drive(eng, kh, hits, t):
        one = np.ones(1, np.int64)
        return eng.decide_arrays(
            kh, np.asarray([hits], np.int64), one * LIM, one * D,
            np.zeros(1, np.int32), np.zeros(1, bool), T0 + t,
        )

    evicted = mk()
    oracle = mk()
    for eng in (evicted, oracle):
        # K created mid-window-1 (engine 1500): window [1500, 2500)
        # consumes 6 of 10 — its tail crosses into fixed window 2
        drive(eng, kk, 6, 1500)
    # at engine 2600 K is dead (2500 < 2600); L's create recycles K's
    # way on `evicted` only — the fold moment
    drive(evicted, ll, 1, 2600)
    assert evicted.stats.snapshot()["evictions"] == 1
    est = evicted.sketch_estimates(kk, np.asarray([D], np.int64), T0 + 2600)
    assert est[0] >= 6, f"fold did not land: estimate {est[0]}"

    # K returns at 2700: bucket full with LIVE L -> sketch-served from
    # the folded estimate; the unevicted oracle recreates exactly
    s_e, _, r_e, t_e = drive(evicted, kk, 1, 2700)
    s_o, _, r_o, t_o = drive(oracle, kk, 1, 2700)
    assert s_o[0] == int(Status.UNDER_LIMIT) and r_o[0] == LIM - 1
    assert s_e[0] >= s_o[0] and r_e[0] <= r_o[0], (
        "evicted-then-recreated key went fail-open vs the unevicted "
        f"oracle: {(s_e[0], r_e[0])} vs {(s_o[0], r_o[0])}"
    )
    # the folded 6 plus this charge: remaining = (10 - 6) - 1
    assert r_e[0] == LIM - 6 - 1
    # sketch window reset = window 2's end (engine 3000; the epoch
    # pins 1ms before first contact since r15, so unix = T0-1+3000)
    assert t_e[0] == (T0 - 1) + 3000

    # exact alignment: an entry whose expiry == the window boundary
    # has NO overlap with the current window -> nothing folds
    aligned = mk()
    K2, L2 = _same_bucket_keys(16, 2, start=500)
    # unix T0+999 = ENGINE 1000 (epoch at T0-1): window [1000, 2000)
    # ends exactly on the fixed-window boundary
    drive(aligned, np.asarray([K2], np.uint64), 6, 999)
    drive(aligned, np.asarray([L2], np.uint64), 1, 2100)  # recycles
    est2 = aligned.sketch_estimates(
        np.asarray([K2], np.uint64), np.asarray([D], np.int64), T0 + 2100
    )
    assert est2[0] == 0, est2


def test_sticky_over_victim_folds_whole_limit(monkeypatch):
    """A recycled sticky-over victim folds its LIMIT: the key stays
    refused for the remainder of its current fixed window when it
    returns sketch-served."""
    eng = TpuEngine(
        StoreConfig(rows=1, slots=16), buckets=(64,),
        sketch=SketchConfig(rows=4, width=1 << 12),
    )
    z = np.zeros(1, np.int64)
    eng.decide_arrays(
        _keys(1, tag=251), z, z + 1, z + 1000,
        np.zeros(1, np.int32), np.zeros(1, bool), T0,
    )
    D, LIM = 1000, 4
    K, L = _same_bucket_keys(16, 2, start=900)

    def drive(kh, hits, t):
        one = np.ones(1, np.int64)
        return eng.decide_arrays(
            np.asarray([kh], np.uint64), np.asarray([hits], np.int64),
            one * LIM, one * D, np.zeros(1, np.int32),
            np.zeros(1, bool), T0 + t,
        )

    # drain K to 0 then over: sticky flag set, remaining 0
    drive(K, 4, 1500)
    s, _, r, _ = drive(K, 1, 1600)
    assert s[0] == int(Status.OVER_LIMIT)
    # dead at 2600; L recycles the way; K returns sketch-served
    drive(L, 1, 2600)
    s2, _, r2, _ = drive(K, 1, 2700)
    assert s2[0] == int(Status.OVER_LIMIT) and r2[0] == 0, (s2, r2)


# -- promotion / demotion ---------------------------------------------------


def test_promote_migrates_estimate_and_skips_live():
    """Promotion installs remaining = limit - estimate with reset = the
    window end; the key then decides EXACTLY (store-resident) and a
    second promote skips it (live)."""
    eng = _pressure_engine()
    fillers = _pin_buckets(eng)
    nf = fillers.shape[0]
    key = _keys(1, tag=9)[:1]
    DUR, LIM = 600_000, 10
    for i in range(3):  # est -> 3
        kh = np.concatenate([fillers, key])
        hits = np.concatenate([np.zeros(nf, np.int64), [1]])
        eng.decide_arrays(
            kh, hits, np.full(nf + 1, LIM, np.int64),
            np.full(nf + 1, DUR, np.int64),
            np.zeros(nf + 1, np.int32), np.zeros(nf + 1, bool), T0 + i,
        )
    assert not eng.live_mask(key, T0 + 5)[0]
    inst, est, reset, over = eng.promote_from_sketch(
        key, np.array([LIM]), np.array([DUR]), T0 + 5
    )
    assert inst[0] and est[0] == 3 and not over[0]
    # window end; the epoch pins 1ms before first contact (r15)
    assert reset[0] == (T0 - 1) + DUR
    assert eng.live_mask(key, T0 + 6)[0]
    # the window CONTINUES: next hit decides exactly at remaining 6
    kh = np.concatenate([fillers, key])
    hits = np.concatenate([np.zeros(nf, np.int64), [1]])
    s, l, r, t = eng.decide_arrays(
        kh, hits, np.full(nf + 1, LIM, np.int64),
        np.full(nf + 1, DUR, np.int64), np.zeros(nf + 1, np.int32),
        np.zeros(nf + 1, bool), T0 + 6,
    )
    assert s[-1] == int(Status.UNDER_LIMIT) and r[-1] == LIM - 3 - 1
    # re-promoting skips the live key and must not clobber its state
    inst2, _, _, _ = eng.promote_from_sketch(
        key, np.array([LIM]), np.array([DUR]), T0 + 7
    )
    assert not inst2[0]
    s, l, r, t = eng.decide_arrays(
        kh, hits, np.full(nf + 1, LIM, np.int64),
        np.full(nf + 1, DUR, np.int64), np.zeros(nf + 1, np.int32),
        np.zeros(nf + 1, bool), T0 + 8,
    )
    assert r[-1] == LIM - 3 - 2


def test_promoter_flow_and_shed_feed():
    """Instance-level promoter loop: hot sketch-tier keys promote on a
    flush tick, over-limit candidates seed the shed cache, and expired
    promotions demote."""
    conf = ServerConfig(
        grpc_address=ADDR, advertise_address=ADDR,
        sketch_sync_wait=600.0,  # manual ticks only
        sketch_topk=64,
    )
    backend = TpuBackend(
        StoreConfig(rows=1, slots=16), buckets=(64, 256),
        sketch=SketchConfig(rows=4, width=1 << 12),
    )

    async def run():
        inst = Instance(conf, backend)
        inst.start()
        await inst.set_peers([PeerInfo(address=ADDR, is_owner=True)])
        try:
            assert inst.promoter is not None
            # force the observer to sample every dispatch
            inst.promoter.tracker._next = 0.0
            import gubernator_tpu.serve.promoter as prom_mod

            orig = prom_mod.OBSERVE_MIN_INTERVAL_S
            prom_mod.OBSERVE_MIN_INTERVAL_S = 0.0
            try:
                reqs = [
                    RateLimitReq(
                        name="p", unique_key=f"pk{j}", hits=1,
                        limit=2, duration=600_000,
                    )
                    for j in range(64)
                ]
                for _ in range(4):  # drive the tail over limit
                    await inst.get_rate_limits(reqs)
            finally:
                prom_mod.OBSERVE_MIN_INTERVAL_S = orig
            assert backend.stats()["dropped"] > 0
            shed_before = len(inst.shed)
            await inst.promoter.flush_once()
            st = inst.promoter.stats()
            assert st["promotions"] > 0
            assert st["shed_seeds"] > 0
            assert len(inst.shed) >= shed_before
            # promoted keys are now exact-resident
            from gubernator_tpu.core.hashing import slot_hash_batch

            promoted = np.array(
                sorted(inst.promoter._promoted), np.uint64
            )
            live = backend.engine.live_mask(promoted)
            assert live.any()
            # demotion: expire every promotion and tick again
            inst.promoter._promoted = {
                h: 0 for h in inst.promoter._promoted
            }
            await inst.promoter.flush_once()
            assert inst.promoter.stats()["demotions"] > 0
        finally:
            await inst.stop()

    asyncio.run(run())


def test_shed_seed_gates():
    clock = FakeClock()
    c = ShedCache(2, now_fn=clock)
    c.seed(1, 5, 1000, clock.t + 500)
    r = RateLimitReq(name="n", unique_key="k", hits=1, limit=5,
                     duration=1000)
    assert c.lookup_resp(1, r).reset_time == clock.t + 500
    c.seed(2, 5, 1000, clock.t - 1)  # expired: ignored
    assert 2 not in c
    c.seed(3, 5, 1000, clock.t + 500)
    c.seed(4, 5, 1000, clock.t + 500)  # capacity 2: LRU evicts
    assert len(c) == 2 and 1 not in c


def test_committed_artifact_headline():
    """BENCH_SKETCH_r13.json: the committed acceptance — the tier
    actually engaged (drops served), zero under-counts, error within
    bound; a missed throughput target must carry the scoping note."""
    import json
    import pathlib

    doc = json.loads(
        (
            pathlib.Path(__file__).resolve().parent.parent
            / "BENCH_SKETCH_r13.json"
        ).read_text()
    )
    assert doc["acceptance"]["error_met"] is True
    assert doc["tail_error"]["under_counts"] == 0
    assert doc["tail_error"]["within_bound"] is True
    sk = next(
        r for r in doc["rows"] if r["metric"] == "zipf100m_sketch_tier"
    )
    assert sk["dropped_creates"] > 0, "the sketch tier never engaged"
    assert doc["key_space"] >= 100_000_000
    assert doc["acceptance"]["throughput_met"] or doc["acceptance_note"]


def test_committed_artifact_headline_r21():
    """BENCH_SKETCH_r21.json: the r21 acceptance — v2's measured max
    overestimate strictly below the r13 bound at the same budget with
    zero under-counts anywhere, and the sliding/GCRA arms actually
    served from the window-ring at 100M-key cardinality."""
    import json
    import pathlib

    doc = json.loads(
        (
            pathlib.Path(__file__).resolve().parent.parent
            / "BENCH_SKETCH_r21.json"
        ).read_text()
    )
    acc = doc["acceptance"]
    assert acc["error_met"] is True
    assert acc["derivation_met"] is True
    assert acc["arms_met"] is True
    ab = doc["tail_error_derivation_ab"]
    assert ab["v2_max_below_r13_bound"] is True
    assert ab["zero_under_counts"] is True
    assert ab["v2"]["documented_bound"] < ab["r13"]["documented_bound"]
    for arm in ("sliding", "gcra"):
        e = doc["tail_error_arms"][arm]
        assert e["under_counts"] == 0 and e["within_bound"] is True
        row = next(
            r
            for r in doc["rows"]
            if r["metric"] == f"zipf100m_sketch_{arm}"
        )
        assert row["dropped_creates"] > 0, f"{arm} arm never engaged"
    assert doc["key_space"] >= 100_000_000
    assert acc["throughput_met"] or doc["acceptance_note"]


# -- shared key streams -----------------------------------------------------


def test_keystreams_bit_identical_and_churn_disjoint():
    """The factored zipf recipe reproduces the historical inline recipe
    bit for bit, and churn phases present disjoint key sets."""
    from gubernator_tpu.cli import keystreams

    rng = np.random.default_rng(42)
    zipf = rng.zipf(1.2, size=4096) % 10_000_000
    legacy = (
        zipf.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    ) ^ np.uint64(0xDEADBEEFCAFEF00D)
    np.testing.assert_array_equal(
        keystreams.zipf_pool(10_000_000, 4096), legacy
    )
    a = keystreams.churn_pool(1 << 30, 4096, phase=0)
    b = keystreams.churn_pool(1 << 30, 4096, phase=1)
    assert np.intersect1d(a, b).size == 0
    assert keystreams.stream_pool("zipf", 1000, 64).shape == (64,)
    with pytest.raises(ValueError):
        keystreams.stream_pool("nope", 1000, 64)


def test_spacesaving_weighted_payload_decay():
    from gubernator_tpu.core.sketches import SpaceSaving

    ss = SpaceSaving(capacity=3)
    ss.observe_weighted({1: 10, 2: 5}, payloads={1: ("a", 1)})
    ss.observe_weighted({3: 2, 4: 8})  # 4 evicts 3 (min) at capacity
    top = ss.top_with_payload(3)
    assert top[0][0] == 1 and top[0][3] == ("a", 1)
    assert ss.payload(2) is None
    ss.decay(shift=3)  # 10>>3=1, 5>>3=0 (dropped), 8+2>>3...
    assert 1 in ss._counts and 2 not in ss._counts
    assert ss.payload(1) == ("a", 1)
