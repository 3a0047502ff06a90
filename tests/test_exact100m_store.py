"""The store that fills a chip (PR 30): `GUBER_STORE_TARGET_KEYS=100000000`
derives 16 ways x 2^24 bucket rows = 8 GiB, int32[2^24, 128] = exactly
2^31 words, on a chip of 16 GB. Two things have to hold for it, and
neither needs the table to check:

- every index the program and its host twins reckon stays in range at
  2^24 rows (per-axis indices; a word offset just fits int32, a byte
  offset does not);
- every program that takes `store.data` holds ONE table: the compiled
  programs' `memory_analysis()` (the CPU backend reports aliasing and
  temporaries too) at a geometry the CPU holds, and the engine's
  `reset()` handing the old state back before it allocates the new.
  The same programs at the real row count, for the real chip, are
  compiled in tests/test_tpu_compile.py.

`rebase` was rewritten for it (block by block, in place) and is held
word for word to the formulation it replaced; the delta-add writeback
was NOT changed (its scatter is in place; the pass over the table it
makes is the TPU scatter's own) and is pinned here against a plain
row-by-row write so that a later change has something to equal.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import gubernator_tpu.core  # noqa: F401  (enables x64)
from gubernator_tpu.core import engine as engine_mod
from gubernator_tpu.core import kernels as K
from gubernator_tpu.core import store as store_mod
from gubernator_tpu.core.sketches import derive_sketch_config
from gubernator_tpu.core.store import (
    FLAG_ALGO_GCRA,
    FLAG_ALGO_LEAKY,
    FLAG_ALGO_SLIDING,
    FLAG_STICKY_OVER,
    L_EXPIRE,
    L_FLAGS,
    L_TAG,
    L_TS,
    LANES,
    Store,
    StoreConfig,
    bucket_index,
    decode_sort_key,
    derive_store_config,
    fingerprints,
    group_sort_key,
    group_sort_key_np,
    store_footprint_bytes,
)
from gubernator_tpu.parallel.sharded import TpuEngine
from test_tpu_compile import _batch, _captured, _shapes, _table_programs

ROWS_100M = 1 << 24
MIB = 1 << 20
NOW = 1_700_000_000_000


# -- geometry ---------------------------------------------------------------


def test_100m_keys_derive_16_ways_by_2_24_rows():
    config = derive_store_config(target_keys=100_000_000)
    assert config == StoreConfig(rows=16, slots=ROWS_100M)
    assert store_footprint_bytes(config) == 8 << 30
    assert config.slots * config.rows * LANES == 1 << 31  # int32 words
    load = 100_000_000 / (config.rows * config.slots)
    assert 0.37 < load < 0.38
    # one rung down would not do: 2^23 rows hold the keys at load 0.745
    assert 100_000_000 / (16 * (1 << 23)) > store_mod.MAX_LOAD


def _upper_half_hashes(n=4096, seed=30):
    """Key hashes whose bucket at 2^24 rows lies in [2^23, 2^24): the
    half of the table a 23-bit index cannot name."""
    rng = np.random.default_rng(seed)
    kh = rng.integers(0, 2**64, 4 * n, dtype=np.uint64)
    bkt = group_sort_key_np(kh, ROWS_100M) >> np.uint64(32)
    kh = kh[bkt >= np.uint64(1 << 23)][:n]
    assert kh.shape[0] == n
    return kh


def test_bucket_index_and_its_host_twins_agree_in_the_upper_half():
    kh = _upper_half_hashes()
    on_device = np.asarray(bucket_index(jnp.asarray(kh), ROWS_100M))
    assert on_device.dtype == np.int32
    assert ((1 << 23) <= on_device).all() and (on_device < ROWS_100M).all()
    # the numpy twin behind the host presort (pad_request_sorted)
    skey_np = group_sort_key_np(kh, ROWS_100M)
    assert ((skey_np >> np.uint64(32)).astype(np.int64) == on_device).all()
    # the device sort key and its decode: bucket and tag come back whole
    skey = group_sort_key(jnp.asarray(kh), jnp.ones(kh.shape, bool), ROWS_100M)
    assert (np.asarray(skey) == skey_np).all()
    bkt, fp = decode_sort_key(jnp.sort(skey), ROWS_100M)
    order = np.argsort(skey_np, kind="stable")
    assert (np.asarray(bkt) == on_device[order]).all()
    assert (np.asarray(fp) == np.asarray(fingerprints(jnp.asarray(kh)))[order]).all()
    # the invalid tail clamps to the LAST row, not past it
    tail, _ = decode_sort_key(
        jnp.full((4,), 0xFFFFFFFFFFFFFFFF, jnp.uint64), ROWS_100M)
    assert (np.asarray(tail) == ROWS_100M - 1).all()


def test_the_engines_presort_names_upper_half_rows():
    """pad_request_sorted (native radix presort where the library is
    built, numpy otherwise) at 2^24 rows: the batch comes out in the
    device's bucket order, upper-half buckets included."""
    kh = _upper_half_hashes(1000, seed=31)
    n = kh.shape[0]
    ones = np.ones(n, np.int64)
    req, order, groups = engine_mod.pad_request_sorted(
        (64, 256, 1024), ROWS_100M, key_hash=kh, hits=ones, limit=ones * 10,
        duration=ones * 1000, algo=np.zeros(n, np.int32),
        gnp=np.zeros(n, bool), with_groups=True,
    )
    bkt = np.asarray(bucket_index(jnp.asarray(req.key_hash[:n]), ROWS_100M))
    assert (np.diff(bkt.astype(np.int64)) >= 0).all()
    assert bkt.min() >= 1 << 23
    assert sorted(req.key_hash[:n].tolist()) == sorted(kh.tolist())
    g = int(groups.valid.sum())
    assert (np.asarray(groups.key_hash[:g]) ==
            req.key_hash[np.asarray(groups.leader_pos[:g])]).all()


def test_word_offsets_fit_int32_and_byte_offsets_do_not():
    """What a flattened view of the table would have to hold: the last
    word's offset is INT32_MAX exactly, the host's [2^28, 8] entry view
    (export_windows) indexes under 2^28, and a byte offset needs 64
    bits — so nothing in the program flattens the table on the device
    or reckons bytes in int32 (the kernels address [row, lane])."""
    last_row, last_lane = ROWS_100M - 1, 16 * LANES - 1
    assert last_row * 16 * LANES + last_lane == np.iinfo(np.int32).max
    assert last_row * 16 + 15 == (1 << 28) - 1  # entry index, host view
    assert (last_row * 16 * LANES + last_lane) * 4 > np.iinfo(np.int32).max
    # the gathers and the scatter of the decide are per-axis: a [B] row
    # index against axis 0 and whole 128-lane rows
    closed = jax.make_jaxpr(
        lambda d, b, r: K._writeback_apply(
            d, b, jnp.ones(b.shape, bool), jnp.zeros(b.shape, jnp.int32),
            r[:, :LANES], r.reshape(b.shape[0], 16, LANES))
    )(
        jax.ShapeDtypeStruct((ROWS_100M, 128), jnp.int32),
        jax.ShapeDtypeStruct((64,), jnp.int32),
        jax.ShapeDtypeStruct((64, 128), jnp.int32),
    )
    scatters = [e for e in closed.jaxpr.eqns if e.primitive.name == "scatter-add"]
    assert len(scatters) == 1
    dn = scatters[0].params["dimension_numbers"]
    assert dn.scatter_dims_to_operand_dims == (0,)
    assert dn.update_window_dims == (1,)
    assert scatters[0].outvars[0].aval.shape == (ROWS_100M, 128)


# -- rebase: the new formulation equals the old, word for word --------------


def _rebase_as_it_was(data, delta):
    """core/store.py `rebase` before PR 30: one expression over the
    whole table (its reshape cost a copy of the table on the TPU)."""
    lane = jnp.arange(data.shape[-1]) % LANES
    is_expire = lane == L_EXPIRE
    is_ts = lane == L_TS
    lead = data.shape[:-1]
    W = data.shape[-1]
    flags = data.reshape(*lead, W // LANES, LANES)[..., L_FLAGS:L_FLAGS + 1]
    flags = jnp.broadcast_to(flags, (*lead, W // LANES, LANES)).reshape(*lead, W)
    ts_is_count = (flags & FLAG_ALGO_SLIDING) != 0
    is_time = is_expire | (is_ts & ~ts_is_count)
    shifted = jnp.clip(
        data.astype(jnp.int64) - jnp.where(is_time, delta, 0),
        store_mod.TIME_FLOOR, store_mod.COUNTER_MAX,
    ).astype(jnp.int32)
    return jnp.where(is_time, shifted, data)


def _random_table(rng, shape):
    """Entries of every algorithm, times across the int32 envelope."""
    *lead, buckets, W = shape
    ent = rng.integers(-(1 << 31), 1 << 31, (*lead, buckets, W // LANES, LANES),
                       dtype=np.int64).astype(np.int32)
    ent[..., L_FLAGS] = rng.choice(
        [0, FLAG_STICKY_OVER, FLAG_ALGO_LEAKY, FLAG_ALGO_SLIDING,
         FLAG_ALGO_GCRA, FLAG_ALGO_SLIDING | FLAG_STICKY_OVER],
        ent.shape[:-1])
    ent[..., L_TAG] *= rng.integers(0, 2, ent.shape[:-1])  # some empty ways
    return ent.reshape(shape)


@pytest.mark.parametrize("shape", [
    (16, 128),               # one short block
    (4 * store_mod.REBASE_BLOCK_ROWS, 128),  # four whole blocks
    (64, 32),                # 4 ways
    (2, 2 * store_mod.REBASE_BLOCK_ROWS, 128),  # a mesh's leading shard axis
])
@pytest.mark.parametrize("delta", [1, 1 << 30, (1 << 30) + 12345])
def test_rebase_equals_the_formulation_it_replaced(shape, delta):
    table = _random_table(np.random.default_rng(len(shape) * delta % 997), shape)
    want = np.asarray(_rebase_as_it_was(jnp.asarray(table), jnp.int32(delta)))
    got = K.rebase_jit(Store(data=jnp.asarray(table)), np.int32(delta)).data
    assert got.shape == table.shape and got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), want)
    assert not np.array_equal(want, table)  # the pass did something


# -- the delta-add writeback against a plain row-by-row write ---------------


def _plain_writeback(table, bkt, write_item, found, fway, eway, new_vals):
    """What _writeback_delta_add promises, one item at a time in batch
    order: a found writer rewrites its way; the k-th miss of a bucket
    claims the k-th way that was EMPTY before the batch; with none left
    the first miss evicts `eway` unless a found writer of the batch
    writes that way, and every later miss drops."""
    out = table.reshape(table.shape[0], -1, LANES).copy()
    before = out.copy()
    dropped = evicted = 0
    for b in np.unique(bkt):
        rows = np.flatnonzero((bkt == b) & write_item)
        found_ways = {int(fway[j]) for j in rows if found[j]}
        empty = [w for w in range(out.shape[1]) if before[b, w, L_TAG] == 0]
        rank = 0
        for j in rows:
            if found[j]:
                out[b, fway[j]] = new_vals[j]
                continue
            if rank < len(empty):
                out[b, empty[rank]] = new_vals[j]
            elif rank == 0 and int(eway[j]) not in found_ways:
                out[b, eway[j]] = new_vals[j]
                evicted += 1
            else:
                dropped += 1
            rank += 1
    return out.reshape(table.shape), dropped, evicted


def _random_plan(rng, buckets, B, ways, empty_share):
    """(table, the positional arguments of _writeback_delta_add after
    it): found / miss writers and non-writers (duplicates, padding:
    they carry a real bucket and add a zero row), several to a bucket,
    buckets at both ends of the table; `empty_share` of the ways start
    empty, none at 0.0, so misses there evict and drop."""
    table = rng.integers(1, 1 << 20, (buckets, ways, LANES)).astype(np.int32)
    table[rng.random((buckets, ways)) < empty_share] = 0
    table = table.reshape(buckets, ways * LANES)
    bkt = np.sort(rng.choice(
        np.r_[0, buckets - 1, rng.integers(0, buckets, 10)], B)).astype(np.int32)
    cand = table[bkt].reshape(B, ways, LANES)
    # a found item names an occupied way of its bucket, each way at most
    # once a bucket (one tag a way); the rest are misses
    found = np.zeros(B, bool)
    fway = np.zeros(B, np.int32)
    taken = set()
    for j in range(B):
        occupied = np.flatnonzero(cand[j, :, L_TAG] != 0)
        if occupied.size and rng.random() < 0.5:
            w = int(rng.choice(occupied))
            if (int(bkt[j]), w) not in taken:
                taken.add((int(bkt[j]), w))
                found[j], fway[j] = True, w
    eway = rng.integers(0, ways, B).astype(np.int32)
    write_item = rng.random(B) < 0.8  # the rest: duplicates, padding
    new_vals = rng.integers(1, 1 << 30, (B, LANES)).astype(np.int32)
    is_b_leader = np.r_[True, bkt[1:] != bkt[:-1]]
    b_end = np.asarray(K._segment_ends(
        jnp.asarray(is_b_leader), jnp.arange(B, dtype=jnp.int32)))
    return table, (bkt, write_item, found, fway, eway, new_vals, cand,
                   is_b_leader, b_end)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("ways", [16, 4])
def test_writeback_equals_a_plain_row_write_on_random_plans(seed, ways):
    """found / miss / evict / dropped creates, several writers in one
    bucket, every way, buckets at both ends of the table: the table
    after the one scatter-add equals the plain write, word for word."""
    rng = np.random.default_rng(1000 * ways + seed)
    # every third seed leaves no way empty: its misses evict and drop
    empty_share = (0.0, 0.15, 0.6)[seed % 3]
    table, plan = _random_plan(rng, 32, 96, ways, empty_share)
    bkt, write_item, found, fway, eway, new_vals = plan[:6]

    want, want_dropped, want_evicted = _plain_writeback(
        table, bkt, write_item, found, fway, eway, new_vals)
    got, dropped, evicted = jax.jit(K._writeback_delta_add)(
        *(jnp.asarray(x) for x in (table, *plan)))
    assert np.array_equal(np.asarray(got), want)
    assert (int(dropped), int(evicted)) == (want_dropped, want_evicted)
    if empty_share == 0.0:
        assert want_dropped > 0 and want_evicted > 0


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("buckets,B", [
    (32, 96),    # deep beside the store: the shapes alone pick the hint
    (4096, 64),  # a served shape: the shapes alone withhold it
])
def test_the_hinted_and_the_unhinted_scatter_are_one_function(
    monkeypatch, seed, buckets, B
):
    """The same seeded plan — duplicate buckets, non-writers that carry
    a real bucket, full buckets on every third seed — through the
    scatter-add told its indices are sorted and through the one not
    told (kernels.writeback_form's two XLA forms, each forced in turn
    whatever the shapes would pick): the tables are equal word for
    word, `dropped` and `evicted` equal, and both equal the plain
    row-by-row write."""
    rng = np.random.default_rng(7000 + 10 * buckets + seed)
    empty_share = (0.0, 0.15, 0.6)[seed % 3]
    table, plan = _random_plan(rng, buckets, B, 16, empty_share)
    natural = K.writeback_form(buckets, 128, B)
    assert natural == ("scatter_sorted" if 32 * B >= buckets else "scatter")
    out = {}
    for form in ("scatter_sorted", "scatter"):
        monkeypatch.setattr(K, "writeback_form", lambda *a, form=form: form)

        def apply(*a):  # a function of its own: jit caches by function
            return K._writeback_delta_add(*a)

        args = tuple(jnp.asarray(x) for x in (table, *plan))
        sorted_flags = {
            e.params["indices_are_sorted"]
            for e in jax.make_jaxpr(apply)(*args).jaxpr.eqns
            if e.primitive.name == "scatter-add"
        }
        assert sorted_flags == {form == "scatter_sorted"}
        got, dropped, evicted = jax.jit(apply)(*args)
        out[form] = (np.asarray(got), int(dropped), int(evicted))
    hinted, plain = out["scatter_sorted"], out["scatter"]
    assert np.array_equal(hinted[0], plain[0])
    assert hinted[1:] == plain[1:]
    bkt, write_item, found, fway, eway, new_vals = plan[:6]
    want = _plain_writeback(
        table, bkt, write_item, found, fway, eway, new_vals)
    assert np.array_equal(plain[0], want[0]) and plain[1:] == want[1:]
    assert not np.array_equal(plain[0], table)


# -- one table --------------------------------------------------------------


def test_every_program_that_takes_the_table_holds_one():
    """memory_analysis() of the compiled programs at 2^16 rows (32 MiB;
    the CPU backend reports aliasing and temporaries): the donated
    table is the output's buffer and the temporaries are far under one
    table — decide (two-tier, as the default daemon serves it), the
    GLOBAL / promoter install, the full-lane install, rebase. The row
    gather takes the table and returns [B, 128]."""
    rows = 1 << 16
    table = rows * 128 * 4
    small = TpuEngine(StoreConfig(rows=16, slots=1 << 10), buckets=(64,),
                      sketch=derive_sketch_config(mib=1, rows=0, derivation="v2"))
    keys = np.arange(1, 65, dtype=np.uint64) << np.uint64(32)
    _, sketch, packed_in, B, G = _captured(
        engine_mod, "_decide_packed_sketch_jit",
        lambda: small.decide_arrays(**_batch(keys)))
    store = Store(data=jax.ShapeDtypeStruct((rows, 128), jnp.int32))
    b = 64
    programs = _table_programs(rows, jax.ShapeDtypeStruct, b)
    programs["decide"] = engine_mod._decide_packed_sketch_jit.lower(
        store, _shapes(sketch, None), _shapes(packed_in, None), B, G)
    gather = programs.pop("rows_flat").compile().memory_analysis()
    assert gather.output_size_in_bytes == b * 128 * 4
    assert gather.temp_size_in_bytes < MIB
    block = min(rows, store_mod.REBASE_BLOCK_ROWS) * 128 * 4
    for name, lowered in programs.items():
        mem = lowered.compile().memory_analysis()
        assert mem.alias_size_in_bytes >= table, (name, mem)
        assert mem.output_size_in_bytes < table + 4 * MIB, (name, mem)
        # rebase works a block at a time; the others touch rows
        assert mem.temp_size_in_bytes <= (block if name == "rebase" else MIB) + MIB, (name, mem)


def test_rebase_temporaries_do_not_grow_with_the_table():
    S = jax.ShapeDtypeStruct
    temps = [
        K.rebase_jit.lower(Store(data=S((rows, 128), jnp.int32)), S((), jnp.int32))
        .compile().memory_analysis().temp_size_in_bytes
        for rows in (1 << 16, 1 << 18)
    ]
    assert temps[0] == temps[1] <= store_mod.REBASE_BLOCK_ROWS * 128 * 4 + MIB


def test_reset_hands_the_old_state_back_before_it_allocates():
    """Warm-up ends in reset(): with the new table built before the old
    was dropped, two were alive at that instant — the process's peak,
    and more than a chip holds at 8 GiB."""
    eng = TpuEngine(StoreConfig(rows=16, slots=1 << 8), buckets=(64,),
                    sketch=derive_sketch_config(mib=1, rows=0, derivation="v2"))
    n = 8
    ones = np.ones(n, np.int64)
    batch = dict(
        key_hash=np.arange(1, n + 1, dtype=np.uint64) << np.uint64(32),
        hits=ones, limit=ones * 3, duration=ones * 60_000,
        algo=np.zeros(n, np.int32), gnp=np.zeros(n, bool), now=NOW)
    eng.decide_arrays(**batch)
    old_store, old_sketch = eng.store.data, eng.sketch.data
    generation = eng.reset_generation
    eng.reset()
    assert old_store.is_deleted() and old_sketch.is_deleted()
    assert eng.reset_generation == generation + 1
    assert not np.asarray(eng.store.data).any()
    status, _, remaining, *_ = eng.decide_arrays(**batch)
    assert (np.asarray(status) == 0).all() and (np.asarray(remaining) == 2).all()
