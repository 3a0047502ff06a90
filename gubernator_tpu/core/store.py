"""Device-resident rate-limit state: the slot store.

The TPU-native replacement for the reference's per-key LRU hash map
(reference cache/lru.go). State is ONE dense int32 array of shape
[buckets, ways*LANES] living in HBM:

- Each key hashes to ONE bucket of `ways` set-associative entry slots,
  plus a 32-bit fingerprint tag. A bucket is one row of the array
  (ways*LANES lanes), so lookup is a single vectorized gather of whole
  bucket rows and writeback is a single scatter of whole bucket rows —
  no probing loops, fixed shapes for XLA, and (because batches are
  sorted by bucket) both index streams are monotonically sorted.
  CRITICAL LAYOUT INVARIANT: every op in the jitted hot loop consumes
  and produces the store in this exact [buckets, ways*LANES] shape.
  Reshaping the store inside the loop makes XLA materialize
  layout-conversion copies of the whole array per step (measured 3
  copies x ~0.8 ms for a 32 MiB store on v5e — 3x the entire kernel).
- A key occupies exactly one way of its bucket; lookup compares the tag
  lane across the ways with vector selects.
- On insert, an empty way is preferred, otherwise the way with the
  earliest expiry is evicted. For rate-limit state, expiry time is the
  natural recency metric (an entry past its reset is worthless), so
  evict-earliest-expiry plays the role of the reference's LRU eviction
  (cache/lru.go:92-94) with the same "state loss => brief over-admission"
  contract (reference architecture.md:5-11).

int32 everywhere (the TPU-first choice)
---------------------------------------
TPU v5e has no native int64 ALU path — XLA emulates 64-bit integer math as
pairs of 32-bit ops, which measured 2-10x slower for the gathers, scatters
and prefix scans this kernel is made of. All device state and arithmetic is
therefore int32:

- **Time** is milliseconds relative to a host-managed *epoch* (see
  core.engine.EpochClock). Wall clock enters each batch as one int32
  "engine-ms" scalar in [0, 2^30]; the host rebases the epoch (one cheap
  elementwise pass, `rebase`) every ~12 days of uptime so offsets never
  overflow. External APIs remain int64 unix-ms end to end.
- **Counters** (hits/limit/remaining) saturate at 2^31-1 at the host
  boundary. Documented divergence from the reference's int64 fields:
  limits above ~2.1 billion per window and durations above ~12.4 days
  (MAX_DURATION_MS) are clamped. Both are far outside the reference's own
  tested envelope and production use.

The packed lane layout exists for TPU performance: one wide gather and one
wide scatter per batch instead of one per field. Lane meanings:

  L_TAG       fingerprint (bitcast of key-hash high 32 bits; 0 = empty)
  L_EXPIRE    entry expiry, engine-ms; miss if < now
  L_REMAINING tokens remaining in window / bucket
  L_TS        leaky last-leak timestamp (token: creation time), engine-ms
  L_LIMIT     stored limit
  L_DURATION  stored duration ms
  L_FLAGS     FLAG_* bits
  L_KEYLOW    bitcast of the key hash's LOW 32 bits (r14; was padding).
              With L_TAG (the high 32 bits) this makes every entry's
              full uint64 key hash reconstructable on device, which is
              what lets the sketch tier FOLD a recycled dead entry's
              consumed count into the victim key's current count-min
              window instead of dropping it (eviction->sketch
              migration, core/kernels.py). Identity-valued: untouched
              by rebase, ignored by every pre-r14 consumer.

This is the "exact" sibling of a count-min sketch: same dense-array,
gather/scatter compute shape, but tags make collisions explicit (evictions)
rather than silent over-counts, which preserves the reference's observable
semantics.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# lane indices
L_TAG = 0
L_EXPIRE = 1
L_REMAINING = 2
L_TS = 3
L_LIMIT = 4
L_DURATION = 5
L_FLAGS = 6
L_KEYLOW = 7
LANES = 8

# flags lane bits
FLAG_STICKY_OVER = 1  # token window created over-limit: status persists OVER
FLAG_ALGO_LEAKY = 2  # slot holds leaky-bucket state (else token bucket)
# r15 algorithm suite v2 (core/algorithms.py): one flag bit per stored
# algorithm, mutually exclusive with FLAG_ALGO_LEAKY. Token bucket stays
# the all-zero encoding so every pre-r15 entry decodes unchanged.
FLAG_ALGO_SLIDING = 4  # sliding-window counter (per-key anchored windows)
FLAG_ALGO_GCRA = 8  # GCRA: L_EXPIRE holds the theoretical arrival time
FLAG_ALGO_MASK = FLAG_ALGO_LEAKY | FLAG_ALGO_SLIDING | FLAG_ALGO_GCRA

# Engine-time envelope. `now` stays in [0, REBASE_AT]; stored times stay in
# [TIME_FLOOR, INT32_MAX]; durations are clamped to MAX_DURATION_MS so
# now + duration never exceeds int32 range (2^30 + 2^30 - 1 = INT32_MAX).
MAX_DURATION_MS = (1 << 30) - 1  # ~12.4 days
TIME_FLOOR = -(1 << 29)
REBASE_AT = 1 << 30
COUNTER_MAX = (1 << 31) - 1

# 128-lane rows of the dense device view (see dense_view): how many entry
# slots pack into one native (sublane, 128-lane) vector row.
DENSE_LANES = 128
SLOTS_PER_DENSE_ROW = DENSE_LANES // LANES  # 16

BYTES_PER_ENTRY = LANES * 4  # one packed int32 entry = 32 bytes of HBM

# Sizing guidance from the measured footprint≍throughput law (r5 sweep,
# BENCH_ZIPF10M_PROFILE_r5.json): decide cost is a pure function of the
# table's provisioned HBM footprint — the writeback pass ranges over
# capacity whether entries are live or not — so capacity should track
# the live-key budget, not "as much as fits". derive_store_config sizes
# to the smallest power-of-two capacity keeping load under MAX_LOAD
# (above ~68% load over-admission becomes measurable, README table);
# below ~1/OVERSIZE_FACTOR load the extra footprint costs throughput
# and buys nothing (check_store_budget's boot lint).
MAX_LOAD = 0.68
OVERSIZE_FACTOR = 4.0


@dataclass(frozen=True)
class StoreConfig:
    """Capacity knobs. Total capacity = rows * slots entries (`slots`
    buckets of `rows` set-associative ways each); keep load factor under
    ~50% of that for negligible eviction of live entries."""

    rows: int = 16  # ways per bucket (set associativity)
    slots: int = 1 << 15  # buckets (524,288 entries at rows=16, ~16 MiB)
    # rows=16 is the TPU-native default: a bucket row is then exactly 128
    # lanes (16 ways x 8 lanes), so the writeback scatters whole native
    # vector rows — measured ~7x faster than narrower rows on v5e — and
    # eviction picks among 16 candidates instead of 4.

    def __post_init__(self):
        # rows must divide SLOTS_PER_DENSE_ROW so a bucket never straddles
        # a dense 128-lane row (keeps bucket rows contiguous in the native
        # (sublane, 128-lane) tiling)
        assert self.rows in (1, 2, 4, 8, 16), (
            "rows (ways) must be 1, 2, 4, 8 or 16"
        )
        assert self.slots > 0 and (self.slots & (self.slots - 1)) == 0, (
            "slots must be a power of two"
        )
        assert (self.rows * self.slots) % SLOTS_PER_DENSE_ROW == 0, (
            "total capacity must be a multiple of 16 for the dense view"
        )


class Store(NamedTuple):
    """Packed state; a one-leaf pytree so the whole store donates cleanly.

    Convenience lane views (tag/expire/...) exist for tests and debugging;
    kernels index lanes directly.
    """

    data: jax.Array  # int32[buckets, ways*LANES]

    @property
    def entries(self) -> jax.Array:
        """Debug/test view int32[..., buckets, ways, LANES]."""
        *lead, buckets, wl = self.data.shape
        return self.data.reshape(*lead, buckets, wl // LANES, LANES)

    @property
    def tag(self) -> jax.Array:
        return self.entries[..., L_TAG]

    @property
    def expire(self) -> jax.Array:
        return self.entries[..., L_EXPIRE]

    @property
    def remaining(self) -> jax.Array:
        return self.entries[..., L_REMAINING]

    @property
    def ts(self) -> jax.Array:
        return self.entries[..., L_TS]

    @property
    def limit(self) -> jax.Array:
        return self.entries[..., L_LIMIT]

    @property
    def duration(self) -> jax.Array:
        return self.entries[..., L_DURATION]

    @property
    def flags(self) -> jax.Array:
        return self.entries[..., L_FLAGS]


def store_capacity(config: StoreConfig) -> int:
    """Total entry capacity (rows x slots)."""
    return config.rows * config.slots


def store_footprint_bytes(config: StoreConfig) -> int:
    return store_capacity(config) * BYTES_PER_ENTRY


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def derive_store_config(
    target_keys: int = 0, mib: int = 0, rows: int = 16
) -> StoreConfig:
    """Derive store geometry from an operator-level budget.

    Exactly one of `target_keys` / `mib` must be positive:

    - `target_keys`: the SMALLEST power-of-two capacity whose load at
      the expected live-key count stays under MAX_LOAD — throughput
      first, because footprint IS the per-batch cost (10M keys derive
      the 512 MiB shape the r5 sweep measured 1.75x faster than 1 GiB,
      at load 0.60 — the deliberate eviction-pressure trade).
    - `mib`: the largest power-of-two slot count whose footprint fits
      in `mib` MiB — the knob for matching a known HBM budget.

    The derived shape always satisfies StoreConfig's invariants
    (power-of-two slots, rows*slots % 16 == 0): slots are floored at
    SLOTS_PER_DENSE_ROW so even rows=1 keeps the dense 128-lane view.
    """
    if (target_keys > 0) == (mib > 0):
        raise ValueError(
            "derive_store_config needs exactly one of target_keys / mib"
        )
    if target_keys > 0:
        entries = int(target_keys / MAX_LOAD) + 1
        slots = _pow2_at_least(-(-entries // rows))
    else:
        entries = (mib << 20) // BYTES_PER_ENTRY
        if entries < rows:
            raise ValueError(
                f"store budget {mib} MiB holds fewer than one bucket of "
                f"{rows} ways ({rows * BYTES_PER_ENTRY} bytes)"
            )
        slots = 1 << ((entries // rows).bit_length() - 1)
    slots = max(slots, SLOTS_PER_DENSE_ROW)
    return StoreConfig(rows=rows, slots=slots)


def check_store_budget(
    config: StoreConfig, target_keys: int, cold_tier: bool = False
) -> str:
    """Footprint-vs-key-budget lint for boot time. Returns '' when the
    provisioned shape suits `target_keys` live keys, else a one-line
    diagnosis (caller decides warn vs fail): oversized tables pay the
    footprint≍throughput law for nothing; undersized ones over-admit
    under eviction pressure.

    `cold_tier=True` (the r13 sketch tier is active): an "undersized"
    exact tier is the DESIGN, not a misconfiguration — keys past the
    eviction ceiling overflow to the count-min tier fail-closed instead
    of over-admitting, so only the oversize lint fires."""
    if target_keys <= 0:
        return ""
    cap = store_capacity(config)
    mib = store_footprint_bytes(config) / (1 << 20)
    if cap > target_keys * OVERSIZE_FACTOR:
        return (
            f"store is oversized for the key budget: {cap} entries "
            f"({mib:.0f} MiB) provisioned for {target_keys} live keys "
            f"(load {target_keys / cap:.2f}). Decide throughput is a pure "
            f"function of table footprint (BENCH_ZIPF10M_PROFILE_r5.json); "
            f"right-size with GUBER_STORE_TARGET_KEYS={target_keys} "
            f"(~{derive_store_config(target_keys=target_keys, rows=config.rows).slots} slots) "
            f"or accept the throughput cost explicitly"
        )
    if cold_tier:
        return ""
    if target_keys > cap * MAX_LOAD:
        return (
            f"store is undersized for the key budget: {target_keys} live "
            f"keys against {cap} entries (load {target_keys / cap:.2f} > "
            f"{MAX_LOAD}) — expect measurable over-admission from "
            f"eviction pressure; raise GUBER_STORE_TARGET_KEYS sizing or "
            f"GUBER_STORE_MIB"
        )
    return ""


def check_host_budget(budget_mib: int, parts: dict) -> str:
    """Whole-host footprint lint (r13): does EVERYTHING the budget is
    supposed to cover actually fit? `parts` maps tier name -> bytes
    (exact store, sketch rows, shed cache, replication standby).
    Returns '' when the sum fits `budget_mib`, else a one-line
    diagnosis — "1 GiB budget" must mean the whole host's rate-limit
    state, not just the exact tier (caller decides warn vs fail via
    GUBER_STORE_SIZE_STRICT)."""
    if budget_mib <= 0:
        return ""
    total = sum(parts.values())
    if total <= (budget_mib << 20):
        return ""
    detail = " + ".join(
        f"{k} {v / (1 << 20):.1f} MiB" for k, v in parts.items()
    )
    return (
        f"declared GUBER_STORE_MIB={budget_mib} is exceeded by the "
        f"full rate-limit-state footprint: {detail} = "
        f"{total / (1 << 20):.1f} MiB — the budget covers exact tier "
        f"+ sketch tier + shed cache + replication standby; shrink "
        f"one (GUBER_SKETCH_MIB / GUBER_SHED_CACHE_KEYS / "
        f"GUBER_REPLICATION_STANDBY_KEYS) or raise the budget"
    )


def new_store(config: StoreConfig = StoreConfig()) -> Store:
    return Store(
        data=jnp.zeros((config.slots, config.rows * LANES), jnp.int32)
    )


# rows of the table `rebase` shifts at a time (16 MiB at 16 ways)
REBASE_BLOCK_ROWS = 1 << 15


def _rebase_block(data: jax.Array, delta: jax.Array) -> jax.Array:
    """`rebase` of one block of bucket rows int32[..., rows, W]."""
    lane = jnp.arange(data.shape[-1]) % LANES
    is_expire = lane == L_EXPIRE
    is_ts = lane == L_TS
    # broadcast each entry's flags across its 8 lanes so the L_TS
    # decision can read them elementwise (entries are LANES-aligned;
    # shape-generic over any leading axes — sharded stores carry one)
    lead = data.shape[:-1]
    W = data.shape[-1]
    flags = data.reshape(*lead, W // LANES, LANES)[
        ..., L_FLAGS : L_FLAGS + 1
    ]
    flags = jnp.broadcast_to(flags, (*lead, W // LANES, LANES)).reshape(
        *lead, W
    )
    ts_is_count = (flags & FLAG_ALGO_SLIDING) != 0
    is_time = is_expire | (is_ts & ~ts_is_count)
    shifted = jnp.clip(
        data.astype(jnp.int64) - jnp.where(is_time, delta, 0),
        TIME_FLOOR,
        COUNTER_MAX,
    ).astype(jnp.int32)
    return jnp.where(is_time, shifted, data)


@jax.named_scope("rebase")
def rebase(store: Store, delta: jax.Array) -> Store:
    """Shift all stored times by -delta (the host moved the epoch forward
    by `delta` ms). One elementwise pass over the store; runs every ~12
    days of engine uptime (see EpochClock), so the int64 widening here is
    free in practice.

    Flag-aware since r15: the L_TS lane is a TIME for token (creation
    time), leaky (last-leak timestamp) and GCRA (last-touch time)
    entries, but a COUNT for sliding-window entries (the previous
    subwindow's consumed total, core/algorithms.py) — shifting it there
    would corrupt the blend. Each entry's own L_FLAGS lane decides; the
    per-entry broadcast is one extra elementwise select in a pass that
    runs twice a month.

    The pass walks the table REBASE_BLOCK_ROWS bucket rows at a time and
    writes each block back where it was, so with the store donated
    (`kernels.rebase_jit`) the program holds one table and a block:
    reading the flags reshapes what it reads, XLA copies what is
    reshaped, and as one expression over the whole table that copy was
    a second table — at 16 ways x 2^24 rows the TPU compiler refused the
    program (17.25 GB wanted of 15.75; PR 30)."""
    data = store.data
    buckets, W = data.shape[-2:]
    rows = min(buckets, REBASE_BLOCK_ROWS)
    assert buckets % rows == 0, (buckets, rows)
    lead = data.shape[:-2]

    def shift_block(i, data):
        at = (0,) * len(lead) + (i * rows, 0)
        block = jax.lax.dynamic_slice(data, at, lead + (rows, W))
        return jax.lax.dynamic_update_slice(
            data, _rebase_block(block, delta), at
        )

    return Store(
        data=jax.lax.fori_loop(0, buckets // rows, shift_block, data)
    )


def mix64(x: jax.Array) -> jax.Array:
    """splitmix64 finalizer (device-side twin of core.hashing.mix64)."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


_BUCKET_SALT = np.uint64(0x9E3779B97F4A7C15)


def bucket_index(key_hash: jax.Array, buckets: int) -> jax.Array:
    """[B] owning bucket index for each key hash [B]."""
    mixed = mix64(key_hash ^ _BUCKET_SALT)
    return (mixed & jnp.uint64(buckets - 1)).astype(jnp.int32)


def fingerprints(key_hash: jax.Array) -> jax.Array:
    """Nonzero int32 tags [B] from key hashes [B] (bitcast of the high 32
    bits, so the full hash entropy is split between slot index and tag)."""
    fp = (key_hash >> jnp.uint64(32)).astype(jnp.uint32)
    fp = jnp.where(fp == 0, jnp.uint32(1), fp)
    return jax.lax.bitcast_convert_type(fp, jnp.int32)


def group_sort_key(
    key_hash: jax.Array, valid: jax.Array, buckets: int
) -> jax.Array:
    """uint64 (bucket << 32 | fingerprint) sort key [B]; invalid rows sort
    last (all-ones). Sorting batches by this key groups same-key requests
    (up to fingerprint collisions, which the store cannot distinguish
    anyway) in bucket-major order — the monotonic-index fast path for
    every downstream gather/scatter. Decode with decode_sort_key."""
    bkt = bucket_index(key_hash, buckets)
    fp = fingerprints(key_hash)
    fp_u = jax.lax.bitcast_convert_type(fp, jnp.uint32)
    key = (bkt.astype(jnp.uint64) << jnp.uint64(32)) | fp_u.astype(
        jnp.uint64
    )
    return jnp.where(valid, key, jnp.uint64(0xFFFFFFFFFFFFFFFF))


def group_sort_key_np(key_hash: np.ndarray, buckets: int) -> np.ndarray:
    """Host/numpy twin of group_sort_key (without the valid handling):
    uint64 (bucket << 32 | fingerprint) for presorting batches before
    dispatch (engine.pad_request_sorted). Must stay bit-identical to the
    device pair (bucket_index, fingerprints)."""
    from gubernator_tpu.core import hashing

    kh = np.asarray(key_hash, np.uint64)
    mixed = hashing.mix64(kh ^ _BUCKET_SALT)
    bkt = mixed & np.uint64(buckets - 1)
    fp = (kh >> np.uint64(32)).astype(np.uint64)
    fp = np.where(fp == 0, np.uint64(1), fp)
    return (bkt << np.uint64(32)) | fp


def decode_sort_key(skey: jax.Array, buckets: int):
    """(bkt, fp) decoded from sorted group_sort_key values. The invalid
    tail decodes to 2^32-1 and is clamped IN THE UNSIGNED DOMAIN to
    buckets-1 so the index stream stays non-decreasing; fp for those rows
    is garbage that the caller's valid mask ignores."""
    bkt = jnp.minimum(
        skey >> jnp.uint64(32), jnp.uint64(buckets - 1)
    ).astype(jnp.int32)
    fp = jax.lax.bitcast_convert_type(skey.astype(jnp.uint32), jnp.int32)
    return bkt, fp
