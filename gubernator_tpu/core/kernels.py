"""The batched rate-limit decision kernel.

One jitted, branch-free function evaluates a whole batch of rate-limit
requests against the slot store: the TPU-native rewrite of the reference's
per-request, mutex-serialized algorithm dispatch
(reference gubernator.go:236-251 -> algorithms.go:24-186). Control flow is
data flow: every reference branch becomes a mask, the LRU hash map becomes
one wide gather + one wide scatter on the packed store, and the whole
cluster-hot-path lock (reference gubernator.go:237) disappears — a batch
is one XLA program.

Data-movement design (the performance core):
- All state and arithmetic is int32 (native on TPU; int64 is emulated and
  measured 2-10x slower for these gather/scatter/scan shapes). Time is
  epoch-relative engine-ms — see core.store docstring for the envelope.
- The store keeps ONE canonical shape [buckets, ways*LANES] through the
  whole program: the lookup gathers whole bucket rows from it and the
  writeback scatter-ADDS whole delta rows back into it
  (_writeback_delta_add). No reshape of the store ever happens inside
  jit — reshapes force XLA to insert layout-conversion copies of the
  entire array per step, which measured 3x the cost of all actual
  compute (profiler: 3 x ~0.8 ms copies per step for a 32 MiB store on
  v5e). With the default ways=16, a bucket row is exactly 128 lanes —
  the native TPU vector width, the fast path for both transfers.
- The batch is sorted BUCKET-major, so every index stream downstream of
  the sort (bucket gather, group-leader gathers, writeback destinations)
  is monotonically non-decreasing, and all requests touching one bucket
  are contiguous — which gives the writeback its per-bucket conflict
  accounting and XLA its sorted gather fast path (the scatter takes
  the sorted hint only where the batch is deep beside the store:
  writeback_form).
- Per-group hit sums use a *segmented saturating* associative scan:
  segment flags reset at group leaders, and the add saturates at int32
  max so refused oversized hits can never wrap (saturation only engages
  when the true sum already exceeds any representable budget, where
  refusal is the correct answer regardless). Boolean group reductions ride
  plain int32 cumsums. Measured dead end (v5e, r2): replacing these
  scans with global cumsums + leader-row gathers loses 35-100% in every
  variant tried (int64 cumsum overflows scoped VMEM at B=32k; digit-split
  int32 cumsums with int32 lexicographic compares, and scatter-add
  group-ANY flags, are each individually faster in isolation but slower
  in-kernel) — the associative scan's log-steps fuse with surrounding
  elementwise work while reduce-window cumsum lowering does not.
  Likewise the static per-way select chains below beat a
  jnp.take_along_axis gather along the way axis by ~15% whole-kernel.
  Measured dead end (v5e, r3): moving the presort ON-device (to let the
  mesh host ship raw unsorted batches and shuffle via all_to_all, MoE
  dispatch style) is ruled out by lax.sort cost — a u64/i32
  sort_key_val measures 1.4-1.9ms at B=4k-32k (verified with an
  order-sensitive consumer; with permutation-invariant consumers XLA
  deletes the sort and the probe reads ~0us), i.e. 3-4x the ENTIRE
  decide kernel. Host presort + thread-pooled native prep stays the
  design.

Intra-batch duplicate keys
--------------------------
The reference handles concurrent same-key requests by serializing them on
the cache mutex in arbitrary goroutine order (gubernator.go:90-160). Here a
batch is sorted by key hash, each group of same-key requests shares one
state read and one state write, and requests within a group are applied in
batch order under a *cumulative-attempt* rule:

    request j is admitted iff (sum of same-key hits earlier in the batch
    that could ever fit the window) + hits_j <= remaining_at_batch_start

Hits larger than the whole starting budget are excluded from the prefix so
an oversized refused request does not starve later small ones. This matches
sequential-greedy exactly when all duplicate hits are equal (the common
hot-key case) and is conservative otherwise; since the reference's own
ordering is scheduler-dependent, any such consistent order is within its
observable envelope. Same-batch duplicates with *different* algorithms or
behaviors resolve with group-leader (first in batch order) semantics.
One observable consequence: when every duplicate mismatches the STORED
entry's algorithm, the reference recreates the window once per request
(each call wipes and recreates, algorithms.go:33-38,100-105) while this
kernel recreates once per batch and charges the remaining duplicates
against the new window — a strictly more useful behavior for what is a
pathological, scheduler-dependent case in the reference. Similarly,
same-batch duplicate leaky PEEKS (hits=0) all read one state snapshot,
whereas the reference's sequential peeks each re-apply the sub-tick
leak (a peek persists the replenished remaining without advancing the
timestamp, algorithms.go:118-138) and so can ratchet remaining upward
call by call within one tick.

Time enters as one int32 engine-ms scalar `now` per batch; all requests in
a batch share it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from gubernator_tpu.core.store import (
    FLAG_ALGO_GCRA,
    FLAG_ALGO_LEAKY,
    FLAG_ALGO_MASK,
    FLAG_ALGO_SLIDING,
    FLAG_STICKY_OVER,
    L_DURATION,
    L_EXPIRE,
    L_FLAGS,
    L_KEYLOW,
    L_LIMIT,
    L_REMAINING,
    L_TAG,
    L_TS,
    LANES,
    Store,
    bucket_index,
    decode_sort_key,
    fingerprints,
    group_sort_key,
    mix64,
    rebase,
)

UNDER = 0
OVER = 1


_I32_MIN = jnp.iinfo(jnp.int32).min
_I32_MAX = jnp.iinfo(jnp.int32).max


class BatchRequest(NamedTuple):
    """Device-side request batch; all arrays are [B]."""

    key_hash: jax.Array  # uint64
    hits: jax.Array  # int32 (host-saturated from the wire's int64)
    limit: jax.Array  # int32
    duration: jax.Array  # int32 (engine-clamped ms, <= MAX_DURATION_MS)
    algo: jax.Array  # int32: 0 token, 1 leaky
    gnp: jax.Array  # bool: GLOBAL non-owner replica read (gubernator.go:173-195)
    valid: jax.Array  # bool: padding mask


class BatchGroups(NamedTuple):
    """Group (unique-key) structure of a presorted batch.

    Store I/O scales with the number of GROUPS, not requests: duplicate
    keys in a batch share one state read and one state write, so the
    bucket-row gather, the eviction-conflict accounting and the
    writeback scatter all run at [G] instead of [B]. Real traffic is
    duplicate-heavy (the bench's zipf batch has G/B ~ 0.26), which makes
    this the single largest device-time lever after the branch-free
    rewrite itself.

    The host computes groups for free during the radix presort
    (guberhash.cc emits them from the sorted key stream); callers
    without host groups get an on-device derivation at G == B
    (decide_presorted(groups=None)) with the exact historical cost.

    - key_hash uint64[G]: the group leader's key hash (host-gathered:
      a device-side 64-bit 1-column gather measured ~87us at B=16k,
      the single most expensive narrow op in the kernel).
    - leader_pos int32[G]: index in [B] of the group's first row; padded
      groups carry B (clipped gathers repeat the last real row, keeping
      every derived stream monotone).
    - end_pos int32[G]: inclusive index of the group's last row (padding
      request rows belong to the last real group), non-decreasing.
    - valid bool[G]: real group (false for padding slots).
    - group_id int32[B]: each request row's group slot, non-decreasing;
      padding request rows point at the last real group.
    """

    key_hash: jax.Array
    leader_pos: jax.Array
    end_pos: jax.Array
    valid: jax.Array
    group_id: jax.Array


class Sketch(NamedTuple):
    """Count-min cold-tier state (r13/r21, core/sketches.SketchConfig):
    dense [rows, width] counters — int64 under the r13 derivation,
    saturating int32 under the v2 derivation (core/sketches.py
    documents why saturation is fail-closed). A one-leaf pytree like
    Store so the whole sketch donates cleanly through the jitted
    decide."""

    data: jax.Array  # int32 or int64 [rows, width]


@jax.named_scope("sketch_lookup")
def _sketch_lookup(sketch: Sketch, kh: jax.Array, wid: jax.Array):
    """Per-group (min-estimate int64[G], per-row index list int32[G])
    for window-keyed key hashes. The estimate is widened to int64
    regardless of the counter dtype so downstream budget math is
    uniform. MUST stay bit-identical to the host twin
    core/sketches.sketch_indices_np (test-pinned): the promoter and the
    error-bound tests read estimates host-side for windows this kernel
    charged."""
    from gubernator_tpu.core.sketches import SKETCH_SALTS, WINDOW_MIX

    rows, width = sketch.data.shape
    base = mix64(kh ^ (wid.astype(jnp.uint64) * jnp.uint64(WINDOW_MIX)))
    est = None
    idxs = []
    for r in range(rows):
        hr = mix64(base ^ jnp.uint64(SKETCH_SALTS[r]))
        idx = (hr & jnp.uint64(width - 1)).astype(jnp.int32)
        idxs.append(idx)
        # narrow unsorted gather [G]
        c = jnp.take(sketch.data[r], idx).astype(jnp.int64)
        est = c if est is None else jnp.minimum(est, c)
    return est, idxs


class BatchResponse(NamedTuple):
    """Device-side response batch; all arrays are [B]."""

    status: jax.Array  # int32
    limit: jax.Array  # int32
    remaining: jax.Array  # int32
    reset_time: jax.Array  # int32 engine-ms (0 = no reset, leaky UNDER)


class BatchStats(NamedTuple):
    hits: jax.Array  # int32 scalar: groups answered from live state
    misses: jax.Array  # int32 scalar: groups created/recreated
    # over-admission signals (reference exposes cache_size against a
    # known max, cache/lru.go:56-59; a slot store at capacity instead
    # silently sheds state, so these MUST be observable — /metrics
    # exports them as store_dropped_creates_total / store_evictions_total)
    dropped: jax.Array  # int32 scalar: creates lost to way exhaustion
    evictions: jax.Array  # int32 scalar: live entries overwritten




def _shift1(x: jax.Array, fill) -> jax.Array:
    """x shifted right by one along axis 0, with `fill` at position 0."""
    pad = jnp.full((1,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([pad, x[:-1]])


def _sat_add(a: jax.Array, b: jax.Array) -> jax.Array:
    """a + b saturating at int32 max, overflow-free for a, b >= 0."""
    return a + jnp.minimum(b, _I32_MAX - a)


def _seg_scan(is_leader: jax.Array, values: jax.Array):
    """Segmented saturating inclusive prefix sums of values [B, K] over
    contiguous groups whose first element has is_leader set. Returns the
    inclusive scan [B, K]; callers derive exclusive prefixes by shifting
    within segments and group totals by gathering at group end positions.

    Saturating add over non-negatives composes associatively
    (min(a+b, M) for a,b >= 0), and segmentation preserves associativity
    by the standard (flag, value) construction."""
    flags = is_leader

    def op(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf[:, None], bv, _sat_add(av, bv))

    _, incl = lax.associative_scan(op, (flags, values))
    return incl


def _segment_ends(is_leader: jax.Array, ar: jax.Array) -> jax.Array:
    """[B] inclusive end position of each element's segment: predecessor
    of the next leader (B-1 for the final segment)."""
    B = ar.shape[0]
    lead_idx = jnp.where(is_leader, ar, B)
    next_incl = lax.associative_scan(jnp.minimum, lead_idx, reverse=True)
    return (
        jnp.concatenate([next_incl[1:], jnp.full((1,), B, ar.dtype)]) - 1
    )


# writeback_form's crossover: the hinted scatter wins from B = rows / 32
# up (a ~1.5 ns a table row sweep against ~70 ns a batch row of touches
# puts the tie near rows / 44; measured either side of it at 2^15 to
# 2^20 rows, scripts/bench_sweep_regime.py --hint on v5e, PR 31)
SORTED_HINT_ROWS_PER_ITEM = 32


def writeback_form(buckets: int, W: int, B: int) -> str:
    """Trace-time choice of the writeback's form, from the shapes
    _writeback_apply sees (its table is [buckets, W], its batch B rows;
    under shard_map both are one shard's) and nothing else. One
    arithmetic — delta rows added at sorted, possibly duplicate bucket
    indices into disjoint ways — in three forms whose cost models
    differ (ms a call on v5e, PERF.md section 6, PR 31):

    - "sweep": the pallas store sweep (core/pallas_sweep.py), dense
      updates, B >= 4x the bucket count, where it beat the hinted XLA
      scatter by 1.14-1.34x (r3). Never on a non-TPU backend: it is a
      Mosaic kernel.
    - "scatter_sorted": the XLA scatter-add told its indices are
      sorted. The TPU then sweeps the WHOLE operand in place: cost
      follows the table's bytes (0.05 ms at 2^15 rows, 1.6 at 2^20,
      25-26 at 2^24), hardly B. Taken where the batch is deep beside
      the store, B >= buckets / 32.
    - "scatter": the same scatter-add without the hint. The TPU then
      touches the B rows: cost follows B (0.007 ms at B = 64, 0.08 at
      1024, 1.2 at 16384) whatever the table's size. Everywhere else:
      every rung of the default ladder into any store of more than
      2^15 rows, and all but a full 1024-group batch into that one.

    GUBER_WRITEBACK: "auto" (default) as above; "scatter" never takes
    the sweep, "sweep" takes it wherever its shape constraints allow;
    whether the scatter is told is the shapes' business under all
    three. Unknown values fall back to auto."""
    import os

    mode = os.environ.get("GUBER_WRITEBACK", "auto")
    scatter = (
        "scatter_sorted"
        if SORTED_HINT_ROWS_PER_ITEM * B >= buckets
        else "scatter"
    )
    if mode == "scatter":
        return scatter
    if mode != "sweep" and jax.default_backend() != "tpu":
        # the sweep is a Mosaic TPU kernel: auto must never pick it on
        # a CPU/GPU backend, where its non-interpret lowering cannot
        # compile (the CPU mesh-serving stacks hit exactly this before
        # r14 gated it). GUBER_WRITEBACK=sweep still forces the path
        # (interpret-mode tests and TPU-bound benches).
        return scatter
    if mode != "sweep" and B < 4 * buckets:
        return scatter
    from gubernator_tpu.core.pallas_sweep import CHUNK, TILE_ROWS

    if (
        W == 128
        and buckets % TILE_ROWS == 0
        and B >= CHUNK
        and B % 8 == 0
    ):
        return "sweep"
    return scatter


@jax.named_scope("writeback_plan")
def _writeback_plan(
    cand: jax.Array,  # int32[B, ways, LANES] pre-write bucket contents
    bkt: jax.Array,  # int32[B] bucket per item, sorted non-decreasing
    write_item: jax.Array,  # bool[B] the group member designated to write
    found: jax.Array,  # bool[B] tag matched in the bucket
    fway: jax.Array,  # int32[B] matching way (valid where found)
    eway: jax.Array,  # int32[B] eviction-candidate way (for misses)
    is_b_leader: jax.Array,  # bool[B] first item of its bucket segment
    b_end: jax.Array,  # int32[B] inclusive end of the bucket segment
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Phase 1 of the delta-add writeback: decide, per item, WHO writes
    WHERE — and which creates drop (way exhaustion) or evict a live
    occupant. Returns (writer, way, dropped, evicted), all [B]. Split
    from the scatter apply so the decide kernel can consult `dropped`
    BEFORE response math: with the sketch cold tier on, a dropped
    create is decided from the count-min estimate instead of being
    silently over-admitted (decide_presorted_sketch), so the drop mask
    must exist before budgets are computed. See _writeback_delta_add
    for the way-disjointness guarantees this plan enforces."""
    B = bkt.shape[0]
    ways = cand.shape[1]
    ar = jnp.arange(B, dtype=jnp.int32)

    way_ids = jnp.arange(ways, dtype=jnp.int32)[None, :]
    miss_w = write_item & ~found
    found_w = write_item & found
    onehotF = (found_w[:, None] & (fway[:, None] == way_ids)).astype(
        jnp.int32
    )

    # bucket-segment prefix/total machinery over [B, 1+ways] in ONE
    # cumsum: col 0 ranks miss-writers, cols 1.. count found-writers/way
    stacked = jnp.concatenate(
        [miss_w.astype(jnp.int32)[:, None], onehotF], axis=1
    )
    c = jnp.cumsum(stacked, axis=0)
    before = c - stacked
    b_leader_pos = lax.cummax(jnp.where(is_b_leader, ar, 0))
    start_excl = jnp.take(
        before, b_leader_pos, axis=0, indices_are_sorted=True
    )
    prefix = before - start_excl  # strictly-before-j within my bucket
    totals = (
        jnp.take(c, b_end, axis=0, indices_are_sorted=True) - start_excl
    )

    rank = prefix[:, 0]  # earlier miss-writers in my bucket
    empty = cand[:, :, L_TAG] == 0  # [*, ways] pre-write, bucket-uniform
    cumempty = jnp.cumsum(empty.astype(jnp.int32), axis=1)
    n_empty = cumempty[:, -1]
    # the (rank)-th empty way (0-indexed) of my bucket
    pick = empty & (cumempty == (rank + 1)[:, None])
    has_empty = rank < n_empty
    eway_sel = jnp.where(
        has_empty, jnp.argmax(pick, axis=1).astype(jnp.int32), eway
    )
    # eviction fallback: conflict if any found-group WRITES my victim way
    f_tot = totals[:, 1:]  # [*, ways] found-writer count per way
    fconf = (
        jnp.sum(
            jnp.where(eway_sel[:, None] == way_ids, f_tot, 0), axis=1
        )
        > 0
    )
    dropped = miss_w & ~has_empty & ((rank > 0) | fconf)
    # a miss that writes with no empty way left overwrites the
    # earliest-expiry occupant: that's an eviction (lazy-expired entries
    # are indistinguishable from live ones here — counting them is the
    # conservative direction for an over-admission alarm)
    evicted = miss_w & ~has_empty & ~dropped

    writer = found_w | (miss_w & ~dropped)
    way = jnp.where(found, fway, eway_sel)
    return writer, way, dropped, evicted


def _writeback_apply(
    data: jax.Array,  # int32[buckets, ways*LANES]
    bkt: jax.Array,  # int32[B] sorted bucket per item
    writer: jax.Array,  # bool[B] from _writeback_plan
    way: jax.Array,  # int32[B] from _writeback_plan
    new_vals: jax.Array,  # int32[B, LANES] the update for writer rows
    cand: jax.Array,  # int32[B, ways, LANES] pre-write bucket contents
) -> jax.Array:
    """Phase 2: apply the planned updates as ONE scatter-ADD of delta
    rows (the arithmetic and measured rationale live on
    _writeback_delta_add), in the form writeback_form picks from this
    call's traced shapes: the only place a decide writes the table."""
    B = bkt.shape[0]
    buckets, W = data.shape
    ways = W // LANES
    way_ids = jnp.arange(ways, dtype=jnp.int32)[None, :]

    # old entry lanes at the destination way (vector selects; ways static)
    old8 = cand[:, 0]
    for w in range(1, ways):
        old8 = jnp.where((way == w)[:, None], cand[:, w], old8)

    delta8 = jnp.where(writer[:, None], new_vals - old8, 0)
    dmask = (way[:, None] == way_ids) & writer[:, None]  # [B, ways]
    drow = jnp.where(
        dmask[:, :, None], delta8[:, None, :], 0
    ).reshape(B, W)

    form = writeback_form(buckets, W, B)
    if form == "sweep":
        from gubernator_tpu.core.pallas_sweep import _apply_inline

        return _apply_inline(data, bkt, drow)
    return data.at[bkt].add(
        drow, indices_are_sorted=form == "scatter_sorted"
    )


def _writeback_delta_add(
    data: jax.Array,  # int32[buckets, ways*LANES]
    bkt: jax.Array,  # int32[B] bucket per item, sorted non-decreasing,
    # in range for EVERY row (invalid rows carry a real bucket and simply
    # add a zero row — cheaper than sentinel indices, which would break
    # the sorted-index promise when invalid rows are interspersed)
    write_item: jax.Array,  # bool[B] the group member designated to write
    # (decide: the group leader of a VALID group; upsert_globals: the
    # LAST duplicate, for last-wins install) — at most one per group
    found: jax.Array,  # bool[B] tag matched in the bucket
    fway: jax.Array,  # int32[B] matching way (valid where found)
    eway: jax.Array,  # int32[B] eviction-candidate way (for misses)
    new_vals: jax.Array,  # int32[B, LANES] the update for write_item rows
    cand: jax.Array,  # int32[B, ways, LANES] pre-write bucket contents
    is_b_leader: jax.Array,  # bool[B] first item of its bucket segment
    b_end: jax.Array,  # int32[B] inclusive end of the bucket segment
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply per-entry updates as ONE scatter-ADD of delta rows — no
    cross-group merge pass at all. Returns (new_data, n_dropped,
    n_evicted): creates lost to way exhaustion and occupied ways
    overwritten, the store's over-admission signals. (Composition of
    _writeback_plan + _writeback_apply; decide_presorted calls the two
    phases separately so the drop mask can feed the sketch tier.)

    Each designated writer adds (new_vals - old_entry_lanes) into its
    way's lanes of its bucket row; all other positions add zero rows at
    their own (sorted) bucket index, so the scatter's index stream is the
    already-sorted bucket stream and duplicate indices are legal by the
    arithmetic: updates to one bucket touch DISJOINT ways, so the adds
    compose exactly (old + (new - old) = new; int32 wrap-around in the
    subtraction self-corrects on the add) and in any order — which is
    why the three forms of the apply (pallas sweep, scatter-add told
    its indices are sorted, scatter-add not told: writeback_form) leave
    the table equal word for word. Measured on v5e this replaces
    ~500us of [B,128] segmented select-scans with ~30us of [B,16]
    cumsums + one add-scatter at B=16384.

    Way-disjointness is guaranteed, not assumed:
    - two found-groups can never share a way (one tag per way);
    - miss-groups are RANKED within their bucket and the k-th one claims
      the k-th EMPTY way, so simultaneous fresh keys colliding in one
      bucket all persist as long as empty ways remain (the r1 design let
      only the first write and silently dropped the rest — measured ~50%
      of creations lost in a cold-start storm on dense buckets);
    - only the rank-0 miss of a bucket with NO empty way may evict (the
      earliest-expiry way), and not if a found-group writes that way
      this batch; later-ranked misses drop. A dropped create costs brief
      over-admission for that key — the same contract as reference LRU
      eviction / restart state loss (architecture.md:5-11) — and now
      happens only once a bucket's EMPTY ways are exhausted within the
      batch (occupied ways + concurrent fresh keys > ways), instead of
      on any same-batch collision. (With the sketch cold tier on, a
      dropped create is not over-admission at all: the sketch serves it
      fail-closed — decide_presorted_sketch.)
    """
    writer, way, dropped, evicted = _writeback_plan(
        cand, bkt, write_item, found, fway, eway, is_b_leader, b_end
    )
    return (
        _writeback_apply(data, bkt, writer, way, new_vals, cand),
        jnp.sum(dropped).astype(jnp.int32),
        jnp.sum(evicted).astype(jnp.int32),
    )


def decide_presorted(
    store: Store,
    req: BatchRequest,
    now: jax.Array,
    groups: BatchGroups | None = None,
) -> Tuple[Store, BatchResponse, BatchStats]:
    """Exact-only decide (the pre-r13 surface, unchanged semantics):
    see _decide_presorted for the full caller contract."""
    store, _sketch, resp, stats = _decide_presorted(
        store, req, now, groups, None
    )
    return store, resp, stats


def decide_presorted_chain(
    store: Store,
    req: BatchRequest,
    now: jax.Array,
    chain_id: jax.Array,
    groups: BatchGroups | None = None,
) -> Tuple[Store, BatchResponse, BatchStats]:
    """Quota-chain decide (r15): evaluate a presorted batch whose rows
    are COUPLED into chains — `chain_id` int32[B] gives each row its
    chain slot (rows of one hierarchical request share an id; plain
    rows carry a unique id each), ids need not be contiguous in the
    sorted order (chain levels hash to different buckets by design).

    Semantics: each row decides exactly as decide_presorted would
    (optimistically), then a chain whose ANY member row reports
    OVER_LIMIT has EVERY member's state charge rolled back before the
    single writeback — the no-partial-debit contract: a level's
    refusal never consumes quota at any other level, all inside one
    device pass. Responses stay the per-level optimistic verdicts; the
    serving tier collapses them most-restrictive-wins
    (serve/instance.py). In-batch conservatism: a later chain sharing
    a level with an earlier ROLLED-BACK chain still sees the
    optimistic prefix, so it can only be refused where sequential
    processing might have admitted it — at-least-as-restrictive, the
    same direction as the kernel's cumulative-attempt rule. The sketch
    cold tier is not consulted on this path (chain batches run
    exact-only; see core/algorithms.py).

    With every chain a singleton (chain_id all-distinct), decisions
    and the written store are byte-identical to decide_presorted —
    the depth-1 identity pinned by tests/test_chains.py."""
    store, _sketch, resp, stats = _decide_presorted(
        store, req, now, groups, None, chain_id=chain_id
    )
    return store, resp, stats


def decide_presorted_sketch(
    store: Store,
    sketch: Sketch,
    req: BatchRequest,
    now: jax.Array,
    groups: BatchGroups | None = None,
) -> Tuple[Store, Sketch, BatchResponse, BatchStats]:
    """Two-tier decide (r13): the exact slot store stays the heavy-
    hitter tier with byte-identical semantics, and creates the exact
    tier REFUSES are decided from the count-min cold tier instead —
    both the way-exhaustion drops (the exact-only kernel's silent
    over-admission case) and, under live-victim protection, creates
    that would have EVICTED a resident key's live window (the
    eviction-churn case that dominates at 100M-key pressure). Sketch
    decisions are fixed-window token math over the window-keyed
    conservative-update estimate (budget = max(limit - estimate, 0),
    reset = window end, no store write); the estimate never
    under-counts the hits the sketch was charged with, so tail-key
    error is one-sided (fail-closed). Pure; jit with
    donate_argnums=(0, 1).

    Identity contract (pinned by tests/test_sketch_tier.py): a group
    touching a LIVE exact entry produces bit-identical (store,
    response) to decide_presorted, and with no tier pressure (no full
    buckets) the whole pipeline is byte-identical ON vs OFF. Under
    pressure, residency can only be BROADER with the tier on (live
    entries are never churned by tail creates), and every divergent
    response is at-least-as-restrictive."""
    return _decide_presorted(store, req, now, groups, sketch)


def _decide_presorted(
    store: Store,
    req: BatchRequest,
    now: jax.Array,
    groups: BatchGroups | None,
    sketch: Sketch | None,
    chain_id: jax.Array | None = None,
) -> Tuple[Store, Sketch | None, BatchResponse, BatchStats]:
    """Evaluate one PRESORTED padded batch; responses come back in the
    same (sorted) order. `now` is int32 engine-ms. Pure; jit with
    donate_argnums=(0,).

    Caller contract (engine.pad_request_sorted / the decide() wrapper):
    - rows are ordered so that (bucket(key_hash), fingerprint(key_hash))
      is non-decreasing over the WHOLE batch, including invalid rows —
      this is what lets every gather run with indices_are_sorted=True,
      and the writeback scatter where that hint pays (a batch deep
      beside the store; elsewhere the hint is withheld because the
      TPU's sorted scatter sweeps its whole operand: writeback_form).
      Hosts pad by repeating the last real row's key with valid=False,
      which preserves monotonicity.
    - invalid rows may appear anywhere (the mesh path masks non-owned
      rows in place, serve/parallel sharding), with one constraint: a
      group containing any valid row must have a VALID leader (first
      row). Ownership masking keeps whole groups uniform, and padding
      appends invalid followers after the last valid row, so both
      callers satisfy it; a hypothetical invalid-leader/valid-follower
      group would silently skip its state write (w_mask gates on the
      leader's validity).
    - `groups` (optional) carries the host-computed group structure
      padded to a [G] rung; all store I/O (bucket gather, conflict
      accounting, writeback scatter) then runs at [G] instead of [B] —
      the duplicate-compaction fast path (see BatchGroups). Without it,
      an equivalent structure is derived on device at G == B.

    Moving the sort (and the response unsort) to the host removes the
    two largest fixed costs from the device program (~30% at B=16k on
    v5e); in serving both are cheap numpy passes pipelined with device
    compute.
    """
    buckets, _W = store.data.shape
    ways = _W // LANES
    B = req.key_hash.shape[0]
    now = now.astype(jnp.int32)

    h = req.hits
    lim_q = req.limit
    algo = req.algo
    gnp = req.gnp
    valid = req.valid
    ar = jnp.arange(B, dtype=jnp.int32)

    if groups is None:
        # On-device grouping at G == B (compat path): group slot g sits
        # at the group's leader position; follower positions become
        # padding slots. Identical cost/semantics to the pre-compaction
        # kernel.
        bkt_r = bucket_index(req.key_hash, buckets)
        fp_r = fingerprints(req.key_hash)
        same_prev = jnp.concatenate(
            [
                jnp.array([False]),
                (bkt_r[1:] == bkt_r[:-1]) & (fp_r[1:] == fp_r[:-1]),
            ]
        )
        # leaders are KEY-based (first row of each same-key run),
        # regardless of validity: with interspersed invalid rows (mesh
        # masking) a group's leader must still exist so group state
        # resolves; invalid groups are excluded from charging and writes
        # by `valid` downstream.
        is_leader = ~same_prev
        group_id = lax.cummax(jnp.where(is_leader, ar, 0))
        groups = BatchGroups(
            key_hash=req.key_hash,  # slot g == request g
            leader_pos=ar,
            end_pos=_segment_ends(is_leader, ar),
            valid=is_leader & valid,
            group_id=group_id,
        )
    else:
        gi = groups.group_id
        same_prev = jnp.concatenate(
            [jnp.array([False]), gi[1:] == gi[:-1]]
        )
        is_leader = ~same_prev

    G = groups.leader_pos.shape[0]
    lead_clip = jnp.minimum(groups.leader_pos, B - 1)
    end_pos_G = groups.end_pos

    # ---- group-level state: gathers and lookup at [G] ---------------------
    with jax.named_scope("probe_gather"):
        kh_G = groups.key_hash
        bkt = bucket_index(kh_G, buckets)  # [G] non-decreasing
        fp = fingerprints(kh_G)

        # bucket lookup: ONE sorted gather of whole bucket rows, one row per
        # GROUP (duplicate keys share the read)
        cand = jnp.take(
            store.data, bkt, axis=0, indices_are_sorted=True
        ).reshape(G, ways, LANES)

        match = cand[:, :, L_TAG] == fp[:, None]  # [G, ways]
        found = match.any(axis=1)
        fway = jnp.argmax(match, axis=1).astype(jnp.int32)  # first matching way

        # eviction candidate among the ways: empty first, else earliest expiry
        # (the rate-limit analogue of LRU-oldest, see store.py docstring)
        evict_key = jnp.where(
            cand[:, :, L_TAG] == 0, _I32_MIN, cand[:, :, L_EXPIRE]
        )
        eway = jnp.argmin(evict_key, axis=1).astype(jnp.int32)

        # found-way state selection by vector selects (ways is tiny and static)
        sel = cand[:, 0]
        for w in range(1, ways):
            sel = jnp.where((fway == w)[:, None], cand[:, w], sel)

        g_exp = sel[:, L_EXPIRE]
        g_rem = sel[:, L_REMAINING]
        g_ts = sel[:, L_TS]
        g_limS = sel[:, L_LIMIT]
        g_durS = sel[:, L_DURATION]
        g_flg = sel[:, L_FLAGS]

        g_live = found & (g_exp >= now)  # lazy expiry (reference cache/lru.go:109)

        # leader's request fields define the group's semantics (group-leader
        # rule for mixed duplicates, see module docstring)
        lead_req = jnp.take(
            jnp.stack([algo, h, lim_q, req.duration], axis=-1),
            lead_clip,
            axis=0,
            indices_are_sorted=True,
        )
        g_algo = jnp.clip(lead_req[:, 0], 0, 3)
        g_hits = lead_req[:, 1]
        g_limQ = lead_req[:, 2]
        g_durQ = lead_req[:, 3]

        # stored algorithm from the entry's flag bits (core/algorithms.py:
        # token is the all-zero encoding, so pre-r15 entries decode as 0)
        stored_leaky = (g_flg & FLAG_ALGO_LEAKY) != 0
        stored_sld = (g_flg & FLAG_ALGO_SLIDING) != 0
        stored_gcra = (g_flg & FLAG_ALGO_GCRA) != 0
        stored_algo = (
            stored_leaky * 1 + stored_sld * 2 + stored_gcra * 3
        ).astype(jnp.int32)
        req_leaky = g_algo == 1
        # Algorithm switch recreates the window. The token/leaky pair
        # recreates as a fresh *token* bucket in both directions (reference
        # algorithms.go:33-38,100-105, kept verbatim); sliding/GCRA
        # requests recreate as their OWN algorithm (core/algorithms.py).
        mismatch = g_live & (stored_algo != g_algo)
        existing = g_live & ~mismatch
        create_algo = jnp.where(mismatch & req_leaky, 0, g_algo)
        eff_algo = jnp.where(existing, stored_algo, create_algo)
        eff_leaky = eff_algo == 1
        eff_sld = eff_algo == 2
        eff_gcra = eff_algo == 3

        # leaky guard (documented divergence: reference div-by-zero,
        # algorithms.go:107): existing leaky group with request limit <= 0
        leaky_zero = existing & eff_leaky & (g_limQ <= 0)

        # effective duration: stored for existing entries, request's for groups
        # being (re)created in this batch
        g_durE = jnp.where(g_live, g_durS, g_durQ)
        rate = jnp.maximum(g_durE // jnp.maximum(g_limQ, 1), 1)
        leak = jnp.maximum(now - g_ts, 0) // rate
        # overflow-free min(g_rem + leak, g_limS): stored remaining <= limit
        leaky_R0 = g_rem + jnp.minimum(leak, jnp.maximum(g_limS - g_rem, 0))

        now64 = now.astype(jnp.int64)

        # sliding window (r15, core/algorithms.py conventions): rotate the
        # stored subwindow pair to `now` — the entry's L_REMAINING lane is
        # the CURRENT subwindow's consumed count, L_TS the PREVIOUS one's,
        # and the window start reconstructs as expire - 2d. All in int64:
        # the blend multiply (count * ms) overflows int32 by design. The
        # EFFECTIVE period caps at SLIDING_MAX_DURATION_MS = 2^29-1 (half
        # the token envelope: the ws + 2d expire anchor must stay inside
        # int32 with now <= 2^30) — algorithms.sliding_dur is the host
        # twin, so the byte-identity holds for any requested duration.
        _SLD_DMAX = (1 << 29) - 1
        d_sld = jnp.clip(g_durS.astype(jnp.int64), 1, _SLD_DMAX)
        sld_ws0 = g_exp.astype(jnp.int64) - 2 * d_sld
        sld_k = jnp.maximum((now64 - sld_ws0) // d_sld, 0)
        sld_ws = sld_ws0 + sld_k * d_sld  # current subwindow start
        sld_cur0 = jnp.where(sld_k == 0, g_rem, 0)
        sld_prev0 = jnp.where(
            sld_k == 0, g_ts, jnp.where(sld_k == 1, g_rem, 0)
        )
        sld_wrem = d_sld - (now64 - sld_ws)  # in (0, d]
        sld_used = sld_cur0.astype(jnp.int64) + (
            sld_prev0.astype(jnp.int64) * sld_wrem
        ) // d_sld
        lim_s64 = g_limS.astype(jnp.int64)
        R0_sld = (
            jnp.clip(lim_s64 - sld_used, 0, jnp.maximum(lim_s64, 0))
            .astype(jnp.int32)
        )

        # GCRA (r15): the stored L_EXPIRE lane IS the theoretical arrival
        # time; budget = clamp((now + tau - max(TAT, now)) // T, 0, limit)
        # with T/tau from the STORED params for existing entries (creation
        # uses the request's params via the generic creation machinery and
        # the effective-params columns below). int64 throughout: tau =
        # T*limit can exceed int32 for limit >> duration.
        T_stored = jnp.maximum(
            g_durS.astype(jnp.int64)
            // jnp.maximum(g_limS.astype(jnp.int64), 1),
            1,
        )
        tau_stored = jnp.minimum(
            T_stored * jnp.maximum(lim_s64, 0), jnp.int64(_I32_MAX)
        )
        tat0_stored = jnp.maximum(g_exp.astype(jnp.int64), now64)
        R0_gcra = jnp.clip(
            (now64 + tau_stored - tat0_stored) // T_stored,
            0,
            jnp.maximum(lim_s64, 0),
        ).astype(jnp.int32)

        # group budget at batch start
        R0_exist = jnp.where(eff_leaky, leaky_R0, g_rem)
        R0_exist = jnp.where(eff_sld, R0_sld, R0_exist)
        R0_exist = jnp.where(eff_gcra, R0_gcra, R0_exist)

        # creation by the group leader (reference algorithms.go:68-84,161-186)
        over_c = g_hits > g_limQ
        charged_ldr = ~over_c & (g_hits > 0)
        R0_create = g_limQ - jnp.where(charged_ldr, g_hits, 0)
        # token creation with hits > limit stores remaining = limit ("sticky
        # over", algorithms.go:78-81); leaky stores an empty bucket (:180).
        # Sliding/GCRA creation refusals store an untouched fresh window
        # (their status is recomputed every call, nothing to persist).
        R0_create = jnp.where(over_c & eff_leaky, 0, R0_create)

        R0 = jnp.where(existing, R0_exist, R0_create)
        # sticky-over is a token-bucket-only mutation; sliding/GCRA
        # recompute their status from state every call
        sticky0 = jnp.where(
            existing,
            (g_flg & FLAG_STICKY_OVER) != 0,
            (eff_algo == 0) & over_c,
        )

        # effective GCRA params per group (stored for existing, request's
        # for creations) — the response resets and the TAT writeback below
        # share these
        eff_lim64 = jnp.where(existing, lim_s64, g_limQ.astype(jnp.int64))
        eff_dur64 = jnp.where(
            existing, g_durS.astype(jnp.int64), g_durQ.astype(jnp.int64)
        )
        gcra_T = jnp.maximum(eff_dur64 // jnp.maximum(eff_lim64, 1), 1)
        gcra_tau = jnp.minimum(
            gcra_T * jnp.maximum(eff_lim64, 0), jnp.int64(_I32_MAX)
        )
        gcra_tat0 = jnp.where(existing, tat0_stored, now64)
        # sliding response reset: the current subwindow's end (existing) or
        # the creation window's end
        sld_reset_G = jnp.where(
            existing & eff_sld,
            jnp.clip(sld_ws + d_sld, _I32_MIN, _I32_MAX),
            (now + g_durQ).astype(jnp.int64),
        ).astype(jnp.int32)

    # ---- writeback plan + sketch cold tier (r13) --------------------------
    # The writer/way/drop plan runs BEFORE response math so the sketch
    # tier can absorb dropped creates: identical arithmetic to the old
    # end-of-kernel position (inputs are all lookup-stage values).
    w_mask = groups.valid & ~leaky_zero
    ar_G = jnp.arange(G, dtype=jnp.int32)
    is_b_leader_G = jnp.concatenate(
        [jnp.array([True]), bkt[1:] != bkt[:-1]]
    )
    b_end_G = _segment_ends(is_b_leader_G, ar_G)
    writer_G, way_G, dropped_G, evicted_G = _writeback_plan(
        cand, bkt, w_mask, found, fway, eway, is_b_leader_G, b_end_G
    )

    existing0 = existing  # pre-override: GLOBAL replica serving below
    sk_g = None
    fold_G = None
    if sketch is not None:
        # Live-victim protection: with the cold tier on, a create whose
        # eviction victim is still LIVE goes to the sketch instead of
        # wiping that victim's window — eviction churn (the dominant
        # failure at 100M-key pressure: every tail create used to cost
        # some resident key its state, over-admission on its next
        # touch) becomes a fail-closed sketch decision. Dead/expired
        # victims still recycle their ways exactly as before, and the
        # PROMOTER remains the path by which a genuinely hot key claims
        # a way in a full bucket (its install may evict — heat, not
        # arrival order, decides residency). Exact-only mode
        # (sketch=None) keeps the historical evict-on-create contract.
        v_sel = cand[:, 0]
        for w in range(1, cand.shape[1]):
            v_sel = jnp.where((eway == w)[:, None], cand[:, w], v_sel)
        victim_live = (v_sel[:, L_TAG] != 0) & (
            v_sel[:, L_EXPIRE] >= now
        )
        # sketch-servable gate (r21, core/algorithms.py): ALL FOUR
        # algorithms divert to the count-min tier. Token/leaky ride
        # the r13 fixed-window math; sliding rides the r21 window-ring
        # blend and GCRA its TAT-quantized variant (both below) — the
        # import-time registry pin in core/sketches.py asserts
        # SKETCH_SERVABLE_ALGOS matches this kernel; widen together.
        sk_extra = evicted_G & victim_live
        dropped_G = dropped_G | sk_extra
        evicted_G = evicted_G & ~sk_extra

        # Eviction->sketch migration (r14): the evictions that remain
        # after live-victim protection RECYCLE a dead (lazy-expired)
        # victim's way — and used to drop the victim's consumed count
        # on the floor (the exact tier's historical state-loss
        # contract). When the dead entry's own window still overlaps
        # the victim key's CURRENT fixed window (an entry created in
        # the previous fixed window whose tail crosses the boundary),
        # fold its consumed count into the sketch at (victim key,
        # current window) instead: if the victim returns to a full
        # bucket it is sketch-served AT-LEAST-AS-RESTRICTIVELY as the
        # unevicted oracle rather than with a phantom-fresh budget.
        # The victim's full uint64 key hash reconstructs from
        # L_TAG (high 32 bits) + L_KEYLOW (low 32, written below) —
        # exact except for the fp==0 -> 1 substitution, whose
        # mis-attributed fold only ever INFLATES some estimate
        # (fail-closed). Sticky-over victims fold their whole limit
        # (their refusal state is the thing worth preserving); leaky
        # victims are skipped (no fixed window to fold into).
        v_dur_pos = jnp.maximum(v_sel[:, L_DURATION], 1)
        v_wid = now // v_dur_pos
        v_overlap = v_sel[:, L_EXPIRE] > v_wid * v_dur_pos
        # token victims only: leaky has no fixed window to fold into;
        # a dead SLIDING victim's current subwindow ended >= d before
        # now's epoch window began (expire = ws + 2d < now implies
        # ws + d <= now's window start), so its counts are entirely
        # pre-ring and nothing is foldable; a dead GCRA victim
        # (TAT < now) is by definition fully drained. r21 keeps the
        # fold token-only — it loses nothing for the other three.
        v_token = (v_sel[:, L_FLAGS] & FLAG_ALGO_MASK) == 0
        v_sticky = (v_sel[:, L_FLAGS] & FLAG_STICKY_OVER) != 0
        v_consumed = jnp.clip(
            jnp.where(
                v_sticky,
                v_sel[:, L_LIMIT],
                v_sel[:, L_LIMIT] - v_sel[:, L_REMAINING],
            ),
            0,
            None,
        )
        fold_G = evicted_G & v_overlap & v_token & (v_consumed > 0)
        v_kh = (
            lax.bitcast_convert_type(v_sel[:, L_TAG], jnp.uint32).astype(
                jnp.uint64
            )
            << jnp.uint64(32)
        ) | lax.bitcast_convert_type(
            v_sel[:, L_KEYLOW], jnp.uint32
        ).astype(jnp.uint64)
        v_est, v_idx = _sketch_lookup(sketch, v_kh, v_wid)
        v_upd = jnp.where(
            fold_G, v_est + v_consumed.astype(jnp.int64), jnp.int64(0)
        )
        writer_G = writer_G & ~sk_extra

        # Sketch-served groups = valid creates the exact tier refused
        # (way exhaustion, or a live victim under protection).
        # Token/leaky decide with r13 FIXED-WINDOW token math over the
        # window-keyed count-min estimate: budget at batch start =
        # max(limit - estimate, 0), reset = the window's end, no
        # sticky state (leaky's fixed-window ride is the documented
        # tail-only divergence — the sketch has no per-key timestamp
        # to leak from). r21 lifts sliding and GCRA in via the
        # WINDOW-RING: the same window-keyed indexing IS a logical
        # ring of per-epoch-window sub-sketches (rotation = the window
        # id advancing), so the previous window's estimate is one more
        # lookup at wid-1. Sliding blends cur + tail-weighted prev
        # (always >= the true sliding count); GCRA floors the unknown
        # TAT at the latest value any admissible pre-ring history
        # could have left (last pre-ring charge ended before the prev
        # window: TAT <= ws - d + tau + T), then advances it T per
        # counted charge. Estimates only over-count (conservative
        # update + hash collisions + the one-batch fold lag), so every
        # branch refuses at-or-before its exact oracle: fail-closed.
        # Host twins (test-pinned): algorithms.sketch_sliding_budget /
        # algorithms.sketch_gcra_budget.
        sk_g = dropped_G
        sk_tok = sk_g & (eff_algo <= 1)
        sk_sld = sk_g & (eff_algo == 2)
        sk_gcra = sk_g & (eff_algo == 3)
        dur_pos = jnp.maximum(g_durQ, 1)
        wid = now // dur_pos  # int32: engine now >= 0
        window_end = (wid + 1) * dur_pos  # <= now + dur <= INT32_MAX
        sk_est, sk_idx = _sketch_lookup(sketch, kh_G, wid)
        sk_prev, _ = _sketch_lookup(sketch, kh_G, wid - 1)
        est32 = jnp.minimum(sk_est, jnp.int64(_I32_MAX)).astype(
            jnp.int32
        )
        # clamp estimates into [0, max(limit, 0)] before subtractions
        # so budgets stay in int32 for any limit. Clamping is
        # one-sided-safe in every branch: a key's own counted charges
        # per epoch window never exceed its limit (admission stops at
        # the limit), so min(est, limit) >= the key's true count.
        lim_pos = jnp.maximum(g_limQ, 0)
        est_c = jnp.minimum(est32, lim_pos)
        lim64 = lim_pos.astype(jnp.int64)
        cur_c = jnp.minimum(sk_est, lim64)
        prev_c = jnp.minimum(sk_prev, lim64)
        # sliding window-ring blend: the previous epoch window's count
        # weighted by the fraction of it still inside the sliding
        # window — int64, the count*ms product overflows int32
        d64 = dur_pos.astype(jnp.int64)
        wend64 = window_end.astype(jnp.int64)
        sld_used_sk = cur_c + (prev_c * (wend64 - now64)) // d64
        R0_sk_sld = jnp.clip(
            g_limQ.astype(jnp.int64) - sld_used_sk, 0, lim64
        ).astype(jnp.int32)
        # GCRA TAT-quantized reconstruction. gcra_T/gcra_tau above
        # were derived from the REQUEST params (dropped creates are
        # non-existing), matching gcra_params in the host twin.
        ws64 = wend64 - d64  # current epoch window start
        tatq = jnp.maximum(ws64 - d64 + gcra_tau + gcra_T, now64) + (
            cur_c + prev_c
        ) * gcra_T
        R0_sk_gcra = jnp.clip(
            (now64 + gcra_tau - tatq) // gcra_T, 0, lim64
        ).astype(jnp.int32)
        # sketch groups ride the "existing window" machinery: no
        # creation-leader special case, uniform cumulative charging.
        # Token/leaky collapse to algo 0 (the r13 contract);
        # sliding/GCRA KEEP their algo so their rows take the sg
        # response path below with the ring budget as R0.
        existing = existing | sk_g
        eff_leaky = eff_leaky & ~sk_g
        eff_algo = jnp.where(sk_tok, 0, eff_algo)
        R0 = jnp.where(sk_g, jnp.maximum(g_limQ - est_c, 0), R0)
        R0 = jnp.where(sk_sld, R0_sk_sld, R0)
        R0 = jnp.where(sk_gcra, R0_sk_gcra, R0)
        sticky0 = sticky0 & ~sk_g
        g_exp = jnp.where(sk_g, window_end, g_exp)  # token reset
        # sliding reset: the EPOCH window's end, not now + dur — the
        # read grid must be the grid the charges land on; GCRA reset:
        # the quantized TAT, saturated into the int32 bridge lane
        # (the [G]-level budget above used the unclamped value, so
        # saturation only affects the reported reset)
        sld_reset_G = jnp.where(sk_sld, window_end, sld_reset_G)
        gcra_tat0 = jnp.where(
            sk_gcra, jnp.minimum(tatq, jnp.int64(_I32_MAX)), gcra_tat0
        )
        g_limS = jnp.where(sk_g, g_limQ, g_limS)  # params echo the
        g_durS = jnp.where(sk_g, g_durQ, g_durS)  # request's

    # ---- bridge: group values needed per request, one stacked gather ------
    with jax.named_scope("segment_scan_decide"):
        bridge = jnp.take(
            jnp.stack(
                [
                    existing.astype(jnp.int32),
                    eff_leaky.astype(jnp.int32),
                    R0,
                    sticky0.astype(jnp.int32),
                    rate,
                    g_exp,
                    g_rem,
                    g_limS,
                    g_durS,
                    g_limQ,
                    g_durQ,
                    over_c.astype(jnp.int32),
                    leaky_zero.astype(jnp.int32),
                    # existing0, not existing: a sketch-served group is NOT
                    # a token replica — its gnp rows process as owned, the
                    # same contract as an exact-tier miss
                    (existing0 & (stored_algo == 0)).astype(jnp.int32),
                    charged_ldr.astype(jnp.int32),
                    g_hits,
                    eff_algo,
                    sld_reset_G,
                    gcra_T.astype(jnp.int32),  # T <= duration: fits int32
                    gcra_tau.astype(jnp.int32),  # clamped to I32_MAX above
                    gcra_tat0.astype(jnp.int32),  # <= I32_MAX by envelope
                ],
                axis=-1,
            ),
            groups.group_id,
            axis=0,
            indices_are_sorted=True,
        )
        existing_r = bridge[:, 0] != 0
        eff_leaky_r = bridge[:, 1] != 0
        R0_r = bridge[:, 2]
        sticky0_r = bridge[:, 3] != 0
        rate_r = bridge[:, 4]
        g_exp_r = bridge[:, 5]
        g_rem_r = bridge[:, 6]
        g_limS_r = bridge[:, 7]
        g_durS_r = bridge[:, 8]
        g_limQ_r = bridge[:, 9]
        g_durQ_r = bridge[:, 10]
        over_c_r = bridge[:, 11] != 0
        leaky_zero_r = bridge[:, 12] != 0
        tok_replica_r = bridge[:, 13] != 0  # existing & stored token
        charged_ldr_r = bridge[:, 14] != 0
        g_hits_r = bridge[:, 15]
        eff_algo_r = bridge[:, 16]
        eff_sld_r = eff_algo_r == 2
        eff_gcra_r = eff_algo_r == 3
        sld_reset_r = bridge[:, 17]
        gcra_T_r = bridge[:, 18].astype(jnp.int64)
        gcra_tau_r = bridge[:, 19].astype(jnp.int64)
        gcra_tat0_r = bridge[:, 20].astype(jnp.int64)

        # GLOBAL non-owner replica read: answer straight from the live entry,
        # no mutation (reference gubernator.go:178-187). On a miss the request
        # is processed as if owned (gubernator.go:189-194).
        gnp_served = gnp & tok_replica_r

        is_creation_leader = is_leader & ~existing_r

        # ---- cumulative-attempt prefix within groups ----
        viable = valid & ~gnp_served & ~leaky_zero_r
        eligible = viable & (h > 0) & (h <= R0_r)
        inc = jnp.where(eligible & ~is_creation_leader, h, 0)
        incl1 = _seg_scan(
            is_leader,
            jnp.stack([inc, (viable & (h != 0)).astype(jnp.int32)], axis=-1),
        )
        prefix1 = jnp.where(same_prev[:, None], _shift1(incl1, 0), 0)
        S = prefix1[:, 0]

        # admission: S + h <= R0, written subtraction-side to stay in int32
        # (eligible already guarantees h <= R0)
        charged = eligible & ~is_creation_leader & (S <= R0_r - h)
        charged = charged | (is_creation_leader & charged_ldr_r)
        # Attempt-inflated budget: used ONLY for the decr predicate below.
        # For CHARGED positions S == the charged-only prefix (once an
        # equal-or-smaller attempt is refused every later one is too), so
        # decr is unaffected by the inflation; REPORTED remaining must use
        # the charged-only prefix instead (rem_vis) or refused duplicates
        # would see phantom consumption (sequential-greedy reports the true
        # leftover to refused requests).
        rem_b = jnp.maximum(R0_r - S, 0)

        # Real (charged-only) depletion prefix: refused duplicates inflate S but
        # consume nothing, so persistence decisions must not use S.
        inc_chg = jnp.where(charged & ~is_creation_leader, h, 0)
        # sticky status observed by j: a request that arrives when remaining is
        # actually 0 flips the cached token status to OVER_LIMIT persistently
        # (algorithms.go:41-44); leaky expiry refreshes only on a strict-
        # decrement charge (oracle divergence-1 rule; algorithms.go:157)
        decr = charged & ~is_creation_leader & (rem_b - h > 0)
        incl2 = _seg_scan(
            is_leader, jnp.stack([inc_chg, decr.astype(jnp.int32)], axis=-1)
        )
        prefix2 = jnp.where(same_prev[:, None], _shift1(incl2, 0), 0)
        S_chg = prefix2[:, 0]
        rem_vis = jnp.maximum(R0_r - S_chg, 0)  # true budget visible to j

        # token-only sticky flip: sliding/GCRA statuses are recomputed from
        # state every call, like leaky (r15)
        z = (
            viable & (eff_algo_r == 0) & (R0_r - S_chg == 0)
            & ~is_creation_leader
        )
        c3 = jnp.cumsum(z.astype(jnp.int32))
        sticky_live = sticky0_r | (same_prev & _shift1(z, False))

        # ONE fused gather at the group end positions pulls every group
        # total the writeback needs (narrow device gathers carry a large
        # fixed cost; batching columns is nearly free)
        ends = jnp.take(
            jnp.concatenate([incl1, incl2, c3[:, None]], axis=1),
            end_pos_G,
            axis=0,
            indices_are_sorted=True,
        )  # [G, 5]
        any_hits = ends[:, 1] > 0  # [G]
        total_charged = ends[:, 2]  # [G]
        any_decr = ends[:, 3] > 0  # [G]
        z_lead = jnp.take(
            jnp.stack([c3, z.astype(jnp.int32)], axis=-1),
            lead_clip,
            axis=0,
            indices_are_sorted=True,
        )  # [G, 2]
        any_z = (ends[:, 4] - (z_lead[:, 0] - z_lead[:, 1])) > 0  # [G]

    # ---- sketch conservative update at [G] --------------------------------
    with jax.named_scope("sketch_update"):
        new_sketch = sketch
        if sketch is not None:
            # write max(counter, estimate + charged) into each row: only
            # the counters that DEFINE the estimate grow (Count-Less-family
            # discipline), so cross-key collision inflation is never
            # compounded. Non-sketch and padding groups write 0, a no-op
            # against non-negative counters. One narrow scatter-max per row.
            # Writes saturate at the counter dtype's max (v2 int32): a
            # key's OWN update chain never saturates — charged <= budget
            # <= limit - min(est, limit), so est + charged <= limit <=
            # I32_MAX whenever charged > 0 — and a fold that saturates
            # pins the counter at max, which only ever REFUSES (fail-
            # closed, never an under-count of a served key).
            upd = jnp.where(
                sk_g, sk_est + total_charged.astype(jnp.int64), jnp.int64(0)
            )
            data_sk = sketch.data
            cmax = jnp.int64(jnp.iinfo(data_sk.dtype).max)
            upd_w = jnp.minimum(upd, cmax).astype(data_sk.dtype)
            for r in range(len(sk_idx)):
                data_sk = data_sk.at[r, sk_idx[r]].max(upd_w)
            # eviction->sketch migration (computed above with the victim
            # plan): fold recycled dead victims' consumed counts into
            # their keys' current windows — scatter-max like the request
            # update, so ordering between the two is immaterial. A key
            # both folded and sketch-decided in this same batch reads its
            # estimate from before the fold (one-batch lag, conservative
            # thereafter).
            v_upd_w = jnp.minimum(v_upd, cmax).astype(data_sk.dtype)
            for r in range(len(v_idx)):
                data_sk = data_sk.at[r, v_idx[r]].max(v_upd_w)
            new_sketch = Sketch(data=data_sk)

    # ---- responses --------------------------------------------------------
    with jax.named_scope("responses"):
        st_cached = jnp.where(sticky_live, OVER, UNDER)

        # token, existing-style position (incl. followers of a creation)
        tok_status = jnp.where(
            rem_vis == 0,
            OVER,
            jnp.where(charged | (h == 0), st_cached, OVER),
        )
        tok_remaining = jnp.where(
            rem_vis == 0, 0, jnp.where(charged, rem_vis - h, rem_vis)
        )
        g_expire_new_r = jnp.where(existing_r, g_exp_r, now + g_durQ_r)
        tok_reset = g_expire_new_r

        # leaky, existing-style position: status is computed fresh each call and
        # reset_time only appears on OVER paths (algorithms.go:123-160)
        lk_over = (rem_vis == 0) | (~charged & (h != 0))
        lk_status = jnp.where(lk_over, OVER, UNDER)
        lk_remaining = jnp.where(
            rem_vis == 0, 0, jnp.where(charged, rem_vis - h, rem_vis)
        )
        lk_reset = jnp.where(lk_over, now + rate_r, 0)

        g_lim_resp = jnp.where(existing_r, g_limS_r, g_limQ_r)
        status = jnp.where(eff_leaky_r, lk_status, tok_status)
        remaining = jnp.where(eff_leaky_r, lk_remaining, tok_remaining)
        reset = jnp.where(eff_leaky_r, lk_reset, tok_reset)

        # sliding / GCRA, existing-style position (r15): no persisted
        # status — OVER iff the visible budget is gone or this hit-carrying
        # request was refused (the leaky status shape, minus its quirks)
        sg = eff_sld_r | eff_gcra_r
        sg_over = (rem_vis == 0) | (~charged & (h != 0))
        sg_status = jnp.where(sg_over, OVER, UNDER)
        sg_remaining = jnp.where(
            rem_vis == 0, 0, jnp.where(charged, rem_vis - h, rem_vis)
        )
        # GCRA per-row reset: the row's own theoretical arrival time after
        # every charge earlier in its group (S_eff adds a creation leader's
        # charge for follower rows) plus its own n*T; a refused hit-
        # carrying row instead reports the earliest instant the same
        # request could succeed (TAT + n*T - tau). Matches sequential
        # application of core/oracle.gcra by construction.
        S_eff = S_chg + jnp.where(
            ~existing_r & charged_ldr_r & ~is_creation_leader, g_hits_r, 0
        )
        tat_row = gcra_tat0_r + S_eff.astype(jnp.int64) * gcra_T_r
        g_reset64 = (
            tat_row
            + h.astype(jnp.int64) * gcra_T_r
            - jnp.where(sg_over & (h != 0), gcra_tau_r, 0)
        )
        gcra_reset_r = jnp.clip(g_reset64, _I32_MIN, _I32_MAX).astype(
            jnp.int32
        )
        status = jnp.where(sg, sg_status, status)
        remaining = jnp.where(sg, sg_remaining, remaining)
        reset = jnp.where(eff_sld_r, sld_reset_r, reset)
        reset = jnp.where(eff_gcra_r, gcra_reset_r, reset)

        # creation leader overrides (the branchy creation responses)
        cl_status = jnp.where(over_c_r, OVER, UNDER)
        cl_remaining = jnp.where(
            over_c_r, jnp.where(eff_leaky_r, 0, g_limQ_r), g_limQ_r - g_hits_r
        )
        cl_reset = jnp.where(eff_leaky_r, 0, now + g_durQ_r)
        # GCRA creation: reset is the fresh TAT after the leader's own
        # charge (now + n*T); sliding keeps the token-shaped window end
        gcra_cl = jnp.clip(
            gcra_tat0_r
            + jnp.where(charged_ldr_r, g_hits_r, 0).astype(jnp.int64)
            * gcra_T_r,
            _I32_MIN,
            _I32_MAX,
        ).astype(jnp.int32)
        cl_reset = jnp.where(eff_gcra_r, gcra_cl, cl_reset)
        status = jnp.where(is_creation_leader, cl_status, status)
        remaining = jnp.where(is_creation_leader, cl_remaining, remaining)
        reset = jnp.where(is_creation_leader, cl_reset, reset)

        # GLOBAL replica reads return the stored status verbatim
        status = jnp.where(
            gnp_served, jnp.where(sticky0_r, OVER, UNDER), status
        )
        remaining = jnp.where(gnp_served, g_rem_r, remaining)
        reset = jnp.where(gnp_served, g_exp_r, reset)

        # leaky zero-limit guard (documented divergence)
        status = jnp.where(leaky_zero_r, OVER, status)
        remaining = jnp.where(leaky_zero_r, 0, remaining)
        reset = jnp.where(leaky_zero_r, now + g_durS_r, reset)
        resp_limit = jnp.where(leaky_zero_r, lim_q, g_lim_resp)

    # ---- quota-chain no-partial-debit (r15) -------------------------------
    # With chain coupling, a chain ANY of whose member rows reports
    # OVER_LIMIT has every member's charge rolled back before the
    # writeback: recompute the group aggregates the writeback consumes
    # with refused-chain rows masked out (one extra scan pair, traced
    # only into the chain program — the plain program's aggregates are
    # untouched). Responses stay the per-level optimistic verdicts;
    # the serving tier collapses them most-restrictive-wins.
    if chain_id is not None:
        row_over = (status == OVER) & valid
        over_i = row_over.astype(jnp.int32)
        bad_cnt = jnp.zeros((B,), jnp.int32).at[chain_id].add(over_i)
        # A row is rolled back iff ANOTHER member of its chain refused.
        # The refusing level's own refusal is its own decision: its
        # bookkeeping (token sticky flip at exhaustion, leaky touch)
        # keeps plain-kernel semantics — which is exactly what makes
        # the all-singleton program byte-identical to decide_presorted
        # (a refused row never charged, so quota rollback is moot for
        # it; masking it anyway was dropping the plain path's sticky
        # and timestamp writebacks).
        m_ok = (jnp.take(bad_cnt, chain_id) - over_i) == 0
        inc_chg_w = jnp.where(charged & ~is_creation_leader & m_ok, h, 0)
        incl_w = _seg_scan(
            is_leader,
            jnp.stack(
                [
                    inc_chg_w,
                    (decr & m_ok).astype(jnp.int32),
                    (viable & (h != 0) & m_ok).astype(jnp.int32),
                ],
                axis=-1,
            ),
        )
        z_w = z & m_ok
        c3_w = jnp.cumsum(z_w.astype(jnp.int32))
        ends_w = jnp.take(
            jnp.concatenate([incl_w, c3_w[:, None]], axis=1),
            end_pos_G,
            axis=0,
            indices_are_sorted=True,
        )
        total_charged_w = ends_w[:, 0]
        any_decr_w = ends_w[:, 1] > 0
        any_hits_w = ends_w[:, 2] > 0
        z_lead_w = jnp.take(
            jnp.stack([c3_w, z_w.astype(jnp.int32)], axis=-1),
            lead_clip,
            axis=0,
            indices_are_sorted=True,
        )
        any_z_w = (ends_w[:, 3] - (z_lead_w[:, 0] - z_lead_w[:, 1])) > 0
        ldr_ok_G = jnp.take(
            m_ok, lead_clip, axis=0, indices_are_sorted=True
        )
        ldr_chg_w = jnp.where(
            ~existing & charged_ldr & ldr_ok_G, g_hits, 0
        )
    else:
        total_charged_w = total_charged
        any_decr_w = any_decr
        any_hits_w = any_hits
        any_z_w = any_z
        ldr_chg_w = jnp.where(~existing & charged_ldr, g_hits, 0)

    # ---- state writeback at [G]: merged whole-bucket-row scatter ----------
    with jax.named_scope("writeback_apply"):
        # chg_all: every hit actually charged to the group this batch,
        # INCLUDING a creation leader's (the historical rem_final folded
        # the leader's charge into R0_create; chains need it explicit so a
        # rolled-back leader restores the full budget). Without chains the
        # arithmetic is identical to the pre-r15 R0 - total_charged.
        chg_all = total_charged_w + ldr_chg_w
        R0C = R0 + jnp.where(~existing & charged_ldr, g_hits, 0)
        rem_final = R0C - chg_all

        sticky_final = sticky0 | any_z_w

        w_leaky = eff_leaky
        g_expire_new = jnp.where(existing, g_exp, now + g_durQ)
        new_expire = jnp.where(
            w_leaky,
            jnp.where(
                existing,
                jnp.where(any_decr_w, now + g_durS, g_exp),
                now + g_durQ,
            ),
            g_expire_new,
        )
        # sliding (r15): the rotated subwindow pair persists — expire pins
        # the current window start (ws + 2d), L_REMAINING the current
        # count, L_TS the previous count (store.rebase skips it there)
        d_eff64 = jnp.where(
            existing,
            d_sld,
            jnp.clip(g_durQ.astype(jnp.int64), 1, _SLD_DMAX),
        )
        ws_eff64 = jnp.where(existing, sld_ws, now64)
        sld_exp_new = jnp.clip(
            ws_eff64 + 2 * d_eff64, _I32_MIN, _I32_MAX
        ).astype(jnp.int32)
        new_expire = jnp.where(eff_sld, sld_exp_new, new_expire)
        # GCRA (r15): the stored entry IS one theoretical arrival time —
        # TAT' = max(TAT, now) + charged * T, int64 math clamped into the
        # int32 expiry lane; TAT < now on a later batch lazy-expires the
        # entry, which is exactly "fully drained == fresh"
        gcra_tat_new = jnp.clip(
            gcra_tat0 + chg_all.astype(jnp.int64) * gcra_T,
            _I32_MIN,
            _I32_MAX,
        ).astype(jnp.int32)
        new_expire = jnp.where(eff_gcra, gcra_tat_new, new_expire)

        new_rem = jnp.where(
            eff_sld,
            jnp.where(existing, sld_cur0, 0) + chg_all,
            rem_final,
        )
        new_ts = jnp.where(existing & w_leaky & ~any_hits_w, g_ts, now)
        new_ts = jnp.where(
            eff_sld, jnp.where(existing, sld_prev0, 0), new_ts
        )
        new_limit = jnp.where(existing, g_limS, g_limQ)
        new_duration = jnp.where(existing, g_durS, g_durQ)
        new_flags = (
            jnp.where(w_leaky, FLAG_ALGO_LEAKY, 0)
            | jnp.where(eff_sld, FLAG_ALGO_SLIDING, 0)
            | jnp.where(eff_gcra, FLAG_ALGO_GCRA, 0)
            | jnp.where(
                (eff_algo == 0) & sticky_final, FLAG_STICKY_OVER, 0
            )
        ).astype(jnp.int32)

        # Groups served entirely from a replica write back identical values
        # (harmless); invalid (padding / non-owned), zero-guard, and
        # sketch-served groups skip the write (w_mask / the plan's dropped
        # mask, computed above before the sketch overrides).
        new_vals = jnp.stack(
            [
                fp,
                new_expire,
                new_rem,
                new_ts,
                new_limit,
                new_duration,
                new_flags,
                # L_KEYLOW: the key hash's low 32 bits — with the tag this
                # makes the entry's full hash reconstructable on device
                # (eviction->sketch migration above). Written in BOTH
                # modes so sketch on/off store bytes stay identical.
                lax.bitcast_convert_type(
                    (kh_G & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
                    jnp.int32,
                ),
            ],
            axis=-1,
        )  # [G, LANES]

        # Delta-add writeback, phase 2 of the plan computed above: each
        # writing group adds (new - old) into its way's lanes; disjoint
        # ways compose exactly and the store keeps its canonical shape
        # (see _writeback_delta_add).
        new_data = _writeback_apply(
            store.data, bkt, writer_G, way_G, new_vals, cand
        )

    resp = BatchResponse(
        status=status, limit=resp_limit, remaining=remaining, reset_time=reset
    )
    stats = BatchStats(
        hits=jnp.sum(
            jnp.where(groups.valid & g_live, 1, 0)
        ).astype(jnp.int32),
        misses=jnp.sum(
            jnp.where(groups.valid & ~g_live, 1, 0)
        ).astype(jnp.int32),
        # with the sketch tier on, `dropped` doubles as the
        # sketch-served group count: every dropped create IS a
        # sketch-tier decision (fail-closed), not silent over-admission
        dropped=jnp.sum(dropped_G).astype(jnp.int32),
        evictions=jnp.sum(evicted_G).astype(jnp.int32),
    )
    return Store(data=new_data), new_sketch, resp, stats


def decide(
    store: Store, req: BatchRequest, now: jax.Array
) -> Tuple[Store, BatchResponse, BatchStats]:
    """Evaluate one padded batch in ARBITRARY row order: sorts on device,
    runs decide_presorted, and unsorts the responses. Convenience wrapper
    for tests and callers without a host-side presort; the serving engine
    uses the presorted path directly (engine.pad_request_sorted)."""
    buckets, _W = store.data.shape
    B = req.key_hash.shape[0]

    sort_key = group_sort_key(req.key_hash, req.valid, buckets)
    order = jnp.argsort(sort_key, stable=True)
    kh_s = req.key_hash[order]
    req_stack = jnp.stack(
        [
            req.hits,
            req.limit,
            req.duration,
            req.algo,
            req.gnp.astype(jnp.int32),
            req.valid.astype(jnp.int32),
        ],
        axis=-1,
    )[order]
    valid_s = req_stack[:, 5] != 0
    # invalid rows sorted to the tail carry arbitrary keys; repeat the
    # last valid row's key so the bucket stream stays monotonic (the
    # presorted caller contract). All-invalid batches degrade to one
    # arbitrary-key group that never writes.
    n_valid = jnp.sum(valid_s.astype(jnp.int32))
    last_kh = kh_s[jnp.maximum(n_valid - 1, 0)]
    kh_s = jnp.where(valid_s, kh_s, last_kh)

    sorted_req = BatchRequest(
        key_hash=kh_s,
        hits=req_stack[:, 0],
        limit=req_stack[:, 1],
        duration=req_stack[:, 2],
        algo=req_stack[:, 3],
        gnp=req_stack[:, 4] != 0,
        valid=valid_s,
    )
    new_store, resp_s, stats = decide_presorted(store, sorted_req, now)

    resp_stack = jnp.stack(
        [resp_s.status, resp_s.limit, resp_s.remaining, resp_s.reset_time],
        axis=-1,
    )
    unsorted = jnp.zeros_like(resp_stack).at[order].set(
        resp_stack, unique_indices=True
    )
    resp = BatchResponse(
        status=unsorted[:, 0],
        limit=unsorted[:, 1],
        remaining=unsorted[:, 2],
        reset_time=unsorted[:, 3],
    )
    return new_store, resp, stats


def upsert_globals(
    store: Store,
    key_hash: jax.Array,  # uint64[B]
    limit: jax.Array,  # int32[B]
    remaining: jax.Array,  # int32[B]
    reset_time: jax.Array,  # int32[B] engine-ms
    is_over: jax.Array,  # bool[B]
    valid: jax.Array,  # bool[B]
    duration: Optional[jax.Array] = None,  # int32[B] stored duration ms
    ts: Optional[jax.Array] = None,  # int32[B] raw L_TS lane
    flags: Optional[jax.Array] = None,  # int32[B] full L_FLAGS word
) -> Store:
    """Install owner-broadcast GLOBAL statuses as local replica entries —
    the receive side of UpdatePeerGlobals (reference gubernator.go:199-207,
    cache.Add of a token-typed status with expiry = reset_time). Sorts by
    bucket so the same merged-bucket-row writeback as decide() applies
    (later-in-batch wins for duplicate keys, matching the reference's
    sequential cache.Add order).

    The optional lanes (r19 checkpoint/restore): `duration`/`ts`/`flags`
    carry the raw L_DURATION/L_TS/L_FLAGS words so exported entries of
    ANY algorithm (token, leaky, sliding, GCRA — with their sticky and
    algo flag bits) reinstall byte-exact; omitted (the GLOBAL-broadcast
    path) they keep the historical token-replica encoding: zero
    duration/ts and a flags word derived from `is_over` alone."""
    buckets, _W = store.data.shape
    ways = _W // LANES
    B = key_hash.shape[0]
    ar = jnp.arange(B, dtype=jnp.int32)

    sort_key = group_sort_key(key_hash, valid, buckets)
    order = jnp.argsort(sort_key, stable=True)
    skey = sort_key[order]
    bkt, fp = decode_sort_key(skey, buckets)
    valid_s = valid[order]
    stack = jnp.stack(
        [
            limit,
            remaining,
            reset_time,
            is_over.astype(jnp.int32),
        ],
        axis=-1,
    )[order]

    cand = jnp.take(
        store.data, bkt, axis=0, indices_are_sorted=True
    ).reshape(B, ways, LANES)

    match = (cand[:, :, L_TAG] == fp[:, None]) & valid_s[:, None]
    found = match.any(axis=1)
    fway = jnp.argmax(match, axis=1).astype(jnp.int32)

    evict_key = jnp.where(
        cand[:, :, L_TAG] == 0, _I32_MIN, cand[:, :, L_EXPIRE]
    )
    eway = jnp.argmin(evict_key, axis=1).astype(jnp.int32)

    zero = jnp.zeros_like(bkt)
    if flags is None:
        flags_s = jnp.where(stack[:, 3] != 0, FLAG_STICKY_OVER, 0).astype(
            jnp.int32
        )
    else:
        flags_s = flags.astype(jnp.int32)[order]
    dur_s = zero if duration is None else duration.astype(jnp.int32)[order]
    ts_s = zero if ts is None else ts.astype(jnp.int32)[order]
    # L_KEYLOW from the sorted key hashes (skey carries only bucket|fp,
    # not the low bits): replica/promoter installs stay reconstructable
    # for the eviction->sketch fold like decide-written entries
    klow = lax.bitcast_convert_type(
        (key_hash[order] & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
        jnp.int32,
    )
    new_vals = jnp.stack(
        [fp, stack[:, 2], stack[:, 1], ts_s, stack[:, 0], dur_s, flags_s,
         klow],
        axis=-1,
    )

    # duplicate keys in one broadcast batch: LAST in batch order wins,
    # matching the reference's sequential cache.Add (gubernator.go:199-207)
    # — the writer for each (bucket,fp) group is its final member.
    is_last = jnp.concatenate([skey[:-1] != skey[1:], jnp.array([True])])
    writer = valid_s & is_last

    b_same_prev = jnp.concatenate(
        [jnp.array([False]), bkt[1:] == bkt[:-1]]
    )
    is_b_leader = ~b_same_prev
    b_end = _segment_ends(is_b_leader, ar)
    # drop/eviction counts are discarded on this path: replica installs
    # shed REPLICA state (re-creatable from the next broadcast), not the
    # owner-side admission state the over-admission alarm watches
    new_data, _n_dropped, _n_evicted = _writeback_delta_add(
        store.data,
        bkt,
        writer,
        found,
        fway,
        eway,
        new_vals,
        cand,
        is_b_leader,
        b_end,
    )
    return Store(data=new_data)


# scalar tail of the packed transfer: hits, misses, dropped, evictions
PACKED_STATS = 4


@jax.named_scope("pack")
def pack_outputs(resp: BatchResponse, stats: BatchStats) -> jax.Array:
    """Responses + stats as ONE int32[4*B+PACKED_STATS] array: every
    device->host transfer has a fixed cost, so hosts fetch a single
    array and split with unpack_outputs — one PCIe fetch per batch
    instead of six."""
    return jnp.concatenate(
        [
            resp.status,
            resp.limit,
            resp.remaining,
            resp.reset_time,
            jnp.stack(
                [stats.hits, stats.misses, stats.dropped, stats.evictions]
            ),
        ]
    )


def unpack_outputs(packed, B: int):
    """(status, limit, remaining, reset_time, hits, misses, dropped,
    evictions) from a pack_outputs array (host-side numpy or device
    array)."""
    return (
        packed[0:B],
        packed[B : 2 * B],
        packed[2 * B : 3 * B],
        packed[3 * B : 4 * B],
        packed[4 * B],
        packed[4 * B + 1],
        packed[4 * B + 2],
        packed[4 * B + 3],
    )


# The way in, packed like the way out: a batch's host inputs cross as
# ONE int32 array, because every host->device transfer has a fixed cost
# too and a decide call used to make thirteen. Layout in int32 words,
# with B request rows and G group slots (the program is specialised on
# both already); on a mesh the same row per shard, [n_shards, W]:
#   B-word segments: key_hash low, key_hash high, hits, limit, duration,
#                    algo, gnp, valid, group_id
#   G-word segments: group key_hash low, high, leader_pos, end_pos, valid
#   1 word:          now (engine-ms)
#   then whatever [.., B] int32 columns the caller appends (the chain
#   program's chain_id).
_REQ_SEGMENTS = 9
_GROUP_SEGMENTS = 5


def packed_inputs_width(B: int, G: int) -> int:
    return _REQ_SEGMENTS * B + _GROUP_SEGMENTS * G + 1


def pack_inputs(
    req: BatchRequest, groups: BatchGroups, e_now, *extra
) -> np.ndarray:
    """(req, groups, now) as ONE fresh int32[..., W] host array (numpy
    in, numpy out; any leading shard axes are kept). 64-bit hashes go
    as their two little-endian 32-bit words, split here so the device
    reads two contiguous segments; bools go as 0/1 words. The buffer is
    new every batch: it stays untouched while its transfer is in
    flight, whatever the caller does with `req` next."""
    B = req.key_hash.shape[-1]
    G = groups.key_hash.shape[-1]
    kh = req.key_hash.view(np.int32)  # (low, high) word pairs
    gk = groups.key_hash.view(np.int32)
    buf = np.empty(
        req.key_hash.shape[:-1]
        + (packed_inputs_width(B, G) + B * len(extra),),
        np.int32,
    )
    o = 0
    for n, columns in (
        (B, (kh[..., 0::2], kh[..., 1::2], req.hits, req.limit,
             req.duration, req.algo, req.gnp, req.valid, groups.group_id)),
        (G, (gk[..., 0::2], gk[..., 1::2], groups.leader_pos,
             groups.end_pos, groups.valid)),
        (1, (e_now,)),
        (B, extra),
    ):
        for c in columns:
            buf[..., o : o + n] = c
            o += n
    return buf


def _join_u64(lo: jax.Array, hi: jax.Array) -> jax.Array:
    # shifts and ORs, not a 64-bit bitcast: the TPU has no 64-bit lanes
    # and its compiler expands a u32[n,2]->u64[n] bitcast-convert into
    # a loop, while this form is the (low, high) pair it keeps anyway
    lo = lax.bitcast_convert_type(lo, jnp.uint32).astype(jnp.uint64)
    hi = lax.bitcast_convert_type(hi, jnp.uint32).astype(jnp.uint64)
    return (hi << jnp.uint64(32)) | lo


def unpack_inputs(packed: jax.Array, B: int, G: int):
    """(BatchRequest, BatchGroups, now) from a pack_inputs array, inside
    the jitted program: static slices, bit for bit what the host held.
    Appended columns are `packed[..., packed_inputs_width(B, G):]`."""
    def seg(i, n=B, base=0):
        return packed[..., base + i * n : base + (i + 1) * n]

    g0 = _REQ_SEGMENTS * B
    req = BatchRequest(
        key_hash=_join_u64(seg(0), seg(1)),
        hits=seg(2),
        limit=seg(3),
        duration=seg(4),
        algo=seg(5),
        gnp=seg(6) != 0,
        valid=seg(7) != 0,
    )
    groups = BatchGroups(
        key_hash=_join_u64(seg(0, G, g0), seg(1, G, g0)),
        leader_pos=seg(2, G, g0),
        end_pos=seg(3, G, g0),
        valid=seg(4, G, g0) != 0,
        group_id=seg(8),
    )
    return req, groups, packed[..., g0 + _GROUP_SEGMENTS * G]


@functools.partial(jax.jit, donate_argnums=(0,))
def upsert_globals_jit(store, key_hash, limit, remaining, reset_time, is_over, valid):
    return upsert_globals(store, key_hash, limit, remaining, reset_time, is_over, valid)


@functools.partial(jax.jit, donate_argnums=(0,))
def upsert_windows_jit(
    store, key_hash, limit, remaining, reset_time, duration, ts, flags, valid
):
    """Full-lane window install (r19 checkpoint/restore + re-partition):
    like upsert_globals_jit but carrying the raw L_DURATION/L_TS/L_FLAGS
    words, so exported entries of any algorithm reinstall byte-exact."""
    return upsert_globals(
        store, key_hash, limit, remaining, reset_time,
        (flags & FLAG_STICKY_OVER) != 0, valid,
        duration=duration, ts=ts, flags=flags,
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def rebase_jit(store, delta):
    return rebase(store, delta)
