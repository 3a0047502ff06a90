"""Single-device engine: host glue around the decide kernel.

Converts request objects to dense device arrays (strings are hashed
host-side — no strings ever reach the TPU), pads batches to a small set of
fixed bucket sizes so XLA compiles a handful of programs once, runs the
jitted kernel with the store donated (in-place HBM update, no copies), and
converts decisions back.

The public API speaks int64 unix-ms and int64 counters (the reference's
wire types); this layer owns the translation into the device's int32
envelope — epoch-relative engine-ms via EpochClock, saturating counter
clamps — documented in core.store.

Thread model: not thread-safe by design; all access is funneled through one
serving thread/event loop, the same discipline the reference imposes with
its cache mutex (reference gubernator.go:237-238) but without per-request
lock traffic.
"""

from __future__ import annotations

import bisect
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.api.types import (
    Algorithm,
    RateLimitReq,
    RateLimitResp,
    Status,
    millisecond_now,
    resps_from_columns,
)
from gubernator_tpu.core.hashing import native_lib, slot_hash_batch
from gubernator_tpu.core.kernels import (
    BatchGroups,
    BatchRequest,
    decide_presorted,
    decide_presorted_sketch,
    pack_outputs,
    packed_inputs_width,
    rebase_jit,
    unpack_inputs,
    unpack_outputs,
    upsert_globals_jit,
)
from gubernator_tpu.core.store import (
    COUNTER_MAX,
    MAX_DURATION_MS,
    REBASE_AT,
    TIME_FLOOR,
    Store,
    StoreConfig,
    group_sort_key_np,
    new_store,
)

DEFAULT_BUCKETS = (64, 256, 1024, 4096)

# Throughput-mode extension of the ladder: deep rungs for big-store
# deployments, where the writeback's full-table HBM pass is paid once
# per batch and only batch depth amortizes it (a 1 GiB store measured
# 4.28M dec/s at B=16384 vs 20.6M at B=131072 —
# BENCH_ZIPF10M_PROFILE_r5.json). Only rungs below the
# configured GUBER_DEVICE_BATCH_LIMIT materialize (buckets_for_limit),
# so default deployments compile nothing extra.
DEEP_BUCKETS = (16384, 32768, 131072)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2, 3))
def _decide_packed_jit(store, packed_in, B, G):
    """unpack_inputs + decide_presorted + pack_outputs: one host
    transfer per batch each way (kernels.pack_inputs lays `packed_in`
    out; B request rows and G group slots are static)."""
    req, groups, now = unpack_inputs(packed_in, B, G)
    store, resp, stats = decide_presorted(store, req, now, groups)
    return store, pack_outputs(resp, stats)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2, 3))
def _decide_packed_chain_jit(store, packed_in, B, G):
    """Quota-chain twin of _decide_packed_jit (r15): one jitted pass
    with chain-coupled rows (kernels.decide_presorted_chain), their
    chain ids the one column appended to `packed_in`. Chain batches run
    exact-only — the sketch tier is never consulted
    (core/algorithms.py eligibility)."""
    from gubernator_tpu.core.kernels import decide_presorted_chain

    req, groups, now = unpack_inputs(packed_in, B, G)
    chain_id = packed_in[packed_inputs_width(B, G) :]
    store, resp, stats = decide_presorted_chain(
        store, req, now, chain_id, groups
    )
    return store, pack_outputs(resp, stats)


@functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=(3, 4))
def _decide_packed_sketch_jit(store, sketch, packed_in, B, G):
    """Two-tier twin of _decide_packed_jit (r13): store AND sketch
    donate; the packed transfer layouts are identical both ways, so
    _dispatch and decide_wait serve both variants unchanged."""
    req, groups, now = unpack_inputs(packed_in, B, G)
    store, sketch, resp, stats = decide_presorted_sketch(
        store, sketch, req, now, groups
    )
    return store, sketch, pack_outputs(resp, stats)


def buckets_for_limit(limit: int) -> tuple:
    """Padding buckets covering batches up to `limit` (the daemon's
    GUBER_DEVICE_BATCH_LIMIT) — each rung costs one XLA compile at
    warmup. Rungs above the limit are useless, so the ladder is trimmed
    to the rungs below it plus one final rung at the limit itself
    (rounded up to a 128-lane multiple): a limit between rungs (e.g.
    5000) caps padding waste at the rounding instead of jumping to the
    next power-of-four (which would pad 4097-5000-row batches 3.3x).
    Limits past the default envelope pick up the DEEP_BUCKETS rungs, so
    a throughput-mode ladder (limit=131072) keeps intermediate rungs
    (16384, 32768) instead of padding a 5k-row lull 26x to the top."""
    base = [b for b in DEFAULT_BUCKETS + DEEP_BUCKETS if b < limit]
    base.append(-(-limit // 128) * 128)
    return tuple(base)


def _np_presort(key_hash: np.ndarray, store_buckets: int) -> np.ndarray:
    return np.argsort(
        group_sort_key_np(key_hash, store_buckets), kind="stable"
    ).astype(np.int32)


# libguberhash.so, whole or absent (core/hashing.native_lib): the native
# LSD radix presort (~3.6x numpy at 16k keys, same order) and one-pass
# gather+clip+pad marshalling (the numpy form costs ~40ns/element across
# the six request fields), else the numpy twins in this file
_hn = native_lib()
_presort = _hn.presort if _hn is not None else _np_presort


def _np_presort_grouped(key_hash: np.ndarray, store_buckets: int):
    """Numpy twin of hashlib_native.presort_grouped."""
    skey = group_sort_key_np(key_hash, store_buckets)
    order = np.argsort(skey, kind="stable").astype(np.int32)
    s = skey[order]
    is_leader = np.empty(s.shape[0], bool)
    if s.shape[0]:
        is_leader[0] = True
        np.not_equal(s[1:], s[:-1], out=is_leader[1:])
    group_id = np.cumsum(is_leader).astype(np.int32) - 1
    leader_pos = np.flatnonzero(is_leader).astype(np.int32)
    return order, group_id, leader_pos, int(leader_pos.shape[0])


_presort_grouped = (
    _hn.presort_grouped if _hn is not None else _np_presort_grouped
)


def build_groups(
    kh_padded: np.ndarray,
    group_id_n: np.ndarray,
    leader_pos_n: np.ndarray,
    G_real: int,
    n: int,
    B: int,
    G: int,
) -> "BatchGroups":
    """Assemble the padded BatchGroups arrays from a grouped presort.

    Padding conventions the kernel relies on (single source of truth for
    pad_request_sorted and the benchmarks): padded group slots carry
    leader_pos=B / end_pos=B-1 / valid=False; the final real group owns
    the request padding tail; padded request rows point at the last real
    group; group leader keys are host-gathered from the sorted padded
    key array."""
    leader_pos = np.full(G, B, np.int32)
    end_pos = np.full(G, B - 1, np.int32)
    g_valid = np.zeros(G, bool)
    if G_real:
        leader_pos[:G_real] = leader_pos_n[:G_real]
        end_pos[: G_real - 1] = leader_pos_n[1:G_real] - 1
        g_valid[:G_real] = True
    group_id = np.empty(B, np.int32)
    group_id[:n] = group_id_n[:n]
    group_id[n:] = max(G_real - 1, 0)
    return BatchGroups(
        key_hash=kh_padded[np.minimum(leader_pos, B - 1)],
        leader_pos=leader_pos,
        end_pos=end_pos,
        valid=g_valid,
        group_id=group_id,
    )


def group_rungs(b: int) -> tuple:
    """Group-count padding rungs for a request bucket of size b: G <= n
    always, and real traffic is duplicate-heavy (zipf batches measure
    G/B ~ 0.23-0.26), so compact rungs at 15b/64, b/4 and 3b/8 plus the
    full-size fallback capture most of the win for three extra XLA
    programs per request bucket at warmup. The fine low rungs matter at
    the flagship batch: 32k-row zipf batches carry ~7.4-7.6k unique
    keys; padding their store I/O to 12288 instead of 8192 costs ~12%
    of the whole kernel, and the r3-added 15b/64 rung (7680) over 8192
    bought another ~5% — 918 -> 814 us/batch, 40.3M decisions/s
    (scripts/profile_decide.py; bench.py). MUST stay in lockstep with
    guberhash.cc group_rungs_c (the native prep's twin)."""
    return tuple(
        sorted(
            {
                min(b, max(64, (15 * b) // 64)),
                min(b, max(64, b // 4)),
                min(b, max(64, (3 * b) // 8)),
                b,
            }
        )
    )

_I32_SAT = COUNTER_MAX


def _sat_i32(x: np.ndarray) -> np.ndarray:
    """Saturate int64 counters into int32 (documented divergence: values
    beyond ~2.1e9 clamp; see core.store docstring)."""
    return np.clip(np.asarray(x, np.int64), -_I32_SAT, _I32_SAT).astype(
        np.int32
    )


def _sat_duration(x: np.ndarray) -> np.ndarray:
    return np.clip(np.asarray(x, np.int64), TIME_FLOOR, MAX_DURATION_MS).astype(
        np.int32
    )


class EpochClock:
    """Maps int64 unix-ms to the store's int32 engine-ms envelope.

    The epoch pins engine-ms 0; `advance` returns now as engine-ms plus a
    rebase delta once offsets exceed 2^30 (~12.4 days of uptime), which
    the caller applies to the store with one elementwise pass
    (store.rebase): stored times shift down by delta and entries already
    past the new epoch clamp to TIME_FLOOR, i.e. expire naturally. Only
    jumps rebase cannot represent surface as reset_required — a forward
    jump past int32 range (> ~24.8 days in one step, no window survives
    it anyway) or a backward jump past REBASE_AT (shifting up could clamp
    entries into the far future, making them immortal) — matching the
    reference's state-loss-on-restart contract."""

    def __init__(self):
        self.epoch: Optional[int] = None

    def advance(self, now: int) -> Tuple[np.int32, Optional[int], bool]:
        """Returns (engine_now, rebase_delta, reset_required).

        The epoch pins ONE MILLISECOND before the first observed time,
        so live engine-ms values are always >= 1: engine-ms 0 is the
        wire's "no reset" sentinel (from_engine passes it through), and
        since r15 a real timestamp can land there — a GCRA peek at the
        pinning instant reports reset_time = its TAT = the current
        time, which a 0-based epoch would silently map to "no reset"."""
        now = int(now)
        if self.epoch is None:
            self.epoch = now - 1
        e = now - self.epoch
        if 0 <= e <= REBASE_AT:
            return np.int32(e), None, False
        self.epoch = now - 1
        e -= 1
        if -REBASE_AT < e <= _I32_SAT:
            return np.int32(1), e, False
        return np.int32(1), None, True

    def to_engine(self, t) -> np.ndarray:
        """int64 unix-ms (vector) -> int32 engine-ms, clamped."""
        assert self.epoch is not None
        return np.clip(
            np.asarray(t, np.int64) - self.epoch, TIME_FLOOR, _I32_SAT
        ).astype(np.int32)

    def from_engine(self, t32) -> np.ndarray:
        """int32 engine-ms -> int64 unix-ms; 0 passes through as the
        'no reset' sentinel (leaky UNDER_LIMIT, algorithms.go:123-174)."""
        assert self.epoch is not None
        t = np.asarray(t32, np.int64)
        return np.where(t == 0, 0, t + self.epoch)


def choose_bucket(buckets: Sequence[int], n: int) -> int:
    """Smallest configured batch bucket holding n requests."""
    i = bisect.bisect_left(buckets, n)
    if i == len(buckets):
        raise ValueError(f"batch of {n} exceeds max bucket {buckets[-1]}")
    return buckets[i]


def extend_ladder(buckets: Sequence[int], n: int) -> Sequence[int]:
    """`buckets`, continued past its top rung up to n with the ladder's
    1.5x-midpoint progression (hi*1.5, hi*2, hi*3, hi*4, ...) when n
    overflows it. MeshEngine serves batches beyond its configured ladder
    this way — per-shard sub-batching is exactly what makes an oversized
    batch affordable — while repeat overflows reuse O(log) compiled
    shapes instead of one XLA program per distinct size. TpuEngine does
    NOT use this: its ladder stays a hard cap sized to the serving
    batcher's device batch limit (buckets_for_limit)."""
    p = max(buckets)
    if n <= p:
        return buckets
    rungs = sorted(buckets)
    while p < n:
        half_up = p * 3 // 2
        p = half_up if n <= half_up else p * 2
        rungs.append(p)
    return tuple(rungs)


def dense_ladder_extension(buckets: Sequence[int], n: int) -> tuple:
    """`buckets` plus EVERY 1.5x-midpoint and doubling rung above its top
    up to n. For any m <= n, the smallest rung here >= m equals
    choose_bucket(extend_ladder(buckets, m), m): extend_ladder doubles
    while m exceeds the 1.5x midpoint and takes the midpoint only on its
    final step, so its chosen rung is exactly the smallest element of
    {top*2^j} u {1.5*top*2^j} >= m. The native one-call prep receives
    this dense form because it must pick a rung before the per-shard max
    count (the extend_ladder target) is known host-side."""
    rungs = set(buckets)
    p = max(buckets)
    while p < n:
        rungs.add(p * 3 // 2)
        p *= 2
        rungs.add(p)
    return tuple(sorted(rungs))


def pad_to_bucket(buckets: Sequence[int], n: int, *arrs):
    """Pad (array, dtype) pairs to the chosen bucket; returns
    (padded_arrays..., valid_mask)."""
    B = choose_bucket(buckets, n)
    out = []
    for x, dtype in arrs:
        p = np.zeros(B, dtype)
        p[:n] = x
        out.append(p)
    valid = np.zeros(B, bool)
    valid[:n] = True
    return (*out, valid)


def pad_request_sorted(
    buckets: Sequence[int],
    store_buckets: int,
    key_hash: np.ndarray,
    hits: np.ndarray,
    limit: np.ndarray,
    duration: np.ndarray,
    algo: np.ndarray,
    gnp: np.ndarray,
    with_groups: bool = False,
):
    """Pad request arrays to a fixed bucket size (one compiled program
    per bucket, not per batch size) plus the host-side presort that
    decide_presorted requires: rows ordered by (bucket, fingerprint) of the key hash, with
    the padding tail repeating the LAST sorted row's key (valid=False) so
    the device's bucket stream stays monotonic.

    Returns (sorted_request, order) — or (sorted_request, order, groups)
    when with_groups is set — where order[i] is the caller's index of
    sorted row i (order is a permutation of the padded size B; padding
    rows map to themselves). Unpermute device responses with
    `resp_orig[order] = resp_sorted`. Sorting host-side removes the two
    largest fixed costs (key sort + response unsort) from the device
    program; it is one numpy argsort pipelined with device compute.

    with_groups additionally emits the batch's duplicate-key group
    structure (kernels.BatchGroups) padded to a group_rungs(B) rung so
    the kernel runs all store I/O at unique-key granularity."""
    n = key_hash.shape[0]
    B = choose_bucket(buckets, n)

    if (
        _hn is not None
        and n
        and with_groups
        and _hn.prep_threads() > 1
    ):
        # with_groups gate doubles as a buffer-lifetime guard: only the
        # pipelined decide path (which owns the two-in-flight contract
        # behind prep buffer flip-flopping) runs the native prep;
        # sync_globals and other with_groups=False callers must not flip
        # a thread's generations between a decide submit and its wait.
        # one-call native prep (n_shards=1): presort + groups + marshal
        # fused (guberhash.cc guber_prep_sharded); [1, B] rows view as
        # the flat [B] arrays this path returns. Bit-identical to the
        # numpy path below (tests/test_prep_native.py). Gated to
        # multi-thread hosts: on one core the fused counting path below
        # measures ~18% faster (763 vs 903 us/32k), while with a thread
        # pool the one-call path parallelizes and single-call GIL
        # release lets batcher prep workers overlap.
        order_w, _counts, _take, fields, groups_d, Bn, _G = (
            _hn.prep_sharded(
                key_hash, hits, limit, duration, algo, gnp,
                store_buckets, 1, np.asarray([B], np.int64), 0,
                -_I32_SAT, _I32_SAT, TIME_FLOOR, MAX_DURATION_MS,
            )
        )
        req = BatchRequest(**{k: v[0] for k, v in fields.items()})
        order = np.empty(B, np.int32)
        order[:n] = order_w
        order[n:] = np.arange(n, B, dtype=np.int32)
        return req, order, BatchGroups(
            key_hash=groups_d["key_hash"][0],
            leader_pos=groups_d["leader_pos"][0],
            end_pos=groups_d["end_pos"][0],
            valid=groups_d["valid"][0],
            group_id=groups_d["group_id"][0],
        )

    if with_groups:
        order_n, group_id_n, leader_pos_n, G_real = _presort_grouped(
            key_hash, store_buckets
        )
        G = choose_bucket(group_rungs(B), max(G_real, 1))
    else:
        order_n = _presort(key_hash, store_buckets)

    valid = np.zeros(B, bool)
    valid[:n] = True
    if _hn is not None and n:
        req = BatchRequest(
            key_hash=_hn.gather_pad_u64(key_hash, order_n, B),
            hits=_hn.gather_pad_i64_clip(
                hits, order_n, B, -_I32_SAT, _I32_SAT
            ),
            limit=_hn.gather_pad_i64_clip(
                limit, order_n, B, -_I32_SAT, _I32_SAT
            ),
            duration=_hn.gather_pad_i64_clip(
                duration, order_n, B, TIME_FLOOR, MAX_DURATION_MS
            ),
            algo=_hn.gather_pad_i32(algo, order_n, B),
            gnp=_hn.gather_pad_u8(
                np.asarray(gnp, bool).view(np.uint8), order_n, B
            ).view(bool),
            valid=valid,
        )
    else:
        def pad_sorted(x, dtype, sat=None):
            x = sat(x) if sat is not None else np.asarray(x, dtype)
            out = np.empty(B, dtype)
            out[:n] = x[order_n]
            out[n:] = out[n - 1] if n else 0
            return out

        req = BatchRequest(
            key_hash=pad_sorted(key_hash, np.uint64),
            hits=pad_sorted(hits, np.int32, _sat_i32),
            limit=pad_sorted(limit, np.int32, _sat_i32),
            duration=pad_sorted(duration, np.int32, _sat_duration),
            algo=pad_sorted(algo, np.int32),
            gnp=pad_sorted(gnp, bool),
            valid=valid,
        )
    order = np.empty(B, np.int32)
    order[:n] = order_n
    order[n:] = np.arange(n, B, dtype=np.int32)
    if with_groups:
        groups = build_groups(
            req.key_hash, group_id_n, leader_pos_n, G_real, n, B, G
        )
        return req, order, groups
    return req, order


def groups_from_sorted_keys(
    skey_sorted: np.ndarray, kh_padded: np.ndarray, n: int, B: int
) -> "BatchGroups":
    """Duplicate-key group structure of an ALREADY-SORTED key stream —
    the presorted-submit twin of _np_presort_grouped's grouping pass
    (one O(n) diff instead of the argsort it no longer needs). `skey`
    ties define groups exactly as the flush-time path's sorted stream
    would, so the padded BatchGroups are bit-identical."""
    is_leader = np.empty(n, bool)
    if n:
        is_leader[0] = True
        np.not_equal(skey_sorted[1:n], skey_sorted[: n - 1],
                     out=is_leader[1:])
    group_id_n = np.cumsum(is_leader).astype(np.int32) - 1
    leader_pos_n = np.flatnonzero(is_leader).astype(np.int32)
    G_real = int(leader_pos_n.shape[0])
    G = choose_bucket(group_rungs(B), max(G_real, 1))
    return build_groups(
        kh_padded, group_id_n, leader_pos_n, G_real, n, B, G
    )


def pad_sorted_fields(fields: dict, n: int, B: int) -> "BatchRequest":
    """BatchRequest from device-dtype arrays ALREADY in sorted order
    (arrival-time prep + merge combine): pure pad — repeat the last
    sorted row with valid=False, the same tail pad_request_sorted
    emits."""

    def pad(x, dtype):
        out = np.empty(B, dtype)
        out[:n] = x
        out[n:] = out[n - 1] if n else 0
        return out

    valid = np.zeros(B, bool)
    valid[:n] = True
    return BatchRequest(
        key_hash=pad(fields["key_hash"], np.uint64),
        hits=pad(fields["hits"], np.int32),
        limit=pad(fields["limit"], np.int32),
        duration=pad(fields["duration"], np.int32),
        algo=pad(fields["algo"], np.int32),
        gnp=pad(fields["gnp"], bool),
        valid=valid,
    )


def _gather_clip_sorted(fields: dict, order: np.ndarray, n: int) -> dict:
    """Device-dtype clip + gather of one group's fields into sorted
    order. Native gather_pad helpers when built (one GIL-free C call
    per field — arrival preps run while the serving loop is hot, so op
    count is wall time); numpy fallback is elementwise-identical."""
    if _hn is not None and n:
        return dict(
            key_hash=_hn.gather_pad_u64(
                fields["key_hash"], order, n
            ),
            hits=_hn.gather_pad_i64_clip(
                fields["hits"], order, n, -_I32_SAT, _I32_SAT
            ),
            limit=_hn.gather_pad_i64_clip(
                fields["limit"], order, n, -_I32_SAT, _I32_SAT
            ),
            duration=_hn.gather_pad_i64_clip(
                fields["duration"], order, n, TIME_FLOOR,
                MAX_DURATION_MS,
            ),
            algo=_hn.gather_pad_i32(fields["algo"], order, n),
            gnp=_hn.gather_pad_u8(
                np.asarray(fields["gnp"], bool).view(np.uint8), order, n
            ).view(bool),
        )
    return dict(
        key_hash=np.asarray(fields["key_hash"], np.uint64)[order],
        hits=_sat_i32(fields["hits"])[order],
        limit=_sat_i32(fields["limit"])[order],
        duration=_sat_duration(fields["duration"])[order],
        algo=np.asarray(fields["algo"], np.int32)[order],
        gnp=np.asarray(fields["gnp"], bool)[order],
    )


def prep_run_single(fields: dict, store_buckets: int) -> dict:
    """Arrival-time per-group prep for the single-device engine:
    presort one group by (bucket, fingerprint) and clip every field
    into its device dtype, producing a sorted run the flush-time merge
    combine (serve/prep.py) stitches into one device batch. `order` is
    the caller index of sorted row j; `counts` is the single-shard row
    count (shape [1], mirroring the mesh engine's per-shard counts so
    the merge is engine-agnostic). One fused native call when built
    (guber_prep_run — prep threads stay off the interpreter); the
    numpy fallback below is bit-identical."""
    if _hn is not None:
        return _hn.prep_run(
            fields, store_buckets, 1, -_I32_SAT, _I32_SAT, TIME_FLOOR,
            MAX_DURATION_MS,
        )
    kh = np.ascontiguousarray(fields["key_hash"], np.uint64)
    n = kh.shape[0]
    order = _presort(kh, store_buckets)
    sorted_fields = _gather_clip_sorted(fields, order, n)
    return dict(
        n=n,
        # the sort key is elementwise in the key hash, so computing it
        # on the SORTED hashes equals gathering the unsorted keys
        skey=group_sort_key_np(sorted_fields["key_hash"], store_buckets),
        order=order,
        counts=np.array([n], np.int64),
        fields=sorted_fields,
    )


def build_presorted_request(
    buckets: Sequence[int], fields: dict, skey: np.ndarray, n: int
):
    """(req, groups, B) for an already-sorted batch — the merge-combine
    twin of pad_request_sorted(with_groups=True), minus the argsort it
    no longer needs. Byte-identical outputs are pinned by
    tests/test_prep_pipeline.py."""
    B = choose_bucket(buckets, n)
    req = pad_sorted_fields(fields, n, B)
    groups = groups_from_sorted_keys(skey, req.key_hash, n, B)
    return req, groups, B


def unpermute_responses(order: np.ndarray, sorted_arrays):
    """Inverse of pad_request_sorted's row order: one O(B) numpy store
    per response array (`out[order] = sorted`)."""
    out = []
    for a in sorted_arrays:
        u = np.empty_like(a)
        u[order] = a
        out.append(u)
    return out


class EngineStats:
    """Monotonic counters. Batch results land via add_batch under a lock:
    with fetch_depth > 1 the batcher completes several decide_waits
    concurrently (serve/batcher.py), and unlocked += would drop counts."""

    def __init__(self):
        import threading

        self.hits = 0
        self.misses = 0
        self.batches = 0
        # over-admission signals (kernels.BatchStats dropped/evictions)
        self.dropped = 0
        self.evictions = 0
        self._lock = threading.Lock()

    def add_batch(
        self, hits: int, misses: int, dropped: int = 0, evictions: int = 0
    ) -> None:
        with self._lock:
            self.hits += hits
            self.misses += misses
            self.dropped += dropped
            self.evictions += evictions
            self.batches += 1

    def snapshot(self):
        with self._lock:
            return dict(
                hits=self.hits,
                misses=self.misses,
                batches=self.batches,
                dropped=self.dropped,
                evictions=self.evictions,
            )


def __getattr__(name):
    # TpuEngine is now the degenerate (single-device, flat) case of the
    # ONE partitioned engine (r14): its implementation lives in
    # parallel/sharded.py next to the mesh layout so decide/upsert/
    # snapshot/sketch paths cannot drift between topologies. This lazy
    # alias keeps every historical `from core.engine import TpuEngine`
    # import site working without a core -> parallel import cycle.
    if name == "TpuEngine":
        from gubernator_tpu.parallel.sharded import TpuEngine

        return TpuEngine
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )
