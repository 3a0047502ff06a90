"""Mesh-sharded rate limiting: the consistent-hash ring mapped onto a
`jax.sharding.Mesh`.

The reference distributes keys across peers with a consistent-hash ring and
forwards requests over gRPC (reference hash.go:80-96, peers.go:111-127).
Inside one host, this framework distributes keys across TPU chips instead:

- The slot store gains a leading `shard` axis, laid out over the mesh's
  "shard" axis — every chip owns `1/n` of the key space, the moral
  equivalent of one ring peer per chip, with ownership decided by a cheap
  hash (`owner = mix64(key_hash) mod n`) instead of a sorted ring search:
  with homogeneous chips there is no reason to pay the ring's lookup cost
  or its imbalance (the reference places one point per peer, hash.go:62-67).
- The request BATCH is sharded too: the host presorts each batch by
  (owner_shard, bucket, fingerprint) — one native radix pass — slices the
  contiguous per-shard runs into per-chip sub-batches, and lays the
  [n_shards, B_sub] request arrays out over the mesh's batch axis. Each
  chip evaluates ONLY the ~B/n rows it owns, so aggregate decisions/s
  scales with chip count — the same economy as the reference forwarding
  each key only to its owner peer (reference peers.go:111-207). The decide
  path needs NO collective at all: responses come back per-shard and the
  host unpermutes them into request order (it already owns the
  permutation).
- GLOBAL mode's owner->replica broadcast (reference global.go:158-232)
  becomes `sync_globals`: owners peek authoritative status, one psum
  replicates it mesh-wide, and every non-owner installs replica entries —
  the async gossip loop collapsed into a single collective step.

Multi-host scaling composes: each host runs one mesh-sharded engine over
its chips, and hosts peer with each other over gRPC exactly like reference
nodes (serve/peers.py), so ICI carries intra-host traffic and DCN only
carries the host-level ring's.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import ctypes
import functools
import logging
import os
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gubernator_tpu.core.engine import (
    EngineStats,
    EpochClock,
    _sat_i32,
    extend_ladder,
    pad_request_sorted,
    pad_to_bucket,
)
from gubernator_tpu.core.hashing import native_lib
from gubernator_tpu.core.kernels import (
    BatchGroups,
    BatchRequest,
    BatchResponse,
    decide_presorted,
    pack_inputs,
    pack_outputs,
    packed_inputs_width,
    rebase_jit,
    unpack_inputs,
    upsert_globals,
    upsert_globals_jit,
    upsert_windows_jit,
)
from gubernator_tpu.core.store import Store, StoreConfig, mix64, new_store
from gubernator_tpu.parallel.policy import ShardingPolicy

# wall-clock reads go through the api.types MODULE attribute: the test
# suites pin the serving clock by patching millisecond_now there (and on
# core.engine/core.oracle), and a from-import frozen at import time
# would leak real time into fake-clock differential fuzzes
from gubernator_tpu.api import types as api_types

_SHARD_SALT = np.uint64(0xA24BAED4963EE407)

_log = logging.getLogger("gubernator.sharded")
_warned_ladder_overflow = False


def _warn_ladder_overflow(top: int, n: int) -> None:
    """One-time attribution for the multi-second stall a first oversized
    batch causes: extending the ladder compiles a fresh XLA program
    mid-call (library-only path — the serving batcher caps batches at
    the ladder top, so it never gets here)."""
    global _warned_ladder_overflow
    if not _warned_ladder_overflow:
        _warned_ladder_overflow = True
        _log.warning(
            "batch of %d exceeds the configured ladder top %d: extending "
            "the rung ladder triggers a fresh XLA compilation (tens of "
            "seconds on TPU) for this and each new overflow size — size "
            "the `buckets` ladder to your peak batch to avoid the stall",
            n,
            top,
        )


def owner_of(key_hash: jax.Array, n_shards: int) -> jax.Array:
    """Owning shard index for each key hash (device-side)."""
    return (mix64(key_hash ^ _SHARD_SALT) % jnp.uint64(n_shards)).astype(
        jnp.int32
    )


def owner_of_np(key_hash: np.ndarray, n_shards: int) -> np.ndarray:
    """Host-side twin of owner_of (numpy)."""
    from gubernator_tpu.core import hashing

    return (hashing.mix64(key_hash ^ _SHARD_SALT) % np.uint64(n_shards)).astype(
        np.int32
    )


def _axis_me(axes: tuple) -> jax.Array:
    """Flattened shard index under a 1-D ("shard",) or 2-D
    ("host", "chip") mesh — the 2-D form is host-major, matching the
    process-major device order the mesh is built with, so owner_of's
    `mod n_shards` placement is identical under both layouts."""
    me = jax.lax.axis_index(axes[0])
    for ax in axes[1:]:
        me = me * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
    return me


def _hier_psum(x: jax.Array, axes: tuple) -> jax.Array:
    """Hierarchical all-reduce (BASELINE config 5): innermost axis
    first. On a multi-slice mesh with axes ("host", "chip") this stages
    the reduction — chips within a host combine over ICI, then ONE
    pre-reduced vector per host crosses DCN — instead of a flat psum
    whose ring spans DCN on every leg. Mathematically identical to
    `psum(x, axes)`; the staging is the point."""
    for ax in reversed(axes):
        x = jax.lax.psum(x, ax)
    return x


def _local_decide(store: Store, packed_in, *, B, G):
    """Per-device body under shard_map: store AND batch are this device's
    shards. The host routed every request row to its owner chip
    (pad_request_sharded), so each chip runs the plain single-device
    kernel on its own sub-batch — no collective on the decide path, the
    mesh analogue of the reference forwarding only owned keys to a peer
    (reference peers.go:111-207) — with its own per-shard duplicate-key
    group structure (store I/O at unique-key granularity, see
    kernels.BatchGroups). The sub-batch arrives as this shard's row of
    the one packed input array (kernels.pack_inputs, `now` in its own
    slot of every row); responses + stats pack into one int32 row per
    shard (one host transfer each way)."""
    store = jax.tree.map(lambda x: x[0], store)  # [1, r, s] -> [r, s]
    req, groups, now = unpack_inputs(packed_in[0], B, G)
    new_store_shard, resp, stats = decide_presorted(store, req, now, groups)
    packed = pack_outputs(resp, stats)
    return jax.tree.map(lambda x: x[None], new_store_shard), packed[None]


def _gather_rows(out, axes):
    """One all_gather of the packed response rows: when the mesh spans
    processes the serving host cannot fetch follower shards directly,
    so the responses ride the compiled collective path (ICI within a
    host, DCN between hosts) and come out replicated. On the 2-D mesh
    the gather names both axes host-major, so the gathered row order
    equals the flattened shard order."""
    if len(axes) == 1:
        return jax.lax.all_gather(out, axes[0])
    # gather chips within a host over ICI first, then hosts over DCN,
    # then flatten [host, chip, ...] -> [shard, ...]
    out = jax.lax.all_gather(out, axes[-1])
    out = jax.lax.all_gather(out, axes[0])
    return out.reshape((-1,) + out.shape[2:])


def _local_decide_gathered(store: Store, packed_in, *, B, G,
                           axes=("shard",)):
    """_local_decide with its response rows gathered (_gather_rows)."""
    store, packed = _local_decide(store, packed_in, B=B, G=G)
    return store, _gather_rows(packed[0], axes)


def _local_decide_sketch(store: Store, sketch, packed_in, *, B, G):
    """Two-tier twin of _local_decide (r14): each shard carries its own
    count-min SUB-SKETCH next to its store shard. The host routes every
    key to its owner chip, so a key's sketch charges land only in its
    owner's sub-sketch — the sketch identity is (shard, key, window),
    and the per-key error bound is the CLASSIC bound over that shard's
    charged total N_s <= N (sharding can only tighten it; see
    docs/operations.md "Partitioned engine (r14)")."""
    from gubernator_tpu.core.kernels import decide_presorted_sketch

    store = jax.tree.map(lambda x: x[0], store)
    sketch = jax.tree.map(lambda x: x[0], sketch)
    req, groups, now = unpack_inputs(packed_in[0], B, G)
    new_store, new_sketch, resp, stats = decide_presorted_sketch(
        store, sketch, req, now, groups
    )
    packed = pack_outputs(resp, stats)
    return (
        jax.tree.map(lambda x: x[None], new_store),
        jax.tree.map(lambda x: x[None], new_sketch),
        packed[None],
    )


def _local_decide_sketch_gathered(store: Store, sketch, packed_in, *, B, G,
                                  axes=("shard",)):
    """_local_decide_sketch with its response rows gathered (r20): the
    two-tier step's replicated-response form for meshes that span
    processes, exactly like the exact-only step."""
    store, sketch, packed = _local_decide_sketch(
        store, sketch, packed_in, B=B, G=G
    )
    return store, sketch, _gather_rows(packed[0], axes)


def _shard_sketch_min(data, owner, idx, axes):
    """Owner-masked collective count-min read (r20): each shard row-mins
    its LOCAL sub-sketch at the probe indices, zeroes the keys it does
    not own, and a hierarchical psum leaves every key's owner estimate
    replicated on all shards — the collective twin of _sketch_min_sharded
    for meshes whose shards the reading host cannot address (multihost:
    the promoter's estimate gathers become lockstep device programs
    instead of leader-only host indexing). Exactly one shard contributes
    per key, so the psum IS the owner's row-min."""
    local = data[0]  # [1, rows, width] -> [rows, width]
    est = None
    for r in range(idx.shape[0]):
        c = jnp.take(local[r], idx[r])
        est = c if est is None else jnp.minimum(est, c)
    me = _axis_me(axes)
    est = jnp.where(owner == me, est, 0)
    return _hier_psum(est, axes)


def _shard_rows(data, owner, b, axes):
    """Owner-masked collective bucket-row gather (r20): the collective
    twin of _rows_sharded — each shard gathers the requested bucket rows
    from its local store shard, zeroes rows for keys it does not own,
    and the psum replicates the owner's rows everywhere. Non-mutating;
    backs _gather_entries (live_mask / snapshot_read) on process-
    spanning meshes."""
    local = data[0]  # [1, buckets, lanes] -> [buckets, lanes]
    rows = jnp.take(local, b, axis=0)
    me = _axis_me(axes)
    rows = jnp.where((owner == me)[:, None], rows, 0)
    return _hier_psum(rows, axes)


def _np_presort_sharded(
    key_hash: np.ndarray, store_buckets: int, n_shards: int
):
    """Numpy fallback for the native sharded presort: stable argsort by
    (owner_shard, bucket, fingerprint) + per-shard counts."""
    from gubernator_tpu.core.store import group_sort_key_np

    owner = owner_of_np(key_hash, n_shards)
    # owner bits sit just above the (bucket << 32 | fp) group key, like
    # the native sort key (guberhash.cc guber_presort_sharded)
    bucket_bits = max(int(store_buckets).bit_length() - 1, 1)
    comp = (
        owner.astype(np.uint64) << np.uint64(32 + bucket_bits)
    ) | group_sort_key_np(key_hash, store_buckets)
    order = np.argsort(comp, kind="stable").astype(np.int32)
    counts = np.bincount(owner, minlength=n_shards).astype(np.int64)
    return order, counts


def _np_presort_sharded_grouped(
    key_hash: np.ndarray, store_buckets: int, n_shards: int
):
    """Numpy fallback for the native sharded+grouped presort."""
    from gubernator_tpu.core.store import group_sort_key_np

    owner = owner_of_np(key_hash, n_shards)
    bucket_bits = max(int(store_buckets).bit_length() - 1, 1)
    comp = (
        owner.astype(np.uint64) << np.uint64(32 + bucket_bits)
    ) | group_sort_key_np(key_hash, store_buckets)
    order = np.argsort(comp, kind="stable").astype(np.int32)
    counts = np.bincount(owner, minlength=n_shards).astype(np.int64)
    s = comp[order]
    n = s.shape[0]
    is_leader = np.empty(n, bool)
    if n:
        is_leader[0] = True
        np.not_equal(s[1:], s[:-1], out=is_leader[1:])
    group_id = np.cumsum(is_leader).astype(np.int32) - 1
    leader_pos = np.flatnonzero(is_leader).astype(np.int32)
    g_owner = (s[leader_pos] >> np.uint64(32 + bucket_bits)).astype(np.int64)
    group_counts = np.bincount(g_owner, minlength=n_shards).astype(np.int64)
    return order, counts, group_id, leader_pos, group_counts


# libguberhash.so, whole or absent (core/hashing.native_lib): the native
# radix presort with shard partitioning and the fused one-call prep,
# else the numpy twins above
_hn = native_lib()
if _hn is not None:
    _presort_sharded = _hn.presort_sharded
    _presort_sharded_grouped = _hn.presort_sharded_grouped
    _prep_native = _hn.prep_sharded
else:
    _presort_sharded = _np_presort_sharded
    _presort_sharded_grouped = _np_presort_sharded_grouped
    _prep_native = None


def sub_batch_ladder(buckets: Sequence[int]) -> tuple:
    """Padding rungs for per-shard sub-batches: the host ladder densified
    with 1.5x midpoints (64, 96, 128, 192, ... between min and max rung).
    Shard counts concentrate at ~B/n_shards + multinomial jitter, so the
    coarse 4x host ladder would pad a shard's rows up to 4x (measured:
    total mesh work grew instead of staying flat); midpoints cap padding
    waste at 1.5x for one extra compile per octave at warmup."""
    lo, hi = min(buckets), max(buckets)
    rungs = set(buckets)
    p = lo
    while p < hi:
        rungs.add(p)
        rungs.add(min(p * 3 // 2, hi))
        p *= 2
    rungs.add(hi)
    return tuple(sorted(rungs))


def pad_request_sharded(
    buckets: Sequence[int],
    store_buckets: int,
    n_shards: int,
    key_hash: np.ndarray,
    hits: np.ndarray,
    limit: np.ndarray,
    duration: np.ndarray,
    algo: np.ndarray,
    gnp: np.ndarray,
    with_groups: bool = False,
    group_rung: Optional[int] = None,
):
    """Partition a batch into per-shard sub-batches: the mesh sibling of
    engine.pad_request_sorted. One (owner, bucket, fp) radix sort makes
    each shard's rows a contiguous presorted run; every field becomes a
    [n_shards, B_sub] array (B_sub = bucket fitting the LARGEST shard's
    count) whose row s is shard s's sub-batch padded by repeating its
    last row with valid=False (preserving the monotonic bucket stream).

    Returns (req, order, take_idx) — plus `groups` when with_groups:
    - req: BatchRequest of [n_shards, B_sub] arrays, batch-axis shardable
      P("shard") — row s belongs on chip s.
    - order[k]: caller index of the k-th row in global sorted order.
    - take_idx[k]: flattened [n_shards*B_sub] device position of that row.
    - groups: BatchGroups of [n_shards, ...] arrays (per-shard
      duplicate-key structure, indices LOCAL to each shard's sub-batch)
      so each chip's store I/O runs at unique-key granularity.
    `group_rung` overrides the G rung choice (must hold every shard's
    group count) — callers staging SEVERAL batches into one stacked
    array pass a shared rung so the BatchGroups shapes line up.
    Unpermute responses with `out[order] = resp_flat[take_idx]`.
    """
    from gubernator_tpu.core.engine import (
        _sat_duration as sat_dur,
        _sat_i32 as sat_i32,
        choose_bucket,
        group_rungs,
    )

    n = key_hash.shape[0]
    if n == 0:
        # empty batch: one all-invalid row per shard (smallest rung)
        B0 = buckets[0] if hasattr(buckets, "__getitem__") else min(buckets)
        req = BatchRequest(
            key_hash=np.zeros((n_shards, B0), np.uint64),
            hits=np.zeros((n_shards, B0), np.int32),
            limit=np.zeros((n_shards, B0), np.int32),
            duration=np.zeros((n_shards, B0), np.int32),
            algo=np.zeros((n_shards, B0), np.int32),
            gnp=np.zeros((n_shards, B0), bool),
            valid=np.zeros((n_shards, B0), bool),
        )
        empty = (req, np.empty(0, np.int32), np.empty(0, np.int64))
        if with_groups:
            G0 = group_rungs(B0)[0]
            return (*empty, BatchGroups(
                key_hash=np.zeros((n_shards, G0), np.uint64),
                leader_pos=np.full((n_shards, G0), B0, np.int32),
                end_pos=np.full((n_shards, G0), B0 - 1, np.int32),
                valid=np.zeros((n_shards, G0), bool),
                group_id=np.zeros((n_shards, B0), np.int32),
            ))
        return empty
    if _prep_native is not None and with_groups:
        # one-call native prep: presort + groups + marshal fused (3.6x
        # the numpy path on one core, thread-parallel on real hosts —
        # guberhash.cc guber_prep_sharded). Bit-identical to the numpy
        # path below (pinned by tests/test_prep_native.py). Gated to
        # with_groups (the decide path): only it owns the two-in-flight
        # contract the flip-flopped prep buffers rely on.
        from gubernator_tpu.core.engine import dense_ladder_extension
        from gubernator_tpu.core.store import (
            COUNTER_MAX,
            MAX_DURATION_MS,
            TIME_FLOOR,
        )

        rungs = np.asarray(dense_ladder_extension(buckets, n), np.int64)
        order, counts, take_idx, fields, groups_d, B_sub, _G = (
            _prep_native(
                key_hash, hits, limit, duration, algo, gnp,
                store_buckets, n_shards, rungs,
                int(group_rung) if group_rung else 0,
                -COUNTER_MAX, COUNTER_MAX, TIME_FLOOR, MAX_DURATION_MS,
            )
        )
        if int(counts.max()) > max(buckets):
            _warn_ladder_overflow(max(buckets), int(counts.max()))
        req = BatchRequest(**fields)
        return req, order, take_idx, BatchGroups(
            key_hash=groups_d["key_hash"],
            leader_pos=groups_d["leader_pos"],
            end_pos=groups_d["end_pos"],
            valid=groups_d["valid"],
            group_id=groups_d["group_id"],
        )

    if with_groups:
        order, counts, gid_g, lp_g, gcounts = _presort_sharded_grouped(
            key_hash, store_buckets, n_shards
        )
    else:
        order, counts = _presort_sharded(key_hash, store_buckets, n_shards)
    counts32 = counts.astype(np.int64)
    starts = np.zeros(n_shards + 1, np.int64)
    np.cumsum(counts32, out=starts[1:])
    maxc = max(int(counts32.max()), 1)
    # a shard can draw more rows than the ladder's top rung when the
    # caller's batch exceeds max(buckets) — unreachable through the
    # serving tier (the batcher caps batches at the ladder top) but
    # supported for library callers: extend, don't raise
    if maxc > max(buckets):
        _warn_ladder_overflow(max(buckets), maxc)
    B_sub = choose_bucket(extend_ladder(buckets, maxc), maxc)

    # src[s, j]: index into the sorted arrays for padded cell (s, j) —
    # clamped to the shard's last real row (repeat-pad); empty shards
    # clamp to a neighbouring row, masked invalid below.
    j = np.arange(B_sub, dtype=np.int64)[None, :]
    src = starts[:-1, None] + np.minimum(
        j, np.maximum(counts32[:, None] - 1, 0)
    )
    np.clip(src, 0, max(n - 1, 0), out=src)
    valid = j < counts32[:, None]
    idx = order[src]  # compose once: caller index per padded cell

    def shard_field(x, dtype, sat=None):
        x = sat(x) if sat is not None else np.asarray(x, dtype)
        return x[idx]  # [n_shards, B_sub]

    req = BatchRequest(
        key_hash=shard_field(key_hash, np.uint64),
        hits=shard_field(hits, np.int32, sat_i32),
        limit=shard_field(limit, np.int32, sat_i32),
        duration=shard_field(duration, np.int32, sat_dur),
        algo=shard_field(algo, np.int32),
        gnp=shard_field(gnp, bool),
        valid=valid,
    )
    # global sorted position k lives at device cell (shard_of_k, k-start)
    shard_of_k = np.repeat(np.arange(n_shards, dtype=np.int64), counts32)
    take_idx = shard_of_k * B_sub + (np.arange(n, dtype=np.int64) - starts[shard_of_k])
    if not with_groups:
        return req, order, take_idx

    groups = stack_shard_groups(
        req.key_hash, gid_g, lp_g, gcounts, counts32, starts, n_shards,
        B_sub, group_rung,
    )
    return req, order, take_idx, groups


def stack_shard_groups(
    req_kh: np.ndarray,
    gid_g: np.ndarray,
    lp_g: np.ndarray,
    gcounts: np.ndarray,
    counts32: np.ndarray,
    starts: np.ndarray,
    n_shards: int,
    B_sub: int,
    group_rung: Optional[int] = None,
) -> BatchGroups:
    """Per-shard group structure with LOCAL indices (each shard's kernel
    sees only its own [B_sub] sub-batch); padding conventions come from
    the single source of truth, engine.build_groups, called per shard.
    Global group ids are contiguous in shard order (shard boundaries
    break groups), so shard s's groups are exactly
    gstarts[s]..gstarts[s+1] and its first group id IS gstarts[s].
    Shared by the flush-time presort path (pad_request_sharded) and the
    merge-combine path (MeshEngine.decide_submit_presorted) so the two
    can never drift."""
    from gubernator_tpu.core.engine import (
        build_groups,
        choose_bucket,
        group_rungs,
    )

    gstarts = np.zeros(n_shards + 1, np.int64)
    np.cumsum(gcounts, out=gstarts[1:])
    if group_rung is not None:
        if group_rung < int(gcounts.max()):
            raise ValueError(
                f"group_rung {group_rung} < max shard group count "
                f"{int(gcounts.max())}"
            )
        G_sub = group_rung
    else:
        G_sub = choose_bucket(
            group_rungs(B_sub), max(int(gcounts.max()), 1)
        )
    per_shard = []
    for s in range(n_shards):
        gc = int(gcounts[s])
        cs = int(counts32[s])
        per_shard.append(
            build_groups(
                req_kh[s],
                gid_g[starts[s] : starts[s] + cs] - int(gstarts[s]),
                lp_g[gstarts[s] : gstarts[s] + gc] - int(starts[s]),
                gc,
                cs,
                B_sub,
                G_sub,
            )
        )
    return BatchGroups(
        *(np.stack(leaves) for leaves in zip(*per_shard))
    )


def sharded_sort_keys_np(
    key_hash: np.ndarray, store_buckets: int, n_shards: int
) -> np.ndarray:
    """Composite host sort key of the sharded presort order —
    (owner_shard | bucket | fingerprint), the same packing
    _np_presort_sharded and guber_presort_sharded order by."""
    from gubernator_tpu.core.store import group_sort_key_np

    kh = np.asarray(key_hash, np.uint64)
    owner = owner_of_np(kh, n_shards)
    bucket_bits = max(int(store_buckets).bit_length() - 1, 1)
    return (
        owner.astype(np.uint64) << np.uint64(32 + bucket_bits)
    ) | group_sort_key_np(kh, store_buckets)


def prep_run_sharded(
    fields: dict, store_buckets: int, n_shards: int
) -> dict:
    """Arrival-time per-group prep for the mesh engine: presort one
    group by (owner, bucket, fingerprint), clip fields to device
    dtypes, and count rows per shard — a sorted run the flush-time
    merge combine (serve/prep.py) stitches into one sharded batch.
    One fused native call when built (guber_prep_run); the numpy
    fallback below is bit-identical."""
    from gubernator_tpu.core.engine import _gather_clip_sorted

    if _hn is not None:
        from gubernator_tpu.core.store import (
            COUNTER_MAX,
            MAX_DURATION_MS,
            TIME_FLOOR,
        )

        return _hn.prep_run(
            fields, store_buckets, n_shards, -COUNTER_MAX, COUNTER_MAX,
            TIME_FLOOR, MAX_DURATION_MS,
        )
    kh = np.ascontiguousarray(fields["key_hash"], np.uint64)
    n = kh.shape[0]
    order, counts = _presort_sharded(kh, store_buckets, n_shards)
    sorted_fields = _gather_clip_sorted(fields, order, n)
    return dict(
        n=n,
        # elementwise in the key hash, so computed on the sorted hashes
        skey=sharded_sort_keys_np(
            sorted_fields["key_hash"], store_buckets, n_shards
        ),
        order=order,
        counts=np.asarray(counts, np.int64),
        fields=sorted_fields,
    )


def build_presorted_sharded(
    sub_buckets: Sequence[int],
    store_buckets: int,
    n_shards: int,
    fields: dict,
    skey: np.ndarray,
    counts: np.ndarray,
):
    """(req, take_idx, groups, B_sub) for an already-sorted sharded
    batch — the merge-combine twin of pad_request_sharded
    (with_groups=True), minus the argsort it no longer needs.
    Byte-identical outputs are pinned by tests/test_prep_pipeline.py.
    The served mesh path takes the native twin where the library has
    it (guber_merge_runs_sharded: the merge and this layout in one
    GIL-free call, PartitionedEngine.merge_prepped); this form is the
    fallback, the chain lane's and the lockstep follower's layout, and
    the oracle the native one is held to, byte for byte.
    """
    from gubernator_tpu.core.engine import choose_bucket

    n = skey.shape[0]
    counts32 = np.asarray(counts, np.int64)
    starts = np.zeros(n_shards + 1, np.int64)
    np.cumsum(counts32, out=starts[1:])
    maxc = max(int(counts32.max()), 1)
    if maxc > max(sub_buckets):
        _warn_ladder_overflow(max(sub_buckets), maxc)
    B_sub = choose_bucket(extend_ladder(sub_buckets, maxc), maxc)
    # padded cell (s, j) reads merged sorted row starts[s]+min(j,
    # count-1) — the same repeat-pad/clamp pad_request_sharded
    # applies, but gathering from the sorted stream directly
    # (sorted_x[src] == x[order][src] == x[idx])
    j = np.arange(B_sub, dtype=np.int64)[None, :]
    src = starts[:-1, None] + np.minimum(
        j, np.maximum(counts32[:, None] - 1, 0)
    )
    np.clip(src, 0, max(n - 1, 0), out=src)
    valid = j < counts32[:, None]
    req = BatchRequest(
        key_hash=fields["key_hash"][src],
        hits=fields["hits"][src],
        limit=fields["limit"][src],
        duration=fields["duration"][src],
        algo=fields["algo"][src],
        gnp=fields["gnp"][src],
        valid=valid,
    )
    # group structure straight off the sorted key stream (skey ties ==
    # comp ties of _np_presort_sharded_grouped): one diff pass replaces
    # the grouped argsort
    is_leader = np.empty(n, bool)
    is_leader[0] = True
    np.not_equal(skey[1:], skey[:-1], out=is_leader[1:])
    gid_g = np.cumsum(is_leader).astype(np.int32) - 1
    lp_g = np.flatnonzero(is_leader).astype(np.int32)
    bucket_bits = max(int(store_buckets).bit_length() - 1, 1)
    g_owner = (skey[lp_g] >> np.uint64(32 + bucket_bits)).astype(
        np.int64
    )
    gcounts = np.bincount(g_owner, minlength=n_shards).astype(np.int64)
    groups = stack_shard_groups(
        req.key_hash, gid_g, lp_g, gcounts, counts32, starts, n_shards,
        B_sub,
    )
    shard_of_k = np.repeat(np.arange(n_shards, dtype=np.int64), counts32)
    take_idx = shard_of_k * B_sub + (
        np.arange(n, dtype=np.int64) - starts[shard_of_k]
    )
    return req, take_idx, groups, B_sub


def _local_decide_chain(store: Store, packed_in, *, B, G):
    """Per-device chain decide under shard_map (r15): the host routed
    every CHAIN whole to its head-key owner shard (pad_request_chained),
    so the chain AND-reduce runs entirely shard-local — the decide path
    keeps its no-collective property even with coupled rows. The chain
    ids are the one column appended to the packed input row."""
    from gubernator_tpu.core.kernels import decide_presorted_chain

    store = jax.tree.map(lambda x: x[0], store)
    req, groups, now = unpack_inputs(packed_in[0], B, G)
    chain_id = packed_in[0, packed_inputs_width(B, G) :]
    new_store, resp, stats = decide_presorted_chain(
        store, req, now, chain_id, groups
    )
    packed = pack_outputs(resp, stats)
    return jax.tree.map(lambda x: x[None], new_store), packed[None]


def pad_request_chained(
    buckets: Sequence[int],
    store_buckets: int,
    n_shards: int,
    key_hash: np.ndarray,
    hits: np.ndarray,
    limit: np.ndarray,
    duration: np.ndarray,
    algo: np.ndarray,
    chain_ids: np.ndarray,
    route_hash: np.ndarray,
):
    """Presort + pad one CHAINED batch (r15): rows whose `chain_ids`
    match are one hierarchical request's levels and must decide in the
    same kernel invocation (the no-partial-debit AND-reduce is
    shard-local). Ownership therefore follows `route_hash` — the chain
    HEAD's key hash, identical for every row of a chain — while bucket
    addressing keeps each row's OWN key hash, so a chain's levels land
    whole on one shard yet store state in their own buckets. numpy-only
    (the native prep has no chain column; chain batches ride a
    dedicated lane, serve/batcher.py).

    Returns (req, order, take_idx, groups, chain_local) where
    chain_local carries kernel-ready per-shard-local chain slots
    (int32, values < the sub-batch rung; padding rows are singleton
    chains). take_idx is None on the flat (n_shards == 1) layout.

    Consolidation contract: a level key shared by chains with
    DIFFERENT heads lands on each head's owner shard separately, so
    its quota would be tracked per shard. Well-formed hierarchies
    (every child under one parent) never do this; the serving tier
    routes by chain head for the same reason (serve/instance.py).
    """
    from gubernator_tpu.core.engine import (
        _gather_clip_sorted,
        build_presorted_request,
    )
    from gubernator_tpu.core.store import group_sort_key_np

    kh = np.ascontiguousarray(key_hash, np.uint64)
    n = kh.shape[0]
    skey = group_sort_key_np(kh, store_buckets)
    if n_shards > 1:
        owner = owner_of_np(
            np.ascontiguousarray(route_hash, np.uint64), n_shards
        )
        bucket_bits = max(int(store_buckets).bit_length() - 1, 1)
        comp = (
            owner.astype(np.uint64) << np.uint64(32 + bucket_bits)
        ) | skey
    else:
        owner = np.zeros(n, np.int32)
        comp = skey
    order = np.argsort(comp, kind="stable").astype(np.int32)
    s = comp[order]
    sorted_fields = _gather_clip_sorted(
        dict(
            key_hash=kh, hits=hits, limit=limit, duration=duration,
            algo=algo, gnp=np.zeros(n, bool),
        ),
        order,
        n,
    )
    chain_sorted = np.asarray(chain_ids, np.int64)[order]
    # pad/group/take_idx machinery is the merge-combine twins' —
    # delegated so the owner bit-packing, ladder-overflow, and
    # clamp-pad invariants cannot drift between the chain and plain
    # sharded paths; only the chain-slot localization is chain-specific

    if n_shards == 1:
        req, groups, B = build_presorted_request(
            buckets, sorted_fields, s, n
        )
        chain_local = np.arange(B, dtype=np.int32)
        if n:
            _, inv = np.unique(chain_sorted, return_inverse=True)
            chain_local[:n] = inv  # values < n <= B
        return req, order, None, groups, chain_local

    counts = np.bincount(owner, minlength=n_shards).astype(np.int64)
    req, take_idx, groups, B_sub = build_presorted_sharded(
        buckets, store_buckets, n_shards, sorted_fields, s, counts
    )
    starts = np.zeros(n_shards + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    chain_local = np.broadcast_to(
        np.arange(B_sub, dtype=np.int32), (n_shards, B_sub)
    ).copy()
    for sh in range(n_shards):
        c = int(counts[sh])
        if c:
            _, inv = np.unique(
                chain_sorted[starts[sh] : starts[sh] + c],
                return_inverse=True,
            )
            chain_local[sh, :c] = inv  # values < c <= B_sub
    return req, order, take_idx, groups, chain_local


def _shard_sync_globals(
    store: Store,
    key_hash: jax.Array,  # uint64[B] global keys to broadcast
    hits: jax.Array,  # int32[B] aggregated GLOBAL hits to charge on the
    # owner shard BEFORE broadcasting (0 = pure peek, the classic
    # sync_globals gossip step; nonzero = apply_global_hits, the
    # in-mesh psum replacing the owner->replica gossip round trip)
    limit: jax.Array,  # int32[B] request limit (for owner-side peek of misses)
    duration: jax.Array,
    algo: jax.Array,  # int32[B]: must match the stored algorithm, or the
    # peek would take the mismatch-recreate path and wipe owner state
    valid: jax.Array,
    now,
    n_shards: int,
    axes: tuple = ("shard",),
):
    """Owner charges+peeks authoritative status; psum replicates;
    others upsert. On a 2-D ("host", "chip") mesh the replication is
    the hierarchical ICI-then-DCN reduction of BASELINE config 5 (see
    _hier_psum)."""
    me = _axis_me(axes)
    store = jax.tree.map(lambda x: x[0], store)
    mine = owner_of(key_hash, n_shards) == me

    peek = BatchRequest(
        key_hash=key_hash,
        hits=hits,
        limit=limit,
        duration=duration,
        algo=algo,
        gnp=jnp.zeros(key_hash.shape[0], bool),
        valid=valid & mine,
    )
    store2, resp, _ = decide_presorted(store, peek, now)

    mask = mine & valid

    def combine(x):
        return _hier_psum(jnp.where(mask, x, 0), axes)

    status = combine(resp.status)
    r_limit = combine(resp.limit)
    remaining = combine(resp.remaining)
    reset = combine(resp.reset_time)

    # install replicas everywhere except the owner shard
    store3 = upsert_globals(
        store2,
        key_hash,
        r_limit,
        remaining,
        reset,
        status == 1,
        valid & ~mine,
    )
    return jax.tree.map(lambda x: x[None], store3), BatchResponse(
        status=status, limit=r_limit, remaining=remaining, reset_time=reset
    )


def _shard_upsert(
    store: Store,
    key_hash: jax.Array,
    limit: jax.Array,
    remaining: jax.Array,
    reset_time: jax.Array,
    is_over: jax.Array,
    valid: jax.Array,
    n_shards: int,
    axes: tuple = ("shard",),
):
    """Install GLOBAL replica statuses on each key's owning shard."""
    me = _axis_me(axes)
    store = jax.tree.map(lambda x: x[0], store)
    mine = owner_of(key_hash, n_shards) == me
    out = upsert_globals(
        store, key_hash, limit, remaining, reset_time, is_over, valid & mine
    )
    return jax.tree.map(lambda x: x[None], out)


def _shard_upsert_full(
    store: Store,
    key_hash: jax.Array,
    limit: jax.Array,
    remaining: jax.Array,
    reset_time: jax.Array,
    duration: jax.Array,
    ts: jax.Array,
    flags: jax.Array,
    valid: jax.Array,
    n_shards: int,
    axes: tuple = ("shard",),
):
    """Full-lane window install on each key's owning shard (r19): the
    mesh twin of upsert_windows_jit, carrying the raw L_DURATION/L_TS/
    L_FLAGS words so restored/re-partitioned entries of any algorithm
    land byte-exact."""
    from gubernator_tpu.core.store import FLAG_STICKY_OVER

    me = _axis_me(axes)
    store = jax.tree.map(lambda x: x[0], store)
    mine = owner_of(key_hash, n_shards) == me
    out = upsert_globals(
        store, key_hash, limit, remaining, reset_time,
        (flags & FLAG_STICKY_OVER) != 0, valid & mine,
        duration=duration, ts=ts, flags=flags,
    )
    return jax.tree.map(lambda x: x[None], out)


class PartitionedEngine:
    """ONE engine, every topology (r14): host glue + device programs
    for the slot store (and the r13 sketch cold tier), parameterized by
    a ShardingPolicy instead of being forked per topology.

    The policy decides the layout; the engine's host-side surfaces are
    layout-independent and SHARED, so decide/upsert/snapshot/sketch
    paths cannot drift between topologies (the r9 stack_shard_groups
    seam, finished):

    - flat (ShardingPolicy.single, the degenerate case): batches are
      flat [B] arrays, dispatch is a plain jit with the store donated —
      byte-identical to the historical single-device TpuEngine,
      including every padding and presort convention
      (tests/test_prep_pipeline.py pins them).
    - mesh (ShardingPolicy.over_mesh): the store (and sketch) gain a
      leading shard axis laid out over the mesh; batches become
      [n_shards, B_sub] per-shard sub-batches routed host-side by
      `owner = mix64(key_hash) mod n` (the consistent-hash ring mapped
      onto the mesh axis); dispatch is a jitted shard_map where each
      chip runs the SAME single-device kernel on its own sub-batch —
      no collective on the decide path. GLOBAL sync/upsert ride
      collectives (psum / owner-masked upsert) whose structure the
      policy picks (hierarchical ICI-then-DCN on 2-D meshes).

    TpuEngine and MeshEngine below are thin constructor shims over
    this class; parallel/multihost.py wraps it with the lockstep step
    pipe for multi-controller SPMD.
    """

    #: `with self.stage_span(name):` around the two halves of
    #: _dispatch and the mesh's per-shard build. The serving tier
    #: installs its stage clock here (serve/backends.py); a bare
    #: engine times nothing
    stage_span = staticmethod(contextlib.nullcontext)
    #: `self.shard_counts(rows, slots, max_rows)` once per mesh device
    #: batch (`_shard_stack`); the serving tier installs its /metrics
    #: counters here, a bare engine counts nothing
    shard_counts = staticmethod(lambda rows, slots, max_rows: None)

    def __init__(
        self,
        config: StoreConfig = StoreConfig(),
        policy: Optional[ShardingPolicy] = None,
        buckets: Sequence[int] = (64, 256, 1024, 4096),
        sketch=None,
    ):
        self.policy = (
            policy if policy is not None else ShardingPolicy.single()
        )
        self.flat = self.policy.flat
        self.config = config
        self.buckets = sorted(buckets)
        self.device = self.policy.device
        self.clock = EpochClock()
        self.stats = EngineStats()
        # bumped by every reset(): the store-wipe epoch the over-limit
        # shed cache checks (serve/shedcache.py)
        self.reset_generation = 0
        # serve-tier hot-key observer (serve/promoter.py): called with
        # every dispatched BatchRequest (numpy, pre-device, flat [B] or
        # sharded [n_shards, B_sub] — the observer masks by `valid`
        # either way) so the streaming top-K candidate source sees all
        # traffic regardless of door or topology. Must never raise into
        # the dispatch path.
        self.observe_hook = None
        # merged mesh batches by who laid them out per shard: the
        # native merge in its one call, or numpy on the submit thread
        # (/metrics mesh_native_stacks_total, mesh_numpy_stacks_total)
        self.native_stacks = 0
        self.numpy_stacks = 0
        # sketch cold tier (r13; sharded over the mesh axis since r14):
        # `sketch_on` is the runtime A/B flag (scripts/perf_gate.py
        # flips it between paired rounds; both variants compile lazily)
        self.sketch_config = sketch
        self.sketch = None
        self.sketch_on = sketch is not None
        # r20: process-spanning meshes carry the sketch tier too — the
        # promoter's host reads (estimates, live rows) compile to
        # owner-masked psum collectives (_shard_sketch_min/_shard_rows)
        # instead of leader-only sharded-array indexing, and the
        # multihost wrapper broadcasts promote/ghits as lockstep
        # messages so every process issues the identical programs. The
        # pre-r20 GUBER_SKETCH multihost refusal is lifted.

        if self.flat:
            self.n = 1
            self.mesh = None
            self.axes: tuple = ()
        else:
            self.n = self.policy.n_shards
            self.mesh = self.policy.mesh
            self.axes = self.policy.axes
            self.sub_buckets = sub_batch_ladder(self.buckets)
            self.store_sharding = self.policy.store_sharding()
            self._build_mesh_programs()
        self.store: Store = self._fresh_store()
        if sketch is not None:
            self.sketch = self._fresh_sketch()

    # -- state construction -------------------------------------------------

    def _build_mesh_programs(self) -> None:
        Ps = self.policy.request_spec()
        P0 = self.policy.replicated_spec()
        span = self.policy.spans_processes
        step_fn = (
            functools.partial(_local_decide_gathered, axes=self.axes)
            if span
            else _local_decide
        )
        self._step = self._mesh_decide_program(step_fn, 1)
        # quota-chain program (r15): chain-coupled rows, shard-local
        # AND-reduce (chains are routed whole to their head's owner).
        # jit is lazy, so deployments that never see a chain pay only
        # this wrapper construction. Multi-process meshes don't carry
        # it: the lockstep step pipe has no chain message (documented
        # scope limit; decide_chain_submit refuses loudly).
        self._step_chain = None
        if not span:
            self._step_chain = self._mesh_decide_program(
                _local_decide_chain, 1
            )
        self._step_sketch = None
        if self.sketch_config is not None:
            sketch_step_fn = (
                functools.partial(
                    _local_decide_sketch_gathered, axes=self.axes
                )
                if span
                else _local_decide_sketch
            )
            self._step_sketch = self._mesh_decide_program(
                sketch_step_fn, 2
            )
        # collective host-read programs (r20): when the mesh spans
        # processes the serving host cannot index follower shards, so
        # the promoter-surface reads (_gather_entries row gathers,
        # sketch_estimates row-mins) run as owner-masked psum
        # collectives with replicated outputs instead
        self._rows_coll = None
        self._sketch_min_coll = None
        if span:
            self._rows_coll = jax.jit(
                jax.shard_map(
                    functools.partial(_shard_rows, axes=self.axes),
                    mesh=self.mesh,
                    in_specs=(Ps, P0, P0),
                    out_specs=P0,
                    check_vma=False,
                )
            )
            if self.sketch_config is not None:
                self._sketch_min_coll = jax.jit(
                    jax.shard_map(
                        functools.partial(
                            _shard_sketch_min, axes=self.axes
                        ),
                        mesh=self.mesh,
                        in_specs=(Ps, P0, P0),
                        out_specs=P0,
                        check_vma=False,
                    )
                )
        sync_fn = functools.partial(
            _shard_sync_globals, n_shards=self.n, axes=self.axes
        )
        self._sync = jax.jit(
            jax.shard_map(
                sync_fn,
                mesh=self.mesh,
                in_specs=(Ps,) + (P0,) * 7,
                out_specs=(Ps, P0),
            ),
            donate_argnums=(0,),
        )
        upsert_fn = functools.partial(
            _shard_upsert, n_shards=self.n, axes=self.axes
        )
        self._upsert = jax.jit(
            jax.shard_map(
                upsert_fn,
                mesh=self.mesh,
                in_specs=(Ps,) + (P0,) * 6,
                out_specs=Ps,
            ),
            donate_argnums=(0,),
        )
        upsert_full_fn = functools.partial(
            _shard_upsert_full, n_shards=self.n, axes=self.axes
        )
        self._upsert_full = jax.jit(
            jax.shard_map(
                upsert_full_fn,
                mesh=self.mesh,
                in_specs=(Ps,) + (P0,) * 8,
                out_specs=Ps,
            ),
            donate_argnums=(0,),
        )

    def _mesh_decide_program(self, body, n_state: int):
        """jit(shard_map(body)) called as (*state, packed_in, B, G):
        `n_state` donated state pytrees and the one packed input array,
        all split on the shard axis; B and G (the per-shard rungs
        pack_inputs laid the rows out for) are static. A mesh that spans
        processes gets its response rows replicated (its bodies gather
        them) — the all_gather output IS replicated, but the static
        varying-axis check can't prove it, so it is disabled just
        there."""
        Ps = self.policy.request_spec()
        P0 = self.policy.replicated_spec()
        span = self.policy.spans_processes

        def step(*args):
            *sharded, B, G = args
            return jax.shard_map(
                functools.partial(body, B=B, G=G),
                mesh=self.mesh,
                in_specs=(Ps,) * (n_state + 1),
                out_specs=(Ps,) * n_state + (P0 if span else Ps,),
                check_vma=not span,
            )(*sharded)

        # the program keeps its body's name in traces and compile logs
        step.__name__ = step.__qualname__ = getattr(
            body, "func", body
        ).__name__
        return jax.jit(
            step,
            donate_argnums=tuple(range(n_state)),
            static_argnums=(n_state + 1, n_state + 2),
        )

    def _fresh(self, make):
        """One tier's state from `make()` (a zero-argument builder of
        ONE shard's pytree). Flat: on the policy's device. Mesh: the
        [n_shards, ...] stack is built by a jitted broadcast whose
        output already carries the store sharding, so each chip
        allocates its own shard and nothing else — stacking first and
        `device_put`-ing after would materialise all n shards on
        device 0 on the way."""
        if self.flat:
            base = make()
            if self.device is not None:
                base = jax.device_put(base, self.device)
            return base
        n = self.n

        def stacked():
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                make(),
            )

        return jax.jit(stacked, out_shardings=self.store_sharding)()

    def _fresh_store(self) -> Store:
        return self._fresh(functools.partial(new_store, self.config))

    def _fresh_sketch(self):
        from gubernator_tpu.core.sketches import new_sketch

        return self._fresh(
            functools.partial(new_sketch, self.sketch_config)
        )

    def state_bytes_by_device(self) -> dict:
        """{device id: bytes} of store + sketch resident on each device
        — where the state lives, from the arrays' own shardings (the
        boot log and /v1/debug/stages report it; on a mesh every device
        must hold 1/n_shards of the total). Reads shapes and shardings
        only, never a buffer: safe from any thread while the submit
        thread donates the store."""
        out: dict = {}
        for leaf in jax.tree.leaves((self.store, self.sketch)):
            sh = leaf.sharding
            nbytes = leaf.dtype.itemsize * int(
                np.prod(sh.shard_shape(leaf.shape))
            )
            for d in sh.addressable_devices:
                out[d.id] = out.get(d.id, 0) + nbytes
        return out

    def writeback_forms(self) -> dict:
        """{decide rung: the writeback forms its programs were traced
        with}, for the boot log: kernels.writeback_form at this
        engine's table shape (one SHARD's on a mesh, which is what the
        kernel sees under shard_map) and each of the rung's group
        rungs, the row count the writeback runs at. Shapes only, like
        state_bytes_by_device."""
        from gubernator_tpu.core.engine import group_rungs
        from gubernator_tpu.core.kernels import writeback_form

        data = self.store.data
        *_, rows, W = data.sharding.shard_shape(data.shape)
        return {
            b: sorted({writeback_form(rows, W, g) for g in group_rungs(b)})
            for b in (self.buckets if self.flat else self.sub_buckets)
        }

    def reset(self) -> None:
        """Empty state, the old state's device memory handed back
        BEFORE the new is allocated. Built first and swapped after, two
        tables were alive at once: that instant was the process's peak
        (1,128 MB against 554 MB of state at 2^20 rows), and a store
        that fills more than half a chip could not finish its warm-up,
        which ends here (PR 30). Submit-thread contract, like every
        call that replaces the store."""
        for leaf in jax.tree.leaves((self.store, self.sketch)):
            leaf.delete()
        self.store = self._fresh_store()
        if self.sketch_config is not None:
            self.sketch = self._fresh_sketch()
        self.reset_generation += 1

    def _engine_now(self, now: int) -> np.int32:
        e, delta, reset_required = self.clock.advance(now)
        if reset_required:
            self.reset()
        elif delta is not None:
            # rebase is elementwise, so it runs shard-local with the
            # store's sharding preserved — no collective needed
            self.store = rebase_jit(self.store, np.int32(delta))
            if self.sketch is not None:
                # sketch windows are keyed by engine-ms // duration, so
                # a rebase shifts every window id: clear rather than
                # carry counts into wrong windows. Rare (~12-day
                # cadence) and one-sided-safe in the fail-open
                # direction for at most one window per key — the same
                # class of loss as the reference's restart contract.
                self.sketch = self._fresh_sketch()
        return e

    # -- the one dispatch funnel --------------------------------------------

    def _shard_stack(self, build, *args, **kw):
        """One mesh device batch's per-shard sub-batches, built by
        `build` (pad_request_sharded / build_presorted_sharded): the
        owner presort's contiguous slices padded to one sub-rung and
        stacked [n_shards, B_sub] — the `shard_stack` stage — and what
        the zipf skew made of it: rows that carry a request, slots
        launched (shards x sub-rung), the fullest shard's rows."""
        with self.stage_span("shard_stack"):
            out = build(*args, **kw)
        rows = out[0].valid.sum(axis=1)
        self.shard_counts(
            int(rows.sum()), out[0].valid.size, int(rows.max())
        )
        return out

    @property
    def stack_implementation(self) -> str:
        """Who lays a merged mesh batch out per shard: "native"
        (guber_merge_runs_sharded, where the library loaded) or
        "numpy" (`_stack_presorted`)."""
        return "native" if _hn is not None else "numpy"

    def _stack_presorted(self, fields, skey, counts):
        """(req, take_idx, groups, B_sub) of an already-merged sorted
        batch, laid out in numpy: the twin of the native sharded merge
        (`merge_prepped`) — what serves without the library, for a
        batch past the sub-rung ladder's top, and for the lockstep
        follower's `decide_submit_presorted`."""
        self.numpy_stacks += 1
        return self._shard_stack(
            build_presorted_sharded,
            self.sub_buckets, self.config.slots, self.n, fields, skey,
            counts,
        )

    def _decide_call(self, req, groups, e_now):
        """(program, arguments) of one decide: the batch's host inputs
        packed into the ONE array that crosses to the device
        (kernels.pack_inputs) behind the state this engine's layout
        and tiers donate — the exact-only or two-tier program, flat or
        mesh. `_dispatch` calls it; the warm-up lowers and compiles it
        (`compile_ahead`), so the two can never name different
        programs."""
        two_tier = self.sketch is not None and self.sketch_on
        B, G = req.key_hash.shape[-1], groups.key_hash.shape[-1]
        packed_in = pack_inputs(req, groups, e_now)
        if self.flat:
            from gubernator_tpu.core.engine import (
                _decide_packed_jit,
                _decide_packed_sketch_jit,
            )

            fn = _decide_packed_sketch_jit if two_tier else _decide_packed_jit
        else:
            fn = self._step_sketch if two_tier else self._step
        state = (self.store, self.sketch) if two_tier else (self.store,)
        return fn, (*state, packed_in, B, G)

    def _dispatch(self, req, groups, e_now):
        """Every submit path — flat or sharded, flush-prep, arrival-
        prep or merged — ends here: feed the serve-tier hot-key
        observer (numpy fields, pre-device), pack the batch's host
        inputs and pick the program for this engine's layout
        (`_decide_call`), and call it. The hook and the jitted call
        are the `observe` and `jit_call` stages: what is left of the
        batcher's `dispatch` is pad + group-derive + that pack."""
        hook = self.observe_hook
        if hook is not None:
            with self.stage_span("observe"):
                try:
                    hook(req)
                except Exception:  # pragma: no cover - defensive
                    pass  # observability must never fail a dispatch
        fn, args = self._decide_call(req, groups, e_now)
        with self.stage_span("jit_call"):
            *state, packed = fn(*args)
        self.store = state[0]
        if len(state) == 2:
            self.sketch = state[1]
        return packed

    # -- request-object API --------------------------------------------------

    def get_rate_limits_submit(
        self,
        reqs: Sequence["RateLimitReq"],
        now: Optional[int] = None,
        gnp: Optional[Sequence[bool]] = None,
    ):
        """Request-object sibling of decide_submit: convert + presort +
        dispatch one batch without waiting. Returns an opaque handle for
        get_rate_limits_wait, or None for an empty batch."""
        from gubernator_tpu.core.hashing import slot_hash_batch

        n = len(reqs)
        if n == 0:
            return None
        if now is None:
            now = api_types.millisecond_now()
        keys = [r.hash_key() for r in reqs]
        hashes = slot_hash_batch(keys)
        hits = np.fromiter((r.hits for r in reqs), np.int64, n)
        limit = np.fromiter((r.limit for r in reqs), np.int64, n)
        duration = np.fromiter((r.duration for r in reqs), np.int64, n)
        algo = np.fromiter((int(r.algorithm) for r in reqs), np.int32, n)
        gnp_arr = (
            np.asarray(gnp, bool) if gnp is not None else np.zeros(n, bool)
        )
        return self.decide_submit(
            hashes, hits, limit, duration, algo, gnp_arr, now
        )

    def get_rate_limits_wait(self, handle):
        """Fetch + convert the responses for a get_rate_limits_submit
        handle."""
        from gubernator_tpu.api.types import resps_from_columns

        if handle is None:
            return []
        return resps_from_columns(*self.decide_wait(handle))

    def get_rate_limits(
        self,
        reqs: Sequence["RateLimitReq"],
        now: Optional[int] = None,
        gnp: Optional[Sequence[bool]] = None,
    ):
        """Decide a batch. `gnp[i]` marks GLOBAL non-owner replica reads."""
        return self.get_rate_limits_wait(
            self.get_rate_limits_submit(reqs, now=now, gnp=gnp)
        )

    # -- array decide paths --------------------------------------------------

    def decide_submit(
        self,
        key_hash: np.ndarray,
        hits: np.ndarray,
        limit: np.ndarray,
        duration: np.ndarray,
        algo: np.ndarray,
        gnp: np.ndarray,
        now: int,
    ):
        """Presort(/shard) + dispatch one batch WITHOUT waiting.

        The store update is effective immediately (the jitted call
        threads the donated store), so the next submit may follow at
        once; jax dispatch is async, which lets the caller presort
        batch i+1 while the device computes batch i — the pipelining
        the serving batcher relies on. Returns an opaque handle for
        decide_wait; the handle captures the submit-time epoch so a
        later rebase cannot skew an in-flight batch's reset_times."""
        n = key_hash.shape[0]
        e_now = self._engine_now(now)
        if self.flat:
            req, order, groups = pad_request_sorted(
                self.buckets,
                self.config.slots,
                key_hash,
                hits,
                limit,
                duration,
                algo,
                gnp,
                with_groups=True,
            )
            packed = self._dispatch(req, groups, e_now)
            return (
                packed, order, None, n, req.key_hash.shape[0],
                self.clock.epoch,
            )
        req, order, take_idx, groups = self._shard_stack(
            pad_request_sharded,
            self.sub_buckets,
            self.config.slots,
            self.n,
            key_hash,
            hits,
            limit,
            duration,
            algo,
            gnp,
            with_groups=True,
        )
        B_sub = req.key_hash.shape[1]
        packed = self._dispatch(req, groups, e_now)
        if _prep_native is not None:
            # the native prep returns order/take_idx as VIEWS into its
            # reusable buffer ring; this handle can outlive any fixed
            # ring depth under the batcher's out-of-order fetch
            # pipeline, so keep copies (device-field views need none:
            # dispatch commits host inputs before the step returns)
            order = order.copy()
            take_idx = take_idx.copy()
        return (packed, order, take_idx, n, B_sub, self.clock.epoch)

    def decide_chain_submit(
        self,
        key_hash: np.ndarray,
        hits: np.ndarray,
        limit: np.ndarray,
        duration: np.ndarray,
        algo: np.ndarray,
        chain_ids: np.ndarray,
        route_hash: np.ndarray,
        now: int,
    ):
        """Dispatch one CHAINED batch (r15) without waiting: rows
        sharing a `chain_ids` value are one hierarchical request's
        levels, decided atomically under the no-partial-debit contract
        (kernels.decide_presorted_chain); `route_hash` (the chain
        head's key hash per row) picks the owning shard so chains stay
        whole. Handle format is decide_wait's. Chain batches run
        exact-only (no sketch tier) and take the numpy prep path — a
        dedicated lane, not the native-prep pipeline."""
        if self.policy.spans_processes:
            raise ValueError(
                "quota chains are not supported on the multihost "
                "lockstep engine (no chain step message); route chains "
                "to single-host backends"
            )
        n = key_hash.shape[0]
        e_now = self._engine_now(now)
        req, order, take_idx, groups, chain_local = pad_request_chained(
            self.buckets if self.flat else self.sub_buckets,
            self.config.slots,
            self.n,
            key_hash,
            hits,
            limit,
            duration,
            algo,
            chain_ids,
            route_hash,
        )
        hook = self.observe_hook
        if hook is not None:
            try:
                hook(req)
            except Exception:  # pragma: no cover - defensive
                pass
        B, G = req.key_hash.shape[-1], groups.key_hash.shape[-1]
        packed_in = pack_inputs(req, groups, e_now, chain_local)
        if self.flat:
            from gubernator_tpu.core.engine import _decide_packed_chain_jit

            self.store, packed = _decide_packed_chain_jit(
                self.store, packed_in, B, G
            )
            order_p = np.empty(B, np.int32)
            order_p[:n] = order
            order_p[n:] = np.arange(n, B, dtype=np.int32)
            return (packed, order_p, None, n, B, self.clock.epoch)
        self.store, packed = self._step_chain(self.store, packed_in, B, G)
        return (packed, order, take_idx, n, B, self.clock.epoch)

    def decide_chain_arrays(
        self,
        key_hash: np.ndarray,
        hits: np.ndarray,
        limit: np.ndarray,
        duration: np.ndarray,
        algo: np.ndarray,
        chain_ids: np.ndarray,
        route_hash: np.ndarray,
        now: int,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Array-level chained decide: submit + wait (times int64
        unix-ms in/out, like decide_arrays)."""
        return self.decide_wait(
            self.decide_chain_submit(
                key_hash, hits, limit, duration, algo, chain_ids,
                route_hash, now,
            )
        )

    def prep_run(self, fields: dict) -> dict:
        """Arrival-time per-group prep (serve/batcher.py): one sorted,
        device-dtype run the flush-time merge combine stitches. The
        sort key is the policy's — (bucket, fp) flat, (owner, bucket,
        fp) sharded — so runs merge without re-sorting either way."""
        from gubernator_tpu.core.engine import prep_run_single

        if self.flat:
            return prep_run_single(fields, self.config.slots)
        return prep_run_sharded(fields, self.config.slots, self.n)

    def merge_prepped(self, runs):
        """Merge pre-sorted per-group runs into one dispatch-ready
        batch (the submit thread's `merge` stage)."""
        from gubernator_tpu.serve.prep import merge_runs

        if self.flat:
            from gubernator_tpu.core.engine import (
                build_presorted_request,
                choose_bucket,
                group_rungs,
            )

            n = int(sum(r["n"] for r in runs))
            B = choose_bucket(self.buckets, n)
            if _hn is not None and n:
                m = _hn.merge_runs_native(
                    runs, B, g_rungs=group_rungs(B)
                )
                req = BatchRequest(
                    key_hash=m["key_hash"], hits=m["hits"],
                    limit=m["limit"], duration=m["duration"],
                    algo=m["algo"], gnp=m["gnp"], valid=m["valid"],
                )
                groups = BatchGroups(
                    key_hash=m["group_key_hash"],
                    leader_pos=m["leader_pos"],
                    end_pos=m["group_end"],
                    valid=m["group_valid"],
                    group_id=m["group_id"],
                )
                return dict(
                    req=req, groups=groups, order=m["order"], n=n, B=B
                )
            m = merge_runs(runs)
            req, groups, B = build_presorted_request(
                self.buckets, m["fields"], m["skey"], n
            )
            order_p = np.empty(B, np.int32)
            order_p[:n] = m["order"]
            order_p[n:] = np.arange(n, B, dtype=np.int32)
            return dict(req=req, groups=groups, order=order_p, n=n, B=B)
        n = int(sum(r["n"] for r in runs))
        m = None
        if self.stack_implementation == "native" and n:
            # merge + the stacked layout in ONE native call, GIL
            # released; None = the fullest shard is past the ladder's
            # top, which the numpy twin below extends (and warns of)
            with self.stage_span("shard_stack"):
                m = _hn.merge_runs_sharded_native(
                    runs, self.n, self.config.slots, self.sub_buckets
                )
        if m is not None:
            self.native_stacks += 1
            self.shard_counts(
                n, self.n * m["B_sub"], int(m["counts"].max())
            )
            return dict(
                req=BatchRequest(**m["fields"]),
                groups=BatchGroups(**m["groups"]), order=m["order"],
                take_idx=m["take_idx"], n=n, B_sub=m["B_sub"],
            )
        m = merge_runs(runs)
        req, take_idx, groups, B_sub = self._stack_presorted(
            m["fields"], m["skey"], m["counts"]
        )
        return dict(
            req=req, groups=groups, order=m["order"],
            take_idx=take_idx, n=n, B_sub=B_sub,
        )

    def decide_submit_merged(self, merged: dict, now: int):
        """Dispatch a merge_prepped batch: epoch bookkeeping + the one
        jitted call — the submit thread's `dispatch` stage."""
        e_now = self._engine_now(now)
        packed = self._dispatch(merged["req"], merged["groups"], e_now)
        if self.flat:
            return (
                packed, merged["order"], None, merged["n"], merged["B"],
                self.clock.epoch,
            )
        return (
            packed, merged["order"], merged["take_idx"], merged["n"],
            merged["B_sub"], self.clock.epoch,
        )

    def decide_submit_presorted(
        self,
        fields: dict,
        skey: np.ndarray,
        order: Optional[np.ndarray],
        counts: np.ndarray,
        now: int,
    ):
        """Dispatch a batch whose host presort already happened
        (arrival-time prep + merge combine): `fields` are device-dtype
        request arrays in the policy's sorted order, `skey` the
        matching sorted composite keys, `order[k]` the caller index of
        sorted row k (None = identity, the lockstep-follower path),
        `counts` the per-shard row counts ([n] on the flat policy).
        Pads + derives the duplicate-key group structure in O(n) and
        dispatches — no argsort anywhere."""
        n = skey.shape[0]
        if n == 0:
            return None
        e_now = self._engine_now(now)
        if self.flat:
            from gubernator_tpu.core.engine import build_presorted_request

            req, groups, B = build_presorted_request(
                self.buckets, fields, skey, n
            )
            order_p = np.empty(B, np.int32)
            order_p[:n] = (
                order
                if order is not None
                else np.arange(n, dtype=np.int32)
            )
            order_p[n:] = np.arange(n, B, dtype=np.int32)
            packed = self._dispatch(req, groups, e_now)
            return (packed, order_p, None, n, B, self.clock.epoch)
        req, take_idx, groups, B_sub = self._stack_presorted(
            fields, skey, counts
        )
        if order is None:
            order = np.arange(n, dtype=np.int32)
        packed = self._dispatch(req, groups, e_now)
        return (packed, order, take_idx, n, B_sub, self.clock.epoch)

    def decide_wait(
        self, handle
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Fetch + unpermute the responses for a decide_submit handle.
        One handle format for every policy: (packed, order, take_idx,
        n, B, epoch) with take_idx None on the flat layout."""
        packed, order, take_idx, n, B, epoch = handle
        packed = np.asarray(jax.device_get(packed))
        if take_idx is None:
            from gubernator_tpu.core.engine import unpermute_responses
            from gubernator_tpu.core.kernels import unpack_outputs

            self.stats.add_batch(
                int(packed[4 * B]),
                int(packed[4 * B + 1]),
                int(packed[4 * B + 2]),
                int(packed[4 * B + 3]),
            )
            if _hn is not None:
                u = _hn.unpermute_i32(
                    packed[: 4 * B].reshape(4, B), order, n
                )
                status, rlimit, remaining, reset = u[0], u[1], u[2], u[3]
            else:
                s_st, s_lim, s_rem, s_reset = unpack_outputs(packed, B)[:4]
                status, rlimit, remaining, reset = unpermute_responses(
                    order, (s_st, s_lim, s_rem, s_reset)
                )
            r = np.asarray(reset[:n], np.int64)
            reset = np.where(r == 0, 0, r + epoch)
            return status[:n], rlimit[:n], remaining[:n], reset
        B_sub = B
        # [n_shards, 4*B_sub+PACKED_STATS]
        self.stats.add_batch(
            int(packed[:, 4 * B_sub].sum()),
            int(packed[:, 4 * B_sub + 1].sum()),
            int(packed[:, 4 * B_sub + 2].sum()),
            int(packed[:, 4 * B_sub + 3].sum()),
        )
        if _hn is not None and n > 0:
            # native one-pass unflatten of all four response columns
            bounds = np.searchsorted(
                take_idx, np.arange(1, self.n + 1) * B_sub, side="left"
            )
            counts = np.diff(np.concatenate(([0], bounds))).astype(
                np.int64
            )
            u = _hn.unflatten_resp(packed, order, counts, n, B_sub)
            status, rlimit, remaining, reset = u[0], u[1], u[2], u[3]
        else:

            def unflatten(col0):
                flat = packed[
                    :, col0 * B_sub : (col0 + 1) * B_sub
                ].reshape(-1)
                out = np.empty(n, flat.dtype)
                out[order] = flat[take_idx]
                return out

            status, rlimit, remaining, reset = (
                unflatten(c) for c in range(4)
            )
        r = np.asarray(reset, np.int64)
        reset = np.where(r == 0, 0, r + epoch)
        return status, rlimit, remaining, reset

    def decide_arrays(
        self,
        key_hash: np.ndarray,
        hits: np.ndarray,
        limit: np.ndarray,
        duration: np.ndarray,
        algo: np.ndarray,
        gnp: np.ndarray,
        now: int,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Array-level entry point (also the benchmark harness's).
        Times in/out are int64 unix-ms; conversion happens here."""
        return self.decide_wait(
            self.decide_submit(
                key_hash, hits, limit, duration, algo, gnp, now
            )
        )

    # -- shared host-side state reads ---------------------------------------

    @staticmethod
    def _pad_keys_pow2(key_hash: np.ndarray, *cols):
        """Pad key hashes (+ parallel int64 columns) to a power-of-two
        length (floor 64) by repeating the last row: un-jitted device
        gathers compile one kernel PER SHAPE, and the promoter's
        candidate count changes every tick (~500ms/tick of eager
        recompiles unpadded). Returns (kh, cols..., n)."""
        n = int(key_hash.shape[0])
        B = 1 << max(6, (n - 1).bit_length())
        kh = np.empty(B, np.uint64)
        kh[:n] = key_hash
        kh[n:] = kh[n - 1] if n else 0
        out = [kh]
        for c in cols:
            p = np.empty(B, np.int64)
            p[:n] = c
            p[n:] = p[n - 1] if n else 0
            out.append(p)
        out.append(n)
        return tuple(out)

    def _rows_call(self, kh_padded: np.ndarray):
        """(program, arguments) of the bucket-row gather behind every
        non-mutating host read, by layout: a plain take (flat), an
        owner-indexed gather (one-process mesh), or the owner-masked
        psum collective with a replicated output (a mesh that spans
        processes: follower shards are not host-addressable)."""
        from gubernator_tpu.core.store import bucket_index

        b = bucket_index(jnp.asarray(kh_padded), self.config.slots)
        if self.flat:
            return _rows_flat, (self.store.data, b)
        owner = jnp.asarray(owner_of_np(kh_padded, self.n))
        fn = (
            self._rows_coll
            if self.policy.spans_processes
            else _rows_sharded
        )
        return fn, (self.store.data, owner, b)

    def _gather_entries(self, kh_padded: np.ndarray) -> np.ndarray:
        """Host np int32[B, ways, LANES]: each key's candidate bucket
        row, gathered from the key's owning shard's store — THE one
        lookup every non-mutating host read (snapshot_read, live_mask)
        shares, so the addressed row can never drift between
        topologies. Non-mutating; same thread contract as
        snapshot_read."""
        from gubernator_tpu.core.store import LANES

        fn, args = self._rows_call(kh_padded)
        rows = fn(*args)
        return np.asarray(rows).reshape(kh_padded.shape[0], -1, LANES)

    def snapshot_read(
        self, key_hash: np.ndarray, now: Optional[int] = None
    ):
        """NON-MUTATING host read of the store rows for these uint64
        key hashes: per key, (limit, duration, remaining,
        reset_time_unix, over) for a live token window, or None
        (missing, expired, or leaky — leaky state refills continuously
        and is out of the replication scope). Nothing is written: no
        eviction, no expiry deletion, no stats — which is what makes
        bucket replication provably invisible to the decision stream.

        Thread contract: call from the batcher's single submit thread
        (DeviceBatcher.run_serialized) so the gather can never race a
        store-donating dispatch."""
        from gubernator_tpu.core.store import (
            FLAG_ALGO_LEAKY,
            FLAG_STICKY_OVER,
            L_DURATION,
            L_EXPIRE,
            L_FLAGS,
            L_LIMIT,
            L_REMAINING,
            L_TAG,
            fingerprints,
        )
        from gubernator_tpu.core import hashing

        n = int(key_hash.shape[0])
        if n == 0:
            return []
        if self.clock.epoch is None:
            return [None] * n  # nothing ever decided
        if now is None:
            now = api_types.millisecond_now()
        kh_p, _n = self._pad_keys_pow2(
            np.ascontiguousarray(key_hash, dtype=np.uint64)
        )
        ent_rows = self._gather_entries(kh_p)[:n]
        # fingerprint the PADDED pow2 shape and slice: eager per-n
        # shapes would recompile every distinct snapshot batch size
        fp = np.asarray(
            jax.device_get(fingerprints(jnp.asarray(kh_p)))
        )[:n]
        match = ent_rows[:, :, L_TAG] == fp[:, None]
        found = match.any(axis=1)
        way = np.argmax(match, axis=1)
        ent = ent_rows[np.arange(n), way]
        e_now = int(self.clock.to_engine(now))
        out = []
        flags_col = ent[:, L_FLAGS]
        for i in range(n):
            if not found[i] or int(ent[i, L_EXPIRE]) < e_now:
                out.append(None)  # miss, or entry past its reset
                continue
            flags = int(flags_col[i])
            if flags & FLAG_ALGO_LEAKY:
                out.append(None)
                continue
            remaining = int(ent[i, L_REMAINING])
            reset_time = int(
                self.clock.from_engine(np.int64(ent[i, L_EXPIRE]))
            )
            out.append((
                int(ent[i, L_LIMIT]),
                int(ent[i, L_DURATION]),
                remaining,
                reset_time,
                bool(flags & FLAG_STICKY_OVER) or remaining == 0,
            ))
        return out

    def live_mask(
        self, key_hash: np.ndarray, now: Optional[int] = None
    ) -> np.ndarray:
        """bool[n]: key currently holds a LIVE exact-tier entry (tag
        match, not expired) on its owning shard. Non-mutating; same
        thread contract as snapshot_read. The promoter screens
        candidates with this so an install can never clobber live
        exact state."""
        from gubernator_tpu.core.store import L_EXPIRE, L_TAG, fingerprints

        n = int(key_hash.shape[0])
        if n == 0 or self.clock.epoch is None:
            return np.zeros(n, bool)
        if now is None:
            now = api_types.millisecond_now()
        kh_p, _n = self._pad_keys_pow2(
            np.ascontiguousarray(key_hash, np.uint64)
        )
        rows = self._gather_entries(kh_p)
        fp = np.asarray(jax.device_get(fingerprints(jnp.asarray(kh_p))))
        match = rows[:, :, L_TAG] == fp[:, None]
        e_now = int(self.clock.to_engine(now))
        live = match & (rows[:, :, L_EXPIRE] >= e_now)
        return live.any(axis=1)[:n]

    # -- elastic re-partition (r17) ------------------------------------------

    def export_windows(self, now: Optional[int] = None) -> dict:
        """Host-side read of EVERY live window in the store:
        {key_hash uint64[m], limit, remaining, reset_time (unix-ms),
        is_over, duration, ts, flags} — the full-store twin of
        snapshot_read, enumerating entries instead of looking keys up.
        Each entry's key hash is reconstructed from its L_TAG|L_KEYLOW
        lanes (the r14 layout keeps the full 64 bits precisely so
        store state stays re-addressable); the one lossy case is a
        hash whose high 32 bits were zero (fingerprints() coerces the
        tag to 1, ~2^-32 per key). `is_over` carries the
        FLAG_STICKY_OVER bit ONLY — an exhausted-but-not-sticky window
        must reinstall as exactly that (a sticky bit added in transit
        would flip its peek answers from UNDER to OVER).

        r19 widened the export from token-only to flag-aware: the raw
        `duration` (L_DURATION), `ts` (L_TS: the leaky leak clock /
        sliding previous-subwindow count) and `flags` (L_FLAGS: the
        algo bits + sticky) lanes ride along, so leaky, sliding-window
        and GCRA entries — and chain-level rows, which are ordinary
        token rows keyed by level — round-trip byte-exact through
        install_windows under ANY ShardingPolicy ("restore is also a
        re-partition"). `reset_time` is the L_EXPIRE lane in unix-ms
        whatever the algorithm encodes there (expiry, window anchor,
        or GCRA theoretical-arrival time); the same engine-clock
        conversion inverts it on install. Non-mutating; submit-thread
        contract like snapshot_read."""
        from gubernator_tpu.core.store import (
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
        )

        empty = dict(
            key_hash=np.empty(0, np.uint64),
            limit=np.empty(0, np.int64),
            remaining=np.empty(0, np.int64),
            reset_time=np.empty(0, np.int64),
            is_over=np.empty(0, bool),
            duration=np.empty(0, np.int64),
            ts=np.empty(0, np.int64),
            flags=np.empty(0, np.int64),
        )
        if self.clock.epoch is None:
            return empty  # nothing ever decided
        if now is None:
            now = api_types.millisecond_now()
        e_now = int(self.clock.to_engine(now))
        ent = np.asarray(jax.device_get(self.store.data)).reshape(
            -1, LANES
        )
        live = (ent[:, L_TAG] != 0) & (ent[:, L_EXPIRE] >= e_now)
        ent = ent[live]
        if not ent.shape[0]:
            return empty
        hi = ent[:, L_TAG].astype(np.int64).view(np.uint64) & np.uint64(
            0xFFFFFFFF
        )
        lo = ent[:, L_KEYLOW].astype(np.int64).view(
            np.uint64
        ) & np.uint64(0xFFFFFFFF)
        return dict(
            key_hash=(hi << np.uint64(32)) | lo,
            limit=ent[:, L_LIMIT].astype(np.int64),
            remaining=ent[:, L_REMAINING].astype(np.int64),
            reset_time=np.asarray(
                self.clock.from_engine(ent[:, L_EXPIRE]), np.int64
            ),
            is_over=(ent[:, L_FLAGS] & FLAG_STICKY_OVER) != 0,
            duration=ent[:, L_DURATION].astype(np.int64),
            ts=ent[:, L_TS].astype(np.int64),
            flags=ent[:, L_FLAGS].astype(np.int64),
        )

    def repartition(
        self, policy: ShardingPolicy, now: Optional[int] = None
    ) -> "PartitionedEngine":
        """A NEW engine under `policy` carrying every live window of
        this one: export_windows -> install_windows under the new
        ShardingPolicy — the store re-partition path a GUBER_SHARDS
        change drives (serve/backends.py MeshBackend.repartition), and
        since r19 also the checkpoint-restore-across-a-shard-change
        path ("restore is also a re-partition"). The full-lane
        round-trip carries every algorithm's state (token, leaky,
        sliding, GCRA, chain-level rows) byte-exact. Same geometry/
        ladder/sketch config; sketch-tier counts do NOT migrate
        (window-keyed, transient — the loss direction is a one-window
        over-admission in the cold tier, same as a store reset, and
        the hot exact tier moves losslessly). Call with the batcher
        idle or on its serialized submit thread; warm the new engine
        before serving."""
        if now is None:
            now = api_types.millisecond_now()
        eng = PartitionedEngine(
            self.config,
            policy=policy,
            buckets=self.buckets,
            sketch=self.sketch_config,
        )
        w = self.export_windows(now)
        if w["key_hash"].shape[0]:
            eng.install_windows(
                w["key_hash"], w["limit"], w["remaining"],
                w["reset_time"], w["is_over"], now=now,
                duration=w["duration"], ts=w["ts"], flags=w["flags"],
            )
        return eng

    # -- GLOBAL install / sync ----------------------------------------------

    def _upsert_padded(self, hashes, lim, rem, reset, over, valid):
        """One padded replica-install call against this policy's
        layout: flat = the donated single-store upsert jit; mesh = the
        owner-masked shard_map upsert collective."""
        self.store = self._install_program()(
            self.store, hashes, lim, rem, reset, over, valid
        )

    def _install_program(self):
        return upsert_globals_jit if self.flat else self._upsert

    def _upsert_full_padded(self, hashes, lim, rem, reset, dur, ts,
                            flags, valid):
        """One padded full-lane install call (r19): the flag-aware twin
        of _upsert_padded, carrying duration/ts/flags through to the
        store so any algorithm's entry reinstalls byte-exact."""
        if self.flat:
            self.store = upsert_windows_jit(
                self.store, hashes, lim, rem, reset, dur, ts, flags,
                valid,
            )
        else:
            self.store = self._upsert_full(
                self.store, hashes, lim, rem, reset, dur, ts, flags,
                valid,
            )

    def install_windows(
        self,
        key_hash: np.ndarray,
        limit: np.ndarray,
        remaining: np.ndarray,
        reset_time: np.ndarray,
        is_over: np.ndarray,
        now: Optional[int] = None,
        duration: Optional[np.ndarray] = None,
        ts: Optional[np.ndarray] = None,
        flags: Optional[np.ndarray] = None,
    ) -> None:
        """Install windows for pre-hashed keys — the array-level
        GLOBAL replica install (UpdatePeerGlobals receive path) and the
        sketch promoter's migration surface. Batches larger than the
        bucket ladder's top rung are CHUNKED (installs are per-key
        upserts; chunk order preserves last-wins for duplicates), so
        callers never hit a choose_bucket refusal.

        Without the optional lanes the install is the historical
        token-replica form (zero duration/ts, sticky-only flags). With
        `duration`/`ts`/`flags` (r19: export_windows round-trip), the
        raw lanes land verbatim, so leaky/sliding/GCRA entries — and
        sticky bits — survive a restore or re-partition byte-exact;
        `is_over` is then ignored (the sticky bit lives in `flags`)."""
        kh = np.ascontiguousarray(key_hash, np.uint64)
        n = int(kh.shape[0])
        if n == 0:
            return
        if now is None:
            now = api_types.millisecond_now()
        self._engine_now(now)  # pin/refresh the epoch
        top = max(self.buckets)
        limit = np.asarray(limit)
        remaining = np.asarray(remaining)
        reset_time = np.asarray(reset_time)
        full = flags is not None
        if full:
            duration = np.asarray(duration)
            ts = (
                np.zeros(n, np.int64) if ts is None else np.asarray(ts)
            )
            flags = np.asarray(flags)
        else:
            is_over = np.asarray(is_over, bool)
        for s in range(0, n, top):
            e = min(s + top, n)
            if full:
                hashes, lim, rem, reset, dur, tss, flg, valid = (
                    pad_to_bucket(
                        self.buckets,
                        e - s,
                        (kh[s:e], np.uint64),
                        (_sat_i32(limit[s:e]), np.int32),
                        (_sat_i32(remaining[s:e]), np.int32),
                        (self.clock.to_engine(reset_time[s:e]),
                         np.int32),
                        (_sat_i32(duration[s:e]), np.int32),
                        (_sat_i32(ts[s:e]), np.int32),
                        (_sat_i32(flags[s:e]), np.int32),
                    )
                )
                self._upsert_full_padded(
                    hashes, lim, rem, reset, dur, tss, flg, valid
                )
                continue
            hashes, lim, rem, reset, over, valid = pad_to_bucket(
                self.buckets,
                e - s,
                (kh[s:e], np.uint64),
                (_sat_i32(limit[s:e]), np.int32),
                (_sat_i32(remaining[s:e]), np.int32),
                (self.clock.to_engine(reset_time[s:e]), np.int32),
                (is_over[s:e], bool),
            )
            self._upsert_padded(hashes, lim, rem, reset, over, valid)

    def update_globals(self, *args, now: Optional[int] = None, **kw):
        """Install owner-broadcast GLOBAL statuses (UpdatePeerGlobals
        receive path). Two call forms, ONE install path (both funnel
        into install_windows, so the replica-install semantics cannot
        drift between the serving tiers):

        - object form: update_globals([(key, RateLimitResp), ...])
        - array form:  update_globals(key_hash=..., limit=...,
          remaining=..., reset_time=..., is_over=...) — positional
          ndarrays accepted for the historical MeshEngine signature.
        """
        updates_kw = kw.pop("updates", None)
        if updates_kw is not None:
            if args or kw:
                raise TypeError(
                    "update_globals(updates=...) excludes other args"
                )
            args = (updates_kw,)
        if kw or len(args) > 1 or (
            args and isinstance(args[0], np.ndarray)
        ):
            names = ("key_hash", "limit", "remaining", "reset_time",
                     "is_over")
            vals = dict(zip(names, args))
            vals.update(kw)
            return self.install_windows(
                vals["key_hash"], vals["limit"], vals["remaining"],
                vals["reset_time"], vals["is_over"], now=now,
            )
        from gubernator_tpu.api.types import Status
        from gubernator_tpu.core.hashing import slot_hash_batch

        updates = list(args[0]) if args else []
        n = len(updates)
        if n == 0:
            return
        return self.install_windows(
            slot_hash_batch([k for k, _ in updates]),
            np.fromiter((s.limit for _, s in updates), np.int64, n),
            np.fromiter((s.remaining for _, s in updates), np.int64, n),
            np.fromiter((s.reset_time for _, s in updates), np.int64, n),
            np.fromiter(
                (s.status == Status.OVER_LIMIT for _, s in updates),
                bool, n,
            ),
            now=now,
        )

    def _sync_call(self, key_hash, hits, limit, duration, algo, e_now):
        """(program, arguments, pad order) of one mesh sync
        collective: the keys padded and sorted to their host rung."""
        n = key_hash.shape[0]
        req, order = pad_request_sorted(
            extend_ladder(self.buckets, n),
            self.config.slots,
            key_hash,
            hits,
            limit,
            duration,
            algo,
            np.zeros(n, bool),
        )
        args = (
            self.store, req.key_hash, req.hits, req.limit, req.duration,
            req.algo, req.valid, e_now,
        )
        return self._sync, args, order

    def _sync_padded(self, key_hash, hits, limit, duration, algo, now):
        """One padded owner-charge + psum-replicate + replica-install
        collective step; returns the padded sorted-order responses and
        the pad order. Flat degenerate case: the owner leg IS the whole
        mesh, so the same semantics are one local decide (identical
        kernel; the replica-install leg is empty)."""
        n = key_hash.shape[0]
        if algo is None:
            algo = np.zeros(n, np.int32)
        e_now = self._engine_now(now)
        if self.flat:
            # gossip traffic must not heat the promoter's top-K or
            # count as decide batches in EngineStats — the mesh
            # branch's collective records neither, and the two
            # policies may not drift (runs on the serialized submit
            # thread, so the swap-out is not racy)
            hook, self.observe_hook = self.observe_hook, None
            stats, self.stats = self.stats, EngineStats()
            try:
                # sync batches are gossip accumulations with no upper
                # bound; the flat ladder tops out at max(buckets), so
                # chunk (like install_windows) rather than refuse —
                # the mesh branch handles the same overflow by
                # extending its ladder
                top = max(self.buckets)
                if n <= top:
                    h = self.decide_submit(
                        key_hash, hits, limit, duration, algo,
                        np.zeros(n, bool), now,
                    )
                    return self.decide_wait(h), None
                cols = ([], [], [], [])
                for s in range(0, n, top):
                    e = min(s + top, n)
                    h = self.decide_submit(
                        key_hash[s:e], hits[s:e], limit[s:e],
                        duration[s:e], algo[s:e],
                        np.zeros(e - s, bool), now,
                    )
                    for c, v in zip(cols, self.decide_wait(h)):
                        c.append(v)
                return tuple(np.concatenate(c) for c in cols), None
            finally:
                self.observe_hook = hook
                self.stats = stats
        if n > max(self.buckets):
            _warn_ladder_overflow(max(self.buckets), n)
        fn, args, order = self._sync_call(
            key_hash, hits, limit, duration, algo, e_now
        )
        self.store, resp = fn(*args)
        return resp, order

    def sync_globals(
        self,
        key_hash: np.ndarray,
        limit: np.ndarray,
        duration: np.ndarray,
        now: int,
        algo: Optional[np.ndarray] = None,
    ) -> None:
        """One collective gossip step for the given GLOBAL keys: owner
        peeks authoritative status (hits=0), a psum replicates it
        mesh-wide, every non-owner installs replica entries. `algo`
        must carry each key's algorithm (defaults to token bucket)."""
        n = key_hash.shape[0]
        if n == 0:
            return
        self._sync_padded(
            key_hash, np.zeros(n, np.int64), limit, duration, algo, now
        )

    def apply_global_hits(
        self,
        key_hash: np.ndarray,
        hits: np.ndarray,
        limit: np.ndarray,
        duration: np.ndarray,
        now: int,
        algo: Optional[np.ndarray] = None,
    ):
        """In-mesh GLOBAL hit aggregation (r14 prototype, the SNIPPETS
        brief's psum): charge each key's aggregated GLOBAL hits on its
        OWNER shard and replicate the post-charge status to every other
        shard in ONE collective step — the owner->replica gossip loop
        (queue hits -> owner applies -> broadcast -> replicas install)
        collapsed into a single device program when the "peers" are
        shards of one mesh. Returns (status, limit, remaining,
        reset_time_unix) per key in caller order — the authoritative
        post-charge windows, ready for a cross-NODE broadcast when the
        mesh is one node of a wider ring."""
        n = key_hash.shape[0]
        if n == 0:
            z = np.empty(0, np.int64)
            return z, z, z, z
        resp, order = self._sync_padded(
            key_hash, hits, limit, duration, algo, now
        )
        if order is None:  # flat: decide_wait already unpermuted
            return resp
        epoch = self.clock.epoch

        def unpad(a):
            a = np.asarray(a)
            out = np.empty(a.shape[0], a.dtype)
            out[order] = a
            return out[:n]

        status = unpad(resp.status)
        rlimit = unpad(resp.limit)
        remaining = unpad(resp.remaining)
        r = unpad(resp.reset_time).astype(np.int64)
        reset = np.where(r == 0, 0, r + epoch)
        return status, rlimit, remaining, reset

    # -- sketch cold tier (r13, sharded r14) --------------------------------

    def _sketch_windows(self, durations: np.ndarray, now: int):
        """(window_id int64[n], window_end_unix int64[n]) for the
        current fixed windows of these durations."""
        from gubernator_tpu.core.sketches import window_id_np

        e_now = int(self.clock.to_engine(now))
        wid = window_id_np(e_now, durations)
        d = np.maximum(np.asarray(durations, np.int64), 1)
        wend_engine = (wid + 1) * d
        return wid, np.asarray(self.clock.from_engine(wend_engine))

    def _sketch_min_call(self, kh: np.ndarray, idx: np.ndarray):
        """(program, arguments) of the count-min row-min read, by
        layout like `_rows_call`."""
        idx = jnp.asarray(idx)
        if self.flat:
            return _sketch_min_flat, (self.sketch.data, idx)
        owner = jnp.asarray(owner_of_np(kh, self.n))
        fn = (
            self._sketch_min_coll
            if self.policy.spans_processes
            else _sketch_min_sharded
        )
        return fn, (self.sketch.data, owner, idx)

    def sketch_estimates(
        self,
        key_hash: np.ndarray,
        durations: np.ndarray,
        now: Optional[int] = None,
    ) -> np.ndarray:
        """NON-MUTATING current-window count-min estimates int64[n]
        for these keys (0 when the tier is off or nothing was ever
        decided), read from each key's OWNING shard's sub-sketch —
        the same addressing the decide kernel charges, so host and
        device views cannot drift. Narrow gathers only; submit-thread
        contract like snapshot_read."""
        n = int(key_hash.shape[0])
        if self.sketch is None or self.clock.epoch is None or n == 0:
            return np.zeros(n, np.int64)
        if now is None:
            now = api_types.millisecond_now()
        from gubernator_tpu.core.sketches import sketch_indices_np

        kh, dur, _n = self._pad_keys_pow2(
            np.ascontiguousarray(key_hash, np.uint64),
            np.asarray(durations, np.int64),
        )
        wid, _ = self._sketch_windows(dur, now)
        idx = sketch_indices_np(kh, wid, self.sketch_config)
        fn, args = self._sketch_min_call(kh, idx)
        est = fn(*args)
        return np.asarray(est, np.int64)[:n]

    def promote_from_sketch(
        self,
        key_hash: np.ndarray,
        limits: np.ndarray,
        durations: np.ndarray,
        now: Optional[int] = None,
    ):
        """Migrate hot sketch-tier keys into exact buckets: read each
        key's current-window estimate (an all-shards gather on the
        mesh) and install a token window with remaining = max(limit -
        estimate, 0) and reset = the window's end on the key's owning
        shard — the key then decides exactly for the rest of the
        window and re-creates exactly in the next one. Keys already
        holding a LIVE exact entry are skipped (their state is
        authoritative). Returns (installed bool[n], estimate int64[n],
        reset_unix int64[n], over bool[n]). Thread contract: submit-
        thread only (DeviceBatcher.run_serialized) — this reads AND
        upserts the store."""
        n = int(key_hash.shape[0])
        if n == 0 or self.sketch is None:
            z = np.zeros(n, np.int64)
            return np.zeros(n, bool), z, z, np.zeros(n, bool)
        if now is None:
            now = api_types.millisecond_now()
        self._engine_now(now)  # pin the epoch before window math
        kh = np.ascontiguousarray(key_hash, np.uint64)
        limits = np.asarray(limits, np.int64)
        est = self.sketch_estimates(kh, durations, now)
        _, reset_unix = self._sketch_windows(durations, now)
        over = est >= limits
        remaining = np.maximum(limits - est, 0)
        todo = ~self.live_mask(kh, now)
        if todo.any():
            self.install_windows(
                kh[todo], limits[todo], remaining[todo],
                reset_unix[todo], over[todo], now,
            )
        return todo, est, reset_unix, over

    # -- warmup --------------------------------------------------------------

    def _warmup_sketch_reads(self, now: int) -> None:
        """Compile the promoter's host-read surfaces at their pow2
        rungs so the first flush ticks don't pay eager compiles on the
        serving submit thread."""
        if self.sketch is None:
            return
        for B in SKETCH_READ_RUNGS:
            kh = np.arange(1, B + 1, dtype=np.uint64) << np.uint64(32)
            durs = np.full(B, 1000, np.int64)
            self.sketch_estimates(kh, durs, now)
            self.live_mask(kh, now)

    def compile_ahead(self) -> None:
        """Lower and compile SIDE BY SIDE every program the warm-up
        traffic (`warmup_public`) is about to call: one decide program
        per (rung, group rung) — one to four minutes of TPU compile
        each on a cold cache, one at a time 14 to 17 minutes of a boot
        (CHANGES.md, PR 21) — the mesh's sync collective and the
        GLOBAL install per host rung, and the promoter's host reads.
        XLA compiles with the GIL released, so a thread a program, as
        many at once as the host has cores, the slowest (largest group
        rung) first. Each is lowered from the very (program, arguments)
        the serving call makes (`_decide_call`, `_sync_call`, ...),
        state arrays included — lowering reads their shapes and
        shardings and donates nothing — so the traffic that follows
        finds the same executables, in this process's jit cache or in
        the persistent compile cache, and compiles nothing."""
        from gubernator_tpu.core.sketches import sketch_indices_np

        e_now = np.int32(1)
        jobs = []
        for batch in warmup_batches(self):
            if self.flat:
                req, _, groups = pad_request_sorted(
                    self.buckets, self.config.slots, **batch,
                    with_groups=True,
                )
            else:
                req, _, _, groups = pad_request_sharded(
                    self.sub_buckets, self.config.slots, self.n,
                    **batch, with_groups=True,
                )
            jobs.append(self._decide_call(req, groups, e_now))
        # compile time follows the group rung (the last argument)
        jobs.sort(key=lambda job: -job[1][-1])
        for b in self.buckets:
            k = np.arange(1, b + 1, dtype=np.uint64)
            ones = np.ones(b, np.int64)
            if not self.flat:  # flat: the sync IS a decide, above
                jobs.append(
                    self._sync_call(
                        k, ones * 0, ones, ones * 1000,
                        np.zeros(b, np.int32), e_now,
                    )[:2]
                )
            z = np.zeros(b, np.int32)
            cols = pad_to_bucket(
                self.buckets, b, (k, np.uint64), (z, np.int32),
                (z, np.int32), (z, np.int32), (np.zeros(b, bool), bool),
            )
            jobs.append((self._install_program(), (self.store, *cols)))
        if self.sketch is not None:
            for B in SKETCH_READ_RUNGS:
                kh = np.arange(1, B + 1, dtype=np.uint64) << np.uint64(32)
                idx = sketch_indices_np(
                    kh, np.zeros(B, np.int64), self.sketch_config
                )
                jobs.append(self._sketch_min_call(kh, idx))
                jobs.append(self._rows_call(kh))
        workers = min(len(jobs), len(os.sched_getaffinity(0)))
        t = time.monotonic()
        with concurrent.futures.ThreadPoolExecutor(
            workers, thread_name_prefix="guber-compile"
        ) as pool:
            for done in [
                pool.submit(lambda fn, args: fn.lower(*args).compile(),
                            fn, args)
                for fn, args in jobs
            ]:
                done.result()
        took = time.monotonic() - t
        _trim_heap()
        _log.info(
            "warm-up: %d programs compiled side by side on %d threads "
            "in %.1f s, heap trimmed in %.1f s", len(jobs), workers, took,
            time.monotonic() - t - took,
        )

    def warmup(self, now: Optional[int] = None) -> None:
        """Pre-compile every (batch rung, group rung) decide program
        plus the GLOBAL install/sync programs and the promoter's reads
        — none of it may land inside a serving RPC deadline — then
        reset the state the warm-up traffic dirtied. One body for
        every layout: `warmup_public`. NOTE: this drives the engine's
        own methods — the multihost lockstep wrapper runs the same body
        through its broadcasting public surface
        (parallel/multihost.py)."""
        warmup_public(self, now)


def _trim_heap() -> None:
    """Hand the allocator's free memory back to the OS (glibc's
    malloc_trim; a no-op elsewhere). A dozen threads that each loaded
    or compiled a program leave as many malloc arenas full of freed
    blocks, and the first burst of served traffic then paid for their
    consolidation with the GIL held: every gRPC call of the first five
    seconds waited up to a second, and `call_p50_ms` read +12% (chip
    runs, PR 26: with the trim 16.7 ms, without 20.7, one compile
    thread 17.3). The boot pays it instead."""
    try:
        ctypes.CDLL(None).malloc_trim(0)
    except (OSError, AttributeError):
        pass


#: pow2 batch sizes the promoter's host reads are warmed at
SKETCH_READ_RUNGS = (64, 128, 256, 512, 1024)


def warmup_batches(engine) -> list:
    """One crafted batch per decide program, i.e. per (rung, group
    rung) pair, as decide_arrays' keywords less `now`. Flat: rung b with
    g unique keys of distinct FINGERPRINTS (value << 32). Mesh: every
    shard draws sub-rung r with g unique keys of its own (g == r is the
    all-unique case), so every shard hits every rung."""
    from gubernator_tpu.core.engine import group_rungs

    if engine.flat:
        keys = [
            np.resize(
                np.arange(1, g + 1, dtype=np.uint64) << np.uint64(32), b
            )
            for b in engine.buckets
            for g in group_rungs(b)
        ]
    else:
        n = engine.n
        rungs = engine.sub_buckets
        rng = np.random.default_rng(0xB007)
        pool = rng.integers(
            1, 2**63, 4 * n * max(rungs), np.int64
        ).astype(np.uint64)
        owners = owner_of_np(pool, n)
        per_shard = [pool[owners == s] for s in range(n)]
        keys = [
            np.concatenate([np.resize(p[:g], r) for p in per_shard])
            for r in rungs
            for g in group_rungs(r)
        ]
    out = []
    for k in keys:
        ones = np.ones(k.shape[0], np.int64)
        out.append(dict(
            key_hash=k, hits=ones, limit=ones * 10, duration=ones * 1000,
            algo=np.zeros(k.shape[0], np.int32),
            gnp=np.zeros(k.shape[0], bool),
        ))
    return out


def warmup_public(engine, now: Optional[int] = None) -> None:
    """THE warm-up, for every layout, through an engine-like object's
    PUBLIC surface (compile_ahead / decide_arrays / update_globals /
    sync_globals / reset): first every program compiles side by side
    (`PartitionedEngine.compile_ahead`), then one call of each runs —
    a decide per (rung, group rung), the GLOBAL install and the gossip
    collective per host rung, the promoter's reads — and finds it
    compiled; then state and counters are reset. Driving only the
    public surface is what makes it lockstep-safe for the multihost
    wrapper — every call broadcasts, so followers compile and replay
    the identical sequence. The ONE warm-up body for PartitionedEngine
    and the serving MeshBackend/MultiHostBackend (serve/backends.py),
    so the compile coverage cannot drift between the library and
    serving tiers."""
    if now is None:
        now = api_types.millisecond_now()
    engine.compile_ahead()
    for batch in warmup_batches(engine):
        engine.decide_arrays(now=now, **batch)
    # broadcast-receive + gossip collective programs per host rung:
    # neither may pay jit time inside a broadcast RPC deadline
    for b in engine.buckets:
        k = np.arange(1, b + 1, dtype=np.uint64)
        ones = np.ones(b, np.int64)
        engine.update_globals(
            key_hash=k,
            limit=ones,
            remaining=ones,
            reset_time=ones * now,
            is_over=np.zeros(b, bool),
            now=now,
        )
        engine.sync_globals(k, ones, ones * 1000, now=now)
    if getattr(engine, "sketch", None) is not None:
        engine._warmup_sketch_reads(now)
    # clear state and counters dirtied by warmup traffic (the stats
    # object is shared through the multihost wrapper's property, so
    # mutate in place rather than rebinding)
    engine.reset()
    engine.stats.__init__()


# narrow jitted gathers shared by the host-side state reads: jit keeps
# sharded-array indexing off the eager path (whole-array materialization)
# and makes the per-shape compile explicit (warmup pre-pays the pow2
# rungs the promoter/replication loops use)
@jax.jit
def _rows_flat(data, b):
    return jnp.take(data, b, axis=0)


@jax.jit
def _rows_sharded(data, owner, b):
    return data[owner, b]


@jax.jit
def _sketch_min_flat(data, idx):
    est = None
    for r in range(idx.shape[0]):
        c = jnp.take(data[r], idx[r])
        est = c if est is None else jnp.minimum(est, c)
    return est


@jax.jit
def _sketch_min_sharded(data, owner, idx):
    est = None
    for r in range(idx.shape[0]):
        c = data[owner, r, idx[r]]
        est = c if est is None else jnp.minimum(est, c)
    return est


class TpuEngine(PartitionedEngine):
    """Single-device engine: PartitionedEngine under the degenerate
    flat policy (one shard, no mesh, plain-jit dispatch). The
    historical name and constructor, kept because "one chip" remains
    the most common deployment; every code path is the shared
    partitioned implementation."""

    def __init__(
        self,
        config: StoreConfig = StoreConfig(),
        buckets: Sequence[int] = (64, 256, 1024, 4096),
        device: Optional[jax.Device] = None,
        sketch=None,
    ):
        super().__init__(
            config,
            policy=ShardingPolicy.single(device),
            buckets=buckets,
            sketch=sketch,
        )


class MeshEngine(PartitionedEngine):
    """Mesh-sharded engine: PartitionedEngine over a device mesh
    (key-space sharding with collective GLOBAL sync). The historical
    name and constructor; see PartitionedEngine for the shared
    implementation."""

    def __init__(
        self,
        config: StoreConfig = StoreConfig(),
        devices: Optional[Sequence[jax.Device]] = None,
        buckets: Sequence[int] = (64, 256, 1024, 4096),
        mesh_shape: Optional[Tuple[int, int]] = None,
        sketch=None,
    ):
        super().__init__(
            config,
            policy=ShardingPolicy.over_mesh(devices, mesh_shape),
            buckets=buckets,
            sketch=sketch,
        )
