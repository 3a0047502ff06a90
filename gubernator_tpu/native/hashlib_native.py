"""ctypes bindings for libguberhash.so (see guberhash.cc).

Importing this module either binds every symbol below or raises
ImportError: the package reaches it through core/hashing.native_lib()
alone, which turns that error into "absent" once, for everybody."""

from __future__ import annotations

import ctypes
import pathlib
import threading
from typing import List

import numpy as np

_SO = pathlib.Path(__file__).resolve().parent / "libguberhash.so"
_REBUILD = "make -C gubernator_tpu/native"
if not _SO.exists():
    raise ImportError(f"native library not built: {_SO} ({_REBUILD})")

_lib = ctypes.CDLL(str(_SO))

_i32p = ctypes.POINTER(ctypes.c_int32)
_i64p = ctypes.POINTER(ctypes.c_int64)
_u64p = ctypes.POINTER(ctypes.c_uint64)
_u8p = ctypes.POINTER(ctypes.c_uint8)
_vpp = ctypes.POINTER(ctypes.c_void_p)
_vp = ctypes.c_void_p
_i64 = ctypes.c_int64

# Every symbol this module calls, declared here and nowhere else. The
# library is whole or absent: one built from another guberhash.cc than
# this tree's lacks a name, ctypes raises AttributeError on it, and the
# import fails — to every caller (core/hashing.native_lib) the same as
# a library that was never built.
try:
    _lib.guber_hash_batch.argtypes = [
        ctypes.c_char_p, _i64p, _i64, ctypes.c_uint64, _u64p,
    ]
    _lib.guber_crc32_batch.argtypes = [
        ctypes.c_char_p, _i64p, _i64, ctypes.POINTER(ctypes.c_uint32),
    ]
    _lib.guber_presort.argtypes = [_u64p, _i64, ctypes.c_uint64, _i32p]
    _lib.guber_presort_grouped.argtypes = [
        _u64p, _i64, ctypes.c_uint64, _i32p, _i32p, _i32p, _i64p,
    ]
    _lib.guber_presort_sharded.argtypes = [
        _u64p, _i64, ctypes.c_uint64, ctypes.c_uint64, _i32p, _i64p,
    ]
    _lib.guber_presort_sharded_grouped.argtypes = [
        _u64p, _i64, ctypes.c_uint64, ctypes.c_uint64, _i32p, _i64p,
        _i32p, _i32p, _i64p,
    ]
    # one-pass gather + clip + pad marshalling
    _lib.guber_gather_pad_i64_clip.argtypes = [
        _i64p, _i32p, _i64, _i64, _i64, _i64, _i32p,
    ]
    _lib.guber_gather_pad_i32.argtypes = [_i32p, _i32p, _i64, _i64, _i32p]
    _lib.guber_gather_pad_u64.argtypes = [_u64p, _i32p, _i64, _i64, _u64p]
    _lib.guber_gather_pad_u8.argtypes = [_u8p, _i32p, _i64, _i64, _u8p]
    _lib.guber_unpermute_i32.argtypes = [
        _i32p, _i32p, _i64, _i64, _i64, _i32p,
    ]
    # fused batch prep, arrival-time runs and their merges
    _lib.guber_prep_sharded.restype = _i64
    _lib.guber_prep_sharded.argtypes = [
        _u64p, _i64p, _i64p, _i64p, _i32p, _u8p,          # inputs
        _i64, ctypes.c_uint64, _i64,                      # n, buckets, ns
        _i64p, _i64, _i64,                   # rungs, n_rungs, g_override
        _i64, _i64, _i64, _i64,                           # clips
        _i32p, _i64p, _i64p,                      # order, counts, picked
        _u64p, _i32p, _i32p, _i32p, _i32p, _u8p, _u8p,    # fields
        _u64p, _i32p, _i32p, _u8p, _i32p,                 # groups
        _i64p,                                            # take_idx
    ]
    _lib.guber_prep_threads.restype = _i64
    _lib.guber_unflatten_resp.argtypes = [
        _i32p, _i32p, _i64p, _i64, _i64, _i64, _i64, _i32p,
    ]
    _lib.guber_prep_run.restype = _i64
    _lib.guber_prep_run.argtypes = [
        _u64p, _i64p, _i64p, _i64p, _i32p, _u8p,
        _i64, ctypes.c_uint64, _i64, _i64, _i64, _i64, _i64,
        _i32p, _i64p, _u64p, _u64p, _i32p, _i32p, _i32p, _i32p, _u8p,
    ]
    _lib.guber_merge_runs.restype = _i64
    _lib.guber_merge_runs.argtypes = [
        _vpp, _vpp, _vpp, _vpp, _vpp, _vpp, _vpp, _vpp,
        _i64p, _i64p, _i64, _i64,
        _i64p, _i64,
        _u64p, _i32p, _u64p, _i32p, _i32p, _i32p, _i32p, _u8p, _u8p,
        _i32p, _i32p, _u64p, _i32p, _u8p, _i64p, _i64p,
    ]
    _lib.guber_merge_runs_sharded.restype = _i64
    _lib.guber_merge_runs_sharded.argtypes = (
        [_vpp] * 8 + [_i64p, _i64p] + [_i64] * 3 + [_i64p, _i64]
        + [_vp] * 16
    )
    # the PeersV1 door's wire fold, the GEB door's string-frame parse,
    # its split by owner and the forwarder's column RPC
    _lib.guber_parse_peer_batch.restype = _i64
    _lib.guber_parse_peer_batch.argtypes = [
        ctypes.c_char_p, _i64, _i64, ctypes.c_uint64,
    ] + [_vp] * 10
    _lib.guber_encode_peer_answers.restype = _i64
    _lib.guber_encode_peer_answers.argtypes = [_vp] * 4 + [_i64, _vp]
    _lib.guber_peer_answer_max_bytes.restype = _i64
    _lib.guber_parse_string_frame.restype = _i64
    _lib.guber_parse_string_frame.argtypes = [
        ctypes.c_char_p, _i64, _i64, ctypes.c_uint64,
    ] + [_vp] * 12
    _lib.guber_ring_owners.restype = _i64
    _lib.guber_ring_owners.argtypes = [
        ctypes.c_char_p, _i64, _i64, _vp, _i64, _vp,
    ]
    _lib.guber_encode_peer_batch.restype = _i64
    _lib.guber_encode_peer_batch.argtypes = (
        [ctypes.c_char_p] + [_vp] * 10 + [_i64, _vp, _i64]
    )
    _lib.guber_parse_peer_answers.restype = _i64
    _lib.guber_parse_peer_answers.argtypes = [
        ctypes.c_char_p, _i64, _i64,
    ] + [_vp] * 4
    _lib.guber_encode_string_answers.restype = _i64
    _lib.guber_encode_string_answers.argtypes = [_vp] * 6 + [
        ctypes.c_char_p, _vp, _i64, _i64, _vp, _i64,
    ]
    # the traffic observers' per-batch fold
    _lib.guber_hotkeys_new.restype = _vp
    _lib.guber_hotkeys_new.argtypes = [_i64]
    _lib.guber_hotkeys_free.restype = None
    _lib.guber_hotkeys_free.argtypes = [_vp]
    _lib.guber_hotkeys_reset.restype = None
    _lib.guber_hotkeys_reset.argtypes = [_vp]
    _lib.guber_traffic_fold.restype = _i64
    _lib.guber_traffic_fold.argtypes = [
        _vp, _vp, _i64, ctypes.c_char_p, _i64, _vp, _vp, _i64,
    ]
    _lib.guber_hotkeys_size.restype = _i64
    _lib.guber_hotkeys_size.argtypes = [_vp] * 3
    _lib.guber_hotkeys_export.restype = _i64
    _lib.guber_hotkeys_export.argtypes = [_vp] * 5
    # the shed cache's array consult and population
    _lib.guber_shed_screen.restype = _i64
    _lib.guber_shed_screen.argtypes = (
        [_vp] * 6 + [_i64, _i64]                  # the frame, n, now
        + [_vp, _vp, _i64] * 2 + [_vp] * 4        # index, overlay, slots
        + [_vp] * 4 + [_i64p]                     # outputs, eligible
    )
    _lib.guber_shed_observe.restype = _i64
    _lib.guber_shed_observe.argtypes = (
        [_vp, _vp, _i64] + [_vp, _i64] * 4        # key_hash, algo, n, results
        + [_vp, _vp, _i64] * 2 + [_vp, _vp, _i64]  # index, overlay, slots
        + [_vp, _vp, _i64] + [_vp] * 4            # walk, keep, answers
    )
except AttributeError as e:
    raise ImportError(
        f"native library not built from this tree's guberhash.cc: {e} "
        f"({_REBUILD})"
    ) from None

_PEER_ANSWER_MAX = int(_lib.guber_peer_answer_max_bytes())
hotkeys_free = _lib.guber_hotkeys_free


def _ptr(a, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def gather_pad_i64_clip(src, order, b: int, lo: int, hi: int) -> np.ndarray:
    """int32[b] = clip(src[order], lo, hi) padded with its last value."""
    src = np.ascontiguousarray(src, np.int64)
    out = np.empty(b, np.int32)
    _lib.guber_gather_pad_i64_clip(
        _ptr(src, ctypes.c_int64), _ptr(order, ctypes.c_int32),
        src.shape[0], b, lo, hi, _ptr(out, ctypes.c_int32),
    )
    return out


def gather_pad_i32(src, order, b: int) -> np.ndarray:
    src = np.ascontiguousarray(src, np.int32)
    out = np.empty(b, np.int32)
    _lib.guber_gather_pad_i32(
        _ptr(src, ctypes.c_int32), _ptr(order, ctypes.c_int32),
        src.shape[0], b, _ptr(out, ctypes.c_int32),
    )
    return out


def gather_pad_u64(src, order, b: int) -> np.ndarray:
    src = np.ascontiguousarray(src, np.uint64)
    out = np.empty(b, np.uint64)
    _lib.guber_gather_pad_u64(
        _ptr(src, ctypes.c_uint64), _ptr(order, ctypes.c_int32),
        src.shape[0], b, _ptr(out, ctypes.c_uint64),
    )
    return out


def gather_pad_u8(src, order, b: int) -> np.ndarray:
    src = np.ascontiguousarray(src, np.uint8)
    out = np.empty(b, np.uint8)
    _lib.guber_gather_pad_u8(
        _ptr(src, ctypes.c_uint8), _ptr(order, ctypes.c_int32),
        src.shape[0], b, _ptr(out, ctypes.c_uint8),
    )
    return out


def unpermute_i32(sorted_stack: np.ndarray, order: np.ndarray,
                  n: int) -> np.ndarray:
    """[k, b] row-major response stack -> out[:, order[:n]] scatter:
    out[a, order[i]] = sorted[a, i] for i < n (padding rows untouched)."""
    sorted_stack = np.ascontiguousarray(sorted_stack, np.int32)
    k, b = sorted_stack.shape
    out = np.empty((k, b), np.int32)
    _lib.guber_unpermute_i32(
        _ptr(sorted_stack, ctypes.c_int32), _ptr(order, ctypes.c_int32),
        n, b, k, _ptr(out, ctypes.c_int32),
    )
    return out


def presort_grouped(key_hash: np.ndarray, buckets: int):
    """(order int32[n], group_id int32[n], leader_pos int32[n], G) —
    the presort permutation plus the duplicate-key group structure of
    the sorted stream (only leader_pos[:G] is meaningful)."""
    kh = np.ascontiguousarray(key_hash, np.uint64)
    n = kh.shape[0]
    order = np.empty(n, np.int32)
    group_id = np.empty(n, np.int32)
    leader_pos = np.empty(n, np.int32)
    G = ctypes.c_int64(0)
    _lib.guber_presort_grouped(
        _ptr(kh, ctypes.c_uint64), n, ctypes.c_uint64(buckets),
        _ptr(order, ctypes.c_int32), _ptr(group_id, ctypes.c_int32),
        _ptr(leader_pos, ctypes.c_int32), ctypes.byref(G),
    )
    return order, group_id, leader_pos, G.value


# Fixed seed: slot hashes are instance-local but stable across restarts for
# debuggability.
_SEED = 0x67756265726E6174  # "gubernat"


def _pack(keys: List[str]):
    bufs = [k.encode("utf-8") for k in keys]
    offsets = np.zeros(len(bufs) + 1, np.int64)
    np.cumsum([len(b) for b in bufs], out=offsets[1:])
    return b"".join(bufs), offsets


def hash_batch_seed(keys: List[str], seed: int) -> np.ndarray:
    """uint64[len(keys)] XXH64 hashes with an explicit seed (test hook)."""
    buf, offsets = _pack(keys)
    out = np.empty(len(keys), np.uint64)
    _lib.guber_hash_batch(
        buf,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(keys),
        ctypes.c_uint64(seed),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return out


def hash_batch(keys: List[str]) -> np.ndarray:
    """uint64[len(keys)] XXH64 slot hashes."""
    return hash_batch_seed(keys, _SEED)


def crc32_batch(keys: List[str]) -> np.ndarray:
    """uint32[len(keys)] IEEE crc32 ring points (matches zlib.crc32)."""
    buf, offsets = _pack(keys)
    out = np.empty(len(keys), np.uint32)
    _lib.guber_crc32_batch(
        buf,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(keys),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
    )
    return out


def presort(key_hash: np.ndarray, buckets: int) -> np.ndarray:
    """int32[n] stable argsort of key hashes by (bucket, fingerprint) —
    the order decide_presorted requires. Bit-identical to
    np.argsort(store.group_sort_key_np(kh, buckets), kind="stable") and
    ~15x faster (LSD radix in C)."""
    kh = np.ascontiguousarray(key_hash, np.uint64)
    out = np.empty(kh.shape[0], np.int32)
    _lib.guber_presort(
        kh.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        kh.shape[0],
        ctypes.c_uint64(buckets),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out


def presort_sharded_grouped(key_hash: np.ndarray, buckets: int,
                            n_shards: int):
    """(order, counts, group_id, leader_pos, group_counts) — the sharded
    presort plus per-shard duplicate-key group structure. group_id[i] is
    the GLOBAL group index of sorted row i; leader_pos[:sum(group_counts)]
    holds each global group's first sorted row; group_counts[s] counts
    shard s's groups."""
    kh = np.ascontiguousarray(key_hash, np.uint64)
    n = kh.shape[0]
    order = np.empty(n, np.int32)
    counts = np.empty(n_shards, np.int64)
    group_id = np.empty(n, np.int32)
    leader_pos = np.empty(n, np.int32)
    group_counts = np.empty(n_shards, np.int64)
    _lib.guber_presort_sharded_grouped(
        _ptr(kh, ctypes.c_uint64), n, ctypes.c_uint64(buckets),
        ctypes.c_uint64(n_shards), _ptr(order, ctypes.c_int32),
        _ptr(counts, ctypes.c_int64), _ptr(group_id, ctypes.c_int32),
        _ptr(leader_pos, ctypes.c_int32),
        _ptr(group_counts, ctypes.c_int64),
    )
    return order, counts, group_id, leader_pos, group_counts


def presort_sharded(key_hash: np.ndarray, buckets: int, n_shards: int):
    """(order int32[n], counts int64[n_shards]) — stable argsort by
    (owner_shard, bucket, fingerprint) plus per-shard row counts. The
    contiguous per-shard runs of the permutation are the mesh engine's
    per-chip sub-batches (parallel/sharded.py pad_request_sharded)."""
    kh = np.ascontiguousarray(key_hash, np.uint64)
    order = np.empty(kh.shape[0], np.int32)
    counts = np.empty(n_shards, np.int64)
    _lib.guber_presort_sharded(
        kh.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        kh.shape[0],
        ctypes.c_uint64(buckets),
        ctypes.c_uint64(n_shards),
        order.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return order, counts


def prep_threads() -> int:
    """Effective prep thread-pool width (GUBER_PREP_THREADS env,
    default hardware_concurrency; resolved once per process)."""
    return int(_lib.guber_prep_threads())


_PREP_GENS = 2


def set_prep_generations(gens: int) -> None:
    """Size the prep-buffer ring (serve/batcher.py's fetch_depth sets
    gens = depth + 1 at construction, before traffic). Generation k is
    reused at the k+gens'th prep call on the same thread.

    NOTE the ring is NOT what guarantees in-flight correctness under the
    batcher's out-of-order fetch pipeline — no fixed depth could (a
    stalled fetch can be outrun by later submits without bound). The
    guarantees are: (a) decide handles COPY the order/take views they
    keep (sharded.decide_submit), and (b) jax commits host inputs during
    dispatch, before submit returns (verified by mutate-after-dispatch).
    The deeper ring is defense-in-depth for PJRT backends whose dispatch
    might stage host buffers lazily. Threads pick the new width up on
    their next prep call."""
    global _PREP_GENS
    _PREP_GENS = max(2, int(gens))


class _PrepBuffers:
    """Reusable output buffers for prep_sharded, rotated across calls.
    Fresh np.empty per call costs ~0.5-1ms of soft page faults at
    32k batches (every large allocation is a new zeroed mmap); reusing
    warm pages removes that entirely. The ring holds _PREP_GENS
    generations (default two: at most two batches in flight, submits
    serialized — serve/batcher.py) so a pipelined engine never sees
    generation k's arrays overwritten before its wait."""

    _SPECS = (
        ("order", np.int32), ("counts", np.int64), ("take", np.int64),
        ("kh", np.uint64), ("hits", np.int32), ("limit", np.int32),
        ("dur", np.int32), ("algo", np.int32), ("gnp", np.uint8),
        ("valid", np.uint8), ("gid", np.int32), ("gkh", np.uint64),
        ("glead", np.int32), ("gend", np.int32), ("gvalid", np.uint8),
    )

    def __init__(self):
        self._gens: list = []
        self._flip = 0

    def take(self, sizes: dict) -> dict:
        if len(self._gens) != _PREP_GENS:
            # ring width changed (set_prep_generations) or first use
            self._gens = [{} for _ in range(_PREP_GENS)]
            self._flip = 0
        gen = self._gens[self._flip]
        self._flip = (self._flip + 1) % len(self._gens)
        out = {}
        for name, dtype in self._SPECS:
            need = sizes[name]
            cur = gen.get(name)
            if cur is None or cur.shape[0] < need:
                cur = np.empty(need, dtype)
                gen[name] = cur
            out[name] = cur
        return out


class _PrepBuffersTL(threading.local):
    """Per-thread buffer sets: K concurrent prep workers (the batcher's
    prep pool) each flip-flop their own generations, so one worker's
    in-flight batch is never overwritten by another's call."""

    def __init__(self):
        self.bufs = _PrepBuffers()


_prep_buffers_tl = _PrepBuffersTL()


def prep_sharded(
    key_hash, hits, limit, duration, algo, gnp,
    buckets: int, n_shards: int, rungs, g_override: int,
    lo: int, hi: int, dlo: int, dhi: int,
):
    """One-call sharded batch prep (guber_prep_sharded): presort by
    (owner, bucket, fingerprint), duplicate-key group structure with
    engine.build_groups conventions, and all six clipped+padded device
    fields as [n_shards, B_sub] arrays. Returns
    (order, counts, take_idx, fields_dict, groups_dict, B_sub, G_sub).
    Raises ValueError when g_override can't hold a shard's group count
    (mirrors pad_request_sharded's numpy path).

    LIFETIME: returned arrays are views into a reusable buffer ring —
    valid until the _PREP_GENS'th next prep_sharded call on the same
    thread (default 2; see set_prep_generations). Callers keeping
    results past that — e.g. decide handles under a deep fetch
    pipeline — must copy."""
    kh = np.ascontiguousarray(key_hash, np.uint64)
    hits = np.ascontiguousarray(hits, np.int64)
    limit = np.ascontiguousarray(limit, np.int64)
    duration = np.ascontiguousarray(duration, np.int64)
    algo = np.ascontiguousarray(algo, np.int32)
    gnp = np.ascontiguousarray(np.asarray(gnp, bool).view(np.uint8))
    n = kh.shape[0]
    rungs = np.ascontiguousarray(rungs, np.int64)
    # B_sub <= smallest rung covering n (shard counts never exceed n)
    alloc_idx = int(np.searchsorted(rungs, min(n, int(rungs[-1]))))
    B_alloc = int(rungs[min(alloc_idx, rungs.shape[0] - 1)])
    if g_override > 0:
        B_alloc = max(B_alloc, int(g_override))

    nb = n_shards * B_alloc
    buf = _prep_buffers_tl.bufs.take(dict(
        order=n, counts=n_shards, take=n,
        kh=nb, hits=nb, limit=nb, dur=nb, algo=nb, gnp=nb, valid=nb,
        gid=nb, gkh=nb, glead=nb, gend=nb, gvalid=nb,
    ))
    order = buf["order"][:n]
    counts = buf["counts"][:n_shards]
    picked = np.empty(2, np.int64)
    take_idx = buf["take"][:n]
    kh_o, hi_o, li_o, du_o = buf["kh"], buf["hits"], buf["limit"], buf["dur"]
    al_o, gn_o, va_o, gi_o = buf["algo"], buf["gnp"], buf["valid"], buf["gid"]
    gk_o, gl_o, ge_o, gv_o = buf["gkh"], buf["glead"], buf["gend"], buf["gvalid"]

    rc = _lib.guber_prep_sharded(
        _ptr(kh, ctypes.c_uint64), _ptr(hits, ctypes.c_int64),
        _ptr(limit, ctypes.c_int64), _ptr(duration, ctypes.c_int64),
        _ptr(algo, ctypes.c_int32), _ptr(gnp, ctypes.c_uint8),
        n, ctypes.c_uint64(buckets), n_shards,
        _ptr(rungs, ctypes.c_int64), rungs.shape[0], g_override,
        lo, hi, dlo, dhi,
        _ptr(order, ctypes.c_int32), _ptr(counts, ctypes.c_int64),
        _ptr(picked, ctypes.c_int64),
        _ptr(kh_o, ctypes.c_uint64), _ptr(hi_o, ctypes.c_int32),
        _ptr(li_o, ctypes.c_int32), _ptr(du_o, ctypes.c_int32),
        _ptr(al_o, ctypes.c_int32), _ptr(gn_o, ctypes.c_uint8),
        _ptr(va_o, ctypes.c_uint8),
        _ptr(gk_o, ctypes.c_uint64), _ptr(gl_o, ctypes.c_int32),
        _ptr(ge_o, ctypes.c_int32), _ptr(gv_o, ctypes.c_uint8),
        _ptr(gi_o, ctypes.c_int32),
        _ptr(take_idx, ctypes.c_int64),
    )
    if rc == -2:
        raise ValueError(
            f"group_rung {g_override} < max shard group count"
        )
    if rc != 0:
        raise RuntimeError(f"guber_prep_sharded failed: rc={rc}")
    B, G = int(picked[0]), int(picked[1])
    fields, groups = _stack_views(
        n_shards, B, G, kh_o, hi_o, li_o, du_o, al_o, gn_o, va_o, gi_o,
        gk_o, gl_o, ge_o, gv_o,
    )
    return order, counts, take_idx, fields, groups, B, G


def _stack_views(n_shards, B, G, kh, hits, limit, dur, algo, gnp, valid,
                 gid, gkh, glead, gend, gvalid):
    """(fields, groups) of a mesh device batch over buffers a native call
    filled compactly: n_shards rows of stride B (request cells) or G
    (group cells) from each buffer's start — guber_prep_sharded's and
    guber_merge_runs_sharded's output convention."""

    def rows(a, w):
        return a[: n_shards * w].reshape(n_shards, w)

    fields = dict(
        key_hash=rows(kh, B), hits=rows(hits, B), limit=rows(limit, B),
        duration=rows(dur, B), algo=rows(algo, B),
        gnp=rows(gnp, B).view(bool), valid=rows(valid, B).view(bool),
    )
    groups = dict(
        key_hash=rows(gkh, G), leader_pos=rows(glead, G),
        end_pos=rows(gend, G), valid=rows(gvalid, G).view(bool),
        group_id=rows(gid, B),
    )
    return fields, groups


def prep_run(fields: dict, buckets: int, n_shards: int,
             lo: int, hi: int, dlo: int, dhi: int) -> dict:
    """Fused arrival-time per-group prep (guber_prep_run): sharded
    presort + device-dtype clip/gather of all six fields + the merged
    composite sort-key stream, in ONE GIL-free call — the producer
    side of merge_runs_native. Output layout matches the engines'
    numpy prep_run fallbacks bit-for-bit."""
    kh = np.ascontiguousarray(fields["key_hash"], np.uint64)
    hits = np.ascontiguousarray(fields["hits"], np.int64)
    limit = np.ascontiguousarray(fields["limit"], np.int64)
    duration = np.ascontiguousarray(fields["duration"], np.int64)
    algo = np.ascontiguousarray(fields["algo"], np.int32)
    gnp = np.ascontiguousarray(np.asarray(fields["gnp"], bool).view(np.uint8))
    n = kh.shape[0]
    order = np.empty(n, np.int32)
    counts = np.empty(n_shards, np.int64)
    skey = np.empty(n, np.uint64)
    kh_o = np.empty(n, np.uint64)
    hits_o = np.empty(n, np.int32)
    lim_o = np.empty(n, np.int32)
    dur_o = np.empty(n, np.int32)
    algo_o = np.empty(n, np.int32)
    gnp_o = np.empty(n, np.uint8)
    rc = _lib.guber_prep_run(
        _ptr(kh, ctypes.c_uint64), _ptr(hits, ctypes.c_int64),
        _ptr(limit, ctypes.c_int64), _ptr(duration, ctypes.c_int64),
        _ptr(algo, ctypes.c_int32), _ptr(gnp, ctypes.c_uint8),
        n, ctypes.c_uint64(buckets), n_shards, lo, hi, dlo, dhi,
        _ptr(order, ctypes.c_int32), _ptr(counts, ctypes.c_int64),
        _ptr(skey, ctypes.c_uint64), _ptr(kh_o, ctypes.c_uint64),
        _ptr(hits_o, ctypes.c_int32), _ptr(lim_o, ctypes.c_int32),
        _ptr(dur_o, ctypes.c_int32), _ptr(algo_o, ctypes.c_int32),
        _ptr(gnp_o, ctypes.c_uint8),
    )
    if rc != 0:
        raise RuntimeError(f"guber_prep_run failed: rc={rc}")
    run = dict(
        n=n, skey=skey, order=order, counts=counts,
        fields=dict(
            key_hash=kh_o, hits=hits_o, limit=lim_o, duration=dur_o,
            algo=algo_o, gnp=gnp_o.view(bool),
        ),
    )
    run["_addrs"] = run_addrs(run)
    return run


def run_addrs(run: dict) -> tuple:
    """Raw data addresses of one prep run's arrays, in
    guber_merge_runs' table column order. prep_run stamps this into the
    run at arrival time so the flush-time merge pays zero per-run
    ctypes-interface construction on the submit thread."""
    f = run["fields"]
    return (
        run["skey"].ctypes.data,
        f["key_hash"].ctypes.data,
        f["hits"].ctypes.data,
        f["limit"].ctypes.data,
        f["duration"].ctypes.data,
        f["algo"].ctypes.data,
        f["gnp"].ctypes.data,
        run["order"].ctypes.data,
    )


def _run_tables(runs):
    """(tabs, ns, bases) of guber_merge_runs' input: the eight pointer
    tables from the per-run address tuples prep stamped at ARRIVAL
    (run_addrs above) — `.ctypes.data` per array here would cost ~8k
    ctypes-interface constructions of pure submit-thread Python, which
    is exactly the wall this path exists to remove — the run lengths,
    and each run's base in the flattened batch. The run dicts keep the
    arrays alive for the duration of the call."""
    k = len(runs)
    addrs = [r.get("_addrs") or run_addrs(r) for r in runs]
    tabs = [
        (ctypes.c_void_p * k)(*[a[col] for a in addrs])
        for col in range(8)
    ]
    ns = np.asarray([r["n"] for r in runs], np.int64)
    bases = np.zeros(k, np.int64)
    np.cumsum(ns[:-1], out=bases[1:])
    return tabs, ns, bases


def merge_runs_native(runs, B: int, g_rungs=None) -> dict:
    """Fused k-way merge of pre-sorted per-group runs (guber_merge_runs):
    one GIL-free pass produces the merged sort-key stream, the global
    caller-order permutation, all six device-dtype field arrays padded
    to B rows (tail repeats the last merged row, valid=False — the
    engine's padding convention; pass B == n for a flat merge: the
    numpy twins' — the mesh's stacked layout is
    merge_runs_sharded_native's), and the duplicate-key group stream. `runs` are engine prep_run dicts in
    caller order; ties across runs resolve in run order, so the merged
    permutation equals np.argsort(concat, kind='stable') — the
    merge-combine equivalence contract (tests/test_prep_pipeline.py).

    Returns dict(n, skey[n], order[B], key_hash/hits/limit/duration/
    algo[B], gnp/valid[B] (bool), group_id[n], leader_pos[n], G_real).
    With `g_rungs` (engine.group_rungs(B)), the group stream is padded
    in the same pass to the smallest fitting rung G — build_groups'
    conventions — and the dict gains G, group_key_hash/group_end/
    group_valid [:G], padded leader_pos [:G], and a B-sized group_id.
    """
    k = len(runs)
    n = int(sum(r["n"] for r in runs))
    assert B >= n, (B, n)
    tabs, ns, bases = _run_tables(runs)

    if g_rungs is not None:
        rungs = np.ascontiguousarray(g_rungs, np.int64)
        g_max = int(rungs[-1])
    else:
        rungs = np.empty(0, np.int64)
        g_max = 0
    skey = np.empty(n, np.uint64)
    order = np.empty(B, np.int32)
    kh = np.empty(B, np.uint64)
    hits = np.empty(B, np.int32)
    limit = np.empty(B, np.int32)
    dur = np.empty(B, np.int32)
    algo = np.empty(B, np.int32)
    gnp = np.empty(B, np.uint8)
    valid = np.empty(B, np.uint8)
    gid = np.empty(max(B if g_rungs is not None else n, 1), np.int32)
    lead = np.empty(max(n, g_max, 1), np.int32)
    gkh = np.empty(max(g_max, 1), np.uint64)
    gend = np.empty(max(g_max, 1), np.int32)
    gvalid = np.empty(max(g_max, 1), np.uint8)
    g_real = ctypes.c_int64(0)
    g_pick = ctypes.c_int64(0)
    rc = _lib.guber_merge_runs(
        *tabs,
        _ptr(ns, ctypes.c_int64), _ptr(bases, ctypes.c_int64), k, B,
        _ptr(rungs, ctypes.c_int64), rungs.shape[0],
        _ptr(skey, ctypes.c_uint64), _ptr(order, ctypes.c_int32),
        _ptr(kh, ctypes.c_uint64), _ptr(hits, ctypes.c_int32),
        _ptr(limit, ctypes.c_int32), _ptr(dur, ctypes.c_int32),
        _ptr(algo, ctypes.c_int32), _ptr(gnp, ctypes.c_uint8),
        _ptr(valid, ctypes.c_uint8), _ptr(gid, ctypes.c_int32),
        _ptr(lead, ctypes.c_int32), _ptr(gkh, ctypes.c_uint64),
        _ptr(gend, ctypes.c_int32), _ptr(gvalid, ctypes.c_uint8),
        ctypes.byref(g_real), ctypes.byref(g_pick),
    )
    if rc != 0:
        raise RuntimeError(f"guber_merge_runs failed: rc={rc}")
    out = dict(
        n=n, skey=skey, order=order, key_hash=kh, hits=hits,
        limit=limit, duration=dur, algo=algo, gnp=gnp.view(bool),
        valid=valid.view(bool), G_real=int(g_real.value),
    )
    if g_rungs is not None:
        G = int(g_pick.value)
        out.update(
            G=G, group_id=gid, leader_pos=lead[:G],
            group_key_hash=gkh[:G], group_end=gend[:G],
            group_valid=gvalid[:G].view(bool),
        )
    else:
        out.update(group_id=gid[:n], leader_pos=lead[:n])
    return out


def merge_runs_sharded_native(runs, n_shards: int, store_buckets: int,
                              sub_rungs):
    """The same merge laid out for the mesh (guber_merge_runs_sharded):
    one GIL-free call merges the runs and writes the stacked
    [n_shards, B_sub] request columns (repeat-pad / clamp, valid =
    j < count), the per-shard group structure with LOCAL indices at one
    G_sub (engine.build_groups' conventions), the merged `order[n]`,
    `take_idx[n]` and the rows a shard — byte for byte what
    serve/prep.py merge_runs + parallel/sharded.py
    build_presorted_sharded + stack_shard_groups give in numpy
    (tests/test_prep_pipeline.py). `sub_rungs` is the engine's sub-rung
    ladder, ascending; B_sub is its smallest rung that holds the
    fullest shard.

    Returns dict(n, order, take_idx, counts, B_sub, G_sub, fields
    {key_hash/hits/limit/duration/algo/gnp/valid: [n_shards, B_sub]},
    groups {key_hash/leader_pos/end_pos/valid: [n_shards, G_sub],
    group_id: [n_shards, B_sub]}), or None where the fullest shard
    exceeds the ladder's top: extending the ladder, and the warning
    that goes with it, stay the numpy twin's."""
    k = len(runs)
    n = int(sum(r["n"] for r in runs))
    tabs, ns, bases = _run_tables(runs)
    rungs = np.ascontiguousarray(sub_rungs, np.int64)
    # no shard holds more than n rows: buffers for the rung that n
    # itself would take (the ladder's top where n is past it)
    cap = int(rungs[min(np.searchsorted(rungs, n), rungs.shape[0] - 1)])
    cells = n_shards * cap
    order = np.empty(n, np.int32)
    take_idx = np.empty(n, np.int64)
    counts = np.empty(n_shards, np.int64)
    picked = np.empty(2, np.int64)
    u64 = np.empty((2, cells), np.uint64)  # key_hash, group key_hash
    i32 = np.empty((7, cells), np.int32)
    u8 = np.empty((3, cells), np.uint8)
    kh, gkh = u64
    hits, limit, dur, algo, gid, glead, gend = i32
    gnp, valid, gvalid = u8
    bucket_bits = max(int(store_buckets).bit_length() - 1, 1)
    rc = _lib.guber_merge_runs_sharded(
        *tabs,
        _ptr(ns, ctypes.c_int64), _ptr(bases, ctypes.c_int64), k,
        n_shards, 32 + bucket_bits,
        _ptr(rungs, ctypes.c_int64), rungs.shape[0],
        order.ctypes.data, take_idx.ctypes.data, counts.ctypes.data,
        picked.ctypes.data,
        *(
            a.ctypes.data
            for a in (kh, hits, limit, dur, algo, gnp, valid, gid,
                      gkh, glead, gend, gvalid)
        ),
    )
    if rc == 1:
        return None
    if rc != 0:
        raise RuntimeError(f"guber_merge_runs_sharded failed: rc={rc}")
    B, G = int(picked[0]), int(picked[1])
    fields, groups = _stack_views(
        n_shards, B, G, kh, hits, limit, dur, algo, gnp, valid, gid,
        gkh, glead, gend, gvalid,
    )
    return dict(
        n=n, order=order, take_idx=take_idx, counts=counts, B_sub=B,
        G_sub=G, fields=fields, groups=groups,
    )


def unflatten_resp(packed, order, counts, n: int, b_sub: int) -> np.ndarray:
    """[4, n] response columns from a mesh packed matrix
    ([n_shards, 4*b_sub + stats] int32): the native twin of
    `out[order] = flat[take_idx]` per column. `b_sub` comes from the
    caller's handle — inferring it from the stride would silently skew
    every column if the stats tail ever grew."""
    packed = np.ascontiguousarray(packed, np.int32)
    n_shards, stride = packed.shape
    assert stride >= 4 * b_sub, (stride, b_sub)
    counts = np.ascontiguousarray(counts, np.int64)
    out = np.empty((4, n), np.int32)
    _lib.guber_unflatten_resp(
        _ptr(packed, ctypes.c_int32), _ptr(order, ctypes.c_int32),
        _ptr(counts, ctypes.c_int64), n, n_shards, b_sub, stride,
        _ptr(out, ctypes.c_int32),
    )
    return out


# -- the PeersV1 door's wire fold (guberhash.cc, last section) ---------------

#: why guber_parse_peer_batch declined a batch, by its return code
PEER_DECLINE = {
    -1: "chain",
    -2: "unknown_field",
    -3: "bad_enum",
    -4: "bad_utf8",
    -5: "truncated",
    -6: "too_many_items",
}

_PEER_COLUMNS = (
    ("key_hash", np.uint64), ("hits", np.int64), ("limit", np.int64),
    ("duration", np.int64), ("algo", np.int32), ("behavior", np.uint8),
    ("name_off", np.int32), ("name_len", np.int32),
    ("key_off", np.int32), ("key_len", np.int32),
)


def parse_peer_batch(wire: bytes, max_items: int):
    """(n, columns) of a serialised GetPeerRateLimitsReq: n >= 0 items
    and a dict of n-long numpy columns (key_hash as slot_hash_batch
    hashes name + "_" + unique_key, hits, limit, duration, algo,
    behavior, and the offsets of name and unique_key in `wire`), or
    (code < 0, None) where the native parser declines (PEER_DECLINE).
    One call with the GIL released, no object per item."""
    # an item is at least its tag and a length byte
    cap = min(max_items, len(wire) // 2) + 1
    cols = {name: np.empty(cap, dt) for name, dt in _PEER_COLUMNS}
    n = _lib.guber_parse_peer_batch(
        wire, len(wire), max_items, _SEED,
        *[a.ctypes.data for a in cols.values()],
    )
    if n < 0:
        return n, None
    return n, {name: a[:n] for name, a in cols.items()}


# -- the GEB door's string-frame parse (guberhash.cc, same section) ----------

#: why guber_parse_string_frame declined a frame, by its return code:
#: PEER_DECLINE's names where the reason is shared
STRING_DECLINE = {
    -4: PEER_DECLINE[-4],
    -5: PEER_DECLINE[-5],
    -6: PEER_DECLINE[-6],
    -8: "empty_name_or_key",
    -9: "trailing_bytes",
    -10: "nul_byte",
}


def parse_string_frame(payload: bytes, n: int):
    """(n, columns, keys) of one GEB string frame's payload (`n` the
    header's item count): the _PEER_COLUMNS of parse_peer_batch —
    key_hash as slot_hash_batch hashes name + "_" + unique_key, an
    algorithm byte over 3 read as 0, the offsets of name and
    unique_key in `payload` — and the n hash keys as one `bytes`,
    joined by NUL and valid UTF-8. (code < 0, None, None) where the
    native parser declines (STRING_DECLINE). One call with the GIL
    released, no object per item."""
    size = len(payload)
    # the wire count is untrusted: an item is at least 30 bytes
    if not 0 <= n <= size // 30:
        return -6, None, None
    cols = {name: np.empty(n, dt) for name, dt in _PEER_COLUMNS}
    keys = np.empty(size, np.uint8)
    keys_len = ctypes.c_int64(0)
    got = _lib.guber_parse_string_frame(
        payload, size, n, _SEED,
        *[a.ctypes.data for a in cols.values()],
        keys.ctypes.data, ctypes.addressof(keys_len),
    )
    if got < 0:
        return got, None, None
    return got, cols, keys[: keys_len.value].tobytes()


def encode_peer_answers(status, limit, remaining, reset_time) -> bytes:
    """The serialised GetPeerRateLimitsResp of four answer columns
    (zero fields left out, no error, no metadata), in one call."""
    cols = [
        np.ascontiguousarray(c, np.int64)
        for c in (status, limit, remaining, reset_time)
    ]
    n = cols[0].shape[0]
    if any(c.shape != (n,) for c in cols):
        raise ValueError("answer columns differ in length")
    out = np.empty(n * _PEER_ANSWER_MAX, np.uint8)
    m = _lib.guber_encode_peer_answers(
        *[c.ctypes.data for c in cols], n, out.ctypes.data,
    )
    return out[:m].tobytes()


# -- the GEB door's split by owner and the forwarder's column RPC ------------
# (guberhash.cc, same section: the converses of the three calls above)

#: why guber_parse_peer_answers declined a reply, by its return code:
#: PEER_DECLINE's names where the reason is shared
ANSWER_DECLINE = {
    -2: PEER_DECLINE[-2],
    -3: PEER_DECLINE[-3],
    -5: PEER_DECLINE[-5],
    -6: PEER_DECLINE[-6],
    -11: "error_or_metadata",
}

#: the columns of parse_string_frame that guber_encode_peer_batch reads
_FORWARD_COLUMNS = (
    "name_off", "name_len", "key_off", "key_len", "hits", "limit",
    "duration", "algo", "behavior",
)


def ring_owners(keys: bytes, n: int, ring: np.ndarray) -> np.ndarray:
    """int32[n]: for each of the n hash keys in `keys` (joined by NUL,
    as parse_string_frame returns them) the position on `ring` (uint32,
    ascending crc32 points) of the key's successor, wrapping: the index
    ConsistentHashPicker.get finds. One call with the GIL released."""
    out = np.empty(n, np.int32)
    if _lib.guber_ring_owners(
        keys, len(keys), n, ring.ctypes.data, ring.shape[0], out.ctypes.data
    ):
        raise ValueError(f"the joined keys are not {n}")
    return out


def encode_peer_batch(payload: bytes, cols: dict, rows=None) -> bytes:
    """The serialised GetPeerRateLimitsReq of the items `rows` (int32
    indices, or every item) of one parsed string frame: `cols` as
    parse_string_frame returned them for `payload`. The bytes the
    protobuf runtime writes for the same requests (convert.req_to_pb);
    one call, no object per item."""
    if rows is None:
        n = cols["hits"].shape[0]
        lens = cols["name_len"], cols["key_len"]
        rows_at = None
    else:
        rows = np.ascontiguousarray(rows, np.int32)
        n = rows.shape[0]
        lens = cols["name_len"][rows], cols["key_len"][rows]
        rows_at = rows.ctypes.data
    cap = int(lens[0].sum()) + int(lens[1].sum()) + n * 69
    out = np.empty(cap, np.uint8)
    m = _lib.guber_encode_peer_batch(
        payload, *[cols[c].ctypes.data for c in _FORWARD_COLUMNS],
        rows_at, n, out.ctypes.data, cap,
    )
    if m < 0:
        raise ValueError("peer batch does not fit its buffer")
    return out[:m].tobytes()


def parse_peer_answers(wire: bytes, max_items: int):
    """(n, (status, limit, remaining, reset_time)) of a serialised
    GetPeerRateLimitsResp as four int64 columns, or (code < 0, None)
    where the native parser declines (ANSWER_DECLINE: an item with an
    error or metadata among them). One call, no object per item."""
    cap = min(max_items, len(wire) // 2) + 1
    cols = [np.empty(cap, np.int64) for _ in range(4)]
    n = _lib.guber_parse_peer_answers(
        wire, len(wire), max_items, *[c.ctypes.data for c in cols],
    )
    if n < 0:
        return n, None
    return n, tuple(c[:n] for c in cols)


def encode_string_answers(
    status, limit, remaining, reset_time, err, owner, strings
) -> bytes:
    """The items of a GEB string response frame from four answer
    columns and, a row, an error text and an owner tag: `err` and
    `owner` (int32) index `strings` (a list of bytes), -1 for none.
    Byte for byte edge_bridge.encode_response_frame's items."""
    cols = [
        np.ascontiguousarray(c, np.int64)
        for c in (status, limit, remaining, reset_time)
    ]
    n = cols[0].shape[0]
    err = np.ascontiguousarray(err, np.int32)
    owner = np.ascontiguousarray(owner, np.int32)
    if any(c.shape != (n,) for c in (*cols, err, owner)):
        raise ValueError("answer columns differ in length")
    off = np.zeros(len(strings) + 1, np.int64)
    np.cumsum(
        np.fromiter(map(len, strings), np.int64, len(strings)), out=off[1:]
    )
    longest = max(map(len, strings), default=0)
    cap = n * (29 + 2 * longest)
    out = np.empty(cap, np.uint8)
    m = _lib.guber_encode_string_answers(
        *[c.ctypes.data for c in cols], err.ctypes.data, owner.ctypes.data,
        b"".join(strings), off.ctypes.data, len(strings), n,
        out.ctypes.data, cap,
    )
    if m < 0:
        raise ValueError("answer strings do not fit the frame's items")
    return out[:m].tobytes()


# -- the traffic observers' per-batch fold (guberhash.cc, last section) ------


def hotkeys_new(capacity: int) -> int:
    """A handle to an empty native hot-key summary of `capacity` slots
    (hotkeys_free gives it back). The library keeps no lock: the holder
    serialises every call on one handle."""
    return _lib.guber_hotkeys_new(capacity)


def hotkeys_reset(handle: int) -> None:
    _lib.guber_hotkeys_reset(handle)


def traffic_fold(handle, hashes, n: int, keys, offsets, reg, p) -> None:
    """Fold one batch of n items into the summary `handle` and the HLL
    registers at address `reg` (uint8[1 << p]) in one call with the GIL
    released; either may be None and is left out. `hashes` is n
    contiguous uint64 (an array's address or a ctypes array),
    hashes[i] the slot hash of key i; `keys` their UTF-8 bytes end to
    end, cut by `offsets` (n + 1 int64, likewise) or, with offsets
    None, joined by NUL as parse_string_frame returns them."""
    if _lib.guber_traffic_fold(
        handle, hashes, n, keys, 0 if keys is None else len(keys),
        offsets, reg, p,
    ):
        raise ValueError(f"the joined keys are not {n}")


def hotkeys_export(handle):
    """(total, counts, errs, offsets, keys) of a summary: the items it
    has observed, and by slot, in no order, each tracked key's count and
    err and its bytes keys[offsets[i]:offsets[i + 1]]."""
    total = ctypes.c_int64(0)
    size = ctypes.c_int64(0)
    n = _lib.guber_hotkeys_size(
        handle, ctypes.addressof(total), ctypes.addressof(size)
    )
    counts = np.empty(n, np.int64)
    errs = np.empty(n, np.int64)
    offsets = np.empty(n + 1, np.int64)
    keys = np.empty(size.value, np.uint8)
    _lib.guber_hotkeys_export(
        handle, counts.ctypes.data, errs.ctypes.data, offsets.ctypes.data,
        keys.ctypes.data,
    )
    return total.value, counts, errs, offsets, keys.tobytes()


# -- the shed cache's array consult and population (guberhash.cc, last
# section) -------------------------------------------------------------------


def shed_screen(cols: dict, now: int, index):
    """ShedCache.screen_fields' gates over one frame in one call with
    the GIL released (guber_shed_screen). `cols` holds the frame's
    contiguous key_hash uint64 / hits, limit, duration int64 / algo
    int32 and, where it has one, gnp bool columns; `index` is the
    cache's (ix_fp, ix_slot, m, ov_fp, ov_slot, k, fp, lim, dur, reset)
    as addresses and lengths. Returns (shed, eligible, mask bool[n],
    the four int64[n] answer columns, the int64 indices of the rows not
    shed, those rows' columns as a dict like `cols`) — every array but
    the mask a view of one block the call filled."""
    kh, gnp = cols["key_hash"], cols.get("gnp")
    n = kh.shape[0]
    if any(c.shape != (n,) for c in cols.values()):
        raise ValueError("a frame's columns differ in length")
    mask = np.empty(n, bool)
    out = np.empty((9, n), np.int64)
    r_algo = np.empty(n, np.int32)
    r_gnp = None if gnp is None else np.empty(n, bool)
    eligible = ctypes.c_int64(0)
    shed = _lib.guber_shed_screen(
        kh.ctypes.data, cols["hits"].ctypes.data, cols["limit"].ctypes.data,
        cols["duration"].ctypes.data, cols["algo"].ctypes.data,
        None if gnp is None else gnp.ctypes.data, n, now, *index,
        mask.ctypes.data, out.ctypes.data, r_algo.ctypes.data,
        None if gnp is None else r_gnp.ctypes.data, eligible,
    )
    r = n - shed
    residue = dict(
        key_hash=out[5, :r].view(np.uint64), hits=out[6, :r],
        limit=out[7, :r], duration=out[8, :r], algo=r_algo[:r],
    )
    if gnp is not None:
        residue["gnp"] = r_gnp[:r]
    return shed, eligible.value, mask, tuple(out[:4]), out[4, :r], residue


def shed_observe(kh, algo, results, index, cap: int, into=None):
    """The rows ShedCache.observe_fields has to walk, as a list in walk
    order, in one call with the GIL released (guber_shed_observe). `kh`
    is the rows' contiguous uint64 fingerprints, `algo` their contiguous
    int32 algorithms or None (all token bucket), `results` their four
    result columns (int32 or int64 as they come; anything else is
    copied to int64), `index` the cache's (ix_fp, ix_slot, m, ov_fp,
    ov_slot, k, fp, reset) as addresses and lengths. `into`, where
    given, is (four contiguous int64 answer columns of one length, the
    int64 index of each row in them): the results are stitched there in
    the same call; an index outside them raises IndexError with nothing
    written."""
    n = kh.shape[0]
    if algo is not None and algo.shape != (n,):
        raise ValueError("the algorithm column differs from the rows")
    res = []  # held to the end of the call: a copy is nobody else's
    for c in results:
        c = np.ascontiguousarray(c)
        if c.dtype not in (np.int32, np.int64):
            c = c.astype(np.int64)
        if c.shape != (n,):
            raise ValueError("result columns differ from the rows")
        res.append(c)
    stitch = (None, 0) + (None,) * 4
    if into is not None:
        answers, keep = into
        keep = np.ascontiguousarray(keep, np.int64)
        full = answers[0].shape
        if keep.shape != (n,) or len(full) != 1 or any(
            c.dtype != np.int64 or not c.flags.c_contiguous or c.shape != full
            for c in answers
        ):
            raise ValueError(
                "the stitch takes four contiguous int64 answer columns "
                "of one length and an index a row"
            )
        stitch = (keep.ctypes.data, full[0], *[c.ctypes.data for c in answers])
    walk = np.empty(n, np.int64)
    m = _lib.guber_shed_observe(
        kh.ctypes.data, None if algo is None else algo.ctypes.data, n,
        *[x for c in res for x in (c.ctypes.data, c.itemsize)],
        *index, cap, walk.ctypes.data, *stitch,
    )
    if m < 0:
        raise IndexError("a stitched row lies outside the answer columns")
    return walk[:m].tolist()
