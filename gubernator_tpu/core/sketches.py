"""Traffic sketches: HyperLogLog cardinality + Space-Saving heavy hitters +
the device count-min tier (r13).

The reference has no analogue — its LRU cache caps state at 50k entries and
offers no visibility into key-space size or hot keys (reference
cache/lru.go). At the scale this framework targets (10M-100M keys per chip,
BASELINE.json configs 4-5), "how many distinct keys am I limiting" and
"which keys are hot" become operational questions, so both are first-class
here:

- `HyperLogLog`: distinct-key estimate from the same 64-bit key hashes the
  engine already computes. Vectorized numpy over batch arrays — this is
  observability riding the serving path's existing host-side arrays, NOT a
  device kernel: a per-batch register update is a tiny scatter-max (16 KiB
  of registers) that would waste a TPU dispatch, while numpy's
  `maximum.at` on 4k hashes costs single-digit microseconds.
- `SpaceSaving`: the classic top-K stream summary (Metwally et al.) with
  per-batch pre-aggregation. Guarantees: every true heavy hitter with
  count > N/capacity is tracked, with overestimate bounded by `err`.

Both feed /metrics gauges and the /v1/debug endpoints (serve/server.py).

The sketch tier (r13)
---------------------
`SketchConfig` + the `Sketch` device state below back the approximate
cold tier of the two-tier store (core/kernels.py decide_presorted's
`sketch` argument): a count-min sketch of `rows` independent hash rows x
`width` dense int64 counters living next to the slot store in device
memory. The exact slot store remains the heavy-hitter tier; the sketch
absorbs the long tail — every create the exact tier DROPS to way
exhaustion is decided from the sketch's window-keyed estimate instead of
being silently over-admitted (the pre-r13 contract).

Design choices, mapped to PAPERS.md:

- **Conservative update** ("Count-Less"-family discipline): an update
  writes `max(counter, min_estimate + charged)` into each row instead of
  incrementing all rows, so only the counters that define the estimate
  grow — tail overestimates stay bounded without any per-update minimum
  scan beyond the gather the estimate already pays. The per-batch shape
  is exactly what this engine batches anyway: one [G]-gather per row +
  one scatter-max per row at unique-key granularity.
- **Window-keyed counting** (fixed-window approximation): the sketch
  index mixes the key hash with `window_id = now // duration`, so
  counts reset implicitly at window boundaries — no per-key reset state
  anywhere. Tail keys therefore get FIXED-WINDOW token semantics with a
  one-sided error: the estimate never under-counts the hits the sketch
  was charged with (collisions only inflate), so refusal comes at-or-
  before the true budget — fail-closed, matching the shed cache's
  stance.
- **Counter width** (re-derived in r21, the "v2" derivation): the r13
  tier spent its byte budget on 4 rows of int64 counters. The
  additive-error counter argument (arXiv 2004.10332) says that is the
  wrong corner of the budget: the count-min overestimate is ADDITIVE —
  bounded by e*N/width with failure probability e^-rows — so at a
  fixed byte budget B = rows * width * counter_bytes, width buys error
  LINEARLY while rows only sharpens the (already one-sided) tail
  exponent. The serve-side clamp makes deep rows redundant outright:
  an estimate is always clamped to the request limit before deciding
  (est >= limit simply refuses), so counters past int32 range carry no
  information — the v2 derivation uses SATURATING int32 counters
  (update math stays int64, the write clamps at 2^31-1; saturation can
  only occur >= 2^31 true charges, where the clamp refuses regardless,
  so the one-sided contract survives) and 2 rows, buying 4x the width
  of the r13 derivation at the same budget: a 4x tighter error bound
  AND half the gathers/scatters per decision (the Count-Less lesson,
  arXiv 2111.02759: fewer, wider rows under conservative update beat
  deeper stacks per byte and per update). `derive_sketch_config` keeps
  the r13 derivation reachable (`derivation="r13"`) for the committed
  paired A/B (scripts/perf_gate.py sketch2_r21, BENCH_SKETCH_r21).

The window-ring (r21): sliding-window and GCRA serve from the SAME
counter array — the ring is positional in hash space (the window id is
mixed into the index), so "rotate on window advance" means reading ids
`w` and `w-1` instead of `w` alone; see core/algorithms.py
sketch_sliding_budget / sketch_gcra_budget for the blend math and the
one-sidedness argument.

`sketch_indices_np` is the host twin of the device indexing in
core/kernels.py; the two MUST stay bit-identical (pinned by
tests/test_sketch_tier.py) — the promoter and the error-bound property
tests read estimates host-side for windows the device charged.
"""

from __future__ import annotations

import ctypes
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from gubernator_tpu.core.algorithms import (
    ALGO_GCRA,
    ALGO_LEAKY,
    ALGO_SLIDING,
    ALGO_TOKEN,
    SKETCH_SERVABLE_ALGOS,
)

# r21 interplay audit (supersedes the r15 pin): the sketch tier serves
# ALL FOUR algorithms — token/leaky with r13 fixed-window math, sliding
# with the window-ring blend, GCRA with the re-quantized TAT (the
# kernel's sk_sld/sk_gcra branches; host twins in core/algorithms.py
# sketch_sliding_budget/sketch_gcra_budget). The kernel's serve gate
# covers the full id range {0..3}; if the registry ever grows an
# algorithm the kernel does not serve (or drops one it still serves),
# this pin fails the IMPORT, not production. Callers that still assume
# the r15 pair {token, leaky} must be updated together with this pin —
# grep for SKETCH_SERVABLE_ALGOS.
assert SKETCH_SERVABLE_ALGOS == {
    ALGO_TOKEN, ALGO_LEAKY, ALGO_SLIDING, ALGO_GCRA,
}, (
    "the r21 sketch tier serves exactly {token, leaky, sliding, gcra}; "
    "update the core/kernels.py sketch branch and this pin together "
    "with core/algorithms.py SKETCH_SERVABLE_ALGOS"
)

_ALPHA_INF = 0.721347520444482  # 1 / (2 ln 2)

# -- the device sketch tier (r13) -------------------------------------------

#: per-row index salts (splitmix64-style odd constants); supports up to
#: 8 rows. Device and host indexing share these — see sketch_indices_np.
SKETCH_SALTS = (
    0x9AE16A3B2F90404F,
    0xC2B2AE3D27D4EB4F,
    0x165667B19E3779F9,
    0x27D4EB2F165667C5,
    0x85EBCA6B27D4EB4F,
    0xFF51AFD7ED558CCD,
    0xC4CEB9FE1A85EC53,
    0x2545F4914F6CDD1D,
)

#: window-id mix multiplier: decorrelates the same key's indices across
#: consecutive windows so a hot key's collision set rotates per window
WINDOW_MIX = 0xD6E8FEB86659FD93

SKETCH_BYTES_PER_COUNTER = 8  # r13 dense int64 rows (the default dtype)

#: derivation -> (default rows, counter bytes). "v2" (r21) is the
#: default: 2 rows of saturating int32 counters — 4x the width of the
#: r13 derivation (4 rows x int64) at the same byte budget, so a 4x
#: tighter additive error bound and half the per-decision gathers.
#: "r13" stays reachable for the committed paired A/B.
SKETCH_DERIVATIONS = {
    "v2": (2, 4),
    "r13": (4, SKETCH_BYTES_PER_COUNTER),
}


@dataclass(frozen=True)
class SketchConfig:
    """Count-min tier geometry: `rows` independent hash rows of `width`
    counters each, `counter_bytes` wide (8 = int64, the r13 default for
    direct constructions; 4 = saturating int32, the v2 derivation).
    Error bound (classic CM additive bound; conservative update only
    tightens it): with N charged sketch-tier hits in a window,
    P[estimate - true > e*N/width] < e^-rows."""

    rows: int = 4
    width: int = 1 << 19  # 16 MiB at rows=4 x int64
    counter_bytes: int = SKETCH_BYTES_PER_COUNTER

    def __post_init__(self):
        assert 1 <= self.rows <= len(SKETCH_SALTS), (
            f"sketch rows must be 1..{len(SKETCH_SALTS)}"
        )
        assert self.width > 0 and (self.width & (self.width - 1)) == 0, (
            "sketch width must be a power of two"
        )
        assert self.counter_bytes in (4, 8), (
            "sketch counters are int32 (4) or int64 (8)"
        )


def sketch_footprint_bytes(config: SketchConfig) -> int:
    return config.rows * config.width * config.counter_bytes


def derive_sketch_config(
    mib: int, rows: int = 0, derivation: str = "v2"
) -> SketchConfig:
    """Largest power-of-two width whose rows x width x counter_bytes
    footprint fits in `mib` MiB — the sketch sibling of
    store.derive_store_config. `rows=0` takes the derivation's default
    (v2: 2, r13: 4); an explicit row count keeps the derivation's
    counter dtype."""
    if derivation not in SKETCH_DERIVATIONS:
        raise ValueError(
            f"unknown sketch derivation {derivation!r}; "
            f"one of {sorted(SKETCH_DERIVATIONS)}"
        )
    if mib <= 0:
        raise ValueError("sketch budget must be positive MiB")
    default_rows, cbytes = SKETCH_DERIVATIONS[derivation]
    rows = rows or default_rows
    counters = (mib << 20) // (rows * cbytes)
    if counters < 1:
        raise ValueError(
            f"sketch budget {mib} MiB holds no counters at {rows} rows"
        )
    width = 1 << (counters.bit_length() - 1)
    return SketchConfig(rows=rows, width=width, counter_bytes=cbytes)


def new_sketch(config: SketchConfig):
    """Fresh zeroed device sketch (kernels.Sketch). Lazy jax import:
    this module's host-side classes must stay importable without
    touching the device runtime."""
    import jax.numpy as jnp

    from gubernator_tpu.core.kernels import Sketch

    dtype = jnp.int32 if config.counter_bytes == 4 else jnp.int64
    return Sketch(data=jnp.zeros((config.rows, config.width), dtype))


def window_id_np(engine_now: int, durations: np.ndarray) -> np.ndarray:
    """Fixed-window id per request: engine-ms `now` // duration (floored
    at 1ms so a zero/negative duration cannot divide by zero — such
    requests never reach the sketch anyway)."""
    d = np.maximum(np.asarray(durations, np.int64), 1)
    return np.asarray(engine_now, np.int64) // d


def sketch_indices_np(
    key_hash: np.ndarray, window_id: np.ndarray, config: SketchConfig
) -> np.ndarray:
    """int64[rows, n] counter index per (key, window) — the host twin of
    the device indexing in core/kernels.py (bit-identical, test-pinned).
    One mix binds the window id into the key hash; per-row salts then
    derive independent indices."""
    from gubernator_tpu.core import hashing

    kh = np.asarray(key_hash, np.uint64)
    wid = np.asarray(window_id, np.uint64)
    base = hashing.mix64(kh ^ (wid * np.uint64(WINDOW_MIX)))
    out = np.empty((config.rows, kh.shape[0]), np.int64)
    mask = np.uint64(config.width - 1)
    for r in range(config.rows):
        hr = hashing.mix64(base ^ np.uint64(SKETCH_SALTS[r]))
        out[r] = (hr & mask).astype(np.int64)
    return out


def _popcount64(x: np.ndarray) -> np.ndarray:
    """SWAR popcount over uint64 (numpy<2 has no bitwise_count)."""
    x = x - ((x >> np.uint64(1)) & np.uint64(0x5555555555555555))
    x = (x & np.uint64(0x3333333333333333)) + (
        (x >> np.uint64(2)) & np.uint64(0x3333333333333333)
    )
    x = (x + (x >> np.uint64(4))) & np.uint64(0x0F0F0F0F0F0F0F0F)
    return (x * np.uint64(0x0101010101010101)) >> np.uint64(56)


class HyperLogLog:
    """Fixed-memory distinct-count estimator over uint64 hashes.

    Standard HLL with linear-counting small-range correction; typical
    error ~1.04/sqrt(m) (p=14 -> ~0.8%). Thread-safe.
    """

    def __init__(self, p: int = 14):
        assert 4 <= p <= 18
        self.p = p
        self.m = 1 << p
        self._reg = np.zeros(self.m, np.uint8)
        self._lock = threading.Lock()

    def add_hashes(self, hashes: np.ndarray) -> None:
        """Fold a batch of uint64 key hashes into the registers."""
        if hashes.size == 0:
            return
        if hashes.size <= 16:
            # small-batch fast path: plain ints beat numpy's per-op
            # overhead by ~10x at serving-RPC sizes
            w = 64 - self.p
            with self._lock:
                for v in hashes.tolist():
                    idx = v >> (64 - self.p)
                    rem = (v << self.p) & 0xFFFFFFFFFFFFFFFF
                    rho = 65 - rem.bit_length() if rem else w + 1
                    if rho > self._reg[idx]:
                        self._reg[idx] = rho
            return
        h = hashes.astype(np.uint64, copy=False)
        idx = (h >> np.uint64(64 - self.p)).astype(np.int64)
        w = 64 - self.p
        rem = h << np.uint64(self.p)  # remaining bits at the top
        # leading zeros among the w bits via smear + popcount
        x = rem.copy()
        for s in (1, 2, 4, 8, 16, 32):
            x |= x >> np.uint64(s)
        clz = (np.uint64(64) - _popcount64(x)).astype(np.uint8)
        rho = np.where(rem == 0, w + 1, clz + 1).astype(np.uint8)
        with self._lock:
            np.maximum.at(self._reg, idx, rho)

    def estimate(self) -> int:
        with self._lock:
            reg = self._reg.copy()
        m = float(self.m)
        raw = (
            _ALPHA_INF
            * m
            * m
            / float(np.sum(np.exp2(-reg.astype(np.float64))))
        )
        zeros = int(np.count_nonzero(reg == 0))
        if raw <= 2.5 * m and zeros > 0:
            return int(round(m * np.log(m / zeros)))  # linear counting
        return int(round(raw))

    def reset(self) -> None:
        with self._lock:
            self._reg.fill(0)

    def merge(self, other: "HyperLogLog") -> None:
        assert self.p == other.p
        with self._lock, other._lock:
            np.maximum(self._reg, other._reg, out=self._reg)


class SpaceSaving:
    """Top-K heavy hitters with bounded overestimate (stream-summary).

    `observe` pre-aggregates a batch, then folds it in: known keys add
    their weight; unknown keys replace the current minimum (inheriting its
    count as the error bound) once capacity is reached. Thread-safe.
    """

    def __init__(self, capacity: int = 256):
        self.capacity = capacity
        self._counts: Dict[str, int] = {}
        self._errs: Dict[str, int] = {}
        # optional per-key payload (the sketch promoter stores the
        # candidate's last-seen (limit, duration) here); evicted with
        # its key, so bounded by `capacity`
        self._payload: Dict = {}
        self.total = 0
        self._lock = threading.Lock()

    def observe(self, keys: List[str]) -> None:
        if not keys:
            return
        agg: Dict[str, int] = {}
        for k in keys:
            agg[k] = agg.get(k, 0) + 1
        self.observe_weighted(agg)

    def observe_weighted(
        self, agg: Dict, payloads: Optional[Dict] = None
    ) -> None:
        """Fold a pre-aggregated {key: weight} batch in (keys may be any
        hashable — the sketch promoter uses uint64 key-hash ints).
        `payloads` optionally records a per-key payload for keys that
        end up tracked (last write wins).

        Replacement runs as a HEAP cascade — one heapify per call plus
        O(log capacity) per evicting insert — instead of the historical
        O(capacity) min-scan per new key, which measured 10x of serving
        throughput away once the r13 promoter hook started folding
        dispatch-sized batches on the submit thread. Semantics are the
        classic per-item cascade's: each new key replaces the CURRENT
        minimum (which may be a key inserted earlier in this same
        call) and inherits its count as the error floor, so an
        established heavy hitter can never be displaced by a flood of
        singletons — the floor only creeps up one weight at a time."""
        if not agg:
            return
        with self._lock:
            self.total += sum(agg.values())
            counts, errs = self._counts, self._errs
            new = []
            for k, w in agg.items():
                if k in counts:
                    counts[k] += w
                    if payloads is not None and k in payloads:
                        self._payload[k] = payloads[k]
                else:
                    new.append((k, w))
            i = 0
            while i < len(new) and len(counts) < self.capacity:
                k, w = new[i]
                counts[k] = w
                errs[k] = 0
                if payloads is not None and k in payloads:
                    self._payload[k] = payloads[k]
                i += 1
            if i < len(new):
                import heapq

                # counts are final for surviving keys at this point, so
                # the heap has exactly one live entry per key; cascade
                # insertions push their own entries back (they may be
                # re-evicted by later new keys, exactly like the
                # per-item original)
                heap = [(c, k) for k, c in counts.items()]
                heapq.heapify(heap)
                for k, w in new[i:]:
                    while True:
                        floor, vk = heapq.heappop(heap)
                        if counts.get(vk) == floor:
                            break  # live entry (defensive: see above)
                    del counts[vk]
                    errs.pop(vk, None)
                    self._payload.pop(vk, None)
                    counts[k] = floor + w
                    errs[k] = floor
                    heapq.heappush(heap, (floor + w, k))
                    if payloads is not None and k in payloads:
                        self._payload[k] = payloads[k]

    def payload(self, key):
        with self._lock:
            return self._payload.get(key)

    def decay(self, shift: int = 1) -> None:
        """Halve (>> shift) every tracked count/err — the streaming
        demotion half of the promoter: without decay a formerly-hot key
        rides its historical count forever and the top-K can never turn
        over under churn. Keys decayed to zero are dropped entirely
        (full demotion)."""
        with self._lock:
            dead = []
            for k in self._counts:
                c = self._counts[k] >> shift
                if c <= 0:
                    dead.append(k)
                else:
                    self._counts[k] = c
                    self._errs[k] = self._errs.get(k, 0) >> shift
            for k in dead:
                del self._counts[k]
                self._errs.pop(k, None)
                self._payload.pop(k, None)

    def top(self, n: int = 20) -> List[Tuple[str, int, int]]:
        """[(key, count, err)] sorted hot-first. count-err is a lower
        bound on the key's true frequency."""
        with self._lock:
            items = sorted(
                self._counts.items(), key=lambda kv: kv[1], reverse=True
            )[:n]
            return [(k, c, self._errs.get(k, 0)) for k, c in items]

    def top_with_payload(self, n: int = 20) -> List[Tuple]:
        """[(key, count, err, payload)] sorted hot-first; payload is
        None for keys observed without one."""
        with self._lock:
            items = sorted(
                self._counts.items(), key=lambda kv: kv[1], reverse=True
            )[:n]
            return [
                (k, c, self._errs.get(k, 0), self._payload.get(k))
                for k, c in items
            ]

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._errs.clear()
            self._payload.clear()
            self.total = 0


#: at or under this many items (HyperLogLog.add_hashes' own threshold)
#: a batch's hashes go into the native fold as plain ints in a ctypes
#: array: a 2-item gRPC call pays for no numpy array's address
_SMALL = 16
_SMALL_HASHES = [ctypes.c_uint64 * n for n in range(_SMALL + 1)]


class NativeHotKeys:
    """SpaceSaving's summary of str keys behind libguberhash.so: slots
    of (key hash, count, err, the key's bytes) that one native call per
    batch folds with the GIL released (guberhash.cc guber_traffic_fold;
    TrafficStats drives it). After the same batches it tracks the same
    keys with the same count and err as SpaceSaving — the class above
    is its oracle (tests/test_traffic_native.py) and what TrafficStats
    holds where the library is not built. Strings are built here, at
    scrape time, for the keys asked for. `lock` is TrafficStats' one
    lock: held across every call on the handle."""

    _handle = None

    def __init__(self, lib, capacity: int, lock):
        if capacity < 1:
            raise ValueError(f"capacity {capacity}: at least one slot")
        self.capacity = capacity
        self._lib = lib
        self._lock = lock
        self._handle = lib.hotkeys_new(capacity)
        # held here: at interpreter exit the module's names are gone
        # before the last summary is
        self._free = lib.hotkeys_free

    def __del__(self):
        if self._handle is not None:
            self._free(self._handle)

    def total_and_top(self, n: int = 20):
        """(items observed, [(key, count, err)] hot-first) of ONE
        moment: both read under the lock in one export."""
        with self._lock:
            total, counts, errs, offsets, keys = self._lib.hotkeys_export(
                self._handle
            )
        order = np.argsort(-counts, kind="stable")[: max(n, 0)]
        return total, [
            (
                keys[offsets[i] : offsets[i + 1]].decode(),
                int(counts[i]),
                int(errs[i]),
            )
            for i in order.tolist()
        ]

    @property
    def total(self) -> int:
        return self.total_and_top(0)[0]

    def top(self, n: int = 20) -> List[Tuple[str, int, int]]:
        return self.total_and_top(n)[1]

    def reset(self) -> None:
        with self._lock:
            self._lib.hotkeys_reset(self._handle)


def _fold_lib():
    """The native library, whose guber_traffic_fold is the observers'
    fold, or None where it is absent."""
    from gubernator_tpu.core.hashing import native_lib

    return native_lib()


class TrafficStats:
    """Per-instance traffic observability: distinct keys + hot keys.

    One batch is one fold of both sketches. Where libguberhash.so
    loaded it is ONE native call on columns with the GIL released
    (`implementation` "native": `hot` a NativeHotKeys, the HLL's
    registers still this object's numpy array); anywhere else, and with
    `native=False`, the Python classes above fold it ("python") — same
    registers, same tracked keys, counts and errs either way.
    `native_folds` / `python_folds` count the batches each folded
    (plain ints, exported at scrape: traffic_*_folds_total)."""

    def __init__(
        self, hll_p: int = 14, top_capacity: int = 256, native: bool = True
    ):
        self.hll = HyperLogLog(hll_p)
        self._lib = _fold_lib() if native else None
        if self._lib is None:
            self.hot = SpaceSaving(top_capacity)
        else:
            # one lock for the registers and the summary: held across
            # the call, which gives the GIL up
            self.hot = NativeHotKeys(
                self._lib, top_capacity, self.hll._lock
            )
            self._reg = self.hll._reg.ctypes.data
        self.native_folds = 0
        self.python_folds = 0

    @property
    def implementation(self) -> str:
        return "python" if self._lib is None else "native"

    def observe(
        self,
        keys: List[str],
        hashes: np.ndarray,
        packed: Optional[bytes] = None,
    ) -> None:
        """Fold one batch: `hashes[i]` the slot hash of `keys[i]`.
        `packed` is the same keys as UTF-8 joined by NUL, where the
        caller holds them so (the GEB door's native parse): the native
        fold then reads no `keys` at all."""
        if not keys:
            return
        lib = self._lib
        if lib is None:
            self.python_folds += 1
            self.hll.add_hashes(hashes)
            self.hot.observe(keys)
            return
        self.native_folds += 1
        offsets = None
        if packed is None:
            if len(keys) != hashes.shape[0]:
                raise ValueError(
                    f"{len(keys)} keys for {hashes.shape[0]} hashes"
                )
            # one join and one encode, no step per key; a key that
            # holds a NUL itself (the object path serves those) cannot
            # be split again, so such a batch is cut by offsets
            packed = "\x00".join(keys).encode()
            if packed.count(b"\x00") != len(keys) - 1:
                packed, offs = lib._pack(keys)
                offsets = offs.ctypes.data
        self._fold(self.hot._handle, hashes, packed, offsets)

    def _fold(self, handle, hashes: np.ndarray, packed, offsets) -> None:
        n = hashes.shape[0]
        if n <= _SMALL:
            at = _SMALL_HASHES[n](*hashes.tolist())
        else:
            hashes = np.ascontiguousarray(hashes, np.uint64)
            at = hashes.ctypes.data
        with self.hll._lock:
            self._lib.traffic_fold(
                handle, at, n, packed, offsets, self._reg, self.hll.p
            )

    def observe_hashes(self, hashes: np.ndarray) -> None:
        """Hash-only observation (edge fast path: key strings never
        reach Python). Distinct-key estimation stays exact; hot-key
        NAMES are unavailable for this traffic by design."""
        if not hashes.size:
            return
        if self._lib is None:
            self.python_folds += 1
            self.hll.add_hashes(hashes)
            return
        self.native_folds += 1
        self._fold(None, hashes, None, None)

    def snapshot(self, top_n: int = 20) -> dict:
        if self._lib is None:
            total, top = self.hot.total, self.hot.top(top_n)
        else:
            total, top = self.hot.total_and_top(top_n)
        return {
            "distinct_keys_estimate": self.hll.estimate(),
            "observed_total": total,
            "hot_keys": [
                {"key": k, "count": c, "max_overestimate": e}
                for k, c, e in top
            ],
        }
