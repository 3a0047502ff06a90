"""Host-side over-limit shed cache: answer sticky verdicts before the device.

Under the Zipf workloads the ROADMAP targets, the keys that dominate
traffic are exactly the ones sitting over limit — and the token-bucket
kernel makes their verdict *sticky*: an existing token entry whose
remaining is 0 answers every hit-carrying request with exactly
(OVER_LIMIT, stored_limit, remaining=0, stored_reset_time) and mutates
nothing until the window expires (kernels.py decide_presorted: rem_vis
== 0 forces the OVER branch; the writeback re-stores identical values;
oracle.token_bucket's `remaining == 0` path is the same fixed point).
Today every one of those hits still pays the full enqueue -> prep ->
merge -> dispatch -> device round trip. This module is the standard
scalable-rate-limiter move (Raghavan et al., arXiv:2602.11741): a tiny
bounded host cache of those frozen verdicts, consulted BEFORE a request
enters the batcher, absorbing the hot head of the skew.

Shedding is gated to the cases where the cached verdict is provably
byte-identical to what the device would return:

- token bucket only — a leaky bucket refills continuously, so its
  OVER_LIMIT verdict (and reset_time = now + rate) changes every
  millisecond and must never be shed;
- `hits > 0` only — peeks are read-only probes and always reach the
  device (they are also how the GLOBAL broadcast loop peeks status);
- request limit/duration must equal the cached window's params — the
  stores never rewrite an existing window's params (kernels.py
  new_limit/new_duration; oracle keeps the cached resp), so an entry
  created under other params is answered by the device from the STORED
  params and the mismatched request must go see it;
- `now < reset_time` — the first post-reset hit must reach the device
  (it recreates the window there).

Population is device-authoritative: only a device/oracle response with
status == OVER_LIMIT and remaining == 0 whose params echo the request's
inserts an entry; any other response for a cached fingerprint DROPS it
(an under-limit or param-drifted response proves the cached window is
gone — recreated, evicted, or algorithm-switched). Invalidation:

- entries lazily expire at `reset_time`, compared against the same
  unix-ms clock the engines feed their EpochClock (decide converts
  engine-ms responses back with the identical epoch arithmetic, so the
  unix-domain comparison is exactly the device's `g_exp >= now` check);
- a `generation` check against the engine's reset counter
  (core/engine.py reset_generation) clears the whole cache when the
  engine wipes its store (clock jump past the rebase envelope);
- `purge()` is called for every key an UpdatePeerGlobals install or
  update_globals broadcast touches (serve/instance.py), so GLOBAL mode
  cannot serve a stale verdict after an owner-side reset;
- a LEAKY request for a cached fingerprint drops the entry when its
  response is observed (algorithm switch recreates the window).

Accepted staleness (documented, bounded by the original window): an
entry EVICTED from the device store by way pressure, or recreated by
another NODE's algorithm-switch traffic, keeps shedding OVER_LIMIT
until its reset_time — the fail-closed direction for a rate limiter,
and the same over-admission-adjacent envelope the store's eviction
counters already flag.

Structure: two tiers consult it in two shapes. The instance tier
probes one request at a time (lookup_resp / observe_resps): a dict of
fingerprint -> (limit, duration, reset_time, slot) in LRU order
answers those, O(1) each. The bridge tier screens whole thousand-item
frames (screen_fields / observe_fields): every verdict also owns a
slot of four numpy columns, found through a sorted index of the
fingerprints plus a small overlay of those bound since the last sort.
The cached VALUES change all the time — a one-second window gets a
new reset_time every second, verdicts are confirmed, contradicted,
purged — and each such change is a scalar write to a slot; only a
change of the cached KEY SET reaches the index, one argsort per
OVERLAY_MAX new fingerprints (class docstring; counted by
index_uses / index_rebuilds). Nothing on the frame path iterates over
the entries in Python. The two array methods are ONE call each into
libguberhash.so with the GIL released (guber_shed_screen,
guber_shed_observe: the searches, the gates, the answers, the residue's
rows, the stitch); where the library is absent (core/hashing
native_lib, the one fact every native selection reads) their numpy
twins run the same rules call by call, and the tests hold the two to
each other. The dictionary, its LRU order and every write to a slot are
Python's either way.

Thread model: event-loop confined like the rest of the serving tier
(the bridge and instance both consult from the loop); the only
cross-thread reader is the /metrics scrape, which reads plain ints.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from gubernator_tpu.api.types import (
    Algorithm,
    RateLimitReq,
    RateLimitResp,
    Status,
    millisecond_now,
    over_limit_resp,
)
from gubernator_tpu.core.algorithms import ALGO_TOKEN, SHEDDABLE_ALGOS
from gubernator_tpu.core.hashing import native_lib

_hn = native_lib()

# r15 interplay audit: every consult and populate path below is gated
# on Algorithm.TOKEN_BUCKET because the frozen-verdict fixed point this
# cache serves exists ONLY there — a leaky reset_time refills
# continuously, a sliding blend's weight decays every millisecond, and
# a GCRA TAT drains every millisecond, so none of their OVER verdicts
# is provably current after the response that produced it. This pin
# keeps the registry (core/algorithms.py SHEDDABLE_ALGOS) and this
# module from drifting apart: marking a new algorithm sheddable there
# without teaching lookup/screen_fields/_observe_one its fixed point
# fails at import, not silently in production.
assert SHEDDABLE_ALGOS == {ALGO_TOKEN}, (
    "shed cache only understands the token bucket's frozen verdicts; "
    "extend serve/shedcache.py before marking another algorithm "
    "sheddable in core/algorithms.py"
)

#: default LRU bound (GUBER_SHED_CACHE_KEYS): sized to the hot head a
#: Zipf workload can keep over limit at once, not the whole key space
DEFAULT_KEYS = 1 << 16

#: rough per-entry host footprint used by the boot-time lint, measured
#: on CPython 3.10 at the default bound: ~260 B of OrderedDict node,
#: uint64 key and 4-int tuple, 32 B of slot columns, 16 B of sorted
#: index, the columns' doubling slack
ENTRY_BYTES = 320

#: per-call bound on observe_fields' population walk (uncached frozen
#: verdicts); correctness rows (cached fingerprints) are never capped
OBSERVE_INSERT_CAP = 512

#: fingerprints bound to a slot since the last sort wait in a small
#: overlay (searched beside the sorted index); one more than this and
#: the next consult folds them in, by one argsort of the fingerprint
#: column
OVERLAY_MAX = 256

#: slot columns start this long and double up to the capacity
_FIRST_SLOTS = 1024

#: a frame's columns as the screen reads them, with the dtypes the
#: doors' parsers give them (api/columns.py DECIDE_FIELDS, and the
#: batcher's `gnp` where a caller has it)
_COLUMNS = (
    ("key_hash", np.uint64), ("hits", np.int64), ("limit", np.int64),
    ("duration", np.int64), ("algo", np.int32), ("gnp", np.bool_),
)

#: _native_index of a cache that holds nothing
_NO_INDEX = ((None, None, 0, None, None, 0, None), None, None, None)


def footprint_mib(keys: int) -> float:
    return keys * ENTRY_BYTES / (1 << 20)


def lint_footprint(keys: int, store_capacity: int = 0) -> str:
    """Boot-time sizing lint, the shed-cache sibling of the store
    sizing pass (core/store.check_store_budget): returns a warning
    string ('' = fine). The cache holds only the over-limit head, so a
    bound beyond the store's own entry capacity can never be used."""
    if store_capacity and keys > store_capacity:
        return (
            f"GUBER_SHED_CACHE_KEYS={keys} exceeds the store's entry "
            f"capacity ({store_capacity}); the shed cache mirrors "
            f"store-resident over-limit windows, so the excess "
            f"({footprint_mib(keys - store_capacity):.0f} MiB) can "
            f"never hold a live verdict — lower it"
        )
    if footprint_mib(keys) > 512:
        return (
            f"GUBER_SHED_CACHE_KEYS={keys} ~ {footprint_mib(keys):.0f} "
            f"MiB of host memory for shed verdicts; the cache only "
            f"needs to cover the over-limit HEAD of the key "
            f"distribution, not the key space"
        )
    return ""


class ShedCache:
    """Bounded LRU of frozen token-bucket over-limit verdicts.

    Keys are the uint64 slot-hash fingerprints the device store is
    addressed by (core/hashing.slot_hash_batch) — shared between the
    instance tier (which hashes key strings once per batch anyway) and
    the bridge tier (whose fast frames arrive pre-hashed).

    Every live verdict owns one SLOT of four numpy columns
    (fingerprint, limit, duration, reset_time): the table the
    array screen reads. `_entries` maps fingerprint ->
    (limit, duration, reset_time, slot) in LRU order for the point
    operations, which never read a numpy scalar. A change of VALUE
    (new reset_time, confirm, drop) is a write in place; a dropped
    slot reads reset_time 0 — a live verdict's is a unix-ms instant in
    the future — which `now < reset_time` already refuses, until a
    fresh verdict is written to it. Only a change of the KEY SET
    touches the lookup index, and lazily: a fingerprint bound to a
    slot waits in an unsorted overlay of at most OVERLAY_MAX, folded
    into the sorted index by one argsort of the fingerprint column. A
    stale index row (its slot dropped or recycled since the sort) is
    harmless: a match is confirmed against the slot's own fingerprint."""

    def __init__(
        self,
        capacity: int = DEFAULT_KEYS,
        now_fn=millisecond_now,
        generation_fn=None,
    ):
        self.capacity = max(1, int(capacity))
        self.now_fn = now_fn
        # engine reset counter (backend.shed_generation); None = the
        # backend never wholesale-resets (exact backend)
        self.generation_fn = generation_fn
        self._gen = generation_fn() if generation_fn is not None else 0
        # fingerprint -> (limit, duration, reset_time_unix_ms, slot),
        # least recently inserted-or-looked-up first
        self._entries: "OrderedDict[int, Tuple[int, int, int, int]]" = (
            OrderedDict()
        )
        # the slots: a column each for fingerprint, limit, duration
        # and reset_time (0 = no live verdict here)
        n = min(self.capacity, _FIRST_SLOTS)
        self._fp = np.zeros(n, np.uint64)
        self._lim = np.zeros(n, np.int64)
        self._dur = np.zeros(n, np.int64)
        self._reset = np.zeros(n, np.int64)
        self._new_fp = np.zeros(OVERLAY_MAX, np.uint64)
        self._new_slot = np.zeros(OVERLAY_MAX, np.int64)
        self._top = 0
        self._at = None  # _native_index's addresses, and whose they are
        self.purge_all()
        # monotonic counters (ints: GIL-atomic, scrape reads them raw)
        self.hits = 0
        self.lookups = 0
        self.index_uses = 0  # screen_fields / observe_fields consults
        self.index_rebuilds = 0  # re-sorts of the index
        # index_uses by the body that served the consult: the native
        # call, or its numpy twin
        self.native_consults = 0
        self.numpy_consults = 0

    # -- lifecycle -----------------------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    def __bool__(self) -> bool:
        # an EMPTY cache must not read as "no cache": len() above would
        # otherwise make `if shed:` silently skip population
        return True

    def __contains__(self, h: int) -> bool:
        return h in self._entries

    def get(self, h: int) -> Optional[Tuple[int, int, int]]:
        """(limit, duration, reset_time) cached for a fingerprint, or
        None: a read with no side effect (no counter, no recency)."""
        e = self._entries.get(h)
        return None if e is None else e[:3]

    def refresh_generation(self) -> None:
        """Clear everything when the engine wiped its store (EpochClock
        reset_required -> engine.reset()): every cached verdict pointed
        at state that no longer exists. One int compare per screen."""
        if self.generation_fn is None:
            return
        g = self.generation_fn()
        if g != self._gen:
            self._gen = g
            self.purge_all()

    def purge(self, fingerprints) -> None:
        """Drop entries for these uint64 fingerprints (GLOBAL installs:
        the owner's broadcast replaced the replica, so the cached
        verdict is no longer provably current)."""
        for h in fingerprints:
            self._drop(int(h))

    def purge_all(self) -> None:
        self._entries.clear()
        self._reset[: self._top] = 0
        self._top = 0  # slots [0, _top) have been handed out
        self._free: List[int] = []  # dropped slots below _top
        # the sorted index: fingerprints of the slots live at the last
        # sort, and their slots
        self._ix_fp = np.zeros(0, np.uint64)
        self._ix_slot = np.zeros(0, np.int64)
        # the overlay: how many of _new_fp / _new_slot were bound since
        # the sort (past OVERLAY_MAX: the next consult re-sorts), and
        # those as sorted arrays, with their addresses, once a consult
        # needed them
        self._new = 0
        self._overlay = None

    def reset_counters(self) -> None:
        """Zero the counters (entries stay live) — the profiler scopes
        measurement windows with /v1/debug/stages?reset=1, and
        per-window hit and rebuild rates need the same scoping."""
        self.hits = 0
        self.lookups = 0
        self.index_uses = 0
        self.index_rebuilds = 0
        self.native_consults = 0
        self.numpy_consults = 0

    @property
    def screen_implementation(self) -> str:
        """Which body screen_fields and observe_fields run: "native"
        (one libguberhash.so call each) or "numpy"."""
        return "numpy" if _hn is None else "native"

    def stats(self) -> dict:
        lk = self.lookups
        return dict(
            entries=len(self._entries),
            capacity=self.capacity,
            hits=self.hits,
            lookups=lk,
            hit_rate=round(self.hits / lk, 4) if lk else 0.0,
            index_uses=self.index_uses,
            index_rebuilds=self.index_rebuilds,
            native_consults=self.native_consults,
            numpy_consults=self.numpy_consults,
            generation=self._gen,
        )

    # -- slots and their index -----------------------------------------------

    def _store(
        self, h: int, limit: int, duration: int, reset_time: int
    ) -> None:
        """Write one frozen verdict: in place when the fingerprint is
        cached, else into a slot of its own — the LRU entry's at the
        bound, a dropped one, or the next never used."""
        entries = self._entries
        e = entries.get(h)
        if e is not None:
            slot = e[3]
            if e[2] != reset_time or e[0] != limit or e[1] != duration:
                entries[h] = (limit, duration, reset_time, slot)
                self._lim[slot] = limit
                self._dur[slot] = duration
                self._reset[slot] = reset_time
            entries.move_to_end(h)
            return
        if len(entries) >= self.capacity:
            slot = entries.popitem(last=False)[1][3]
        elif self._free:
            slot = self._free.pop()
        else:
            slot = self._top
            if slot == self._fp.shape[0]:
                self._grow()
            self._top = slot + 1
        entries[h] = (limit, duration, reset_time, slot)
        self._fp[slot] = h
        self._lim[slot] = limit
        self._dur[slot] = duration
        self._reset[slot] = reset_time
        k = self._new
        if k < OVERLAY_MAX:
            self._new_fp[k] = h
            self._new_slot[k] = slot
        self._new = k + 1
        self._overlay = None

    def _drop(self, h: int) -> None:
        """Forget a fingerprint if it is cached; its slot can never
        shed again until _store writes a fresh verdict to it."""
        e = self._entries.pop(h, None)
        if e is not None:
            self._reset[e[3]] = 0
            self._free.append(e[3])

    def _grow(self) -> None:
        n = self._fp.shape[0]
        more = min(self.capacity, 2 * n) - n
        self._fp, self._lim, self._dur, self._reset = (
            np.concatenate([col, np.zeros(more, col.dtype)])
            for col in (self._fp, self._lim, self._dur, self._reset)
        )

    def _resort(self) -> None:
        """Rebuild the sorted index from the live slots' fingerprint
        column and empty the overlay."""
        self.index_rebuilds += 1
        live = np.flatnonzero(self._reset[: self._top])
        fp = self._fp[live]
        order = np.argsort(fp)
        self._ix_fp = fp[order]
        self._ix_slot = live[order]
        self._new = 0
        self._overlay = None

    def _consult(self):
        """Count one consult of the index and bring it up to date:
        an overlay past OVERLAY_MAX (or a cache that has no index yet)
        is folded in by one sort, and an overlay that gained a binding
        since the last consult is sorted again. Returns the overlay —
        (fingerprints sorted, their slots, the two arrays' addresses) —
        or None where it holds nothing. Callers have checked that the
        cache is not empty."""
        self.index_uses += 1
        k = self._new
        if k > OVERLAY_MAX or not self._ix_fp.shape[0]:
            self._resort()
            k = 0
        if not k:
            return None
        if self._overlay is None:
            fp = self._new_fp[:k]
            # stable: of equal fingerprints the LAST is the binding in
            # force, and the searches take the last
            order = np.argsort(fp, kind="stable")
            ov_fp, ov_slot = fp[order], self._new_slot[:k][order]
            self._overlay = (
                ov_fp, ov_slot, ov_fp.ctypes.data, ov_slot.ctypes.data
            )
        return self._overlay

    def _find(self, kh):
        """(slot int64[n], found bool[n]) for a frame's fingerprints,
        in numpy: one searchsorted against the sorted index, one
        against the overlay where it has anything (its latest binding
        of a fingerprint wins), and the slot's own fingerprint as the
        proof. `found` rows may be dropped slots (reset_time 0).
        native/guberhash.cc ShedIndex::find is this rule for one row."""
        overlay = self._consult()
        self.numpy_consults += 1
        ix_fp = self._ix_fp
        pos = np.searchsorted(ix_fp, kh)
        np.minimum(pos, ix_fp.shape[0] - 1, out=pos)
        slot = self._ix_slot[pos]
        if overlay is not None:
            ov_fp, ov_slot = overlay[:2]
            # side="right" - 1: the LAST of equal fingerprints, the
            # binding in force (-1 wraps to a row the compare refuses)
            j = np.searchsorted(ov_fp, kh, side="right") - 1
            hit = np.flatnonzero(ov_fp[j] == kh)
            slot[hit] = ov_slot[j[hit]]
        return slot, self._fp[slot] == kh

    def _native_index(self):
        """One consult's view of the cache for the native calls, arrays
        as addresses: (the lookup structure guberhash.cc ShedIndex
        takes — index fingerprints, index slots, their length, overlay
        fingerprints, overlay slots, their length, the slots'
        fingerprint column —, then the slots' limit, duration and
        reset_time columns). The cache keeps every array alive and,
        confined to the loop's thread, unchanged for the length of the
        call."""
        overlay = self._consult()
        self.native_consults += 1
        at = self._at
        if at is None or at[0] is not self._ix_fp or at[1] is not self._fp:
            # the index pair is replaced as one (_resort, purge_all) and
            # so are the four columns (_grow): the held arrays tell
            # whether the addresses are still theirs
            at = self._at = (self._ix_fp, self._fp) + tuple(
                a.ctypes.data for a in (
                    self._ix_fp, self._ix_slot, self._fp, self._lim,
                    self._dur, self._reset,
                )
            )
        _, _, ix_fp, ix_slot, fp, lim, dur, reset = at
        ov = (None, None, 0) if overlay is None else (
            overlay[2], overlay[3], overlay[0].shape[0]
        )
        find = (ix_fp, ix_slot, self._ix_fp.shape[0], *ov, fp)
        return find, lim, dur, reset

    # -- consult -------------------------------------------------------------

    def lookup(
        self, h: int, limit: int, duration: int, now: Optional[int] = None
    ) -> Optional[int]:
        """reset_time for a sheddable verdict, or None. The caller has
        already gated algorithm == TOKEN_BUCKET and hits > 0; this
        checks entry existence, param match, and expiry. A param
        mismatch is a MISS, not a drop — the mismatched request goes to
        the device, and its response drops the entry only if the stored
        window really drifted (observe())."""
        self.lookups += 1
        e = self._entries.get(h)
        if e is None:
            return None
        if now is None:
            now = self.now_fn()
        if now >= e[2]:
            # expired: the first post-reset hit must reach the device
            self._drop(h)
            return None
        if e[0] != limit or e[1] != duration:
            return None
        self._entries.move_to_end(h)
        self.hits += 1
        return e[2]

    def lookup_resp(
        self, h: int, req: RateLimitReq, now: Optional[int] = None
    ) -> Optional[RateLimitResp]:
        """Instance-tier consult: the full shed gate over a request
        object. Returns the verdict response (a fresh object — callers
        stamp metadata) or None."""
        if req.hits <= 0 or req.algorithm != Algorithm.TOKEN_BUCKET:
            return None
        reset = self.lookup(h, req.limit, req.duration, now)
        if reset is None:
            return None
        return over_limit_resp(req.limit, reset)

    def screen_fields(self, fields: Dict, now: Optional[int] = None):
        """Bridge-tier consult over one frame's dense arrays
        (key_hash/hits/limit/duration/algo[/gnp]). Returns None when
        nothing sheds, else (shed_mask bool[n], (status, limit,
        remaining, reset) int64[n] with the shed rows filled — residue
        rows are zero and overwritten by the device results —, the
        indices int64[r] of the rows NOT shed, and those rows' columns
        as a dict like `fields`: what goes on to the batcher).

        One native call (guber_shed_screen, GIL released) where
        libguberhash.so is there, else _screen_numpy: the same gates
        row for row, a frame's cost whatever the cache holds and
        however often its values change. The numpy body is ~0.2-0.3
        ms of work a thousand-row frame but some thirty calls that
        each give the GIL up, and was read as ~0.9 ms of `shed` span a
        side under load (PERF.md section 6, PR 48).
        Two deliberate approximations vs lookup(): screen hits do not
        refresh LRU recency (entries refresh on insert; with the
        bound sized to the over-limit head that's ample), and expired
        entries are skipped, not deleted (lookup()/observe/insert
        pressure prunes them)."""
        if not self._entries:
            return None
        if now is None:
            now = self.now_fn()
        cols = {
            k: np.ascontiguousarray(fields[k], t)
            for k, t in _COLUMNS
            if fields.get(k) is not None
        }
        if _hn is None:
            return self._screen_numpy(cols, now)
        find, lim, dur, reset = self._native_index()
        shed, eligible, *screened = _hn.shed_screen(
            cols, now, (*find, lim, dur, reset)
        )
        self.lookups += eligible
        self.hits += shed
        return tuple(screened) if shed else None

    def _screen_numpy(self, cols: Dict, now: int):
        """screen_fields' body where libguberhash.so is absent, and
        the tests' reference for the native call: _find's searchsorted,
        gathers from the slot columns and elementwise gates."""
        kh = cols["key_hash"]
        slot, found = self._find(kh)
        eligible = (
            (cols["algo"] == int(Algorithm.TOKEN_BUCKET))
            & (cols["hits"] > 0)
        )
        gnp = cols.get("gnp")
        if gnp is not None:
            # replica reads answer from the live replica entry;
            # screening them here would skip the replica-miss
            # local-processing path — leave them to the device
            eligible &= ~gnp
        limit = cols["limit"]
        reset = self._reset[slot]
        mask = (
            found
            & eligible
            & (self._lim[slot] == limit)
            & (self._dur[slot] == cols["duration"])
            & (now < reset)
        )
        shed = int(mask.sum())
        self.lookups += int(eligible.sum())
        self.hits += shed
        if not shed:
            return None
        status = np.where(
            mask, int(Status.OVER_LIMIT), 0
        ).astype(np.int64)
        limit_out = np.where(mask, limit, 0)
        remaining = np.zeros(kh.shape[0], np.int64)
        reset_out = np.where(mask, reset, 0)
        keep = np.flatnonzero(~mask)
        return (
            mask, (status, limit_out, remaining, reset_out), keep,
            {k: v[keep] for k, v in cols.items()},
        )

    # -- populate / invalidate ----------------------------------------------

    def seed(
        self,
        h: int,
        limit: int,
        duration: int,
        reset_time: int,
        now: Optional[int] = None,
    ) -> None:
        """Promoter feed (r13, serve/promoter.py): install a frozen
        verdict for a hot key whose PROMOTION just wrote an over-limit
        token window (remaining=0, sticky over, this reset_time) into
        the device store — the cached verdict matches store state by
        construction, the same authority as observing the device's own
        response. Expired seeds are ignored."""
        if now is None:
            now = self.now_fn()
        if now >= reset_time:
            return
        self._store(int(h), int(limit), int(duration), int(reset_time))

    def _observe_one(
        self,
        h: int,
        req_hits: int,
        req_limit: int,
        req_duration: int,
        req_algo: int,
        r_status: int,
        r_limit: int,
        r_remaining: int,
        r_reset: int,
        now: int,
    ) -> None:
        if req_algo != int(Algorithm.TOKEN_BUCKET):
            # a leaky request recreates a stored token window
            # (algorithm switch, kernels.py mismatch path): whatever we
            # cached for this fingerprint no longer exists
            self._drop(h)
            return
        frozen = (
            r_status == int(Status.OVER_LIMIT) and r_remaining == 0
        )
        if frozen and r_limit == req_limit and now < r_reset:
            # the frozen fixed point: stored remaining is 0 and sticky,
            # and every same-param hit until r_reset echoes this exact
            # response (module docstring)
            self._store(h, req_limit, req_duration, r_reset)
            return
        e = self._entries.get(h)
        if e is None:
            return
        if frozen and r_limit == e[0] and r_reset == e[2]:
            # the response ECHOES the cached window (the device answers
            # an existing window's hits with the STORED limit, so a
            # param-mismatched request confirms the entry rather than
            # disproving it — dropping here would let mixed-param
            # traffic thrash the cache on exactly the hottest keys)
            return
        # a response that contradicts the cached window (under limit,
        # different stored params, different reset) proves it is gone —
        # reset, evicted, or rewritten
        self._drop(h)

    def observe_resps(
        self,
        fingerprints: Sequence[int],
        reqs: Sequence[RateLimitReq],
        resps: Sequence[RateLimitResp],
        now: Optional[int] = None,
    ) -> None:
        """Object-path population (instance tier): one device/owner
        response per request. Error and degraded responses are skipped
        entirely — they carry no authoritative window state."""
        if now is None:
            now = self.now_fn()
        for h, r, resp in zip(fingerprints, reqs, resps):
            if resp.error or resp.metadata.get("degraded"):
                continue
            self._observe_one(
                int(h), r.hits, r.limit, r.duration, int(r.algorithm),
                int(resp.status), resp.limit, resp.remaining,
                resp.reset_time, now,
            )

    def observe_fields(
        self, fields: Dict, results, now: Optional[int] = None, into=None
    ) -> None:
        """Array-path population (bridge tier): `results` is the
        (status, limit, remaining, reset) tuple the batcher resolved
        for exactly these `fields` rows. `into`, where given, is
        ((status, limit, remaining, reset) int64 columns of a whole
        frame, the int64 indices of these rows in it) and the results
        are written there too: screen_fields' answers and kept rows,
        stitched on the way.

        The walk is bounded: every
        row touching a CACHED fingerprint is visited (confirm / drop /
        leaky pop — the correctness rows, pre-filtered by one
        membership test against the index), while frozen-verdict
        rows for UNCACHED fingerprints — pure population — are capped
        at OBSERVE_INSERT_CAP per call, so an over-limit-heavy frame
        whose key cardinality exceeds the cache bound cannot drag a
        ~1 ms/frame Python walk into steady state (the cost the
        array screen exists to avoid). Of those, a row that is no
        token-bucket request is left out unless the call also has a
        frozen token-bucket row of its fingerprint: _observe_one can
        only drop for it, and there is nothing to drop — a hot leaky
        key over its limit was four fifths of the rows walked (~280 a
        thousand-row frame, ~0.6 ms of this loop's Python; PERF.md
        section 6, PR 48). Which rows are walked, and the stitch, is
        one native call (guber_shed_observe, GIL released) or
        _observe_numpy; the walk itself is Python's either way: the
        rows in flight when a one-second window turned, some tens a
        frame."""
        kh = np.ascontiguousarray(fields["key_hash"], np.uint64)
        algo = fields.get("algo")
        if algo is not None:
            algo = np.ascontiguousarray(algo, np.int32)
        if _hn is None:
            rows = self._observe_numpy(kh, algo, results, into)
        else:
            # an empty cache holds no fingerprint and counts no consult
            find, _, _, reset = (
                self._native_index() if self._entries else _NO_INDEX
            )
            rows = _hn.shed_observe(
                kh, algo, results, (*find, reset), OBSERVE_INSERT_CAP, into
            )
        if not rows:
            return
        # a key's rows all land on one side of the cached split, and
        # row order is kept within each side, so last-wins semantics
        # per key survive the concat
        if now is None:
            now = self.now_fn()
        status, limit_r, remaining, reset = results
        sa = np.asarray(status)
        ra = np.asarray(remaining)
        hits = fields["hits"]
        limit = fields["limit"]
        duration = fields["duration"]
        limit_a = np.asarray(limit_r)
        reset_a = np.asarray(reset)
        token = int(Algorithm.TOKEN_BUCKET)
        for i in rows:
            self._observe_one(
                int(kh[i]), int(hits[i]), int(limit[i]),
                int(duration[i]),
                int(algo[i]) if algo is not None else token,
                int(sa[i]), int(limit_a[i]), int(ra[i]),
                int(reset_a[i]), now,
            )

    def _observe_numpy(self, kh, algo, results, into) -> List[int]:
        """The rows observe_fields walks, in walk order, and the
        stitch, where libguberhash.so is absent; the tests' reference
        for the native call."""
        status, _, remaining, _ = results
        frozen = (
            (np.asarray(status) == int(Status.OVER_LIMIT))
            & (np.asarray(remaining) == 0)
        )
        if self._entries:
            slot, found = self._find(kh)
            cached = found & (self._reset[slot] != 0)
        else:
            cached = np.zeros(kh.shape[0], bool)
        must = np.flatnonzero(cached)
        fresh = frozen & ~cached
        if algo is not None:
            # a row that is no token-bucket request can only DROP an
            # entry, and its fingerprint holds none: it matters only
            # where this call also has a frozen token-bucket row of
            # the fingerprint, which the walk may have stored by then
            token = algo == int(Algorithm.TOKEN_BUCKET)
            fresh &= token | np.isin(kh, kh[fresh & token])
        ins = np.flatnonzero(fresh)[:OBSERVE_INSERT_CAP]
        if into is not None:
            answers, keep = into
            for col, got in zip(answers, results):
                col[keep] = got
        return np.concatenate([must, ins]).tolist()


async def screened_decide(
    shed: Optional[ShedCache], fields: Dict, n: int, decide, stamp
):
    """One array group through the screen, the batcher and the cache's
    population — the array doors' shared decide (the GEB door's frames,
    serve/edge_bridge.py _decide_arrays_shed; the PeersV1 door's folded
    batches, serve/instance.py): rows whose frozen refusal is cached
    are answered here and never enqueue, `await decide(rows, count)`
    resolves the residue to its (status, limit, remaining, reset_time)
    arrays, which populate the cache and stitch back in the group's
    order. `stamp(seconds)` is handed the screen's and the stitch's
    wall time, one call for each side of the await (a GEB frame's two
    `shed` samples); with no cache (`shed` None) the group goes to
    `decide` whole. Returns the four arrays for all n rows."""
    if shed is None:
        return await decide(fields, n)
    t0 = time.monotonic()
    shed.refresh_generation()
    screened = shed.screen_fields(fields)
    stamp(time.monotonic() - t0)
    if screened is None:
        res = await decide(fields, n)
        # population is shed work too: without the stamp, a
        # cold-cache frame's observe walk would sit between the
        # device and encode spans as a coverage hole
        t1 = time.monotonic()
        shed.observe_fields(fields, res)
        stamp(time.monotonic() - t1)
        return res
    _, answers, keep, residue = screened
    if not keep.shape[0]:
        return answers
    res = await decide(residue, keep.shape[0])
    t1 = time.monotonic()
    shed.observe_fields(residue, res, into=(answers, keep))
    stamp(time.monotonic() - t1)
    return answers
