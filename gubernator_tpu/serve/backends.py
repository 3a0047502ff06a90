"""Decision backends behind the serving tier.

The serving layer (instance.py) speaks one small interface; three backends
implement it:

- ExactBackend — host LRU + exact oracle algorithms. The semantics
  reference and a sensible choice for tiny deployments.
- TpuBackend — single-device slot store + jitted decide kernel.
- MeshBackend — multi-device mesh-sharded store (key-space sharding with
  psum combine); the scale-up backend for one host with a TPU slice.
- MultiHostBackend — the same sharded store over a GLOBAL mesh spanning
  jax.distributed processes (parallel/multihost.py); only the leader
  process serves, followers run the lockstep step loop.

All backends are driven from the single serving event loop / batcher task.
Concurrency contract with the pipelined batcher: decide_submit_merged
calls are strictly serialized (one submit thread), but up to fetch_depth
decide_wait_arrays calls run CONCURRENTLY on fetch worker threads and may
overlap later decide_submit_merged/update_globals calls — safe because a wait
touches only its own handle and the engine's stats counters (which land
under EngineStats' lock), never the store or clock. Keep that split when
adding backend state; no other locking exists anywhere (the reference
instead serializes on a cache mutex, gubernator.go:237).
"""

from __future__ import annotations

from dataclasses import replace
from typing import Dict, List, Optional, Sequence, Tuple

from gubernator_tpu.api.types import (
    Algorithm,
    RateLimitReq,
    RateLimitResp,
    Status,
    hash_key,
    resps_from_columns,
)
from gubernator_tpu.core.cache import LRUCache
from gubernator_tpu.core.engine import TpuEngine
from gubernator_tpu.core.oracle import get_rate_limit
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.parallel.sharded import PartitionedEngine
from gubernator_tpu.serve import metrics
from gubernator_tpu.serve.stages import STAGES


def _count_shards(rows: int, slots: int, max_rows: int) -> None:
    metrics.MESH_SHARD_ROWS.inc(rows)
    metrics.MESH_SHARD_SLOTS.inc(slots)
    metrics.MESH_SHARD_MAX_ROWS.inc(max_rows)


# every engine a backend serves from times its dispatch interior
# (observe, jit_call, shard_stack) on the serving tier's stage clock
# and counts its per-shard batches into /metrics
PartitionedEngine.stage_span = staticmethod(STAGES.span)
PartitionedEngine.shard_counts = staticmethod(_count_shards)


def chain_level_keys(r: RateLimitReq):
    """(cache_key, limit, duration) per level of a chained request,
    shallow -> deep, the request's own key last. A level duration of 0
    inherits the request's. Shared by every backend's chain expansion
    so level addressing can never drift between the exact and device
    tiers."""
    rows = [
        (hash_key(r.name, lv.unique_key), lv.limit,
         lv.duration or r.duration)
        for lv in r.chain
    ]
    rows.append((r.hash_key(), r.limit, r.duration))
    return rows


def collapse_chain_responses(resps):
    """Most-restrictive-wins collapse (r15): the FIRST (shallowest)
    OVER_LIMIT level's response answers the whole chained request —
    a global refusal dominates a tenant's dominates the leaf's — else
    the leaf's response (every level admitted and was debited).
    `metadata["chain_level"]` names the refusing level's index when it
    is not the leaf."""
    pick = len(resps) - 1
    for j, resp in enumerate(resps):
        if resp.status == Status.OVER_LIMIT or resp.error:
            pick = j
            break
    out = resps[pick]
    if pick != len(resps) - 1:
        out.metadata["chain_level"] = str(pick)
    return out


class ExactBackend:
    """Host-memory exact semantics (reference algorithms over an LRU)."""

    # decide() is microseconds of pure-Python dict work: running it via
    # asyncio.to_thread costs two thread handoffs per batch (~0.3-0.5ms
    # of the GLOBAL p50 on a contended host) for zero overlap benefit.
    # The batcher executes inline on the event loop when this is set —
    # the reference likewise answers local cache hits synchronously
    # (gubernator.go:236-251). Device backends keep the thread hop: they
    # BLOCK on the device and would stall the loop.
    inline_decide = True

    def __init__(self, cache_size: int = 50_000):
        self.cache = LRUCache(cache_size)

    def decide(
        self,
        reqs: Sequence[RateLimitReq],
        gnp: Sequence[bool],
        now: Optional[int] = None,
    ) -> List[RateLimitResp]:
        out = []
        for r, is_gnp in zip(reqs, gnp):
            if is_gnp:
                item, ok = self.cache.get(r.hash_key(), now)
                if ok and isinstance(item, RateLimitResp):
                    out.append(replace(item, metadata=dict(item.metadata)))
                    continue
                if ok:
                    # algorithm switched under a GLOBAL key: drop the stale
                    # entry and reprocess (gubernator.go:181-185)
                    self.cache.remove(r.hash_key())
                # miss: process locally as if owned (gubernator.go:189-194)
            out.append(get_rate_limit(self.cache, r, now))
        return out

    def update_globals(
        self, updates: Sequence[Tuple[str, RateLimitResp]], now=None
    ) -> None:
        # cache.Add(key, status, status.reset_time) — gubernator.go:199-207
        # (`now` is unused here — expiry comes from the status — but kept
        # for interface parity with the device backends, whose epoch
        # clocks must see the caller's clock domain in tests)
        for key, status in updates:
            self.cache.add(key, status, status.reset_time)

    def stats(self) -> dict:
        s = self.cache.stats()
        return dict(size=s.size, hit=s.hit, miss=s.miss)

    def snapshot_read(self, keys, now=None):
        """Bucket-replication snapshot surface (serve/replication.py):
        per key, (limit, duration, remaining, reset_time, over) for a
        live token window, else None. NON-MUTATING via LRUCache.peek —
        no recency move, no stats, no expiry deletion — so the flush
        loop is invisible to the decision stream (replication ON == OFF
        without failures). Leaky state (_LeakyState) is out of scope.
        Token windows here don't persist the creating duration (the
        cached RateLimitResp has none); the manager backfills it from
        the dirtying request's params."""
        if now is None:
            from gubernator_tpu.api.types import millisecond_now

            now = millisecond_now()
        out = []
        for key in keys:
            v, ok = self.cache.peek(key, now)
            if not ok or not isinstance(v, RateLimitResp):
                out.append(None)
                continue
            out.append((
                v.limit,
                0,  # duration not persisted; caller backfills
                v.remaining,
                v.reset_time,
                v.status == Status.OVER_LIMIT or v.remaining == 0,
            ))
        return out

    def shed_generation(self) -> int:
        """Store-wipe epoch for the over-limit shed cache: the host LRU
        never wholesale-resets, so cached verdicts only die by their
        own expiry/purge rules."""
        return 0

    def decide_chain(
        self, reqs: Sequence[RateLimitReq], now=None
    ) -> List[RateLimitResp]:
        """Hierarchical quota chains on the host backend (r15):
        two-phase per request — peek every level, debit every level
        only if all admit, else answer from the first refusing level
        (most-restrictive-wins; no level consumed anything). Matches
        the device kernel's no-partial-debit CONTRACT; byte-level
        parity is pinned only for the device path (the kernel is
        authoritative — this is the small-deployment convenience)."""
        from dataclasses import replace as _replace

        out = []
        for r in reqs:
            rows = chain_level_keys(r)
            # ancestor levels always decide as TOKEN buckets; only the
            # LEAF uses the request's algorithm. A shared ancestor
            # (one hierarchy, many tenants) would otherwise flip its
            # stored algorithm with each caller's choice and the
            # mismatch rule would recreate it every flip — erasing the
            # parent quota (review finding). Same convention as the
            # device path (decide_chain below / _ArrayOps).
            levels = [
                _replace(
                    r, unique_key="", name="", chain=[], hits=r.hits,
                    limit=lim, duration=dur,
                    algorithm=(
                        r.algorithm
                        if j == len(rows) - 1
                        else Algorithm.TOKEN_BUCKET
                    ),
                )
                for j, (_k, lim, dur) in enumerate(rows)
            ]
            # peek pass, made NON-mutating by snapshot/restore: a
            # plain reference peek is not side-effect free — a leaky
            # peek PERSISTS its elapsed leak credit without advancing
            # the timestamp (the reference's quirk, kept faithful in
            # the oracle) — so an advisory peek followed by the real
            # debit would credit the same elapsed leak TWICE (review
            # finding: chained leaky leaves refilled at ~2x the
            # configured rate). Restoring pristine state makes the
            # debit pass byte-equal to a single sequential pass, and
            # a refused chain leaves no trace at all.
            # `planned` accumulates the charges earlier levels of THIS
            # chain would make per cache key, so a chain naming the
            # same key twice (ancestor == leaf, duplicated ancestors)
            # is judged against the post-charge budget — without it
            # the debit pass would charge the first occurrence and
            # refuse the second, a partial debit (the device kernel
            # gets this from cumulative same-group charging + chain
            # rollback; this keeps the host twin on the contract)
            refuse = None
            planned: Dict[str, int] = {}
            saved = {
                key: self.cache.snapshot(key) for key, _l, _d in rows
            }
            for j, ((key, lim, dur), lv) in enumerate(zip(rows, levels)):
                peek = self._decide_key(key, _replace(lv, hits=0), now)
                already = planned.get(key, 0)
                if (
                    peek.status == Status.OVER_LIMIT
                    or r.hits + already > peek.remaining
                    or r.hits > lim
                ):
                    refuse = (j, peek, already)
                    break
                if r.hits > 0:
                    planned[key] = already + r.hits
            for key, snap in saved.items():
                if snap is None:
                    self.cache.remove(key)
                else:
                    self.cache.add(key, snap[0], snap[1])
            if refuse is not None:
                j, peek, already = refuse
                resp = RateLimitResp(
                    status=Status.OVER_LIMIT,
                    limit=peek.limit,
                    remaining=max(peek.remaining - already, 0),
                    reset_time=peek.reset_time,
                )
                if j != len(rows) - 1:
                    resp.metadata["chain_level"] = str(j)
                out.append(resp)
                continue
            resps = [
                self._decide_key(key, lv, now)
                for (key, _lim, _dur), lv in zip(rows, levels)
            ]
            out.append(collapse_chain_responses(resps))
        return out

    def _decide_key(self, key: str, r: RateLimitReq, now=None):
        """get_rate_limit against an explicit cache key (chain levels
        address level keys directly; the oracle hashes name/unique_key,
        so wrap with a pre-keyed request)."""
        from dataclasses import replace as _replace

        # oracle keys on name + "_" + unique_key; split the precomputed
        # key back so hash_key() reproduces it exactly
        name, _, uk = key.partition("_")
        return get_rate_limit(
            self.cache, _replace(r, name=name, unique_key=uk), now
        )


class _ArrayOps:
    """Array-level decide surface shared by the device backends.

    The serving hot path (edge GEB6 frames, serve/edge_bridge.py) carries
    pre-hashed dense arrays end-to-end; these helpers are the object<->
    array seam so the batcher can merge MIXED batches (array groups
    from the edge + request-object groups from gRPC/JSON callers) into
    ONE device submit. Requires self.engine with prep_run /
    merge_prepped / decide_submit_merged / decide_wait."""

    #: field order used everywhere a fields-dict is flattened
    ARRAY_FIELDS = ("key_hash", "hits", "limit", "duration", "algo", "gnp")

    def arrays_from_reqs(self, reqs, gnp) -> dict:
        import numpy as np

        from gubernator_tpu.core.hashing import slot_hash_batch

        n = len(reqs)
        return dict(
            key_hash=slot_hash_batch([r.hash_key() for r in reqs]),
            hits=np.fromiter((r.hits for r in reqs), np.int64, n),
            limit=np.fromiter((r.limit for r in reqs), np.int64, n),
            duration=np.fromiter((r.duration for r in reqs), np.int64, n),
            algo=np.fromiter((int(r.algorithm) for r in reqs), np.int32, n),
            gnp=np.asarray(list(gnp), bool),
        )

    def prep_group(self, fields: dict) -> dict:
        """Arrival-time per-group prep (serve/batcher.py): presort +
        clip one caller group on a prep-pool thread, so it sits in the
        batcher queue as a sorted run the flush-time merge combine
        stitches without re-sorting. `gnp` defaults to all-False."""
        if "gnp" not in fields:
            import numpy as np

            fields = dict(fields)
            fields["gnp"] = np.zeros(fields["key_hash"].shape[0], bool)
        return self.engine.prep_run(fields)

    def prep_reqs(self, reqs, gnp) -> dict:
        """prep_group for a request-object group: batch hashing +
        array conversion first (the other half of the flush work that
        moves to arrival time)."""
        return self.prep_group(self.arrays_from_reqs(reqs, gnp))

    def merge_prepped(self, runs):
        """Merge the groups' pre-sorted runs into one dispatch-ready
        batch (the submit thread's `merge` stage; engine-specific
        layout)."""
        return self.engine.merge_prepped(runs)

    def decide_submit_merged(self, merged, now: Optional[int] = None):
        """Dispatch one merge_prepped batch; fetch with
        decide_wait_arrays."""
        from gubernator_tpu.api.types import millisecond_now

        if now is None:
            now = millisecond_now()
        return self.engine.decide_submit_merged(merged, now)

    def decide_wait_arrays(self, handle):
        """(status, limit, remaining, reset_time) int arrays."""
        return self.engine.decide_wait(handle)

    @staticmethod
    def resps_from_arrays(status, limit, remaining, reset):
        return resps_from_columns(status, limit, remaining, reset)

    def snapshot_read(self, keys, now=None):
        """Bucket-replication snapshot surface over the device store:
        hash the keys once and gather their rows non-mutatingly
        (core/engine.py TpuEngine.snapshot_read). MUST run on the
        batcher's single submit thread (DeviceBatcher.run_serialized)
        so the gather never races a store-donating dispatch; the
        replication manager honors that contract."""
        from gubernator_tpu.core.hashing import slot_hash_batch

        return self.engine.snapshot_read(slot_hash_batch(list(keys)), now)

    def shed_generation(self) -> int:
        """Engine store-wipe epoch (core/engine.py reset_generation):
        the over-limit shed cache clears itself whenever this moves, so
        a clock-jump store reset can never leave stale host verdicts."""
        return self.engine.reset_generation

    # -- mesh-native GLOBAL flush (r20) --------------------------------------

    def apply_global_hits_reqs(self, reqs, now=None):
        """Aggregated GLOBAL gossip hits applied in ONE in-mesh
        collective (engine.apply_global_hits): each key's summed hits
        charge its OWNER shard and the post-charge window replicates
        mesh-wide — the hits-flush leg of the gossip cycle collapsed
        into a single device program when the destination peer is this
        node itself (serve/global_mgr.py picks this path per
        destination). MUST run on the batcher's single submit thread
        (DeviceBatcher.run_serialized): the sync collective donates the
        store. Returns RateLimitResp per request (post-charge owner
        state, caller order)."""
        import numpy as np

        from gubernator_tpu.api.types import millisecond_now
        from gubernator_tpu.core.hashing import slot_hash_batch

        if not reqs:
            return []
        if now is None:
            now = millisecond_now()
        n = len(reqs)
        status, limit, remaining, reset = self.engine.apply_global_hits(
            slot_hash_batch([r.hash_key() for r in reqs]),
            np.fromiter((r.hits for r in reqs), np.int64, n),
            np.fromiter((r.limit for r in reqs), np.int64, n),
            np.fromiter((r.duration for r in reqs), np.int64, n),
            now,
            algo=np.fromiter(
                (int(r.algorithm) for r in reqs), np.int32, n
            ),
        )
        return self.resps_from_arrays(status, limit, remaining, reset)

    # -- sketch cold tier (r13) ---------------------------------------------

    @property
    def sketch_enabled(self) -> bool:
        """True when the engine carries the count-min cold tier — the
        gate for the serve-tier promoter (serve/promoter.py)."""
        return getattr(self.engine, "sketch", None) is not None

    def set_hot_observer(self, fn) -> None:
        """Attach the promoter's per-dispatch hot-key observer (called
        with every numpy BatchRequest the engine dispatches; None
        detaches)."""
        self.engine.observe_hook = fn

    def promote_hashes(self, key_hash, limits, durations, now=None):
        """Migrate hot sketch-tier keys into exact buckets
        (core/engine.py promote_from_sketch). MUST run on the batcher's
        submit thread (DeviceBatcher.run_serialized): reads and upserts
        the donated store."""
        return self.engine.promote_from_sketch(
            key_hash, limits, durations, now
        )

    def sketch_estimates(self, key_hash, durations, now=None):
        """Current-window count-min estimates (non-mutating; submit-
        thread contract like snapshot_read)."""
        return self.engine.sketch_estimates(key_hash, durations, now)

    # -- hierarchical quota chains (r15) -------------------------------------

    def decide_chain(
        self, reqs: Sequence[RateLimitReq], now=None
    ) -> List[RateLimitResp]:
        """Chained decide on the device engine: expand every request
        into per-level rows (shallow -> deep, leaf last; the request's
        hits charge EVERY level), run ONE chain-coupled kernel pass
        (engine.decide_chain_arrays — all levels debit atomically
        under the no-partial-debit contract), and collapse each
        request's level responses most-restrictive-wins. MUST run on
        the batcher's single submit thread (DeviceBatcher routes the
        chain lane there): this submits AND waits against the donated
        store."""
        import numpy as np

        from gubernator_tpu.api.types import millisecond_now
        from gubernator_tpu.core.hashing import slot_hash_batch

        if not reqs:
            return []
        if now is None:
            now = millisecond_now()
        keys, lims, durs, spans, routes = [], [], [], [], []
        hits_l, algo_l, cids = [], [], []
        for i, r in enumerate(reqs):
            rows = chain_level_keys(r)
            route = r.routing_key()
            for j, (key, lim, dur) in enumerate(rows):
                keys.append(key)
                lims.append(lim)
                durs.append(dur)
                routes.append(route)
                hits_l.append(r.hits)
                # ancestors are TOKEN counters; only the leaf carries
                # the request's algorithm (see ExactBackend.decide_chain
                # — a shared ancestor must not mismatch-recreate under
                # callers with different leaf algorithms)
                algo_l.append(
                    int(r.algorithm) if j == len(rows) - 1 else 0
                )
                cids.append(i)
            spans.append(len(rows))
        m = len(keys)
        status, limit, remaining, reset = self.engine.decide_chain_arrays(
            slot_hash_batch(keys),
            np.asarray(hits_l, np.int64),
            np.asarray(lims, np.int64),
            np.asarray(durs, np.int64),
            np.asarray(algo_l, np.int32),
            np.asarray(cids, np.int64),
            slot_hash_batch(routes),
            now,
        )
        out = []
        k = 0
        for r, span in zip(reqs, spans):
            resps = resps_from_columns(
                status[k : k + span],
                limit[k : k + span],
                remaining[k : k + span],
                reset[k : k + span],
            )
            k += span
            out.append(collapse_chain_responses(resps))
        return out


class TpuBackend(_ArrayOps):
    """Single-chip slot-store backend."""

    def __init__(
        self,
        store: StoreConfig = StoreConfig(),
        buckets: Sequence[int] = (64, 256, 1024, 4096),
        sketch=None,
    ):
        self.engine = TpuEngine(store, buckets=buckets, sketch=sketch)

    def decide(self, reqs, gnp, now=None):
        return self.engine.get_rate_limits(reqs, now=now, gnp=list(gnp))

    def update_globals(self, updates, now=None):
        self.engine.update_globals(list(updates), now=now)

    def warmup(self) -> None:
        """Compile all batch buckets at boot so no request pays jit time."""
        self.engine.warmup()

    def stats(self) -> dict:
        return self.engine.stats.snapshot()


class MeshBackend(_ArrayOps):
    """Mesh-sharded slot-store backend (all local devices by default).

    Since r14 this is the same PartitionedEngine as TpuBackend's under
    a mesh ShardingPolicy, so it carries the full surface: the sketch
    cold tier (sub-sketches sharded over the mesh axis), the
    replication snapshot reads, and the arrival-prep pipeline — none
    of which the pre-r14 MeshEngine fork had."""

    def __init__(
        self,
        store: StoreConfig = StoreConfig(),
        devices=None,
        buckets: Sequence[int] = (64, 256, 1024, 4096),
        engine=None,
        sketch=None,
    ):
        import numpy as np

        from gubernator_tpu.core.hashing import slot_hash_batch

        self._np = np
        self._hash = slot_hash_batch
        if engine is None:
            from gubernator_tpu.parallel.sharded import MeshEngine

            engine = MeshEngine(
                store, devices=devices, buckets=buckets, sketch=sketch
            )
        self.engine = engine
        # every engine carries the launch surface the batcher drives; one
        # that does not is refused here, not at its first flush
        for name in ("prep_run", "merge_prepped", "decide_submit_merged",
                     "decide_wait"):
            getattr(engine, name)
        if not hasattr(engine, "snapshot_read"):
            # bucket replication needs the engine's non-mutating row
            # read (r11); the sharded engines don't expose it yet —
            # Instance refuses GUBER_REPLICATION=1 on such backends at
            # boot instead of failing at the first flush
            self.snapshot_read = None
        if not hasattr(engine, "decide_chain_arrays"):
            # quota chains (r15) need the engine's chain-coupled
            # kernel pass; the multihost lockstep wrapper has no chain
            # step message (documented scope limit) — the batcher's
            # chain lane then fails chained callers with a clear error
            self.decide_chain = None
        if not hasattr(engine, "apply_global_hits"):
            # the mesh-native GLOBAL flush (r20) needs the engine's
            # one-collective hit apply; without it the GlobalManager
            # falls back to the local decide path for self-destined
            # flushes
            self.apply_global_hits_reqs = None

    def decide(self, reqs, gnp, now=None):
        from gubernator_tpu.api.types import millisecond_now

        if len(reqs) == 0:
            return []
        if now is None:
            now = millisecond_now()
        return self.resps_from_arrays(
            *self.engine.decide_arrays(
                now=now, **self.arrays_from_reqs(reqs, gnp)
            )
        )

    def update_globals(self, updates, now=None):
        np = self._np
        n = len(updates)
        if n == 0:
            return
        self.engine.update_globals(
            key_hash=self._hash([k for k, _ in updates]),
            limit=np.fromiter((s.limit for _, s in updates), np.int64, n),
            remaining=np.fromiter(
                (s.remaining for _, s in updates), np.int64, n
            ),
            reset_time=np.fromiter(
                (s.reset_time for _, s in updates), np.int64, n
            ),
            is_over=np.fromiter(
                (s.status == Status.OVER_LIMIT for _, s in updates), bool, n
            ),
            now=now,
        )

    def repartition(self, devices=None, now=None) -> None:
        """Re-shard the live store over a different device set — the
        GUBER_SHARDS-change path (r17): every live token window of the
        current engine exports host-side and reinstalls under the new
        ShardingPolicy (parallel/sharded.py repartition), then the new
        engine replaces the old in place. One device (or an empty
        list's single default) degenerates to the flat policy — the
        same engine class either way (r14). MUST run with the batcher
        idle or on its serialized submit thread
        (DeviceBatcher.run_serialized): the export reads and the
        install upserts the donated store. Callers re-warm before
        serving traffic (warmup())."""
        import jax

        from gubernator_tpu.parallel.policy import ShardingPolicy

        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        policy = (
            ShardingPolicy.single(devices[0])
            if len(devices) == 1
            else ShardingPolicy.over_mesh(devices)
        )
        self.engine = self.engine.repartition(policy, now=now)

    def warmup(self) -> None:
        # The decide path pads PER-SHARD sub-batches to the dense
        # sub-rung ladder (sharded.sub_batch_ladder); warmup_public
        # compiles each rung through the PUBLIC decide_arrays, which
        # keeps it lockstep-safe for the multi-host wrapper (followers
        # replay every call). One real wall-clock now threads through
        # all of it: mixing clock domains would trip the EpochClock's
        # large-jump reset path.
        from gubernator_tpu.parallel.sharded import warmup_public

        warmup_public(self.engine)

    def stats(self) -> dict:
        return self.engine.stats.snapshot()


class MultiHostBackend(MeshBackend):
    """Leader-side backend over a multi-process global mesh. Construct
    only on process 0; follower processes run
    MultiHostMeshEngine.follower_loop instead of serving (cli/daemon.py
    wires both roles from GUBER_DIST_* env). The lockstep wrapper
    exposes the submit/wait split (followers dispatch and move on —
    fetches are leader-local), so the fetch-depth pipeline and the
    edge's array fast path work across hosts too."""

    def __init__(
        self,
        store: StoreConfig = StoreConfig(),
        followers: Sequence[str] = (),
        buckets: Sequence[int] = (64, 256, 1024, 4096),
        sketch=None,
    ):
        from gubernator_tpu.parallel.multihost import MultiHostMeshEngine

        # the lockstep wrapper exposes the same decide/update/sync/reset
        # surface MeshBackend drives; since r20 the sketch cold tier
        # rides along (promotion + estimate reads are lockstep
        # collectives, see parallel/multihost.py)
        super().__init__(
            store,
            buckets=buckets,
            engine=MultiHostMeshEngine(
                store, followers=list(followers), buckets=buckets,
                sketch=sketch,
            ),
        )

    def close(self) -> None:
        self.engine.close()
