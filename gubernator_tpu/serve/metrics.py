"""Prometheus metrics, name-compatible with the reference's collectors.

- grpc_request_counts{status,method} and
  grpc_request_duration_milliseconds{method} (reference prometheus.go:50-63)
- cache_size, cache_access_count{type} (reference cache/lru.go:56-59,164-176)
- async_durations / broadcast_durations GLOBAL histograms
  (reference global.go:44-51)
- plus TPU-specific gauges: device batch sizes and kernel launch latency.
"""

from __future__ import annotations

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    Summary,
    generate_latest,
)

REGISTRY = CollectorRegistry()

GRPC_REQUEST_COUNTS = Counter(
    "grpc_request_counts",
    "The count of gRPC requests",
    ["status", "method"],
    registry=REGISTRY,
)
GRPC_REQUEST_DURATION = Histogram(
    "grpc_request_duration_milliseconds",
    "The duration of gRPC requests in milliseconds",
    ["method"],
    buckets=(0.1, 0.5, 1, 2, 5, 10, 25, 50, 100, 500, 1000),
    registry=REGISTRY,
)
CACHE_SIZE = Gauge(
    "cache_size",
    "The number of rate-limit entries in the store",
    registry=REGISTRY,
)
CACHE_ACCESS_COUNT = Counter(
    "cache_access_count",
    "Store access counts",
    ["type"],  # hit | miss
    registry=REGISTRY,
)
GLOBAL_ASYNC_DURATIONS = Histogram(
    "async_durations",
    "The duration of GLOBAL async sends in seconds",
    registry=REGISTRY,
)
GLOBAL_BROADCAST_DURATIONS = Histogram(
    "broadcast_durations",
    "The duration of GLOBAL broadcasts to peers in seconds",
    registry=REGISTRY,
)
DEVICE_BATCH_SIZE = Histogram(
    "device_batch_size",
    "Requests coalesced per device kernel launch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
    registry=REGISTRY,
)
DEVICE_BATCH_SLOTS = Counter(
    "device_batch_slots_total",
    "Padded rows launched: each device batch counts its padding-ladder "
    "rung. device_batch_size_sum over this is the share of launched "
    "slots that carried a request",
    registry=REGISTRY,
)
DEVICE_BATCH_ROWS = Gauge(
    "device_batch_rows_total",
    "Rows of device batches by who sent them: door (a call or frame "
    "that came through one of this node's own doors: the rows it owns "
    "of them) or peer (a batch another node forwarded, "
    "Instance.get_peer_rate_limits). A row's source rides the queue "
    "entry that carried it (serve/batcher.py _QMeta.peer); plain ints "
    "exported lazily at scrape. The two sum to device_batch_size_sum; "
    "peer / both = the share of a ring member's device work that is "
    "its peers'",
    ["source"],
    registry=REGISTRY,
)
DEVICE_BATCHES_MIXED = Gauge(
    "device_batches_mixed_total",
    "Device batches that carried rows of both sources, the node's own "
    "doors' and a peer's forward, merged in one launch; / "
    "device_batch_size_count = their share. 0 on a node that owns "
    "every key, and on a ring member that is asked at no door of its "
    "own",
    registry=REGISTRY,
)
DEVICE_GROUPS_OVERTAKING = Gauge(
    "device_groups_overtaking_total",
    "Caller groups launched while an OLDER group of another source "
    "(this node's own doors, or one forwarding peer) stayed queued: "
    "the batcher collects the head group of each source's lane in turn "
    "(serve/aio.py SourceLanes), so an owner that is a busy door "
    "answers its peers without their waiting out its own backlog. 0 "
    "wherever one source feeds the batcher: the order is arrival order "
    "there. A plain int exported lazily at scrape",
    registry=REGISTRY,
)
DEVICE_BATCH_SOURCES = Summary(
    "device_batch_sources",
    "Distinct sources (this node's own doors; each forwarding peer) "
    "whose groups one device batch merged, summed and counted: "
    "_sum / _count is 1.0 wherever one source feeds the batcher",
    registry=REGISTRY,
)
MESH_SHARD_ROWS = Counter(
    "mesh_shard_rows_total",
    "Mesh backend: rows that carried a request, summed over the shards "
    "of every device batch. Over mesh_shard_slots_total it is the share "
    "of launched per-shard slots that did useful work",
    registry=REGISTRY,
)
MESH_SHARD_SLOTS = Counter(
    "mesh_shard_slots_total",
    "Mesh backend: padded rows launched, shards x the sub-rung every "
    "shard of a device batch is padded to (the fullest shard picks it)",
    registry=REGISTRY,
)
MESH_SHARD_MAX_ROWS = Counter(
    "mesh_shard_max_rows_total",
    "Mesh backend: the fullest shard's rows, summed over device batches. "
    "Times the shard count over mesh_shard_rows_total it is the skew: "
    "1 when every shard draws the same, the shard count when one shard "
    "draws everything",
    registry=REGISTRY,
)
MESH_NATIVE_STACKS = Gauge(
    "mesh_native_stacks_total",
    "Mesh backend: merged device batches whose per-shard layout "
    "(stacked [shards, sub-rung] columns, group structure, take_idx) "
    "the native merge wrote in its one call with the GIL released "
    "(libguberhash.so guber_merge_runs_sharded); exported lazily at "
    "scrape. / (this + mesh_numpy_stacks_total) = its share of "
    "engagement",
    registry=REGISTRY,
)
MESH_NUMPY_STACKS = Gauge(
    "mesh_numpy_stacks_total",
    "Mesh backend: merged device batches laid out per shard in numpy "
    "on the submit thread (parallel/sharded.py "
    "build_presorted_sharded): every one where libguberhash.so is "
    "absent, a batch past the sub-rung "
    "ladder's top, a lockstep follower's; none otherwise",
    registry=REGISTRY,
)
DEVICE_LAUNCH_MS = Histogram(
    "device_launch_milliseconds",
    "Wall time of one decide kernel launch (host-observed)",
    buckets=(0.05, 0.1, 0.25, 0.5, 1, 2, 5, 10, 25, 100),
    registry=REGISTRY,
)
STORE_DROPPED_CREATES = Counter(
    "store_dropped_creates_total",
    "Creates lost to bucket way exhaustion (over-admission signal: the "
    "dropped key is re-admitted fresh on its next batch)",
    registry=REGISTRY,
)
STORE_EVICTIONS = Counter(
    "store_evictions_total",
    "Store entries overwritten by the earliest-expiry eviction policy "
    "(over-admission signal at capacity; reference cache/lru.go:164-176 "
    "exposes the analogous cache_size-vs-max pressure)",
    registry=REGISTRY,
)
EDGE_FAST_ITEMS = Counter(
    "edge_fast_items_total",
    "Rate-limit items served through the pre-hashed (GEB6) edge fast "
    "path on this node — in a cluster, nonzero on every node proves the "
    "edge ships per-owner frames instead of funnelling through one node",
    registry=REGISTRY,
)
EDGE_FOLDED_ITEMS = Counter(
    "edge_folded_items_total",
    "String-frame items served through the bridge's string->array fold "
    "(all-valid frames skip request/response objects and instance "
    "routing: every key owned, or — edge_split_frames_total — split "
    "by owner) — the slow path's share of fast-path treatment",
    registry=REGISTRY,
)
EDGE_FOLDED_GLOBAL_ITEMS = Counter(
    "edge_folded_global_items_total",
    "Items of Behavior GLOBAL among edge_folded_items_total: owned by "
    "this node, decided on the array path with the rest of their frame, "
    "their keys queued for the owner's status broadcast",
    registry=REGISTRY,
)
EDGE_STRING_NATIVE_FRAMES = Counter(
    "edge_string_native_frames_total",
    "String frames whose payload ONE native call parsed into columns, "
    "key hashes and hash keys (libguberhash.so guber_parse_string_frame, "
    "GIL released) for the string->array fold; a string frame counted "
    "neither here nor as declined was parsed by the per-item Python "
    "loop (library not built)",
    registry=REGISTRY,
)
EDGE_STRING_NATIVE_DECLINED = Counter(
    "edge_string_native_declined_total",
    "String frames the native parser declined, by reason "
    "(hashlib_native.STRING_DECLINE: too_many_items, truncated, "
    "empty_name_or_key, bad_utf8, trailing_bytes, nul_byte); the "
    "object path then answers the frame",
    ["reason"],
    registry=REGISTRY,
)
EDGE_OBJECT_ITEMS = Counter(
    "edge_object_items_total",
    "String-frame items the bridge served through request/response "
    "objects and Instance.get_rate_limits: frames the array fold "
    "declined (one chained, invalid or foreign-owned item sends the "
    "whole frame here). With edge_fast_items_total and "
    "edge_folded_items_total it splits every bridge item by path",
    registry=REGISTRY,
)
EDGE_STALE_RINGS = Counter(
    "edge_stale_ring_total",
    "GEB6 frames rejected because the edge routed with a different "
    "membership view than this node (the edge refreshes and retries)",
    registry=REGISTRY,
)
GEB_SHM_SESSIONS = Counter(
    "geb_shm_sessions_total",
    "Shared-memory GEB lanes negotiated on this node's bridge (r18, "
    "serve/shm.py GEBM/GEBN over the unix control socket); compare "
    "with geb_shm_teardowns_total to see lanes torn down early",
    registry=REGISTRY,
)
GEB_SHM_FRAMES = Counter(
    "geb_shm_frames_total",
    "Request frames served through shared-memory rings instead of a "
    "socket (r18) — the co-located fast lane's share of bridge traffic",
    registry=REGISTRY,
)
GEB_SHM_TEARDOWNS = Counter(
    "geb_shm_teardowns_total",
    "Shared-memory lanes torn down for cause (hostile/torn ring "
    "state, a client that stopped draining, serve failures) rather "
    "than a clean close — nonzero under normal operation means a "
    "misbehaving co-located peer",
    registry=REGISTRY,
)
DISTINCT_KEYS = Gauge(
    "distinct_keys_estimate",
    "HyperLogLog estimate of distinct rate-limit keys seen",
    registry=REGISTRY,
)
STAGE_SECONDS = Gauge(
    "serving_stage_seconds_total",
    "Cumulative wall seconds attributed to one serving-pipeline stage "
    "(serve/stages.py; exported lazily at scrape — the hot path "
    "records into a plain accumulator). Pair with "
    "serving_stage_samples_total for per-sample means.",
    ["stage"],
    registry=REGISTRY,
)
STAGE_SAMPLES = Gauge(
    "serving_stage_samples_total",
    "Samples accumulated per serving-pipeline stage",
    ["stage"],
    registry=REGISTRY,
)
THREAD_CPU_SECONDS = Gauge(
    "thread_cpu_seconds_total",
    "Seconds the serving threads were ON A CORE, summed by role, from "
    "the kernel's per-thread CPU clocks read at scrape and nowhere "
    "else (serve/stages.py ThreadClocks): loop | submit | fetch | prep "
    "| other (the process's CPU clock less the four: PJRT, gRPC core, "
    "native pools). What a thread RAN, beside the stage spans, which "
    "say what it was TAKEN. Absent where the host gives no thread "
    "clock",
    ["thread"],
    registry=REGISTRY,
)
THREAD_WALL_SECONDS = Gauge(
    "thread_wall_seconds_total",
    "The monotonic clock at the instant thread_cpu_seconds_total was "
    "read: difference both over two scrapes for a share of one core",
    registry=REGISTRY,
)
LOOP_PAUSES_OVER_HALF_DEADLINE = Gauge(
    "loop_pauses_over_half_deadline_total",
    "Ticks of the 50 ms loop_lag timer that found the serving loop "
    "held for half of a forwarded batch's deadline or longer "
    "(BehaviorConfig.effective_peer_timeout / 2: 0.25 s by default) — "
    "the timer itself that late, or a GC collection that long since "
    "the tick before (serve/stages.py ProcessProbes; a daemon's, 0 in "
    "an in-process cluster). A pause of twice that on an owner fails "
    "every hit-carrying batch its peers have in flight, and those are "
    "never sent again: any growth here is the warning before error "
    "items",
    registry=REGISTRY,
)
PROGRAMS_BUILT_AFTER_READY = Gauge(
    "programs_built_after_ready_total",
    "Programs XLA was handed since the daemon said Ready, compiled or "
    "loaded from the persistent cache (a jax.monitoring listener on "
    "the backend-compile event): each was traced and lowered on a "
    "serving thread while callers and peers waited. The warm-up "
    "exists so that this stays 0; the benchmark voids a window in "
    "which it grew",
    registry=REGISTRY,
)
SHED_HITS = Gauge(
    "shed_hits_total",
    "Requests answered from the host over-limit shed cache instead of "
    "the device (serve/shedcache.py; exported lazily at scrape like the "
    "stage totals — the hot path only bumps a plain int)",
    registry=REGISTRY,
)
SHED_LOOKUPS = Gauge(
    "shed_lookups_total",
    "Shed-cache consults for gate-eligible requests (token bucket, "
    "hits > 0); shed hit rate = shed_hits_total / shed_lookups_total",
    registry=REGISTRY,
)
SHED_ENTRIES = Gauge(
    "shed_entries",
    "Live over-limit verdicts in the host shed cache (bounded by "
    "GUBER_SHED_CACHE_KEYS)",
    registry=REGISTRY,
)
SHED_INDEX_USES = Gauge(
    "shed_index_uses_total",
    "Consults of the shed cache's sorted fingerprint index by the "
    "array paths (screen_fields / observe_fields: two a GEB frame)",
    registry=REGISTRY,
)
SHED_INDEX_REBUILDS = Gauge(
    "shed_index_rebuilds_total",
    "Re-sorts of that index: only a change of the cached KEY SET "
    "leads to one, once the overlay of new fingerprints is full; a "
    "change of a cached value never does. rebuilds / uses near 1 "
    "means every frame pays a sort",
    registry=REGISTRY,
)
SHED_NATIVE_CONSULTS = Gauge(
    "shed_native_consults_total",
    "Of shed_index_uses_total, the consults ONE native call served "
    "with the GIL released (libguberhash.so guber_shed_screen / "
    "guber_shed_observe); the boot log's `shed screen:` line says "
    "which body runs",
    registry=REGISTRY,
)
SHED_NUMPY_CONSULTS = Gauge(
    "shed_numpy_consults_total",
    "Of shed_index_uses_total, the consults the numpy twin served call "
    "by call on the serving loop: all of them where libguberhash.so is "
    "absent, none where it is there",
    registry=REGISTRY,
)
PEER_SERVE_BATCHES = Gauge(
    "peer_serve_batches_total",
    "GetPeerRateLimits batches this node served as the owner "
    "(Instance.get_peer_rate_limits; exported lazily at scrape like "
    "the shed cache's totals). Pair with the peer_serve stage for the "
    "owner side's own seconds a batch",
    registry=REGISTRY,
)
PEER_SERVE_ITEMS = Gauge(
    "peer_serve_items_total",
    "Rate-limit items in those batches: what the node's peers "
    "forwarded to it",
    registry=REGISTRY,
)
PEER_SERVE_SHED_HITS = Gauge(
    "peer_serve_shed_hits_total",
    "Forwarded items the owner-side shed screen answered over-limit "
    "from the host cache, without a device trip; / "
    "peer_serve_items_total = the share of a ring member's peer "
    "traffic that never reaches the batcher",
    registry=REGISTRY,
)
PEER_SERVE_FOLDED_ITEMS = Gauge(
    "peer_serve_folded_items_total",
    "Forwarded items the PeersV1 door served as arrays (the wire fold: "
    "no request or response object per item); / peer_serve_items_total "
    "= the fold's share of engagement, below 1 where chains, "
    "replication, rescale or odd wire sent batches down the object path",
    registry=REGISTRY,
)
PEER_FORWARD_BATCHES = Gauge(
    "peer_forward_batches_total",
    "GetPeerRateLimits RPCs this node sent to the peers that own what "
    "it was asked (serve/peers.py PeerClient; plain ints exported "
    "lazily at scrape like peer_serve_*). Pair with the forward_rpc "
    "stage for a forward's seconds; 0 on a node that owns every key",
    registry=REGISTRY,
)
PEER_FORWARD_ITEMS = Gauge(
    "peer_forward_items_total",
    "Rate-limit items in those RPCs; / peer_forward_batches_total = "
    "the forwarded batch's size, and over a ring the sum equals the "
    "owners' peer_serve_items_total",
    registry=REGISTRY,
)
PEER_FORWARD_FAILED_ITEMS = Gauge(
    "peer_forward_failed_items_total",
    "Forwarded items that came back to their caller as an error, by "
    "reason: deadline (no answer within GUBER_PEER_TIMEOUT_MS / "
    "GUBER_BATCH_TIMEOUT_MS; the owner may have applied the hits, so "
    "the batch is not sent again), breaker_open (never sent), "
    "transport (refused, reset, an application error), closed (the "
    "client was replaced under its caller). Each failed RPC also logs "
    "one WARNING with the peer, the items and the seconds waited",
    ["reason"],
    registry=REGISTRY,
)
EDGE_SPLIT_FRAMES = Gauge(
    "edge_split_frames_total",
    "String frames of mixed ownership the GEB door served split by "
    "owner as columns (serve/edge_bridge.py _plan_split: one owner "
    "index a row, the owned rows to the batcher, each other node's to "
    "its forwarder as a column group, one encode); plain ints exported "
    "lazily at scrape. 0 on a node that shares its ring with nobody",
    registry=REGISTRY,
)
EDGE_SPLIT_ITEMS = Gauge(
    "edge_split_items_total",
    "The items of those frames by lane: owned (decided here), "
    "forwarded (sent to the peer that owns them: they are "
    "peer_forward_items_total's too), shed (answered over-limit from "
    "the host cache, owned and foreign alike). The three sum to the "
    "split frames' items, which are counted in "
    "edge_folded_items_total as well",
    ["lane"],
    registry=REGISTRY,
)
EDGE_SPLIT_DECLINED = Gauge(
    "edge_split_declined_total",
    "String frames that a node on a shared ring served through "
    "request objects, every item of them, by reason "
    "(serve/peers.py SPLIT_DECLINE_REASONS): invalid_item, chain, "
    "foreign_global, foreign_no_batching, rescale_transition, "
    "too_many_items, no_arrays, no_native, error. Their items are "
    "edge_object_items_total's",
    ["reason"],
    registry=REGISTRY,
)
TRAFFIC_NATIVE_FOLDS = Gauge(
    "traffic_native_folds_total",
    "Batches the traffic observers (distinct-key HLL + hot-key "
    "summary, /v1/debug/stats) folded in one native call with the GIL "
    "released (libguberhash.so guber_traffic_fold); exported lazily "
    "at scrape. / (this + traffic_python_folds_total) = the native "
    "fold's share of engagement",
    registry=REGISTRY,
)
TRAFFIC_PYTHON_FOLDS = Gauge(
    "traffic_python_folds_total",
    "Batches the same observers folded in Python on the serving loop "
    "(core/sketches.py SpaceSaving + HyperLogLog): every one where "
    "libguberhash.so is absent, none otherwise",
    registry=REGISTRY,
)
FAULTS_INJECTED = Counter(
    "faults_injected_total",
    "Injected faults fired (serve/faults.py, GUBER_FAULT_SPEC) — a "
    "chaos run asserts this is nonzero so it can't pass with its "
    "faults silently misconfigured",
    ["point", "action"],
    registry=REGISTRY,
)
PEER_RPC_RETRIES = Counter(
    "peer_rpc_retries_total",
    "Peer RPC attempts retried after a retryable failure (bounded by "
    "GUBER_PEER_RETRIES, exponential backoff + full jitter)",
    ["peer"],
    registry=REGISTRY,
)
PEER_BREAKER_STATE = Gauge(
    "peer_breaker_state",
    "Per-peer circuit breaker state: 0=closed, 1=half-open, 2=open "
    "(serve/breaker.py; also surfaced through HealthCheck)",
    ["peer"],
    registry=REGISTRY,
)
PEER_BREAKER_TRANSITIONS = Counter(
    "peer_breaker_transitions_total",
    "Circuit breaker state transitions, labelled by destination state",
    ["peer", "to"],
    registry=REGISTRY,
)
DEGRADED_RESPONSES = Counter(
    "degraded_responses_total",
    "Requests answered from the LOCAL store because the owning peer was "
    "unreachable (GUBER_DEGRADED_LOCAL=1; responses carry "
    'metadata["degraded"]="true")',
    registry=REGISTRY,
)
GLOBAL_TASK_RESTARTS = Counter(
    "global_task_restarts_total",
    "GlobalManager background loops restarted after an unexpected death "
    "(supervised with backoff; pre-r8 a dead loop only logged and GLOBAL "
    "gossip silently stopped)",
    ["task"],
    registry=REGISTRY,
)
GLOBAL_FLUSH_BYTES = Counter(
    "global_flush_bytes_total",
    "Approximate payload bytes flushed by the GLOBAL hits loop, "
    "labelled by delivery path: 'rpc' for per-peer gossip sends to "
    "off-mesh ring peers, 'mesh' for self-destined hits applied in one "
    "in-mesh psum collective (r20 mesh-native GLOBAL) — the byte split "
    "shows how much gossip the collective path absorbed",
    ["path"],
    registry=REGISTRY,
)
GLOBAL_BACKLOG_DROPPED = Counter(
    "global_backlog_dropped_total",
    "GLOBAL gossip entries dropped because the aggregation backlog hit "
    "GUBER_GLOBAL_BACKLOG distinct keys (an unreachable owner no longer "
    "grows the hit backlog without bound); labelled by queue (hits | "
    "updates)",
    ["queue"],
    registry=REGISTRY,
)
GLOBAL_BROADCAST_KEYS = Counter(
    "global_broadcast_keys_total",
    "Owned GLOBAL keys whose authoritative status one broadcast flush "
    "peeked and held ready for this node's peers (sent to none where "
    "the node has no peer)",
    registry=REGISTRY,
)
GLOBAL_PEEK_ROWS = Counter(
    "global_peek_rows_total",
    "Zero-hit rows the owner broadcast's status peeks put through the "
    "device batcher (one a queued key a flush): device rows no client "
    "asked for",
    registry=REGISTRY,
)
REPLICATION_SNAPSHOTS_SENT = Counter(
    "replication_snapshots_sent_total",
    "Owned-bucket snapshots shipped to ring successors (and reconcile "
    "handbacks to returned owners) over ReplicateBuckets "
    "(GUBER_REPLICATION=1, serve/replication.py)",
    registry=REGISTRY,
)
REPLICATION_STANDBY_ENTRIES = Gauge(
    "replication_standby_entries",
    "Live snapshots in the receiver-side standby table (bounded by "
    "GUBER_REPLICATION_STANDBY_KEYS; consulted only on takeover)",
    registry=REGISTRY,
)
REPLICATED_TAKEOVERS = Counter(
    "replicated_takeovers_total",
    "First-touch decisions seeded from a standby snapshot after a "
    "takeover (owner dead or removed) instead of starting a fresh "
    'window; the seeded responses carry metadata["replicated"]="true"',
    registry=REGISTRY,
)
REPLICATION_RECONCILES = Counter(
    "replication_reconciles_total",
    "Snapshots installed directly into the LOCAL store because this "
    "node owns their keys (reconcile handback from the interim "
    "successor after an owner returns)",
    registry=REGISTRY,
)
REPLICATION_LAG = Gauge(
    "replication_lag_seconds",
    "Age of the last snapshot applied at takeover/reconcile time "
    "(receiver clock minus the owner's snapshot_ms stamp; bounded by "
    "one GUBER_REPLICATION_SYNC_WAIT_MS window + RTT when healthy)",
    registry=REGISTRY,
)
REPLICATION_DROPPED = Counter(
    "replication_dropped_total",
    "Replication entries dropped at a bound: dirty-backlog keys past "
    "GUBER_REPLICATION_BACKLOG, standby evictions past "
    "GUBER_REPLICATION_STANDBY_KEYS",
    ["what"],
    registry=REGISTRY,
)
RESCALE_KEYS_MOVED = Counter(
    "rescale_keys_moved_total",
    "Live token windows handed to their NEW ring owner on a membership "
    "change, a planned drain, or a double-serve reconcile tick "
    "(GUBER_RESCALE=1, serve/rescale.py; delivered over "
    "ReplicateBuckets with last-write-wins installs, so retries and "
    "duplicates re-count here but no-op on the receiver)",
    registry=REGISTRY,
)
RESCALE_HANDOFF_LAG = Gauge(
    "rescale_handoff_lag_seconds",
    "Sender side: wall time from a ring change to its moved windows "
    "being delivered to their new owners (target: under two "
    "GUBER_REPLICATION_SYNC_WAIT_MS flush windows). Receivers "
    "re-stamp it with the age of the snapshots they install",
    registry=REGISTRY,
)
RESCALE_DOUBLE_SERVE = Counter(
    "rescale_double_serve_answers_total",
    "Peer-forwarded requests this node answered for keys it no longer "
    "owns, inside an open GUBER_RESCALE_DOUBLE_SERVE_MS window after a "
    "ring change (the old owner's warm store answers while the new "
    "owner installs; the end-of-window flush reconciles, LWW)",
    registry=REGISTRY,
)
RESCALE_DROPPED = Counter(
    "rescale_dropped_total",
    "Rescale entries dropped at a bound: tracked owned keys evicted "
    "past GUBER_RESCALE_TRACK_KEYS (freshest kept), pending handoff "
    "snapshots evicted past the same bound on the receiver",
    ["what"],
    registry=REGISTRY,
)
RESCALE_TRACKED_ENTRIES = Gauge(
    "rescale_tracked_entries",
    "Owned token windows tracked for planned handoff + pending "
    "received snapshots awaiting this node's ring flip (bounded by "
    "GUBER_RESCALE_TRACK_KEYS each; set lazily at /metrics scrape)",
    registry=REGISTRY,
)
CHECKPOINT_AGE = Gauge(
    "checkpoint_age_seconds",
    "Age of the newest durable checkpoint on disk (now minus the last "
    "successful flush's snapshot stamp; set lazily at /metrics scrape). "
    "Grows without bound while writes fail or hang — alert when it "
    "passes GUBER_CHECKPOINT_MAX_AGE_MS, because a restart past that "
    "bound boots cold by design",
    registry=REGISTRY,
)
RESTORE_LAG = Gauge(
    "restore_lag_seconds",
    "Staleness of the state this process restored at boot (restore "
    "wall clock minus the checkpoint's owner-clock snapshot stamp, or "
    "the import batch's stamp for a blue-green bulk load). Bounded by "
    "GUBER_CHECKPOINT_MAX_AGE_MS for disk restores — stale checkpoints "
    "are refused and the node boots cold instead",
    registry=REGISTRY,
)
RESTORED_WINDOWS = Counter(
    "restored_windows_total",
    "Bucket windows installed from durable state: boot-time warm "
    "restore from GUBER_CHECKPOINT_DIR plus blue-green import installs "
    "received over ReplicateBuckets (LWW, so double-delivery counts "
    "once per accepted install, never double-admits)",
    registry=REGISTRY,
)
CHECKPOINT_FAILURES = Counter(
    "checkpoint_failures_total",
    "Checkpoint subsystem failures by kind: 'write' (a flush could not "
    "land its chunks/manifest), 'read' (unreadable file at restore), "
    "'corrupt' (CRC/parse mismatch — torn or truncated file), 'stale' "
    "(manifest older than GUBER_CHECKPOINT_MAX_AGE_MS), 'version' (a "
    "FUTURE format version refused), 'export' (a blue-green export "
    "send failed). Every kind boots/continues cold and loudly — never "
    "a crash, never a wedge",
    ["what"],
    registry=REGISTRY,
)
CHECKPOINT_TRACKED_ENTRIES = Gauge(
    "checkpoint_tracked_entries",
    "Owned token windows tracked for the next checkpoint flush + "
    "pending import snapshots awaiting re-route to their ring owner "
    "(bounded by GUBER_CHECKPOINT_TRACK_KEYS each; set lazily at "
    "/metrics scrape)",
    registry=REGISTRY,
)
SKETCH_PROMOTIONS = Counter(
    "sketch_promotions_total",
    "Hot sketch-tier keys migrated into exact-tier buckets by the "
    "streaming promoter (GUBER_SKETCH=1, serve/promoter.py): the "
    "window continues from the count-min estimate instead of the tail "
    "tier's approximate math",
    registry=REGISTRY,
)
SKETCH_DEMOTIONS = Counter(
    "sketch_demotions_total",
    "Promoted keys released by the promoter (their installed window "
    "expired, or their count decayed out of the top-K candidate set); "
    "the key falls back to the sketch tier on its next window",
    registry=REGISTRY,
)
SKETCH_SHED_SEEDS = Counter(
    "sketch_shed_seeds_total",
    "Over-limit hot candidates the promoter seeded straight into the "
    "r10 shed cache (estimate >= limit at promotion time): their "
    "refusals answer host-side without a device trip",
    registry=REGISTRY,
)
DRAIN_DURATION = Gauge(
    "drain_duration_seconds",
    "Wall time of the last graceful drain (SIGTERM: deregister, refuse "
    "new edge frames, flush batcher + GLOBAL queues; bounded by "
    "GUBER_DRAIN_TIMEOUT_MS)",
    registry=REGISTRY,
)
# -- device memory (PR 30): what the chip holds against what the state
# needs, set lazily at /metrics scrape from the device's own allocator
# statistics; on a mesh the fullest device's. 0 where the backend keeps
# no such statistic (the CPU).
DEVICE_MEMORY_PEAK = Gauge(
    "device_memory_peak_bytes",
    "Most bytes the device allocator has had in use at once since the "
    "process started (memory_stats peak_bytes_in_use; the fullest "
    "device's): against store_state_bytes it says whether a second "
    "table was ever alive",
    registry=REGISTRY,
)
DEVICE_MEMORY_LIMIT = Gauge(
    "device_memory_limit_bytes",
    "Bytes the device allocator may hand out (memory_stats "
    "bytes_limit): HBM less what the runtime reserves",
    registry=REGISTRY,
)
STORE_STATE_BYTES = Gauge(
    "store_state_bytes",
    "Bytes of rate-limit state resident on a device: exact table + "
    "sketch, from the arrays' own shapes and shardings (the fullest "
    "device's; on a mesh 1/n_shards of the whole)",
    registry=REGISTRY,
)
# -- queue-visibility gauges (r16): occupancy the stage clock cannot
# express (it times spans, not standing depth). All set lazily at
# /metrics scrape like shed_entries — the hot paths keep plain
# counters/queues and pay nothing.
BATCHER_QUEUE_DEPTH = Gauge(
    "batcher_queue_depth",
    "Caller groups standing in the device batcher (queued + collected "
    "+ parked carry) at scrape time",
    registry=REGISTRY,
)
BATCHER_QUEUE_AGE = Gauge(
    "batcher_queue_oldest_age_seconds",
    "Age of the oldest caller group standing in the device batcher — "
    "a growing value with flat depth means the flusher is wedged, not "
    "merely busy",
    registry=REGISTRY,
)
PREP_BACKLOG = Gauge(
    "prep_pool_backlog",
    "Arrival-prep tasks queued behind the prep pool's workers "
    "(GUBER_PREP_THREADS); sustained backlog means prep no longer "
    "hides inside the batcher queue wait (serve/batcher.py, r9)",
    registry=REGISTRY,
)
FRAME_INFLIGHT = Gauge(
    "frame_inflight",
    "GEB frames accepted but not yet answered on this door (bounded "
    "by credit window x connections); door = edge (bridge socket/TCP) "
    "| geb (GUBER_GEB_PORT client door)",
    ["door"],
    registry=REGISTRY,
)
FRAME_CONNECTIONS = Gauge(
    "frame_connections",
    "Live connections on a GEB frame door (same door label set as "
    "frame_inflight)",
    ["door"],
    registry=REGISTRY,
)
REPLICATION_BACKLOG_ENTRIES = Gauge(
    "replication_backlog_entries",
    "Dirty owned keys + takeover-tracked keys awaiting the next "
    "replication flush (bounded by GUBER_REPLICATION_BACKLOG)",
    registry=REGISTRY,
)
GLOBAL_BACKLOG_ENTRIES = Gauge(
    "global_backlog_entries",
    "Distinct keys standing in a GLOBAL aggregation queue (bounded by "
    "GUBER_GLOBAL_BACKLOG); queue = hits (non-owner forwards) | "
    "updates (owner broadcasts)",
    ["queue"],
    registry=REGISTRY,
)
# -- distributed tracing (r16, serve/tracing.py): recorder counters,
# exported lazily at scrape from the per-instance flight recorder
TRACES_STARTED = Gauge(
    "traces_started_total",
    "Requests that began span collection (head-sampled via "
    "GUBER_TRACE_SAMPLE, joined from a remote sampled context, or "
    "armed for tail capture via GUBER_TRACE_SLOW_MS)",
    registry=REGISTRY,
)
TRACES_RECORDED = Gauge(
    "traces_recorded_total",
    "Completed traces retained in the flight recorder "
    "(/v1/debug/traces)",
    registry=REGISTRY,
)
TRACES_TAIL_CAPTURED = Gauge(
    "traces_tail_captured_total",
    "Traces retained by the tail rule alone: unsampled requests "
    "slower than max(GUBER_TRACE_SLOW_MS, rolling p99)",
    registry=REGISTRY,
)
TRACES_DROPPED = Gauge(
    "traces_dropped_total",
    "Retained traces evicted from the flight-recorder ring "
    "(GUBER_TRACE_BUFFER bound)",
    registry=REGISTRY,
)
TRACE_SLOW_THRESHOLD = Gauge(
    "trace_slow_threshold_ms",
    "Current tail-capture retention threshold: max of the "
    "GUBER_TRACE_SLOW_MS floor and the rolling p99 of recent request "
    "durations",
    registry=REGISTRY,
)


def render() -> bytes:
    """Text exposition for the /metrics endpoint."""
    return generate_latest(REGISTRY)
