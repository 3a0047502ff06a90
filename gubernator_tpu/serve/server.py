"""Server daemon: gRPC V1 + PeersV1 services, HTTP JSON gateway, /metrics.

Wires config -> backend -> Instance -> servers, mirroring the reference
daemon's shape (reference cmd/gubernator/main.go:40-147): gRPC on one
listener, an HTTP gateway exposing POST /v1/GetRateLimits and
GET /v1/HealthCheck as JSON plus GET /metrics for Prometheus, discovery
(static peers, etcd, or kubernetes) pushing peer updates into
Instance.set_peers, and graceful shutdown.
"""

from __future__ import annotations

import asyncio
import gc
import os
import json
import logging
import time
from typing import Optional

import grpc
from aiohttp import web
from google.protobuf.message import DecodeError

from gubernator_tpu.api import convert
from gubernator_tpu.api.grpc_glue import add_peers_servicer, add_v1_servicer
from gubernator_tpu.api.proto.gen import gubernator_pb2, peers_pb2
from gubernator_tpu.core.hashing import native_lib
from gubernator_tpu.serve import metrics, tracing
from gubernator_tpu.serve.batcher import peer_rows
from gubernator_tpu.serve.backends import (
    ExactBackend,
    MeshBackend,
    TpuBackend,
)
from gubernator_tpu.serve.config import ServerConfig
from gubernator_tpu.serve.instance import BatchTooLargeError, Instance
from gubernator_tpu.serve.stages import (
    BATCH_TILES,
    STAGES,
    ProcessProbes,
    ThreadClocks,
    mark_call,
    unmark_call,
)

log = logging.getLogger("gubernator_tpu.server")

#: middle collections between two full ones once the daemon is Ready
#: (CPython's default is 10; see settle_collector)
FULL_COLLECTION_EVERY = 1000


def settle_collector() -> None:
    """The cyclic collector's settings for a daemon that is about to
    say Ready.

    What the boot built lives as long as the process — the traced
    programs alone are ~10^6 Python objects — and every full collection
    walked all of it: 106-128 ms each on the chip's host (PERF.md,
    PR 24), longer still once the warm-up traced its programs on a
    dozen threads (PR 26). `gc.freeze()` puts it out of the collector's
    sight.

    With the old generation emptied, CPython's brake on full
    collections (only once the young survivors outgrow a quarter of it)
    holds nothing back: they ran every tenth middle collection, 1.6
    times a second under 1000-item string frames, each walking every
    request and response in flight for ~0.1 s — a pause as long as a
    10-per-second leaky bucket's tick, which every starved hot key
    turned into an extra token (PERF.md, PR 26). Served requests die by
    reference count; cycles that reach the old generation can wait a
    hundred times longer."""
    gc.collect()
    gc.freeze()
    gc.set_threshold(*gc.get_threshold()[:2], FULL_COLLECTION_EVERY)


def make_backend(conf: ServerConfig):
    if conf.jax_platform:
        import jax

        jax.config.update("jax_platforms", conf.jax_platform)

    if conf.backend == "exact":
        return ExactBackend(conf.cache_size)
    # a device backend serves from a TPU, or from the platform the
    # operator named — never from whatever JAX fell back to
    from gubernator_tpu.jaxenv import require_tpu

    require_tpu(f"GUBER_BACKEND={conf.backend}", conf.jax_platform)
    # sizing knobs (GUBER_STORE_MIB / GUBER_STORE_TARGET_KEYS) resolve
    # here; an oversized/undersized footprint for the declared key
    # budget warns (or fails under GUBER_STORE_SIZE_STRICT) at boot,
    # per the measured footprint≍throughput law
    store = conf.store_config(logger=log)
    from gubernator_tpu.core.store import (
        check_host_budget,
        store_capacity,
        store_footprint_bytes,
    )

    # whole-host budget accounting (r13): the boot log reports the
    # per-tier split, and the lint checks that GUBER_STORE_MIB covers
    # exact + sketch + shed + replication standby — not just the exact
    # tier (warning, or a hard failure under GUBER_STORE_SIZE_STRICT)
    sketch = conf.sketch_config()
    sketch_bytes = 0
    if sketch is not None:
        from gubernator_tpu.core.sketches import sketch_footprint_bytes

        sketch_bytes = sketch_footprint_bytes(sketch)
    from gubernator_tpu.serve.shedcache import ENTRY_BYTES as SHED_BYTES

    shed_bytes = (
        conf.shed_cache_keys * SHED_BYTES if conf.shed_cache else 0
    )
    # a standby snapshot is a small dataclass + dict node; ~160 B
    # measured on CPython 3.10 (serve/replication.py)
    standby_bytes = (
        conf.replication_standby_keys * 160 if conf.replication else 0
    )
    log.info(
        "store tiers: exact %d slots x %d ways = %d entries (%.0f MiB)"
        "%s + shed %.1f MiB + standby %.1f MiB",
        store.slots, store.rows, store_capacity(store),
        store_footprint_bytes(store) / (1 << 20),
        (
            f" + sketch {sketch.rows}x{sketch.width} "
            f"int{sketch.counter_bytes * 8} "
            f"({sketch_bytes / (1 << 20):.0f} MiB)"
            if sketch is not None
            else " (sketch tier off)"
        ),
        shed_bytes / (1 << 20),
        standby_bytes / (1 << 20),
    )
    host_lint = check_host_budget(
        conf.store_mib,
        {
            "exact store": store_footprint_bytes(store),
            "sketch": sketch_bytes,
            "shed cache": shed_bytes,
            "replication standby": standby_bytes,
        },
    )
    if host_lint:
        # STRICT hard-fails only when a HOST-side part was explicitly
        # sized (the operator oversubscribed on purpose): the device
        # tiers always fit by the carve-out, but the DEFAULT shed
        # cache (~20 MiB) overflows any tiny budget on its own, and
        # failing a pre-r13 strict config whose knobs never changed
        # would be a regression — those boots warn instead
        fields = type(conf).__dataclass_fields__
        host_explicit = (
            conf.shed_cache_keys != fields["shed_cache_keys"].default
        ) or (
            conf.replication
            and conf.replication_standby_keys
            != fields["replication_standby_keys"].default
        )
        if conf.store_size_strict and host_explicit:
            raise ValueError(f"GUBER_STORE_SIZE_STRICT: {host_lint}")
        log.warning("%s", host_lint)
    from gubernator_tpu.core.engine import buckets_for_limit

    buckets = buckets_for_limit(conf.device_batch_limit)
    if conf.device_deep_batch:
        log.info(
            "throughput mode: deep-batch accumulation toward %d "
            "(ladder %s)",
            conf.device_batch_limit, buckets,
        )
    if conf.backend == "tpu":
        return TpuBackend(store, buckets=buckets, sketch=sketch)
    if conf.backend == "mesh":
        devices = None
        if conf.shards:
            import jax

            avail = jax.devices()
            if conf.shards > len(avail):
                raise ValueError(
                    f"GUBER_SHARDS={conf.shards} exceeds the "
                    f"{len(avail)} visible devices; on CPU, raise "
                    "XLA_FLAGS --xla_force_host_platform_device_count"
                )
            devices = avail[: conf.shards]
        backend = MeshBackend(
            store, devices=devices, buckets=buckets, sketch=sketch
        )
        # the operator's confirmation that GUBER_SHARDS took effect
        log.info(
            "partitioned engine: %s", backend.engine.policy.describe()
        )
        return backend
    if conf.backend == "multihost":
        from gubernator_tpu.serve.backends import MultiHostBackend

        return MultiHostBackend(
            store, followers=conf.dist_followers, buckets=buckets,
            sketch=sketch,
        )
    raise ValueError(f"unknown backend '{conf.backend}'")


class _Timed:
    """Method timing -> grpc_request_counts / duration histograms
    (the stats-handler role, reference prometheus.go:104-127)."""

    def __init__(self, method: str):
        self.method = method

    def __enter__(self):
        self.start = time.monotonic()
        return self

    def __exit__(self, exc_type, *_):
        ms = (time.monotonic() - self.start) * 1000.0
        metrics.GRPC_REQUEST_DURATION.labels(self.method).observe(ms)
        metrics.GRPC_REQUEST_COUNTS.labels(
            "failed" if exc_type else "success", self.method
        ).inc()
        return False


class StatsInterceptor(grpc.aio.ServerInterceptor):
    """Times EVERY unary RPC generically by method name — the
    stats-handler contract of the reference (prometheus.go:104-127): a
    method added tomorrow is metered automatically instead of silently
    unmetered (r1 hand-wrapped exactly four methods)."""

    async def intercept_service(self, continuation, handler_call_details):
        handler = await continuation(handler_call_details)
        if handler is None or handler.unary_unary is None:
            return handler  # only unary-unary RPCs exist in this API
        method = handler_call_details.method
        inner = handler.unary_unary

        async def timed(request, context):
            with _Timed(method):
                return await inner(request, context)

        return grpc.unary_unary_rpc_method_handler(
            timed,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )


def _md_traceparent(context) -> "Optional[str]":
    """The traceparent entry of an RPC's invocation metadata, or None.
    One pass over a handful of per-RPC metadata pairs — never per-item
    work, so the untraced path stays flat."""
    try:
        for k, v in context.invocation_metadata() or ():
            if k == tracing.TRACEPARENT:
                return v
    except Exception:  # pragma: no cover - defensive
        pass
    return None


async def _serve_call(instance, door, context, pb_reqs, decide, reply):
    """One rate-limit call through a gRPC door as request OBJECTS,
    tiled on the stage clock (serve/stages.py CALL_TILES): every
    V1/GetRateLimits call — its items route, validate, forward and
    carry metadata one by one — and the PeersV1/GetPeerRateLimits
    batches the wire fold declines (_serve_folded serves the rest as
    arrays, with the same tiles). grpc_decode and grpc_encode
    here, instance_route (GetRateLimits) or peer_serve
    (GetPeerRateLimits) in the instance, call_queue / call_device /
    call_wake in the batcher for the group this handler enqueues
    first (mark_call), call_e2e around them all. Decode and encode
    run inside the trace scope, so a sampled call's trace holds them
    too. Bare stamps, not STAGES.span: the serving loop
    pays for every microsecond a call (PERF.md, PR 24), and on a
    two-item call the two spans are ~20 us long."""
    t0 = time.monotonic()
    tracer = instance.tracer
    trace = tracer.join(
        door, tracing.parse_traceparent(_md_traceparent(context))
    )
    mark = mark_call()
    try:
        with tracing.scope(tracer, trace) as tr:
            reqs = [convert.req_from_pb(p) for p in pb_reqs]
            t1 = time.monotonic()
            STAGES.add("grpc_decode", t1 - t0)
            if tr is not None:
                tr.annotate(items=len(reqs))
            resps = await decide(reqs)
            t2 = time.monotonic()
            out = reply([convert.resp_to_pb(r) for r in resps])
            t3 = time.monotonic()
            STAGES.add("grpc_encode", t3 - t2)
    except BatchTooLargeError as e:
        await context.abort(grpc.StatusCode.OUT_OF_RANGE, str(e))
    finally:
        unmark_call(mark)
    STAGES.add("call_e2e", t3 - t0)
    return out


def _v1_reply(pbs):
    return gubernator_pb2.GetRateLimitsResp(responses=pbs)


def _peers_reply(pbs):
    return peers_pb2.GetPeerRateLimitsResp(rate_limits=pbs)


class V1Servicer:
    def __init__(self, instance: Instance):
        self.instance = instance

    async def GetRateLimits(self, request, context):
        return await _serve_call(
            self.instance, "grpc", context, request.requests,
            self.instance.get_rate_limits, _v1_reply,
        )

    async def HealthCheck(self, request, context):
        h = self.instance.health_check()
        return gubernator_pb2.HealthCheckResp(
            status=h.status, message=h.message, peer_count=h.peer_count
        )


async def _serve_folded(instance, context, wire: bytes):
    """One GetPeerRateLimits call served as arrays, or None where the
    fold declines the batch (Instance.fold_peer_batch) and the caller
    serves it through _serve_call: wire bytes -> columns (one native
    parse: `grpc_decode`, which here covers the bytes -> fields work
    the runtime's FromString does outside that span on the object
    path) -> Instance.get_peer_rate_limits on the columns
    (`peer_serve`, and the batcher's three call tiles for the residue's
    array group) -> wire bytes (one native encode: `grpc_encode`).
    No request, response or protobuf item object is made. The same
    tiles, trace scope and call mark as _serve_call; a declined batch
    has recorded nothing."""
    t0 = time.monotonic()
    batch = instance.fold_peer_batch(wire)
    if batch is None:
        return None
    tracer = instance.tracer
    trace = tracer.join(
        "peers", tracing.parse_traceparent(_md_traceparent(context))
    )
    mark = mark_call()
    try:
        with tracing.scope(tracer, trace) as tr:
            t1 = time.monotonic()
            STAGES.add("grpc_decode", t1 - t0)
            if tr is not None:
                tr.annotate(items=len(batch))
            answers = await instance.get_peer_rate_limits(batch)
            t2 = time.monotonic()
            out = answers.to_wire()
            t3 = time.monotonic()
            STAGES.add("grpc_encode", t3 - t2)
    finally:
        unmark_call(mark)
    STAGES.add("call_e2e", t3 - t0)
    return out


class PeersV1Servicer:
    def __init__(self, instance: Instance):
        self.instance = instance

    async def GetPeerRateLimits(self, wire: bytes, context):
        # owner-serve hop of a distributed trace (r16): a forwarding
        # peer's sampled context arrives as gRPC metadata; the owner
        # records its own queue/device spans under the SAME trace id
        # in its own flight recorder.
        # `wire` is the serialised GetPeerRateLimitsReq
        # (api/grpc_glue.py registers the method pass-through): the
        # fold serves it as arrays where it can, and what it declines
        # is parsed here and served through request objects. What the
        # call enqueues on the batcher is THIS sender's: its lane of
        # the queue, collected in turn with this node's own doors'
        with peer_rows(context.peer()):
            out = await _serve_folded(self.instance, context, wire)
            if out is not None:
                return out
            try:
                request = peers_pb2.GetPeerRateLimitsReq.FromString(wire)
            except DecodeError:
                # what grpc answers when its own deserializer raises
                await context.abort(
                    grpc.StatusCode.INTERNAL,
                    "Exception deserializing request!",
                )
            return await _serve_call(
                self.instance, "peers", context, request.requests,
                self.instance.get_peer_rate_limits, _peers_reply,
            )

    async def UpdatePeerGlobals(self, request, context):
        updates = [
            (g.key, convert.resp_from_pb(g.status))
            for g in request.globals
        ]
        # background gossip sends bare metadata; only an install that
        # originated inside a traced request carries context here
        tracer = self.instance.tracer
        tp = _md_traceparent(context)
        trace = (
            tracer.join("peers_update", tracing.parse_traceparent(tp))
            if tp
            else None
        )
        with tracing.scope(tracer, trace):
            await self.instance.update_peer_globals(updates)
        return peers_pb2.UpdatePeerGlobalsResp()

    async def ReplicateBuckets(self, request, context):
        from gubernator_tpu.serve.replication import Snapshot

        snaps = [
            Snapshot(
                key=b.key,
                algorithm=b.algorithm,
                limit=b.limit,
                duration=b.duration,
                remaining=b.remaining,
                reset_time=b.reset_time,
                status=b.status,
                snapshot_ms=b.snapshot_ms,
            )
            for b in request.buckets
        ]
        tracer = self.instance.tracer
        tp = _md_traceparent(context)
        trace = (
            tracer.join(
                "peers_replicate", tracing.parse_traceparent(tp)
            )
            if tp
            else None
        )
        with tracing.scope(tracer, trace):
            await self.instance.replicate_buckets(request.owner, snaps)
        return peers_pb2.ReplicateBucketsResp()


def register_servicers(grpc_server, instance: Instance):
    """Embed gubernator in a caller-owned `grpc.aio` server.

    The reference explicitly supports this shape: the application
    provides the gRPC server and drives peer membership itself
    (reference config.go:29-30, architecture.md:79-91). Here the same
    contract: register the V1 + PeersV1 services on `grpc_server` and
    return the instance (for chaining). The caller owns the server
    lifecycle and discovery:

        backend = make_backend(conf)          # or any backend object
        instance = Instance(conf, backend)
        instance.start()                      # batcher + gossip tasks
        register_servicers(my_grpc_server, instance)
        await my_grpc_server.start()
        await instance.set_peers([PeerInfo(address=..., is_owner=...)])
        ...
        await instance.stop()                 # before the loop closes

    Notes: call inside the event loop that will run the server —
    Instance.start() binds its batcher to the running loop; set_peers
    replaces the full membership each call (pass every live peer, with
    is_owner=True on this node's own advertise address); warmup of a
    device backend (backend.warmup()) is the caller's pre-serve step,
    as in Server._start_inner."""
    add_v1_servicer(grpc_server, V1Servicer(instance))
    add_peers_servicer(grpc_server, PeersV1Servicer(instance))
    return instance


#: content type gating the HTTP gateway's binary GEB door (r12) — a
#: deliberate mirror of client_geb.GEB_CONTENT_TYPE (the client module
#: must not be a serving-tier dependency; test-pinned equal)
GEB_CONTENT_TYPE = "application/x-guber-geb"


class Server:
    """One daemon: gRPC + HTTP, an Instance, and discovery."""

    _profiling = False
    _edge = None
    _geb = None
    _geb_core = None

    def __init__(self, conf: ServerConfig, backend=None):
        self.conf = conf
        self.backend = backend if backend is not None else make_backend(conf)
        self.instance = Instance(conf, self.backend)
        self.grpc_server: Optional[grpc.aio.Server] = None
        self._http_runner: Optional[web.AppRunner] = None
        self._pool = None
        # read by the scrape and snapshot handlers only
        self.thread_clocks = ThreadClocks()
        #: the process's ProcessProbes once something started them
        #: (run_daemon, at Ready): their counts are exported at scrape
        self.probes: Optional[ProcessProbes] = None

    def device_report(self) -> dict:
        """What this daemon serves from, for the boot log and
        /v1/debug/stages: the devices as JAX reports them (None on the
        exact backend, which touches no device) and which host-side
        implementations — fused native prep, slot hasher — serve:
        both follow the ONE fact core/hashing.native_lib() holds (a
        missing, unloadable or stale .so never aborts startup: it is
        absent, and native_lib's one warning says why)."""
        native = native_lib() is not None
        return {
            "device": self._describe_device(),
            "host_prep": "native" if native else "numpy",
            "hasher": "native" if native else "python",
        }

    def _mesh_engine(self):
        """The backend's engine where it lays device batches out per
        shard (a mesh), else None."""
        engine = getattr(self.backend, "engine", None)
        return None if getattr(engine, "flat", True) else engine

    def _describe_device(self):
        """The devices as JAX reports them, with the engine's state
        bytes on each; None on the exact backend."""
        engine = getattr(self.backend, "engine", None)
        if engine is None:
            return None
        from gubernator_tpu.jaxenv import describe_devices

        by_dev = getattr(engine, "state_bytes_by_device", None)
        return describe_devices(by_dev() if by_dev else None)

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> None:
        try:
            await self._start_inner()
        except Exception:
            # a partial start (bind failure, bad static peer, edge socket
            # in use, ...) must not leak the instance's already-running
            # tasks: the caller's loop may close next, and a still-pending
            # flusher dies with "Task was destroyed but it is pending"
            await self.stop()
            raise

    async def _start_inner(self) -> None:
        warmup = getattr(self.backend, "warmup", None)
        if warmup is not None:
            # compile every device-batch bucket before accepting traffic:
            # one decide program costs about a minute of TPU compile with
            # a cold cache (CHANGES.md, PR 21) and must never be paid
            # inside a request deadline
            await asyncio.to_thread(warmup)
        self.instance.start()
        if self.instance.checkpoint is not None:
            # boot-time warm restore (r19) BEFORE any door opens: the
            # batcher is running (installs need it) but no traffic can
            # race the install. Every failure path inside boots cold
            # and loudly — a bad checkpoint must never wedge a boot.
            await self.instance.checkpoint.restore()

        self.grpc_server = grpc.aio.server(
            interceptors=[StatsInterceptor()],
            options=[("grpc.max_receive_message_length", 1 << 20)],
        )
        register_servicers(self.grpc_server, self.instance)
        bound = self.grpc_server.add_insecure_port(self.conf.grpc_address)
        if bound == 0:
            raise RuntimeError(
                f"failed to bind gRPC address {self.conf.grpc_address}"
            )
        await self.grpc_server.start()
        log.info("gRPC listening on %s", self.conf.grpc_address)
        dev = self.device_report()
        if dev["device"] is not None:
            d = dev["device"]
            log.info(
                "serving from %s (%s) x %d; per device after warm-up: %s",
                d["platform"], d["kind"], d["count"],
                ", ".join(
                    "#%d state %.0f MiB, in use %s, peak %s" % (
                        x["id"], x["state_bytes"] / (1 << 20),
                        *("n/a" if x[k] is None
                          else "%.0f MiB" % (x[k] / (1 << 20))
                          for k in ("bytes_in_use", "peak_bytes_in_use")),
                    )
                    for x in d["devices"]
                ),
            )
        if dev["host_prep"] == "native":
            engine = getattr(self.backend, "engine", None)
            forms = (
                engine.writeback_forms()
                if hasattr(engine, "writeback_forms") else {}
            )
            log.info(
                "native prep: %d thread(s) (GUBER_PREP_THREADS), "
                "writeback=%s (GUBER_WRITEBACK), form by rung: %s",
                native_lib().prep_threads(),
                os.environ.get("GUBER_WRITEBACK", "auto"),
                " ".join(
                    "%d:%s" % (b, "/".join(f)) for b, f in forms.items()
                ) or "n/a",
            )
        else:
            log.info(
                "native prep: libguberhash.so is absent (the warning "
                "above says why); numpy fallbacks active"
            )
        log.info(
            "slot hasher: %s",
            "native XXH64 (libguberhash.so)"
            if dev["hasher"] == "native"
            else "pure-Python blake2b fallback (libguberhash.so is "
            "absent: make -C gubernator_tpu/native)",
        )
        log.info(
            "traffic observers: %s",
            "one native fold a batch, GIL released (libguberhash.so "
            "guber_traffic_fold; traffic_native_folds_total)"
            if self.instance.traffic.implementation == "native"
            else "Python SpaceSaving + HyperLogLog on the serving loop "
            "(libguberhash.so is absent: make -C gubernator_tpu/native; "
            "traffic_python_folds_total)",
        )

        engine = self._mesh_engine()
        if engine is not None:
            log.info(
                "mesh batches: %s",
                "merged and laid out per shard in one native call, GIL "
                "released (libguberhash.so guber_merge_runs_sharded; "
                "mesh_native_stacks_total)"
                if engine.stack_implementation == "native"
                else "laid out per shard in numpy on the submit thread "
                "(a multi-host mesh, whose merged batch crosses flat; "
                "else libguberhash.so is absent: make -C "
                "gubernator_tpu/native; mesh_numpy_stacks_total)",
            )

        shed = self.instance.shed
        if shed is not None:
            # boot-time sizing lint, like the store footprint pass in
            # make_backend: an over-provisioned shed bound is host
            # memory that can never hold a live verdict
            from gubernator_tpu.serve.shedcache import (
                footprint_mib,
                lint_footprint,
            )

            cap = 0
            stats = self.backend.stats()
            if "size" not in stats:  # device backends: rows * slots
                try:
                    sc = self.conf.store_config(logger=log)
                    cap = sc.rows * sc.slots
                except Exception:
                    cap = 0
            lint = lint_footprint(shed.capacity, cap)
            if lint:
                log.warning("%s", lint)
            log.info(
                "over-limit shed cache: %d keys (~%.1f MiB) "
                "(GUBER_SHED_CACHE / GUBER_SHED_CACHE_KEYS)",
                shed.capacity, footprint_mib(shed.capacity),
            )
            log.info(
                "shed screen: %s",
                "a frame's consult and its population are one native "
                "call each, GIL released (libguberhash.so "
                "guber_shed_screen / guber_shed_observe; "
                "shed_native_consults_total)"
                if shed.screen_implementation == "native"
                else "numpy on the serving loop, call by call "
                "(libguberhash.so is absent: make -C "
                "gubernator_tpu/native; shed_numpy_consults_total)",
            )
        else:
            log.info("over-limit shed cache: off (GUBER_SHED_CACHE=0)")

        repl = self.instance.repl
        if repl is not None:
            from gubernator_tpu.serve.replication import footprint_mib

            log.info(
                "bucket replication: on — window %.0f ms, standby "
                "bound %d keys (~%.1f MiB), backlog %d "
                "(GUBER_REPLICATION / GUBER_REPLICATION_SYNC_WAIT_MS / "
                "GUBER_REPLICATION_STANDBY_KEYS / "
                "GUBER_REPLICATION_BACKLOG)",
                repl.sync_wait * 1e3, repl.standby_cap,
                footprint_mib(repl.standby_cap), repl.backlog_cap,
            )
        else:
            log.info("bucket replication: off (GUBER_REPLICATION=0)")

        resc = self.instance.rescale
        if resc is not None:
            log.info(
                "elastic rescale: on — double-serve window %.0f ms, "
                "tracked-key bound %d, flush tick %.0f ms "
                "(GUBER_RESCALE / GUBER_RESCALE_DOUBLE_SERVE_MS / "
                "GUBER_RESCALE_TRACK_KEYS / "
                "GUBER_REPLICATION_SYNC_WAIT_MS)",
                resc.double_serve_s * 1e3, resc.track_cap,
                resc.sync_wait * 1e3,
            )
        else:
            log.info("elastic rescale: off (GUBER_RESCALE=0)")

        ckpt = self.instance.checkpoint
        if ckpt is not None:
            from gubernator_tpu.serve.checkpoint import (
                disk_footprint_mib,
            )

            log.info(
                "checkpoint/restore: on — dir %r, interval %.0f ms, "
                "max restore age %.0f s, tracked-key bound %d "
                "(~%.1f MiB on disk), export targets %s "
                "(GUBER_CHECKPOINT_DIR / GUBER_CHECKPOINT_INTERVAL_MS "
                "/ GUBER_CHECKPOINT_MAX_AGE_MS / "
                "GUBER_CHECKPOINT_TRACK_KEYS / "
                "GUBER_CHECKPOINT_EXPORT_PEERS)",
                ckpt.dir, ckpt.sync_wait * 1e3, ckpt.max_age,
                ckpt.track_cap, disk_footprint_mib(ckpt.track_cap),
                ckpt.export_peers or "none",
            )
        else:
            log.info(
                "checkpoint/restore: off (GUBER_CHECKPOINT_DIR unset)"
            )

        if self.conf.geb_port:
            from gubernator_tpu.serve.edge_bridge import GebListener

            geb_peer_doors = {}
            for pair in self.conf.geb_peer_doors.split(","):
                if not pair.strip():
                    continue
                grpc_addr, sep, door = pair.strip().partition("=")
                if not sep or not grpc_addr or not door:
                    raise ValueError(
                        "GUBER_GEB_PEER_DOORS entries must be "
                        f"'grpc_addr=door_addr', got {pair!r}"
                    )
                geb_peer_doors[grpc_addr] = door
            self._geb = GebListener(
                self.instance,
                f"0.0.0.0:{self.conf.geb_port}",
                fast_enabled=self.conf.edge_fast,
                window=self.conf.geb_window or self.conf.edge_window,
                peer_bridges=geb_peer_doors or None,
            )
            await self._geb.start()
            log.info(
                "GEB client protocol door on :%d (GUBER_GEB_PORT; "
                "window %d, GUBER_GEB_WINDOW)",
                self.conf.geb_port, self._geb.window,
            )
        if self.conf.http_address:
            await self._start_http()
        if self.conf.edge_socket or self.conf.edge_tcp:
            from gubernator_tpu.serve.edge_bridge import EdgeBridge

            peer_bridges = {}
            for pair in self.conf.edge_peer_bridges.split(","):
                if not pair.strip():
                    continue
                grpc_addr, sep, bridge = pair.strip().partition("=")
                if not sep or not grpc_addr or not bridge:
                    raise ValueError(
                        "GUBER_EDGE_PEER_BRIDGES entries must be "
                        f"'grpc_addr=bridge_addr', got {pair!r}"
                    )
                peer_bridges[grpc_addr] = bridge
            self._edge = EdgeBridge(
                self.instance,
                self.conf.edge_socket,
                tcp_address=self.conf.edge_tcp,
                peer_bridges=peer_bridges,
                fast_enabled=self.conf.edge_fast,
                window=self.conf.edge_window,
                max_payload=self.conf.edge_max_frame_mib << 20,
                shm_enabled=self.conf.shm,
                shm_ring_kib=self.conf.shm_ring_kib,
                shm_poll_us=self.conf.shm_poll_us,
            )
            await self._edge.start()

        await self._start_discovery()

    async def drain(self) -> dict:
        """Graceful drain (SIGTERM path), bounded end to end by
        GUBER_DRAIN_TIMEOUT_MS: (1) deregister from discovery so peers
        and edges stop routing new work here; (2) the edge bridge
        refuses NEW frames (GEBR drain code) after answering the ones
        in flight; (3) the gRPC server and (4) the HTTP gateway stop
        accepting and let in-flight requests finish — every request
        door is closed BEFORE the queue flushes, or the batcher's
        run-dry wait could chase a moving target; (5) aggregated
        GLOBAL hits/updates flush to their owners; (6) the device
        batcher runs dry. Each step gets the budget remaining; a step
        that times out keeps its handle so the caller's stop() still
        hard-closes it. Returns step timings (the chaos soak records
        them)."""
        t0 = time.monotonic()
        budget = getattr(self.conf, "drain_timeout", 5.0)
        deadline = t0 + budget

        def remaining() -> float:
            return max(0.05, deadline - time.monotonic())

        timings = {}

        async def step(name, coro) -> bool:
            t = time.monotonic()
            ok = True
            try:
                await asyncio.wait_for(coro, remaining())
            except asyncio.TimeoutError:
                log.warning("drain step '%s' exceeded the budget", name)
                ok = False
            except Exception as e:
                log.warning("drain step '%s' failed: %s", name, e)
            timings[name] = time.monotonic() - t
            return ok

        if self.instance.rescale is not None:
            # planned-departure handoff (r17) BEFORE deregistration:
            # every tracked window ships to the owner the ring elects
            # once this node is gone, so the snapshots are parked on
            # their new owners before any peer's ring flips — the
            # receiving side seeds them on its first owned touch
            await step(
                "rescale_handoff", self.instance.rescale.drain()
            )
        if self._pool is not None:
            if await step("deregister", self._pool.close()):
                self._pool = None
        if self._edge is not None:
            # self-bounding (its poll loop carries the deadline): no
            # wait_for, so it is never cancelled mid-refusal
            t = time.monotonic()
            await self._edge.drain(remaining())
            timings["edge"] = time.monotonic() - t
        if self._geb is not None:
            # the client-protocol door drains like the bridge: answer
            # accepted frames, GEBR-refuse new ones, close the listener
            t = time.monotonic()
            await self._geb.drain(remaining())
            timings["geb"] = time.monotonic() - t
        if self.conf.http_address:
            # HTTP-door frame core: flag it so a frame POSTed
            # mid-drain gets the GEBR drain body (the HTTP runner
            # cleanup below bounds the in-flight ones). Built through
            # _frame_core(), not checked-if-built: a node that saw no
            # GEB traffic yet must still refuse the first frame that
            # races the drain, instead of lazily building an
            # un-flagged core for it
            self._frame_core()._draining = True
        if self.grpc_server is not None:
            # grace makes stop() self-bounding (handlers are
            # force-cancelled when it expires) — and it must NOT run
            # under wait_for: cancelling grpc.aio's stop() mid-flight
            # leaves the server in a state where a LATER stop() can
            # await forever (observed as SIGTERMed daemons outliving
            # their supervisor's kill timeout by minutes)
            t = time.monotonic()
            await self.grpc_server.stop(grace=remaining())
            timings["grpc"] = time.monotonic() - t
            self.grpc_server = None
        if self._http_runner is not None:
            # stops the sites (no new connections) and shuts the app
            # down, finishing in-flight handlers — without this, HTTP
            # requests accepted mid-drain would be reset by stop().
            # Bounded by the site's shutdown_timeout (2s, _start_http)
            # on top of the wait_for; a timed-out cleanup keeps the
            # handle so stop() finishes it
            if await step("http", self._http_runner.cleanup()):
                self._http_runner = None
        await step("global_flush", self.instance.global_mgr.drain())
        if self.instance.repl is not None:
            # ship still-dirty owned windows to their successors (and
            # attempt one handback round) before the batcher runs dry —
            # a SIGTERMed owner must not take its freshest quota state
            # down with it
            await step(
                "replication_flush", self.instance.repl.drain()
            )
        if self.instance.checkpoint is not None:
            # final checkpoint + blue-green export (r19): state on
            # disk (and on the replacement fleet) leaves at most one
            # in-flight request stale instead of one interval
            await step(
                "checkpoint_flush", self.instance.checkpoint.drain()
            )
        await step("batcher", self.instance.batcher.drain())
        timings["total"] = time.monotonic() - t0
        try:
            metrics.DRAIN_DURATION.set(timings["total"])
        except Exception:  # pragma: no cover - defensive
            pass
        log.info(
            "drained in %.0f ms (budget %.0f ms): %s",
            timings["total"] * 1e3, budget * 1e3,
            {k: round(v * 1e3, 1) for k, v in timings.items()},
        )
        return timings

    async def stop(self) -> None:
        if self._edge is not None:
            await self._edge.stop()
            self._edge = None
        if self._geb is not None:
            await self._geb.stop()
            self._geb = None
        self._geb_core = None
        if self._pool is not None:
            await self._pool.close()
            self._pool = None
        if self._http_runner is not None:
            await self._http_runner.cleanup()
            self._http_runner = None
        if self.grpc_server is not None:
            await self.grpc_server.stop(grace=1.0)
            self.grpc_server = None
        await self.instance.stop()
        close = getattr(self.backend, "close", None)
        if close is not None:
            close()  # e.g. MultiHostBackend: clean step-pipe shutdown

    # -- HTTP gateway -------------------------------------------------------

    async def _start_http(self) -> None:
        app = web.Application()
        app.router.add_post("/v1/GetRateLimits", self._http_get_rate_limits)
        # protobuf-free binary door (r12): one GEB frame per POST body,
        # content-type gated; GET serves the hello (ring + flags) so a
        # fast client can negotiate exactly like the socket doors
        app.router.add_post("/v1/geb", self._http_geb)
        app.router.add_get("/v1/geb", self._http_geb_hello)
        app.router.add_get("/v1/HealthCheck", self._http_health)
        app.router.add_get("/metrics", self._http_metrics)
        app.router.add_get("/v1/debug/stats", self._http_debug_stats)
        app.router.add_get("/v1/debug/stages", self._http_debug_stages)
        app.router.add_get("/v1/debug/traces", self._http_debug_traces)
        app.router.add_get("/v1/debug/profile", self._http_debug_profile)
        self._http_runner = web.AppRunner(app)
        await self._http_runner.setup()
        host, _, port = self.conf.http_address.rpartition(":")
        # shutdown_timeout bounds how long cleanup() waits for open
        # connections (aiohttp default: 60s!). Rate-limit requests are
        # milliseconds of work, so 2s covers any in-flight handler
        # while keeping SIGTERM (drain, then stop) promptly bounded —
        # a lingering idle keep-alive must not stall shutdown.
        site = web.TCPSite(
            self._http_runner, host or "0.0.0.0", int(port),
            shutdown_timeout=2.0,
        )
        await site.start()
        log.info("HTTP listening on %s", self.conf.http_address)

    async def _http_get_rate_limits(self, request: web.Request):
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            # JSONDecodeError for bad JSON; UnicodeDecodeError for a
            # non-UTF-8 body (raised by aiohttp's .text() underneath)
            return web.json_response({"error": "invalid json"}, status=400)
        # shape-validate before field access: a JSON array or scalar body
        # (or a non-list "requests") must be a 400, not an unhandled
        # AttributeError turned 500
        if not isinstance(body, dict) or not isinstance(
            body.get("requests", []), list
        ):
            return web.json_response(
                {"error": "body must be an object with a 'requests' list"},
                status=400,
            )
        reqs = []
        try:
            for item in body.get("requests", []):
                pb = gubernator_pb2.RateLimitReq(
                    name=item.get("name", ""),
                    unique_key=item.get(
                        "uniqueKey", item.get("unique_key", "")
                    ),
                    hits=int(item.get("hits", 0)),
                    limit=int(item.get("limit", 0)),
                    duration=int(item.get("duration", 0)),
                    algorithm=_enum_val(
                        gubernator_pb2.Algorithm, item.get("algorithm", 0)
                    ),
                    behavior=_enum_val(
                        gubernator_pb2.Behavior, item.get("behavior", 0)
                    ),
                )
                # hierarchical quota chain (r15): ancestor levels,
                # shallow to deep; depth/behavior validation happens
                # serving-side (instance.chain_error)
                for lv in item.get("chain", []) or []:
                    pb.chain.add(
                        unique_key=str(lv.get("uniqueKey",
                                              lv.get("unique_key", ""))),
                        limit=int(lv.get("limit", 0)),
                        duration=int(lv.get("duration", 0)),
                    )
                reqs.append(convert.req_from_pb(pb))
        except (AttributeError, TypeError, ValueError) as e:
            # non-object items, non-numeric int64 fields, bad enum names
            return web.json_response(
                {"error": f"invalid request item: {e}"}, status=400
            )
        # traceparent on the JSON door (r16): an incoming sampled
        # context joins the distributed trace; otherwise head/tail
        # sampling applies exactly as on the socket doors
        tracer = self.instance.tracer
        trace = tracer.join(
            "http",
            tracing.parse_traceparent(
                request.headers.get(tracing.TRACEPARENT)
            ),
        )
        try:
            with tracing.scope(tracer, trace) as tr:
                if tr is not None:
                    tr.annotate(items=len(reqs))
                resps = await self.instance.get_rate_limits(reqs)
        except BatchTooLargeError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.json_response(
            {
                "responses": [
                    {
                        "status": r.status.name,
                        "limit": str(r.limit),
                        "remaining": str(r.remaining),
                        "resetTime": str(r.reset_time),
                        "error": r.error,
                        "metadata": r.metadata,
                    }
                    for r in resps
                ]
            }
        )

    def _frame_core(self):
        """Frame-service core backing the HTTP binary door: the GEB
        listener when enabled (so drain state is shared), else a
        lazily-built listenerless FrameService over the same instance
        — either way the exact decode/shed/batch/encode pipeline the
        socket doors run (serve/edge_bridge.py)."""
        if self._geb is not None:
            return self._geb
        if self._geb_core is None:
            from gubernator_tpu.serve.edge_bridge import FrameService

            self._geb_core = FrameService(
                self.instance,
                fast_enabled=self.conf.edge_fast,
                window=self.conf.geb_window or self.conf.edge_window,
            )
        return self._geb_core

    async def _http_geb_hello(self, request: web.Request):
        return web.Response(
            body=self._frame_core().hello_bytes(),
            content_type=GEB_CONTENT_TYPE,
        )

    async def _http_geb(self, request: web.Request):
        """Binary GEB frame door (r12): the edge wire protocol with an
        HTTP request body as the transport — for clients whose
        infrastructure only passes HTTP. Content-type gated so a JSON
        client posting to the wrong path gets a clear 415, never a
        frame-decode of its JSON bytes."""
        if request.content_type != GEB_CONTENT_TYPE:
            return web.json_response(
                {
                    "error": (
                        f"content-type must be {GEB_CONTENT_TYPE} "
                        f"(one binary GEB frame per request body)"
                    )
                },
                status=415,
            )
        import struct

        from gubernator_tpu.serve.edge_bridge import MAX_FRAME_PAYLOAD

        # this door's legal frames exceed aiohttp's 1 MiB default
        # client_max_size (a full 65536-item fast frame is ~2.1 MiB),
        # so it reads the raw stream under its OWN cap — the socket
        # doors' payload bound plus frame-header slack — rather than
        # raising the app-wide bound for the JSON routes too. Not
        # request.read(): that enforces (only) the app-wide limit.
        max_body = MAX_FRAME_PAYLOAD + 64
        if (request.content_length or 0) > max_body:
            return web.json_response(
                {"error": "GEB frame exceeds the payload bound"},
                status=413,
            )
        chunks, got = [], 0
        while True:
            # StreamReader.read(n) short-reads, so loop to EOF,
            # bailing the moment the cap is crossed
            chunk = await request.content.read(1 << 16)
            if not chunk:
                break
            got += len(chunk)
            if got > max_body:
                return web.json_response(
                    {"error": "GEB frame exceeds the payload bound"},
                    status=413,
                )
            chunks.append(chunk)
        body = b"".join(chunks)
        try:
            # a traceparent header on the binary door joins the frame
            # to an existing trace (the GEBT in-frame extension works
            # here too; the header covers clients that can set HTTP
            # headers more easily than re-framing)
            resp = await self._frame_core().serve_frame_bytes(
                body,
                remote_ctx=tracing.parse_traceparent(
                    request.headers.get(tracing.TRACEPARENT)
                ),
            )
        except (ValueError, struct.error) as e:
            # struct.error covers truncated varlen payloads that pass
            # the outer length checks — client garbage, still a 400
            return web.json_response(
                {"error": f"bad GEB frame: {e}"}, status=400
            )
        except BatchTooLargeError as e:
            return web.json_response({"error": str(e)}, status=400)
        return web.Response(body=resp, content_type=GEB_CONTENT_TYPE)

    async def _http_health(self, request: web.Request):
        h = self.instance.health_check()
        return web.json_response(
            {
                "status": h.status,
                "message": h.message,
                "peerCount": h.peer_count,
            }
        )

    async def _http_metrics(self, request: web.Request):
        self._refresh_store_metrics()
        return web.Response(
            body=metrics.render(), content_type="text/plain", charset="utf-8"
        )

    def _refresh_store_metrics(self) -> None:
        stats = self.backend.stats()
        if "size" in stats:
            metrics.CACHE_SIZE.set(stats["size"])
        metrics.DISTINCT_KEYS.set(self.instance.traffic.hll.estimate())
        # per-peer breaker state gauges refresh at scrape time (state
        # also changes lazily at acquire, so transitions alone would
        # leave the gauge stale between calls)
        for peer in self.instance.peer_list():
            if peer.breaker is not None:
                metrics.PEER_BREAKER_STATE.labels(peer=peer.host).set(
                    peer.breaker.state_code
                )
        # device memory against state bytes, from the allocator's own
        # statistics (None on the CPU, which keeps none: the gauges
        # stay 0); on a mesh the fullest device's
        device = self._describe_device()
        if device is not None:
            per = device["devices"]
            metrics.DEVICE_MEMORY_PEAK.set(
                max(x["peak_bytes_in_use"] or 0 for x in per))
            metrics.DEVICE_MEMORY_LIMIT.set(
                max(x["bytes_limit"] or 0 for x in per))
            metrics.STORE_STATE_BYTES.set(
                max(x["state_bytes"] for x in per))
        # shed-cache totals export lazily at scrape time too: the hot
        # path only bumps plain ints (serve/shedcache.py)
        shed = self.instance.shed
        if shed is not None:
            metrics.SHED_HITS.set(shed.hits)
            metrics.SHED_LOOKUPS.set(shed.lookups)
            metrics.SHED_ENTRIES.set(len(shed))
            metrics.SHED_INDEX_USES.set(shed.index_uses)
            metrics.SHED_INDEX_REBUILDS.set(shed.index_rebuilds)
            metrics.SHED_NATIVE_CONSULTS.set(shed.native_consults)
            metrics.SHED_NUMPY_CONSULTS.set(shed.numpy_consults)
        metrics.PEER_SERVE_BATCHES.set(self.instance.peer_serve_batches)
        metrics.PEER_SERVE_ITEMS.set(self.instance.peer_serve_items)
        metrics.PEER_SERVE_SHED_HITS.set(self.instance.peer_serve_shed_hits)
        metrics.PEER_SERVE_FOLDED_ITEMS.set(
            self.instance.peer_serve_folded_items
        )
        batcher = self.instance.batcher
        for source, rows in batcher.rows_by_source.items():
            metrics.DEVICE_BATCH_ROWS.labels(source=source).set(rows)
        metrics.DEVICE_BATCHES_MIXED.set(batcher.mixed_batches)
        metrics.DEVICE_GROUPS_OVERTAKING.set(batcher.groups_overtaking)
        fwd = self.instance.peer_forward
        metrics.PEER_FORWARD_BATCHES.set(fwd.batches)
        metrics.PEER_FORWARD_ITEMS.set(fwd.items)
        for reason, items in fwd.failed.items():
            metrics.PEER_FORWARD_FAILED_ITEMS.labels(reason=reason).set(items)
        split = self.instance.edge_split
        metrics.EDGE_SPLIT_FRAMES.set(split.frames)
        for lane, items in split.items.items():
            metrics.EDGE_SPLIT_ITEMS.labels(lane=lane).set(items)
        for reason, frames in split.declined.items():
            metrics.EDGE_SPLIT_DECLINED.labels(reason=reason).set(frames)
        traffic = self.instance.traffic
        metrics.TRAFFIC_NATIVE_FOLDS.set(traffic.native_folds)
        metrics.TRAFFIC_PYTHON_FOLDS.set(traffic.python_folds)
        engine = self._mesh_engine()
        if engine is not None:
            metrics.MESH_NATIVE_STACKS.set(engine.native_stacks)
            metrics.MESH_NUMPY_STACKS.set(engine.numpy_stacks)
        if self.instance.repl is not None:
            metrics.REPLICATION_STANDBY_ENTRIES.set(
                self.instance.repl.standby_len
            )
        # stage totals export lazily at scrape time: the hot path only
        # touches the plain-float accumulator (serve/stages.py)
        snap = STAGES.snapshot()
        for name, s in snap["stages"].items():
            metrics.STAGE_SECONDS.labels(stage=name).set(s["total_s"])
            metrics.STAGE_SAMPLES.labels(stage=name).set(s["count"])
        # what the serving threads ran, beside what the spans say they
        # were taken: the threads' CPU clocks, read here and in
        # /v1/debug/stages only (this handler runs on the serving loop)
        threads = self.thread_clocks.snapshot()
        metrics.THREAD_WALL_SECONDS.set(threads["wall_s"])
        for role, cpu_s in threads["cpu_s"].items():
            metrics.THREAD_CPU_SECONDS.labels(thread=role).set(cpu_s)
        # the process probes' two counts (a daemon's: run_daemon starts
        # them at Ready; an in-process cluster has none and reads 0)
        probes = self.probes
        if probes is not None:
            metrics.LOOP_PAUSES_OVER_HALF_DEADLINE.set(probes.pauses_over)
            metrics.PROGRAMS_BUILT_AFTER_READY.set(probes.programs_built)
        # queue-visibility gauges (r16): standing occupancy the stage
        # clock can't express, set lazily at scrape like shed_entries
        qs = self.instance.batcher.queue_stats()
        metrics.BATCHER_QUEUE_DEPTH.set(qs["depth"])
        metrics.BATCHER_QUEUE_AGE.set(qs["oldest_age_s"])
        metrics.PREP_BACKLOG.set(qs["prep_backlog"])
        for door, svc in (("edge", self._edge), ("geb", self._geb)):
            if svc is not None:
                metrics.FRAME_INFLIGHT.labels(door=door).set(
                    svc._active_frames
                )
                metrics.FRAME_CONNECTIONS.labels(door=door).set(
                    len(svc._conns)
                )
        if self.instance.repl is not None:
            metrics.REPLICATION_BACKLOG_ENTRIES.set(
                self.instance.repl.backlog_len
            )
        ckpt = self.instance.checkpoint
        if ckpt is not None:
            metrics.CHECKPOINT_TRACKED_ENTRIES.set(ckpt.tracked_len)
            # age refreshes at scrape time (the flush loop only stamps
            # last_ok_ms) so operators see it GROW when writes fail
            age = ckpt.age_seconds
            if age is not None:
                metrics.CHECKPOINT_AGE.set(age)
        if self.instance.rescale is not None:
            metrics.RESCALE_TRACKED_ENTRIES.set(
                self.instance.rescale.tracked_len
                + self.instance.rescale.pending_len
            )
        for queue, size in (
            self.instance.global_mgr.backlog_sizes().items()
        ):
            metrics.GLOBAL_BACKLOG_ENTRIES.labels(queue=queue).set(size)
        # flight-recorder counters (r16): plain ints on the recorder,
        # exported here
        rec = self.instance.tracer.recorder
        metrics.TRACES_STARTED.set(rec.started)
        metrics.TRACES_RECORDED.set(rec.recorded)
        metrics.TRACES_TAIL_CAPTURED.set(rec.tail_captured)
        metrics.TRACES_DROPPED.set(rec.dropped)
        metrics.TRACE_SLOW_THRESHOLD.set(rec.threshold_ms())

    async def _http_debug_stats(self, request: web.Request):
        """Traffic observability: HLL cardinality + top hot keys + backend
        counters (no reference analogue; see core/sketches.py)."""
        try:
            top_n = int(request.query.get("top", "20"))
        except ValueError:
            return web.json_response(
                {"error": "'top' must be an integer"}, status=400
            )
        body = self.instance.traffic.snapshot(max(top_n, 0))
        body["backend"] = self.backend.stats()
        return web.json_response(body)

    async def _http_debug_stages(self, request: web.Request):
        """Serving-pipeline stage attribution (serve/stages.py): where
        one served decision's wall time goes — edge transit, frame
        decode, batcher queue, device span (with the submit/fetch
        split), response encode — plus the coverage of those stages
        against frame end-to-end time. `?reset=1` zeroes the
        accumulators (the profiler scopes a measurement window with
        it). The reference has per-RPC Prometheus totals only; this is
        the decomposition that says which stage to attack next."""
        shed = self.instance.shed
        if request.query.get("reset") in ("1", "true"):
            STAGES.reset()
            if shed is not None:
                shed.reset_counters()
        body = STAGES.snapshot()
        # over-limit shed cache counters ride along (entries, hits,
        # lookups, hit_rate): the shed stage's spans above say where
        # the time went, this says how much work never became a stage
        if shed is not None:
            body["shed_cache"] = shed.stats()
        # the serving threads' CPU clocks at this instant, cumulative
        # since the process began (a reset does not touch them: a
        # reader differences two snapshots' `cpu_s` and `wall_s`)
        body["threads"] = self.thread_clocks.snapshot()
        # the batcher's rows by who sent them and the process probes'
        # two counts, as /metrics has them (cumulative: a reset does
        # not touch them)
        batcher, probes = self.instance.batcher, self.probes
        body["batch_rows"] = dict(
            batcher.rows_by_source, mixed_batches=batcher.mixed_batches,
            groups_overtaking=batcher.groups_overtaking,
        )
        if probes is not None:
            body["process"] = {
                "pause_threshold_s": probes.pause_s,
                "loop_pauses_over_half_deadline": probes.pauses_over,
                "programs_built_after_ready": probes.programs_built,
            }
        # what served those stages: devices as JAX reports them, with
        # per-device bytes, and the host-prep / hasher implementations
        body.update(self.device_report())
        return web.json_response(body)

    async def _http_debug_traces(self, request: web.Request):
        """The flight recorder (r16, serve/tracing.py): completed
        sampled + tail-captured traces, newest last. `?id=<32-hex>`
        fetches one trace by id (404 when it aged out of the ring);
        `?limit=N` bounds the listing (default 64); `?reset=1` clears
        the ring and counters (a profiler scopes a window with it,
        like /v1/debug/stages)."""
        rec = self.instance.tracer.recorder
        if request.query.get("reset") in ("1", "true"):
            rec.reset()
        tid = request.query.get("id", "")
        if tid:
            doc = rec.get(tid)
            if doc is None:
                return web.json_response(
                    {"error": f"no retained trace with id '{tid}'"},
                    status=404,
                )
            return web.json_response(doc)
        try:
            limit = int(request.query.get("limit", "64"))
        except ValueError:
            return web.json_response(
                {"error": "'limit' must be an integer"}, status=400
            )
        body = rec.snapshot(limit=max(0, limit))
        body["sample"] = self.instance.tracer.sample
        body["slow_ms"] = self.instance.tracer.slow_ms
        return web.json_response(body)

    async def _http_debug_profile(self, request: web.Request):
        """Capture a JAX/XLA device profile for ?ms= milliseconds (default
        1000) and write it under <tmpdir>/guber-profile/<?name=> (the
        process's temporary directory: /tmp unless TMPDIR says
        otherwise; ?name= is a
        single path component, default "trace"). ?python=0 leaves the
        profiler's Python tracer off: the host plane then
        holds the stage clock's own spans (serve/stages.py
        StageStats.span) and no Python frames, and the capture costs the
        serving loop less. The default is 1 on a node that owns every
        key and 0 on a member of a shared ring: STOPPING a
        Python-tracer capture holds the interpreter lock while it
        gathers every thread's frames — longer, on a busy owner, than
        the 0.5 s its peers give a forwarded batch
        (GUBER_BATCH_TIMEOUT_MS), so the capture would fail their
        forwards (PR 43: 868 items of one RPC, in the ring cell's
        traced run); ?python=1 asks for it all the same, and the reply
        says which it was and why (`python`, `python_from`). View with
        TensorBoard or
        Perfetto. The reference has no tracing at all
        (SURVEY.md section 5); this is the TPU-native replacement for its
        per-RPC Prometheus histograms when you need to see *inside* a
        batch."""
        import asyncio
        import os.path
        import tempfile

        base = os.path.join(tempfile.gettempdir(), "guber-profile")
        if request.query.get("list") in ("1", "true"):
            # served artifact dir (r16): enumerate captured profiles so
            # an operator can find what to pull into TensorBoard/
            # Perfetto without shelling into the box
            out = []
            try:
                for name in sorted(os.listdir(base)):
                    d = os.path.join(base, name)
                    if not os.path.isdir(d):
                        continue
                    files = size = 0
                    for dp, _, fs in os.walk(d):
                        files += len(fs)
                        size += sum(
                            os.path.getsize(os.path.join(dp, f))
                            for f in fs
                        )
                    out.append(
                        {"name": name, "files": files, "bytes": size}
                    )
            except FileNotFoundError:
                pass
            return web.json_response(
                {"base_dir": base, "profiles": out}
            )
        try:
            ms = int(request.query.get("ms", "1000"))
        except ValueError:
            return web.json_response(
                {"error": "'ms' must be an integer"}, status=400
            )
        ms = max(0, min(ms, 60_000))  # reported below as actually captured
        python = request.query.get("python")
        # said in the reply: the default follows the ring (docstring)
        python_from = "query"
        if python is None:
            shared = self.instance.picker.size() > 1
            python = "0" if shared else "1"
            python_from = (
                "default: a member of a shared ring" if shared
                else "default: a node that owns every key"
            )
        if python not in ("0", "1"):
            return web.json_response(
                {"error": "'python' must be 0 or 1"}, status=400
            )
        # `name` is a single path component under a fixed base — this is
        # the only write-capable endpoint on the HTTP surface, so clients
        # must not be able to aim it at arbitrary paths
        name = request.query.get("name", "trace")
        if os.path.basename(name) != name or name in ("", ".", ".."):
            return web.json_response(
                {"error": "'name' must be a bare directory name"},
                status=400,
            )
        out_dir = os.path.join(base, name)
        if self._profiling:
            return web.json_response(
                {"error": "profile already in progress"}, status=409
            )
        self._profiling = True
        started = False
        stop = {}
        try:
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = int(python)
            jax.profiler.start_trace(out_dir, profiler_options=options)
            started = True
            await asyncio.sleep(ms / 1000.0)
        except Exception as e:
            # a profiler that cannot start is a fault of this process
            # (every supported backend can trace), reported with its cause
            log.exception("profile capture failed")
            return web.json_response(
                {"error": f"profile capture failed: {type(e).__name__}: {e}"},
                status=500,
            )
        finally:
            # stop even on client disconnect (CancelledError) so the
            # endpoint is usable again without a restart. Off the
            # serving loop: stop_trace collects and writes the capture
            # for seconds, and calls must be answered meanwhile
            try:
                if started:
                    stop = await self._stop_capture(jax.profiler.stop_trace)
                    log.info(
                        "profile capture '%s' (%d ms, python tracer %s): "
                        "stop_trace took %.3f s on a worker thread and "
                        "held the serving loop for %.1f ms at most "
                        "(a forwarded batch's deadline: %.0f ms)",
                        name, ms, python, stop["stop_s"],
                        stop["loop_held_s"] * 1e3,
                        self.conf.behaviors.effective_peer_timeout() * 1e3,
                    )
            except Exception:
                log.exception("stop_trace failed")
            finally:
                self._profiling = False
        return web.json_response(
            {"trace_dir": out_dir, "captured_ms": ms,
             "python": int(python), "python_from": python_from, **stop}
        )

    @staticmethod
    async def _stop_capture(stop_trace) -> dict:
        """`stop_trace` on a worker thread, and what it cost the serving
        loop meanwhile: `stop_s`, the call's seconds, and `loop_held_s`,
        how late at worst a 10 ms timer on this loop fired while the
        call ran — the stop holds the interpreter lock in stretches (a
        Python-tracer capture's for seconds: PR 43), and on a ring
        member a stretch of half a second fails its peers' forwards."""
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        call = loop.run_in_executor(None, stop_trace)
        held = 0.0
        while not call.done():
            due = loop.time() + 0.010
            await asyncio.wait([call], timeout=0.010)
            held = max(held, loop.time() - due)
        call.result()
        return {"stop_s": loop.time() - t0, "loop_held_s": held}

    # -- discovery ----------------------------------------------------------

    async def _start_discovery(self) -> None:
        advertise = self.conf.resolved_advertise()
        if self.conf.etcd_endpoints:
            from gubernator_tpu.serve.discovery import EtcdPool

            self._pool = EtcdPool(
                endpoints=self.conf.etcd_endpoints,
                prefix=self.conf.etcd_prefix,
                advertise=advertise,
                on_update=self._on_peers,
                tls_cert=self.conf.etcd_tls_cert,
                tls_key=self.conf.etcd_tls_key,
                tls_ca=self.conf.etcd_tls_ca,
            )
            await self._pool.start()
        elif self.conf.k8s_endpoints_selector:
            from gubernator_tpu.serve.discovery import K8sPool

            self._pool = K8sPool(
                namespace=self.conf.k8s_namespace,
                selector=self.conf.k8s_endpoints_selector,
                pod_ip=self.conf.k8s_pod_ip,
                pod_port=self.conf.k8s_pod_port,
                on_update=self._on_peers,
            )
            await self._pool.start()
        else:
            from gubernator_tpu.serve.discovery import StaticPool

            self._pool = StaticPool(
                peers=self.conf.peers or [advertise],
                advertise=advertise,
                on_update=self._on_peers,
            )
            await self._pool.start()

    async def _on_peers(self, peers) -> None:
        await self.instance.set_peers(peers)


def _enum_val(enum_pb, v):
    if isinstance(v, str):
        return enum_pb.Value(v)
    return int(v)


async def run_daemon(conf: ServerConfig) -> None:
    """Start a server and run until SIGINT/SIGTERM (reference
    cmd/gubernator/main.go:127-139). SIGTERM (the orchestrated-shutdown
    signal) drains gracefully — deregister, refuse new edge frames,
    finish in-flight work, flush GLOBAL + batcher queues — bounded by
    GUBER_DRAIN_TIMEOUT_MS; SIGINT stops immediately."""
    import signal

    server = Server(conf)
    await server.start()
    settle_collector()
    clocks = server.thread_clocks
    step_s = clocks.measure_granularity()
    log.info(
        "tracing: stage clock on (%d batch tiles from collect to "
        "resolve, /v1/debug/stages batch_coverage); thread CPU clock %s"
        "%s, read at scrape only (thread_cpu_seconds_total); from Ready "
        "on, loop pauses of %.0f ms or more (half a forwarded batch's "
        "deadline) and programs built are counted",
        len(BATCH_TILES), clocks.source,
        f", observed granularity {step_s * 1e3:.4g} ms"
        if step_s is not None else "",
        conf.behaviors.effective_peer_timeout() * 500,
    )
    log.info("Ready")
    # the stage clock starts at Ready: warm-up ran every rung through
    # the engine's dispatch, and its jit_call spans are compiles
    STAGES.reset()
    probes = server.probes = ProcessProbes(
        STAGES, conf.behaviors.effective_peer_timeout() / 2
    )
    probes.start()
    stop = asyncio.Event()
    graceful: list = []
    drain_task: list = []
    loop = asyncio.get_running_loop()

    def on_term():
        # second SIGTERM = the supervisor is impatient: abandon the
        # drain and hard-stop now
        if graceful:
            graceful.clear()
            for t in drain_task:
                t.cancel()
        graceful.append(True)
        stop.set()

    loop.add_signal_handler(signal.SIGINT, stop.set)
    loop.add_signal_handler(signal.SIGTERM, on_term)
    await stop.wait()
    # shutdown watchdog on a plain THREAD (immune to a wedged event
    # loop): a signalled daemon must exit within a bound, full stop.
    # The drain itself is budgeted, but a teardown await that never
    # returns (e.g. a client-library close wedging under load) would
    # otherwise leave a zombie the supervisor has to SIGKILL minutes
    # later — observed in the full-suite soak as daemons outliving
    # their test's kill timeout.
    import os
    import threading

    def _force_exit():
        log.error(
            "shutdown watchdog fired (teardown wedged); forcing exit"
        )
        logging.shutdown()
        os._exit(1)

    watchdog = threading.Timer(
        2 * getattr(conf, "drain_timeout", 5.0) + 10.0, _force_exit
    )
    watchdog.daemon = True
    watchdog.start()
    if graceful:
        log.info("SIGTERM: draining")
        drain_task.append(asyncio.ensure_future(server.drain()))
        try:
            await drain_task[0]
        except asyncio.CancelledError:
            log.warning("drain aborted (second SIGTERM)")
    log.info("shutting down")
    probes.stop()
    await server.stop()
    watchdog.cancel()
