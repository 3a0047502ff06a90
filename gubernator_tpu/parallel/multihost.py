"""Multi-host mesh: one logical device mesh spanning several processes.

`parallel/sharded.py` shards the key space over the chips of ONE host
(single-controller). This module extends the same engine across hosts the
way JAX scales: `jax.distributed` turns N processes into one SPMD program
over a global `Mesh`, and the psum that combines per-shard decisions rides
ICI within a host and DCN (gloo/TCP on CPU, ICI/DCN collectives on TPU
pods) between hosts — the moral equivalent of the reference wiring more
peers into its gossip mesh (reference peers.go/global.go), except the
"gossip" is a compiler-scheduled collective.

Multi-controller SPMD requires every process to issue the SAME jitted
calls in the same order. Serving is request-driven on the leader
(process 0), so followers run a lockstep loop fed by a step pipe: before
each device call the leader broadcasts (kind, now, arrays) over plain
length-prefixed TCP; every process then issues the identical call. The
pipe is a trusted-cluster side channel exactly like the reference's
insecure peer gRPC (reference peers.go:130-139); a follower failure
surfaces as a broken pipe and the cluster restarts fresh — the documented
state-loss contract (reference architecture.md:5-11).

Scaling model (BASELINE config 5, v5e-32 = 4 hosts x 8 chips): each chip
owns 1/32 of the key space. A multi-process mesh is built 2-D as
("host", "chip") — process-major device order groups each host's chips —
and the GLOBAL-sync reduction is HIERARCHICAL (sharded._hier_psum):
chips combine within a host over ICI first, then one pre-reduced vector
per host crosses DCN, instead of a flat 32-way all-reduce whose ring
spans DCN on every leg. Collective structure is asserted from the
compiled module in tests/test_sharded.py; the multi-process topologies
in tests/test_multihost.py run it end to end.
"""

from __future__ import annotations

import logging
import socket
import struct
from typing import List, Optional, Sequence

import numpy as np

log = logging.getLogger("gubernator_tpu.multihost")

# Wire format GMH2: a typed, gadget-free codec. GMH1 framed pickle, which
# hands arbitrary code execution to anything that can reach a follower's
# listen port — a strictly worse trust posture than the reference's
# insecure-but-parse-safe protobuf peer channel (reference
# peers.go:130-139). Step messages are only flat dicts of scalars, strings,
# int tuples, one nested config dict, and dense numpy arrays, so a
# six-tag TLV encoding covers the whole surface with no deserialization
# gadget: decode constructs nothing but bytes, ints, str, tuple, dict and
# whitelisted-dtype ndarrays.
_MAGIC = b"GMH2"

_T_NONE, _T_INT, _T_STR, _T_ARR, _T_TUPLE, _T_DICT = range(6)

# dtype whitelist — everything the step pipe ever carries. Explicit
# little-endian codes so a mixed-endian cluster fails loudly at the
# codec, not silently in the kernels.
_DTYPES = {
    0: np.dtype("<u8"),  # key_hash
    1: np.dtype("<i8"),  # hits/limit/duration/remaining/reset_time
    2: np.dtype("<i4"),  # algo
    3: np.dtype("|b1"),  # gnp/is_over
}
_DTYPE_CODES = {dt: code for code, dt in _DTYPES.items()}

_MAX_DEPTH = 4  # message dict -> config dict -> tuples; headroom of one
_MAX_ITEMS = 4096  # fields per dict / elements per tuple
_MAX_STR = 1 << 20
_MAX_ARR_BYTES = 1 << 31


def _encode_value(out: bytearray, v, depth: int = 0) -> None:
    if depth > _MAX_DEPTH:
        raise ValueError("step message nests too deep to encode")
    if v is None:
        out.append(_T_NONE)
    elif isinstance(v, (int, np.integer)) and not isinstance(v, bool):
        out.append(_T_INT)
        out += struct.pack("<q", int(v))
    elif isinstance(v, str):
        b = v.encode()
        if len(b) > _MAX_STR:
            raise ValueError("string field too large for step pipe")
        out.append(_T_STR)
        out += struct.pack("<I", len(b))
        out += b
    elif isinstance(v, np.ndarray):
        dt = v.dtype.newbyteorder("<") if v.dtype.byteorder == ">" else v.dtype
        arr = np.ascontiguousarray(v, dtype=dt)
        code = _DTYPE_CODES.get(arr.dtype)
        if code is None:
            raise ValueError(f"dtype {arr.dtype} not in step-pipe whitelist")
        if arr.ndim > 4:
            raise ValueError("array rank > 4 on step pipe")
        out.append(_T_ARR)
        out.append(code)
        out.append(arr.ndim)
        for d in arr.shape:
            out += struct.pack("<I", d)
        out += arr.tobytes()
    elif isinstance(v, tuple):
        if len(v) > _MAX_ITEMS:
            raise ValueError("tuple too long for step pipe")
        out.append(_T_TUPLE)
        out += struct.pack("<I", len(v))
        for e in v:
            _encode_value(out, e, depth + 1)
    elif isinstance(v, dict):
        if len(v) > _MAX_ITEMS:
            raise ValueError("dict too large for step pipe")
        out.append(_T_DICT)
        out += struct.pack("<I", len(v))
        for k, e in v.items():
            kb = str(k).encode()
            out += struct.pack("<H", len(kb))
            out += kb
            _encode_value(out, e, depth + 1)
    else:
        raise ValueError(f"type {type(v).__name__} not encodable on step pipe")


def _utf8(raw) -> str:
    # keep the "hostile frame -> ConnectionError" contract airtight
    try:
        return str(raw, "utf-8")
    except UnicodeDecodeError as e:
        raise ConnectionError(f"invalid utf-8 in step pipe frame: {e}")


class _Reader:
    def __init__(self, buf: bytes):
        self.buf = memoryview(buf)
        self.pos = 0

    def take(self, n: int) -> memoryview:
        if n < 0 or self.pos + n > len(self.buf):
            raise ConnectionError("step pipe frame truncated")
        v = self.buf[self.pos : self.pos + n]
        self.pos += n
        return v

    def u8(self) -> int:
        return self.take(1)[0]

    def unpack(self, fmt: str):
        (v,) = struct.unpack("<" + fmt, self.take(struct.calcsize(fmt)))
        return v


def _decode_value(r: _Reader, depth: int = 0):
    if depth > _MAX_DEPTH:
        raise ConnectionError("step pipe frame nests too deep")
    tag = r.u8()
    if tag == _T_NONE:
        return None
    if tag == _T_INT:
        return r.unpack("q")
    if tag == _T_STR:
        n = r.unpack("I")
        if n > _MAX_STR:
            raise ConnectionError("oversized string in step pipe frame")
        return _utf8(r.take(n))
    if tag == _T_ARR:
        dt = _DTYPES.get(r.u8())
        if dt is None:
            raise ConnectionError("unknown dtype in step pipe frame")
        ndim = r.u8()
        if ndim > 4:
            raise ConnectionError("array rank > 4 in step pipe frame")
        shape = tuple(r.unpack("I") for _ in range(ndim))
        n_elem = 1
        for d in shape:
            n_elem *= d
        if n_elem * dt.itemsize > _MAX_ARR_BYTES:
            raise ConnectionError("oversized array in step pipe frame")
        raw = r.take(n_elem * dt.itemsize)
        return np.frombuffer(raw, dtype=dt).reshape(shape).copy()
    if tag == _T_TUPLE:
        n = r.unpack("I")
        if n > _MAX_ITEMS:
            raise ConnectionError("oversized tuple in step pipe frame")
        return tuple(_decode_value(r, depth + 1) for _ in range(n))
    if tag == _T_DICT:
        n = r.unpack("I")
        if n > _MAX_ITEMS:
            raise ConnectionError("oversized dict in step pipe frame")
        d = {}
        for _ in range(n):
            klen = r.unpack("H")
            k = _utf8(r.take(klen))
            d[k] = _decode_value(r, depth + 1)
        return d
    raise ConnectionError(f"unknown tag {tag} in step pipe frame")


def _encode_msg(obj: dict) -> bytes:
    out = bytearray()
    _encode_value(out, obj)
    return _MAGIC + struct.pack("<Q", len(out)) + bytes(out)


def _send_msg(sock: socket.socket, obj: dict) -> None:
    sock.sendall(_encode_msg(obj))


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n:
        b = sock.recv(min(n, 1 << 20))
        if not b:
            raise ConnectionError("step pipe closed")
        chunks.append(b)
        n -= len(b)
    return b"".join(chunks)


def _recv_msg(sock: socket.socket) -> dict:
    if _recv_exact(sock, 4) != _MAGIC:
        raise ConnectionError("step pipe desync")
    (n,) = struct.unpack("<Q", _recv_exact(sock, 8))
    if n > _MAX_ARR_BYTES:
        raise ConnectionError("oversized step pipe frame")
    r = _Reader(_recv_exact(sock, n))
    msg = _decode_value(r)
    if not isinstance(msg, dict):
        raise ConnectionError("step pipe frame is not a message dict")
    if r.pos != len(r.buf):
        raise ConnectionError("trailing bytes in step pipe frame")
    return msg


class StepPipe:
    """Leader side: broadcast each device step to every follower and wait
    for acks (the ack keeps processes in lockstep so no follower falls
    more than one collective behind)."""

    def __init__(self, follower_addrs: Sequence[str], timeout_s: float = 30.0):
        import time

        self.socks: List[socket.socket] = []
        for addr in follower_addrs:
            host, _, port = addr.rpartition(":")
            deadline = time.monotonic() + timeout_s
            while True:  # follower binds its listener after the jax
                # rendezvous; retry until it is up
                try:
                    s = socket.create_connection((host, int(port)), timeout=5)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)
            s.settimeout(None)  # connect timeout must not cap step acks
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self.socks.append(s)

    def broadcast(self, msg: dict) -> None:
        wire = _encode_msg(msg)  # serialize once for every follower
        for s in self.socks:
            s.sendall(wire)

    def await_acks(self) -> None:
        for s in self.socks:
            m = _recv_msg(s)
            if m.get("kind") == "nack":
                raise RuntimeError(f"follower rejected step: {m.get('error')}")
            if m.get("kind") != "ack":
                raise RuntimeError(f"unexpected follower reply: {m}")

    def close(self) -> None:
        for s in self.socks:
            try:
                _send_msg(s, {"kind": "shutdown"})
                s.close()
            except OSError:
                pass


def initialize_distributed(
    coordinator: str, num_processes: int, process_id: int
) -> None:
    """jax.distributed.initialize, with the CPU backend's cross-process
    collectives selected first: they must be chosen BEFORE the client
    initializes, or the CPU client refuses multi-process computations
    outright ("Multiprocess computations aren't implemented on the CPU
    backend"). gloo-over-TCP is the CPU stand-in for DCN in the tests;
    a multi-process TPU backend ignores the setting. All three
    arguments are always passed, so JAX looks nothing up (no metadata
    server is asked)."""
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


class MultiHostMeshEngine:
    """The ONE partitioned engine (parallel/sharded.PartitionedEngine,
    r14) over the GLOBAL device mesh, plus the leader-side lockstep
    step pipe — this wrapper owns only the multi-controller choreography
    (broadcast each device call so every process issues the identical
    program); every decide/upsert/sync code path is the shared engine's.
    Construct identically in every process; only the leader calls the
    public decide/update/sync methods (followers run follower_loop).
    """

    def __init__(
        self,
        store_config,
        followers: Optional[Sequence[str]] = None,
        buckets: Sequence[int] = (64, 256, 1024, 4096),
        sketch=None,
    ):
        import jax

        from gubernator_tpu.parallel.sharded import MeshEngine

        self.is_leader = jax.process_index() == 0
        self.inner = MeshEngine(
            store_config, devices=jax.devices(), buckets=buckets,
            sketch=sketch,
        )
        self.pipe = (
            StepPipe(followers) if (self.is_leader and followers) else None
        )
        if self.pipe:
            # Config handshake: every process derives batch-padding shapes
            # independently from its own ladder, and the lockstep shard_map
            # requires those shapes to be IDENTICAL across processes. A
            # mismatch used to surface only as a distributed shape
            # divergence mid-serving (or, before the skew-overflow
            # fallback, an incidental choose_bucket error during warmup
            # replay); verify it explicitly at connect time instead.
            self.pipe.broadcast({"kind": "hello", "config": self._config()})
            self.pipe.await_acks()

    def _config(self) -> dict:
        skc = self.inner.sketch_config
        return {
            "buckets": tuple(self.inner.buckets),
            "sub_buckets": tuple(self.inner.sub_buckets),
            "store": (self.inner.config.rows, self.inner.config.slots),
            "n_shards": self.inner.n,
            # sketch geometry (r20; counter width since r21): a leader
            # with the cold tier on and a follower without it (or with
            # a different width or counter dtype) would diverge at the
            # first two-tier dispatch — verify at hello
            "sketch": (
                (skc.rows, skc.width, skc.counter_bytes)
                if skc is not None
                else None
            ),
        }

    @property
    def buckets(self):
        return self.inner.buckets

    @property
    def sub_buckets(self):
        return self.inner.sub_buckets

    @property
    def n(self):
        return self.inner.n

    @property
    def flat(self):
        return self.inner.flat

    #: a lockstep fleet's merged batch crosses as the FLAT sorted form
    #: (merge_prepped below), so every process lays it out in numpy
    stack_implementation = "numpy"

    @property
    def native_stacks(self):
        return self.inner.native_stacks

    @property
    def numpy_stacks(self):
        return self.inner.numpy_stacks

    @property
    def stats(self):
        return self.inner.stats

    @property
    def reset_generation(self):
        # store-wipe epoch for the over-limit shed cache; follower
        # stores reset in lockstep with the leader's, so the leader's
        # counter is authoritative for the whole mesh
        return self.inner.reset_generation

    # -- sketch cold tier surfaces (r20) ------------------------------------
    # The backend tier probes `sketch` (tier present?) and sets
    # `observe_hook` (the promoter's hot-key observer — leader-local by
    # construction: only the leader dispatches request batches, so only
    # its hook ever fires). `sketch_on` is the runtime A/B flag; both
    # sides of a lockstep dispatch must pick the same two-tier-or-not
    # program, so the leader's flag rides every decide message ("sk")
    # and followers adopt it before dispatching — flipping it here can
    # never diverge the fleet.

    @property
    def sketch(self):
        return self.inner.sketch

    @property
    def sketch_config(self):
        return self.inner.sketch_config

    @property
    def sketch_on(self):
        return self.inner.sketch_on

    @sketch_on.setter
    def sketch_on(self, value):
        self.inner.sketch_on = value

    @property
    def observe_hook(self):
        return self.inner.observe_hook

    @observe_hook.setter
    def observe_hook(self, fn):
        self.inner.observe_hook = fn

    # -- leader API ---------------------------------------------------------

    def _lockstep(self, msg: dict) -> None:
        if self.pipe:
            self.pipe.broadcast(msg)

    def _done(self) -> None:
        if self.pipe:
            self.pipe.await_acks()

    def decide_arrays(self, key_hash, hits, limit, duration, algo, gnp, now):
        assert self.is_leader
        return self.decide_wait(
            self.decide_submit(
                key_hash, hits, limit, duration, algo, gnp, now
            )
        )

    def decide_submit(self, key_hash, hits, limit, duration, algo, gnp,
                      now):
        """Pipelined split for the multihost leader: followers only need
        to ISSUE the identical jitted call (their psum legs run inside
        the device program) — they never fetch results, so the leader
        may submit batch N+1 while batch N's fetch is in flight, exactly
        like the single-host engines. The ack still bounds skew at one
        collective."""
        assert self.is_leader
        self._lockstep(
            {
                "kind": "decide",
                "key_hash": key_hash,
                "hits": hits,
                "limit": limit,
                "duration": duration,
                "algo": algo,
                "gnp": gnp,
                "now": now,
                "sk": int(self.inner.sketch_on),
            }
        )
        try:
            return self.inner.decide_submit(
                key_hash, hits, limit, duration, algo, gnp, now
            )
        finally:
            self._done()

    def decide_wait(self, handle):
        """Leader-local: fetching the packed outputs involves no
        collective, so no lockstep message is needed (followers already
        moved on at submit time)."""
        assert self.is_leader
        return self.inner.decide_wait(handle)

    def prep_run(self, fields: dict) -> dict:
        """Leader-local arrival-time prep (serve/batcher.py): pure host
        work, no collective — followers receive the already-sorted run
        via decide_submit_presorted's lockstep message and never
        re-sort, so the prep cost is paid once per cluster."""
        assert self.is_leader
        return self.inner.prep_run(fields)

    def merge_prepped(self, runs):
        """Leader-side merge of pre-sorted runs. Returns the FLAT
        merged form (serve/prep.py) — deliberately not the padded
        per-shard layout, because it doubles as the lockstep wire
        format decide_submit_merged broadcasts; each process derives
        its identical [n_shards, B_sub] layout locally."""
        assert self.is_leader
        from gubernator_tpu.serve.prep import merge_runs

        return merge_runs(runs)

    def decide_submit_merged(self, merged, now):
        """Dispatch a merge_prepped batch across the lockstep fleet."""
        return self.decide_submit_presorted(
            merged["fields"], merged["skey"], merged["order"],
            merged["counts"], now,
        )

    def decide_submit_presorted(self, fields, skey, order, counts, now):
        """Merge-combine sibling of decide_submit: broadcasts the
        SORTED batch (fields + sort keys + per-shard counts), so
        followers skip the presort entirely and only issue the
        identical jitted call. `order` stays leader-local — it exists
        only to unpermute responses, which followers never fetch."""
        assert self.is_leader
        msg = {"kind": "decide_p", "skey": skey, "counts": counts,
               "now": now, "sk": int(self.inner.sketch_on)}
        msg.update(fields)
        self._lockstep(msg)
        try:
            return self.inner.decide_submit_presorted(
                fields, skey, order, counts, now
            )
        finally:
            self._done()

    def update_globals(self, key_hash, limit, remaining, reset_time, is_over,
                       now=None):
        assert self.is_leader
        from gubernator_tpu.api.types import millisecond_now

        now = millisecond_now() if now is None else now
        self._lockstep(
            {
                "kind": "upsert",
                "key_hash": key_hash,
                "limit": limit,
                "remaining": remaining,
                "reset_time": reset_time,
                "is_over": is_over,
                "now": now,
            }
        )
        try:
            return self.inner.update_globals(
                key_hash, limit, remaining, reset_time, is_over, now=now
            )
        finally:
            self._done()

    def compile_ahead(self) -> None:
        """The warm-up's side-by-side compile (sharded.warmup_public
        calls this first), in every process at once: compiling is local
        to a process, and each must hold the programs before the
        warm-up traffic that follows is replayed to it."""
        assert self.is_leader
        self._lockstep({"kind": "compile_ahead"})
        try:
            self.inner.compile_ahead()
        finally:
            self._done()

    def reset(self) -> None:
        assert self.is_leader
        self._lockstep({"kind": "reset"})
        try:
            self.inner.reset()
        finally:
            self._done()

    def sync_globals(self, key_hash, limit, duration, now, algo=None):
        assert self.is_leader
        self._lockstep(
            {
                "kind": "sync",
                "key_hash": key_hash,
                "limit": limit,
                "duration": duration,
                "algo": algo,
                "now": now,
            }
        )
        try:
            return self.inner.sync_globals(
                key_hash, limit, duration, now, algo=algo
            )
        finally:
            self._done()

    def apply_global_hits(self, key_hash, hits, limit, duration, now,
                          algo=None):
        """Mesh-native GLOBAL flush (r20): aggregate gossip hits charge
        their owner shards + replicate post-charge windows in ONE
        collective step across the whole multi-process mesh. The step's
        response legs are psum outputs (replicated), so the leader
        fetches them host-side while followers dispatch-and-discard."""
        assert self.is_leader
        n = key_hash.shape[0]
        if n == 0:
            z = np.empty(0, np.int64)
            return z, z, z, z
        self._lockstep(
            {
                "kind": "ghits",
                "key_hash": key_hash,
                "hits": hits,
                "limit": limit,
                "duration": duration,
                "algo": algo,
                "now": now,
            }
        )
        try:
            return self.inner.apply_global_hits(
                key_hash, hits, limit, duration, now, algo=algo
            )
        finally:
            self._done()

    def promote_from_sketch(self, key_hash, limits, durations, now=None):
        """decide_p-style lockstep promotion (r20): the serving-tier
        promoter stays a host loop on the leader, but its device
        surfaces (collective estimate/live-row reads + the conditional
        window install) broadcast so every process issues the identical
        programs. The branch on `todo.any()` cannot diverge: both reads
        return psum-replicated arrays, so all processes see the same
        values."""
        assert self.is_leader
        from gubernator_tpu.api.types import millisecond_now

        now = millisecond_now() if now is None else now
        kh = np.ascontiguousarray(key_hash, np.uint64)
        limits = np.asarray(limits, np.int64)
        durations = np.asarray(durations, np.int64)
        if kh.shape[0] == 0 or self.inner.sketch is None:
            return self.inner.promote_from_sketch(kh, limits, durations, now)
        self._lockstep(
            {
                "kind": "promote",
                "key_hash": kh,
                "limits": limits,
                "durations": durations,
                "now": now,
            }
        )
        try:
            return self.inner.promote_from_sketch(kh, limits, durations, now)
        finally:
            self._done()

    def _warmup_sketch_reads(self, now) -> None:
        """Lockstep-safe twin of the engine's promoter-surface warmup
        (warmup_public calls this by name): each pow2 rung rides a
        `promote` broadcast so followers compile the identical
        collective read + install programs. The installs dirty the
        store, but warmup_public ends with a (broadcast) reset()."""
        if self.inner.sketch is None:
            return
        for B in (64, 128, 256, 512, 1024):
            kh = np.arange(1, B + 1, dtype=np.uint64) << np.uint64(32)
            self.promote_from_sketch(
                kh, np.full(B, 10, np.int64), np.full(B, 1000, np.int64),
                now,
            )

    def close(self) -> None:
        if self.pipe:
            self.pipe.close()

    # -- follower API -------------------------------------------------------

    def follower_loop(self, listen_addr: str, ready_cb=None) -> None:
        """Serve lockstep steps until the leader shuts the pipe. Each
        message triggers the identical jitted call the leader makes, so
        the global-mesh collectives line up."""
        assert not self.is_leader
        host, _, port = listen_addr.rpartition(":")
        srv = socket.create_server((host, int(port)))
        if ready_cb:
            ready_cb()
        conn, peer = srv.accept()
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        log.info("step pipe connected from %s", peer)
        while True:
            msg = _recv_msg(conn)
            kind = msg.pop("kind")
            if kind == "shutdown":
                break
            if kind == "hello":
                want, have = msg["config"], self._config()
                if want != have:
                    err = (
                        "leader/follower config mismatch (batch shapes "
                        f"would diverge in lockstep): leader={want} "
                        f"follower={have}"
                    )
                    # nack first so the leader's await_acks surfaces the
                    # diagnostic instead of an opaque closed-pipe error
                    _send_msg(conn, {"kind": "nack", "error": err})
                    raise RuntimeError(err)
            elif kind == "decide":
                # submit only: the follower's psum legs execute inside
                # the dispatched device program; fetching the packed
                # outputs here would buy nothing and cost a device->host
                # transfer per step (plus it would serialize the
                # leader's fetch pipeline through follower acks).
                # "sk" carries the leader's sketch_on so the two-tier
                # program choice can never diverge across processes.
                sk = msg.pop("sk", None)
                if sk is not None:
                    self.inner.sketch_on = bool(sk)
                self.inner.decide_submit(**msg)
            elif kind == "decide_p":
                # merge-combined batch: already sorted + clipped on the
                # leader; order=None (identity) — the handle is
                # discarded, responses are leader-only
                sk = msg.get("sk")
                if sk is not None:
                    self.inner.sketch_on = bool(sk)
                self.inner.decide_submit_presorted(
                    {
                        k: msg[k]
                        for k in ("key_hash", "hits", "limit",
                                  "duration", "algo", "gnp")
                    },
                    msg["skey"],
                    None,
                    msg["counts"],
                    msg["now"],
                )
            elif kind == "compile_ahead":
                self.inner.compile_ahead()
            elif kind == "reset":
                self.inner.reset()
            elif kind == "upsert":
                self.inner.update_globals(
                    msg["key_hash"],
                    msg["limit"],
                    msg["remaining"],
                    msg["reset_time"],
                    msg["is_over"],
                    now=msg["now"],
                )
            elif kind == "sync":
                self.inner.sync_globals(
                    msg["key_hash"],
                    msg["limit"],
                    msg["duration"],
                    msg["now"],
                    algo=msg["algo"],
                )
            elif kind == "ghits":
                # mesh-native GLOBAL flush: dispatch the identical sync
                # collective and discard — post-charge responses are
                # leader-only (replicated psum outputs), so fetching
                # them here would only serialize the leader behind a
                # follower device->host transfer
                self.inner._sync_padded(
                    msg["key_hash"],
                    msg["hits"],
                    msg["limit"],
                    msg["duration"],
                    msg["algo"],
                    msg["now"],
                )
            elif kind == "promote":
                # sketch-tier promotion: the collective reads return
                # replicated arrays, so this process's todo/install
                # control flow is byte-identical to the leader's
                self.inner.promote_from_sketch(
                    msg["key_hash"],
                    msg["limits"],
                    msg["durations"],
                    msg["now"],
                )
            else:
                raise RuntimeError(f"unknown step kind {kind!r}")
            _send_msg(conn, {"kind": "ack"})
        conn.close()
        srv.close()
