"""Edge-bridge frame protocol unit tests (no native binary needed).

The C++ edge passes client bytes through its minimal JSON parser
verbatim, so the Python bridge is the first place invalid UTF-8 can
surface; one client's garbage must fail only its own item, never the
co-batched requests of other connections (ADVICE r1 medium).

r5: the hello carries the cluster ring ('GEBI') and pre-hashed frames
('GEB6') echo the membership fingerprint they were routed with; a
frame routed under a different view is refused with 'GEBR' — the
over-admission guard that replaced r4's single-node gate.
"""

import asyncio
import struct
from dataclasses import dataclass

import pytest

from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    RateLimitResp,
    Status,
)
from gubernator_tpu.serve.edge_bridge import (
    HELLO_FAST,
    HELLO_WINDOWED,
    MAGIC_REQ,
    MAGIC_RESP,
    EdgeBridge,
    decode_request_frame,
    encode_response_frame,
    ring_fingerprint,
)


def _item(name: bytes, key: bytes, hits=1, limit=5, duration=1000,
          algo=0, behavior=0) -> bytes:
    return (
        struct.pack("<H", len(name)) + name
        + struct.pack("<H", len(key)) + key
        + struct.pack("<qqqBB", hits, limit, duration, algo, behavior)
    )


def _frame(items) -> bytes:
    payload = b"".join(items)
    return struct.pack("<II", MAGIC_REQ, len(items)) + struct.pack(
        "<I", len(payload)
    ) + payload


async def _read_hello(reader):
    """Parse the GEBI hello; returns (flags, ring_hash, nodes) where
    nodes is a list of (is_self, grpc, bridge)."""
    from gubernator_tpu.serve.edge_bridge import MAGIC_HELLO

    magic, flags, rhash, n = struct.unpack(
        "<IIII", await reader.readexactly(16)
    )
    assert magic == MAGIC_HELLO
    nodes = []
    for _ in range(n):
        is_self, glen = struct.unpack("<BH", await reader.readexactly(3))
        grpc = (await reader.readexactly(glen)).decode()
        (blen,) = struct.unpack("<H", await reader.readexactly(2))
        bridge = (await reader.readexactly(blen)).decode()
        nodes.append((bool(is_self), grpc, bridge))
    return flags, rhash, nodes


@dataclass
class FakePeer:
    host: str
    is_owner: bool = False


BAD = b"\xff\xfe\x80"  # not valid UTF-8


def test_decode_isolates_invalid_utf8_items():
    items = [
        _item(b"api", b"good-1"),
        _item(b"api", BAD),
        _item(BAD, b"good-key"),
        _item(b"api", b"good-2"),
    ]
    payload = b"".join(items)
    decoded = decode_request_frame(payload, 4)
    assert decoded[0] is not None and decoded[0].unique_key == "good-1"
    assert decoded[1] is None
    assert decoded[2] is None
    assert decoded[3] is not None and decoded[3].unique_key == "good-2"


def test_bridge_answers_bad_item_without_failing_frame():
    """A frame mixing a bad-UTF-8 item with good ones must answer ALL
    items: per-item error for the bad one, real decisions for the rest."""

    class FakeInstance:
        async def get_rate_limits(self, reqs, stage_frame=False):
            return [
                RateLimitResp(
                    status=Status.UNDER_LIMIT, limit=r.limit,
                    remaining=r.limit - r.hits, reset_time=123,
                )
                for r in reqs
            ]

    async def run():
        path = "/tmp/guber-bridge-utf8-test.sock"
        bridge = EdgeBridge(FakeInstance(), path)
        await bridge.start()
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            await _read_hello(reader)
            writer.write(_frame([
                _item(b"api", b"ok-1"),
                _item(b"api", BAD),
                _item(b"api", b"ok-2"),
            ]))
            await writer.drain()
            magic, n = struct.unpack("<II", await reader.readexactly(8))
            assert magic == MAGIC_RESP and n == 3
            out = []
            for _ in range(n):
                st, limit, rem, reset = struct.unpack(
                    "<Bqqq", await reader.readexactly(25)
                )
                (elen,) = struct.unpack("<H", await reader.readexactly(2))
                err = (await reader.readexactly(elen)).decode()
                (olen,) = struct.unpack("<H", await reader.readexactly(2))
                await reader.readexactly(olen)  # owner (unused here)
                out.append((st, limit, rem, reset, err))
            writer.close()
            return out
        finally:
            await bridge.stop()

    out = asyncio.run(run())
    assert out[0] == (0, 5, 4, 123, "")
    assert out[2] == (0, 5, 4, 123, "")
    assert "UTF-8" in out[1][4] and out[1][1] == 0


def test_response_roundtrip():
    resps = [
        RateLimitResp(status=Status.OVER_LIMIT, limit=9, remaining=0,
                      reset_time=42, error="boom",
                      metadata={"owner": "10.0.0.3:81"}),
    ]
    raw = encode_response_frame(resps)
    magic, n = struct.unpack_from("<II", raw)
    assert magic == MAGIC_RESP and n == 1
    st, limit, rem, reset = struct.unpack_from("<Bqqq", raw, 8)
    assert (st, limit, rem, reset) == (1, 9, 0, 42)
    off = 8 + 25
    (elen,) = struct.unpack_from("<H", raw, off)
    assert raw[off + 2 : off + 2 + elen] == b"boom"
    off += 2 + elen
    (olen,) = struct.unpack_from("<H", raw, off)
    assert raw[off + 2 : off + 2 + olen] == b"10.0.0.3:81"


class _FakeBackendArrays:
    decide_submit_merged = object()  # a device backend


class _FakeTraffic:
    def observe_hashes(self, h):
        pass

    def observe(self, keys, hashes, packed=None):
        pass


def _fast_frame(rec, ring_hash):
    from gubernator_tpu.serve.edge_bridge import MAGIC_FAST_REQ

    payload = rec.tobytes()
    return (
        struct.pack("<II", MAGIC_FAST_REQ, len(rec))
        + struct.pack("<II", ring_hash, len(payload))
        + payload
    )


def test_fast_frame_chunks_oversized_batches():
    """A GEB6 frame beyond MAX_BATCH_SIZE must reach the batcher as
    ladder-sized chunks (the engine's compiled rungs top out there), and
    the concatenated responses must preserve request order."""
    import numpy as np

    from gubernator_tpu.serve.config import MAX_BATCH_SIZE
    from gubernator_tpu.serve.edge_bridge import (
        MAGIC_FAST_RESP,
        _fast_dtypes,
    )

    seen_sizes = []

    class FakeBatcher:
        async def decide_arrays(self, fields, frame=True):
            n = fields["key_hash"].shape[0]
            seen_sizes.append(n)
            # echo limit back as remaining so order is checkable
            return (
                np.zeros(n, np.int64),
                fields["limit"],
                fields["limit"],
                np.zeros(n, np.int64),
            )

    class FakePicker:
        # live membership, the surface the hello actually consults
        def peers(self):
            return [FakePeer("127.0.0.1:81", is_owner=True)]

    class FakeInstance:
        backend = _FakeBackendArrays()
        picker = FakePicker()
        batcher = FakeBatcher()
        traffic = _FakeTraffic()

    async def run():
        path = "/tmp/guber-bridge-fast-chunk.sock"
        bridge = EdgeBridge(FakeInstance(), path)
        await bridge.start()
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            flags, rhash, nodes = await _read_hello(reader)
            assert flags & HELLO_FAST
            assert flags & HELLO_WINDOWED  # r7: windowed frames accepted
            assert (flags >> 16) >= 1  # advertised credit window
            assert rhash == ring_fingerprint(["127.0.0.1:81"])
            assert nodes == [(True, "127.0.0.1:81", "")]
            n = MAX_BATCH_SIZE + 500
            req_dt, resp_dt = _fast_dtypes()
            rec = np.empty(n, req_dt)
            rec["key_hash"] = np.arange(1, n + 1, dtype=np.uint64)
            rec["hits"] = 1
            rec["limit"] = np.arange(n, dtype=np.int64)
            rec["duration"] = 1000
            rec["algo"] = 0
            writer.write(_fast_frame(rec, rhash))
            await writer.drain()
            magic, rn = struct.unpack("<II", await reader.readexactly(8))
            assert magic == MAGIC_FAST_RESP and rn == n
            out = np.frombuffer(
                await reader.readexactly(n * resp_dt.itemsize), resp_dt
            )
            writer.close()
            return out
        finally:
            await bridge.stop()

    out = asyncio.run(run())
    assert seen_sizes == [MAX_BATCH_SIZE, 500]
    assert (out["remaining"] == np.arange(MAX_BATCH_SIZE + 500)).all()


def test_multinode_hello_carries_ring_and_bridge_endpoints():
    """With >1 peers and a TCP listener configured, the hello must
    advertise the fast path plus every node's bridge endpoint (peer
    gRPC host + this node's TCP port — the symmetric-fleet convention),
    with an empty endpoint for self (the edge uses its --backend)."""

    class FakePicker:
        def peers(self):
            return [
                FakePeer("10.0.0.2:81"),
                FakePeer("10.0.0.1:81", is_owner=True),
            ]

    class FakeInstance:
        backend = _FakeBackendArrays()
        picker = FakePicker()

    async def run():
        path = "/tmp/guber-bridge-ring-hello.sock"
        bridge = EdgeBridge(FakeInstance(), path)
        await bridge.start()
        # set after start: only the hello's endpoint derivation reads
        # it here; the real TCP listener is covered by the cluster e2e
        # (tests/test_edge_cluster.py)
        bridge.tcp_address = "0.0.0.0:9470"
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            flags, rhash, nodes = await _read_hello(reader)
            writer.close()
            return flags, rhash, nodes
        finally:
            await bridge.stop()

    flags, rhash, nodes = asyncio.run(run())
    assert flags & HELLO_FAST  # fast path stays on in a cluster (r5)
    assert rhash == ring_fingerprint(["10.0.0.1:81", "10.0.0.2:81"])
    # sorted by gRPC address; self has no bridge endpoint, the peer's is
    # derived from its host + our TCP port
    assert nodes == [
        (True, "10.0.0.1:81", ""),
        (False, "10.0.0.2:81", "10.0.0.2:9470"),
    ]


def test_stale_ring_fast_frame_refused_with_gebr():
    """A GEB6 frame whose ring fingerprint does not match the live
    membership must be answered with GEBR and the connection closed —
    deciding it locally could admit keys this node no longer owns
    (the r5 replacement for r4's fast-path-off-in-clusters gate)."""
    import numpy as np

    from gubernator_tpu.serve.edge_bridge import MAGIC_STALE, _fast_dtypes

    class FakePicker:
        def peers(self):
            return [
                FakePeer("10.0.0.1:81", is_owner=True),
                FakePeer("10.0.0.2:81"),
            ]

    class FakeInstance:
        backend = _FakeBackendArrays()
        picker = FakePicker()
        traffic = _FakeTraffic()

    async def run():
        path = "/tmp/guber-bridge-stale-ring.sock"
        bridge = EdgeBridge(FakeInstance(), path)
        await bridge.start()
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            flags, rhash, _nodes = await _read_hello(reader)
            assert flags & HELLO_FAST
            req_dt, _ = _fast_dtypes()
            rec = np.zeros(2, req_dt)
            rec["key_hash"] = [1, 2]
            stale = (rhash + 1) & 0xFFFFFFFF
            writer.write(_fast_frame(rec, stale))
            await writer.drain()
            magic, n = struct.unpack("<II", await reader.readexactly(8))
            assert magic == MAGIC_STALE and n == 0
            got = await reader.read(8)
            assert got == b"", got  # bridge closed after GEBR
            writer.close()
        finally:
            await bridge.stop()

    asyncio.run(run())


def _witem_frame(frame_id: int, items, t_sent_us: int = 0) -> bytes:
    """Windowed string request (GEB2): frame_id + monotonic stamp."""
    from gubernator_tpu.serve.edge_bridge import MAGIC_WREQ

    payload = b"".join(items)
    return (
        struct.pack("<II", MAGIC_WREQ, len(items))
        + struct.pack("<IQ", frame_id, t_sent_us)
        + struct.pack("<I", len(payload))
        + payload
    )


async def _read_wresp(reader):
    """One GEB4 windowed response: (frame_id, [(status, limit, rem,
    reset, error, owner)])."""
    from gubernator_tpu.serve.edge_bridge import MAGIC_WRESP

    magic, n = struct.unpack("<II", await reader.readexactly(8))
    assert magic == MAGIC_WRESP, hex(magic)
    (fid,) = struct.unpack("<I", await reader.readexactly(4))
    out = []
    for _ in range(n):
        st, limit, rem, reset = struct.unpack(
            "<Bqqq", await reader.readexactly(25)
        )
        (elen,) = struct.unpack("<H", await reader.readexactly(2))
        err = (await reader.readexactly(elen)).decode()
        (olen,) = struct.unpack("<H", await reader.readexactly(2))
        owner = (await reader.readexactly(olen)).decode()
        out.append((st, limit, rem, reset, err, owner))
    return fid, out


def test_windowed_frames_complete_out_of_order():
    """Two GEB2 frames in flight on one connection: the first is served
    slowly, the second fast — the responses must come back second-first,
    matched by frame id. Out-of-order completion IS the pipelining win:
    a slow frame no longer convoys the frames behind it."""
    import time as _time

    release_slow = asyncio.Event()

    class FakeInstance:
        async def get_rate_limits(self, reqs, stage_frame=False):
            if reqs[0].unique_key == "slow":
                await release_slow.wait()
            return [
                RateLimitResp(
                    status=Status.UNDER_LIMIT, limit=r.limit,
                    remaining=r.limit - r.hits, reset_time=7,
                )
                for r in reqs
            ]

    async def run():
        path = "/tmp/guber-bridge-windowed-ooo.sock"
        bridge = EdgeBridge(FakeInstance(), path)
        await bridge.start()
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            await _read_hello(reader)
            t_us = int(_time.monotonic() * 1e6)
            writer.write(_witem_frame(11, [_item(b"api", b"slow")], t_us))
            writer.write(_witem_frame(12, [_item(b"api", b"fast")], t_us))
            await writer.drain()
            first = await asyncio.wait_for(_read_wresp(reader), 5)
            release_slow.set()
            second = await asyncio.wait_for(_read_wresp(reader), 5)
            writer.close()
            return first, second
        finally:
            await bridge.stop()

    (fid1, resp1), (fid2, resp2) = asyncio.run(run())
    assert fid1 == 12  # the fast frame finished first
    assert fid2 == 11
    assert resp1[0][:4] == (0, 5, 4, 7)
    assert resp2[0][:4] == (0, 5, 4, 7)


def test_windowed_credit_exhaustion_backpressures_reads():
    """With window=2 and the instance gated shut, only the first two
    frames may reach the instance — the bridge must stop READING the
    connection (credit acquired before the next frame read) so TCP
    backpressure, not a drop or an error, polices an edge overrunning
    its credit. Opening the gate completes all four frames."""
    gate = asyncio.Event()
    calls = []

    class FakeInstance:
        async def get_rate_limits(self, reqs, stage_frame=False):
            calls.append(reqs[0].unique_key)
            await gate.wait()
            return [
                RateLimitResp(
                    status=Status.UNDER_LIMIT, limit=r.limit,
                    remaining=r.limit - r.hits, reset_time=1,
                )
                for r in reqs
            ]

    async def run():
        path = "/tmp/guber-bridge-windowed-credit.sock"
        bridge = EdgeBridge(FakeInstance(), path, window=2)
        await bridge.start()
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            flags, _rhash, _nodes = await _read_hello(reader)
            assert flags >> 16 == 2  # the advertised window
            for fid in range(1, 5):
                writer.write(
                    _witem_frame(fid, [_item(b"api", b"k%d" % fid)])
                )
            await writer.drain()
            await asyncio.sleep(0.3)
            # credit window exhausted after two in-flight frames: the
            # bridge must not have started serving frames 3 and 4
            blocked_calls = list(calls)
            gate.set()
            fids = set()
            for _ in range(4):
                fid, resps = await asyncio.wait_for(_read_wresp(reader), 5)
                fids.add(fid)
                assert resps[0][0] == 0
            writer.close()
            return blocked_calls, fids
        finally:
            await bridge.stop()

    blocked_calls, fids = asyncio.run(run())
    assert len(blocked_calls) == 2, blocked_calls
    assert fids == {1, 2, 3, 4}


def test_windowed_stale_ring_refused_mid_window():
    """A GEB7 fast frame routed with a stale membership fingerprint must
    be refused with GEBR carrying ITS frame id — even while other
    frames are still in flight on the window — and the connection
    closed (every outstanding frame was routed with the same stale
    view; the edge fails them stale and re-reads the ring)."""
    import numpy as np

    from gubernator_tpu.serve.edge_bridge import (
        MAGIC_STALE,
        MAGIC_WFAST_REQ,
        _fast_dtypes,
    )

    gate = asyncio.Event()

    class FakeBatcher:
        async def decide_arrays(self, fields, frame=True):
            await gate.wait()  # frame 1 parks here, mid-window
            n = fields["key_hash"].shape[0]
            return (
                np.zeros(n, np.int64),
                fields["limit"],
                fields["limit"],
                np.zeros(n, np.int64),
            )

    class FakePicker:
        def peers(self):
            return [FakePeer("127.0.0.1:81", is_owner=True)]

    class FakeInstance:
        backend = _FakeBackendArrays()
        picker = FakePicker()
        batcher = FakeBatcher()
        traffic = _FakeTraffic()

    def wfast(fid, rec, ring_hash):
        payload = rec.tobytes()
        return (
            struct.pack("<II", MAGIC_WFAST_REQ, len(rec))
            + struct.pack("<IIQ", fid, ring_hash, 0)
            + struct.pack("<I", len(payload))
            + payload
        )

    async def run():
        path = "/tmp/guber-bridge-windowed-stale.sock"
        bridge = EdgeBridge(FakeInstance(), path)
        await bridge.start()
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            _flags, rhash, _nodes = await _read_hello(reader)
            req_dt, _ = _fast_dtypes()
            rec = np.zeros(1, req_dt)
            rec["key_hash"] = [1]
            rec["limit"] = [5]
            writer.write(wfast(21, rec, rhash))  # parks in the batcher
            writer.write(wfast(22, rec, (rhash + 1) & 0xFFFFFFFF))
            await writer.drain()
            magic, fid = struct.unpack(
                "<II", await asyncio.wait_for(reader.readexactly(8), 5)
            )
            assert magic == MAGIC_STALE and fid == 22
            got = await asyncio.wait_for(reader.read(8), 5)
            assert got == b"", got  # connection closed after GEBR
            writer.close()
        finally:
            await bridge.stop()

    asyncio.run(run())


def test_fast_kill_switch_unadvertises():
    """GUBER_EDGE_FAST=0 (EdgeBridge fast_enabled=False) must stop
    advertising the pre-hashed path in the hello — the operational
    fallback that forces every edge item through the full instance."""

    class FakePicker:
        def peers(self):
            return [FakePeer("127.0.0.1:81", is_owner=True)]

    class FakeInstance:
        backend = _FakeBackendArrays()
        picker = FakePicker()

    async def run():
        path = "/tmp/guber-bridge-killswitch.sock"
        bridge = EdgeBridge(FakeInstance(), path, fast_enabled=False)
        await bridge.start()
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            flags, _rhash, _nodes = await _read_hello(reader)
            writer.close()
            return flags
        finally:
            await bridge.stop()

    flags = asyncio.run(run())
    assert not (flags & HELLO_FAST)
    assert flags & HELLO_WINDOWED  # windowed framing is fast-agnostic


def _fold_fixture(is_owner: bool, fast_enabled: bool = True, shed=None,
                  peers: int = 1):
    """Bridge over a real ConsistentHashPicker (one peer) and a real
    GlobalManager (never started: what the door queues stays in
    `_updates`) whose batcher and instance record which path served
    each frame. `peers` > 1 puts that many other nodes on the ring."""
    import numpy as np

    from gubernator_tpu.serve.config import BehaviorConfig
    from gubernator_tpu.serve.global_mgr import GlobalManager
    from gubernator_tpu.serve.peers import ConsistentHashPicker, SplitCounts

    folded_sizes = []
    object_path_keys = []

    class FakeBatcher:
        async def decide_arrays(self, fields, frame=True):
            n = fields["key_hash"].shape[0]
            folded_sizes.append(n)
            return (
                np.zeros(n, np.int64),
                fields["limit"],
                fields["limit"] - fields["hits"],
                np.full(n, 77, np.int64),
            )

    class FakeInstance:
        backend = _FakeBackendArrays()
        traffic = _FakeTraffic()
        batcher = FakeBatcher()
        picker = ConsistentHashPicker()
        edge_split = SplitCounts()

        def split_unavailable(self):
            return "no_arrays"  # no forwarder behind this fake

        async def get_rate_limits(self, reqs, stage_frame=False):
            object_path_keys.extend(r.unique_key for r in reqs)
            return [
                RateLimitResp(
                    status=Status.UNDER_LIMIT, limit=r.limit,
                    remaining=r.limit - r.hits, reset_time=77,
                )
                for r in reqs
            ]

    inst = FakeInstance()
    inst.shed = shed
    inst.global_mgr = GlobalManager(BehaviorConfig(), inst)
    inst.picker.add(FakePeer("127.0.0.1:81", is_owner=is_owner))
    for i in range(1, peers):
        inst.picker.add(FakePeer(f"127.0.0.{i + 1}:81"))
    bridge = EdgeBridge(inst, "", fast_enabled=fast_enabled)
    return bridge, folded_sizes, object_path_keys


def _roundtrip_string_frame(bridge, items, sock_name):
    """Send one GEB1 frame through a started bridge; return the decoded
    per-item responses."""

    async def run():
        path = f"/tmp/guber-bridge-{sock_name}.sock"
        bridge.path = path
        await bridge.start()
        try:
            reader, writer = await asyncio.open_unix_connection(path)
            await _read_hello(reader)
            writer.write(_frame(items))
            await writer.drain()
            magic, n = struct.unpack("<II", await reader.readexactly(8))
            assert magic == MAGIC_RESP and n == len(items)
            out = []
            for _ in range(n):
                st, limit, rem, reset = struct.unpack(
                    "<Bqqq", await reader.readexactly(25)
                )
                (elen,) = struct.unpack("<H", await reader.readexactly(2))
                err = (await reader.readexactly(elen)).decode()
                (olen,) = struct.unpack("<H", await reader.readexactly(2))
                owner = (await reader.readexactly(olen)).decode()
                out.append((st, limit, rem, reset, err, owner))
            writer.close()
            return out
        finally:
            await bridge.stop()

    return asyncio.run(run())


def test_string_frame_fold_serves_plain_owned_frame_via_arrays(native):
    """An all-plain all-owned GEB1 frame must skip the instance and
    ride the array path (r7 string->array fold), producing wire bytes
    identical in layout to the object path: 25-byte decisions with
    empty error and owner fields. The fold must work with the fast
    kill switch thrown — that is the case it exists for."""
    bridge, folded_sizes, object_path_keys = _fold_fixture(
        is_owner=True, fast_enabled=False
    )
    out = _roundtrip_string_frame(
        bridge,
        [_item(b"api", b"k1", hits=1, limit=5),
         _item(b"api", b"k2", hits=2, limit=9)],
        "fold-owned",
    )
    assert folded_sizes == [2]
    assert object_path_keys == []
    assert out[0] == (0, 5, 4, 77, "", "")
    assert out[1] == (0, 9, 7, 77, "", "")


def _folded_global_items() -> float:
    from gubernator_tpu.serve.metrics import REGISTRY

    return REGISTRY.get_sample_value("edge_folded_global_items_total") or 0.0


@pytest.mark.parametrize("shed_answers", [False, True],
                         ids=["device-decided", "shed-answered"])
def test_string_frame_fold_serves_owned_global_items(native, shed_answers):
    """Owned plain + GLOBAL items in one frame fold as ONE array group
    with no Instance call, and every GLOBAL item queues its key's
    status broadcast before the decide — once per distinct key with
    the frame's last item's fields — also when the shed cache answers
    the item and it never reaches the batcher."""
    from gubernator_tpu.core.hashing import slot_hash_batch
    from gubernator_tpu.serve.shedcache import ShedCache

    shed = None
    if shed_answers:
        # a frozen refusal for g1's window: the screen answers both of
        # its items host-side
        shed = ShedCache(8, now_fn=lambda: 50)
        shed.seed(int(slot_hash_batch(["api_g1"])[0]), 7, 1000, 99)
    bridge, folded_sizes, object_path_keys = _fold_fixture(
        is_owner=True, shed=shed
    )
    counted = _folded_global_items()
    out = _roundtrip_string_frame(
        bridge,
        [_item(b"api", b"k1"),
         _item(b"api", b"g1", hits=1, limit=7, behavior=2),
         _item(b"api", b"g2", hits=2, limit=9, algo=1, behavior=2),
         _item(b"api", b"k2", behavior=1),
         _item(b"api", b"g1", hits=3, limit=7, behavior=2)],
        "fold-global-" + ("shed" if shed_answers else "device"),
    )
    assert object_path_keys == []
    assert _folded_global_items() - counted == 3
    if shed_answers:
        assert folded_sizes == [3]  # the residue: k1, g2, k2
        assert out[1] == out[4] == (1, 7, 0, 99, "", "")
    else:
        assert folded_sizes == [5]
        assert out[1] == (0, 7, 6, 77, "", "")
        assert out[4] == (0, 7, 4, 77, "", "")
    assert out[0] == out[3] == (0, 5, 4, 77, "", "")
    assert out[2] == (0, 9, 7, 77, "", "")
    assert bridge.instance.global_mgr._updates == {
        "api_g1": RateLimitReq(
            name="api", unique_key="g1", hits=3, limit=7, duration=1000,
            behavior=Behavior.GLOBAL,
        ),
        "api_g2": RateLimitReq(
            name="api", unique_key="g2", hits=2, limit=9, duration=1000,
            algorithm=Algorithm.LEAKY_BUCKET, behavior=Behavior.GLOBAL,
        ),
    }


@pytest.mark.parametrize(
    "is_owner,items,answered",
    [
        (False, [_item(b"api", b"k1"), _item(b"api", b"g1", behavior=2)],
         True),
        (False, [_item(b"api", b"k1")], True),
        (True, [_item(b"api", b"k1"), _item(b"", b"g1", behavior=2)], True),
        (True, [_item(b"api", b"k1"), _item(b"api", BAD, behavior=2)], True),
        (True, [_item(b"api", b"k1"), _item(b"api", b"g1", behavior=2)[:-3]],
         False),
    ],
    ids=["unowned-global", "unowned-plain", "empty-name", "bad-utf8",
         "truncated"],
)
def test_string_frame_fold_declines_invalid_and_unowned_frames(
    is_owner, items, answered
):
    """Any key this node does not own (a non-owner's GLOBAL item needs
    the replica answer and queue_hit, a plain one a forward), an empty
    name, bad UTF-8 or a truncated payload pushes the WHOLE frame onto
    the object path — the fold never bypasses forwarding or per-item
    validation, and queues no broadcast for a frame it declined."""
    bridge, folded_sizes, object_path_keys = _fold_fixture(is_owner=is_owner)
    payload = b"".join(items)
    assert bridge._screen_string_frame(payload, len(items))[0] is None
    if answered:
        out = _roundtrip_string_frame(
            bridge, items, f"fold-decline-{is_owner}-{len(payload)}"
        )
        assert object_path_keys[0] == "k1"
        assert out[0][:4] == (0, 5, 4, 77)
    else:
        # malformed input closes the connection on either path
        with pytest.raises(struct.error):
            asyncio.run(bridge._decide_string(payload, len(items)))
    assert folded_sizes == []
    assert bridge.instance.global_mgr._updates == {}


def _seeded_mixed_frame(seed: int, n: int = 1000):
    """One frame of what the four-chip cell sends (both algorithms, the
    three limit classes, every tenth key id Behavior GLOBAL, zipf-like
    duplicates, peeks) as (payload, [RateLimitReq]); a key carries the
    same hits all through the frame (the program's rule for same-key
    items of one batch equals one-by-one service exactly then)."""
    import random

    rng = random.Random(seed)
    classes = ((100, 60_000), (10, 1_000), (1000, 3_600_000))
    hits_of = {}
    items = []
    for _ in range(n):
        # a zipf-like head over a uniform tail of 400 key ids
        i = (min(int(rng.paretovariate(0.9)), 400) if rng.random() < 0.6
             else rng.randrange(400))
        limit, duration = classes[i % 7 % 3]
        hits = hits_of.setdefault(i, rng.choice((1, 1, 1, 2, 0)))
        behavior = 2 if i % 10 == 3 else (1 if i % 10 == 5 else 0)
        items.append(_item(
            b"mixed", b"k%d" % i, hits=hits, limit=limit,
            duration=duration, algo=i % 2, behavior=behavior,
        ))
    return b"".join(items), n


def test_seeded_mixed_frame_folds_byte_identical_to_object_path(
    native, monkeypatch
):
    """The fold's answers are the object path's, byte for byte: two
    fresh nodes on a standing clock serve the same two seeded 1000-item
    mixed frames (the first drives keys over their limit and fills the
    shed cache), one through the fold and one through the object path
    (`_decide_string` + `encode_response_frame`, where the door sends
    whatever the fold declines), and leave the same broadcasts
    queued."""
    from gubernator_tpu.core.store import StoreConfig
    from gubernator_tpu.serve.backends import TpuBackend
    from gubernator_tpu.serve.config import ServerConfig
    from gubernator_tpu.serve.instance import Instance
    from gubernator_tpu.serve.metrics import REGISTRY
    from gubernator_tpu.api.types import PeerInfo

    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    def clock():
        return 1_700_000_000_000

    monkeypatch.setattr(types_mod, "millisecond_now", clock)
    monkeypatch.setattr(engine_mod, "millisecond_now", clock)
    addr = "127.0.0.1:9981"
    frames = [_seeded_mixed_frame(27), _seeded_mixed_frame(2027)]

    async def serve(fold: bool):
        conf = ServerConfig(
            grpc_address=addr, advertise_address=addr, shed_cache=True
        )
        # the broadcast loop stays asleep: what was queued is read back
        conf.behaviors.global_sync_wait = 3600.0
        inst = Instance(
            conf,
            TpuBackend(
                StoreConfig(rows=16, slots=1 << 10), buckets=(64, 1024)
            ),
        )
        inst.start()
        await inst.set_peers([PeerInfo(address=addr, is_owner=True)])
        inst.shed.now_fn = clock
        calls = []
        served = inst.get_rate_limits

        async def counting(reqs, stage_frame=False):
            calls.append(len(reqs))
            return await served(reqs, stage_frame=stage_frame)

        inst.get_rate_limits = counting
        bridge = EdgeBridge(inst, "")

        async def by_objects(payload, n):
            return encode_response_frame(
                await bridge._decide_string(payload, n)
            )

        decide = bridge._decide_string_frame if fold else by_objects
        try:
            out = [await decide(payload, n) for payload, n in frames]
            return out, calls, dict(inst.global_mgr._updates), inst.shed.hits
        finally:
            await inst.stop()

    def grown():
        return {
            c: REGISTRY.get_sample_value(c) or 0.0
            for c in ("edge_folded_items_total", "edge_object_items_total",
                      "edge_folded_global_items_total")
        }

    before = grown()
    folded, fold_calls, fold_updates, fold_shed = asyncio.run(serve(True))
    mid = grown()
    plain, obj_calls, obj_updates, obj_shed = asyncio.run(serve(False))
    after = grown()
    assert fold_calls == [] and obj_calls == [1000, 1000]
    assert folded == plain
    assert fold_updates == obj_updates and len(fold_updates) > 20
    assert fold_shed == obj_shed > 0  # the shed cache answered items
    statuses = [folded[1][8 + 29 * j] for j in range(1000)]
    assert 0 < sum(statuses) < 1000  # keys over their limit, and under
    n_global = sum(
        1 for payload, n in frames for r in decode_request_frame(payload, n)
        if r.behavior == Behavior.GLOBAL
    )
    assert n_global > 100
    assert {c: mid[c] - before[c] for c in mid} == {
        "edge_folded_items_total": 2000.0,
        "edge_object_items_total": 0.0,
        "edge_folded_global_items_total": float(n_global),
    }
    assert {c: after[c] - mid[c] for c in mid} == {
        "edge_folded_items_total": 0.0,
        "edge_object_items_total": 2000.0,
        "edge_folded_global_items_total": 0.0,
    }


def _object_items() -> float:
    from gubernator_tpu.serve.metrics import REGISTRY

    return REGISTRY.get_sample_value("edge_object_items_total") or 0.0


@pytest.mark.parametrize(
    "peers,reason", [(1, None), (3, "no_native")],
    ids=["one-node-ring", "shared-ring"],
)
def test_string_frame_without_the_library_is_the_object_paths(
    monkeypatch, peers, reason
):
    """Where native_lib() is None nothing parses a string frame into
    arrays: the object path answers every one (no second parser in
    Python), its items counted edge_object_items_total — and on a ring
    this node shares, edge_split_declined_total{reason="no_native"}."""
    from gubernator_tpu.serve import edge_bridge

    monkeypatch.setattr(edge_bridge, "native_lib", lambda: None)
    bridge, folded_sizes, object_path_keys = _fold_fixture(
        is_owner=True, peers=peers
    )
    items = [_item(b"api", b"k1"), _item(b"api", b"k2", behavior=2)]
    payload = b"".join(items)
    assert bridge._screen_string_frame(payload, 2) == (
        None, None, reason or ""
    )
    objects = _object_items()
    out = _roundtrip_string_frame(bridge, items, f"no-native-{peers}")
    assert _object_items() - objects == 2
    assert folded_sizes == []
    assert object_path_keys == ["k1", "k2"]
    assert [o[:4] for o in out] == [(0, 5, 4, 77)] * 2
    declined = bridge.instance.edge_split.declined
    assert {r: c for r, c in declined.items() if c} == (
        {reason: 1} if reason else {}
    )
    assert bridge.instance.global_mgr._updates == {}


def test_picker_owner_column_matches_get():
    """owner_column under the ring's is_owner column (the fold's
    vectorized ownership screen) must agree with get() — the
    authoritative per-key placement — across a multi-peer ring."""
    from gubernator_tpu.serve.peers import ConsistentHashPicker

    def owned(picker, keys):
        return picker.ring()[2][picker.owner_column(keys)]

    picker = ConsistentHashPicker()
    picker.add(FakePeer("10.0.0.1:81", is_owner=True))
    picker.add(FakePeer("10.0.0.2:81"))
    picker.add(FakePeer("10.0.0.3:81"))
    keys = [f"api_k{i}" for i in range(500)]
    mask = owned(picker, keys)
    assert mask.any() and not mask.all()  # 500 keys spread over 3 peers
    for k, own in zip(keys, mask):
        assert picker.get(k).is_owner == bool(own)
    # a ring whose only point is this node owns every key; one whose
    # only point is another node owns none
    for is_owner in (True, False):
        alone = ConsistentHashPicker()
        alone.add(FakePeer("10.0.0.1:81", is_owner=is_owner))
        assert owned(alone, keys).tolist() == [is_owner] * 500
