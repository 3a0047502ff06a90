"""GEB client protocol tests (r12, gubernator_tpu.client_geb).

The client speaks the bridge wire protocol from outside the serving
tier, so its constants are deliberate duplicates — pinned equal here —
and its behavior is tested against the REAL frame-service core
(serve/edge_bridge.py FrameService/GebListener) over real sockets with
fake instances, the test_edge_bridge pattern.
"""

import asyncio
import struct
from dataclasses import dataclass

import pytest

from _util import free_ports
from gubernator_tpu.api.types import RateLimitResp, Status
from gubernator_tpu.serve.edge_bridge import EdgeBridge, GebListener


@dataclass
class FakePeer:
    host: str
    is_owner: bool = False


class _FakeBackendArrays:
    decide_submit_merged = object()  # a device backend


class _FakeTraffic:
    def observe_hashes(self, h):
        pass

    def observe(self, keys, hashes, packed=None):
        pass


class _FakePicker:
    def __init__(self, hosts=("127.0.0.1:81",)):
        self._hosts = list(hosts)

    def peers(self):
        return [
            FakePeer(h, is_owner=(i == 0))
            for i, h in enumerate(self._hosts)
        ]


def _reqs(n=3, prefix="k", limit=5, hits=1):
    from gubernator_tpu.api.types import RateLimitReq

    return [
        RateLimitReq(
            name="geb",
            unique_key=f"{prefix}{i}",
            hits=hits,
            limit=limit,
            duration=60_000,
        )
        for i in range(n)
    ]


def test_wire_constants_match_bridge():
    """client_geb must not import the serving tier, so its wire
    constants are duplicates — this pin is what makes that safe."""
    import gubernator_tpu.client_geb as cg
    import gubernator_tpu.serve.edge_bridge as eb

    for name in (
        "MAGIC_REQ", "MAGIC_RESP", "MAGIC_HELLO", "MAGIC_FAST_REQ",
        "MAGIC_FAST_RESP", "MAGIC_STALE", "MAGIC_WREQ", "MAGIC_WRESP",
        "MAGIC_WFAST_REQ", "MAGIC_WFAST_RESP", "HELLO_FAST",
        "HELLO_WINDOWED", "HELLO_XXH64", "DRAIN_FRAME_ID",
        "MAX_FRAME_PAYLOAD",
    ):
        assert getattr(cg, name) == getattr(eb, name), name
    from gubernator_tpu.serve.server import GEB_CONTENT_TYPE

    assert cg.GEB_CONTENT_TYPE == GEB_CONTENT_TYPE


def test_client_geb_imports_without_jax():
    """The GEB client is a packaged client like client.py: importing it
    must not drag JAX in (subprocess so the rest of the suite can't
    contaminate the check)."""
    import subprocess
    import sys

    r = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sys\n"
            "import gubernator_tpu.client_geb as cg\n"
            "banned = [m for m in sys.modules if m == 'jax' "
            "or m.startswith('jax.') or m == 'jaxlib' "
            "or m.startswith('jaxlib.')]\n"
            "assert not banned, banned\n"
            "cg.client_hash_batch(['a_b'])  # hashing path is JAX-free too\n"
            "assert not [m for m in sys.modules if m.startswith('jax')]\n"
            "print('OK')\n",
        ],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_client_hash_matches_store_hash():
    """Fast frames only work if the client's hash equals the store's
    (core.hashing.slot_hash_batch) — same implementation tier, same
    bytes. In-process the tiers always match, so equality must be
    exact."""
    import numpy as np

    from gubernator_tpu.client_geb import (
        client_hash_batch,
        client_hash_is_native,
    )
    from gubernator_tpu.core.hashing import (
        slot_hash_batch,
        using_native_hash,
    )

    assert client_hash_is_native() == using_native_hash()
    keys = [f"geb_k{i}" for i in range(50)] + ["a_b", "x_" + "y" * 300]
    assert np.array_equal(client_hash_batch(keys), slot_hash_batch(keys))


class _ObjectInstance:
    """String-path fake: serves request objects, echoing limit-hits."""

    def __init__(self):
        self.calls = []

    async def get_rate_limits(self, reqs, stage_frame=False):
        self.calls.append([r.unique_key for r in reqs])
        return [
            RateLimitResp(
                status=Status.UNDER_LIMIT,
                limit=r.limit,
                remaining=r.limit - r.hits,
                reset_time=42,
            )
            for r in reqs
        ]


class _ArrayInstance:
    """Array-path fake: echoes limit back as remaining so the fast
    path's ordering is checkable."""

    backend = _FakeBackendArrays()
    traffic = _FakeTraffic()

    def __init__(self, hosts=("127.0.0.1:81",)):
        import numpy as np

        self.picker = _FakePicker(hosts)
        outer = self

        class B:
            async def decide_arrays(self, fields, frame=True):
                n = fields["key_hash"].shape[0]
                outer.seen = outer.__dict__.setdefault("seen", [])
                outer.seen.append(n)
                return (
                    np.zeros(n, np.int64),
                    fields["limit"],
                    fields["limit"],
                    np.full(n, 7, np.int64),
                )

        self.batcher = B()


def _with_listener(instance, coro_fn, window=0):
    """Run `coro_fn(port)` against a GebListener over `instance`."""

    async def run():
        (port,) = free_ports(1)
        lst = GebListener(
            instance, f"127.0.0.1:{port}", window=window
        )
        await lst.start()
        try:
            return await coro_fn(port, lst)
        finally:
            await lst.stop()

    return asyncio.run(run())


def test_string_mode_roundtrip_and_negotiation():
    inst = _ObjectInstance()

    async def go(port, lst):
        from gubernator_tpu.client_geb import AsyncGebClient

        async with AsyncGebClient(
            f"127.0.0.1:{port}", mode="string"
        ) as c:
            assert c.hello.windowed
            assert not c._use_fast
            out = await c.get_rate_limits(_reqs(4))
            return out

    out = _with_listener(inst, go)
    assert [
        (int(r.status), r.limit, r.remaining, r.reset_time) for r in out
    ] == [(0, 5, 4, 42)] * 4
    assert inst.calls == [["k0", "k1", "k2", "k3"]]


def test_chain_frames_roundtrip_and_capability_gate():
    """Quota chains over the socket door (r15): a mixed plain/chained
    batch rides ONE GEBC frame — the chain levels arrive intact at the
    instance, fast framing is bypassed, responses come back in order —
    and a server hello without HELLO_CHAIN is refused client-side
    before anything hits the wire."""
    from gubernator_tpu.api.types import ChainLevel

    inst = _ObjectInstance()
    seen_chains = []
    orig = inst.get_rate_limits

    async def capture(reqs, stage_frame=False):
        seen_chains.append(
            [[(lv.unique_key, lv.limit, lv.duration) for lv in r.chain]
             for r in reqs]
        )
        return await orig(reqs, stage_frame)

    inst.get_rate_limits = capture

    async def go(port, lst):
        from gubernator_tpu.client_geb import (
            HELLO_CHAIN,
            AsyncGebClient,
            GebError,
        )

        async with AsyncGebClient(f"127.0.0.1:{port}") as c:
            assert c.hello.chain, hex(c.hello.flags)
            reqs = _reqs(3)
            reqs[1].chain = [
                ChainLevel("global", 100, 0),
                ChainLevel("tenant:a", 10, 2000),
            ]
            out = await c.get_rate_limits(reqs)
            assert len(out) == 3
            # a pre-r15 hello (no chain capability) refuses client-side
            c.hello.flags &= ~HELLO_CHAIN
            try:
                await c.get_rate_limits(reqs)
            except GebError as e:
                assert "HELLO_CHAIN" in str(e)
            else:
                raise AssertionError("expected GebError")
        return out

    _with_listener(inst, go)
    assert seen_chains == [[
        [],
        [("global", 100, 0), ("tenant:a", 10, 2000)],
        [],
    ]]


def test_hello_chain_bit_follows_kill_switch():
    """With GUBER_CHAINS=0 the hello must NOT advertise HELLO_CHAIN,
    so a chained caller fails fast client-side instead of shipping
    GEBC frames destined for per-item refusal (review finding)."""
    from types import SimpleNamespace

    from gubernator_tpu.api.types import ChainLevel

    inst = _ObjectInstance()
    inst.conf = SimpleNamespace(chains=False)

    async def go(port, lst):
        from gubernator_tpu.client_geb import AsyncGebClient, GebError

        async with AsyncGebClient(f"127.0.0.1:{port}") as c:
            assert not c.hello.chain, hex(c.hello.flags)
            reqs = _reqs(1)
            reqs[0].chain = [ChainLevel("g", 5, 0)]
            try:
                await c.get_rate_limits(reqs)
            except GebError as e:
                assert "HELLO_CHAIN" in str(e)
            else:
                raise AssertionError("expected GebError")
            # plain traffic is unaffected
            out = await c.get_rate_limits(_reqs(2))
            assert len(out) == 2

    _with_listener(inst, go)


def test_auto_mode_uses_fast_on_single_node():
    """In-process, the client and 'store' share a hash tier, the ring
    is single-node, and the fake backend takes arrays — auto must pick
    fast framing and the responses must come back in order."""
    inst = _ArrayInstance()

    async def go(port, lst):
        from gubernator_tpu.client_geb import AsyncGebClient

        async with AsyncGebClient(f"127.0.0.1:{port}") as c:
            assert c._use_fast, hex(c.hello.flags)
            reqs = _reqs(5)
            for i, r in enumerate(reqs):
                r.limit = 100 + i
            out = await c.get_rate_limits(reqs)
            return out

    out = _with_listener(inst, go)
    # fast echo: remaining == limit, reset from the fake batcher
    assert [(r.remaining, r.reset_time) for r in out] == [
        (100 + i, 7) for i in range(5)
    ]


def test_auto_mode_downgrades_to_string_on_multinode():
    """Fast frames bypass instance routing, so auto mode must refuse
    them on a multi-node ring (string framing keeps forwarding
    semantics); the object path serves instead."""
    import numpy as np  # noqa: F401

    class Both(_ObjectInstance):
        backend = _FakeBackendArrays()
        traffic = _FakeTraffic()
        picker = _FakePicker(["10.0.0.1:81", "10.0.0.2:81"])

    inst = Both()

    async def go(port, lst):
        from gubernator_tpu.client_geb import AsyncGebClient

        async with AsyncGebClient(f"127.0.0.1:{port}") as c:
            assert not c._use_fast
            return await c.get_rate_limits(_reqs(2))

    out = _with_listener(inst, go)
    assert len(out) == 2 and inst.calls


def test_global_items_ride_string_frames_even_in_fast_mode():
    """A batch carrying GLOBAL/NO_BATCHING behaviors cannot be encoded
    as fast records — the client must fall back to string framing for
    that batch (auto mode, fast otherwise negotiated)."""
    from gubernator_tpu.api.types import Behavior

    class Both(_ArrayInstance, _ObjectInstance):
        def __init__(self):
            _ArrayInstance.__init__(self)
            _ObjectInstance.__init__(self)

    inst = Both()

    async def go(port, lst):
        from gubernator_tpu.client_geb import AsyncGebClient

        async with AsyncGebClient(f"127.0.0.1:{port}") as c:
            assert c._use_fast
            reqs = _reqs(3)
            reqs[1].behavior = Behavior.GLOBAL
            return await c.get_rate_limits(reqs)

    out = _with_listener(inst, go)
    assert len(out) == 3
    # the GLOBAL batch went through the object path (string frame)
    assert inst.calls and inst.calls[0] == ["k0", "k1", "k2"]


def test_out_of_order_completion_pipelines():
    """Two concurrent calls on one connection: the slow frame must not
    convoy the fast one (out-of-order completion by frame id), and
    both must resolve with their OWN batch's responses."""
    release = asyncio.Event()

    class Inst:
        async def get_rate_limits(self, reqs, stage_frame=False):
            if reqs[0].unique_key == "slow":
                await release.wait()
            return [
                RateLimitResp(
                    status=Status.UNDER_LIMIT,
                    limit=r.limit,
                    remaining=len(r.unique_key),
                    reset_time=1,
                )
                for r in reqs
            ]

    async def go(port, lst):
        from gubernator_tpu.api.types import RateLimitReq
        from gubernator_tpu.client_geb import AsyncGebClient

        async with AsyncGebClient(
            f"127.0.0.1:{port}", mode="string"
        ) as c:
            slow = asyncio.ensure_future(
                c.get_rate_limits(
                    [RateLimitReq(name="n", unique_key="slow", hits=1,
                                  limit=5, duration=1000)]
                )
            )
            await asyncio.sleep(0.05)
            fast = await c.get_rate_limits(
                [RateLimitReq(name="n", unique_key="quick!", hits=1,
                              limit=5, duration=1000)]
            )
            assert not slow.done()  # still parked behind the gate
            release.set()
            return fast, await slow

    fast, slow = _with_listener(Inst(), go)
    assert fast[0].remaining == len("quick!")
    assert slow[0].remaining == len("slow")


def test_window_negotiation_caps_client_side():
    inst = _ObjectInstance()

    async def go(port, lst):
        from gubernator_tpu.client_geb import AsyncGebClient

        async with AsyncGebClient(
            f"127.0.0.1:{port}", mode="string", window=2
        ) as c:
            assert c.hello.window == lst.window >= 2
            assert c._window == 2  # min(server, requested)
            return True

    assert _with_listener(inst, go)


def test_stale_ring_fails_frame_and_reconnect_heals():
    """A ring change between hello and frame must surface as
    GebStaleRingError (the frame was NOT served), and the next call
    must transparently reconnect onto the fresh ring and succeed."""
    inst = _ArrayInstance()

    async def go(port, lst):
        from gubernator_tpu.client_geb import (
            AsyncGebClient,
            GebStaleRingError,
        )

        c = AsyncGebClient(f"127.0.0.1:{port}")
        await c.connect()
        assert c._use_fast
        # membership changes AFTER the hello: new picker object, new
        # fingerprint — the client's next fast frame is now stale
        inst.picker = _FakePicker(["127.0.0.1:82"])
        with pytest.raises(GebStaleRingError):
            await c.get_rate_limits(_reqs(2))
        out = await c.get_rate_limits(_reqs(2))  # reconnect re-hellos
        await c.close()
        return out

    out = _with_listener(inst, go)
    assert len(out) == 2


def test_drain_refusal_surfaces_and_names_retry_safety():
    inst = _ObjectInstance()

    async def go(port, lst):
        from gubernator_tpu.client_geb import (
            AsyncGebClient,
            GebDrainingError,
        )

        c = AsyncGebClient(f"127.0.0.1:{port}", mode="string")
        await c.connect()
        await lst.drain(0.5)
        with pytest.raises(GebDrainingError):
            await c.get_rate_limits(_reqs(1))
        await c.close()
        return True

    assert _with_listener(inst, go)


def test_sync_client_roundtrip_and_pipelined():
    inst = _ObjectInstance()

    async def hold(port, lst):
        # keep the listener alive while the BLOCKING client (own loop
        # thread) drives it
        from gubernator_tpu.client_geb import GebClient

        def blocking():
            with GebClient(
                f"127.0.0.1:{port}", mode="string"
            ) as c:
                one = c.get_rate_limits(_reqs(2))
                many = c.get_rate_limits_pipelined(
                    [_reqs(1), _reqs(3)]
                )
                return one, many

        return await asyncio.to_thread(blocking)

    one, many = _with_listener(inst, hold)
    assert len(one) == 2
    assert [len(b) for b in many] == [1, 3]


def test_client_against_edge_bridge_unix_socket():
    """The same client speaks to a bridge unix socket (the co-located
    deployment shape) — endpoint parsing picks the unix transport from
    the path spec."""
    inst = _ObjectInstance()

    async def run():
        path = "/tmp/guber-geb-client-bridge.sock"
        bridge = EdgeBridge(inst, path)
        await bridge.start()
        try:
            from gubernator_tpu.client_geb import AsyncGebClient

            async with AsyncGebClient(path, mode="string") as c:
                return await c.get_rate_limits(_reqs(2))
        finally:
            await bridge.stop()

    out = asyncio.run(run())
    assert [r.reset_time for r in out] == [42, 42]


def test_http_binary_door_content_type_and_roundtrip():
    """POST /v1/geb end to end against a real gateway (exact backend):
    hello on GET, string-frame round trip, content-type gate, and
    frame-level malformed input as 400 — no protobuf, no JSON."""
    import json
    import urllib.error
    import urllib.request

    from gubernator_tpu.client_geb import (
        GEB_CONTENT_TYPE,
        build_frame,
        decode_string_body,
        parse_hello_bytes,
    )
    from gubernator_tpu.cluster import LocalCluster
    from gubernator_tpu.serve.backends import ExactBackend

    g, h = free_ports(2)
    base = f"http://127.0.0.1:{h}"
    c = LocalCluster(
        [f"127.0.0.1:{g}"],
        backend_factory=lambda: ExactBackend(10_000),
        http_addresses=[f"127.0.0.1:{h}"],
    )
    c.start()
    try:
        with urllib.request.urlopen(base + "/v1/geb", timeout=10) as r:
            hello = parse_hello_bytes(r.read())
        assert hello.windowed and len(hello.nodes) == 1

        frame, is_fast = build_frame(
            _reqs(3), fast=False, windowed=False
        )
        req = urllib.request.Request(
            base + "/v1/geb", frame,
            {"Content-Type": GEB_CONTENT_TYPE},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            body = r.read()
        magic, n = struct.unpack_from("<II", body, 0)
        out = decode_string_body(body[8:], n)
        assert [x.remaining for x in out] == [4, 4, 4]

        # >1 MiB LEGAL frame: aiohttp's default client_max_size (1
        # MiB) would 413 this before the handler runs — the door must
        # size its body bound to the max legal GEB frame instead
        from gubernator_tpu.api.types import RateLimitReq

        big_reqs = [
            RateLimitReq(
                name="api", unique_key="K" * 50_000 + str(i),
                hits=1, limit=5, duration=60_000,
            )
            for i in range(24)
        ]
        frame, _ = build_frame(big_reqs, fast=False, windowed=False)
        assert len(frame) > (1 << 20)
        req = urllib.request.Request(
            base + "/v1/geb", frame,
            {"Content-Type": GEB_CONTENT_TYPE},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            body = r.read()
        magic, n = struct.unpack_from("<II", body, 0)
        assert n == 24
        out = decode_string_body(body[8:], n)
        assert [x.remaining for x in out] == [4] * 24

        # past the payload bound: 413 from the door's own cap (the
        # app-wide client_max_size stays at the JSON routes' 1 MiB)
        req = urllib.request.Request(
            base + "/v1/geb", b"\x00" * ((8 << 20) + 128),
            {"Content-Type": GEB_CONTENT_TYPE},
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 413

        # wrong content type: a clear 415, never a frame decode
        req = urllib.request.Request(
            base + "/v1/geb", b'{"requests": []}',
            {"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 415

        # malformed frames: 400 with a JSON error, not a 500 — incl.
        # a GEB1 frame whose plen is self-consistent but whose item
        # count lies (payload holds 1 item, header says 2: the
        # truncated-varlen shape that surfaces as struct.error)
        one_item = (
            struct.pack("<H", 3) + b"api" + struct.pack("<H", 1) + b"k"
            + struct.pack("<qqqBB", 1, 5, 1000, 0, 0)
        )
        lying_count = (
            struct.pack("<II", 0x31424547, 2)
            + struct.pack("<I", len(one_item)) + one_item
        )
        for payload in (
            b"", b"\x00" * 7, b"GARBAGE-",
            struct.pack("<II", 0x31424547, 5) + b"\x01\x02",
            frame[:-3],
            lying_count,
        ):
            req = urllib.request.Request(
                base + "/v1/geb", payload,
                {"Content-Type": GEB_CONTENT_TYPE},
            )
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 400, payload
            assert "error" in json.loads(e.value.read())
    finally:
        c.stop()


def test_unknown_status_byte_fails_loudly():
    """A corrupted or future-version status byte must raise GebError —
    never decode fail-open as UNDER_LIMIT while every other malformed
    field in the module fails loudly."""
    import gubernator_tpu.client_geb as cg

    bad_fast = struct.pack("<Bqqq", 7, 5, 4, 1)
    with pytest.raises(cg.GebError, match="status"):
        cg.decode_fast_body(bad_fast, 1)
    bad_string = bad_fast + struct.pack("<H", 0) + struct.pack("<H", 0)
    with pytest.raises(cg.GebError, match="status"):
        cg.decode_string_body(bad_string, 1)


def test_wire_count_bound_mirrors_server():
    """A server-supplied response count beyond the frame bound raises
    before sizing a read from it — the client-side mirror of the
    server's lying-length defense."""
    import gubernator_tpu.client_geb as cg

    assert cg._check_wire_count(5) == 5
    with pytest.raises(cg.GebError, match="item count"):
        cg._check_wire_count(cg.MAX_FRAME_ITEMS + 1)


def test_oversized_payload_refused_client_side():
    """A string frame whose payload would cross MAX_FRAME_PAYLOAD is
    refused loudly before the wire — the server's read-side bound
    kills the connection for anything larger."""
    import gubernator_tpu.client_geb as cg
    from gubernator_tpu.api.types import RateLimitReq

    reqs = [
        RateLimitReq(
            name="n", unique_key="K" * 60_000, hits=1, limit=5,
            duration=60_000,
        )
        for _ in range(150)
    ]
    with pytest.raises(cg.GebError, match="payload"):
        cg.build_frame(reqs, fast=False, windowed=False)


def test_http_client_short_body_raises_geberror():
    """A truncating proxy or an empty 200 body surfaces as GebError
    (the module's contract), not a raw struct.error."""
    import gubernator_tpu.client_geb as cg
    from aiohttp import web

    async def run():
        (port,) = free_ports(1)

        async def hello(request):
            return web.Response(
                body=struct.pack("<IIII", cg.MAGIC_HELLO, 0, 0, 0),
                content_type=cg.GEB_CONTENT_TYPE,
            )

        async def post(request):
            return web.Response(
                body=b"\x01", content_type=cg.GEB_CONTENT_TYPE
            )

        app = web.Application()
        app.router.add_get("/v1/geb", hello)
        app.router.add_post("/v1/geb", post)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        try:
            c = cg.AsyncHttpGebClient(f"http://127.0.0.1:{port}")
            with pytest.raises(cg.GebError, match="short response"):
                await c.get_rate_limits(_reqs(1))
            await c.close()
        finally:
            await runner.cleanup()

    asyncio.run(run())
