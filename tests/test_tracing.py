"""Distributed tracing + flight recorder (r16, serve/tracing.py).

Covers, bottom-up:

- traceparent parse/format and the GEBT wire extension round trip;
- sampling policy: head sampling, tail-capture arming, the rolling
  p99 retention threshold, the ring bound, and the disabled fast path
  (no trace, no ids);
- the stage-clock hook: STAGES.add forwards spans into the active
  trace only;
- the acceptance scenario: a three-node LocalCluster drives ONE
  sampled request through the GEB door with a NON-owned key and a
  single trace id yields spans covering edge/bridge, queue, device
  (annotated with batch size and ladder rung), and the peer-forward
  hop on BOTH nodes — the context survived the gRPC hop;
- the differential identity fuzz: GUBER_TRACE_SAMPLE=0 vs 1 produce
  byte-identical decisions over the full device pipeline (the
  r10/r13 fake-clock rig).
"""

import asyncio
import time

import numpy as np
import pytest

from gubernator_tpu.api.types import (
    Algorithm,
    PeerInfo,
    RateLimitReq,
)
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.serve import tracing
from gubernator_tpu.serve.backends import ExactBackend, TpuBackend
from gubernator_tpu.serve.config import ServerConfig
from gubernator_tpu.serve.instance import Instance

ADDR = "127.0.0.1:7988"


# -- context / wire format --------------------------------------------------


def test_traceparent_roundtrip():
    ctx = tracing.TraceContext(0xABCDEF0102030405060708090A0B0C0D, 0x11223344AABBCCDD, True)
    hdr = ctx.header()
    assert hdr == (
        "00-abcdef0102030405060708090a0b0c0d-11223344aabbccdd-01"
    )
    back = tracing.parse_traceparent(hdr)
    assert back is not None
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled is True
    unsampled = tracing.TraceContext(5, 7, False).header()
    assert tracing.parse_traceparent(unsampled).sampled is False


@pytest.mark.parametrize(
    "bad",
    [
        None,
        "",
        "garbage",
        "00-xyz-123-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # zero trace id
        "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # zero span id
        "00-" + "1" * 31 + "-" + "1" * 16 + "-01",  # short trace id
        "00-1-2-3-4",
    ],
)
def test_traceparent_malformed_degrades_to_none(bad):
    assert tracing.parse_traceparent(bad) is None


def test_gebt_wire_extension_roundtrip():
    from gubernator_tpu.serve.edge_bridge import (
        _WTRACE_EXT,
        _trace_ctx_from_ext,
    )

    ctx = tracing.TraceContext((1 << 127) | 42, (1 << 63) | 7, True)
    raw = _WTRACE_EXT.pack(
        ctx.trace_id.to_bytes(16, "big"), ctx.span_id, 1
    )
    back = _trace_ctx_from_ext(*_WTRACE_EXT.unpack(raw))
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.sampled
    # zero ids degrade to untraced, never error
    assert _trace_ctx_from_ext(b"\0" * 16, 1, 1) is None


# -- sampling policy / flight recorder --------------------------------------


def test_disabled_tracer_allocates_nothing():
    t = tracing.Tracer(sample=0.0, slow_ms=0.0)
    assert not t.enabled
    assert t.begin("grpc") is None
    assert t.join("grpc", None) is None
    # with tracing fully OFF, even a remote SAMPLED context is ignored:
    # traceparent arrives on untrusted doors, and a client header must
    # not override the operator's policy
    assert t.join("peers", tracing.TraceContext(9, 9, True)) is None
    # any enabled policy (tail capture alone suffices) honors it
    t2 = tracing.Tracer(sample=0.0, slow_ms=5.0)
    tr = t2.join("peers", tracing.TraceContext(9, 9, True))
    assert tr is not None and tr.sampled and tr.trace_id == 9
    # ...but an unsampled remote context still only tail-arms
    armed = t2.join("peers", tracing.TraceContext(9, 9, False))
    assert armed is not None and not armed.sampled


def test_head_sampling_and_recorder():
    t = tracing.Tracer(sample=1.0)
    tr = t.begin("geb")
    assert tr is not None and tr.sampled
    tr.add_span("bridge_decode", duration_s=0.001)
    tr.add_span("device", duration_s=0.002, batch=8, rung=16)
    t.finish(tr)
    snap = t.recorder.snapshot()
    assert snap["counters"]["recorded"] == 1
    doc = snap["traces"][0]
    assert doc["sampled"] and not doc["tail"]
    names = [s["name"] for s in doc["spans"]]
    assert names == ["bridge_decode", "device"]
    dev = doc["spans"][1]
    assert dev["annotations"] == {"batch": 8, "rung": 16}
    # by-id lookup round-trips
    assert t.recorder.get(doc["trace_id"])["span_id"] == doc["span_id"]
    assert t.recorder.get("f" * 32) is None


def test_tail_capture_retains_only_slow_requests():
    t = tracing.Tracer(sample=0.0, slow_ms=10.0)
    assert t.enabled
    fast = t.begin("http")
    assert fast is not None and not fast.sampled
    t.finish(fast)  # ~0ms: below the floor, not retained
    slow = t.begin("http")
    slow.t0 -= 0.05  # pretend it took 50ms
    t.finish(slow)
    snap = t.recorder.snapshot()
    assert snap["counters"]["recorded"] == 1
    assert snap["counters"]["tail_captured"] == 1
    assert snap["traces"][0]["tail"] is True
    assert snap["traces"][0]["duration_ms"] >= 10.0
    # unsampled traces never propagate a header
    assert slow.header() is None


def test_rolling_p99_lifts_the_threshold():
    t = tracing.Tracer(sample=0.0, slow_ms=1.0)
    # feed enough finishes that the p99 recompute (every 64) sees a
    # spread: most ~0ms, a few at ~100ms
    for i in range(200):
        tr = t.begin("grpc")
        if i % 50 == 0:
            tr.t0 -= 0.1
        t.finish(tr)
    assert t.recorder.threshold_ms() > 1.0  # p99 lifted off the floor


def test_recorder_ring_bound_and_reset():
    t = tracing.Tracer(sample=1.0, capacity=4)
    for _ in range(10):
        t.finish(t.begin("grpc"))
    snap = t.recorder.snapshot()
    assert snap["count"] == 4
    assert snap["counters"]["dropped"] == 6
    # limit=0 means counters-only, never "the whole ring" ([-0:] trap)
    assert t.recorder.snapshot(limit=0)["traces"] == []
    assert len(t.recorder.snapshot(limit=2)["traces"]) == 2
    t.recorder.reset()
    snap = t.recorder.snapshot()
    assert snap["count"] == 0 and snap["counters"]["recorded"] == 0


def test_lazy_ids_and_scope():
    t = tracing.Tracer(sample=0.0, slow_ms=5.0)
    tr = t.begin("geb")
    assert tr._trace_id is None  # armed, no id generated yet
    with tracing.scope(t, tr) as active:
        assert tracing.active() is active
    assert tracing.active() is None
    # the fast finish retained nothing and still never generated ids
    assert tr._trace_id is None


def test_stage_clock_forwards_spans_into_active_trace():
    from gubernator_tpu.serve.stages import STAGES

    t = tracing.Tracer(sample=1.0)
    tr = t.begin("geb")
    tok = tracing.activate(tr)
    try:
        STAGES.add("shed", 0.003)
    finally:
        tracing.deactivate(tok)
    STAGES.add("shed", 0.004)  # no active trace: stage clock only
    with tr._lock:
        spans = list(tr._spans)
    assert len(spans) == 1
    name, s, e, _ann = spans[0]
    assert name == "shed" and (e - s) == pytest.approx(0.003, abs=1e-6)


# -- GEBT over the frame service --------------------------------------------


def _mk_instance_coro(backend, **conf_kw):
    async def mk():
        conf = ServerConfig(
            grpc_address=ADDR, advertise_address=ADDR, **conf_kw
        )
        inst = Instance(conf, backend)
        inst.start()
        await inst.set_peers([PeerInfo(address=ADDR, is_owner=True)])
        return inst

    return mk()


def test_hello_advertises_trace_capability():
    from gubernator_tpu.client_geb import parse_hello_bytes
    from gubernator_tpu.serve.edge_bridge import FrameService

    async def run():
        inst = await _mk_instance_coro(ExactBackend(1000))
        try:
            hello = parse_hello_bytes(
                FrameService(inst).hello_bytes()
            )
            assert hello.trace
        finally:
            await inst.stop()

    asyncio.run(run())


def test_gebt_frame_joins_remote_trace():
    """A GEBT frame's carried SAMPLED context is honored with the
    server's own HEAD sampling off (any enabled policy — here tail
    capture — suffices), and the retained trace keeps the client's
    trace id + parent span id — the cross-process contract."""
    from gubernator_tpu.client_geb import build_frame
    from gubernator_tpu.serve.edge_bridge import FrameService

    async def run():
        inst = await _mk_instance_coro(
            ExactBackend(1000), trace_slow_ms=60_000
        )
        try:
            svc = FrameService(inst)
            ctx = tracing.TraceContext(0xDEADBEEF, 0xFEED, True)
            frame, _ = build_frame(
                [RateLimitReq(name="t", unique_key="k", hits=1,
                              limit=5, duration=1000)],
                fast=False, windowed=True, frame_id=3,
                trace_ctx=ctx,
            )
            await svc.serve_frame_bytes(frame)
            snap = inst.tracer.recorder.snapshot()
            assert snap["counters"]["recorded"] == 1
            doc = snap["traces"][0]
            assert doc["trace_id"] == "%032x" % 0xDEADBEEF
            assert doc["parent_span_id"] == "%016x" % 0xFEED
            names = {s["name"] for s in doc["spans"]}
            assert "bridge_decode" in names
            assert "device" in names
        finally:
            await inst.stop()

    asyncio.run(run())


# -- acceptance: three-node cluster, one sampled request --------------------


def test_three_node_trace_covers_both_sides_of_the_forward():
    """ISSUE 12 acceptance: one sampled request through the GEB door
    with a NON-owned key; a single trace id yields spans covering
    edge/bridge + peer-forward on the origin node and queue + device
    (annotated with batch size and ladder rung) on the owner — the
    context survived the gRPC hop into the owner's own recorder."""
    from _util import free_ports
    from gubernator_tpu.client_geb import AsyncGebClient
    from gubernator_tpu.cluster import LocalCluster

    g1, g2, g3, geb = free_ports(4)
    cluster = LocalCluster(
        [f"127.0.0.1:{p}" for p in (g1, g2, g3)],
        backend_factory=lambda: TpuBackend(
            StoreConfig(rows=16, slots=1 << 8), buckets=(16,)
        ),
        geb_ports=[geb, 0, 0],
        trace_sample=1.0,
    )
    cluster.start()
    try:
        inst0 = cluster.instance_at(0)
        # a key node 0 does NOT own (forwarded over gRPC to its owner)
        key = next(
            k
            for k in (f"trace-k{i}" for i in range(256))
            if not inst0.get_peer(f"t_{k}").is_owner
        )
        owner_host = inst0.get_peer(f"t_{key}").host
        owner_idx = cluster.addresses.index(owner_host)
        assert owner_idx != 0

        async def drive():
            client = AsyncGebClient(
                f"127.0.0.1:{geb}", mode="string"
            )
            async with client:
                return await client.get_rate_limits(
                    [RateLimitReq(name="t", unique_key=key, hits=1,
                                  limit=100, duration=60_000)],
                    timeout=30.0,
                )

        (resp,) = asyncio.run(drive())
        assert not resp.error
        assert resp.metadata.get("owner") == owner_host

        def recorded(idx):
            return cluster.instance_at(idx).tracer.recorder.snapshot()[
                "traces"
            ]

        # recorders fill just after the response writes; poll briefly
        deadline = time.monotonic() + 10.0
        origin = owner = None
        while time.monotonic() < deadline:
            origin_traces = [
                t for t in recorded(0) if t["door"] == "geb"
            ]
            if origin_traces:
                origin = origin_traces[-1]
                owner_traces = [
                    t
                    for t in recorded(owner_idx)
                    if t["trace_id"] == origin["trace_id"]
                ]
                if owner_traces:
                    owner = owner_traces[-1]
                    break
            time.sleep(0.05)
        assert origin is not None, "origin node recorded no geb trace"
        assert owner is not None, (
            "owner node holds no trace with the origin's id — context "
            "lost on the gRPC hop"
        )

        # ONE trace id, spans covering the whole path across the two
        # recorders
        origin_names = {s["name"] for s in origin["spans"]}
        owner_names = {s["name"] for s in owner["spans"]}
        assert "bridge_decode" in origin_names  # edge/bridge
        assert "peer_forward" in origin_names  # the hop
        # the owner served a gRPC call: the call family's names
        assert "call_queue" in owner_names  # queue
        assert "call_device" in owner_names  # device
        assert owner["door"] == "peers"
        fwd = next(
            s for s in origin["spans"] if s["name"] == "peer_forward"
        )
        assert fwd["annotations"]["peer"] == owner_host
        dev = next(
            s for s in owner["spans"] if s["name"] == "call_device"
        )
        # device span annotated with batch size and ladder rung
        assert dev["annotations"]["batch"] >= 1
        assert dev["annotations"]["rung"] == 16  # the (16,) ladder
        assert "algo_mix" in dev["annotations"]
    finally:
        cluster.stop()


# -- differential identity fuzz ---------------------------------------------


class FakeClock:
    def __init__(self, t=1_700_000_000_000):
        self.t = t

    def __call__(self):
        return self.t


def _pin_clock(monkeypatch, clock):
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod
    import gubernator_tpu.core.oracle as oracle_mod

    monkeypatch.setattr(types_mod, "millisecond_now", clock)
    monkeypatch.setattr(engine_mod, "millisecond_now", clock)
    monkeypatch.setattr(oracle_mod, "millisecond_now", clock)


def _fuzz_stream(rng, keys, steps):
    for step in range(steps):
        n = int(rng.integers(1, 7))
        batch = []
        for _ in range(n):
            k = int(rng.integers(len(keys)))
            batch.append(
                RateLimitReq(
                    name="tracefuzz",
                    unique_key=keys[k],
                    hits=int(rng.choice([0, 1, 1, 1, 2, 9])),
                    limit=int(rng.choice([1, 1, 2, 3, 50])),
                    duration=int(rng.choice([400, 2000, 60_000])),
                    algorithm=Algorithm(k % 4),
                )
            )
        yield step, batch, int(rng.choice([0, 0, 1, 7, 150, 500, 2500]))


def _assert_same(a, b, ctx):
    assert (
        a.status, a.limit, a.remaining, a.reset_time, a.error
    ) == (
        b.status, b.limit, b.remaining, b.reset_time, b.error
    ), (ctx, a, b)


@pytest.mark.parametrize("seed", [6, 13])
def test_differential_identity_fuzz_tracing(monkeypatch, seed):
    """GUBER_TRACE_SAMPLE=0 is byte-identical to sample=1 (+ tail
    capture) over the full device pipeline: instance -> batcher (queue
    marks, device spans) -> arrival prep -> kernel. Tracing observes;
    it must never decide."""
    clock = FakeClock()
    _pin_clock(monkeypatch, clock)

    def be():
        return TpuBackend(
            StoreConfig(rows=16, slots=1 << 10), buckets=(16, 64)
        )

    async def run():
        on = await _mk_instance_coro(
            be(), trace_sample=1.0, trace_slow_ms=0.0001
        )
        off = await _mk_instance_coro(be())
        assert on.tracer.enabled and not off.tracer.enabled
        try:
            rng = np.random.default_rng(seed)
            keys = [f"t{i}" for i in range(12)]
            for step, batch, dt in _fuzz_stream(rng, keys, 120):
                clock.t += dt
                # the traced side runs under an active door trace,
                # exactly as the servicers set one up
                trace = on.tracer.begin("grpc")
                with tracing.scope(on.tracer, trace):
                    a = await on.get_rate_limits(batch)
                b = await off.get_rate_limits(batch)
                for x, y, r in zip(a, b, batch):
                    _assert_same(x, y, (step, r))
            rec = on.tracer.recorder
            assert rec.recorded > 0, "fuzz never recorded a trace"
        finally:
            await on.stop()
            await off.stop()

    asyncio.run(run())
