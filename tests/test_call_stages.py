"""The gRPC door's call tiles, the span helper, the stage clock's
quantile buckets and the process probes (serve/stages.py, PR 24).

- a tiny daemon served over real gRPC records a GetRateLimits call's
  six CALL_TILES (all but peer_serve, the peer door's) with
  call_coverage >= 0.8 and NO batch_queue/device for those calls; a GEB
  frame through the same daemon records no call_* stage (one test, a
  case a door), and both count their batches' padded slots;
- a sampled gRPC call's retained trace holds the new span names: the
  stage clock still feeds the tracer, no second clock;
- STAGES.span records the seconds the profiler annotation was open for
  (and makes none while no capture runs),
  shows in a /v1/debug/profile?python=0 capture's host plane, and works
  in a process where JAX cannot be imported;
- the bucket arithmetic: known durations land in the bucket whose edges
  bracket them, `n` samples count n times, reset() clears;
- the loop-lag and GC probes record through the same clock.
"""

import asyncio
import gc
import glob
import json
import os
import shutil
import subprocess
import sys
import time
import types
import urllib.error
import urllib.request

import pytest

from _util import free_ports
from gubernator_tpu.api.types import ChainLevel, RateLimitReq
from gubernator_tpu.client import V1Client
from gubernator_tpu.client_geb import GebClient
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core.store import StoreConfig
from gubernator_tpu.serve import metrics, stages
from gubernator_tpu.serve.backends import TpuBackend
from gubernator_tpu.serve.stages import (
    BUCKET_EDGES_S,
    CALL_TILES,
    STAGES,
    ProcessProbes,
    StageStats,
    bucket_of,
)

CALLS = 40
#: a GetRateLimits call's tiles: peer_serve is a GetPeerRateLimits
#: call's, in instance_route's place (tests/test_ring_owner6.py)
V1_TILES = tuple(t for t in CALL_TILES if t != "peer_serve")


@pytest.fixture()
def node():
    """One node on the device backend (CPU here) with every door."""
    grpc_port, http_port, geb_port = free_ports(3)
    cluster = LocalCluster(
        [f"127.0.0.1:{grpc_port}"],
        backend_factory=lambda: TpuBackend(
            StoreConfig(rows=16, slots=1 << 8), buckets=(16, 64)
        ),
        http_addresses=[f"127.0.0.1:{http_port}"],
        geb_ports=[geb_port],
        trace_sample=1.0,
    )
    cluster.start()
    try:
        yield types.SimpleNamespace(
            cluster=cluster,
            grpc=f"127.0.0.1:{grpc_port}",
            http=f"127.0.0.1:{http_port}",
            geb=f"127.0.0.1:{geb_port}",
        )
    finally:
        cluster.stop()


def _reqs(tag, i, n=2):
    return [
        RateLimitReq(name="calls", unique_key=f"{tag}-{i}-{j}", hits=1,
                     limit=100, duration=60_000)
        for j in range(n)
    ]


def _drive_grpc(node):
    with V1Client(node.grpc) as client:
        for i in range(CALLS):
            resps = client.get_rate_limits(_reqs("g", i), timeout=30)
            assert not any(r.error for r in resps)


def _drive_geb(node):
    with GebClient(node.geb) as client:
        for i in range(CALLS):
            resps = client.get_rate_limits(_reqs("f", i), timeout=30)
            assert not any(r.error for r in resps)


def _drive_http(node):
    for i in range(CALLS):
        body = {"requests": [
            {"name": "calls", "uniqueKey": f"h-{i}-{j}", "hits": 1,
             "limit": 100, "duration": 60_000} for j in range(2)
        ]}
        req = urllib.request.Request(
            f"http://{node.http}/v1/GetRateLimits",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            resps = json.loads(r.read())["responses"]
        assert len(resps) == 2 and not any(r.get("error") for r in resps)


def _drive_grpc_two_lanes(node):
    """Every call rides the chain lane AND the plain lane: one call,
    two batcher groups that overlap in time."""
    with V1Client(node.grpc) as client:
        for i in range(CALLS):
            reqs = _reqs("m", i, n=1) + [RateLimitReq(
                name="calls", unique_key=f"leaf-{i}", hits=1, limit=100,
                duration=60_000,
                chain=[ChainLevel(f"org-{i}", 1000, 0)],
            )]
            resps = client.get_rate_limits(reqs, timeout=30)
            assert not any(r.error for r in resps)


def _counter(c, suffix: str = "_total") -> float:
    return sum(s.value for m in c.collect() for s in m.samples
               if s.name.endswith(suffix))


@pytest.mark.parametrize("door", ["grpc", "grpc_two_lanes", "geb", "http"])
def test_each_door_records_its_own_family(node, door):
    slots0 = _counter(metrics.DEVICE_BATCH_SLOTS)
    batches0 = _counter(metrics.DEVICE_BATCH_SIZE, "_count")
    STAGES.reset()
    {"grpc": _drive_grpc, "grpc_two_lanes": _drive_grpc_two_lanes,
     "geb": _drive_geb, "http": _drive_http}[door](node)
    snap = STAGES.snapshot()
    seen = snap["stages"]
    call_family = set(snap["per_call_stages"]) - {"instance_route"}
    if door.startswith("grpc"):
        # one set of tiles a call, however many lanes its items ride
        for tile in V1_TILES + ("call_e2e",):
            assert seen[tile]["count"] == CALLS, (tile, seen.get(tile))
        assert "peer_serve" not in seen
        assert snap["calls"] == CALLS
        assert 0.8 <= snap["call_coverage"] <= 1.0, snap["call_coverage"]
        # the r7 contract: per-frame stages are frames' alone
        assert not {"batch_queue", "device"} & set(seen)
        assert snap["frames"] == 0
        # the submit thread's interior, one level down
        assert seen["jit_call"]["count"] == seen["dispatch"]["count"] > 0
        assert seen["jit_call"]["total_s"] <= seen["dispatch"]["total_s"]
    else:
        # a frame keeps its own family; the JSON door has no call_e2e,
        # so its groups record no tile that would lack a denominator
        frames = CALLS if door == "geb" else 0
        assert seen.get("batch_queue", {}).get("count", 0) == frames
        assert seen.get("device", {}).get("count", 0) == frames
        assert not call_family & set(seen), sorted(seen)
        assert snap["calls"] == 0 and snap["call_coverage"] == 0.0
    for name, s in seen.items():
        assert sum(s["buckets"]) == s["count"], name
    # every batch counted its rung: slots are whole rungs, and no
    # fewer than the rows they carried
    batches = _counter(metrics.DEVICE_BATCH_SIZE, "_count") - batches0
    slots = _counter(metrics.DEVICE_BATCH_SLOTS) - slots0
    assert batches >= 1 and slots % 16 == 0
    assert 16 * batches <= slots <= 64 * batches
    assert slots >= 2 * CALLS


def test_sampled_grpc_call_trace_holds_the_call_spans(node):
    recorder = node.cluster.instance_at(0).tracer.recorder
    recorder.reset()
    with V1Client(node.grpc) as client:
        client.get_rate_limits(_reqs("t", 0), timeout=30)
    (trace,) = recorder.snapshot()["traces"]
    names = [s["name"] for s in trace["spans"]]
    for name in V1_TILES:
        assert names.count(name) == 1, (name, names)
    # one name a span: the stage clock's, not the frame family's
    assert not {"batch_queue", "device"} & set(names)
    # call_e2e is recorded after the scope closed: the trace's own
    # duration is that number
    assert "call_e2e" not in names


def _http(node, path):
    with urllib.request.urlopen(f"http://{node.http}{path}", timeout=120) as r:
        return json.loads(r.read())


def test_profile_without_the_python_tracer_shows_the_stage_spans(node):
    import jax

    with pytest.raises(urllib.error.HTTPError) as bad:
        _http(node, "/v1/debug/profile?ms=10&python=2")
    assert bad.value.code == 400
    name = f"test_call_stages_{os.getpid()}"
    done = {}

    def capture():
        done.update(_http(
            node, f"/v1/debug/profile?ms=600&python=0&name={name}"))

    import threading

    t = threading.Thread(target=capture)
    t.start()
    deadline = time.monotonic() + 120
    with V1Client(node.grpc) as client:
        i = 0
        while t.is_alive() and time.monotonic() < deadline:
            # calls are answered while the capture runs and stops
            client.get_rate_limits(_reqs("p", i), timeout=30)
            i += 1
    t.join(5)
    assert not t.is_alive() and done["python"] == 0 and i > 0
    assert done["captured_ms"] == 600
    files = glob.glob(os.path.join(done["trace_dir"], "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    data = jax.profiler.ProfileData.from_file(files[0])
    shutil.rmtree(done["trace_dir"], ignore_errors=True)
    events = {
        e.name
        for plane in data.planes if plane.name.startswith("/host")
        for line in plane.lines for e in line.events
    }
    wanted = {"prep", "merge", "dispatch", "jit_call", "fetch_wait"}
    assert wanted <= events, sorted(wanted - events)
    # no thread owns these, or they are bare stamps on the serving
    # loop: stage clock only
    assert not set(stages.PER_CALL) & events


class _FakeAnnotation:
    seen = []
    recording = False

    @classmethod
    def is_enabled(cls):
        return cls.recording

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0 = time.monotonic()

    def __exit__(self, *exc):
        _FakeAnnotation.seen.append((self.name, time.monotonic() - self.t0))


def test_span_records_the_seconds_the_annotation_was_open(monkeypatch):
    monkeypatch.setitem(
        sys.modules, "jax.profiler",
        types.SimpleNamespace(TraceAnnotation=_FakeAnnotation),
    )
    monkeypatch.setattr(_FakeAnnotation, "seen", [])
    stats = StageStats()
    _FakeAnnotation.recording = False
    with stats.span("prep"):  # no capture runs: no annotation is made
        pass
    assert _FakeAnnotation.seen == []
    _FakeAnnotation.recording = True
    with stats.span("dispatch"):
        time.sleep(0.02)
    with pytest.raises(KeyError):
        with stats.span("merge"):
            raise KeyError("the body's fault is the body's")
    (name, open_s), (name2, _) = _FakeAnnotation.seen
    assert (name, name2) == ("dispatch", "merge")
    got = stats.snapshot()["stages"]
    assert got["merge"]["count"] == got["prep"]["count"] == 1
    recorded = got["dispatch"]["total_s"]
    # inside the annotation, and the same span to within the two
    # clock reads that separate them
    assert 0.02 <= recorded <= open_s < recorded + 0.002


def test_span_works_where_jax_cannot_be_imported():
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from gubernator_tpu.serve.stages import StageStats\n"
        "s = StageStats()\n"
        "with s.span('prep'):\n"
        "    pass\n"
        "try:\n"
        "    import jax\n"
        "    raise SystemExit('jax imported')\n"
        "except ImportError:\n"
        "    pass\n"
        "assert 'jax.profiler' not in sys.modules\n"
        "print(s.snapshot()['stages']['prep']['count'])\n"
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == "1"


@pytest.mark.parametrize("seconds,lo,hi", [
    (0.0, 0.0, 1e-6),
    (0.5e-6, 0.0, 1e-6),
    (1.2e-6, 1e-6, 1.4142135623730951e-6),
    (181e-6, 181.01933598375618e-6 / 1.4142135623730951, 181.01933598375618e-6),
    (16e-3, 11.585237502960396e-3, 16.384e-3),
    (0.150, 0.131072, 0.18536380004736634),
    (1e4, BUCKET_EDGES_S[-1], float("inf")),
])
def test_a_duration_lands_in_the_bucket_that_brackets_it(seconds, lo, hi):
    edges = (0.0,) + BUCKET_EDGES_S + (float("inf"),)
    b = bucket_of(seconds)
    assert edges[b] == pytest.approx(lo) and edges[b + 1] == pytest.approx(hi)
    assert edges[b] <= seconds < edges[b + 1]
    stats = StageStats()
    stats.add("x", seconds)
    buckets = stats.snapshot()["stages"]["x"]["buckets"]
    assert len(buckets) == len(BUCKET_EDGES_S) + 1
    assert buckets[b] == 1 and sum(buckets) == 1


def test_buckets_count_samples_and_reset_clears_them():
    stats = StageStats()
    for _ in range(90):
        stats.add("call_e2e", 0.012)
    stats.add("call_e2e", 10 * 0.150, n=10)  # ten samples of 150 ms
    snap = stats.snapshot()
    assert snap["bucket_edges_s"] == list(BUCKET_EDGES_S)
    got = snap["stages"]["call_e2e"]
    assert got["count"] == 100 and sum(got["buckets"]) == 100
    assert got["buckets"][bucket_of(0.012)] == 90
    assert got["buckets"][bucket_of(0.150)] == 10
    # the median's bucket brackets 12 ms, the p99's 150 ms
    running, at = 0, {}
    for b, n in enumerate(got["buckets"]):
        running += n
        for q in (50, 99):
            if q not in at and running >= q:
                at[q] = b
    assert at == {50: bucket_of(0.012), 99: bucket_of(0.150)}
    stats.add("call_e2e", -1.0)  # a stamp from the future records nothing
    assert stats.snapshot()["stages"]["call_e2e"]["count"] == 100
    stats.reset()
    assert stats.snapshot()["stages"] == {}


def test_threads_record_into_their_own_tables_and_lose_nothing():
    """No lock on the recording path: every thread owns its table, and
    snapshot() sums them — while they are written, too."""
    import threading

    stats = StageStats()
    threads, per_thread = 2 * (os.cpu_count() or 4), 20_000
    go = threading.Event()

    def record():
        go.wait(10)
        for i in range(per_thread):
            stats.add("call_queue", 0.004)
            stats.add("jit_call", 0.006, n=2)

    workers = [threading.Thread(target=record) for _ in range(threads)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in workers:
            t.start()
        go.set()
        deadline = time.monotonic() + 60
        while any(t.is_alive() for t in workers):
            assert time.monotonic() < deadline
            live = stats.snapshot()["stages"]  # a read in mid-write
            for s in live.values():
                assert s["count"] <= 2 * threads * per_thread
        for t in workers:
            t.join(10)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    got = stats.snapshot()["stages"]
    assert got["call_queue"]["count"] == threads * per_thread
    assert got["jit_call"]["count"] == 2 * threads * per_thread
    assert got["jit_call"]["buckets"][bucket_of(0.003)] == got["jit_call"]["count"]
    assert got["call_queue"]["total_s"] == pytest.approx(
        0.004 * threads * per_thread)
    stats.reset()  # clears every thread's table, from another thread
    assert stats.snapshot()["stages"] == {}


def test_process_probes_record_loop_lag_and_gc_pauses():
    stats = StageStats()

    async def run():
        probes = ProcessProbes(stats)
        probes.start()
        await asyncio.sleep(3.2 * probes.TICK_S)
        time.sleep(0.08 + probes.TICK_S)  # no timer can fire meanwhile
        gc.collect()
        await asyncio.sleep(2.2 * probes.TICK_S)
        probes.stop()
        assert probes._on_gc not in gc.callbacks

    asyncio.run(run())
    got = stats.snapshot()["stages"]
    assert set(got) == set(stages.PER_PROCESS)
    late = sum(got["loop_lag"]["buckets"][bucket_of(0.05):])
    assert late == 1, got["loop_lag"]
    assert got["loop_lag"]["count"] >= 5
    assert got["gc_pause"]["count"] >= 1
    assert got["gc_pause"]["total_s"] < 5.0


def test_the_daemon_settles_the_collector_before_ready():
    """In a child, so this process keeps its own collector: what the
    boot built is frozen, the young thresholds stay CPython's, and a
    full collection waits for a thousand middle ones."""
    code = (
        "import gc\n"
        "from gubernator_tpu.serve import server\n"
        "young = gc.get_threshold()[:2]\n"
        "keep = [[] for _ in range(1000)]\n"
        "server.settle_collector()\n"
        "assert gc.get_freeze_count() >= 1000\n"
        "assert gc.get_threshold() == (*young, server.FULL_COLLECTION_EVERY)\n"
        "assert server.FULL_COLLECTION_EVERY == 1000\n"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode == 0, p.stderr[-2000:]
