"""The traffic observers' native fold (PR 40): one call of
`libguberhash.so guber_traffic_fold` a batch — key hashes and the keys'
bytes in, GIL released — against `core/sketches.py`'s Python classes
(`SpaceSaving.observe` + `observe_weighted`, `HyperLogLog.add_hashes`),
which stay as its fallback and are the oracle here.

- (a) parity: over seeds, batch sizes and streams (zipf 1.2, floods of
  distinct keys, non-ASCII names of equal counts that exercise the
  tie-break), after EVERY batch the native summary's {key: (count, err)}
  and total equal SpaceSaving's and the HLL registers are byte-equal,
  whichever form the keys came in (the parser's NUL-joined bytes, a
  list of str, a list of str one of which holds a NUL);
- (c) a folded string frame served through EdgeBridge leaves
  /v1/debug/stats' picture equal to the same items sent down the object
  path, and to the Python classes', and `native_folds` grows by one a
  frame;
- (d) with the symbol absent the Python path serves and
  `traffic_python_folds_total` grows in /metrics, where with it
  `traffic_native_folds_total` does; the boot log names which;
- (e) `snapshot()` from another thread while folds run (the call gives
  the GIL up: the lock is held across it) is one moment's summary.

(b) — the three summary tests of tests/test_sketches.py on either
implementation — lives there.

libguberhash.so is git-ignored: tests/conftest.py builds it before
collection, and its `native` fixture skips where it is absent.
"""

import asyncio
import logging
import sys
import threading
import urllib.request

import numpy as np
import pytest

from _util import free_ports
from gubernator_tpu.core import hashing, sketches
from gubernator_tpu.core.sketches import (
    NativeHotKeys,
    SpaceSaving,
    TrafficStats,
)
from test_edge_bridge import _seeded_mixed_frame


def _tracked(hot):
    return {k: (c, e) for k, c, e in hot.top(hot.capacity)}


def _same(a: TrafficStats, b: TrafficStats):
    assert a.implementation == "native" and b.implementation == "python"
    assert isinstance(a.hot, NativeHotKeys) and isinstance(b.hot, SpaceSaving)
    assert _tracked(a.hot) == _tracked(b.hot)
    assert a.hot.total == b.hot.total
    assert len(_tracked(a.hot)) <= a.hot.capacity
    assert a.hll._reg.tobytes() == b.hll._reg.tobytes()
    assert a.hll.estimate() == b.hll.estimate()


# -- (a) parity ----------------------------------------------------------------

# names whose order by code point is their order as UTF-8 bytes, and
# neither their order by length nor as UTF-16: the heap of (count, key)
# tuples breaks ties of equal counts by exactly this
_ODD = [
    "a", "ab", "b", "z", "~", "\x7f", "é", "ÿ", "Ā", "߿", "ࠀ", "日本",
    "퟿", "", "￿", "\U00010000", "\U0001f600", "\U0010ffff",
    " ", "_", "__", "a_", "",
]


def _stream(kind: str, rng, n: int, batch_no: int):
    if kind == "zipf":
        return [f"bench_acct:{i}" for i in rng.zipf(1.2, n) % 10_000_000]
    if kind == "flood":
        # every key new, every count equal: the cascade evicts by key
        return [f"flood_{batch_no}:{i}" for i in rng.permutation(n)]
    assert kind == "odd"
    # a few dozen names around a small summary, counts mostly tied
    return [
        f"{_ODD[i]}_{_ODD[j]}"
        for i, j in rng.integers(0, len(_ODD), (n, 2))
    ]


@pytest.mark.parametrize("n", [1, 2, 16, 17, 500, 1000])
@pytest.mark.parametrize("kind", ["zipf", "flood", "odd"])
@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_native_fold_equals_python_classes(native, seed, kind, n):
    rng = np.random.default_rng(seed)
    # a summary small enough that every batch size reaches the cascade
    capacity = 256 if n >= 500 else 24
    a = TrafficStats(top_capacity=capacity)
    b = TrafficStats(top_capacity=capacity, native=False)
    for batch_no in range(40 if n >= 500 else 120):
        keys = _stream(kind, rng, n, batch_no)
        hashes = hashing.slot_hash_batch(keys)
        form = batch_no % 3
        if form == 0:  # as the GEB door's native parse hands them on
            a.observe(keys, hashes, "\x00".join(keys).encode())
        elif form == 1:  # as the object path does
            a.observe(keys, hashes)
        else:  # a key with a NUL in it: cut by offsets, not by NUL
            keys[-1] = keys[-1] + "\x00" + keys[0]
            hashes = hashing.slot_hash_batch(keys)
            a.observe(keys, hashes)
        b.observe(keys, hashes)
        _same(a, b)
    assert a.native_folds == b.python_folds == batch_no + 1
    assert a.python_folds == b.native_folds == 0
    a.hot.reset()
    a.hll.reset()
    assert a.snapshot() == {
        "distinct_keys_estimate": 0, "observed_total": 0, "hot_keys": [],
    }
    a.observe(["x_1"], hashing.slot_hash_batch(["x_1"]))
    assert _tracked(a.hot) == {"x_1": (1, 0)}


@pytest.mark.parametrize("n", [1, 16, 17, 1000])
def test_observe_hashes_registers_equal(native, n):
    """A pre-hashed frame's observation: the registers only, the
    summary untouched."""
    rng = np.random.default_rng(n)
    a, b = TrafficStats(hll_p=12), TrafficStats(hll_p=12, native=False)
    for _ in range(20):
        h = rng.integers(0, 2**64, n, dtype=np.uint64)
        # the rare registers: a hash whose low bits are all zero
        h[0] &= np.uint64(0xFFF0_0000_0000_0000)
        a.observe_hashes(h)
        # a view, as the fast frames' structured records give
        b.observe_hashes(h[::1])
        assert a.hll._reg.tobytes() == b.hll._reg.tobytes()
    a.observe_hashes(np.empty(0, np.uint64))
    assert a.native_folds == b.python_folds == 20
    assert a.hot.total == 0 and a.hot.top(5) == []


def test_joined_keys_must_be_as_many_as_the_hashes(native):
    ts = TrafficStats()
    h = hashing.slot_hash_batch(["a_1", "b_2", "c_3"])
    for packed in (b"a_1\x00b_2", b"a_1\x00b_2\x00c_3\x00d_4", b""):
        with pytest.raises(ValueError):
            ts.observe(["a_1", "b_2", "c_3"], h, packed)
    assert ts.hot.total == 0 and not ts.hll._reg.any()
    with pytest.raises(ValueError):
        ts.observe(["a_1", "b_2"], h)
    ts.observe(["a_1", "b_2", "c_3"], h, b"a_1\x00b_2\x00c_3")
    assert ts.hot.total == 3
    with pytest.raises(ValueError):
        TrafficStats(top_capacity=0)


# -- (c) one picture whatever the door ----------------------------------------


def _serve_frames(monkeypatch, frames, fold: bool):
    """A fresh one-node Instance on a standing clock serves `frames`
    through EdgeBridge: folded, or (`fold` False) by the door's object
    path, as request objects through Instance.get_rate_limits. Returns
    its TrafficStats."""
    from gubernator_tpu.api.types import PeerInfo
    from gubernator_tpu.core.store import StoreConfig
    from gubernator_tpu.serve.backends import TpuBackend
    from gubernator_tpu.serve.config import ServerConfig
    from gubernator_tpu.serve.edge_bridge import EdgeBridge
    from gubernator_tpu.serve.instance import Instance

    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    def clock():
        return 1_700_000_000_000

    monkeypatch.setattr(types_mod, "millisecond_now", clock)
    monkeypatch.setattr(engine_mod, "millisecond_now", clock)
    addr = "127.0.0.1:9985"

    async def serve():
        conf = ServerConfig(
            grpc_address=addr, advertise_address=addr, shed_cache=True
        )
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
        bridge = EdgeBridge(inst, "")
        decide = (
            bridge._decide_string_frame if fold else bridge._decide_string
        )
        try:
            seen = []
            for payload, n in frames:
                await decide(payload, n)
                seen.append(
                    (inst.traffic.native_folds, inst.traffic.python_folds)
                )
            return inst.traffic, seen
        finally:
            await inst.stop()

    return asyncio.run(serve())


def _picture(ts: TrafficStats):
    snap = ts.snapshot(ts.hot.capacity)
    # order among equal counts is free
    snap["hot_keys"] = sorted(
        snap["hot_keys"], key=lambda k: (-k["count"], k["key"])
    )
    return snap


def test_folded_frame_leaves_the_object_paths_stats(native, monkeypatch):
    frames = [_seeded_mixed_frame(s) for s in (41, 2041, 4041)]
    folded, seen = _serve_frames(monkeypatch, frames, True)
    assert folded.implementation == "native"
    assert seen == [(1, 0), (2, 0), (3, 0)]  # one native fold a frame
    objects, seen = _serve_frames(monkeypatch, frames, False)
    assert objects.implementation == "native"
    assert seen == [(1, 0), (2, 0), (3, 0)]
    want = _picture(folded)
    assert want["observed_total"] == 3000
    assert len(want["hot_keys"]) == 256  # 401 key ids: the cascade ran
    assert want["hot_keys"][0]["key"] == "mixed_k1"
    assert _picture(objects) == want
    assert folded.hll._reg.tobytes() == objects.hll._reg.tobytes()
    # and both are the Python classes' picture of the same frames
    # (the observers alone without the library: the door parses and
    # hashes natively still, so the hashes folded are the same)
    monkeypatch.setattr(sketches, "_fold_lib", lambda: None)
    plain, seen = _serve_frames(monkeypatch, frames, True)
    assert plain.implementation == "python"
    assert seen == [(0, 1), (0, 2), (0, 3)]
    assert _picture(plain) == want
    assert plain.hll._reg.tobytes() == folded.hll._reg.tobytes()


# -- (d) the counters and the boot log, library present and absent ---------


def _folds(http_port) -> dict:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{http_port}/metrics", timeout=10
    ) as r:
        text = r.read().decode()
    return {
        line.split()[0]: float(line.split()[1])
        for line in text.splitlines()
        if line.startswith("traffic_")
    }


@pytest.mark.parametrize("present", [True, False])
def test_counters_and_boot_log_by_implementation(
    native, monkeypatch, caplog, present
):
    from gubernator_tpu.api.types import RateLimitReq
    from gubernator_tpu.client import V1Client
    from gubernator_tpu.cluster import LocalCluster

    if not present:
        # the one handle says absent: to the whole process
        monkeypatch.setattr(hashing, "_native", None)
        monkeypatch.setattr(hashing, "_native_checked", True)
    grpc_port, http = free_ports(2)
    addr = f"127.0.0.1:{grpc_port}"
    cluster = LocalCluster([addr], http_addresses=[f"127.0.0.1:{http}"])
    with caplog.at_level(logging.INFO, logger="gubernator_tpu"):
        cluster.start()
    try:
        before = _folds(http)
        assert set(before) == {
            "traffic_native_folds_total", "traffic_python_folds_total"
        }
        with V1Client(addr) as client:
            for i in range(7):
                resps = client.get_rate_limits(
                    [
                        RateLimitReq(
                            name="t", unique_key=f"k{i % 3}", hits=1,
                            limit=100, duration=60_000,
                        ),
                        RateLimitReq(
                            name="t", unique_key="hot", hits=1,
                            limit=100, duration=60_000,
                        ),
                    ],
                    timeout=10,
                )
                assert not resps[0].error and not resps[1].error
        after = _folds(http)
        grown = {k: after[k] - before[k] for k in after}
        mine, other = (
            ("traffic_native_folds_total", "traffic_python_folds_total")
            if present else
            ("traffic_python_folds_total", "traffic_native_folds_total")
        )
        assert grown == {mine: 7.0, other: 0.0}
        with urllib.request.urlopen(
            f"http://127.0.0.1:{http}/v1/debug/stats", timeout=10
        ) as r:
            import json

            stats = json.loads(r.read())
        assert stats["observed_total"] == 14
        assert stats["hot_keys"][0] == {
            "key": "t_hot", "count": 7, "max_overestimate": 0
        }
        assert {k["key"] for k in stats["hot_keys"]} == {
            "t_hot", "t_k0", "t_k1", "t_k2"
        }
        assert 3 <= stats["distinct_keys_estimate"] <= 5
    finally:
        cluster.stop()
    lines = [
        r.getMessage() for r in caplog.records
        if r.getMessage().startswith("traffic observers:")
    ]
    assert len(lines) == 1
    assert ("one native fold a batch" in lines[0]) is present
    assert ("Python SpaceSaving" in lines[0]) is not present


# -- (e) a scrape beside the folds ---------------------------------------------


def test_snapshot_beside_running_folds_is_one_moment(native):
    """Every key fits the summary, so in any ONE moment the counts sum
    to the items observed; a snapshot that read the two at different
    moments (the fold gives the GIL up mid-batch) would not."""
    ts = TrafficStats()
    rng = np.random.default_rng(5)
    batches = []
    for _ in range(30):
        keys = [f"k_{i}" for i in rng.integers(0, 200, 1000)]
        batches.append(
            (keys, hashing.slot_hash_batch(keys), "\x00".join(keys).encode())
        )
    stop = threading.Event()
    snaps, errors = [], []

    def scrape():
        try:
            while not stop.is_set():
                snaps.append(ts.snapshot(256))
        except Exception as e:  # surfaced by the assert below
            errors.append(e)

    t = threading.Thread(target=scrape)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # the threads change places often
    t.start()
    rounds = 0
    try:
        # ten rounds, and on a host whose other work starves the scrape
        # thread more, until it has looked beside the folds
        while rounds < 10 or (len(snaps) < 3 and rounds < 500):
            rounds += 1
            for keys, hashes, packed in batches:
                ts.observe(keys, hashes, packed)
    finally:
        stop.set()
        t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not t.is_alive()
    snaps.append(ts.snapshot(256))
    assert not errors
    assert snaps[-1]["observed_total"] == rounds * 30_000
    assert len(snaps) > 2
    totals = [s["observed_total"] for s in snaps]
    assert totals == sorted(totals)
    for s in snaps:
        assert s["observed_total"] % 1000 == 0
        assert sum(k["count"] for k in s["hot_keys"]) == s["observed_total"]
        assert all(k["max_overestimate"] == 0 for k in s["hot_keys"])
        assert len(s["hot_keys"]) <= 200
