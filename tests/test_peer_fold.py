"""The PeersV1 door's wire fold (PR 35): a forwarded batch served as
arrays — wire bytes -> columns -> decide_arrays -> wire bytes — against
the object path it replaces.

- (a) differential: seeded batches (token and leaky, duplicates inside
  a batch, hits 0 / 1 / 2, keys driven over their limit, GLOBAL items,
  lengths 1, 13 and 1000) through the folded door of one node and the
  object path of its twin give equal reply BYTES, equal answers item by
  item (and the plain reference's), equal shed-cache contents and equal
  queued GLOBAL updates;
- (b) wire: the native parser against `GetPeerRateLimitsReq.FromString`
  on random hand-encoded messages (negative int64 as 10-byte varints,
  fields out of order, absent, sent twice, empty strings, padded
  varints), one case per reason it declines, and the native encoder's
  bytes against the runtime's;
- (c) every stage a peer call tiles into and the four `peer_serve_*`
  counters advance on a folded call; `peer_serve_folded_items_total`
  stays put on a declined one, which is still answered.

libguberhash.so is git-ignored: tests/conftest.py builds it before
collection, and its `native` fixture skips where it is absent. The
clock stands still, as in tests/test_ring_owner6.py.
"""

import asyncio
import os
import random
import sys

import grpc
import numpy as np
import pytest

from _util import free_ports
from gubernator_tpu.api.columns import PeerAnswers, PeerBatch
from gubernator_tpu.api.grpc_glue import PEERS_SERVICE
from gubernator_tpu.api.proto.gen import gubernator_pb2, peers_pb2
from gubernator_tpu.api.types import Status
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core import hashing, oracle
from gubernator_tpu.serve.config import config_from_env
from gubernator_tpu.serve.metrics import REGISTRY
from gubernator_tpu.serve.server import make_backend
from gubernator_tpu.serve.stages import CALL_TILES, STAGES
from test_exact100m_served import CLASSES  # the traffic's limit classes
from test_global_mesh4_served import FakeClock, T0

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark")
)
import reference_ring  # noqa: E402  (the configuration's plain reference)

ENV = {"GUBER_BACKEND": "tpu", "GUBER_STORE_TARGET_KEYS": "20000"}
COUNTERS = ("peer_serve_batches_total", "peer_serve_items_total",
            "peer_serve_shed_hits_total", "peer_serve_folded_items_total")
METHOD = f"/{PEERS_SERVICE}/GetPeerRateLimits"
U64 = (1 << 64) - 1


# -- hand encoding: what the runtime's own serialiser never sends -----------


def varint(v: int, pad: int = 0) -> bytes:
    """`pad` more bytes than the value needs (0x80 continuation bytes
    that carry nothing): legal, and no serialiser sends it."""
    v &= U64
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    for _ in range(min(pad, 10 - len(out))):
        out[-1] |= 0x80
        out.append(0)
    return bytes(out)


def field(num: int, value, pad: int = 0) -> bytes:
    if isinstance(value, bytes):
        return bytes([num << 3 | 2]) + varint(len(value)) + value
    return bytes([num << 3]) + varint(value, pad)


def item_bytes(fields) -> bytes:
    """One `requests = 1` entry holding `fields` [(number, value)] in
    the order given."""
    body = b"".join(field(n, v) for n, v in fields)
    return field(1, body)


def plain_item(name="n", key="k", hits=1, limit=10, duration=60_000,
               algo=0, behavior=0) -> bytes:
    return item_bytes([(1, name.encode()), (2, key.encode()), (3, hits),
                       (4, limit), (5, duration), (6, algo), (7, behavior)])


INTS = (0, 1, 2, -1, 127, 128, 2**31, -(2**31) - 1, 2**63 - 1, -(2**63))
NAMES = ("", "n", "requests_per_second", "ключ", "a_b", "\U0001F511k", "x" * 40)


def random_message(rng: random.Random, n: int) -> bytes:
    out = []
    for _ in range(n):
        fields = []
        for num in rng.sample(range(1, 8), rng.randrange(0, 8)):
            for _twice in range(1 + (rng.random() < 0.15)):
                if num <= 2:
                    fields.append((num, rng.choice(NAMES).encode()))
                elif num <= 5:
                    v = rng.choice(INTS) if rng.random() < 0.5 else (
                        rng.randrange(-(2**63), 2**63))
                    fields.append((num, v))
                else:
                    fields.append((num, rng.randrange(4 if num == 6 else 3)))
        rng.shuffle(fields)
        body = b"".join(
            field(num, v, pad=rng.choice((0, 0, 0, 1, 3))) for num, v in fields)
        out.append(field(1, body))
    return b"".join(out)


# -- (b) the wire ------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
def test_parser_reads_what_the_runtime_reads(native, seed):
    rng = random.Random(3500 + seed)
    wire = random_message(rng, rng.choice((1, 13, 200, 1000)))
    want = peers_pb2.GetPeerRateLimitsReq.FromString(wire).requests
    n, cols = native.parse_peer_batch(wire, 1000)
    assert n == len(want)
    for name, attr in (("hits", "hits"), ("limit", "limit"),
                       ("duration", "duration"), ("algo", "algorithm"),
                       ("behavior", "behavior")):
        assert cols[name].tolist() == [getattr(p, attr) for p in want], name
    keys = [p.name + "_" + p.unique_key for p in want]
    assert cols["key_hash"].tolist() == native.hash_batch(keys).tolist()
    for i in rng.sample(range(n), min(n, 50)):
        no, nl = cols["name_off"][i], cols["name_len"][i]
        ko, kl = cols["key_off"][i], cols["key_len"][i]
        assert wire[no:no + nl].decode() == want[i].name
        assert wire[ko:ko + kl].decode() == want[i].unique_key
    # the door's form of it
    batch = PeerBatch.from_wire(wire, 1000)
    assert len(batch) == n and set(batch.fields) == {
        "key_hash", "hits", "limit", "duration", "algo"}
    keys_of, glob = batch.global_items()
    assert [g[0] for g in glob] == [
        i for i, p in enumerate(want) if p.behavior == 2]
    assert all(keys_of[i] == keys[i] and (name, ukey) == (
        want[i].name, want[i].unique_key) for i, name, ukey in glob)


def test_parser_agrees_with_the_runtimes_own_serialiser(native):
    reqs = [gubernator_pb2.RateLimitReq(
        name="ring", unique_key=f"k{i}", hits=i % 3, limit=-i, duration=i * 1000,
        algorithm=i % 4, behavior=i % 3) for i in range(300)]
    wire = peers_pb2.GetPeerRateLimitsReq(requests=reqs).SerializeToString()
    n, cols = native.parse_peer_batch(wire, 1000)
    assert n == 300
    assert cols["limit"].tolist() == [-i for i in range(300)]
    assert cols["algo"].tolist() == [i % 4 for i in range(300)]


DECLINED = {
    "chain": plain_item() + item_bytes(
        [(1, b"n"), (2, b"k"), (8, field(1, b"tenant"))]),
    "chain_empty_level": item_bytes([(1, b"n"), (8, b"")]),
    "unknown_field": plain_item() + item_bytes([(1, b"n"), (9, 1)]),
    "unknown_field_outer": plain_item() + field(2, b"x"),
    "unknown_field_wire_type": item_bytes([(1, b"n"), (3, b"\x01")]),
    "unknown_field_string_as_varint": item_bytes([(1, 5)]),
    "unknown_field_two_byte_tag": field(1, b"\x8a\x00\x01n"),
    "bad_enum_algorithm": plain_item(algo=4),
    "bad_enum_behavior": plain_item(behavior=3),
    "bad_enum_negative": plain_item(algo=-1),
    "bad_utf8": item_bytes([(1, b"\xff")]),
    "bad_utf8_key_surrogate": item_bytes([(1, b"n"), (2, b"\xed\xa0\x80")]),
    "bad_utf8_overlong": item_bytes([(2, b"\xc0\xaf")]),
    "bad_utf8_cut_short": item_bytes([(2, b"ab\xe2\x82")]),
    "truncated": plain_item()[:-1],
    "truncated_length": plain_item() + b"\x0a\x7f" + b"n",
    "truncated_string": field(1, b"\x0a\x05ab"),
    "truncated_varint": field(1, b"\x18\x80"),
    "truncated_eleven_byte_varint": field(1, b"\x18" + b"\x80" * 10 + b"\x01"),
    "too_many_items": plain_item() * 1001,
}


@pytest.mark.parametrize("case", sorted(DECLINED))
def test_parser_declines(native, case):
    wire = DECLINED[case]
    code, cols = native.parse_peer_batch(wire, 1000)
    assert cols is None and case.startswith(native.PEER_DECLINE[code])
    assert PeerBatch.from_wire(wire, 1000) is None
    # whatever it declines the runtime refuses too, or reads as what
    # the object path treats on its own
    try:
        parsed = peers_pb2.GetPeerRateLimitsReq.FromString(wire)
    except Exception:
        assert case.split("_")[0] in ("bad", "truncated") and "enum" not in case
    else:
        assert case.split("_")[0] in ("chain", "unknown", "too") or "enum" in case
        assert len(parsed.requests) >= 1


def test_an_empty_message_and_a_missing_library_decline(native, monkeypatch):
    assert native.parse_peer_batch(b"", 1000)[0] == 0
    assert PeerBatch.from_wire(b"", 1000) is None
    assert len(PeerBatch.from_wire(plain_item(), 1000)) == 1
    assert len(PeerBatch.from_wire(plain_item() * 1000, 1000)) == 1000
    monkeypatch.setattr(hashing, "_native", None)
    assert PeerBatch.from_wire(plain_item(), 1000) is None


@pytest.mark.parametrize("seed", range(3))
def test_encoder_writes_what_the_runtime_writes(native, seed):
    rng = np.random.default_rng(3500 + seed)
    n = (1, 13, 1000)[seed]
    cols = [rng.choice(INTS, n) if k else rng.integers(0, 2, n)
            for k in range(4)]
    cols[1][::3] = 0  # zero fields are left out
    cols[3] = np.where(cols[3] == 0, T0, cols[3])
    wire = native.encode_peer_answers(*cols)
    got = peers_pb2.GetPeerRateLimitsResp.FromString(wire).rate_limits
    assert [(r.status, r.limit, r.remaining, r.reset_time) for r in got] == list(
        zip(*(c.tolist() for c in cols)))
    assert all(not r.error and not r.metadata for r in got)
    same = peers_pb2.GetPeerRateLimitsResp(rate_limits=[
        gubernator_pb2.RateLimitResp(
            status=s, limit=li, remaining=r, reset_time=t)
        for s, li, r, t in zip(*(c.tolist() for c in cols))])
    assert wire == same.SerializeToString()
    # narrower columns, as a device batch answers in
    assert native.encode_peer_answers(
        cols[0].astype(np.int32), *cols[1:]) == wire


def test_answer_rows_read_like_responses_and_write_through(native):
    status = np.array([1, 0, 1], np.int32)
    status.setflags(write=False)  # a device batch's columns may be
    a = PeerAnswers(status, np.array([5, 6, 7]), np.array([0, 2, 0]),
                    np.array([T0, T0 + 1, 0]))
    assert len(a) == 3 and a[2].limit == 7 and a[-1].limit == 7
    assert [(r.status, r.limit, r.remaining, r.reset_time, r.error, r.metadata)
            for r in a] == [(Status.OVER_LIMIT, 5, 0, T0, "", {}),
                            (Status.UNDER_LIMIT, 6, 2, T0 + 1, "", {}),
                            (Status.OVER_LIMIT, 7, 0, 0, "", {})]
    for r in a:  # what benchmark/tests/faulty_peer_door.py does
        r.status = Status.UNDER_LIMIT
    a[1].remaining = 9
    got = peers_pb2.GetPeerRateLimitsResp.FromString(a.to_wire()).rate_limits
    assert [(r.status, r.remaining) for r in got] == [(0, 0), (0, 9), (0, 0)]
    failed = PeerAnswers.failed(2, "boom")
    assert [r.error for r in failed] == ["boom", "boom"]
    got = peers_pb2.GetPeerRateLimitsResp.FromString(failed.to_wire()).rate_limits
    assert [(r.error, r.limit) for r in got] == [("boom", 0)] * 2


# -- the served door ---------------------------------------------------------


def _loop(made=[]):
    if not made:
        made.append(asyncio.new_event_loop())
    return made[0]


def call_raw(addr: str, wires, together: bool = False):
    """Reply bytes for each request's bytes, one keep-alive channel; a
    round's calls in flight together where `together`."""

    async def run():
        channel = grpc.aio.insecure_channel(addr)
        await channel.channel_ready()
        call = channel.unary_unary(METHOD)  # bytes out, bytes in
        try:
            if together:
                return list(await asyncio.gather(
                    *[call(w, timeout=120) for w in wires]))
            return [await call(w, timeout=120) for w in wires]
        finally:
            await channel.close()

    return _loop().run_until_complete(run())


def answers(reply: bytes):
    return [(r.status, r.limit, r.remaining, r.reset_time, r.error)
            for r in peers_pb2.GetPeerRateLimitsResp.FromString(reply).rate_limits]


@pytest.fixture(scope="module")
def clock():
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    fake = FakeClock()
    mp = pytest.MonkeyPatch()
    for mod in (types_mod, engine_mod, oracle):
        mp.setattr(mod, "millisecond_now", fake)
    yield fake
    mp.undo()


def _node(clock):
    conf = config_from_env(dict(ENV))
    backend = make_backend(conf)
    (port,) = free_ports(1)
    cluster = LocalCluster(
        [f"127.0.0.1:{port}"], backend_factory=lambda: backend,
        device_batch_limit=conf.device_batch_limit,
        global_sync_wait=3600.0,  # queued GLOBAL updates stay queued
    )
    cluster.start(timeout=600.0)
    inst = cluster.servers[0].instance
    assert inst.shed is not None
    inst.shed.now_fn = clock
    return cluster, f"127.0.0.1:{port}"


@pytest.fixture(scope="module")
def twins(native, clock):
    """Two fresh nodes: the first serves the fold, the second has it
    declined for every batch, so it serves request objects."""
    folded, addr_f = _node(clock)
    objects, addr_o = _node(clock)
    objects.servers[0].instance.fold_peer_batch = lambda wire: None
    try:
        yield (folded, addr_f), (objects, addr_o)
    finally:
        folded.stop()
        objects.stop()


def counters(cluster):
    cluster.servers[0]._refresh_store_metrics()
    return {c: REGISTRY.get_sample_value(c) for c in COUNTERS}


def stage_counts():
    snap = STAGES.snapshot()["stages"]
    return {name: (s["count"], s["total_s"]) for name, s in snap.items()}


def is_global(i: int) -> bool:
    return i % 7 == 3


def item(i: int, hits: int):
    """(key, hits, limit, duration, algo) as reference_ring takes it,
    by key id: limit class 70 / 20 / 10%, 25% leaky."""
    limit, duration = CLASSES[0 if i % 10 < 7 else 1 if i % 10 < 9 else 2]
    return (f"k{i}", hits, limit, duration, 1 if i % 4 == 3 else 0)


def item_wire(it, i: int) -> bytes:
    key, hits, limit, duration, algo = it
    return plain_item("fold", key, hits, limit, duration, algo,
                      2 if is_global(i) else 0)


def stream(seed: int):
    """[(items, wire)]: batches of 1, 13 and 1000; a key carries the
    same hits wherever it repeats inside a batch (benchmark/check.py
    checked_sequence: the program's rule for same-key items of one
    batch equals one-by-one service then)."""
    rng = random.Random(seed)
    hot = list(range(60))
    driven = [i for i in hot if i % 10 in (7, 8)][:8]  # 10-per-window ids
    fresh = 10_000
    out = []
    for size in (1, 13, 1000, 13, 1, 1000, 13, 13, 1000, 1, 13, 1000):
        hits_of, ids = {}, []
        for n in range(size - (size > 1)):
            if size == 1000 and n % 3 == 0:
                i, fresh = fresh, fresh + 1
            else:
                i = rng.choice(driven) if rng.random() < 0.4 else rng.choice(hot)
            hits_of.setdefault(i, rng.choice((1, 1, 1, 2, 0)))
            ids.append(i)
        if size > 1:
            ids.append(ids[0])  # an in-batch duplicate for certain
        items = [item(i, hits_of[i]) for i in ids]
        out.append((items, b"".join(
            item_wire(it, i) for it, i in zip(items, ids))))
    return out


def test_folded_door_equals_the_object_path(twins):
    (folded, addr_f), (objects, addr_o) = twins
    batches = stream(35)
    wires = [w for _, w in batches]
    n_items = sum(len(items) for items, _ in batches)
    before_f, before_o = counters(folded), counters(objects)
    got_f = call_raw(addr_f, wires)
    got_o = call_raw(addr_o, wires)
    after_f, after_o = counters(folded), counters(objects)

    def grew(after, before, k):
        return after[COUNTERS[k]] - before[COUNTERS[k]]

    # the first node folded every item, the second none
    assert grew(after_f, before_f, 3) == grew(after_f, before_f, 1) == n_items
    assert grew(after_o, before_o, 3) == 0
    assert grew(after_o, before_o, 1) == n_items
    # equal reply bytes, so equal answers item by item; and the reference's
    assert got_f == got_o
    want = reference_ring.owner_answers([items for items, _ in batches], T0)
    for reply, (items, _), ws in zip(got_f, batches, want):
        got = answers(reply)
        assert len(got) == len(items)
        assert [g[:3] + (g[4],) for g in got] == [(*w, "") for w in ws]
    flat = [a for reply in got_f for a in answers(reply)]
    assert sum(a[0] == 1 for a in flat) > 100  # keys driven over their limit
    assert sum(it[1] == 0 for items, _ in batches for it in items) > 300
    assert sum(it[4] == 1 for items, _ in batches for it in items) > 500
    # the screen answered the same items on both sides
    assert grew(after_f, before_f, 2) == grew(after_o, before_o, 2) > 0

    inst_f, inst_o = folded.servers[0].instance, objects.servers[0].instance

    def verdicts(shed):
        return {h: e[:3] for h, e in shed._entries.items()}

    assert verdicts(inst_f.shed) == verdicts(inst_o.shed) != {}

    def queued(inst):
        return {k: (r.name, r.unique_key, r.hits, r.limit, r.duration,
                    r.algorithm, r.behavior)
                for k, r in inst.global_mgr._updates.items()}

    assert queued(inst_f) == queued(inst_o)
    sent_global = {f"fold_k{i}" for i in range(11_500) if is_global(i)} & {
        f"fold_{it[0]}" for items, _ in batches for it in items}
    assert set(queued(inst_f)) == sent_global and len(sent_global) > 50


def test_two_folded_batches_in_flight_equal_the_object_path(twins):
    (folded, addr_f), (objects, addr_o) = twins
    rng = random.Random(351)
    wires = []
    for lane in range(2):  # each forwarder its own keys: the order is free
        ids = [rng.randrange(40) + 100_000 * (lane + 1) for _ in range(1000)]
        wires.append(b"".join(item_wire(item(i, 1), i) for i in ids))
    for _ in range(3):
        assert call_raw(addr_f, wires, together=True) == call_raw(
            addr_o, wires, together=True)


def test_folded_call_advances_every_stage_and_counter(twins):
    (folded, addr), _ = twins
    key = item_wire(("stage-me", 1, 3, 86_400_000, 0), 0)
    before, counted = stage_counts(), counters(folded)
    # 2, 1, 0 remaining, then the frozen refusal the screen caches
    got = [answers(r)[0][:3] for r in call_raw(addr, [key] * 4)]
    assert got == [(0, 3, 2), (0, 3, 1), (0, 3, 0), (1, 3, 0)]
    after, mid = stage_counts(), counters(folded)

    def grew(name, f=0):
        return after.get(name, (0, 0.0))[f] - before.get(name, (0, 0.0))[f]

    for name in CALL_TILES:
        assert grew(name) == (0 if name == "instance_route" else 4), name
    assert grew("call_e2e") == 4
    assert grew("shed") == grew("batch_queue") == grew("device") == 0
    tiled = sum(grew(name, 1) for name in CALL_TILES)
    assert 0.9 <= tiled / grew("call_e2e", 1) <= 1.0
    assert [mid[c] - counted[c] for c in COUNTERS] == [4, 4, 0, 4]
    # a batch the screen answers whole: no batcher tiles, all four counters
    (reply,) = call_raw(addr, [key * 5])
    assert [a[:3] for a in answers(reply)] == [(1, 3, 0)] * 5
    end = counters(folded)
    assert [end[c] - mid[c] for c in COUNTERS] == [1, 5, 5, 5]
    assert stage_counts()["call_queue"][0] == after["call_queue"][0]
    assert stage_counts()["peer_serve"][0] == after["peer_serve"][0] + 1


@pytest.mark.parametrize("case", ["chain", "unknown_field", "one_bad_item"])
def test_declined_batch_is_served_by_the_object_path(twins, case):
    (folded, addr), _ = twins
    wire = {
        "chain": item_bytes(
            [(1, b"fold"), (2, b"c-leaf"), (3, 1), (4, 5), (5, 60_000),
             (8, field(1, b"c-tenant") + field(2, 100))]),
        "unknown_field": item_bytes(
            [(1, b"fold"), (2, b"u-key"), (3, 1), (4, 5), (5, 60_000), (15, 1)]),
        "one_bad_item": item_wire(("ok-key", 1, 5, 60_000, 0), 0) * 12
        + DECLINED["unknown_field"],
    }[case]
    n = len(peers_pb2.GetPeerRateLimitsReq.FromString(wire).requests)
    before, counted = stage_counts(), counters(folded)
    (reply,) = call_raw(addr, [wire])
    got = answers(reply)
    assert len(got) == n and got[0][:3] == (0, 5, 4) and not got[0][4]
    after, end = stage_counts(), counters(folded)
    assert [end[c] - counted[c] for c in COUNTERS] == [1, n, 0, 0]
    for name in ("grpc_decode", "peer_serve", "grpc_encode", "call_e2e"):
        assert after[name][0] - before[name][0] == 1, name


def test_what_the_runtime_refuses_is_an_internal_error(twins):
    (folded, addr), _ = twins
    counted = counters(folded)
    for case in ("bad_utf8", "truncated"):
        with pytest.raises(grpc.aio.AioRpcError) as e:
            call_raw(addr, [DECLINED[case]])
        assert e.value.code() == grpc.StatusCode.INTERNAL
    with pytest.raises(grpc.aio.AioRpcError) as e:
        call_raw(addr, [DECLINED["too_many_items"]])
    assert e.value.code() == grpc.StatusCode.OUT_OF_RANGE
    assert counters(folded) == counted


def test_instance_declines_from_what_it_sees_in_itself(twins, native):
    (folded, _), _ = twins
    inst = folded.servers[0].instance
    wire = plain_item()
    assert len(inst.fold_peer_batch(wire)) == 1
    for attr in ("repl", "rescale"):
        setattr(inst, attr, object())
        try:
            assert inst.fold_peer_batch(wire) is None
        finally:
            setattr(inst, attr, None)
    backend = inst.backend
    inst.backend = object()  # no decide_submit_merged: a host backend
    try:
        assert inst.fold_peer_batch(wire) is None
    finally:
        inst.backend = backend
    assert len(inst.fold_peer_batch(wire)) == 1


def test_failure_inside_the_fold_answers_every_item_with_the_error(twins):
    (folded, addr), _ = twins
    inst = folded.servers[0].instance
    decide = inst.batcher.decide_arrays

    async def boom(fields, frame=True):
        raise RuntimeError("device on fire")

    inst.batcher.decide_arrays = boom
    try:
        (reply,) = call_raw(
            addr, [item_wire(("err-key", 1, 5, 60_000, 0), 0) * 3])
    finally:
        inst.batcher.decide_arrays = decide
    assert [(a[4], a[1]) for a in answers(reply)] == [("device on fire", 0)] * 3
