"""The GEB door's native string-frame parse (PR 37): one call of
`libguberhash.so guber_parse_string_frame` — wire bytes -> columns, key
hashes and the NUL-joined hash keys, GIL released — against the
object path's decoder (`decode_request_frame`) and `slot_hash_batch`,
the oracle that exists anyway. There is no second parser in Python: a
frame the native call does not take is the object path's.

- (a) wire: random frames (names and keys of 1-200 bytes, multi-byte
  UTF-8, every algorithm byte, behaviors 0-5, n = 0, 1, 1000) give the
  decoder's keys, columns and GLOBAL rows, and `slot_hash_batch`'s
  hashes; one case per reason the parser declines, the screen saying
  `(None, None, reason)` for each — a NUL byte in a name or key among
  them, which the object path answers as the fold answers a plain byte;
- (b) fold: `_decide_string_frame` answers with the same bytes with the
  library present and with `native_lib()` patched to None (the object
  path), on a one-node ring (a real Instance on a standing clock) and
  on a three-node ring where some frames are of mixed ownership; a
  frame the parser declines is answered by the object path as before;
- (c) the two counters by growth.

libguberhash.so is git-ignored: tests/conftest.py builds it before
collection, and its `native` fixture skips where it is absent.
"""

import asyncio
import random
import struct

import numpy as np
import pytest

from gubernator_tpu.api.types import Behavior, RateLimitResp, Status
from gubernator_tpu.core import hashing
from gubernator_tpu.serve import edge_bridge
from gubernator_tpu.serve.edge_bridge import EdgeBridge, decode_request_frame
from gubernator_tpu.serve.metrics import REGISTRY
from gubernator_tpu.serve.peers import ConsistentHashPicker, SplitCounts
from test_edge_bridge import (
    BAD,
    FakePeer,
    _FakeBackendArrays,
    _FakeTraffic,
    _item,
    _seeded_mixed_frame,
)

FIELDS = ("key_hash", "hits", "limit", "duration", "algo")


@pytest.fixture
def python_parse(monkeypatch):
    """Run the body with the door's native parse off — the door as it
    behaves where libguberhash.so is absent: every string frame is the
    object path's (key hashing aside: it stays native, so both nodes
    of a comparison hash alike)."""

    def off():
        monkeypatch.setattr(edge_bridge, "native_lib", lambda: None)

    return off


# -- a bridge over fakes: a real picker, recorded paths ----------------------


def _bridge(owners=(True,)):
    """EdgeBridge over a real ConsistentHashPicker with one peer per
    entry of `owners` (True = this node), a batcher that answers from
    the columns it is handed and an Instance door that answers the
    same from the request objects — deterministic, so two serves
    compare bytewise whichever path took the frame."""
    from gubernator_tpu.serve.config import BehaviorConfig
    from gubernator_tpu.serve.global_mgr import GlobalManager

    paths = []

    class FakeBatcher:
        async def decide_arrays(self, fields, frame=True):
            n = fields["key_hash"].shape[0]
            paths.append(("arrays", n))
            return (
                (fields["key_hash"] % np.uint64(2)).astype(np.int64),
                fields["limit"],
                fields["limit"] - fields["hits"],
                fields["duration"] + fields["algo"],
            )

    class FakeInstance:
        backend = _FakeBackendArrays()
        traffic = _FakeTraffic()
        batcher = FakeBatcher()
        picker = ConsistentHashPicker()
        shed = None
        edge_split = SplitCounts()

        def split_unavailable(self):
            return "no_arrays"  # no forwarder behind this fake

        async def get_rate_limits(self, reqs, stage_frame=False):
            paths.append(("objects", len(reqs)))
            hashes = hashing.slot_hash_batch([r.hash_key() for r in reqs])
            return [
                RateLimitResp(
                    status=Status(int(h) % 2), limit=r.limit,
                    remaining=r.limit - r.hits,
                    reset_time=r.duration + int(r.algorithm),
                )
                for r, h in zip(reqs, hashes)
            ]

    inst = FakeInstance()
    inst.global_mgr = GlobalManager(BehaviorConfig(), inst)
    for i, own in enumerate(owners):
        inst.picker.add(FakePeer(f"10.0.0.{i + 1}:81", is_owner=own))
    return EdgeBridge(inst, ""), paths


# -- (a) the wire -------------------------------------------------------------

# code points of one, two, three and four UTF-8 bytes; never NUL, never
# a surrogate
_ALPHABET = (
    [chr(c) for c in range(1, 128)]
    + [chr(c) for c in range(0x80, 0x800, 37)]
    + [chr(c) for c in range(0x800, 0xD800, 1201)]
    + [chr(c) for c in range(0xE000, 0x10000, 997)]
    + [chr(c) for c in range(0x10000, 0x110000, 40009)]
)


def _text(rng, max_bytes=200) -> bytes:
    want = rng.randint(1, max_bytes)
    out = b""
    while True:
        more = rng.choice(_ALPHABET).encode()
        if len(out) + len(more) > want:
            return out or b"x"
        out += more


def _random_frame(seed: int, n: int):
    rng = random.Random(seed)
    names = [_text(rng) for _ in range(7)]
    items, rows = [], []
    for i in range(n):
        name = rng.choice(names)
        key = _text(rng)
        row = (
            rng.choice((0, 1, -1, 2**63 - 1, -(2**63), rng.getrandbits(40))),
            rng.getrandbits(62),
            rng.randrange(-5, 2**40),
            i % 256,
            rng.randrange(6),
        )
        items.append(_item(name, key, *row))
        rows.append((name, key) + row)
    return b"".join(items), rows


@pytest.mark.parametrize(
    "seed,n", [(1, 0), (2, 1), (3, 1), (4, 2), (5, 37), (6, 1000), (7, 1000)]
)
def test_native_parse_equals_the_object_decoder(native, seed, n):
    payload, rows = _random_frame(seed, n)
    bridge, _ = _bridge()
    got, cols, keys = native.parse_string_frame(payload, n)
    assert got == n
    fold, mixed, reason = bridge._screen_string_frame(payload, n)
    assert mixed is None and reason == ""
    full, fields, glob, _, packed = fold
    assert packed == keys
    assert set(fields) == set(FIELDS)
    # the oracle: what the object path makes of the same bytes
    reqs = decode_request_frame(payload, n)
    assert len(reqs) == n and None not in reqs
    assert full == [r.name + "_" + r.unique_key for r in reqs]
    assert np.array_equal(fields["key_hash"], hashing.slot_hash_batch(full))
    assert fields["key_hash"].dtype == np.uint64
    assert fields["hits"].tolist() == [r.hits for r in reqs]
    assert fields["limit"].tolist() == [r.limit for r in reqs]
    assert fields["duration"].tolist() == [r.duration for r in reqs]
    # an algorithm byte over 3 reads as the default, on both sides
    assert fields["algo"].tolist() == [int(r.algorithm) for r in reqs]
    assert fields["algo"].dtype == np.int32
    assert glob == [
        (i, r.name, r.unique_key)
        for i, r in enumerate(reqs) if r.behavior == Behavior.GLOBAL
    ]
    # and the frame as it was written
    assert keys == b"\x00".join(name + b"_" + key for name, key, *_ in rows)
    for k in FIELDS:
        assert np.array_equal(fields[k], cols[k])
    assert fields["hits"].tolist() == [r[2] for r in rows]
    assert fields["algo"].tolist() == [
        r[5] if r[5] <= 3 else 0 for r in rows
    ]
    assert cols["behavior"].tolist() == [r[6] for r in rows]
    for i, (name, key, *_) in enumerate(rows):
        no, nl = int(cols["name_off"][i]), int(cols["name_len"][i])
        ko, kl = int(cols["key_off"][i]), int(cols["key_len"][i])
        assert payload[no : no + nl] == name
        assert payload[ko : ko + kl] == key


def _decline_cases():
    """(id, payload, n, reasons): the parser must decline with one of
    `reasons`."""
    a = _item(b"api", b"k-one", hits=3, limit=9, behavior=2)
    b = _item("né".encode(), "clé-€".encode(), algo=2)
    cases = [
        ("count-too-large", a + b, 3, {"too_many_items"}),
        ("count-huge", a + b, 2**31, {"too_many_items"}),
        ("empty-name", a + _item(b"", b"k"), 2, {"empty_name_or_key"}),
        ("empty-key", _item(b"api", b"") + a, 2, {"empty_name_or_key"}),
        ("bad-utf8-name", a + _item(BAD, b"k"), 2, {"bad_utf8"}),
        ("bad-utf8-key", a + _item(b"api", BAD), 2, {"bad_utf8"}),
        ("overlong-utf8", a + _item(b"api", b"\xc0\xaf"), 2, {"bad_utf8"}),
        ("surrogate", a + _item(b"api", b"\xed\xa0\x80"), 2, {"bad_utf8"}),
        ("cut-multibyte", a + _item(b"api", "€".encode()[:2]), 2,
         {"bad_utf8"}),
        ("trailing-byte", a + b + b"\x00", 2, {"trailing_bytes"}),
        ("trailing-item", a + b + a, 2, {"trailing_bytes"}),
        ("count-too-small", a + b, 1, {"trailing_bytes"}),
    ]
    # the second item cut at every field boundary, and inside each
    # field: the name's length prefix, the name, the key's prefix, the
    # key, hits, limit, duration, algorithm, behavior
    nl, kl = len("né".encode()), len("clé-€".encode())
    bounds = [0, 1, 2, 2 + 1, 2 + nl, 2 + nl + 1, 4 + nl, 4 + nl + 2,
              4 + nl + kl]
    fix = 4 + nl + kl
    bounds += [fix + 4, fix + 8, fix + 12, fix + 16, fix + 24, fix + 25]
    assert fix + 26 == len(b)
    for cut in bounds:
        cases.append((
            f"truncated-at-{cut}", a + b[:cut], 2,
            {"truncated", "too_many_items"},
        ))
    return cases


@pytest.mark.parametrize(
    "payload,n,reasons",
    [pytest.param(*c[1:], id=c[0]) for c in _decline_cases()],
)
def test_native_parse_declines_and_the_screen_says_why(
    native, payload, n, reasons
):
    got, cols, keys = native.parse_string_frame(payload, n)
    assert got < 0 and cols is None and keys is None
    assert native.STRING_DECLINE[got] in reasons
    # the frame is the object path's: for no counted reason on a ring
    # this node shares with nobody, as an invalid item on a shared one
    alone, _ = _bridge()
    assert alone._screen_string_frame(payload, n) == (None, None, "")
    shared, _ = _bridge(owners=(True, False, False))
    assert shared._screen_string_frame(payload, n) == (
        None, None, "invalid_item"
    )


def _standing_node(monkeypatch, addr):
    """serve(frames) -> (reply frames, queued broadcasts, shed hits):
    each call boots a fresh one-node Instance over a small device store
    on a standing clock and sends `frames` through its door."""
    from gubernator_tpu.api.types import PeerInfo
    from gubernator_tpu.core.store import StoreConfig
    from gubernator_tpu.serve.backends import TpuBackend
    from gubernator_tpu.serve.config import ServerConfig
    from gubernator_tpu.serve.instance import Instance

    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    def clock():
        return 1_700_000_000_000

    monkeypatch.setattr(types_mod, "millisecond_now", clock)
    monkeypatch.setattr(engine_mod, "millisecond_now", clock)

    async def serve(frames):
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
        try:
            out = [
                await bridge._decide_string_frame(payload, n)
                for payload, n in frames
            ]
            return out, dict(inst.global_mgr._updates), inst.shed.hits
        finally:
            await inst.stop()

    return lambda frames: asyncio.run(serve(frames))


@pytest.mark.parametrize("where", ["name", "key", "key-end"])
def test_nul_byte_declines_natively_and_the_object_path_answers(
    native, monkeypatch, where
):
    """A NUL inside a name or key cannot ride the parser's NUL-joined
    buffer: the object path answers that frame, byte for byte what the
    fold answers for the same frame with a plain byte in the NUL's
    place (fresh nodes, a standing clock)."""

    def frame(byte: bytes):
        odd = {
            "name": _item(b"a" + byte + b"pi", b"k2", behavior=2),
            "key": _item(b"api", b"k" + byte + b"2", behavior=2),
            "key-end": _item(b"api", b"k2" + byte, behavior=2),
        }[where]
        return _item(b"api", b"k1") + odd + _item(b"api", b"k3", algo=1), 3

    got, _, _ = native.parse_string_frame(*frame(b"\x00"))
    assert native.STRING_DECLINE[got] == "nul_byte"
    serve = _standing_node(monkeypatch, "127.0.0.1:9984")
    before = (
        _declined("nul_byte"), _sample("edge_object_items_total"),
        _sample("edge_folded_items_total"),
    )
    with_nul, updates, _ = serve([frame(b"\x00")])
    assert _declined("nul_byte") - before[0] == 1
    assert _sample("edge_object_items_total") - before[1] == 3
    assert _sample("edge_folded_items_total") == before[2]
    plain, plain_updates, _ = serve([frame(b"~")])
    assert _sample("edge_folded_items_total") - before[2] == 3
    assert _sample("edge_object_items_total") - before[1] == 3
    assert with_nul == plain and len(with_nul[0]) == 8 + 3 * 29
    # the GLOBAL item's broadcast is queued under the key as it came
    assert len(updates) == len(plain_updates) == 1
    assert "\x00" in next(iter(updates))
    assert "~" in next(iter(plain_updates))


# -- (b) the fold -------------------------------------------------------------


def _serve(bridge, frames):
    async def run():
        return [
            await bridge._decide_string_frame(payload, n)
            for payload, n in frames
        ]

    return asyncio.run(run())


def _ring_frames(picker):
    """Four frames for a three-node ring: two of keys this node owns
    (one with GLOBAL items), two of mixed ownership."""
    owned, foreign = [], []
    i = 0
    while len(owned) < 300 or len(foreign) < 50:
        key = b"k%d" % i
        mine = picker.get(f"ring_{key.decode()}").is_owner
        (owned if mine else foreign).append(key)
        i += 1

    def frame(keys, global_every=0):
        items = [
            _item(
                b"ring", k, hits=j % 3, limit=10 + j, duration=1000 + j,
                algo=j % 5,
                behavior=2 if global_every and j % global_every == 0 else 0,
            )
            for j, k in enumerate(keys)
        ]
        return b"".join(items), len(items)

    return [
        frame(owned[:300]),
        frame(owned[:257] + foreign[:1]),  # foreign past the first 256
        frame(owned[100:300], global_every=7),
        frame(foreign[:50] + owned[:50], global_every=5),
    ]


def test_three_node_ring_same_bytes_with_and_without_native(
    native, python_parse
):
    bridge, paths = _bridge(owners=(True, False, False))
    frames = _ring_frames(bridge.instance.picker)
    with_native = _serve(bridge, frames)
    assert [p[0] for p in paths] == [
        "arrays", "objects", "arrays", "objects"
    ]
    assert len(bridge.instance.global_mgr._updates) > 20
    declined = bridge.instance.edge_split.declined
    bridge2, paths2 = _bridge(owners=(True, False, False))
    python_parse()
    without = _serve(bridge2, frames)
    assert with_native == without
    # without the library every frame is the object path's, and says so
    assert paths2 == [("objects", n) for _, n in frames]
    declined2 = bridge2.instance.edge_split.declined
    assert declined2["no_native"] == 4 and declined["no_native"] == 0


def test_one_node_ring_same_bytes_with_and_without_native(
    native, python_parse, monkeypatch
):
    """Two fresh nodes on a standing clock serve the same two seeded
    1000-item mixed frames (both algorithms, every tenth key id GLOBAL,
    keys driven over their limit, the shed cache filling): one parses
    natively and folds, one has no library and serves request objects;
    reply bytes, queued broadcasts and shed hits are equal."""
    serve = _standing_node(monkeypatch, "127.0.0.1:9983")
    frames = [_seeded_mixed_frame(37), _seeded_mixed_frame(2037)]
    parsed = _native_frames()
    folded = _sample("edge_folded_items_total")
    objects = _sample("edge_object_items_total")
    with_native = serve(frames)
    assert _native_frames() - parsed == 2
    assert _sample("edge_folded_items_total") - folded == 2000
    assert _sample("edge_object_items_total") == objects
    python_parse()
    without = serve(frames)
    assert _native_frames() - parsed == 2
    assert _sample("edge_folded_items_total") - folded == 2000
    assert _sample("edge_object_items_total") - objects == 2000
    assert with_native == without
    out, updates, shed_hits = with_native
    assert len(updates) > 20 and shed_hits > 0
    statuses = [out[1][8 + 29 * j] for j in range(1000)]
    assert 0 < sum(statuses) < 1000


@pytest.mark.parametrize(
    "items,reason",
    [
        ([_item(b"api", b"k1"), _item(b"api", BAD), _item(b"api", b"k3")],
         "bad_utf8"),
        ([_item(b"api", b"k1"), _item(b"", b"k2", behavior=2)],
         "empty_name_or_key"),
    ],
    ids=["bad-utf8", "empty-name"],
)
def test_declined_frame_is_answered_by_the_object_path_as_before(
    native, python_parse, items, reason
):
    payload, n = b"".join(items), len(items)
    bridge, paths = _bridge()
    declined = _declined(reason)
    objects = _sample("edge_object_items_total")
    (with_native,) = _serve(bridge, [(payload, n)])
    assert _declined(reason) - declined == 1
    assert _sample("edge_object_items_total") - objects == n
    good = sum(1 for r in decode_request_frame(payload, n) if r is not None)
    assert paths == [("objects", good)]
    bridge2, paths2 = _bridge()
    python_parse()
    (without,) = _serve(bridge2, [(payload, n)])
    assert with_native == without and paths2 == paths


# -- (c) the counters ---------------------------------------------------------


def _sample(name, labels=None) -> float:
    return REGISTRY.get_sample_value(name, labels or {}) or 0.0


def _native_frames() -> float:
    return _sample("edge_string_native_frames_total")


def _declined(reason: str) -> float:
    return _sample("edge_string_native_declined_total", {"reason": reason})


def test_counters_follow_the_frames(native):
    bridge, paths = _bridge()
    good = [
        (_item(b"api", b"k%d" % i) + _item(b"api", b"g", behavior=2), 2)
        for i in range(5)
    ]
    cut = (good[0][0][:-3], 2)
    reasons = edge_bridge.native_lib().STRING_DECLINE.values()
    before = (
        _native_frames(), {r: _declined(r) for r in reasons},
        _sample("edge_folded_items_total"),
    )
    out = _serve(bridge, good)
    assert _native_frames() - before[0] == 5
    assert {r: _declined(r) for r in reasons} == before[1]
    assert _sample("edge_folded_items_total") - before[2] == 10
    assert paths == [("arrays", 2)] * 5
    assert all(len(frame) == 8 + 2 * 29 for frame in out)
    # a malformed frame: declined natively, and the object path answers
    # it as it always did — the decoder raises, the connection's
    # handler closes it
    with pytest.raises(struct.error):
        _serve(bridge, [cut])
    assert _native_frames() - before[0] == 5
    assert _declined("truncated") - before[1]["truncated"] == 1
    # one the object path can answer: served, counted once
    bad = (_item(b"api", b"k1") + _item(b"api", BAD), 2)
    (frame,) = _serve(bridge, [bad])
    assert _declined("bad_utf8") - before[1]["bad_utf8"] == 1
    assert b"UTF-8" in frame and paths[-1] == ("objects", 1)
