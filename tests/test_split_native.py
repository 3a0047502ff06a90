"""The four native calls of the GEB door's split by owner and the
forwarder's column RPC (PR 43, `native/guberhash.cc`, last section but
one), each against the code it stands in for:

- `guber_encode_peer_batch`: the forwarder's request bytes are, byte
  for byte, what the protobuf runtime serialises for
  `convert.req_to_pb`'s messages — so `peers_pb2` parses them to the
  same fields and the owner's `guber_parse_peer_batch` takes them with
  no decline, to the same columns;
- `guber_parse_peer_answers`: reply bytes from `resp_to_pb` and from
  the owner's `guber_encode_peer_answers` parse to equal columns;
  truncated, oversized and error-carrying replies are declined to the
  runtime (`peers._WireReply`), never misread;
- `guber_ring_owners`: the owner column equals
  `ConsistentHashPicker.get` on 100k seeded keys, on rings of 1-8;
- `guber_encode_string_answers`: the response frame's items equal
  `edge_bridge.encode_response_frame`'s, owner tags and error texts
  included;
- all four under AddressSanitizer on seeded, truncated and mutated
  inputs (a driver built here, as tests/test_edge_asan.py builds the
  edge);
- `PeerClient._forward_wire`: column groups and request groups in one
  flusher batch, each answered its own slice; a stub that takes
  messages only; a reply of the wrong length or of garbage fails the
  batch as the message path's does.

libguberhash.so is git-ignored: tests/conftest.py builds it before
collection (its `native` fixture skips where it is absent).
"""

import asyncio
import random
import struct
import subprocess
import zlib

import numpy as np
import pytest

from gubernator_tpu.api import convert
from gubernator_tpu.api.columns import ForwardAnswers, ForwardGroup
from gubernator_tpu.api.proto.gen import gubernator_pb2, peers_pb2
from gubernator_tpu.api.types import RateLimitResp, Status
from gubernator_tpu.serve import peers as peers_mod
from gubernator_tpu.serve.config import BehaviorConfig
from gubernator_tpu.serve.edge_bridge import (
    decode_request_frame,
    encode_response_frame,
)
from gubernator_tpu.serve.peers import ConsistentHashPicker
from test_string_frame_native import _random_frame


def _extreme_frame(seed: int, n: int):
    """Items with the integers a varint finds hard: 0, negatives,
    2**63 - 1, and every algorithm and behavior byte."""
    rng = random.Random(seed)
    edge = [0, 1, -1, 127, 128, 2**31, -(2**31), 2**63 - 1, -(2**63)]
    items = []
    for i in range(n):
        name, key = b"n%d" % rng.randrange(9), ("ké%d" % i).encode()
        items.append(
            struct.pack("<H", len(name)) + name
            + struct.pack("<H", len(key)) + key
            + struct.pack("<qqqBB", rng.choice(edge), rng.choice(edge),
                          rng.choice(edge), rng.randrange(6), rng.randrange(6)))
    return b"".join(items)


# -- the request's bytes -------------------------------------------------------


@pytest.mark.parametrize("seed,n", [(1, 1), (2, 37), (3, 1000), (4, 1000)])
def test_request_bytes_are_the_runtimes_and_the_owner_folds_them(native, seed, n):
    payload = (_random_frame(seed, n)[0] if seed % 2 else _extreme_frame(seed, n))
    got, cols, _ = native.parse_string_frame(payload, n)
    assert got == n
    reqs = decode_request_frame(payload, n)
    want = peers_pb2.GetPeerRateLimitsReq(
        requests=[convert.req_to_pb(r) for r in reqs])
    wire = native.encode_peer_batch(payload, cols)
    assert wire == want.SerializeToString()
    parsed = peers_pb2.GetPeerRateLimitsReq.FromString(wire)
    assert [convert.req_from_pb(p) for p in parsed.requests] == reqs
    m, pcols = native.parse_peer_batch(wire, 1000)
    assert m == n  # no decline
    for k in ("key_hash", "hits", "limit", "duration", "algo"):
        assert np.array_equal(pcols[k], cols[k]), k
    assert pcols["behavior"].tolist() == [int(r.behavior) for r in reqs]
    # a group is the rows it names, in the order it names them
    rows = np.array(sorted(random.Random(seed).sample(range(n), n // 3 + 1)),
                    np.int32)
    group = ForwardGroup(payload, cols, rows)
    assert group.to_wire() == peers_pb2.GetPeerRateLimitsReq(
        requests=[convert.req_to_pb(reqs[i]) for i in rows]).SerializeToString()
    assert group.requests() == [reqs[i] for i in rows]
    assert group.all_peeks() == all(reqs[i].hits == 0 for i in rows)
    assert len(group) == len(rows)
    assert np.array_equal(group.fields()["key_hash"], cols["key_hash"][rows])


def test_request_groups_concatenate_into_one_message(native):
    payload, _ = _random_frame(9, 60)
    _, cols, _ = native.parse_string_frame(payload, 60)
    reqs = decode_request_frame(payload, 60)
    a = ForwardGroup(payload, cols, np.arange(0, 20))
    b = peers_pb2.GetPeerRateLimitsReq(
        requests=[convert.req_to_pb(r) for r in reqs[20:45]]).SerializeToString()
    c = ForwardGroup(payload, cols, np.arange(45, 60))
    joined = a.to_wire() + b + c.to_wire()
    assert [convert.req_from_pb(p) for p in
            peers_pb2.GetPeerRateLimitsReq.FromString(joined).requests] == reqs
    assert native.parse_peer_batch(joined, 1000)[0] == 60


# -- the reply's bytes ---------------------------------------------------------


def _answers(seed: int, n: int):
    rng = random.Random(seed)
    edge = [0, 1, 127, 128, 2**40, -1, 2**63 - 1]
    return tuple(
        np.array([rng.choice(pool) for _ in range(n)], np.int64)
        for pool in ([0, 1], edge, edge, [0, 1_700_000_000_000, -5]))


@pytest.mark.parametrize("seed,n", [(1, 0), (2, 1), (3, 490), (4, 1000)])
def test_reply_bytes_from_the_runtime_and_from_the_owner_parse_alike(native, seed, n):
    cols = _answers(seed, n)
    from_owner = native.encode_peer_answers(*cols)
    from_runtime = peers_pb2.GetPeerRateLimitsResp(rate_limits=[
        convert.resp_to_pb(RateLimitResp(
            status=Status(int(s)), limit=int(li), remaining=int(r),
            reset_time=int(t)))
        for s, li, r, t in zip(*cols)]).SerializeToString()
    assert from_owner == from_runtime
    for wire in (from_owner, from_runtime):
        got, parsed = native.parse_peer_answers(wire, 1000)
        assert got == n
        for a, b in zip(parsed, cols):
            assert a.dtype == np.int64 and np.array_equal(a, b)
    reply = peers_mod._WireReply(from_owner, n)
    assert reply.items is None
    half = reply.answers(n // 2, n)
    assert np.array_equal(half.status, cols[0][n // 2:]) and not half.errors
    assert reply.resps(0, n) == [
        convert.resp_from_pb(p) for p in
        peers_pb2.GetPeerRateLimitsResp.FromString(from_owner).rate_limits]


def _reply(items):
    return peers_pb2.GetPeerRateLimitsResp(rate_limits=items).SerializeToString()


def test_truncated_oversized_and_text_carrying_replies_are_declined(native):
    ok = gubernator_pb2.RateLimitResp(status=1, limit=9, remaining=0, reset_time=77)
    whole = _reply([ok] * 5)
    assert native.parse_peer_answers(whole, 5)[0] == 5
    for cut in range(1, len(whole)):  # every truncation: declined or fewer items
        got, cols = native.parse_peer_answers(whole[:cut], 5)
        assert got < 5
        if got >= 0:  # it ended between two items
            assert whole[:cut] == _reply([ok] * got)
    assert native.ANSWER_DECLINE[native.parse_peer_answers(whole, 4)[0]] == \
        "too_many_items"
    err = _reply([ok, gubernator_pb2.RateLimitResp(error="boom")])
    assert native.ANSWER_DECLINE[native.parse_peer_answers(err, 9)[0]] == \
        "error_or_metadata"
    meta = gubernator_pb2.RateLimitResp(status=0, limit=3)
    meta.metadata["degraded"] = "true"
    assert native.ANSWER_DECLINE[native.parse_peer_answers(_reply([meta]), 9)[0]] \
        == "error_or_metadata"
    assert native.ANSWER_DECLINE[native.parse_peer_answers(
        b"\x0a\x02\x08\x02", 9)[0]] == "bad_enum"  # status 2 has no name
    assert native.ANSWER_DECLINE[native.parse_peer_answers(
        b"\x12\x00", 9)[0]] == "unknown_field"
    # what the parser declines the runtime reads: the text and the
    # rows that must not reach the shed cache
    reply = peers_mod._WireReply(_reply([ok, gubernator_pb2.RateLimitResp(
        error="boom"), meta]), 3)
    ans = reply.answers(0, 3)
    assert ans.errors == {1: "boom"} and ans.opaque == [1, 2]
    assert ans.status.tolist() == [1, 0, 0] and ans.limit.tolist() == [9, 0, 3]
    assert [r.error for r in reply.resps(0, 3)] == ["", "boom", ""]
    assert reply.resps(2, 3)[0].metadata == {"degraded": "true"}
    with pytest.raises(RuntimeError, match="mismatched"):
        peers_mod._WireReply(whole, 4)
    with pytest.raises(Exception):
        peers_mod._WireReply(whole[:-3], 5)
    assert ForwardAnswers.from_resps([convert.resp_from_pb(ok)]).resps() == [
        convert.resp_from_pb(ok)]


# -- the owner column ----------------------------------------------------------


@pytest.mark.parametrize("size", range(1, 9))
def test_owner_column_equals_the_pickers_get(native, size):
    class Peer:
        def __init__(self, host, is_owner):
            self.host, self.is_owner = host, is_owner

    rng = random.Random(size)
    picker = ConsistentHashPicker()
    for i in range(size):
        picker.add(Peer(f"10.{rng.randrange(256)}.{i}.7:{rng.randrange(1, 65536)}",
                        is_owner=(i == size // 2)))
    n = 100_000 if size in (4, 8) else 5_000
    keys = [f"ring4_{rng.randrange(10**7)}_s{rng.randrange(2**31)}ü"[
        : rng.randrange(3, 40)] for _ in range(n)]
    packed = "\x00".join(keys).encode()
    points, peers, own = picker.ring()
    assert [p.host for p in peers] == [
        picker._by_point[k].host for k in picker._keys]
    column = picker.owner_column(keys, packed)
    assert column.dtype == np.int32
    want = [picker.get(k) for k in keys]
    assert [peers[i] for i in column.tolist()] == want
    # the same without the packed bytes (no native call), and the
    # ring's is_owner column under it
    assert np.array_equal(picker.owner_column(keys), column)
    assert own[column].tolist() == [p.is_owner for p in want]
    crc = np.array([zlib.crc32(k.encode()) for k in keys[:200]], np.uint64)
    idx = np.searchsorted(points, crc)
    idx[idx == size] = 0
    assert np.array_equal(column[:200], idx)


def test_owner_column_refuses_a_buffer_of_another_count(native):
    ring = np.array([5, 10], np.uint32)
    assert native.ring_owners(b"", 0, ring).shape == (0,)
    assert native.ring_owners(b"a\x00b", 2, ring).shape == (2,)
    for keys, n in ((b"a\x00b", 3), (b"a\x00b", 1), (b"a", 0)):
        with pytest.raises(ValueError):
            native.ring_owners(keys, n, ring)


# -- the response frame's items ------------------------------------------------


@pytest.mark.parametrize("seed,n", [(1, 0), (2, 1), (3, 1000)])
def test_string_answers_equal_the_item_encoder(native, seed, n):
    rng = random.Random(seed)
    cols = _answers(seed, n)
    strings = [b"10.0.0.1:81", b"10.0.0.2:81", b"",
               "while fetching rate limit 'a_bé' from peer - 'x'".encode(),
               b"e" * 0xFFFF]
    err = np.array([rng.choice([-1, -1, -1, 3, 4]) for _ in range(n)], np.int32)
    tag = np.array([rng.choice([-1, 0, 1, 2]) for _ in range(n)], np.int32)
    resps = []
    for i in range(n):
        r = RateLimitResp(
            status=Status(int(cols[0][i])), limit=int(cols[1][i]),
            remaining=int(cols[2][i]), reset_time=int(cols[3][i]),
            error=strings[err[i]].decode() if err[i] >= 0 else "")
        if tag[i] >= 0:
            r.metadata["owner"] = strings[tag[i]].decode()
        resps.append(r)
    assert native.encode_string_answers(*cols, err, tag, strings) == \
        encode_response_frame(resps)[8:]
    with pytest.raises(ValueError):  # an index past the table
        native.encode_string_answers(
            *_answers(1, 1), np.array([9], np.int32), np.array([-1], np.int32),
            strings)
    with pytest.raises(ValueError):  # a text a u16 cannot say
        native.encode_string_answers(
            *_answers(1, 1), np.array([0], np.int32), np.array([-1], np.int32),
            [b"x" * 0x10000])


# -- under AddressSanitizer ----------------------------------------------------

_DRIVER = r"""
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <vector>
extern "C" {
int64_t guber_parse_string_frame(const uint8_t*, int64_t, int64_t, uint64_t,
    uint64_t*, int64_t*, int64_t*, int64_t*, int32_t*, uint8_t*, int32_t*,
    int32_t*, int32_t*, int32_t*, uint8_t*, int64_t*);
int64_t guber_ring_owners(const uint8_t*, int64_t, int64_t, const uint32_t*,
    int64_t, int32_t*);
int64_t guber_encode_peer_batch(const uint8_t*, const int32_t*, const int32_t*,
    const int32_t*, const int32_t*, const int64_t*, const int64_t*,
    const int64_t*, const int32_t*, const uint8_t*, const int32_t*, int64_t,
    uint8_t*, int64_t);
int64_t guber_parse_peer_batch(const uint8_t*, int64_t, int64_t, uint64_t,
    uint64_t*, int64_t*, int64_t*, int64_t*, int32_t*, uint8_t*, int32_t*,
    int32_t*, int32_t*, int32_t*);
int64_t guber_parse_peer_answers(const uint8_t*, int64_t, int64_t, int64_t*,
    int64_t*, int64_t*, int64_t*);
int64_t guber_encode_peer_answers(const int64_t*, const int64_t*,
    const int64_t*, const int64_t*, int64_t, uint8_t*);
int64_t guber_encode_string_answers(const int64_t*, const int64_t*,
    const int64_t*, const int64_t*, const int32_t*, const int32_t*,
    const uint8_t*, const int64_t*, int64_t, int64_t, uint8_t*, int64_t);
}
static uint64_t s = 88172645463325252ull;
static uint64_t rnd() { s ^= s << 13; s ^= s >> 7; s ^= s << 17; return s; }

// every buffer is a heap block of its exact size: a byte read or written
// past it is ASan's to report
int main(int argc, char** argv) {
  long frames = 0, folded = 0, replies = 0, parsed = 0;
  for (int a = 1; a < argc; ++a) {
    FILE* f = std::fopen(argv[a], "rb");
    if (!f) return 2;
    std::vector<uint8_t> file;
    uint8_t tmp[4096];
    size_t got;
    while ((got = std::fread(tmp, 1, sizeof tmp, f)) > 0)
      file.insert(file.end(), tmp, tmp + got);
    std::fclose(f);
    if (file.size() < 5) return 2;
    const char kind = file[0];
    uint32_t n;
    std::memcpy(&n, &file[1], 4);
    for (int round = 0; round < 60; ++round) {
      size_t len = file.size() - 5;
      if (round % 3 == 1) len = rnd() % (len + 1);  // truncated
      uint8_t* buf = static_cast<uint8_t*>(std::malloc(len ? len : 1));
      std::memcpy(buf, &file[5], len);
      if (round % 3 == 2 && len)  // mutated
        for (int k = 0; k < 4; ++k) buf[rnd() % len] = rnd() & 0xFF;
      if (kind == 'F') {
        ++frames;
        const int64_t cap = n ? n : 1;
        std::vector<uint64_t> kh(cap);
        std::vector<int64_t> hits(cap), limit(cap), dur(cap);
        std::vector<int32_t> algo(cap), no(cap), nl(cap), ko(cap), kl(cap);
        std::vector<uint8_t> beh(cap);
        uint8_t* keys = static_cast<uint8_t*>(std::malloc(len ? len : 1));
        int64_t keys_len = 0;
        const int64_t m = guber_parse_string_frame(buf, len, n, 7, kh.data(),
            hits.data(), limit.data(), dur.data(), algo.data(), beh.data(),
            no.data(), nl.data(), ko.data(), kl.data(), keys, &keys_len);
        if (m >= 0) {
          ++folded;
          uint32_t ring[4] = {1u << 30, 1u << 31, 3u << 30, 0xFFFFFFF0u};
          std::vector<int32_t> owner(cap);
          if (guber_ring_owners(keys, keys_len, m, ring, 4, owner.data()))
            return 3;
          std::vector<int32_t> rows;
          for (int64_t i = 0; i < m; ++i)
            if (owner[i] == int32_t(rnd() % 4)) rows.push_back(i);
          int64_t need = 0;
          for (int32_t i : rows) need += nl[i] + kl[i] + 69;
          uint8_t* out = static_cast<uint8_t*>(std::malloc(need ? need : 1));
          const int64_t w = guber_encode_peer_batch(buf, no.data(), nl.data(),
              ko.data(), kl.data(), hits.data(), limit.data(), dur.data(),
              algo.data(), beh.data(), rows.data(), rows.size(), out, need);
          if (w < 0) return 4;
          // what was written is what the owner's parser takes
          uint8_t* wire = static_cast<uint8_t*>(std::malloc(w ? w : 1));
          std::memcpy(wire, out, w);
          const int64_t r = rows.size() + 1;
          std::vector<uint64_t> kh2(r);
          std::vector<int64_t> h2(r), l2(r), d2(r);
          std::vector<int32_t> a2(r), o1(r), o2(r), o3(r), o4(r);
          std::vector<uint8_t> b2(r);
          if (guber_parse_peer_batch(wire, w, rows.size(), 7, kh2.data(),
                  h2.data(), l2.data(), d2.data(), a2.data(), b2.data(),
                  o1.data(), o2.data(), o3.data(), o4.data()) !=
              int64_t(rows.size()))
            return 5;
          for (size_t j = 0; j < rows.size(); ++j)
            if (kh2[j] != kh[rows[j]] || h2[j] != hits[rows[j]]) return 6;
          // a buffer one byte short is refused, not overrun
          if (w > 0 && guber_encode_peer_batch(buf, no.data(), nl.data(),
                  ko.data(), kl.data(), hits.data(), limit.data(), dur.data(),
                  algo.data(), beh.data(), rows.data(), rows.size(), out,
                  w - 1) >= 0)
            return 7;
          std::free(wire);
          std::free(out);
        }
        std::free(keys);
      } else {
        ++replies;
        const int64_t cap = n + 1;
        std::vector<int64_t> st(cap), li(cap), re(cap), rt(cap);
        const int64_t m = guber_parse_peer_answers(buf, len, n, st.data(),
            li.data(), re.data(), rt.data());
        if (m > int64_t(n)) return 8;
        if (m >= 0) {
          ++parsed;
          std::vector<int32_t> err(cap), tag(cap);
          for (int64_t i = 0; i < m; ++i) {
            err[i] = int32_t(rnd() % 4) - 1;
            tag[i] = int32_t(rnd() % 4) - 1;
          }
          const uint8_t strs[] = "hostAhostBBerr";
          const int64_t off[4] = {0, 5, 11, 14};
          const int64_t need = m * (29 + 2 * 6);
          uint8_t* out = static_cast<uint8_t*>(std::malloc(need ? need : 1));
          const int64_t w = guber_encode_string_answers(st.data(), li.data(),
              re.data(), rt.data(), err.data(), tag.data(), strs, off, 3, m,
              out, need);
          if (w < 0 || w > need) return 9;
          if (w > 0 && guber_encode_string_answers(st.data(), li.data(),
                  re.data(), rt.data(), err.data(), tag.data(), strs, off, 3,
                  m, out, w - 1) >= 0)
            return 10;
          std::free(out);
        }
      }
      std::free(buf);
    }
  }
  std::printf("frames %ld folded %ld replies %ld parsed %ld\n", frames,
              folded, replies, parsed);
  return 0;
}
"""


def test_the_four_calls_are_clean_under_asan(native, tmp_path):
    import pathlib
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    src = pathlib.Path(peers_mod.__file__).resolve().parent.parent / "native"
    (tmp_path / "driver.cc").write_text(_DRIVER)
    built = subprocess.run(
        ["g++", "-O1", "-g", "-fsanitize=address,undefined",
         "-fno-sanitize-recover=all", "-pthread", "-o", str(tmp_path / "driver"),
         str(tmp_path / "driver.cc"), str(src / "guberhash.cc")],
        capture_output=True, text=True)
    if built.returncode != 0 and "sanitize" in built.stderr:
        pytest.skip("this toolchain has no sanitizer runtime")
    assert built.returncode == 0, built.stderr[-2000:]
    files = []
    for seed, n in ((1, 1), (2, 40), (3, 1000), (4, 0)):
        payload = (_random_frame(seed, n)[0] if seed % 2
                   else _extreme_frame(seed, n))
        files.append(("F", n, payload))
    for seed, n in ((5, 1), (6, 300), (7, 1000)):
        files.append(("R", n, native.encode_peer_answers(*_answers(seed, n))))
    err = gubernator_pb2.RateLimitResp(error="boom", status=1)
    files.append(("R", 3, _reply([err] * 3)))
    paths = []
    for i, (kind, n, body) in enumerate(files):
        path = tmp_path / f"in{i}"
        path.write_bytes(kind.encode() + struct.pack("<I", n) + body)
        paths.append(str(path))
    ran = subprocess.run(
        [str(tmp_path / "driver"), *paths], capture_output=True, text=True,
        timeout=300)
    assert ran.returncode == 0, (ran.returncode, ran.stderr[-3000:])
    counts = dict(zip(ran.stdout.split()[::2], map(int, ran.stdout.split()[1::2])))
    assert counts["frames"] == 240 and counts["replies"] == 240
    assert counts["folded"] >= 80 and counts["parsed"] >= 60


# -- the forwarder's flusher over a fake stub ----------------------------------


class _Owner:
    """A stub that answers every item (0, limit, duration, 77) and
    records what it was sent; `tagged` = every answer carries a
    metadata entry, which the native reply parser declines and the
    protobuf runtime parses."""

    def __init__(self, tagged=False, reply=None):
        self.batches = []
        self._tagged = tagged
        self._reply = reply

    async def GetPeerRateLimitsWire(self, wire, timeout=None):
        if self._reply is not None:
            return self._reply
        pb_req = peers_pb2.GetPeerRateLimitsReq.FromString(wire)
        self.batches.append(len(pb_req.requests))
        return peers_pb2.GetPeerRateLimitsResp(rate_limits=[
            gubernator_pb2.RateLimitResp(
                limit=r.limit, remaining=r.duration, reset_time=77,
                metadata={"seen": "1"} if self._tagged else None)
            for r in pb_req.requests]).SerializeToString()


def _client(stub):
    counts = peers_mod.ForwardCounts()
    c = peers_mod.PeerClient(BehaviorConfig(), "10.0.0.9:81", counts=counts)
    c.stub = stub
    c._flusher = asyncio.ensure_future(c._run())
    return c, counts


@pytest.mark.parametrize("tagged", [False, True])
def test_column_groups_and_request_groups_share_a_flusher_batch(native, tagged):
    payload, _ = _random_frame(11, 90)
    _, cols, _ = native.parse_string_frame(payload, 90)
    reqs = decode_request_frame(payload, 90)

    async def run():
        stub = _Owner(tagged=tagged)
        c, counts = _client(stub)
        try:
            got = await asyncio.gather(
                c.forward_columns(ForwardGroup(payload, cols, np.arange(0, 30))),
                c.get_peer_rate_limits_grouped(reqs[30:50]),
                c.forward_columns(ForwardGroup(payload, cols, np.arange(50, 90))),
                c.get_peer_rate_limits_grouped(reqs[50:60]),
            )
            alone = await c.get_peer_rate_limits_grouped(reqs[:5])
        finally:
            await c.close()
        return stub, counts, got, alone

    stub, counts, (a, b, c_, d), alone = asyncio.run(run())
    assert stub.batches == [100, 5] and (counts.batches, counts.items) == (2, 105)
    assert not any(counts.failed.values())
    assert isinstance(a, ForwardAnswers) and isinstance(b, list)
    assert a.limit.tolist() == [r.limit for r in reqs[:30]]
    assert a.remaining.tolist() == [r.duration for r in reqs[:30]]
    assert c_.limit.tolist() == [r.limit for r in reqs[50:90]]
    assert [(r.limit, r.remaining, r.reset_time) for r in b] == [
        (q.limit, q.duration, 77) for q in reqs[30:50]]
    assert [r.limit for r in d] == [q.limit for q in reqs[50:60]]
    assert len(alone) == 5 and all(not r.error for r in alone)
    assert [r.metadata for r in b] == [{"seen": "1"} if tagged else {}] * 20
    assert a.opaque == [] and not a.errors


@pytest.mark.parametrize("reply,reason", [
    (b"\x0a\x02\x08\x01" * 3, "transport"),  # 3 answers for 8
    (b"\x0a\x7f\x08", "transport"),  # not the message
])
def test_a_reply_the_batch_cannot_use_fails_every_group(native, reply, reason):
    payload, _ = _random_frame(12, 8)
    _, cols, _ = native.parse_string_frame(payload, 8)

    async def run():
        c, counts = _client(_Owner(reply=reply))
        try:
            with pytest.raises(RuntimeError, match="while fetching from peer"):
                await c.forward_columns(ForwardGroup(payload, cols, np.arange(8)))
        finally:
            await c.close()
        return counts

    counts = asyncio.run(run())
    assert counts.failed[reason] == 8 and counts.batches == 1


def test_a_closed_client_refuses_a_column_group(native):
    payload, _ = _random_frame(13, 4)
    _, cols, _ = native.parse_string_frame(payload, 4)

    async def run():
        c, counts = _client(_Owner())
        await c.close()
        with pytest.raises(RuntimeError, match="is closed"):
            await c.forward_columns(ForwardGroup(payload, cols, np.arange(4)))
        return counts

    assert asyncio.run(run()).failed["closed"] == 4
