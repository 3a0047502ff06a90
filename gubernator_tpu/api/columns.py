"""A forwarded batch as columns: the PeersV1 door's array form.

`PeersV1/GetPeerRateLimits` carries up to 1000 items a call, and an
owner that makes a `RateLimitReq`, a `RateLimitResp` and two protobuf
messages for each of them spends its one GIL on objects (PERF.md,
PR 32: ~20 ms a batch). `PeerBatch` and `PeerAnswers` are what the
door and the instance pass instead: the request's serialised bytes
parsed into numpy columns by one native call (`PeerBatch.from_wire`),
and the answer columns serialised by another (`PeerAnswers.to_wire`).
`ForwardGroup` and `ForwardAnswers` are the same thing seen from the
forwarder (serve/peers.py PeerClient.forward_columns): the rows of a
GEB string frame that one peer owns, serialised from the frame's own
columns and key bytes, and the peer's reply parsed back to columns.
Nothing here imports JAX or the serving tier.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from gubernator_tpu.api.types import (
    Algorithm,
    Behavior,
    RateLimitReq,
    RateLimitResp,
    Status,
)
from gubernator_tpu.core.hashing import native_lib


#: the columns DeviceBatcher.decide_arrays and the shed cache's screen
#: take, of those a native parser fills
DECIDE_FIELDS = ("key_hash", "hits", "limit", "duration", "algo")


class PeerBatch:
    """The items of one serialised GetPeerRateLimitsReq, as columns.

    `fields` is what DeviceBatcher.decide_arrays and the shed cache's
    screen take (key_hash / hits / limit / duration / algo); `behavior`
    rides beside it; the key strings stay in `wire` and are decoded
    for GLOBAL items alone (global_items)."""

    __slots__ = ("wire", "fields", "behavior", "_spans")

    def __init__(self, wire: bytes, cols: Dict[str, np.ndarray]):
        self.wire = wire
        self.fields = {k: cols[k] for k in DECIDE_FIELDS}
        self.behavior = cols["behavior"]
        self._spans = cols

    def __len__(self) -> int:
        return self.behavior.shape[0]

    @classmethod
    def from_wire(
        cls, wire: bytes, max_items: int
    ) -> Optional["PeerBatch"]:
        """The batch, or None where the native parser is not built or
        declines the message (native/guberhash.cc
        guber_parse_peer_batch: a chain, an unknown field or wire type,
        an enum value without a name, invalid UTF-8, a truncated
        message, more than `max_items` items) and for an empty
        message: the caller parses those with the protobuf runtime and
        serves objects."""
        lib = native_lib()
        if lib is None:
            return None
        n, cols = lib.parse_peer_batch(wire, max_items)
        if n <= 0:
            return None
        return cls(wire, cols)

    def global_items(self) -> Tuple[Dict[int, str], List[tuple]]:
        """({index: hash key}, [(index, name, unique_key)]) of the
        Behavior GLOBAL items in batch order: the `keys` and `glob`
        GlobalManager.queue_update_fields takes. Strings are built for
        these items only."""
        glob = global_rows(self.wire, self._spans)
        return {i: name + "_" + ukey for i, name, ukey in glob}, glob


def global_rows(wire: bytes, cols: Dict[str, np.ndarray]) -> List[tuple]:
    """[(index, name, unique_key)] of the Behavior GLOBAL items among
    natively parsed columns, in item order: name and unique_key are
    decoded from their spans in `wire` (name_off / name_len / key_off /
    key_len, validated UTF-8 by the parser) for these rows alone."""
    idx = np.flatnonzero(cols["behavior"] == int(Behavior.GLOBAL))
    if not idx.shape[0]:
        return []
    return [
        (i, wire[no : no + nl].decode(), wire[ko : ko + kl].decode())
        for i, no, nl, ko, kl in zip(
            idx.tolist(),
            cols["name_off"][idx].tolist(), cols["name_len"][idx].tolist(),
            cols["key_off"][idx].tolist(), cols["key_len"][idx].tolist(),
        )
    ]


def _column(name: str, cast=int) -> property:
    """One field of an _AnswerRow: read from, and written to, row
    `_i` of the PeerAnswers column `name`."""

    def get(row: "_AnswerRow"):
        return cast(int(getattr(row._cols, name)[row._i]))

    def put(row: "_AnswerRow", v) -> None:
        getattr(row._cols, name)[row._i] = int(v)

    return property(get, put)


class _AnswerRow:
    """One row of a PeerAnswers as a RateLimitResp would read — and a
    `status` (limit, ...) written here lands in the column."""

    __slots__ = ("_cols", "_i")

    def __init__(self, cols: "PeerAnswers", i: int):
        self._cols = cols
        self._i = i

    status = _column("status", Status)
    limit = _column("limit")
    remaining = _column("remaining")
    reset_time = _column("reset_time")

    @property
    def error(self) -> str:
        return self._cols.error

    @property
    def metadata(self) -> Dict[str, str]:
        return {}


class PeerAnswers:
    """The answers to one PeerBatch, as four int64 columns in batch
    order. `error` is set where the whole batch failed (every item then
    answers with it, as the object path's per-item error replies do).
    Iterating yields rows that read like RateLimitResp and write
    through to the columns."""

    __slots__ = ("status", "limit", "remaining", "reset_time", "error")

    def __init__(self, status, limit, remaining, reset_time, error=""):
        # columns of its own, writable and of the wire's width: a
        # device batch may answer in narrower, read-only ones
        self.status, self.limit, self.remaining, self.reset_time = (
            np.array(c, np.int64)
            for c in (status, limit, remaining, reset_time)
        )
        self.error = error

    @classmethod
    def failed(cls, n: int, error: str) -> "PeerAnswers":
        return cls(*np.zeros((4, n), np.int64), error=error)

    def __len__(self) -> int:
        return self.status.shape[0]

    def __getitem__(self, i: int) -> _AnswerRow:
        return _AnswerRow(self, range(len(self))[i])

    def __iter__(self) -> Iterator[_AnswerRow]:
        return (_AnswerRow(self, i) for i in range(len(self)))

    def to_wire(self) -> bytes:
        """The serialised GetPeerRateLimitsResp: one native call, or
        the protobuf runtime where every item carries the error."""
        if self.error:
            from gubernator_tpu.api.proto.gen import gubernator_pb2, peers_pb2

            return peers_pb2.GetPeerRateLimitsResp(
                rate_limits=[gubernator_pb2.RateLimitResp(error=self.error)]
                * len(self)
            ).SerializeToString()
        return native_lib().encode_peer_answers(
            self.status, self.limit, self.remaining, self.reset_time
        )


def split_ready() -> bool:
    """True where libguberhash.so loaded, and with it the four calls
    the GEB door's split by owner and the forwarder's column RPC are
    made of (ring_owners, encode_peer_batch, parse_peer_answers,
    encode_string_answers): without it a frame of mixed ownership is
    served through request objects, as before the split."""
    return native_lib() is not None


class ForwardGroup:
    """The rows of one natively parsed GEB string frame that one peer
    owns: the forwarder's queue entry where the door holds columns and
    no request objects. `cols` are hashlib_native.parse_string_frame's
    over `payload`, shared by the frame's groups; `rows` (int32, frame
    order) names this group's items."""

    __slots__ = ("payload", "cols", "rows")

    def __init__(self, payload: bytes, cols: Dict[str, np.ndarray], rows):
        self.payload = payload
        self.cols = cols
        self.rows = np.ascontiguousarray(rows, np.int32)

    def __len__(self) -> int:
        return self.rows.shape[0]

    def all_peeks(self) -> bool:
        """Every row's hits is 0: the batch may be sent again."""
        return not self.cols["hits"][self.rows].any()

    def fields(self) -> Dict[str, np.ndarray]:
        """The rows' decide columns, for the shed cache's population."""
        return {k: self.cols[k][self.rows] for k in DECIDE_FIELDS}

    def to_wire(self) -> bytes:
        """The rows as a serialised GetPeerRateLimitsReq, the bytes
        convert.req_to_pb's messages serialise to: one native call."""
        return native_lib().encode_peer_batch(
            self.payload, self.cols, self.rows
        )

    def requests(self) -> List[RateLimitReq]:
        """The rows as request objects, equal to what
        edge_bridge.decode_request_frame makes of the same items: built
        only where a forward FAILED, for the failure code that exists
        (Instance.forward_failed)."""
        c, wire = self.cols, self.payload
        rows = self.rows
        return [
            RateLimitReq(
                name=wire[no : no + nl].decode(),
                unique_key=wire[ko : ko + kl].decode(),
                hits=h, limit=li, duration=d,
                algorithm=Algorithm(a),
                behavior=Behavior(b) if b <= 2 else Behavior.BATCHING,
            )
            for no, nl, ko, kl, h, li, d, a, b in zip(
                *(c[k][rows].tolist() for k in (
                    "name_off", "name_len", "key_off", "key_len", "hits",
                    "limit", "duration", "algo", "behavior",
                ))
            )
        ]


class ForwardAnswers:
    """A peer's answers to one ForwardGroup, in the group's order: four
    int64 columns, `errors` {row: text} for the items the owner
    answered with an error, and `opaque` — the rows whose answer says
    nothing of a stored window (an error, a degraded answer) and must
    not reach the shed cache (ShedCache.observe_resps skips the same)."""

    __slots__ = (
        "status", "limit", "remaining", "reset_time", "errors", "opaque"
    )

    def __init__(self, status, limit, remaining, reset_time,
                 errors=None, opaque=()):
        self.status = status
        self.limit = limit
        self.remaining = remaining
        self.reset_time = reset_time
        self.errors: Dict[int, str] = errors or {}
        self.opaque = list(opaque)

    def __len__(self) -> int:
        return self.status.shape[0]

    @classmethod
    def from_resps(cls, resps) -> "ForwardAnswers":
        """From response objects or protobuf items (a reply the native
        parser declined: one that carries an error or metadata)."""
        n = len(resps)
        cols = np.zeros((4, n), np.int64)
        errors, opaque = {}, []
        for i, r in enumerate(resps):
            cols[0, i] = int(r.status)
            cols[1, i] = r.limit
            cols[2, i] = r.remaining
            cols[3, i] = r.reset_time
            if r.error:
                errors[i] = r.error
                opaque.append(i)
            elif r.metadata.get("degraded"):
                opaque.append(i)
        return cls(*cols, errors=errors, opaque=opaque)

    def resps(self) -> List[RateLimitResp]:
        """As response objects, for a caller that passed requests to
        the flusher (no error, no metadata: a reply with either is
        handed on as the runtime parsed it)."""
        return [
            RateLimitResp(
                status=Status(s), limit=li, remaining=r, reset_time=t
            )
            for s, li, r, t in zip(
                self.status.tolist(), self.limit.tolist(),
                self.remaining.tolist(), self.reset_time.tolist(),
            )
        ]
