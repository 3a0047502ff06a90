"""The GEB door's split of a string frame by owner (PR 43), on the
`ring4` deployment cut to the CPU's size (tests/test_ring4_served.py's
ring: four device-backend daemons, the clock pinned, every door open).

Differential: the same seeded frames of mixed ownership go through
node 0's door twice from the same empty state — once with the split
live, once with it forced to decline (a monkeypatch of `_plan_split`
here, no option) so that `Instance.get_rate_limits` serves them through
request objects — and the reply FRAMES are byte-identical (owner tags,
error fields, order), the shed cache holds the same entries, the
`peer_forward_*` / `peer_serve_*` counts agree and the answers equal
`benchmark/reference_ring4.py`.

Failure: an owner that hangs past the deadline, one that is down, a
breaker that opens mid-frame, a peer client closed mid-batch — the
frame's bytes equal the object path's for the same frame and fault
(per-item error text, takeover and degraded answers), the other groups
of the frame are answered, and the door's connection answers the next
frame.

libguberhash.so is git-ignored: like tests/test_string_frame_native.py
this file builds it out of tree where the checkout has none or a stale
one and lends it to the process's hashing singleton.
"""

import asyncio
import random
import struct
import sys

import pytest

from gubernator_tpu.api.types import Behavior
from gubernator_tpu.cluster import LocalCluster
from gubernator_tpu.core import hashing, oracle
from gubernator_tpu.serve import peers as peers_mod
from gubernator_tpu.serve.config import config_from_env
from gubernator_tpu.serve.edge_bridge import MAGIC_RESP, FrameService
from gubernator_tpu.serve.faults import FAULTS
from gubernator_tpu.serve.metrics import REGISTRY
from gubernator_tpu.serve.server import make_backend
from gubernator_tpu.serve.stages import STAGES
from test_global_mesh4_served import FakeClock, T0
from test_ring4_served import (
    BENCH,
    FRAME,
    NAME,
    NODES,
    deployment_env,
    frames_of,
    item,
    ring_ports,
    to_req,
)

sys.path.insert(0, BENCH)
import reference_ring  # noqa: E402
import reference_ring4  # noqa: E402

@pytest.fixture(scope="module")
def ring(native):
    """tests/test_ring4_served.py's ring, with the native library lent
    and every node's HTTP door open."""
    import gubernator_tpu.api.types as types_mod
    import gubernator_tpu.core.engine as engine_mod

    clock = FakeClock()
    mp = pytest.MonkeyPatch()
    for mod in (types_mod, engine_mod, oracle):
        mp.setattr(mod, "millisecond_now", clock)
    # the sketch tier off: its window-aligned reset_time (one
    # millisecond short of a fresh window's) reaches a replayed key
    # through the promoter, which remembers the run before the reset
    conf = config_from_env({**deployment_env(), "GUBER_SKETCH": "0"})
    ports = ring_ports(3)
    addresses = [f"127.0.0.1:{p}" for p in ports[:NODES]]
    cluster = LocalCluster(
        addresses, backend_factory=lambda: make_backend(conf),
        geb_ports=ports[NODES : 2 * NODES],
        http_addresses=[f"127.0.0.1:{p}" for p in ports[2 * NODES :]],
        device_batch_limit=conf.device_batch_limit,
    )
    cluster.start(timeout=900.0)
    for server in cluster.servers:
        server.instance.shed.now_fn = clock
        # four daemons share one interpreter beside other test workers:
        # the answers' tests give a forward room; the deadline's own
        # test sets upstream's 0.5 s back
        server.instance.conf.behaviors.batch_timeout = 20.0
    try:
        yield cluster, [f"127.0.0.1:{p}" for p in ports[NODES : 2 * NODES]]
    finally:
        cluster.stop()
        mp.undo()


# -- helpers ------------------------------------------------------------------


def wire_item(it, behavior: int = 0, name: str = NAME) -> bytes:
    key, hits, limit, duration, algo = it
    nb, kb = name.encode(), key.encode()
    return (struct.pack("<H", len(nb)) + nb + struct.pack("<H", len(kb)) + kb
            + struct.pack("<qqqBB", hits, limit, duration, algo, behavior))


def payload_of(frame, behaviors=None) -> bytes:
    return b"".join(
        wire_item(it, behaviors[i] if behaviors else 0)
        for i, it in enumerate(frame))


def door_of(cluster, i: int = 0) -> FrameService:
    return cluster.servers[i]._geb


def serve(cluster, payloads, node: int = 0, timeout: float = 120.0):
    """Each payload through node's GEB door core, one at a time: the
    reply frames' bytes."""
    door = door_of(cluster, node)

    async def run():
        return [await door._decide_string_frame(p, n) for p, n in payloads]

    return cluster.run(run(), timeout=timeout)


def reset(cluster):
    """Every node's store and shed cache empty again (the clock stands
    still, so a replay from here gives the same answers)."""
    for i in range(NODES):
        inst = cluster.instance_at(i)
        cluster.run(inst.batcher.run_serialized(inst.backend.engine.reset))
        inst.shed.refresh_generation()
        assert len(inst.shed) == 0
        for p in inst.peer_list():  # and every breaker closed
            p.breaker = p._make_breaker()


def shed_entries(cluster, i: int = 0):
    return {h: e[:3] for h, e in cluster.instance_at(i).shed._entries.items()}


def counts(cluster):
    fwd = cluster.instance_at(0).peer_forward
    split = cluster.instance_at(0).edge_split
    owners = [cluster.instance_at(i) for i in range(1, NODES)]
    return {
        "fwd_items": fwd.items, "fwd_failed": dict(fwd.failed),
        "served_items": sum(o.peer_serve_items for o in owners),
        "folded_items": sum(o.peer_serve_folded_items for o in owners),
        "shed_hits": sum(o.peer_serve_shed_hits for o in owners),
        "split_frames": split.frames, "split_items": dict(split.items),
        "declined": dict(split.declined),
    }


def grew(after, before):
    out = {}
    for k, v in after.items():
        out[k] = ({r: v[r] - before[k][r] for r in v} if isinstance(v, dict)
                  else v - before[k])
    return out


def decode(frame: bytes):
    """[(status, limit, remaining, reset, error, owner)] of a GEB3 frame."""
    magic, n = struct.unpack_from("<II", frame, 0)
    assert magic == MAGIC_RESP
    off, out = 8, []
    for _ in range(n):
        st, li, re, rt = struct.unpack_from("<Bqqq", frame, off)
        off += 25
        (el,) = struct.unpack_from("<H", frame, off)
        err = frame[off + 2 : off + 2 + el].decode()
        off += 2 + el
        (ol,) = struct.unpack_from("<H", frame, off)
        owner = frame[off + 2 : off + 2 + ol].decode()
        off += 2 + ol
        out.append((st, li, re, rt, err, owner))
    assert off == len(frame)
    return out


@pytest.fixture
def object_path(monkeypatch):
    """Force the split to decline: the frame goes where it went before
    PR 43, whole."""

    def on():
        monkeypatch.setattr(
            FrameService, "_plan_split", lambda self, *a: "error")

    return on


def both_ways(cluster, payloads, object_path, monkeypatch, between=None):
    """(split frames, object frames, split growth, object growth, shed
    entries each way) of the same payloads from the same empty state."""
    reset(cluster)
    if between:
        between()
    c0 = counts(cluster)
    live = serve(cluster, payloads)
    c1 = counts(cluster)
    shed_live = shed_entries(cluster)
    reset(cluster)
    if between:
        between()
    object_path()
    c2 = counts(cluster)
    plain = serve(cluster, payloads)
    c3 = counts(cluster)
    shed_plain = shed_entries(cluster)
    monkeypatch.undo()
    return live, plain, grew(c1, c0), grew(c3, c2), shed_live, shed_plain


def owner_of(cluster, it) -> str:
    return reference_ring.owner_of(f"{NAME}_{it[0]}", cluster.addresses)


# -- differential -------------------------------------------------------------


@pytest.mark.parametrize("seed", [43, 2**31 + 43])
def test_seeded_mixed_frames_split_equals_object_path_and_the_reference(
    ring, object_path, monkeypatch, seed
):
    cluster, _ = ring
    frames = frames_of(seed, 6, tag=f"s{seed}-")
    payloads = [(payload_of(fr), len(fr)) for fr in frames]
    live, plain, g_live, g_plain, shed_live, shed_plain = both_ways(
        cluster, payloads, object_path, monkeypatch)
    diff = [(f, j, it, a, b) for f, fr in enumerate(frames)
            for j, (it, a, b) in enumerate(zip(fr, decode(live[f]), decode(plain[f])))
            if a != b]
    assert not diff, diff[:6]
    assert live == plain  # owner tags, order, every byte
    assert shed_live == shed_plain and len(shed_live) > 0
    assert g_live["split_frames"] == len(frames)
    assert g_plain["split_frames"] == 0
    assert g_plain["declined"]["error"] == len(frames)
    assert not any(g_live["declined"].values())
    for k in ("fwd_items", "served_items", "folded_items", "shed_hits",
              "fwd_failed"):
        assert g_live[k] == g_plain[k], k
    assert g_live["fwd_items"] == g_live["served_items"] == g_live["folded_items"]
    lanes = g_live["split_items"]
    assert sum(lanes.values()) == len(frames) * FRAME
    assert lanes["forwarded"] == g_live["fwd_items"] > 0
    assert lanes["shed"] > 0 and lanes["owned"] > 0
    # the answers are the ring's, and a forwarded answer carries its
    # owner's address, shed or not; an owned one carries none
    model = reference_ring4.Ring(cluster.addresses)
    me = cluster.addresses[0]
    shed_foreign = 0
    for fr, frame in zip(frames, live):
        want = model.call(fr, T0, name=NAME, asked=me)
        got = decode(frame)
        assert [g[:3] for g in got] == want
        for it, g in zip(fr, got):
            owner = owner_of(cluster, it)
            assert g[4] == "" and g[5] == ("" if owner == me else owner)
            shed_foreign += owner != me and g[0] == 1
    assert shed_foreign > 50


@pytest.mark.parametrize("case", ["every_row_foreign", "no_row_foreign",
                                  "one_row_foreign", "one_row_owned"])
def test_the_shares_of_ownership(ring, object_path, monkeypatch, case):
    cluster, _ = ring
    me = cluster.addresses[0]
    mine, theirs, i = [], [], 0
    while len(mine) < 300 or len(theirs) < 300:
        it = item(700_000 + i, 1, f"o-{case}-")
        (mine if owner_of(cluster, it) == me else theirs).append(it)
        i += 1
    frame = {
        "every_row_foreign": theirs[:300],
        "no_row_foreign": mine[:300],
        "one_row_foreign": mine[:150] + theirs[:1] + mine[150:299],
        "one_row_owned": theirs[:150] + mine[:1] + theirs[150:299],
    }[case]
    payloads = [(payload_of(frame), len(frame))] * 2  # twice: hits add up
    live, plain, g_live, g_plain, shed_live, shed_plain = both_ways(
        cluster, payloads, object_path, monkeypatch)
    assert live == plain and shed_live == shed_plain
    foreign = sum(owner_of(cluster, it) != me for it in frame)
    if case == "no_row_foreign":  # today's fold, untouched: no split
        assert g_live["split_frames"] == 0 and g_live["fwd_items"] == 0
        assert not any(g_live["declined"].values())
        assert not any(g_plain["declined"].values())
    else:
        assert g_live["split_frames"] == 2
        assert g_live["split_items"]["forwarded"] == g_live["fwd_items"]
    assert g_live["fwd_items"] == g_plain["fwd_items"] <= 2 * foreign
    tags = [g[5] for g in decode(live[0])]
    assert tags == ["" if owner_of(cluster, it) == me else owner_of(cluster, it)
                    for it in frame]


def test_duplicates_of_one_key_and_peeks_across_the_split(
    ring, object_path, monkeypatch
):
    """A key's rows all lie in one lane, in frame order: duplicates of a
    foreign key decide as one batch at its owner, of an owned key in
    one device batch, as on the object path; a frame of peeks (hits 0:
    the idempotent retry rule's case) reads what they left."""
    cluster, _ = ring
    rng = random.Random(7)
    base = [item(800_000 + i, 1, "dup-") for i in range(40)]
    frame = [rng.choice(base) for _ in range(400)]
    peeks = [(k, 0, li, d, a) for k, _, li, d, a in base]
    payloads = [(payload_of(frame), len(frame)), (payload_of(peeks), len(peeks))]
    live, plain, g_live, g_plain, *_ = both_ways(
        cluster, payloads, object_path, monkeypatch)
    assert live == plain
    assert g_live["split_frames"] == 2 and g_live["fwd_items"] == g_plain["fwd_items"]
    model = reference_ring4.Ring(cluster.addresses)
    for fr, got in zip((frame, peeks), live):
        assert [g[:3] for g in decode(got)] == model.call(
            fr, T0, name=NAME, asked=cluster.addresses[0])
    owners = {owner_of(cluster, it) for it in base}
    assert owners == set(cluster.addresses)


@pytest.mark.parametrize("manager", ["replication", "rescale", "checkpoint"])
def test_owned_global_rows_of_a_split_frame_with_a_manager_on(
    ring, object_path, monkeypatch, manager
):
    """GUBER_REPLICATION / GUBER_RESCALE / GUBER_CHECKPOINT_DIR on a
    ring node: the managers note the OWNED rows of a split frame, and
    its owned GLOBAL rows queue their status broadcast from the FRAME's
    columns — a GLOBAL row's frame index lies past the owned rows'
    slice in the first frame and inside it, on another row's limit and
    duration, in the second. The same bytes, the same queued updates
    and the same tracked keys as the object path."""
    from gubernator_tpu.serve.checkpoint import CheckpointManager
    from gubernator_tpu.serve.replication import ReplicationManager
    from gubernator_tpu.serve.rescale import RescaleManager

    cluster, _ = ring
    inst = cluster.instance_at(0)
    mine, theirs = _foreign_and_owned(cluster, f"g-{manager}-", 40, 12)
    # limits differ row by row, so a row read at another's index shows
    mine = [(k, 1, 50 + 3 * i, d, a) for i, (k, _, _, d, a) in enumerate(mine)]
    theirs = [(k, 1, 900 + i, d, a) for i, (k, _, _, d, a) in enumerate(theirs)]
    tail = theirs[:30] + mine  # owned rows at 30..41: past a slice of 12
    laced = [it for pair in zip(theirs[:12], mine) for it in pair]
    frames = [tail, laced + [laced[1]]]  # and a GLOBAL key twice: last wins
    me = cluster.addresses[0]
    payloads = [
        (payload_of(fr, [int(Behavior.GLOBAL) * (owner_of(cluster, it) == me
                                                   and j % 2 == 1)
                         for j, it in enumerate(fr)]), len(fr))
        for fr in frames]
    kind, attr = {
        "replication": (ReplicationManager, "repl"),
        "rescale": (RescaleManager, "rescale"),
        "checkpoint": (CheckpointManager, "checkpoint"),
    }[manager]
    queued = []
    real = inst.global_mgr._update_peers

    async def recording(updates):
        queued.append({k: (r.name, r.unique_key, r.hits, r.limit, r.duration,
                           int(r.algorithm), int(r.behavior))
                       for k, r in updates.items()})
        await real(updates)

    def run(forced):
        reset(cluster)
        if forced:
            object_path()
        # never started: what the door notes stays where it was put
        mgr = cluster.run(_made(kind, inst))
        monkeypatch.setattr(inst, attr, mgr)
        monkeypatch.setattr(inst.global_mgr, "_update_peers", recording)
        del queued[:]
        before = counts(cluster)
        out = []
        for p in payloads:
            out += serve(cluster, [p])
            # the broadcast's peek lands between the frames both ways
            cluster.run(inst.global_mgr.drain(), timeout=60.0)
        after = grew(counts(cluster), before)
        noted = dict(mgr._dirty if manager == "replication" else mgr._tracked)
        monkeypatch.undo()
        return out, [dict(q) for q in queued], noted, after

    live, q_live, noted_live, g_live = run(False)
    plain, q_plain, noted_plain, g_plain = run(True)
    assert g_live["split_frames"] == 2 and not any(g_live["declined"].values())
    assert g_plain["declined"]["error"] == 2
    assert live == plain
    merged = [{k: v for q in qs for k, v in q.items()} for qs in (q_live, q_plain)]
    assert merged[0] == merged[1]
    globals_sent = {f"{NAME}_{it[0]}": it for fr in frames
                    for j, it in enumerate(fr)
                    if owner_of(cluster, it) == me and j % 2 == 1}
    assert set(merged[0]) == set(globals_sent) and len(globals_sent) >= 12
    for key, (_, ukey, hits, limit, duration, algo, behavior) in merged[0].items():
        it = globals_sent[key]
        assert (ukey, hits, limit, duration, algo) == it
        assert behavior == int(Behavior.GLOBAL)
    # the managers hold the owned token-bucket rows, and no foreign one
    assert noted_live == noted_plain
    assert set(noted_live) == {f"{NAME}_{it[0]}" for it in mine if it[4] == 0}
    for it, a in zip(frames[0], decode(live[0])):
        assert a[4] == "" and a[:3] == (0, it[2], it[2] - 1)


async def _made(kind, inst):
    return kind(inst.conf, inst)


def test_a_group_of_peeks_is_idempotent_and_one_with_hits_is_not(native):
    from gubernator_tpu.api.columns import ForwardGroup

    frame = [("a", 0, 5, 1000, 0), ("b", 1, 5, 1000, 0), ("c", 0, 5, 1000, 1)]
    payload = payload_of(frame)
    got, cols, _ = native.parse_string_frame(payload, 3)
    assert got == 3
    assert ForwardGroup(payload, cols, [0, 2]).all_peeks()
    assert not ForwardGroup(payload, cols, [0, 1]).all_peeks()
    reqs = ForwardGroup(payload, cols, [1, 2]).requests()
    assert reqs == [to_req(frame[1]), to_req(frame[2])]


def _foreign_and_owned(cluster, tag, n_foreign=20, n_owned=20):
    me = cluster.addresses[0]
    mine, theirs, i = [], [], 0
    while len(mine) < n_owned or len(theirs) < n_foreign:
        it = item(900_000 + i, 1, tag)
        (mine if owner_of(cluster, it) == me else theirs).append(it)
        i += 1
    return mine[:n_owned], theirs[:n_foreign]


@pytest.mark.parametrize("reason", [
    "foreign_global", "foreign_no_batching", "invalid_item", "chain",
    "rescale_transition", "no_arrays", "no_native", "too_many_items",
    "error",
])
def test_each_decline_reason_is_counted_once_and_the_frame_answered(
    ring, monkeypatch, reason
):
    cluster, _ = ring
    reset(cluster)
    inst = cluster.instance_at(0)
    door = door_of(cluster)
    mine, theirs = _foreign_and_owned(cluster, f"d-{reason}-")
    frame = mine + theirs
    behaviors = [0] * len(frame)
    payload, n = None, len(frame)
    chain = False
    if reason == "foreign_global":
        behaviors[len(mine)] = int(Behavior.GLOBAL)
    elif reason == "foreign_no_batching":
        behaviors[len(mine) + 1] = int(Behavior.NO_BATCHING)
    elif reason == "invalid_item":
        payload = payload_of(frame) + wire_item(("", 1, 5, 1000, 0))
        n += 1
    elif reason == "chain":
        chain = True
    elif reason == "rescale_transition":
        class _Open:
            _transition = object()

            def route_override(self, key, r):
                return None

            def note_owned(self, r):
                pass

            def pending_pop(self, key):
                return None

        monkeypatch.setattr(inst, "rescale", _Open())
    elif reason == "no_arrays":
        # what a host backend answers
        monkeypatch.setattr(door, "_arrays_ok", lambda: False)
    elif reason == "no_native":
        hide_native_lib(monkeypatch)
    elif reason == "too_many_items":
        frame = frame + [item(950_000 + i, 1, "big-") for i in range(1001)]
        behaviors, n = None, len(frame)
    elif reason == "error":
        def boom(*a, **k):
            raise RuntimeError("boom")

        monkeypatch.setattr(inst.traffic, "observe", boom)
    if payload is None:
        payload = payload_of(frame, behaviors)
    before = counts(cluster)

    async def run():
        if chain:
            from gubernator_tpu.serve.edge_bridge import MAGIC_WCHAIN

            items = b"".join(wire_item(it) + b"\x00" for it in frame)
            return await door.serve_frame_bytes(
                struct.pack("<IIIQI", MAGIC_WCHAIN, n, 7, 0, len(items)) + items)
        return await door._decide_string_frame(payload, n)

    if reason == "error":
        # the object path observes too: let it, after the split's try
        calls = []
        real = type(inst.traffic).observe

        def once(*a, **k):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("boom")
            return real(inst.traffic, *a, **k)

        monkeypatch.setattr(inst.traffic, "observe", once)
    got = cluster.run(run(), timeout=60.0)
    after = grew(counts(cluster), before)
    assert after["declined"] == {
        r: int(r == reason) for r in peers_mod.SPLIT_DECLINE_REASONS}
    assert after["split_frames"] == 0
    if chain:
        got = got[:4] + got[8:]  # GEB4: drop the frame id
        got = struct.pack("<II", MAGIC_RESP, n) + got[8:]
    answers = decode(got)
    assert len(answers) == n
    bad = [a for a in answers if a[4]]
    assert len(bad) == (1 if reason == "invalid_item" else 0)


def hide_native_lib(monkeypatch):
    """The one handle says absent, to every node of this process."""
    monkeypatch.setattr(hashing, "_native", None)
    monkeypatch.setattr(hashing, "_native_checked", True)


# -- failure -------------------------------------------------------------------


def _hang_then_compare(cluster, frame, object_path, monkeypatch, spec, setup=None):
    """The frame through the split and through the object path under
    the same fault from the same state; (split bytes, object bytes)."""
    payload = [(payload_of(frame), len(frame))]
    out = []
    for forced in (False, True):
        reset(cluster)
        if forced:
            object_path()
        if setup:
            setup()
        if spec:
            FAULTS.configure(spec)
        try:
            out.append(serve(cluster, payload)[0])
        finally:
            FAULTS.clear()
            monkeypatch.undo()
    return out


def test_an_owner_that_hangs_past_the_deadline(ring, object_path, monkeypatch, caplog):
    """`peer_rpc:hang:n=1`: the frame's first forward never leaves; its
    rows come back as the object path's error items (the text names the
    deadline), the other groups' rows are answered, nothing is re-sent,
    and the same connection answers the next frame."""
    cluster, doors = ring
    mine, theirs = _foreign_and_owned(cluster, "hang-", 60, 20)
    frame = mine + theirs
    conf = cluster.instance_at(0).conf.behaviors

    def short():
        monkeypatch.setattr(conf, "batch_timeout", 0.5)

    live, plain = _hang_then_compare(
        cluster, frame, object_path, monkeypatch, "peer_rpc:hang:n=1", short)
    a_live, a_plain = decode(live), decode(plain)
    # whose group hung is the first the flusher sent: the same owner
    # both ways (one frame, groups queued in ring order) or not — the
    # texts name it; compare by shape, then bytes where it is the same
    failed_live = {owner_of(cluster, it) for it, a in zip(frame, a_live) if a[4]}
    failed_plain = {owner_of(cluster, it) for it, a in zip(frame, a_plain) if a[4]}
    assert len(failed_live) == len(failed_plain) == 1
    (victim,) = failed_live
    for it, a in zip(frame, a_live):
        if owner_of(cluster, it) == victim:
            assert a[:4] == (0, 0, 0, 0) and a[5] == ""
            assert "from peer" in a[4] and "GUBER_BATCH_TIMEOUT_MS = 500 ms" in a[4]
            assert f"'{NAME}_{it[0]}'" in a[4] and "not sent again" in a[4]
        else:
            assert a[4] == "" and a[:3] == (0, it[2], it[2] - 1)
    if failed_live == failed_plain:
        strip = lambda rows: [  # noqa: E731  the seconds waited differ
            (*r[:4], r[4].split(" after ")[0], r[4].split(" s: ")[-1], r[5])
            for r in rows]
        assert strip(a_live) == strip(a_plain)

    # the door's connection stays open: a frame with a hung forward and
    # the next one on the SAME connection
    from gubernator_tpu.client_geb import AsyncGebClient

    async def two():
        c = AsyncGebClient(doors[0], mode="string")
        await c.connect()
        try:
            FAULTS.configure("peer_rpc:hang:n=1")
            conf.batch_timeout = 0.5
            try:
                first = await c.get_rate_limits(
                    [to_req(it) for it in frame], timeout=60.0)
            finally:
                FAULTS.clear()
                conf.batch_timeout = 20.0
            second = await c.get_rate_limits(
                [to_req((k, 0, li, d, a)) for k, _, li, d, a in frame],
                timeout=60.0)
            return first, second
        finally:
            await c.close()

    before = counts(cluster)
    first, second = asyncio.run(two())
    after = grew(counts(cluster), before)
    assert sum(bool(r.error) for r in first) > 0
    assert all(not r.error for r in second)
    assert after["split_frames"] == 2 and not any(after["declined"].values())
    assert sum(after["fwd_failed"].values()) == after["fwd_failed"]["deadline"] > 0


def test_an_owner_that_is_down_and_a_breaker_that_opens(
    ring, object_path, monkeypatch
):
    """An injected transport error on every forward: each group fails,
    every foreign row is an error item in the object path's words and
    every owned row is answered; after enough failures the breaker
    opens and the next frame's rows say so — the same bytes either
    way."""
    cluster, _ = ring
    mine, theirs = _foreign_and_owned(cluster, "down-", 45, 15)
    frame = mine + theirs
    payloads = [(payload_of(frame), len(frame))] * 4
    out = []
    for forced in (False, True):
        reset(cluster)
        if forced:
            object_path()
        FAULTS.configure("peer_rpc:error")
        try:
            out.append(serve(cluster, payloads))
        finally:
            FAULTS.clear()
            monkeypatch.undo()
    live, plain = out
    assert live == plain
    me = cluster.addresses[0]
    texts = set()
    for got in live:
        for it, a in zip(frame, decode(got)):
            if owner_of(cluster, it) == me:
                assert a[4] == "" and a[0] == 0
            else:
                assert a[4].startswith(
                    f"while fetching rate limit '{NAME}_{it[0]}' from peer - '")
                assert a[:4] == (0, 0, 0, 0) and a[5] == ""
                texts.add(a[4].split(" - '", 1)[1])
    assert any("circuit open" in t for t in texts), texts  # it opened mid-run
    assert any("circuit open" not in t for t in texts)


def test_degraded_answers_equal_the_object_paths(ring, object_path, monkeypatch):
    cluster, _ = ring
    mine, theirs = _foreign_and_owned(cluster, "degr-", 30, 10)
    frame = mine + theirs
    conf = cluster.instance_at(0).conf

    def degraded():
        monkeypatch.setattr(conf, "degraded_local", True, raising=False)

    live, plain = _hang_then_compare(
        cluster, frame, object_path, monkeypatch, "peer_rpc:error", degraded)
    assert live == plain
    me = cluster.addresses[0]
    for it, a in zip(frame, decode(live)):
        assert a[4] == "" and a[:3] == (0, it[2], it[2] - 1)
        assert a[5] == ("" if owner_of(cluster, it) == me else owner_of(cluster, it))
    # a degraded answer says nothing of the owner's window: not cached
    assert shed_entries(cluster) == {}


def test_a_peer_client_closed_mid_batch(ring, monkeypatch):
    """The flusher cancelled with a column group queued: its rows are
    error items ("closed"), the other groups are answered, the frame
    is answered whole."""
    cluster, _ = ring
    reset(cluster)
    inst = cluster.instance_at(0)
    mine, theirs = _foreign_and_owned(cluster, "closed-", 60, 10)
    frame = mine + theirs
    victim = next(p for p in inst.peer_list() if not p.is_owner)
    door = door_of(cluster)
    before = counts(cluster)

    async def run():
        FAULTS.configure("peer_rpc:hang")
        try:
            task = asyncio.ensure_future(
                door._decide_string_frame(payload_of(frame), len(frame)))
            await asyncio.sleep(0.2)  # the RPCs are out, hanging
            await victim.close()
            FAULTS.clear()
            victim.connect()
            return await asyncio.wait_for(task, 60.0)
        finally:
            FAULTS.clear()

    conf = inst.conf.behaviors
    monkeypatch.setattr(conf, "batch_timeout", 2.0)
    got = decode(cluster.run(run(), timeout=90.0))
    after = grew(counts(cluster), before)
    hers = [it for it in frame if owner_of(cluster, it) == victim.host]
    assert hers and after["fwd_failed"]["closed"] == len(hers)
    for it, a in zip(frame, got):
        owner = owner_of(cluster, it)
        if owner == victim.host:
            assert "closed" in a[4] and a[:4] == (0, 0, 0, 0)
        elif owner == cluster.addresses[0]:
            assert a[4] == "" and a[:3] == (0, it[2], it[2] - 1)
        else:  # the others hung to the deadline: error items, not lost rows
            assert a[4] == "" or "from peer" in a[4]


def test_an_error_item_from_the_owner_keeps_its_tag(ring, object_path, monkeypatch):
    """`peer_serve:error` at the owners: their whole reply is error
    items (PeerAnswers.failed), which the native reply parser declines
    and the runtime parses; the frame carries the text under the
    owner's tag, as the object path's does, and caches nothing."""
    cluster, _ = ring
    mine, theirs = _foreign_and_owned(cluster, "oerr-", 30, 10)
    frame = mine + theirs
    live, plain = _hang_then_compare(
        cluster, frame, object_path, monkeypatch, "peer_serve:error")
    assert live == plain
    me = cluster.addresses[0]
    for it, a in zip(frame, decode(live)):
        owner = owner_of(cluster, it)
        if owner == me:
            assert a[4] == "" and a[5] == ""
        else:
            assert a[4] and a[5] == owner
    assert shed_entries(cluster) == {}


# -- counters and the boot line ------------------------------------------------


def test_the_counters_reach_the_scrape_and_the_stages_stamp_once(ring):
    cluster, _ = ring
    reset(cluster)
    frames = frames_of(5, 4, tag="scr-")
    payloads = [(payload_of(fr), len(fr)) for fr in frames]
    snap0 = {k: v["count"] for k, v in STAGES.snapshot()["stages"].items()}
    before = counts(cluster)
    serve(cluster, payloads)
    after = grew(counts(cluster), before)
    snap1 = {k: v["count"] for k, v in STAGES.snapshot()["stages"].items()}
    g = lambda name: snap1.get(name, 0) - snap0.get(name, 0)  # noqa: E731
    assert g("instance_route") == g("bridge_decode") == g("encode") == 4
    assert g("forward_wait") == 4 and g("shed") == 8
    assert g("forward_queue") == 4 * (NODES - 1)
    assert g("forward_encode") == g("forward_rpc") == g("forward_decode") == 4 * (NODES - 1)
    cluster.servers[0]._refresh_store_metrics()
    split = cluster.instance_at(0).edge_split
    assert REGISTRY.get_sample_value("edge_split_frames_total") == split.frames
    for lane, v in split.items.items():
        assert REGISTRY.get_sample_value(
            "edge_split_items_total", {"lane": lane}) == v
    for reason, v in split.declined.items():
        assert REGISTRY.get_sample_value(
            "edge_split_declined_total", {"reason": reason}) == v
    assert after["split_frames"] == 4


def test_the_boot_line_says_whether_the_split_is_live(ring, caplog, monkeypatch):
    import logging

    from gubernator_tpu.api.types import PeerInfo

    cluster, _ = ring
    inst = cluster.instance_at(0)
    infos = [PeerInfo(address=a, is_owner=(a == cluster.addresses[0]))
             for a in cluster.addresses]
    with caplog.at_level(logging.INFO, logger="gubernator_tpu.instance"):
        cluster.run(inst.set_peers(infos))
        hide_native_lib(monkeypatch)
        cluster.run(inst.set_peers(infos))
    lines = [r.getMessage() for r in caplog.records if "ring of 4" in r.getMessage()]
    assert len(lines) == 2
    assert "split by owner as columns" in lines[0]
    assert "request objects" in lines[1] and "no_native" in lines[1]


def test_a_ring_members_profile_capture_leaves_the_python_tracer_off(ring):
    """Stopping a Python-tracer capture holds the GIL past a forward's
    deadline on a busy owner, so on a shared ring /v1/debug/profile
    defaults to the tracer off; `python=1` is still an answer away."""
    import json
    import urllib.request

    cluster, _ = ring
    http = cluster.http_addresses[1]

    def capture(query):
        with urllib.request.urlopen(
                f"http://{http}/v1/debug/profile?ms=20&name=split{query}",
                timeout=120) as r:
            return json.load(r)

    by_default = capture("")
    assert by_default["python"] == 0
    assert by_default["python_from"] == "default: a member of a shared ring"
    asked = capture("&python=1")
    assert (asked["python"], asked["python_from"]) == (1, "query")
    # PR 46: the reply says what stopping the capture cost the loop
    for reply in (by_default, asked):
        assert 0 <= reply["loop_held_s"] <= reply["stop_s"] and reply["stop_s"] > 0
