"""Closed loop at saturation through the PEER door: each worker is one
other member of the ring, forwarding to the daemon as upstream's
forwarder does (peers.go:143-172): ONE keep-alive gRPC channel, ONE
`PeersV1/GetPeerRateLimits` of `items_per_batch` items outstanding, the
next sent when the last is answered. What is measured is the items
answered inside the window over the window's length.

Every batch is built and serialised before "ready", so a send is bytes:
a repeated protobuf field serialises as its elements one after another,
so a batch is the join of its items' one-item messages, and a canary
takes the last item's place by the same rule.

The harness's own checks can call V1 or GEB only (harness/doors.py), so
the peer door is held to the reference here: before "ready", worker 0
sends `check.checked_sequence` (a seed of its own) through the peer door
and compares with `check.reference_answers`; every answer that differs
counts as a malformed reply in its tally, which the run prints beside
its limit 0 and which decides `correct`.

Traffic parameters (benchmark/traffic/<mix>.json): workers,
items_per_batch, prebuilt_batches_per_s, warmup_s, call_timeout_s,
drain_timeout_s, base_seed, canary_every, canaries_per_worker,
key_classes, algorithms.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

import check
from generators import closed_loop_frames as frames
from harness import keyspace, stats, workers

DOOR = "grpc"  # the harness's checks and canary peeks: V1, the same address
METHOD = "/pb.gubernator.PeersV1/GetPeerRateLimits"
CHECK_SEED_OFFSET = 32_000_011  # worker 0's sequence: not the harness's keys


def build(spec: dict) -> np.ndarray:
    """ids[batches, items], by closed_loop_frames' rule: drawn once from
    the traffic's base seed, the rows then ordered by the run's seed."""
    t = spec["traffic"]
    return frames.build(dict(spec, traffic=dict(
        t, prebuilt_frames_per_s=t["prebuilt_batches_per_s"],
        items_per_frame=t["items_per_batch"],
    )))


def one_item(req) -> bytes:
    """A GetPeerRateLimitsReq that holds `req` alone, serialised."""
    from gubernator_tpu.api import convert
    from gubernator_tpu.api.proto.gen import peers_pb2

    return peers_pb2.GetPeerRateLimitsReq(
        requests=[convert.req_to_pb(req)]
    ).SerializeToString()


def answers(raw: bytes):
    """(status, limit, remaining, an item answered an error) of one
    serialised GetPeerRateLimitsResp, item by item."""
    from gubernator_tpu.api.proto.gen import peers_pb2

    rs = peers_pb2.GetPeerRateLimitsResp.FromString(raw).rate_limits
    return ([r.status for r in rs], [r.limit for r in rs],
            [r.remaining for r in rs], any(r.error for r in rs))


def item_errors(raw: bytes):
    """Which items of one serialised GetPeerRateLimitsResp are errors."""
    from gubernator_tpu.api.proto.gen import peers_pb2

    return [bool(r.error) for r in
            peers_pb2.GetPeerRateLimitsResp.FromString(raw).rate_limits]


async def peer_door_check(call, seed: int, algos, timeout: float) -> dict:
    """check.py's seeded sequence through the peer door, item by item
    against the plain reference."""
    calls = check.checked_sequence(seed, algos)
    got = []
    for batch in calls:
        raw = await call(
            b"".join(one_item(keyspace.req(*item)) for item in batch),
            timeout=timeout,
        )
        status, limit, remaining, error = answers(raw)
        if error or len(status) != len(batch):
            raise RuntimeError("the peer door answered the check with an error")
        got.append(list(zip(status, limit, remaining)))
    want = check.reference_answers(calls, int(time.time() * 1000))
    n, bad, first = check.compare_sequence(calls, got, want)
    over = sum(a[0] == 1 for g in got for a in g)
    if not over:
        raise RuntimeError("the peer door's check drove no key over its limit")
    return {"compared": n, "differ": bad, "limit": 0,
            "over_limit_answers": over, "first_difference": first}


def run_worker(spec: dict, conn) -> None:
    asyncio.run(_run(spec, conn))


async def _run(spec: dict, conn) -> None:
    import grpc

    t = spec["traffic"]
    items = t["items_per_batch"]
    ids = build(spec)
    tally = workers.Tally(spec, ids)
    item_of = dict(zip(
        tally.pool.tolist(),
        map(one_item, keyspace.make_reqs(spec["tag"], tally.pool, tally.rules, 1)),
    ))
    rows = [row.tolist() for row in ids]
    heads = [b"".join(item_of[k] for k in row[:-1]) for row in rows]
    tails = [item_of[row[-1]] for row in rows]
    canary_items = [one_item(tally.canary_req(j))
                    for j in range(len(tally.canaries))]

    channel = grpc.aio.insecure_channel(spec["grpc"])
    call = channel.unary_unary(METHOD)  # no serialisers: bytes out, bytes in
    await channel.channel_ready()
    info = {"batches_prebuilt": len(ids)}
    if spec["worker"] == 0:
        algos = [keyspace.ALGORITHMS[a["algorithm"]] for a in t["algorithms"]]
        info["peer_door_check"] = await peer_door_check(
            call, spec["seed"] + CHECK_SEED_OFFSET, algos, t["call_timeout_s"])
        tally.malformed += info["peer_door_check"]["differ"]
    conn.send(("ready", info))
    loop = asyncio.get_running_loop()
    _, t0 = await loop.run_in_executor(None, conn.recv)
    t_end = t0 + spec["seconds"]
    await asyncio.sleep(max(0.0, t0 - t["warmup_s"] - time.monotonic()))

    sent, done, failed_batches = [], [], 0
    first = time.monotonic()
    cpu0 = time.process_time()
    i = 0
    while time.monotonic() < t_end:
        n = i % len(ids)
        row, payload, canary = ids[n], heads[n], -1
        if i % t["canary_every"] == 0:
            canary = (i // t["canary_every"]) % len(canary_items)
            payload += canary_items[canary]
            row = row[:-1]
        else:
            payload += tails[n]
        i += 1
        ts = time.monotonic()
        try:
            raw = await call(payload, timeout=t["call_timeout_s"])
        except grpc.aio.AioRpcError:
            failed_batches += 1
            tally.lost(row)
            continue
        sent.append(ts)
        done.append(time.monotonic())
        status, limit, remaining, error = answers(raw)
        if len(status) != items:  # not an answer to this batch
            tally.malformed += 1
            tally.lost(row)
            continue
        if canary >= 0:
            tally.canary_replies[canary].append(
                (status.pop(), limit.pop(), remaining.pop()))
        if error:
            errors = item_errors(raw)
            if canary >= 0:  # an error in the canary's place is malformed too
                tally.malformed += errors.pop()
            tally.answered_with_errors(row, status, limit, remaining, errors)
        else:
            tally.answered(row, status, limit, remaining)
    last = time.monotonic()
    cpu_share = (time.process_time() - cpu0) / (last - first)
    await channel.close()
    sent, done = np.array(sent), np.array(done)
    inside = (done >= t0) & (done < t_end)
    conn.send(("done", {
        "batches_in_window": int(inside.sum()),
        "batch_ms": (done - sent)[inside] * 1e3,
        "batches_sent": i, "failed": failed_batches,
        "wrapped": max(0, i - len(ids)),
        "first_sent": first, "last_done": last,
        "cpu_share": cpu_share,
        "tally": tally.result(),
    }))


def summarize(results, spec: dict) -> dict:
    items = spec["traffic"]["items_per_batch"]
    batches = sum(r["batches_in_window"] for r in results)
    failed = sum(r["failed"] for r in results)
    batch_ms = np.sort(np.concatenate([r["batch_ms"] for r in results]))
    return {
        "attempted": (batches + failed) * items, "failed": failed * items,
        "end_to_end": {
            "decisions_per_s": (batches * items / spec["seconds"], "decisions/s"),
        },
        "generator": {
            "batches_per_s": batches / spec["seconds"],
            "batch_p50_ms": stats.percentile(batch_ms, 50) if len(batch_ms) else None,
            "batch_p99_ms": stats.percentile(batch_ms, 99) if len(batch_ms) else None,
            "batches_wrapped": sum(r["wrapped"] for r in results),
            # process time / wall from a worker's first send to its last
            # reply: above ~0.6 of a core the generator is what is measured
            "worker_cpu_share": [round(r["cpu_share"], 4) for r in results],
        },
    }
