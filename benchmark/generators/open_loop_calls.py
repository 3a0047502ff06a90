"""Open loop: GetRateLimits calls over the daemon's gRPC door on a
seeded Poisson schedule at a fixed rate, whatever the daemon does. A
call's latency runs from the instant it was DUE, so a stall shows in
every call it delays; how late the generator itself sent is reported
beside it (`late_*`, and `late_events`: seconds into the window and ms,
per worker, of every send more than 5 ms late).

Traffic parameters (benchmark/traffic/<mix>.json): workers,
items_per_call, warmup_s, call_timeout_s, base_seed, canary_every,
canaries_per_worker, key_classes, algorithms. The cell gives `rate`
(calls a second, all workers together).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from harness import keyspace, stats, workers

DOOR = "grpc"


def _schedule(rate: float, span: float, rng) -> np.ndarray:
    """round(rate * span) Poisson arrivals, as offsets in [0, span):
    exponential gaps scaled so they fill the span exactly, so every
    seed offers the same number of calls."""
    n = max(1, round(rate * span))
    gaps = rng.exponential(1.0, n + 1)
    return np.cumsum(gaps)[:n] * (span / gaps.sum())


def build(spec: dict):
    """(offsets from the warm-up's start, ids[n, items], n_warm): the
    same gaps and the same keys for every seed, in an order the seed
    picks, for the warm-up and for the window apart."""
    t, w = spec["traffic"], spec["worker"]
    rate = spec["cell"]["rate"] / t["workers"]
    base = np.random.default_rng([t["base_seed"], w])
    offsets, ids = [], []
    for stream, (start, span) in enumerate(
        ((0.0, t["warmup_s"]), (t["warmup_s"], spec["seconds"]))
    ):
        off = _schedule(rate, span, base)
        gaps = keyspace.seeded_order(np.diff(off, prepend=0.0), spec["seed"],
                                     2 * w + stream)
        offsets.append(start + np.cumsum(gaps))
        draws = keyspace.zipf_ids(
            spec["config"]["key_population"], (len(off), t["items_per_call"]),
            base,
        )
        ids.append(keyspace.seeded_order(draws, spec["seed"], 100 + 2 * w + stream))
    return np.concatenate(offsets), np.concatenate(ids), len(offsets[0])


def run_worker(spec: dict, conn) -> None:
    asyncio.run(_run(spec, conn))


async def _run(spec: dict, conn) -> None:
    import grpc

    from gubernator_tpu.api import convert
    from gubernator_tpu.api.grpc_glue import V1Stub
    from gubernator_tpu.api.proto.gen import gubernator_pb2

    t = spec["traffic"]
    offsets, ids, n_warm = build(spec)
    n = len(offsets)
    tally = workers.Tally(spec, ids)
    canary_of = np.full(n, -1)
    slots = np.arange(0, n, t["canary_every"])
    canary_of[slots] = np.arange(len(slots)) % len(tally.canaries)
    reqs = []
    for i in range(n):
        items = keyspace.make_reqs(spec["tag"], ids[i], tally.rules, 1)
        if canary_of[i] >= 0:  # the call's last item gives way to a canary
            items[-1] = tally.canary_req(canary_of[i])
        reqs.append(gubernator_pb2.GetRateLimitsReq(
            requests=[convert.req_to_pb(r) for r in items]
        ))

    channel = grpc.aio.insecure_channel(spec["grpc"])
    stub = V1Stub(channel)
    await channel.channel_ready()
    conn.send(("ready", {"calls": n}))
    loop = asyncio.get_running_loop()
    _, t0 = await loop.run_in_executor(None, conn.recv)
    due = (t0 - t["warmup_s"]) + offsets
    sent = np.zeros(n)
    done = np.full(n, np.nan)
    width = t["items_per_call"]
    status = np.zeros((n, width), np.int8)
    limit = np.zeros((n, width), np.int64)
    remaining = np.zeros((n, width), np.int64)

    async def call(i: int) -> None:
        try:
            resp = await stub.GetRateLimits(reqs[i], timeout=t["call_timeout_s"])
        except grpc.aio.AioRpcError:
            return  # done[i] stays NaN: a failed call
        done[i] = time.monotonic()
        rs = resp.responses
        if len(rs) != width or any(r.error for r in rs):
            done[i] = np.nan
            return
        for k, r in enumerate(rs):
            status[i, k], limit[i, k], remaining[i, k] = (
                r.status, r.limit, r.remaining,
            )

    tasks = []
    for i in range(n):
        # the selector sleeps in whole milliseconds: sleep short of the
        # due instant, then yield to the loop (which keeps reading
        # replies) until it has come
        while (delay := due[i] - time.monotonic()) > 0:
            await asyncio.sleep(delay - 0.0015 if delay > 0.002 else 0)
        sent[i] = time.monotonic()
        tasks.append(asyncio.ensure_future(call(i)))
    await asyncio.gather(*tasks)
    await channel.close()

    ok = ~np.isnan(done)
    plain = np.ones((n, width), bool)
    plain[canary_of >= 0, -1] = False
    sel = ok[:, None] & plain
    tally.answered(ids[sel], status[sel], limit[sel], remaining[sel])
    tally.lost(ids[~ok[:, None] & plain])
    for i in np.flatnonzero(ok & (canary_of >= 0)):
        tally.canary_replies[canary_of[i]].append(
            (int(status[i, -1]), int(limit[i, -1]), int(remaining[i, -1]))
        )
    win = slice(n_warm, n)
    conn.send(("done", {
        "latency_ms": ((done - due)[win][ok[win]] * 1e3),
        "due_s": (due - t0)[win][ok[win]],
        "late_ms": (sent - due)[win] * 1e3,
        "attempted": n - n_warm, "failed": int((~ok[win]).sum()),
        "failed_warmup": int((~ok[:n_warm]).sum()),
        "first_sent": float(sent[0]), "last_done": float(np.nanmax(done)),
        # when this worker itself ran late (seconds into the window, ms)
        "late_events": [
            (round(float(due[i] - t0), 3), round(float((sent[i] - due[i]) * 1e3), 1))
            for i in np.flatnonzero((sent - due) > 5e-3)[:40]
        ],
        "tally": tally.result(),
    }))


def summarize(results, spec: dict) -> dict:
    """End to end: the median of all calls of the window. The tail
    (p90, p99, p99.9) is reported beside it for the per-layer readers,
    over the calls due in the first `read_share` of the window (a
    traced run captures its profile after that, and the capture stalls
    the daemon)."""
    lat = np.concatenate([r["latency_ms"] for r in results])
    due = np.concatenate([r["due_s"] for r in results])
    late = np.sort(np.concatenate([r["late_ms"] for r in results]))
    sec = np.floor(due).astype(int)
    attempted = sum(r["attempted"] for r in results)
    failed = sum(r["failed"] for r in results)
    timeout_ms = 1e3 * spec["traffic"]["call_timeout_s"]
    pct = stats.latency_percentiles(np.sort(lat), failed, timeout_ms, (50,))
    early = np.sort(lat[due < spec.get("read_share", 1.0) * spec["seconds"]])
    tail = stats.latency_percentiles(early, failed, timeout_ms, (90, 99, 99.9))
    return {
        "attempted": attempted, "failed": failed,
        "end_to_end": {
            "call_p50_ms": (pct[50], "ms"),
        },
        "generator": {
            "offered_per_s": attempted / spec["seconds"],
            "completed_per_s": (attempted - failed) / spec["seconds"],
            "call_p90_ms": tail[90],
            "call_p99_ms": tail[99], "call_p999_ms": tail[99.9],
            "calls_in_tail_sample": len(early),
            "call_max_ms": float(lat.max()) if len(lat) else None,
            # the slowest call due in each whole second: when a stall fell
            "call_max_ms_by_s": [
                round(float(lat[sec == s].max()), 1) if (sec == s).any() else None
                for s in range(int(spec["seconds"]))
            ],
            "late_p50_ms": stats.percentile(late, 50),
            "late_p99_ms": stats.percentile(late, 99),
            "late_max_ms": float(late[-1]),
            "late_events": [r["late_events"] for r in results],
        },
    }
