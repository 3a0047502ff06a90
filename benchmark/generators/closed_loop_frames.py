"""Closed loop at saturation: each worker keeps `inflight` frames of
`items_per_frame` requests outstanding on ONE connection of the daemon's
GEB door, through the shipped AsyncGebClient; a lane sends its next
frame when its last is answered. What is measured is the items answered
inside the window over the window's length.

Traffic parameters (benchmark/traffic/<mix>.json): workers, inflight,
items_per_frame, prebuilt_frames_per_s, warmup_s, frame_timeout_s,
base_seed, canary_every, canaries_per_worker, key_classes, algorithms.
"""

from __future__ import annotations

import asyncio
import math
import time

import numpy as np

from harness import keyspace, stats, workers

DOOR = "geb"


def build(spec: dict) -> np.ndarray:
    """ids[frames, items]: drawn once from the traffic's base seed, the
    rows then ordered by the run's seed."""
    t, w = spec["traffic"], spec["worker"]
    frames = int(t["prebuilt_frames_per_s"] * (t["warmup_s"] + spec["seconds"]))
    base = np.random.default_rng([t["base_seed"], w])
    draws = keyspace.zipf_ids(
        spec["config"]["key_population"], (frames, t["items_per_frame"]), base
    )
    return keyspace.seeded_order(draws, spec["seed"], w)


def by_second(done: np.ndarray, t0: float, seconds: float) -> np.ndarray:
    """Frames answered in each second of the window."""
    return np.bincount((done - t0).astype(int), minlength=math.ceil(seconds))


def run_worker(spec: dict, conn) -> None:
    asyncio.run(_run(spec, conn))


async def _run(spec: dict, conn) -> None:
    from gubernator_tpu.client_geb import AsyncGebClient, GebError

    t = spec["traffic"]
    ids = build(spec)
    tally = workers.Tally(spec, ids)
    pool = dict(zip(
        tally.pool.tolist(),
        keyspace.make_reqs(spec["tag"], tally.pool, tally.rules, 1),
    ))
    client = AsyncGebClient(
        spec["geb"], window=t["inflight"], timeout=t["frame_timeout_s"]
    )
    hello = await client.connect()
    conn.send(("ready", {"frames_prebuilt": len(ids), "geb_window": hello.window}))
    loop = asyncio.get_running_loop()
    _, t0 = await loop.run_in_executor(None, conn.recv)
    t_end = t0 + spec["seconds"]
    await asyncio.sleep(max(0.0, t0 - t["warmup_s"] - time.monotonic()))

    sent, done, failed_frames = [], [], 0
    next_frame = 0

    async def lane() -> None:
        nonlocal next_frame, failed_frames
        while time.monotonic() < t_end:
            i = next_frame
            next_frame += 1
            row = ids[i % len(ids)]
            reqs = [pool[k] for k in row.tolist()]
            canary = -1
            if i % t["canary_every"] == 0:
                canary = (i // t["canary_every"]) % len(tally.canaries)
                reqs[-1] = tally.canary_req(canary)
                row = row[:-1]
            ts = time.monotonic()
            try:
                resps = await client.get_rate_limits(reqs)
            except (GebError, asyncio.TimeoutError, OSError):
                failed_frames += 1
                tally.lost(row)
                continue
            sent.append(ts)
            done.append(time.monotonic())
            if canary >= 0:
                tally.canary_answered(canary, resps.pop())
            fields = ([r.status for r in resps], [r.limit for r in resps],
                      [r.remaining for r in resps])
            if any(r.error for r in resps):
                tally.answered_with_errors(
                    row, *fields, [bool(r.error) for r in resps])
            else:
                tally.answered(row, *fields)

    first = time.monotonic()
    await asyncio.gather(*[lane() for _ in range(t["inflight"])])
    last = time.monotonic()
    client_stats = client.stats()
    await client.close()
    sent, done = np.array(sent), np.array(done)
    inside = (done >= t0) & (done < t_end)
    conn.send(("done", {
        "frames_in_window": int(inside.sum()),
        "frames_by_second": by_second(done[inside], t0, spec["seconds"]),
        "frame_ms": (done - sent)[inside] * 1e3,
        "frames_sent": next_frame, "failed": failed_frames,
        "wrapped": max(0, next_frame - len(ids)),
        "first_sent": first, "last_done": last,
        "client": {k: client_stats.get(k)
                   for k in ("transport", "use_fast", "downgrades")},
        "tally": tally.result(),
    }))


def summarize(results, spec: dict) -> dict:
    items = spec["traffic"]["items_per_frame"]
    frames = sum(r["frames_in_window"] for r in results)
    failed = sum(r["failed"] for r in results)
    frame_ms = np.sort(np.concatenate([r["frame_ms"] for r in results]))
    return {
        "attempted": (frames + failed) * items, "failed": failed * items,
        "end_to_end": {
            "decisions_per_s": (frames * items / spec["seconds"], "decisions/s"),
        },
        "generator": {
            "frames_per_s": frames / spec["seconds"],
            "frame_p50_ms": stats.percentile(frame_ms, 50) if len(frame_ms) else None,
            "frame_p99_ms": stats.percentile(frame_ms, 99) if len(frame_ms) else None,
            "frames_wrapped": sum(r["wrapped"] for r in results),
            # the window second by second: a run that reads low by a hole
            # (a stalled second) shows it here, one that ran at a lower
            # level all through does not (PERF.md section 6, PR 38)
            "frames_by_second": sum(
                r["frames_by_second"] for r in results).tolist(),
            "client": results[0]["client"],
        },
    }
