"""Closed loop at saturation with Behavior GLOBAL mixed in: the
`closed_loop_frames` generator (same frames, lanes, tallies and summary:
its `build` and `summarize` are used as they are), with a share of the
key ids sent as Behavior GLOBAL in the same frames as the plain ones.

A key id is GLOBAL or plain for the whole run (`is_global`): exact over
any 100 consecutive ids, and independent of the id's limit class and
algorithm (harness/keyspace.py picks those by other multipliers). Every
second canary is GLOBAL too. A frame that holds one GLOBAL item cannot
be sent pre-hashed (the shipped client falls back to a string frame:
client_geb._fast_eligible), which is the point of the mix.

Traffic parameters: those of closed_loop_frames, and global_share.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from generators import closed_loop_frames as plain
from harness import keyspace, workers

DOOR = plain.DOOR
build = plain.build


def is_global(ids, share: float) -> np.ndarray:
    """bool per key id: `share` of any 100 consecutive ids, exactly, in
    whole percent (27 is coprime to 100, so one hundred consecutive ids
    take every slot once). At 0.10 the ten GLOBAL ids of a hundred (8,
    19, 23, 34, 45, 60, 71, 82, 86, 97) fall 7 / 2 / 1 over the limit
    classes as all ids do (harness/keyspace.py picks the class by
    id * 37), and the algorithm's pick turns against both from one
    hundred ids to the next; id 8 is the one GLOBAL key of the ten
    hottest."""
    ids = np.asarray(ids, np.int64)
    return (ids * 27 + 87) % 100 < round(share * 100)


def global_reqs(tag: str, ids, rules, hits: int, share: float):
    """keyspace.make_reqs with the GLOBAL ids' behaviour set."""
    from gubernator_tpu.api.types import Behavior

    reqs = keyspace.make_reqs(tag, ids, rules, hits)
    for i in np.flatnonzero(is_global(ids, share)).tolist():
        reqs[i].behavior = Behavior.GLOBAL
    return reqs


class Tally(workers.Tally):
    """Every second canary is sent as Behavior GLOBAL."""

    def canary_req(self, j: int):
        from gubernator_tpu.api.types import Behavior

        req = super().canary_req(j)
        if j % 2:
            req.behavior = Behavior.GLOBAL
        return req


def run_worker(spec: dict, conn) -> None:
    asyncio.run(_run(spec, conn))


async def _run(spec: dict, conn) -> None:
    """closed_loop_frames._run with the pool's behaviours set, GLOBAL
    canaries, and the GLOBAL items counted."""
    from gubernator_tpu.client_geb import AsyncGebClient, GebError

    t = spec["traffic"]
    ids = build(spec)
    tally = Tally(spec, ids)
    pool = dict(zip(
        tally.pool.tolist(),
        global_reqs(spec["tag"], tally.pool, tally.rules, 1,
                    t["global_share"]),
    ))
    global_of_row = is_global(ids, t["global_share"]).sum(axis=1)
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
    next_frame = global_items = plain_frames = 0

    async def lane() -> None:
        nonlocal next_frame, failed_frames, global_items, plain_frames
        while time.monotonic() < t_end:
            i = next_frame
            next_frame += 1
            row = ids[i % len(ids)]
            reqs = [pool[k] for k in row.tolist()]
            n_global = int(global_of_row[i % len(ids)])
            canary = -1
            if i % t["canary_every"] == 0:
                canary = (i // t["canary_every"]) % len(tally.canaries)
                n_global += canary % 2 - int(
                    is_global(row[-1:], t["global_share"])[0])
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
            global_items += n_global
            plain_frames += n_global == 0
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
        "frames_by_second": plain.by_second(done[inside], t0, spec["seconds"]),
        "frame_ms": (done - sent)[inside] * 1e3,
        "frames_sent": next_frame, "failed": failed_frames,
        "wrapped": max(0, next_frame - len(ids)),
        "first_sent": first, "last_done": last,
        "frames_answered": len(done), "global_items": global_items,
        "frames_without_global": plain_frames,
        "client": {k: client_stats.get(k)
                   for k in ("transport", "use_fast", "downgrades")},
        "tally": tally.result(),
    }))


def summarize(results, spec: dict) -> dict:
    out = plain.summarize(results, spec)
    answered = sum(r["frames_answered"] for r in results)
    items = answered * spec["traffic"]["items_per_frame"]
    out["generator"]["global_items_pct"] = (
        100.0 * sum(r["global_items"] for r in results) / items
        if items else None
    )
    out["generator"]["frames_without_global_pct"] = (
        100.0 * sum(r["frames_without_global"] for r in results) / answered
        if answered else None
    )
    return out
