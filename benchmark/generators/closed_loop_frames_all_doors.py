"""Closed loop at saturation at EVERY door of a ring: the
`closed_loop_frames` generator (same frames, lanes, tallies and
summary: its `build`, `_run` and `summarize` are used as they are), with
worker w on the GEB door of node w mod N instead of node 0's — a
balancer that spreads clients evenly and knows nothing of key ownership
(round-robin DNS, a Kubernetes Service). Nothing else differs: a worker
is still one connection with `inflight` frames outstanding, and its
tallies and canaries are its own (a canary key belongs to one worker,
so to one door; the tallies are summed over workers per key, whichever
door each used: reference_ring4_doors.py says why that decides).

The summary adds, per door, the frames a second its workers were
answered, and `door_skew_pct` = (fastest door - slowest door) / mean
x 100: how evenly the ring served its four doors (which node owns the
zipf head follows ports x seed; PERF.md section 4), and
`worker_cpu_share`: each worker process's CPU seconds over wall seconds
from "go" to "done" (a worker near 1.0 is a generator that holds the
pace; eight workers because one is proved to 60 frames/s and this cell
may ask 125 of four).

Traffic parameters: those of closed_loop_frames.
"""

from __future__ import annotations

import asyncio
import time

from generators import closed_loop_frames as plain

DOOR = plain.DOOR
build = plain.build


def node_of(worker: int, n_nodes: int) -> int:
    """The node whose door worker `worker` dials."""
    if n_nodes < 2:
        raise ValueError(
            "closed_loop_frames_all_doors needs a configuration of several "
            f"nodes (a ring); this one has {n_nodes}: use closed_loop_frames"
        )
    return worker % n_nodes


def at_its_door(spec: dict) -> dict:
    """The worker's spec with `geb` its own node's door."""
    nodes = spec["nodes"]
    return dict(spec, geb=nodes[node_of(spec["worker"], len(nodes))]["geb"])


class _Clocked:
    """The worker's end of its pipe as closed_loop_frames._run uses it,
    with the process's CPU share between "go" and "done" added to the
    result."""

    def __init__(self, conn):
        self._conn = conn
        self._cpu0 = self._t0 = 0.0

    def recv(self):
        msg = self._conn.recv()
        self._cpu0, self._t0 = time.process_time(), time.monotonic()
        return msg

    def send(self, msg) -> None:
        tag, body = msg
        if tag == "done":
            body = dict(body, cpu_share=(time.process_time() - self._cpu0)
                        / (time.monotonic() - self._t0))
        self._conn.send((tag, body))


def run_worker(spec: dict, conn) -> None:
    asyncio.run(plain._run(at_its_door(spec), _Clocked(conn)))


def summarize(results, spec: dict) -> dict:
    """closed_loop_frames' summary; results come in worker order."""
    out = plain.summarize(results, spec)
    n = len(spec["nodes"])
    by_node = [0.0] * n
    for worker, r in enumerate(results):
        by_node[node_of(worker, n)] += r["frames_in_window"] / spec["seconds"]
    mean = sum(by_node) / n
    out["generator"]["frames_per_s_by_node"] = by_node
    out["generator"]["door_skew_pct"] = (
        (max(by_node) - min(by_node)) / mean * 100.0 if mean else None
    )
    out["generator"]["worker_cpu_share"] = [
        round(r["cpu_share"], 4) for r in results]
    return out
