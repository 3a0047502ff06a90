#!/usr/bin/env python3
"""A ring's forwards right after a burst of creates, many times on one
boot: by hand, on the CPU and on the chip (ROADMAP R-A9; PERF.md
section 6, PR 41).

    chiprun --chips 4 -- python3 scripts/ring_preload_soak.py --reps 15

Boots the `ring4` configuration once, as benchmark/run.py boots it (the
harness's `Ring`, `preload` and `check.checked_sequence` are imported,
nothing of them is edited), and repeats, with a fresh key tag each
time, what a run of the cell does before its window: `preload_keys`
creates through node 0 — three quarters of them forwarded to the nodes
that own them — and then AT ONCE the 300-item checked sequence through
node 0, item by item against the plain reference. One boot, ~30 s a
repetition.

For each repetition one JSON line: every error item with its text, the
answers that differ, and from the nodes' own instruments over the
repetition (two /v1/debug/stages and /metrics snapshots a node and phase): the
longest `forward_rpc` on node 0 against the deadline, the forwards that
failed by reason, and on EVERY node the longest `loop_lag`, `gc_pause`
and `call_e2e` (an owner's whole GetPeerRateLimits call), node 0's
frame `coverage` (the per-frame stages over the frames' end-to-end
time: the preload's frames are the cell's own 1000-item string frames) and the
programs it BUILT meanwhile (a program first used after Ready is
traced and built while a peer waits), during the creates and during
the sequence apart, so a pause names its node and its phase — or
shows in all four nodes at once, which is the host's.
The longest are a log-scale bucket's upper edge (two buckets an octave:
at most 1.41 times the sample). The last line sums the repetitions;
the nodes' WARNING lines about failed forwards follow on standard
error. Exit 0 when no repetition saw an error item, 1 otherwise.
The parent never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

import check  # noqa: E402
from harness import bench, keyspace  # noqa: E402

WATCHED = ("forward_rpc", "forward_queue", "loop_lag", "gc_pause", "call_e2e",
           "peer_serve")
FAILED = "peer_forward_failed_items_total"


def longest_ms(before: dict, after: dict, stage: str):
    """Upper edge, in ms, of the highest bucket of `stage` that gained a
    sample between two /v1/debug/stages snapshots; None when none did."""
    edges = after["bucket_edges_s"]
    b1 = after["stages"].get(stage, {}).get("buckets")
    if not b1:
        return None
    b0 = before["stages"].get(stage, {}).get("buckets") or [0] * len(b1)
    top = max((i for i, (x, y) in enumerate(zip(b0, b1)) if y > x), default=None)
    if top is None:
        return None
    return round(1e3 * edges[min(top, len(edges) - 1)], 3)


def samples(before: dict, after: dict, stage: str) -> int:
    def count(s):
        return s["stages"].get(stage, {}).get("count", 0)
    return count(after) - count(before)


BUILT = re.compile(
    r"^(?!WARNING:).*Finished XLA compilation of jit\((.+?)\) in ([0-9.]+) sec",
    re.M)


class Logs:
    """What each node's log gained since it was last asked: the
    programs XLA built (or loaded from the compile cache) meanwhile, as
    {name: seconds} a node. A program first used after Ready is traced,
    lowered and built on the thread that called it, while a peer waits."""

    def __init__(self, ring):
        self._nodes = ring.nodes
        self._at = [0] * len(ring.nodes)
        self.built()

    def built(self) -> list:
        out = []
        for i, d in enumerate(self._nodes):
            with open(d.log_path, "r", errors="replace") as f:
                f.seek(self._at[i])
                text = f.read()
                self._at[i] = f.tell()
            found = {}
            for name, seconds in BUILT.findall(text):
                found[name] = round(found.get(name, 0.0) + float(seconds), 4)
            out.append(found)
        return out


def readings(logs: Logs, snaps0, snaps1) -> dict:
    """What the nodes' clocks saw between two rounds of snapshots:
    node 0's forwards, for every node its longest pauses, and the
    programs it built meanwhile."""
    out = {"forward_rpcs": samples(snaps0[0], snaps1[0], "forward_rpc")}
    for stage in WATCHED:
        out[f"{stage}_longest_ms"] = [
            longest_ms(a, b, stage) for a, b in zip(snaps0, snaps1)]
    out["programs_built"] = logs.built()
    # node 0's frames: what share of their end-to-end time the
    # per-frame stages tile (/v1/debug/stages `coverage`, by growth)
    e2e = snaps1[0]["frame_e2e_total_s"] - snaps0[0]["frame_e2e_total_s"]
    out["door_frames"] = snaps1[0]["frames"] - snaps0[0]["frames"]
    out["door_coverage"] = round(
        (snaps1[0]["attributed_total_s"] - snaps0[0]["attributed_total_s"])
        / e2e, 4) if e2e > 0 else None
    return out


def one_repetition(ring, logs, doors, client, rules, algos, tag: str,
                   seed: int, n_keys: int) -> dict:
    """Snapshots before the creates, between the creates and the
    sequence (four HTTP requests, milliseconds: "at once" still) and
    after it, so a pause is told by its phase as well as by its node."""
    snap0 = [d.stages() for d in ring.nodes]
    prom0 = ring.nodes[0].prom()
    out = {"tag": tag, "errors": []}
    t = time.monotonic()
    try:
        out["preload_wrong"] = bench.preload(doors, tag, rules, n_keys)
    except RuntimeError as e:  # doors.py raises on the first error item
        out["errors"].append(f"preload: {e}")
    out["preload_s"] = round(time.monotonic() - t, 3)
    snap1 = [d.stages() for d in ring.nodes]
    out["during_preload"] = readings(logs, snap0, snap1)
    # at once: the sequence a run sends before its window, on keys of
    # this repetition's own
    t = time.monotonic()
    calls = check.checked_sequence(seed, algos)
    got, error_items = [], 0
    for n, call in enumerate(calls):
        resps = client.get_rate_limits([keyspace.req(*item) for item in call])
        for item, r in zip(call, resps):
            if r.error:
                error_items += 1
                out["errors"].append(f"check call {n} '{item[0]}': {r.error}")
        got.append([(int(r.status), int(r.limit), int(r.remaining))
                    for r in resps])
    out["check_s"] = round(time.monotonic() - t, 3)
    want = check.reference_answers(calls, int(time.time() * 1000))
    n, differ, first = check.compare_sequence(calls, got, want)
    out.update(compared=n, differ=differ, error_items=error_items,
               first_difference=first)
    snap2 = [d.stages() for d in ring.nodes]
    prom1 = ring.nodes[0].prom()
    out["during_check"] = readings(logs, snap1, snap2)
    out["failed_forwards"] = {
        name[len(FAILED) + 9:-2]: prom1[name] - prom0.get(name, 0.0)
        for name in prom1 if name.startswith(FAILED)
        and prom1[name] != prom0.get(name, 0.0)}
    out["errors"] = out["errors"][:8]
    return out


def main(argv=None) -> int:
    t_exec = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="ring4.geb-frames")
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--keys", type=int, default=0,
                    help="creates a repetition (default: the configuration's "
                         "preload_keys)")
    args = ap.parse_args(argv)
    cell = bench.load("cells", args.workload)
    config = bench.load("configs", cell["config"])
    traffic = bench.load("traffic", cell["traffic"])
    n_keys = args.keys or config["preload_keys"]
    bench.build_native()
    ring, device, _, boots = bench.boot(
        types.SimpleNamespace(workload=args.workload, daemon_argv=""),
        config, t_exec)
    deadline_ms = None
    doors = client = None
    lines = []
    try:
        from gubernator_tpu.client_geb import GebClient
        from gubernator_tpu.serve.config import config_from_env
        from harness.doors import CALL_TIMEOUT, Doors

        deadline_ms = 1e3 * config_from_env(
            dict(ring.specs[0]["env"])).behaviors.effective_peer_timeout()
        doors = Doors(ring)
        client = GebClient(ring.geb, timeout=CALL_TIMEOUT)
        client.connect()
        rules = keyspace.KeyRules(traffic)
        algos = [keyspace.ALGORITHMS[a["algorithm"]]
                 for a in traffic["algorithms"]]
        logs = Logs(ring)
        print(json.dumps({
            "phase": "boot", "seconds": sum(boots), "nodes": len(ring.nodes),
            "platform": device["platform"], "keys_a_repetition": n_keys,
            "deadline_ms": deadline_ms}), flush=True)
        for rep in range(args.reps):
            line = one_repetition(
                ring, logs, doors, client, rules, algos,
                f"soak{args.seed}r{rep}", args.seed * 1000 + rep, n_keys)
            ring.check_alive()
            lines.append(line)
            print(json.dumps(dict(line, rep=rep)), flush=True)
    finally:
        if client is not None:
            client.close()
        if doors is not None:
            doors.close()
        ring.stop(30.0)

    def worst(phase, key):
        return max((v for x in lines for v in x[phase][key] if v is not None),
                   default=None)

    bad = sum(bool(x["errors"]) or x["differ"] > 0 for x in lines)
    print(json.dumps({
        "phase": "summary", "repetitions": len(lines),
        "repetitions_with_an_error_item": bad,
        "error_items": sum(x["error_items"] for x in lines),
        "preload_errors": sum(e.startswith("preload") for x in lines
                              for e in x["errors"]),
        "answers_that_differ": sum(x["differ"] for x in lines),
        "forward_rpcs": sum(x[p]["forward_rpcs"] for x in lines
                            for p in ("during_preload", "during_check")),
        "deadline_ms": deadline_ms,
        **{f"{stage}_longest_ms_{phase[7:]}": worst(phase, f"{stage}_longest_ms")
           for phase in ("during_preload", "during_check")
           for stage in ("forward_rpc", "loop_lag", "gc_pause", "call_e2e")},
        "programs_built_after_ready": sorted({
            name for x in lines for p in ("during_preload", "during_check")
            for node in x[p]["programs_built"] for name in node}),
        "node_exits": ring.exits,
    }), flush=True)
    for d in ring.nodes:
        for text in d.log_text().splitlines():
            if "forward to peer" in text and "WARNING" in text.upper():
                print(f"node {d.index}: {text}", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except bench.BenchFailure as e:
        print(f"soak: {e}", file=sys.stderr)
        sys.exit(2)
