#!/usr/bin/env python3
"""The one sweep that finds an open-loop cell's knee: the cell's daemons
(one, or the configuration's ring: load, checks and readings at node 0),
rising steps of a few seconds each, every step on fresh keys. Run once,
on the chip, when a cell is defined; the rate it finds is then FIXED in
the cell's file (the benchmark never searches for a rate).

    python3 benchmark/sweep.py --workload <cell> --rates 1000,2000,... [--seconds 10]

One JSON line per step: offered and completed calls a second, p50/p99
from the due instant, failures, the generator's own lateness. The knee
is the highest rate with every call answered, completed == offered and
a p99 that has not left the plateau of the lower rates.
"""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]


def delta(before: dict, after: dict, name: str) -> float:
    return after.get(name, 0.0) - before.get(name, 0.0)


def main() -> int:
    from harness import bench, keyspace, workers
    from harness.daemon import Ring
    from harness.doors import Doors

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--call-timeout", type=float, default=10.0,
                    help="a step far above the knee ends this long after "
                    "its last call, whatever the traffic file allows a call")
    args = ap.parse_args()
    cell = bench.load("cells", args.workload)
    config = bench.load("configs", cell["config"])
    traffic = bench.load("traffic", cell["traffic"])
    if "call_timeout_s" in traffic:
        traffic["call_timeout_s"] = args.call_timeout
    kind = workers.load_kind(traffic["generator"])
    bench.build_native()
    ring = Ring(args.workload + ".sweep", config, bench.OUT_DIR)
    fleet = doors = None
    try:
        for i in range(len(ring.specs)):
            ring.start(i)
        for d in ring.nodes:
            d.wait_ready(time.monotonic() + bench.BOOT_TIMEOUT, len(ring.nodes))
        d = ring.nodes[0]
        doors = Doors(ring)
        rules = keyspace.KeyRules(traffic)
        for step, rate in enumerate(float(r) for r in args.rates.split(",")):
            tag = f"sweep{step}"
            bench.preload(doors, tag, rules, config["preload_keys"])
            spec, fleet, _ = bench.start_fleet(
                ring, kind, args.seed + step, args.seconds, tag,
                dict(cell, rate=rate), config, traffic)
            t0 = time.monotonic() + traffic["warmup_s"] + 0.25
            fleet.go(t0)
            s0, p0 = d.stages(), d.prom()
            results = fleet.results(
                traffic["warmup_s"] + args.seconds + traffic["drain_timeout_s"])
            s1, p1 = d.stages(), d.prom()
            fleet.close()
            fleet = None
            summary = kind.summarize(results, spec)
            drain_s = max(r["last_done"] for r in results) - (t0 + args.seconds)
            stage_ms = {
                name: round(1e3 * (v["total_s"] - s0["stages"].get(name, {}).get("total_s", 0))
                            / max(1, v["count"] - s0["stages"].get(name, {}).get("count", 0)), 4)
                for name, v in s1["stages"].items()
            }
            batches = delta(p0, p1, "device_batch_size_count")
            bench.emit(rate=rate, failed=summary["failed"], drain_s=drain_s,
                       batches=batches,
                       items_per_batch=delta(p0, p1, "device_batch_size_sum") / max(1, batches),
                       **{k: v[0] for k, v in summary["end_to_end"].items()},
                       **summary["generator"], stages_ms=stage_ms)
    finally:
        if fleet is not None:
            fleet.close()
        if doors is not None:
            doors.close()
        ring.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
