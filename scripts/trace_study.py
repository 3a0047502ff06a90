#!/usr/bin/env python3
"""One benchmark cell's load with the daemon's own instruments read
while it runs: by hand, on the chip (PERF.md sections 5 and 6, PR 24).

    chiprun -- python3 scripts/trace_study.py --workload <cell> --seed <n>

Boots the cell's daemon and generators exactly as benchmark/run.py does
(its harness is imported, nothing of it is edited), with
GUBER_TRACE_SLOW_MS=50 so the flight recorder keeps the slow calls, and
over one window of --seconds:

- polls /v1/debug/stages twice a second: `loop_lag` and `gc_pause` per
  half second, to set beside the instants the generators froze;
- takes one /v1/debug/profile capture with the Python tracer on and one
  with it off (each after a short throwaway capture that pays the
  profiler's first-use cost), and reduces both with the benchmark's
  own trace_reduce.py: return time, the slowest call due while the
  capture ran, the loop's worst lag while it ran, idle share, idle gaps;
- dumps /v1/debug/traces and sums the retained slow calls by tile;
- reads a device batch's tiles (`batch_coverage`, the hand-off's legs
  beside the residue they used to be, microseconds a batch) and every
  serving thread's CPU share of the window (`threads`: the CPU clocks
  of the snapshots at its two ends), PR 36;
- names the THREAD whose line holds each of the top idle gaps of the
  Python-tracer capture (scripts/gap_threads.py);
- reads the GEB door's `edge_*` counters by growth over the window
  (`door_counters`: which path served the items, and how many string
  frames the native parser took or declined), PR 37; beside them the
  traffic observers' `traffic_*_folds_total` (which implementation
  folded the batches), PR 40; on a mesh `mesh_*_stacks_total` (who laid
  the merged batches out per shard), PR 44; the shed cache's
  `shed_index_uses_total` beside `shed_native_consults_total` /
  `shed_numpy_consults_total` (which body served the array consults:
  the two sum to the first), PR 48; and on a ring's door node the split by
  owner's `edge_split_frames_total`, `edge_split_items_total{lane}`
  and `edge_split_declined_total{reason}` (how often it engaged, where
  its items went, what it declined and why), PR 43;
- on a ring's door node (the harness's node 0), the forwarder's stages
  and `peer_forward_*` counters (`forwarder`), PR 41: run a ring cell
  with `--captures 0`, a capture on the door node stalls its forwards
  past their deadline;
- with `--nodes 0,1,2,3` (PR 46: a ring whose clients dial every node,
  `ring4-lb.geb-frames-all-doors`), the same `door_counters` and
  `forwarder` for each listed node under `by_node`, beside its
  `device_batch_rows_total{source}`, `device_batches_mixed_total`,
  `peer_serve_*`, `loop_pauses_over_half_deadline_total` and
  `programs_built_after_ready_total` by growth; node 0's stand at the
  top as ever. Since PR 47 each node's own stage means and counts,
  device batches and frames a second and thread CPU shares too (the
  node whose door takes the ring is not always node 0: its
  `call_queue` beside its `batch_queue` says whether a peer's batch
  waits out the node's own backlog), and in `door_counters` the
  batcher's `device_groups_overtaking_total` and
  `device_batch_sources_sum` / `_count`.

Prints one JSON object; the whole of it, and the Python-tracer-off
capture's .xplane.pb, go to chiprun_out/trace_study/. The parent never
imports JAX.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

from harness import bench, keyspace, workers  # noqa: E402
from harness import daemon as daemon_mod  # noqa: E402

POLL_S = 0.5
CAPTURE_MS = 2000
SLOW_MS = 50


def get_json(d, path, timeout=300.0):
    return json.loads(daemon_mod.http_get(d.http, path, timeout))


def worst_bucket_ms(before, after, edges):
    """Upper edge (ms) of the highest bucket that gained a sample."""
    top = max((i for i, (a, b) in enumerate(zip(before, after)) if b > a),
              default=None)
    if top is None:
        return 0.0
    return 1e3 * edges[min(top, len(edges) - 1)]


class Poller(threading.Thread):
    """(seconds into the window, worst loop_lag ms, GC seconds, the
    poll's own round trip ms) every POLL_S."""

    def __init__(self, d, t0):
        super().__init__(daemon=True)
        self.d, self.t0, self.rows, self.stop = d, t0, [], threading.Event()

    def run(self):
        prev = None
        while not self.stop.wait(POLL_S):
            t = time.monotonic()
            try:
                s = get_json(self.d, "/v1/debug/stages", 30.0)
            except Exception:
                continue
            rtt = time.monotonic() - t
            st = s["stages"]
            lag = st.get("loop_lag", {}).get("buckets")
            gc_s = st.get("gc_pause", {}).get("total_s", 0.0)
            if lag is None:
                continue
            if prev is not None:
                self.rows.append((
                    round(t - self.t0, 2),
                    round(worst_bucket_ms(prev[0], lag, s["bucket_edges_s"]), 2),
                    round(gc_s - prev[1], 4), round(rtt * 1e3, 1),
                ))
            prev = (lag, gc_s)


def capture(d, name, python, ms=CAPTURE_MS):
    t = time.monotonic()
    get_json(d, f"/v1/debug/profile?ms={ms}&python={python}&name={name}")
    return t, time.monotonic()


def batch_tiles(st):
    """A device batch's life in microseconds a batch: each tile, the
    interior of submit_call, and the hand-off both ways it can be
    read — the two measured legs, and the residue submit_host less
    prep, merge and dispatch that stood for them before PR 36."""
    def per(name, count):
        n = st.get(count, {}).get("count", 0)
        return st.get(name, {}).get("total_s", 0.0) / n * 1e6 if n else None

    out = {name: per(name, "batch_e2e") for name in (
        "batch_e2e", "admit_wait", "fetch_wake", "fetch_wait", "fetch_return")}
    out.update({name: per(name, "submit_host") for name in (
        "submit_host", "submit_wake", "submit_call", "submit_return",
        "prep", "merge", "dispatch", "jit_call", "observe")})
    if out["submit_host"] is not None and out["submit_wake"] is not None:
        out["legs"] = out["submit_wake"] + out["submit_return"]
        out["residue"] = (out["submit_host"] - out["prep"] - out["merge"]
                          - out["dispatch"])
    return out


def thread_shares(t0, t1, batches):
    """Each role's CPU seconds over the wall seconds between two
    `threads` snapshots, in percent of one core, and the submit
    thread's CPU microseconds a device batch."""
    if not t0 or not t1 or not t1.get("cpu_s") or not t0.get("cpu_s"):
        return {"thread_clock": (t1 or {}).get("thread_clock", "absent")}
    wall = t1["wall_s"] - t0["wall_s"]
    ran = {k: t1["cpu_s"][k] - t0["cpu_s"].get(k, 0.0) for k in t1["cpu_s"]}
    return {
        "thread_clock": t1["thread_clock"],
        "granularity_s": t1.get("granularity_s"),
        "wall_s": wall,
        "cpu_pct": {k: 100.0 * v / wall for k, v in ran.items()},
        "serving_threads_cpu_pct": 100.0 * sum(
            v for k, v in ran.items() if k != "other") / wall,
        "submit_cpu_us_per_batch": (
            ran["submit"] / batches * 1e6 if batches else None),
    }


def gap_threads(profile_dir):
    """scripts/gap_threads.py over one capture, in a CPU child."""
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "gap_threads.py"),
         *files],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)
    if p.returncode != 0:
        return {"error": p.stderr[-1000:]}
    return json.loads(p.stdout.strip().splitlines()[-1])


def grown(prom0, prom1, prefixes):
    """Growth of every `*_total` series of /metrics (and every summed
    and counted one, `*_sum` / `*_count`) that starts with one of
    `prefixes` between two scrapes, series that stood still left out."""
    return {
        k: v - prom0.get(k, 0.0) for k, v in sorted(prom1.items())
        if k.startswith(prefixes)
        and ("_total" in k or k.endswith(("_sum", "_count")))
        and v != prom0.get(k, 0.0)
    }


def door_counters(prom0, prom1):
    """The GEB door's `edge_*_total` (the split by owner's
    `edge_split_*_total` among them), the traffic observers'
    `traffic_*_total` and the mesh's `mesh_*_total` (PR 44: who laid
    the merged batches out per shard, `mesh_native_stacks_total` /
    `mesh_numpy_stacks_total`, beside the shard rows and slots) and
    the batcher's turn-taking between sources (PR 47:
    `device_groups_overtaking_total`, `device_batch_sources_sum` /
    `_count`: 0 and 1.0 a batch wherever one source feeds it) and the
    shed cache's array consults by the body that served them (PR 48:
    `shed_index_uses_total` = `shed_native_consults_total` +
    `shed_numpy_consults_total`, `shed_index_rebuilds_total`) over the
    window. A counter that did not move is left out."""
    return grown(prom0, prom1, (
        "edge_", "traffic_", "mesh_", "device_groups_overtaking_",
        "device_batch_sources_", "shed_index_", "shed_native_",
        "shed_numpy_"))


def forwarder(st, prom0, prom1):
    """The forwarder side of a ring's door node (PR 41), None where
    nothing was forwarded: the four stages that tile a forward
    (`forward_queue` a group; `forward_encode`, `forward_rpc`,
    `forward_decode` an RPC) and a frame's `forward_wait` in
    microseconds a sample, and the growth of the `peer_forward_*`
    counters over the window with the items a batch."""
    def us(name):
        n = st.get(name, {}).get("count", 0)
        return st[name]["total_s"] / n * 1e6 if n else None

    grew = grown(prom0, prom1, ("peer_forward_",))
    if not grew and "forward_rpc" not in st:
        return None
    batches = grew.get("peer_forward_batches_total", 0.0)
    return {
        "stage_us": {name: us(name) for name in (
            "forward_queue", "forward_encode", "forward_rpc", "forward_decode",
            "forward_wait")},
        "counters": grew,
        "items_per_batch": (grew.get("peer_forward_items_total", 0.0) / batches
                            if batches else None),
    }


def tiles_of(traces, slow_ms):
    """The retained calls slower than slow_ms: how many, and the mean
    milliseconds each span name holds in them."""
    slow = [t for t in traces if t["duration_ms"] >= slow_ms]
    total = {}
    for t in slow:
        for s in t["spans"]:
            total[s["name"]] = total.get(s["name"], 0.0) + s["duration_ms"]
    n = max(1, len(slow))
    return {
        "calls": len(slow),
        "mean_duration_ms": round(sum(t["duration_ms"] for t in slow) / n, 2),
        "mean_ms_by_span": {k: round(v / n, 3) for k, v in sorted(total.items())},
        "start_unix_ms": [t["start_unix_ms"] for t in slow][:200],
        "slowest": sorted(slow, key=lambda t: -t["duration_ms"])[:3],
    }


def main() -> int:
    t_exec = time.monotonic()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--captures", type=int, choices=(0, 1), default=1,
                    help="0: an undisturbed window (tiles, slow calls, lag)")
    ap.add_argument("--nodes", default="0",
                    help="a ring's nodes whose door_counters, forwarder and "
                         "rows by source are printed under by_node, e.g. "
                         "0,1,2,3 where clients dial every node (ring4-lb); "
                         "node 0's stand at the top as ever")
    args = ap.parse_args()
    args.daemon_argv = ""
    nodes = sorted({int(i) for i in args.nodes.split(",")})
    os.environ["GUBER_TRACE_SLOW_MS"] = str(SLOW_MS)

    cell = bench.load("cells", args.workload)
    config = bench.load("configs", cell["config"])
    traffic = bench.load("traffic", cell["traffic"])
    kind = workers.load_kind(traffic["generator"])
    bench.build_native()
    base = os.path.join(tempfile.gettempdir(), "guber-profile")
    names = {"1": f"study_{args.workload}_py1", "0": f"study_{args.workload}_py0"}
    for n in names.values():
        shutil.rmtree(os.path.join(base, n), ignore_errors=True)

    out_dir = os.path.join(ROOT, "chiprun_out", "trace_study")
    tag = f"s{args.seed}"
    d, device, _named, boots = bench.boot(args, config, t_exec)
    fleet = doors = None
    out = {"workload": args.workload, "seed": args.seed,
           "device": {k: device[k] for k in ("platform", "kind", "count")},
           "boot_s": boots}
    try:
        from harness.doors import Doors

        doors = Doors(d)
        rules = keyspace.KeyRules(traffic)
        out["preload_wrong"] = bench.preload(
            doors, tag, rules, config["preload_keys"])
        spec, fleet, _ = bench.start_fleet(
            d, kind, args.seed, args.seconds, tag, cell, config, traffic)
        kinds = ("1", "0") if args.captures else ()
        for python in kinds:  # the profiler's first use, paid here
            capture(d, "study_warm", python, ms=100)
        t0 = time.monotonic() + traffic["warmup_s"] + 0.25
        fleet.go(t0)
        workers.wait_until(t0)
        unix0_ms = time.time() * 1e3
        threads0 = get_json(d, "/v1/debug/stages?reset=1").get("threads")
        get_json(d, "/v1/debug/traces?reset=1")
        prom0 = d.prom()
        proms0, threads0_by = {0: prom0}, {0: threads0}
        for i in nodes:
            if i:  # node 0's clock was reset and its counters read above
                threads0_by[i] = get_json(
                    d.nodes[i], "/v1/debug/stages?reset=1").get("threads")
                proms0[i] = d.nodes[i].prom()
        poller = Poller(d, t0)
        poller.start()
        caps = {}
        at = t0 + 0.15 * args.seconds
        for python in kinds:
            workers.wait_until(at)
            start, end = capture(d, names[python], python)
            caps[python] = {"start_s": round(start - t0, 2),
                            "return_s": round(end - start, 2)}
            at = max(end + 5.0, t0 + 0.55 * args.seconds)
        workers.wait_until(t0 + args.seconds)
        poller.stop.set()
        stages = get_json(d, "/v1/debug/stages")
        prom1 = d.prom()
        by_node = {}
        for i in nodes:
            snap_i = (stages if i == 0
                      else get_json(d.nodes[i], "/v1/debug/stages"))
            st_i = snap_i["stages"]
            p1 = prom1 if i == 0 else d.nodes[i].prom()
            by_node[i] = {
                # PR 47: each node's own stage clock and thread CPU (the
                # winner's call_queue is not node 0's): mean ms and
                # samples a stage, device batches and frames a second
                "stage_means_ms": {k: v["mean_ms"] for k, v in st_i.items()},
                "stage_counts": {k: v["count"] for k, v in st_i.items()},
                "batches_per_s": (snap_i.get("batches") or 0) / args.seconds,
                "frames_per_s": (snap_i.get("frames") or 0) / args.seconds,
                "threads": thread_shares(
                    threads0_by[i], snap_i.get("threads"),
                    snap_i.get("batches")),
                "door_counters": door_counters(proms0[i], p1),
                "forwarder": forwarder(st_i, proms0[i], p1),
                # PR 46: a batch's rows by who sent them, the owner
                # side's counts, loop pauses and programs built
                "owner_and_batches": grown(proms0[i], p1, (
                    "device_batch_rows_", "device_batches_mixed_",
                    "device_batch_size_",
                    "peer_serve_", "loop_pauses_", "programs_built_")),
            }
        traces = get_json(d, "/v1/debug/traces?limit=4096")
        results = fleet.results(traffic["drain_timeout_s"])
        summary = kind.summarize(results, spec)
        doors.close()
        doors = None
        fleet.close()
        fleet = None
        d.stop()
    finally:
        if doors is not None:
            doors.close()
        if fleet is not None:
            fleet.close()
        d.stop(10.0)

    for python, c in caps.items():
        lo, hi = c["start_s"], c["start_s"] + c["return_s"]
        if "latency_ms" in results[0]:  # the open-loop generator
            c["call_max_ms_while_capturing"] = max(
                (float(r["latency_ms"][(r["due_s"] >= lo) & (r["due_s"] < hi)].max())
                 for r in results
                 if ((r["due_s"] >= lo) & (r["due_s"] < hi)).any()),
                default=None)
        c["loop_lag_max_ms_while_capturing"] = max(
            (row[1] for row in poller.rows if lo <= row[0] <= hi + POLL_S),
            default=None)
        reduced = bench.reduce_trace(
            os.path.join(base, names[python]), cell["trace_match"])
        c.update(
            window_s=reduced["window_s"], busy_s=reduced["busy_s"],
            idle_share_pct=100.0 * (1 - reduced["busy_s"] / reduced["window_s"]),
            idle_gaps=reduced["idle_gaps"][:10],
            device_ops=reduced["device_ops"][:10], step=reduced["step"])
        if python == "1":  # the Python tracer names what a thread ran
            c["idle_gap_threads"] = gap_threads(
                os.path.join(base, names[python]))
        if python == "0":  # kept: the operations' full HLO lines are in it
            for f in glob.glob(os.path.join(base, names[python], "**",
                                            "*.xplane.pb"), recursive=True):
                os.makedirs(out_dir, exist_ok=True)
                shutil.copy(f, os.path.join(
                    out_dir, f"{args.workload}.py0.xplane.pb"))
        shutil.rmtree(os.path.join(base, names[python]), ignore_errors=True)

    # when each generator itself ran late, inside the window (its own
    # `late_events` list is used up by the first sends of the warm-up)
    froze = []
    for r in results:
        if "late_ms" in r and len(r["late_ms"]) == len(r["due_s"]):
            late = r["late_ms"] > 5.0
            froze.append([(round(float(t), 2), round(float(ms), 1)) for t, ms
                          in zip(r["due_s"][late][:60], r["late_ms"][late][:60])])
    st = stages["stages"]
    out.update(
        captures=caps,
        call_coverage=stages.get("call_coverage"), calls=stages.get("calls"),
        coverage=stages.get("coverage"), frames=stages.get("frames"),
        batch_coverage=stages.get("batch_coverage"),
        batches=stages.get("batches"),
        door_counters=door_counters(prom0, prom1),
        forwarder=forwarder(st, prom0, prom1),
        by_node=by_node,
        batch_tiles_us=batch_tiles(st),
        threads=thread_shares(threads0, stages.get("threads"),
                              stages.get("batches")),
        stage_means_ms={k: v["mean_ms"] for k, v in st.items()},
        stage_counts={k: v["count"] for k, v in st.items()},
        stage_totals_s={k: v["total_s"] for k, v in st.items()},
        generator={k: v for k, v in summary["generator"].items()},
        end_to_end=summary["end_to_end"],
        failed=summary["failed"], attempted=summary["attempted"],
        window_unix0_ms=unix0_ms,
        generators_late_over_5ms=froze,
        loop_lag_rows_over_20ms=[r for r in poller.rows if r[1] >= 20.0],
        gc_rows_over_20ms=[r for r in poller.rows if r[2] >= 0.02],
        polls=len(poller.rows),
        slow_calls=tiles_of(traces["traces"], SLOW_MS),
        recorder=traces.get("counters"),
        slow_threshold_ms=traces.get("slow_threshold_ms"),
    )
    path = os.path.join(out_dir, f"{args.workload}.json")
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump({**out, "poll_rows": poller.rows,
                   "stages": stages}, f, default=float)
    print(json.dumps(out, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
