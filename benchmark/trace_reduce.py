#!/usr/bin/env python3
"""From a profiler trace (.xplane.pb) to numbers: device busy and idle
time, the time of each compiled program and of each device operation,
and what the host was doing in the longest idle gaps.

    JAX_PLATFORMS=cpu python3 benchmark/trace_reduce.py --match decide <file.xplane.pb>...

prints one JSON object. Reading the file needs JAX (`ProfileData`), so
this runs as a child on the CPU after the daemon has gone; the
arithmetic (`reduce_planes`) takes plain lists and is tested on traces
with known intervals (tests/test_trace_reduce.py).

A plane is a device when its name starts with "/device:" (the
"/device:CUSTOM:" planes apart); its "XLA Ops"
line holds the operations and its "XLA Modules" line one event per
execution of a compiled program. Where a trace has no device plane (the
CPU rehearsal) the XLA CPU client's threads stand in, so the code path
runs; nothing read from such a trace is reported as a device metric.
"""

from __future__ import annotations

import argparse
import json
import re
import sys

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAPS_ATTRIBUTED = 500  # the longest gaps, which hold most of the idle time
MIN_HOST_EVENT_NS = 5_000


def merge(intervals):
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(merged) -> float:
    return float(sum(e - s for s, e in merged))


def gaps(merged, t0: float, t1: float):
    """The idle intervals of [t0, t1) that `merged` leaves."""
    out, at = [], t0
    for s, e in merged:
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
        if at >= t1:
            break
    if at < t1:
        out.append((at, t1))
    return [(s, e) for s, e in out if e > s]


def is_device(plane_name: str) -> bool:
    """'/device:TPU:0' is a chip; '/device:CUSTOM:...' planes are not."""
    return plane_name.startswith("/device:") and ":CUSTOM:" not in plane_name


def op_name(event_name: str) -> str:
    """'%fusion.13 = s32[...] fusion(...)' -> '%fusion.13': the trace
    names an operation by its whole HLO line."""
    return event_name.split(" = ", 1)[0]


def program_name(event_name: str) -> str:
    """'jit__decide(123456789)' -> 'jit__decide': executions of one
    program share a name."""
    return re.sub(r"\(\d+\)$", "", event_name)


def attribute(gap_list, host_events):
    """Idle seconds by what the host was doing: each gap goes to the
    SHORTEST host event that covers at least half of it (the most
    specific thing running), else to 'unattributed'."""
    if not host_events:
        return {"unattributed": sum(e - s for s, e in gap_list) / 1e9}
    names = [n for n, _, _ in host_events]
    start = np.array([s for _, s, _ in host_events], float)
    end = start + np.array([d for _, _, d in host_events], float)
    out = {}
    for s, e in gap_list:
        overlap = np.minimum(end, e) - np.maximum(start, s)
        cand = np.flatnonzero(overlap >= 0.5 * (e - s))
        name = "unattributed"
        if len(cand):
            name = names[cand[np.argmin((end - start)[cand])]]
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def window(planes):
    """[t0, t1): the traced window. The capture's own start_trace and
    stop_trace calls are in the host's trace (the python tracer), and
    stop_trace stalls the process that serves: the window ends where
    it begins. Without them, first event to last."""
    every = [ev for p in planes for ln in p["lines"] for ev in ln["events"]]
    if not every:
        raise ValueError("the trace holds no event")
    t0 = min(s for _, s, _ in every)
    t1 = max(s + d for _, s, d in every)
    starts = [s + d for n, s, d in every if n.endswith(" start_trace")]
    stops = [s for n, s, _ in every if n.endswith(" stop_trace")]
    if starts:
        t0 = max(t0, max(starts))
    if stops and min(stops) > t0:
        t1 = min(t1, min(stops))
    return t0, t1


def reduce_planes(planes, match: str) -> dict:
    """planes: [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}]. Returns busy_s and window_s (busy averaged over
    the device planes), per-program and per-operation seconds, and the
    idle seconds by host activity."""
    device = [p for p in planes if is_device(p["name"])]
    stand_in = not device
    if stand_in:
        device = [{
            "name": "xla-cpu-threads",
            "lines": [
                {"name": OPS_LINE, "events": [
                    ev for p in planes for ln in p["lines"]
                    if ln["name"].startswith(("tf_XLAPjRtCpuClient", "tf_XLAEigen"))
                    for ev in ln["events"] if ev[2] > 0
                ]},
            ],
        }]
    host = [
        ev for p in planes if p["name"].startswith("/host:")
        for ln in p["lines"] if not ln["name"].startswith("tf_XLA")
        for ev in ln["events"] if ev[2] >= MIN_HOST_EVENT_NS
    ]
    t0, t1 = window(planes)

    busy, ops, programs, idle = [], {}, {}, {}
    for p in device:
        op_events = [
            ev for ln in p["lines"] if ln["name"] == OPS_LINE
            for ev in ln["events"]
        ]
        merged = merge([
            (max(s, t0), min(s + d, t1)) for _, s, d in op_events
        ])
        busy.append(busy_ns(merged))
        for name, _, d in op_events:
            ops[op_name(name)] = ops.get(op_name(name), 0.0) + d / 1e9
        for ln in p["lines"]:
            if ln["name"] == MODULES_LINE:
                for name, _, d in ln["events"]:
                    n, tot = programs.get(program_name(name), (0, 0.0))
                    programs[program_name(name)] = (n + 1, tot + d / 1e9)
        longest = sorted(gaps(merged, t0, t1), key=lambda g: g[0] - g[1])
        for name, secs in attribute(longest[:GAPS_ATTRIBUTED], host).items():
            idle[name] = idle.get(name, 0.0) + secs / len(device)
    matched = [(n, c, s) for n, (c, s) in programs.items() if match in n]
    step = None
    if matched:
        count = sum(c for _, c, _ in matched)
        step = {"executions": count,
                "seconds": sum(s for _, _, s in matched),
                "programs": sorted(n for n, _, _ in matched)}
    top = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "device_planes": [p["name"] for p in device],
        "stand_in": stand_in,
        "step": step,
        "programs": [[n, c, s] for n, (c, s) in
                     sorted(programs.items(), key=lambda kv: -kv[1][1])][:20],
        "device_ops": top(ops)[:10],
        "idle_gaps": top(idle)[:10],
    }


def load(paths):
    """The planes of one capture (one file per host) as plain lists."""
    from jax.profiler import ProfileData

    planes = []
    for path in paths:
        for p in ProfileData.from_file(path).planes:
            planes.append({"name": p.name, "lines": [
                {"name": ln.name, "events": [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in ln.events
                ]}
                for ln in p.lines
            ]})
    return planes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--match", required=True,
                    help="substring of the step program's name")
    ap.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    print(json.dumps(reduce_planes(load(args.files), args.match)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
