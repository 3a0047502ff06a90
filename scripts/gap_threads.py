#!/usr/bin/env python3
"""Which THREAD was in each of a capture's top idle gaps.

    JAX_PLATFORMS=cpu python3 scripts/gap_threads.py <file.xplane.pb>...

benchmark/trace_reduce.py gives each device-idle gap to the SHORTEST
host event that covers half of it and reports the event's NAME: `get`
tops every one-chip list (1.2-1.3 s of a 2 s capture) and says nothing
— any pool worker's idle `SimpleQueue.get` spans the gap between two
steps by construction. This keeps the same attribution (trace_reduce's
own window, merge and gaps; not a line of it is edited) and adds the
host LINE the winning event sits on, i.e. the thread, and what else ran
on the other threads during the gaps that went to such a wait. The
profiler names every Python thread's line "python3", so a line is
labelled by its index and by the serving thread its own events give it
away as (`role_of`): "python3#12 (guber-fetch)".
Prints one JSON object. A study tool (PERF.md section 7.1), run by
scripts/trace_study.py on its Python-tracer capture.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import trace_reduce as tr  # noqa: E402

TOP = 6


#: the stage clock's thread-bound spans (and the Python tracer's frames)
#: that only one kind of serving thread records
ROLE_MARKS = (
    ("guber-submit", ("submit_call",)),
    ("guber-fetch", ("fetch_wait",)),
    ("loop", ("bridge_decode", "encode", " _run_once")),
    ("guber-prep", (" prep_group", " prep_reqs")),
)


def role_of(names) -> str:
    """The serving thread a host line belongs to, from its events."""
    for role, marks in ROLE_MARKS:
        if any(n == m or n.endswith(m) for n in names for m in marks):
            return role
    return ""


def host_events(planes):
    """(name, start, duration, line label) of what trace_reduce calls
    host; the label tells lines of one name apart."""
    out = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for i, ln in enumerate(p["lines"]):
            if ln["name"].startswith("tf_XLA"):
                continue
            role = role_of({ev[0] for ev in ln["events"]})
            label = f"{ln['name']}#{i}" + (f" ({role})" if role else "")
            out += [(*ev, label) for ev in ln["events"]
                    if ev[2] >= tr.MIN_HOST_EVENT_NS]
    return out


def gaps_by_thread(planes) -> dict:
    device = [p for p in planes if tr.is_device(p["name"])]
    if not device:
        return {"error": "no device plane (a CPU capture)"}
    host = host_events(planes)
    start = np.array([s for _, s, _, _ in host], float)
    end = start + np.array([d for _, _, d, _ in host], float)
    t0, t1 = tr.window(planes)
    by_name, lines_of = {}, {}
    # during the gaps that went to a bare wait: the longest event of
    # every OTHER line that overlaps the gap, by line and name
    meanwhile = {}
    for p in device:
        ops = [ev for ln in p["lines"] if ln["name"] == tr.OPS_LINE
               for ev in ln["events"]]
        merged = tr.merge([(max(s, t0), min(s + d, t1)) for _, s, d in ops])
        longest = sorted(tr.gaps(merged, t0, t1), key=lambda g: g[0] - g[1])
        for s, e in longest[:tr.GAPS_ATTRIBUTED]:
            overlap = np.minimum(end, e) - np.maximum(start, s)
            cand = np.flatnonzero(overlap >= 0.5 * (e - s))
            if not len(cand):
                continue
            win = cand[np.argmin((end - start)[cand])]
            name, line = host[win][0], host[win][3]
            secs = (e - s) / 1e9 / len(device)
            by_name[name] = by_name.get(name, 0.0) + secs
            per = lines_of.setdefault(name, {})
            per[line] = per.get(line, 0.0) + secs
            for j in cand:
                other = host[j]
                if other[3] != line:
                    key = f"{other[3]} :: {other[0]}"
                    slot = meanwhile.setdefault(name, {})
                    slot[key] = slot.get(key, 0.0) + secs

    def top(d, n):
        return sorted(([k, round(v, 4)] for k, v in d.items()),
                      key=lambda kv: -kv[1])[:n]

    names = [k for k, _ in top(by_name, TOP)]
    return {
        "window_s": (t1 - t0) / 1e9,
        "host_lines": sorted({ln for _, _, _, ln in host}),
        "idle_gaps": top(by_name, TOP),
        "thread_of": {n: top(lines_of[n], 4) for n in names},
        "covering_on_other_threads": {
            n: top(meanwhile.get(n, {}), 8) for n in names[:2]},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    print(json.dumps(gaps_by_thread(tr.load(args.files))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
