"""The run: a JAX-free parent that boots the daemon (the only process
that touches the chip), preloads and checks it, starts the generator
processes, measures one window, checks again, stops everything and
prints the result line.

Everything that belongs to one cell, configuration, traffic mix,
generator kind, per-layer metric or reader kind is a file found by its
name (benchmark/cells/<cell>.json and so on): nothing here lists them.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

import check
from harness import daemon as daemon_mod
from harness import keyspace, workers
from harness.daemon import ROOT, BenchFailure, Daemon

BENCH = os.path.join(ROOT, "benchmark")
OUT_DIR = os.path.join(ROOT, "chiprun_out", "benchmark")
BOOT_TIMEOUT = 1100.0  # a cold boot compiles for ~14 minutes (PERF.md)
REBOOT_TIMEOUT = 300.0  # the boot after it finds every program in the cache
PLATFORM_ENVS = ("JAX_PLATFORMS", "GUBER_JAX_PLATFORM")
# a traced run reads spans, counters and the generator's far tail over
# this share of the window and captures the device trace after it: the
# profiler's python tracer, and its stop_trace (which stalls the serving
# loop for seconds), stay out of what is read
READ_SHARE = 0.6


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def load(kind: str, name: str) -> dict:
    path = os.path.join(BENCH, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise BenchFailure(f"no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def build_native() -> float:
    t = time.monotonic()
    native = os.path.join(ROOT, "gubernator_tpu", "native")
    if not os.path.isdir(native):
        raise BenchFailure("the program (gubernator_tpu/) is not in this checkout")
    subprocess.run(["make", "-C", native], check=True, stdout=sys.stderr)
    return time.monotonic() - t


def preload(doors, tag: str, rules, n_keys: int, items: int = 1000) -> int:
    """Create the `n_keys` most frequent keys (zipf ranks 1..n, which
    are the ids 1..n) with hits = 0, each under its own limit; every
    answer must be a fresh, full window. Returns the wrong answers."""
    wrong = 0
    group = 64 * items
    for base in range(1, n_keys + 1, group):
        ids = np.arange(base, min(base + group, n_keys + 1))
        limit = rules.of(ids)[0]
        reqs = keyspace.make_reqs(tag, ids, rules, 0)
        batches = [reqs[i:i + items] for i in range(0, len(reqs), items)]
        got = [a for ans in doors.bulk(batches) for a in ans]
        wrong += sum(
            a != (0, li, li) for a, li in zip(got, limit.tolist())
        )
    return wrong


def pre_window_check(doors, door: str, seed: int, algos) -> dict:
    calls = check.checked_sequence(seed, algos)
    got = [doors.call(door, [keyspace.req(*item) for item in call])
           for call in calls]
    want = check.reference_answers(calls, int(time.time() * 1000))
    n, bad, first = check.compare_sequence(calls, got, want)
    over = sum(a[0] == 1 for g in got for a in g)
    return {"compared": n, "differ": bad, "limit": 0, "over_limit_answers": over,
            "first_difference": first}


def capture_profile(d: Daemon, name: str, ms: int, out: dict) -> None:
    try:
        daemon_mod.http_get(
            d.http, f"/v1/debug/profile?ms={ms}&name={name}", ms / 1e3 + 240.0
        )
        out["t_end"] = time.monotonic()
    except Exception as e:  # a thread: hand the fault to the run
        out["error"] = f"{type(e).__name__}: {e}"


def reduce_trace(profile_dir: str, match: str) -> dict:
    """The reduction runs in a child on the CPU, after the daemon has
    gone: the parent never imports JAX, and no second process may reach
    for the chip."""
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise BenchFailure(f"the profile capture left no trace in {profile_dir}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "trace_reduce.py"),
         "--match", match, *files],
        env=env, capture_output=True, text=True, timeout=300,
    )
    if p.returncode != 0:
        raise BenchFailure(f"trace_reduce failed: {p.stderr[-2000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def layer_metrics(cell_name: str, ctx: dict) -> dict:
    """Every benchmark/layer_metrics/*.json that names this cell, read
    by the reader kind it names; a reader that finds nothing returns
    None and the metric is left out."""
    out = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if cell_name not in spec["cells"]:
            continue
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(spec, ctx)
        if value is not None:
            name = os.path.basename(path)[: -len(".json")]
            out[name] = {"value": value, "unit": spec["unit"]}
    return out


def verdicts(results, spec, doors, span_ms: float, lead_ms: float,
             now_ms: int):
    """The checks after the window: canaries, tallies, well-formedness."""
    rules = keyspace.KeyRules(spec["traffic"])
    canary_faults, n_canary, n_replies = [], 0, 0
    for r in results:
        t = r["tally"]
        peeks = doors.call(spec["door"], [
            keyspace.req(k, 0, li, check.DAY_MS, a) for k, li, a in t["canaries"]
        ])
        for (k, li, a), replies, peek in zip(
            t["canaries"], t["canary_replies"], peeks
        ):
            n_canary += 1
            n_replies += len(replies)
            fault = check.canary_verdict(k, li, a, replies, peek, now_ms)
            if fault:
                canary_faults.append(fault)
    ids, offered, admitted, in_doubt = workers.merge_tallies(results)
    limit, duration, algo = rules.of(ids)
    n_bad, faults, exact = check.tally_faults(
        ids, offered, admitted, in_doubt, limit, duration, algo, span_ms,
        lead_ms
    )
    return {
        "canaries": {"keys": n_canary, "replies": n_replies,
                     "differ": len(canary_faults), "limit": 0,
                     "first": canary_faults[:3]},
        "tallies": {"keys": int(len(ids)), "hits_offered": int(offered.sum()),
                    "hits_admitted": int(admitted.sum()),
                    "keys_held_exactly": exact, "outside_bounds": n_bad,
                    "limit": 0, "first": faults},
        "malformed": {"replies": sum(r["tally"]["malformed"] for r in results),
                      "limit": 0},
    }


def main(argv=None) -> int:
    t_exec = time.monotonic()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--daemon-argv", default="",
                    help="tests only: run this JSON argv in the daemon's place")
    args = ap.parse_args(argv)
    try:
        return run(args, t_exec)
    except BenchFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2


def cache_entries(cache_dir: str) -> int:
    """Programs in JAX's persistent compilation cache."""
    return sum(
        f.endswith("-cache") for _, _, files in os.walk(cache_dir) for f in files
    )


def boot(args, config: dict, t_exec: float):
    """The daemon up and on the device the cell asks for: (daemon,
    device report, the platform named in the environment or '', the
    seconds each boot took).

    The daemon that is measured has found its programs in the compile
    cache. A boot that had to compile some (it added entries to the
    cache: the first run in a checkout) has done the compiling, and is
    stopped and booted once more: a daemon that compiled its ladder
    itself served the window that followed with one stall of ~16 s
    (PERF.md section 6), which no later run of the cell sees."""
    named = next((os.environ[k] for k in PLATFORM_ENVS if os.environ.get(k)), "")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(ROOT, ".jax_cache"))
    argv = json.loads(args.daemon_argv) if args.daemon_argv else None
    deadline, boots = t_exec + BOOT_TIMEOUT, []
    while True:
        cached = cache_entries(cache_dir)
        d = Daemon(args.workload, config["env"], OUT_DIR, argv)
        try:
            t = time.monotonic()
            d.wait_ready(deadline)
            boots.append(time.monotonic() - t)
            report = d.stages()
            device = report["device"]
            if device is None:
                raise BenchFailure("the daemon reports no device")
            if device["platform"] != "tpu" and not named:
                raise BenchFailure(
                    f"the daemon serves from '{device['platform']}', not a TPU"
                )
            if device["count"] < config["chips"]:
                raise BenchFailure(
                    f"{device['count']} devices, the cell needs {config['chips']}"
                )
            compiles = d.compiles()
            added = cache_entries(cache_dir) - cached
            again = added > 0 and len(boots) == 1
            emit(phase="boot", seconds=boots[-1], cold=compiles["cache_hits"] == 0,
                 cache_dir=cache_dir, cache_entries_added=added, boots_again=again,
                 device={k: device[k] for k in ("platform", "kind", "count")},
                 host_prep=report["host_prep"], hasher=report["hasher"], **compiles)
        except BaseException:
            d.stop(10.0)
            raise
        if not again:
            return d, device, named, boots
        d.stop()
        os.replace(d.log_path, d.log_path + ".compiled")
        deadline = time.monotonic() + REBOOT_TIMEOUT


def start_fleet(d: Daemon, kind, seed: int, seconds: float, tag: str,
                cell: dict, config: dict, traffic: dict, read_share: float = 1.0):
    """The generator processes of one window, built and connected:
    (the spec each was given, the fleet, what each said when ready)."""
    spec = {
        "seed": seed, "seconds": seconds, "tag": tag, "cell": cell,
        "config": config, "traffic": traffic, "grpc": d.grpc, "geb": d.geb,
        "door": kind.DOOR, "read_share": read_share,
    }
    fleet = workers.Fleet(
        traffic["generator"],
        [dict(spec, worker=i) for i in range(traffic["workers"])],
    )
    try:
        return spec, fleet, fleet.wait_ready()
    except BaseException:
        fleet.close()
        raise


def snapshot(d: Daemon) -> dict:
    return {"stages": d.stages(), "prom": d.prom()}


def window(args, d: Daemon, fleet, cell: dict, traffic: dict, t_exec: float):
    """Warm-up, then the measured window. Returns what was read at its
    ends; the generators' own results are collected after it."""
    t0 = time.monotonic() + traffic["warmup_s"] + 0.25
    fleet.go(t0)
    workers.wait_until(t0)
    w = {"setup_s": t0 - t_exec, "snap0": snapshot(d),
         "compiles0": d.compiles()["programs"]}
    prof, thread = {}, None
    if args.trace:
        workers.wait_until(t0 + READ_SHARE * args.seconds)
        w["snap1"] = snapshot(d)
        ms = int(min(cell["trace_ms"], args.seconds * 1000 * 0.3))
        prof["t_start"] = time.monotonic()
        thread = threading.Thread(
            target=capture_profile, args=(d, profile_name(args), ms, prof)
        )
        thread.start()
    workers.wait_until(t0 + args.seconds)
    if not args.trace:
        w["snap1"] = snapshot(d)
    w["seconds"] = time.monotonic() - t0
    w["results"] = fleet.results(traffic["drain_timeout_s"])
    if thread is not None:
        thread.join(300.0)
        if "error" in prof or thread.is_alive():
            raise BenchFailure(f"profile capture failed: {prof.get('error')}")
        w["capture_s"] = prof["t_end"] - prof["t_start"]
    w["prom_end"] = d.prom()
    w["compiled"] = d.compiles()["programs"] - w["compiles0"]
    return w


def profile_name(args) -> str:
    return f"bench_{args.workload}"


def run(args, t_exec: float) -> int:
    cell = load("cells", args.workload)
    config = load("configs", cell["config"])
    traffic = load("traffic", cell["traffic"])
    kind = workers.load_kind(traffic["generator"])
    phases = {"build": build_native()}
    profile_dir = os.path.join(
        tempfile.gettempdir(), "guber-profile", profile_name(args))
    shutil.rmtree(profile_dir, ignore_errors=True)

    tag = f"s{args.seed}"
    d, device, named, boots = boot(args, config, t_exec)
    phases["boot"] = sum(boots)
    rehearsal = device["platform"] != "tpu"
    fleet = doors = None
    try:
        from harness.doors import Doors

        doors = Doors(d)
        rules = keyspace.KeyRules(traffic)
        t = t_preload = time.monotonic()
        wrong = preload(doors, tag, rules, config["preload_keys"])
        phases["preload"] = time.monotonic() - t
        emit(phase="preload", keys=config["preload_keys"], wrong=wrong,
             limit=0, seconds=phases["preload"])

        t = time.monotonic()
        algos = [keyspace.ALGORITHMS[a["algorithm"]] for a in traffic["algorithms"]]
        pre = pre_window_check(doors, kind.DOOR, args.seed, algos)
        phases["check"] = time.monotonic() - t
        emit(phase="pre_window_check", door=kind.DOOR, **pre)

        t = time.monotonic()
        spec, fleet, ready = start_fleet(
            d, kind, args.seed, args.seconds, tag, cell, config, traffic,
            READ_SHARE if args.trace else 1.0,
        )
        phases["generators"] = time.monotonic() - t
        phases["warmup"] = traffic["warmup_s"]
        emit(phase="generators_ready", seconds=phases["generators"], workers=ready)

        w = window(args, d, fleet, cell, traffic, t_exec)
        results = w.pop("results")
        summary = kind.summarize(results, spec)
        first_sent = min(r["first_sent"] for r in results)
        span_ms = 1e3 * (max(r["last_done"] for r in results) - first_sent)
        post = verdicts(results, spec, doors, span_ms,
                        1e3 * (first_sent - t_preload), int(time.time() * 1000))
        post["tallies"]["span_ms"] = span_ms
        post["tallies"]["since_preload_ms"] = span_ms + 1e3 * (first_sent - t_preload)
        after = d.stages()["device"]
        doors.close()
        doors = None
        fleet.close()
        fleet = None
        rc = d.stop()
    finally:
        if doors is not None:
            doors.close()
        if fleet is not None:
            fleet.close()
        d.stop(10.0)

    # evictions and dropped creates are held to the whole run, not only
    # the window: the preload must not have cost a live key either
    counters = {
        name: w["prom_end"].get(name, 0.0)
        for name in ("store_evictions_total", "store_dropped_creates_total")
    }
    emit(phase="window", seconds=w["seconds"], setup_s=w["setup_s"],
         phases=phases, generator=summary["generator"], daemon_exit=rc)
    emit(phase="post_window_check", **post, counters_whole_run=counters,
         counters_limit=0, programs_compiled_in_window=w["compiled"])
    if w["compiled"]:
        raise BenchFailure(
            f"{w['compiled']} programs compiled inside the window: "
            "its timings are void"
        )
    # the daemon's exit code after its drain is printed (`daemon_exit`)
    # and decides nothing: how a process leaves the device runtime is
    # no answer of a rate limiter
    held = {
        "preload answers": wrong == 0,
        "pre-window sequence": pre["differ"] == 0
        and pre["over_limit_answers"] > 0,
        "canaries": post["canaries"]["differ"] == 0,
        "tallies": post["tallies"]["outside_bounds"] == 0,
        "well-formed replies": post["malformed"]["replies"] == 0,
        "no eviction, no dropped create": not any(counters.values()),
        "no failed call": summary["failed"] == 0,
    }
    correct = all(held.values())
    if not correct:
        print("benchmark: not correct: "
              + ", ".join(k for k, ok in held.items() if not ok)
              + f"; {json.dumps(post)[:1500]}", file=sys.stderr)

    peaks = [x.get("peak_bytes_in_use") for x in after["devices"]]
    dev = {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"],
        "memory_peak_bytes": max((p for p in peaks if p), default=None),
    }
    line = {"correct": correct, "attempted": summary["attempted"],
            "failed": summary["failed"], "metrics": {}, "device": dev}
    if args.trace:
        reduced = reduce_trace(profile_dir, cell["trace_match"])
        shutil.rmtree(profile_dir, ignore_errors=True)
        ctx = {"stages0": w["snap0"]["stages"], "stages1": w["snap1"]["stages"],
               "prom0": w["snap0"]["prom"], "prom1": w["snap1"]["prom"],
               "trace": reduced, "generator": summary["generator"],
               "device_kind": device["kind"], "config": config, "cell": cell}
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                             "idle_gaps": reduced["idle_gaps"][:10]}
        found = layer_metrics(args.workload, ctx)
        if not rehearsal:
            line["metrics"] = found
        emit(phase="trace", planes=reduced["device_planes"],
             layer_metrics_read=sorted(found),
             programs=reduced["programs"][:6], capture_s=w["capture_s"])
    elif not rehearsal:
        line["metrics"] = {
            name: {"value": v, "unit": u}
            for name, (v, u) in summary["end_to_end"].items()
        }
        line["metrics"]["setup_s"] = {"value": w["setup_s"], "unit": "s"}
    if rehearsal:
        # a named non-TPU platform: the run proves the harness and the
        # checks, and reports no timing under a device metric's name
        line["rehearsal"] = named
    print(json.dumps(line), flush=True)
    return 3 if rehearsal else 0
