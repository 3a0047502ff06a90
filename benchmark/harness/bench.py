"""The run: a JAX-free parent that boots the configuration's daemons
(the only processes that touch a chip; one unless the configuration
says `nodes`: harness/daemon.py), preloads and checks them through node
0, starts the generator processes, measures one window, checks again,
stops everything and prints the result line.

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
from harness.daemon import ROOT, BenchFailure, Daemon, Ring

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
    n_bad, faults, exact, seen = check.tally_faults(
        ids, offered, admitted, in_doubt, limit, duration, algo, span_ms,
        lead_ms
    )
    return {
        "canaries": {"keys": n_canary, "replies": n_replies,
                     "differ": len(canary_faults), "limit": 0,
                     "first": canary_faults[:3]},
        "tallies": {"keys": int(len(ids)), "hits_offered": int(offered.sum()),
                    "hits_admitted": int(admitted.sum()),
                    "hits_in_doubt": int(in_doubt.sum()),
                    "keys_held_exactly": exact, "outside_bounds": n_bad,
                    "limit": 0, "first": faults, **seen},
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
                    help="tests only: run this JSON argv in the daemon's place; "
                         "an object {node: argv} replaces those nodes alone")
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


def daemon_argvs(text: str, n: int) -> dict:
    """--daemon-argv as {node: argv}: a JSON list is every node's."""
    if not text:
        return {}
    argv = json.loads(text)
    if isinstance(argv, dict):
        return {int(i): a for i, a in argv.items()}
    return dict.fromkeys(range(n), argv)


def boot_nodes(ring: Ring, which, deadline: float, named: str, cache_dir: str,
               boots: list, first: bool) -> list:
    """Nodes `which` started together and waited for, each held to the
    device its configuration says; the seconds until the last was Ready
    go to `boots`. Returns the nodes that compiled a program."""
    n, cached = len(ring.nodes), cache_entries(cache_dir)
    started = [ring.start(i) for i in which]
    t = time.monotonic()
    for d in started:
        d.wait_ready(deadline, n)
    boots.append(time.monotonic() - t)
    built = []
    for d in started:
        where = f" (node {d.index})" if n > 1 else ""
        report = d.stages()
        device = ring.devices[d.index] = report["device"]
        chips = ring.specs[d.index]["chips"]
        if device is None:
            raise BenchFailure(f"the daemon{where} reports no device")
        if device["platform"] != "tpu" and not named:
            raise BenchFailure(
                f"the daemon{where} serves from '{device['platform']}', not a TPU"
            )
        # one daemon may see more chips than it uses (a one-chip cell run
        # on a four-chip machine); a ring's node sees its own and no other's
        if device["count"] < chips or (n > 1 and device["count"] != chips):
            raise BenchFailure(
                f"{device['count']} devices{where}, the cell needs {chips}"
            )
        compiles = d.compiles()
        if compiles.pop("built"):
            built.append(d.index)
        emit(phase="boot", seconds=boots[-1], cold=compiles["cache_hits"] == 0,
             cache_dir=cache_dir,
             cache_entries_added=cache_entries(cache_dir) - cached,
             boots_again=first and d.index in built,
             device={k: device[k] for k in ("platform", "kind", "count")},
             host_prep=report["host_prep"], hasher=report["hasher"], **compiles,
             **({"node": d.index} if n > 1 else {}))
    return built


def set_aside(ring: Ring, which) -> float:
    """The nodes that compiled stopped, their logs kept; the deadline of
    the boot that follows, which finds every program in the cache."""
    for i in which:
        ring.nodes[i].stop()
        os.replace(ring.nodes[i].log_path, ring.nodes[i].log_path + ".compiled")
    return time.monotonic() + REBOOT_TIMEOUT


def boot(args, config: dict, t_exec: float, tag: str = None):
    """The configuration's daemons up, each on the device it asks for:
    (the ring, node 0's device report, the platform named in the
    environment or '', the seconds each boot took). Every ring is drawn
    for the run's key `tag`, so node 0 owns least of the zipf head
    whichever of its boots is measured (a script that gives none gets
    the circle's order as drawn).

    The daemons that are measured have found their programs in the
    compile cache. A boot that had to compile some (its own log says so:
    the first run in a checkout) has done the compiling, and is stopped
    and booted once more: a daemon that compiled its ladder itself
    served the window that followed with one stall of ~16 s (PERF.md
    section 6), which no later run of the cell sees.

    Node 0 boots first, alone, under that rule (its second boot on
    addresses drawn anew, as one daemon's always was: no peer has been
    given the list yet); the others then boot together, and those of
    them that compiled once more, on the addresses their peers know."""
    named = next((os.environ[k] for k in PLATFORM_ENVS if os.environ.get(k)), "")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(ROOT, ".jax_cache"))
    argvs = daemon_argvs(args.daemon_argv, len(daemon_mod.node_specs(config)))
    how = (named, cache_dir)
    deadline, boots = t_exec + BOOT_TIMEOUT, []
    ring = Ring(args.workload, config, OUT_DIR, argvs, tag)
    try:
        if boot_nodes(ring, [0], deadline, *how, boots, True):
            deadline = set_aside(ring, [0])
            ring.stop()  # lets the ports held for the other nodes go
            ring = Ring(args.workload, config, OUT_DIR, argvs, tag)
            boot_nodes(ring, [0], deadline, *how, boots, False)
        others = list(range(1, len(ring.nodes)))
        if others:
            deadline = max(deadline, t_exec + BOOT_TIMEOUT)
            built = boot_nodes(ring, others, deadline, *how, boots, True)
            if built:
                deadline = set_aside(ring, built)
                boot_nodes(ring, built, deadline, *how, boots, False)
    except BaseException:
        ring.stop(10.0)
        raise
    return ring, ring.devices[0], named, boots


def start_fleet(ring: Ring, kind, seed: int, seconds: float, tag: str,
                cell: dict, config: dict, traffic: dict, read_share: float = 1.0):
    """The generator processes of one window, built and connected:
    (the spec each was given, the fleet, what each said when ready).
    `grpc` and `geb` are node 0's; `nodes` has every node's doors."""
    spec = {
        "seed": seed, "seconds": seconds, "tag": tag, "cell": cell,
        "config": config, "traffic": traffic, "grpc": ring.grpc, "geb": ring.geb,
        "door": kind.DOOR, "read_share": read_share, "nodes": ring.addrs,
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


def snapshot(ring: Ring) -> dict:
    """Every node's stages and counters, one request each; node 0's at
    the top as well."""
    nodes = [{"stages": d.stages(), "prom": d.prom()} for d in ring.nodes]
    return dict(nodes[0], nodes=nodes)


def programs_built(ring: Ring) -> int:
    return sum(d.compiles()["programs"] for d in ring.nodes)


def window(args, ring: Ring, fleet, cell: dict, traffic: dict, t_exec: float):
    """Warm-up, then the measured window. Returns what was read at its
    ends; the generators' own results are collected after it."""
    t0 = time.monotonic() + traffic["warmup_s"] + 0.25
    fleet.go(t0)
    workers.wait_until(t0)
    w = {"setup_s": t0 - t_exec, "snap0": snapshot(ring),
         "compiles0": programs_built(ring)}
    prof, thread = {}, None
    if args.trace:
        workers.wait_until(t0 + READ_SHARE * args.seconds)
        w["snap1"] = snapshot(ring)
        ms = int(min(cell["trace_ms"], args.seconds * 1000 * 0.3))
        prof["t_start"] = time.monotonic()
        thread = threading.Thread(
            target=capture_profile,
            args=(ring.nodes[cell.get("trace_node", 0)], profile_name(args),
                  ms, prof),
        )
        thread.start()
    workers.wait_until(t0 + args.seconds)
    ring.check_alive()  # before any door is asked through a dead node
    if not args.trace:
        w["snap1"] = snapshot(ring)
    w["seconds"] = time.monotonic() - t0
    w["results"] = fleet.results(traffic["drain_timeout_s"])
    if thread is not None:
        thread.join(300.0)
        if "error" in prof or thread.is_alive():
            raise BenchFailure(f"profile capture failed: {prof.get('error')}")
        w["capture_s"] = prof["t_end"] - prof["t_start"]
    w["prom_end"] = [d.prom() for d in ring.nodes]
    w["compiled"] = programs_built(ring) - w["compiles0"]
    return w


def layer_ctx(snap0: dict, snap1: dict, reduced: dict, generator: dict,
              device_kind: str, config: dict, cell: dict) -> dict:
    """What a reader is handed: node 0's two snapshots at the top, and
    for a ring every node's under `nodes` (readers/nodes.py chooses)."""
    def ends(a, b):
        return {"stages0": a["stages"], "stages1": b["stages"],
                "prom0": a["prom"], "prom1": b["prom"]}

    ctx = {**ends(snap0, snap1), "trace": reduced, "generator": generator,
           "device_kind": device_kind, "config": config, "cell": cell}
    if len(snap0["nodes"]) > 1:
        ctx["nodes"] = [ends(a, b) for a, b in zip(snap0["nodes"], snap1["nodes"])]
    return ctx


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
    ring, device, named, boots = boot(args, config, t_exec, tag)
    n_nodes = len(ring.nodes)
    if n_nodes > 1:
        peers = [a["grpc"] for a in ring.addrs]
        emit(phase="ring", peers=peers,
             head_ids_owned=daemon_mod.head_owned(tag, peers))
    phases["boot"] = sum(boots)
    rehearsal = device["platform"] != "tpu"
    fleet = doors = None
    try:
        from harness.doors import Doors

        doors = Doors(ring)
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
            ring, kind, args.seed, args.seconds, tag, cell, config, traffic,
            READ_SHARE if args.trace else 1.0,
        )
        phases["generators"] = time.monotonic() - t
        phases["warmup"] = traffic["warmup_s"]
        emit(phase="generators_ready", seconds=phases["generators"], workers=ready)

        w = window(args, ring, fleet, cell, traffic, t_exec)
        results = w.pop("results")
        summary = kind.summarize(results, spec)
        first_sent = min(r["first_sent"] for r in results)
        span_ms = 1e3 * (max(r["last_done"] for r in results) - first_sent)
        post = verdicts(results, spec, doors, span_ms,
                        1e3 * (first_sent - t_preload), int(time.time() * 1000))
        post["tallies"]["span_ms"] = span_ms
        post["tallies"]["since_preload_ms"] = span_ms + 1e3 * (first_sent - t_preload)
        after = [d.stages()["device"] for d in ring.nodes]
        doors.close()
        doors = None
        fleet.close()
        fleet = None
        rc = ring.stop()
    finally:
        if doors is not None:
            doors.close()
        if fleet is not None:
            fleet.close()
        ring.stop(10.0)

    # evictions and dropped creates are held to the whole run, not only
    # the window: the preload must not have cost a live key either; on
    # every node
    counters = {
        name: sum(prom.get(name, 0.0) for prom in w["prom_end"])
        for name in ("store_evictions_total", "store_dropped_creates_total")
    }
    emit(phase="window", seconds=w["seconds"], setup_s=w["setup_s"],
         phases=phases, generator=summary["generator"], daemon_exit=rc,
         **({"node_exits": ring.exits} if n_nodes > 1 else {}))
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
    # every number compared, beside its limit: the run's last lines on
    # standard error and the last key of its result line
    compared = {
        "preload_wrong": wrong, "pre_window_differ": pre["differ"],
        "canaries_differ": post["canaries"]["differ"],
        "tallies_outside_bounds": post["tallies"]["outside_bounds"],
        "malformed_replies": post["malformed"]["replies"],
        "evictions": counters["store_evictions_total"],
        "dropped_creates": counters["store_dropped_creates_total"],
        "failed": summary["failed"],
    }
    checks = {name: {"value": v, "limit": 0} for name, v in compared.items()}
    checks["pre_window_over_limit_answers"] = {
        "value": pre["over_limit_answers"], "at_least": 1}
    if not correct:
        print("benchmark: not correct: "
              + ", ".join(k for k, ok in held.items() if not ok)
              + f"; {json.dumps(post)[:1500]}", file=sys.stderr)

    peaks = [x.get("peak_bytes_in_use") for a in after for x in a["devices"]]
    dev = {
        "platform": device["platform"], "kind": device["kind"],
        "count": sum(x["count"] for x in ring.devices),
        "memory_peak_bytes": max((p for p in peaks if p), default=None),
    }
    if n_nodes > 1:
        dev["nodes"] = n_nodes
    line = {"correct": correct, "attempted": summary["attempted"],
            "failed": summary["failed"], "metrics": {}, "device": dev}
    if args.trace:
        reduced = reduce_trace(profile_dir, cell["trace_match"])
        shutil.rmtree(profile_dir, ignore_errors=True)
        ctx = layer_ctx(w["snap0"], w["snap1"], reduced, summary["generator"],
                        device["kind"], config, cell)
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
    line["checks"] = checks
    for name, c in checks.items():
        print(f"benchmark: compared {name}: {json.dumps(c)}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 3 if rehearsal else 0
