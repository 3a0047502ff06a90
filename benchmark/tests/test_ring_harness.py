"""A configuration of several daemons (`"nodes"`: harness/daemon.py):

- the ring's environment is composed as stated, and a configuration
  that says nothing is one daemon given exactly what the harness always
  gave it: no peer variable, the same keys in the generators' spec, the
  readers' ctx and the result line (golden lists from the parent);
- a three-node ring added as NEW FILES ONLY to a copy of the benchmark
  boots, is preloaded and checked through node 0, serves a window with
  forwarded items on the other two nodes and comes out `correct: true`;
  with one faulty node `correct: false`; with a node lost under load no
  result at all, and the failure names the node;
- `benchmark/sweep.py` steps an open-loop cell of one daemon and of a
  ring, booted as the harness boots them.

No port is fixed anywhere: every run draws its own, so the whole runs
may go side by side (`pytest -n 4`).

Whole runs on the CPU (a named platform: the look for a chip is skipped
and no timing is reported), up to a minute each.
"""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from harness import bench, daemon
from readers import gauge, nodes, prom, prom_sum, stage_quantile, stages

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TINY_ENV = {"GUBER_STORE_TARGET_KEYS": "20000", "GUBER_SKETCH_MIB": "1",
            "GUBER_BACKEND": "tpu"}
PEER_ENVS = {"GUBER_PEERS", "GUBER_ADVERTISE_ADDRESS", "GUBER_GEB_PEER_DOORS"}
RING = "dummy-ring3.dummy-frames"
ONE = "dummy-one.dummy-frames"
RING_CALLS = "dummy-ring3.dummy-calls"
ONE_CALLS = "dummy-one.dummy-calls"

# -- the environment, no boot -------------------------------------------------


def ring_config(n=3):
    return {"chips": n, "env": dict(TINY_ENV, SHARED="config"),
            "nodes": [{"env": {"SHARED": f"node{i}", "OWN": str(i)} if i else {},
                       "chips": 1} for i in range(n)]}


def drawn(n, tag=None):
    addrs, held = daemon.draw_addresses(n, tag)
    for s in sum(held, []):
        s.close()
    return addrs


def arcs(addrs):
    """Each node's share of the circle, the nodes taken in the order given."""
    points = [daemon.ring_point(a["grpc"]) for a in addrs]
    return [(points[i] - points[i - 1]) % 2**32 / 2**32 for i in range(len(addrs))]


def test_the_rings_environment_is_composed_as_stated():
    specs = daemon.node_specs(ring_config())
    addrs = drawn(3)
    ports = [a[door].rsplit(":", 1)[1] for a in addrs for door in a]
    assert len(set(ports)) == 9  # gRPC, HTTP, GEB x 3, all drawn at once
    envs = [daemon.node_env(s["env"], addrs, i) for i, s in enumerate(specs)]
    assert len({e["GUBER_PEERS"] for e in envs}) == 1  # one list on every node
    assert envs[0]["GUBER_PEERS"].split(",") == [a["grpc"] for a in addrs]
    doors = ",".join(f"{a['grpc']}={a['geb']}" for a in addrs)
    for i, e in enumerate(envs):
        assert e["GUBER_ADVERTISE_ADDRESS"] == e["GUBER_GRPC_ADDRESS"] == addrs[i]["grpc"]
        assert e["GUBER_HTTP_ADDRESS"] == addrs[i]["http"]
        assert addrs[i]["geb"].endswith(":" + e["GUBER_GEB_PORT"])
        assert e["GUBER_GEB_PEER_DOORS"] == doors
        assert e["GUBER_BACKEND"] == "tpu"  # the configuration's env on every node
    # nodes[i].env over env
    assert [e["SHARED"] for e in envs] == ["config", "node1", "node2"]
    assert "OWN" not in envs[0] and envs[2]["OWN"] == "2"


@pytest.mark.parametrize("n", [2, 3, 4])
def test_a_rings_ports_are_drawn_held_and_cut_the_circle_evenly(n):
    """No port is fixed: two runs on one machine never meet. The arcs
    are even all the same (by the PROGRAM's ring hash, not the
    harness's copy), so every node owns an n-th of the keys in every
    run; a node's ports stay bound until the node is started."""
    import socket

    from gubernator_tpu.core.hashing import ring_hash

    ring = daemon.Ring("x", ring_config(n), "/nonexistent")
    try:
        again = daemon.Ring("x", ring_config(n), "/nonexistent")
        ports = {a[door] for r in (ring, again) for a in r.addrs for door in a}
        assert len(ports) == 6 * n  # two rings at once share none
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            low = int(f.read().split()[0])  # and none a neighbour's bind(0) draws
        assert all(1024 <= int(a.rsplit(":", 1)[1]) < low for a in ports)
        again.stop()
        points = [ring_hash(a["grpc"]) for a in ring.addrs]
        arcs = [(points[i] - points[i - 1]) % 2**32 / 2**32 for i in range(n)]
        assert all(abs(arc - 1 / n) < 0.01 for arc in arcs), arcs
        for a in ring.addrs:
            for door in a:
                host, port = a[door].rsplit(":", 1)
                with socket.socket() as s, pytest.raises(OSError):
                    s.bind((host, int(port)))
        ring._let_go(1)  # what start(1) does first
        host, port = ring.addrs[1]["grpc"].rsplit(":", 1)
        with socket.socket() as s:
            s.bind((host, int(port)))
    finally:
        ring.stop()


def test_the_door_node_owns_least_of_the_zipf_head_and_never_key_id_1():
    """Node 0 is the node the clients of `ring4.geb-frames` dial, and a
    run in which it owns key id 1 sat 9% above one in which it does not
    (PERF.md section 2). Given the run's key tag the ring starts at the
    node that owns none of ids 1-16 whenever one of the four does, else
    at the one without id 1 that owns the fewest of 2-16; the owner is
    the PLAIN REFERENCE's (`reference_ring.owner_of` over the hash key
    the generators send), the arcs are as even as without a tag and in
    the circle's order, and two rings drawn at once share no port."""
    import reference_ring

    from harness import keyspace

    n = 4
    for seed in range(200):
        tag = f"s{2**31 + 7919 * seed}"
        addrs, held = daemon.draw_addresses(n, tag)
        again = drawn(n, tag)  # while the first ring's ports are held
        for s in sum(held, []):
            s.close()
        both = [a[door] for a in addrs + again for door in a]
        assert len(set(both)) == 6 * n  # two rings at once share none
        peers = [a["grpc"] for a in addrs]
        owner = {k: peers.index(reference_ring.owner_of(
            f"{keyspace.NAME}_{tag}:{k}", peers)) for k in daemon.HEAD_IDS}
        owned = [[k for k in daemon.HEAD_IDS if owner[k] == i] for i in range(n)]
        assert owned == daemon.head_owned(tag, peers)
        assert 1 not in owned[0], (tag, owned)
        if any(not o for o in owned):
            assert not owned[0], (tag, owned)
        else:  # every node owns some: the fewest among those without id 1
            assert len(owned[0]) == min(len(o) for o in owned if 1 not in o)
        assert all(abs(arc - 1 / n) < 0.01 for arc in arcs(addrs))  # and in order


def test_a_ring_drawn_without_a_tag_is_in_the_circles_order_as_ever():
    assert all(abs(arc - 0.25) < 0.01 for arc in arcs(drawn(4)))
    # one daemon has no ring to order, tag or none
    (one,) = drawn(1, "s7")
    assert set(one) == {"grpc", "http", "geb"}


def test_one_node_is_given_what_the_harness_always_gave_it():
    config = {"chips": 1, "env": dict(TINY_ENV)}
    (spec,) = daemon.node_specs(config)
    assert spec == {"env": config["env"], "chips": 1}
    addrs = drawn(1)
    env = daemon.node_env(spec["env"], addrs, 0)
    assert not PEER_ENVS & set(env)
    # golden: the parent's Daemon set these five over the configuration's
    assert set(env) == set(TINY_ENV) | {
        "GUBER_GRPC_ADDRESS", "GUBER_HTTP_ADDRESS", "GUBER_GEB_PORT",
        "JAX_LOG_COMPILES", "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"}
    assert (env["JAX_LOG_COMPILES"], env[
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]) == ("1", "0")
    # "nodes" of one is a ring of one: no peer variable either
    one = daemon.node_specs(dict(config, nodes=[{"env": {"A": "b"}, "chips": 1}]))
    assert not PEER_ENVS & set(daemon.node_env(one[0]["env"], addrs, 0))
    assert one[0]["env"]["A"] == "b"


def test_a_configuration_without_nodes_is_one_daemon():
    for name in os.listdir(os.path.join(BENCH, "configs")):
        with open(os.path.join(BENCH, "configs", name)) as f:
            config = json.load(f)
        specs = daemon.node_specs(config)
        if "nodes" in config:  # a ring: its nodes' chips are the cell's
            assert len(specs) == len(config["nodes"]) > 1, name
            assert sum(s["chips"] for s in specs) == config["chips"], name
        else:
            assert specs == [{"env": config["env"], "chips": config["chips"]}], name


@pytest.mark.parametrize("config", [
    dict(ring_config(), chips=4),  # the nodes' chips do not sum to the cell's
    dict(ring_config(), nodes=[]),
])
def test_nodes_that_do_not_add_up_are_refused(config):
    with pytest.raises(daemon.BenchFailure):
        daemon.node_specs(config)


def test_daemon_argv_for_every_node_or_for_some():
    assert bench.daemon_argvs("", 3) == {}
    assert bench.daemon_argvs('["x.py", "f"]', 3) == {i: ["x.py", "f"] for i in range(3)}
    assert bench.daemon_argvs('{"1": ["x.py"]}', 3) == {1: ["x.py"]}


def test_a_log_that_finished_more_programs_than_it_hit_compiled(tmp_path):
    """`boot()` boots again after a boot that compiled, by the boot's
    own log; both handlers print each line, so lines are compared with
    lines. The cache's growth does not enter (one that evicts reads -1)."""
    hit = ("Persistent compilation cache hit for 'jit_f' with key 'jit_f-{k}'\n"
           "Finished XLA compilation of jit(f) in 0.01 sec\n")
    miss = "Finished XLA compilation of jit(g) in 47.5 sec\n"
    d = daemon.Daemon.__new__(daemon.Daemon)
    d.log_path = str(tmp_path / "log")
    for text, built, programs, hits in (
        ("".join(hit.format(k=k) * 2 for k in range(5)), False, 10, 5),
        ("".join(hit.format(k=k) * 2 for k in range(5)) + miss * 2, True, 12, 5),
        (hit.format(k=1) * 4, False, 4, 1),  # one key hit twice: still no compile
        ("", False, 0, 0),
    ):
        (tmp_path / "log").write_text(text)
        c = d.compiles()
        assert (c["built"], c["programs"], c["cache_hits"]) == (built, programs, hits)


# -- what the generators and the readers are handed ----------------------------

SPEC_KEYS = {"seed", "seconds", "tag", "cell", "config", "traffic", "grpc",
             "geb", "door", "read_share", "worker"}  # the parent's
CTX_KEYS = {"stages0", "stages1", "prom0", "prom1", "trace", "generator",
            "device_kind", "config", "cell"}  # the parent's


def fake_ring(n):
    ring = daemon.Ring("x", ring_config(n) if n > 1 else
                       {"chips": 1, "env": {}}, "/nonexistent")
    ring.stop()  # no node was started: the held ports are let go
    ring.nodes = [types.SimpleNamespace(**a) for a in ring.addrs]
    return ring


@pytest.mark.parametrize("n", [1, 3])
def test_the_generators_spec_keeps_node_0_at_the_top(monkeypatch, n):
    seen = []

    class Fleet:
        def __init__(self, kind, specs):
            seen.extend(specs)

        def wait_ready(self):
            return ["ready"]

    monkeypatch.setattr(bench.workers, "Fleet", Fleet)
    ring = fake_ring(n)
    kind = types.SimpleNamespace(DOOR="geb")
    spec, _, ready = bench.start_fleet(
        ring, kind, 7, 3.0, "s7", {}, {}, {"generator": "g", "workers": 2})
    assert ready == ["ready"] and len(seen) == 2
    assert set(seen[0]) == SPEC_KEYS | {"nodes"}
    assert (spec["grpc"], spec["geb"]) == (ring.addrs[0]["grpc"], ring.addrs[0]["geb"])
    assert spec["nodes"] == ring.addrs and len(spec["nodes"]) == n
    assert all(set(a) == {"grpc", "http", "geb"} for a in spec["nodes"])


def snap(values):
    nodes_ = [{"stages": {"stages": {"s": {"count": c, "total_s": t, "buckets": b}},
                          "bucket_edges_s": [1.0, 2.0]},
               "prom": {"a_total": a, "b_total": c, "level": a}}
              for c, t, b, a in values]
    return dict(nodes_[0], nodes=nodes_)


def test_the_readers_ctx_and_which_node_a_metric_file_reads():
    zero = [(0, 0.0, [0, 0, 0], 0.0)] * 3
    # node 0: 10 samples, 1 s; node 1: 30 samples, 6 s; node 2: nothing
    end = [(10, 1.0, [10, 0, 0], 100.0), (30, 6.0, [0, 30, 0], 900.0),
           (0, 0.0, [0, 0, 0], 0.0)]
    one = bench.layer_ctx(snap(zero[:1]), snap(end[:1]), {}, {}, "cpu", {}, {})
    assert set(one) == CTX_KEYS  # one daemon: the parent's keys and no other
    ring = bench.layer_ctx(snap(zero), snap(end), {}, {}, "cpu", {}, {})
    assert set(ring) == CTX_KEYS | {"nodes"} and len(ring["nodes"]) == 3
    assert nodes.chosen({}, one) == [one] == nodes.chosen({"node": "all"}, one)
    assert nodes.chosen({"node": 1}, ring) == [ring["nodes"][1]]
    assert nodes.chosen({"node": 1}, one) == []  # no such node: nothing is read
    for reader, spec in ((prom, {"delta": "a_total", "per_delta": "b_total"}),
                         (stage_quantile, {"stage": "s", "q": 0.5, "scale": 1.0})):
        assert reader.read(dict(spec, node=1), one) is None

    def readings(reader, spec):
        return [reader.read(dict(spec, **which), ctx)
                for ctx, which in ((one, {}), (ring, {}), (ring, {"node": 1}),
                                   (ring, {"node": 2}), (ring, {"node": "all"}))]

    # no "node" reads node 0, on a ring as on one daemon
    assert readings(prom, {"delta": "a_total", "per_delta": "b_total"}) == [
        10.0, 10.0, 30.0, None, 25.0]
    assert readings(prom_sum, {"delta": ["a_total"], "per_delta": ["b_total"]}) == [
        10.0, 10.0, 30.0, None, 25.0]
    assert readings(gauge, {"level": "level", "per_level": "b_total"}) == [
        10.0, 10.0, 30.0, None, 25.0]
    assert readings(stages, {"sum_total_s": ["s"], "per_count_of": "s",
                             "scale": 1.0}) == [0.1, 0.1, 0.2, None, 0.175]
    # the pooled median falls in node 1's bucket, node 0's in its own
    assert readings(stage_quantile, {"stage": "s", "q": 0.5, "scale": 1.0}) == [
        0.5, 0.5, 1.5, None, pytest.approx(1 + 10 / 30)]


# -- the doors of every node, and a node that goes and comes back -------------


def test_every_nodes_doors_and_a_fresh_daemon_on_the_same_ports(tmp_path):
    """Two tiny daemons booted as the harness boots them: the peer door
    and the GEB door of node 1 answer the seeded sequence as the
    reference does; a node that is killed is named; `Ring.start(i)`
    boots it afresh on the addresses its peer knows (what a later
    `events` list needs, and no more)."""
    import time

    import check
    from harness.doors import Doors

    config = {"chips": 2, "env": TINY_ENV, "nodes": [{"env": {}, "chips": 1}] * 2}
    ring = daemon.Ring("two", config, str(tmp_path))
    doors = None
    try:
        for i in (0, 1):
            ring.start(i)
        for d in ring.nodes:
            d.wait_ready(time.monotonic() + 600, 2)
        assert ring.grpc == ring.addrs[0]["grpc"] and ring.nodes[1].http == ring.addrs[1]["http"]
        assert os.path.basename(ring.nodes[1].log_path) == "two.daemon.1.log"
        doors = Doors(ring)

        class Node1:
            def call(self, door, reqs):
                return doors.call(door, reqs, node=1)

        for seed, door in ((11, "peer"), (12, "geb"), (13, "grpc")):
            pre = bench.pre_window_check(Node1(), door, seed, [0, 1])
            assert pre["differ"] == 0 and pre["over_limit_answers"] > 0, (door, pre)
        reqs = [bench.keyspace.req(f"k{i}", 1, 5, 60_000, 0) for i in range(40)]
        assert doors.bulk([reqs[:20], reqs[20:]], node=1) == [[(0, 5, 4)] * 20] * 2
        # node 1 answered all of that itself or through its forward to node 0
        assert ring.nodes[1].prom().get("peer_serve_items_total", 0) > 0
        doors.close()
        doors = None

        ring.nodes[1].proc.kill()
        ring.nodes[1].proc.wait(30)
        with pytest.raises(daemon.BenchFailure, match=r"node 1\) exited -9"):
            ring.check_alive()
        with pytest.raises(daemon.BenchFailure, match=r"node 1\) exited -9"):
            ring.nodes[1].stages()  # a dead node's report is a failure that names it
        ring.nodes[1].stop()
        again = ring.start(1)
        again.wait_ready(time.monotonic() + 600, 2)
        assert (again.grpc, again.geb) == (ring.addrs[1]["grpc"], ring.addrs[1]["geb"])
        ring.check_alive()
        assert ring.stop() == 0 and len(ring.exits) == 2
    finally:
        if doors is not None:
            doors.close()
        ring.stop(10.0)


# -- whole runs ----------------------------------------------------------------


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark beside the program, with files ADDED: a
    one-daemon configuration, a three-node ring of the same daemon, one
    closed-loop and one open-loop traffic mix (all of it to node 0), and
    per-node metric files."""
    root = tmp_path_factory.mktemp("ring")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "gubernator_tpu"), root / "gubernator_tpu")
    b = root / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}

    def add(rel, obj):
        assert not (b / rel).exists()
        (b / rel).write_text(json.dumps(obj))

    base = {"source": "a test", "why": "a test", "key_population": 5000,
            "preload_keys": 5000, "guarantees": [], "reduced": [],
            "store": {"ways": 16, "rows": 2048, "entry_bytes": 32},
            "env": TINY_ENV}
    add("configs/dummy-one.json", dict(base, chips=1))
    add("configs/dummy-ring3.json", dict(
        base, chips=3, nodes=[{"env": {}, "chips": 1}] * 3))
    mix = json.loads((b / "traffic" / "geb-frames.json").read_text())
    # 24 canary keys: a third of the ring owns none of them once in 17,000 runs
    add("traffic/dummy-frames.json", dict(
        mix, workers=2, inflight=4, items_per_frame=200, warmup_s=1.0,
        canaries_per_worker=12, canary_every=2))
    calls = json.loads((b / "traffic" / "grpc-pairs.json").read_text())
    add("traffic/dummy-calls.json", dict(calls, workers=2, warmup_s=1.0))
    for cell in (ONE, RING, ONE_CALLS, RING_CALLS):
        config, traffic = cell.split(".")
        add(f"cells/{cell}.json", {
            "config": config, "traffic": traffic, "rate": 300,
            "trace_ms": 500, "trace_match": "decide", "why": "a test"})
    for node in (0, 1, 2, "all"):  # items a forwarded batch, by who served it
        add(f"layer_metrics/dummy_forwarded_items.n{node}.json", {
            "layer": "Instance", "unit": "items", "moves": "decisions_per_s",
            "source": "program_counter", "cells": [ONE, RING], "reader": "prom",
            "node": node, "delta": "peer_serve_items_total",
            "per_delta": "peer_serve_batches_total"})
    yield root
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there was edited


def run(root, cell, *extra, trace=0, seed=2**31 + 38):
    # a TMPDIR of the checkout's own: the daemon's profile capture lands
    # under <TMPDIR>/guber-profile/bench_<cell>, and two test files that
    # run one cell name side by side (`pytest -n 4`) met there
    os.makedirs(root / "tmp", exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(root / "tmp"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(seed), "--seconds", "3", "--trace", str(trace), *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=900,
    )
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    return p, lines


def phase(lines, name):
    return [x for x in lines if x.get("phase") == name]


def test_a_three_node_ring_of_new_files_runs_and_is_correct(checkout):
    p, lines = run(checkout, RING, trace=1)
    assert p.returncode == 3, p.stderr[-2000:]  # a rehearsal: not a chip run
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["rehearsal"] == "cpu"
    assert last["device"]["nodes"] == 3 and last["device"]["count"] == 3
    boots = phase(lines, "boot")
    assert [b["node"] for b in boots[-3:]] == [0, 1, 2]
    assert not any(b["boots_again"] for b in boots[-3:])
    (window,) = phase(lines, "window")
    assert window["node_exits"] == [0, 0, 0]
    # all traffic went to node 0's door, as string frames: the client
    # sees a ring it does not route, and node 0 forwards what it does not own
    assert window["generator"]["client"]["use_fast"] is False
    read_ = set(phase(lines, "trace")[0]["layer_metrics_read"])
    forwarded = {n for n in read_ if n.startswith("dummy_forwarded_items.")}
    assert forwarded == {"dummy_forwarded_items.n1", "dummy_forwarded_items.n2",
                         "dummy_forwarded_items.nall"}  # nobody forwards to node 0
    (post,) = phase(lines, "post_window_check")
    assert post["canaries"]["replies"] > 0
    assert post["tallies"]["keys_held_exactly"] > 0
    logs = checkout / "chiprun_out" / "benchmark"  # one a node, node 0's as ever
    for name in (f"{RING}.daemon.log", f"{RING}.daemon.1.log", f"{RING}.daemon.2.log"):
        assert "peers updated" in (logs / name).read_text(), name


@pytest.mark.parametrize("fault", ["lost_writes", "altered_answers"])
def test_a_ring_with_one_faulty_node_is_not_correct(checkout, fault):
    """THE CONTROL on a ring: node 1 alone breaks a guarantee, every
    check still goes through node 0's door."""
    argv = {"1": ["benchmark/tests/faulty_daemon.py", fault]}
    p, lines = run(checkout, RING, "--daemon-argv", json.dumps(argv))
    assert p.returncode == 3, p.stderr[-2000:]
    assert lines[-1]["correct"] is False
    assert lines[-1]["device"]["nodes"] == 3


def test_a_node_lost_under_load_is_a_failure_that_names_it(checkout):
    argv = {"2": ["benchmark/tests/dying_daemon.py"]}
    p, lines = run(checkout, RING, "--daemon-argv", json.dumps(argv))
    assert p.returncode == 2, p.stderr[-2000:]
    assert "daemon (node 2) exited 7" in p.stderr
    assert not any("correct" in x for x in lines)  # no result line
    assert phase(lines, "generators_ready")  # it was lost under load


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "breakdown", "rehearsal",  # the parent's, traced, on the CPU
               "checks"}  # PR 39: every number compared beside its limit, last
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes", "busy_s",
               "window_s"}
PHASE_KEYS = {  # the parent's lines, in order
    "boot": {"phase", "seconds", "cold", "cache_dir", "cache_entries_added",
             "boots_again", "device", "host_prep", "hasher", "programs",
             "cache_hits", "compile_seconds"},
    "preload": {"phase", "keys", "wrong", "limit", "seconds"},
    "pre_window_check": {"phase", "door", "compared", "differ", "limit",
                         "over_limit_answers", "first_difference"},
    "generators_ready": {"phase", "seconds", "workers"},
    "window": {"phase", "seconds", "setup_s", "phases", "generator",
               "daemon_exit"},
    "post_window_check": {"phase", "canaries", "tallies", "malformed",
                          "counters_whole_run", "counters_limit",
                          "programs_compiled_in_window"},
    "trace": {"phase", "planes", "layer_metrics_read", "programs", "capture_s"},
}


def test_one_daemon_prints_the_parents_lines_key_for_key(checkout):
    p, lines = run(checkout, ONE, trace=1)
    assert p.returncode == 3, p.stderr[-2000:]
    last = lines.pop()
    assert set(last) == RESULT_KEYS and set(last["device"]) == DEVICE_KEYS
    assert last["correct"] is True and last["device"]["count"] == 1
    # every number compared beside its limit: the line's LAST key, and
    # the last lines of standard error
    assert list(last)[-1] == "checks" and len(last["checks"]) == 9
    assert all(c["value"] == 0 for c in last["checks"].values() if "limit" in c)
    assert last["checks"]["pre_window_over_limit_answers"]["value"] >= 1
    assert [x.split(":")[1].strip() for x in p.stderr.splitlines()[-9:]
            ] == [f"compared {name}" for name in last["checks"]]
    assert [x["phase"] for x in lines if x["phase"] != "boot"] == list(PHASE_KEYS)[1:]
    for x in lines:
        assert set(x) == PHASE_KEYS[x["phase"]], x["phase"]
    (window,) = phase(lines, "window")
    assert set(window["phases"]) == {"build", "boot", "preload", "check",
                                     "generators", "warmup"}
    # reported beside the tallies, judging nothing (PR 39)
    (post,) = phase(lines, "post_window_check")
    assert post["tallies"]["leaky_over_steady"] == 0
    assert post["tallies"]["hits_in_doubt"] == 0
    assert {c["algo"] for c in post["tallies"]["closest"]} == {0, 1}
    assert all(c["admitted"] <= c["upper"] for c in post["tallies"]["closest"])
    # a one-daemon cell reads node 0 and has no other: "node": 0 and
    # "all" read nothing here only because nobody forwards to one daemon
    assert not [n for n in phase(lines, "trace")[0]["layer_metrics_read"]
                if n.startswith("dummy_forwarded_items.")]
    log = checkout / "chiprun_out" / "benchmark" / f"{ONE}.daemon.log"
    assert log.is_file() and not list(log.parent.glob(f"{ONE}.daemon.[0-9]*"))


@pytest.mark.parametrize("cell,nodes", [(ONE_CALLS, 1), (RING_CALLS, 3)])
def test_the_sweep_steps_an_open_loop_cell_of_new_files(checkout, cell, nodes):
    """`benchmark/sweep.py`, which finds an open-loop cell's fixed rate,
    boots what the harness boots: two short steps on fresh keys."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/sweep.py", "--workload", cell,
         "--rates", "50,100", "--seconds", "2"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    steps = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    assert [s["rate"] for s in steps] == [50.0, 100.0]
    for s in steps:
        assert s["failed"] == 0 and s["batches"] > 0
        assert s["call_p50_ms"] > 0 and s["stages_ms"]
    logs = checkout / "chiprun_out" / "benchmark"
    assert len(list(logs.glob(f"{cell}.sweep.daemon*.log"))) == nodes
