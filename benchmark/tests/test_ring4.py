"""PR 41's deployment `ring4` and its cell `ring4.geb-frames` (CPU, by
hand like the rest of this directory):

- the configuration differs from `zipf10m.json` in the listed keys
  alone, fixes no address and none of the forwarder's options, and its
  `source` is the one `BENCHMARK.json` gives, within 200 characters;
- `reference_ring4.Ring` answers every item from its owner's limiter,
  in the caller's order, and as ONE `reference.Limiter` whichever nodes
  are asked (the statement `check.py` relies on when it goes through
  node 0);
- the cell's metrics are the `BENCHMARK.json` entries that list it =
  the files that list it, each with a reader that exists and a `node`
  (a `.ring4` file is this cell's alone, `.ring` the two ring cells');
  those that read PR 41's spans and counters read nothing from a
  program without them (the parent) and what is said from one with them;
- the cell rehearsed traced on the CPU at a CPU's size: exit 3,
  `correct: true`, every program-side metric read, items forwarded and
  none failed; with `faulty_daemon.py lost_writes` as an OWNER (node 2:
  the door node is sound) `correct: false`.
"""

import json
import os
import random
import shutil
import subprocess
import sys

import pytest

import cell_metrics
import check
import reference_ring
import reference_ring4
from readers import prom_sum, stages

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "ring4.geb-frames"
#: what reads the forwarder's stages and counters (PR 41)
NEW = {"forward_queue_us_per_group", "forward_codec_us_per_batch",
       "forward_rpc_us_per_batch", "forward_items_per_batch",
       "forwarded_items_pct", "forward_failed_items_pct"}
FORWARDER_OPTIONS = (
    "GUBER_BATCH_LIMIT", "GUBER_BATCH_WAIT_MS", "GUBER_BATCH_TIMEOUT_MS",
    "GUBER_PEER_TIMEOUT_MS", "GUBER_PEER_RETRIES", "GUBER_PEER_BACKOFF_MS",
    "GUBER_PEER_BACKOFF_MAX_MS", "GUBER_BREAKER_FAILURES", "GUBER_BREAKER_RATIO",
    "GUBER_BREAKER_WINDOW", "GUBER_BREAKER_COOLDOWN_MS", "GUBER_BREAKER_PROBES")
ADDRESSES = ("GUBER_PEERS", "GUBER_ADVERTISE_ADDRESS", "GUBER_GEB_PEER_DOORS",
             "GUBER_GRPC_ADDRESS", "GUBER_HTTP_ADDRESS", "GUBER_GEB_PORT")


def load(*rel):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


# -- the files ----------------------------------------------------------------


def test_the_configuration_is_zipf10m_four_times_in_a_ring():
    from harness import daemon

    config, zipf10m = load("configs", "ring4.json"), load("configs", "zipf10m.json")
    for key in ("env", "store", "key_population", "preload_keys", "guarantees"):
        assert config[key] == zipf10m[key], key
    assert config["chips"] == 4 and len(config["nodes"]) == 4
    for i, node in enumerate(config["nodes"]):
        assert node == {"env": {"TPU_VISIBLE_CHIPS": str(i),
                                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                                "TPU_PROCESS_BOUNDS": "1,1,1"}, "chips": 1}
    for spec in daemon.node_specs(config):
        # upstream's forwarder as the program's defaults run it, and no
        # address: the harness draws the ports
        assert not set(spec["env"]) & set(FORWARDER_OPTIONS + ADDRESSES)
    ring = config["ring"]
    assert (ring["nodes"], ring["batch_limit"], ring["batch_wait_us"],
            ring["batch_timeout_ms"], ring["behavior"]) == (4, 1000, 500, 500, "BATCHING")
    assert (ring["arc_share_pct"], ring["arc_tolerance_pct"]) == (25.0, 0.1)
    assert config["reduced"] == ["peers", "client_load", "live_keys_at_start"]
    assert set(config["reduced"]) == set(config["reduced_detail"])
    assert "DEADLINE" in config["never_cut"] and "error" in config[
        "guarantees_through_the_forward"]
    assert len(config["source"]) <= 200
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "ring4")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": "ring4", "traffic": "geb-frames",
                    "chips": 4, "why": work["why"]} and len(work["why"]) <= 200
    # at most half of the cells may ask for four chips
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(bench["workloads"]) // 2
    cell = load("cells", CELL + ".json")
    assert (cell["config"], cell["traffic"], cell["trace_node"]) == (
        "ring4", "geb-frames", 1)
    # the forwarder's defaults the configuration leaves alone ARE
    # upstream's limit and deadline
    from gubernator_tpu.serve.config import config_from_env
    b = config_from_env(dict(config["env"])).behaviors
    assert (b.batch_limit, b.effective_peer_timeout()) == (1000, 0.5)
    # upstream's wait is stated, not run: one key says so
    assert b.batch_wait == 0 and "NOT what runs" in config["assumed"]["batch_wait"]
    assert "id 1" in config["assumed"]["zipf_head"]  # node 0 never owns it


def test_the_harness_cuts_four_even_arcs_for_it():
    from harness import daemon

    ring = daemon.Ring("x", load("configs", "ring4.json"), "/nonexistent")
    try:
        peers = [a["grpc"] for a in ring.addrs]
    finally:
        ring.stop()
    keys = [f"bench_s7:{i}" for i in range(1, 40001)]
    share = [sum(reference_ring.owner_of(k, peers) == p for k in keys) / len(keys)
             for p in peers]
    assert all(abs(s - 0.25) < 0.01 for s in share), share


def test_every_metric_of_the_cell_is_an_entry_a_file_a_reader_and_a_node():
    mine = cell_metrics.held_together(CELL)
    assert {n.split(".")[0] for n in mine} >= NEW
    for name, spec in mine.items():
        assert spec["moves"] == "decisions_per_s" and spec["node"] in (0, 1, "all"), name
        assert name.endswith((".ring4", ".ring")), name  # "node": the spec is a ring's
        assert (spec["cells"] == [CELL]) == name.endswith(".ring4"), name
        base = name.rsplit(".", 1)[0]
        # reads as its accepted twin on one daemon does, less the node
        twin = {"object_path_items_pct": "object_path_items_pct.mesh4",
                "instance_route_us_per_frame": "instance_route_us_per_frame.mesh4",
                "peer_serve_us_per_batch": "peer_serve_us_per_batch.peer",
                "peer_shed_hit_pct": "peer_shed_hit_pct.peer"}.get(base, base)
        if base in NEW:
            continue
        old = cell_metrics.spec(twin)
        for key in set(old) - {"cells", "what", "layer", "moves"}:
            assert spec[key] == old[key], (name, key)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "decisions_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.1


def _node(stage_s, count, prom):
    def snap(scale):
        return {"stages": {name: {"total_s": s * scale, "count": count * scale}
                           for name, s in stage_s.items()}}
    return {"stages0": snap(1), "stages1": snap(2),
            "prom0": {k: v for k, v in prom.items()},
            "prom1": {k: 2 * v for k, v in prom.items()}}


def test_the_new_readings_by_hand_and_on_a_program_without_them():
    reasons = ("deadline", "breaker_open", "transport", "closed")
    door = _node(
        {"forward_queue": 3.0, "forward_encode": 0.2, "forward_rpc": 4.0,
         "forward_decode": 0.3},
        100,
        {"peer_forward_items_total": 40000.0, "peer_forward_batches_total": 100.0,
         "edge_object_items_total": 100000.0, "edge_fast_items_total": 0.0,
         "edge_folded_items_total": 0.0, "peer_serve_items_total": 0.0,
         "peer_serve_folded_items_total": 0.0,
         **{f'peer_forward_failed_items_total{{reason="{r}"}}': 0.0 for r in reasons}})
    door["prom1"]['peer_forward_failed_items_total{reason="deadline"}'] = 400.0
    owner = _node({"peer_serve": 0.05}, 100,
                  {"peer_serve_items_total": 13000.0,
                   "peer_serve_folded_items_total": 13000.0})
    ctx = dict(door, nodes=[door, owner, owner, owner])

    def read(name, suffix=".ring4"):
        spec = load("layer_metrics", name + suffix + ".json")
        reader = stages if spec["reader"] == "stages" else prom_sum
        return reader.read(spec, ctx), reader.read(spec, parent)

    bare = {"stages": {"bridge_decode": {"total_s": 1.0, "count": 10}}}
    old = {"stages0": bare, "stages1": bare, "prom0": {},
           "prom1": {"edge_object_items_total": 5.0, "edge_fast_items_total": 0.0,
                     "edge_folded_items_total": 0.0, "peer_serve_items_total": 0.0}}
    parent = dict(old, nodes=[old] * 4)
    assert read("forward_queue_us_per_group") == (pytest.approx(30000.0), None)
    assert read("forward_codec_us_per_batch") == (pytest.approx(5000.0), None)
    assert read("forward_rpc_us_per_batch") == (pytest.approx(40000.0), None)
    assert read("forward_items_per_batch") == (pytest.approx(400.0), None)
    assert read("forwarded_items_pct") == (pytest.approx(40.0), None)
    assert read("forward_failed_items_pct") == (pytest.approx(1.0), None)
    # the owners pooled: node 0 served no peer batch and adds nothing
    assert read("peer_serve_us_per_batch", ".ring")[0] == pytest.approx(500.0)
    assert read("peer_folded_items_pct")[0] == pytest.approx(100.0)


# -- the plain reference --------------------------------------------------------

PEERS = ["10.0.0.%d:81" % i for i in range(1, 5)]


def test_the_ring_answers_from_the_owner_in_the_callers_order():
    ring = reference_ring4.Ring(PEERS)
    calls = check.checked_sequence(41, [0, 1], n_calls=40, items=25)
    owners = {ring.owner("bench", k) for c in calls for k, *_ in c}
    assert owners == set(PEERS)  # the sequence reaches every node
    limiters = {p: reference_ring4.Limiter() for p in PEERS}
    crossed = 0
    for n, call in enumerate(calls):
        got = ring.call(call, 1_000, asked=PEERS[n % 4])
        want = [limiters[reference_ring.owner_of(f"bench_{k}", PEERS)]
                .decide(k, h, li, d, a, 1_000)[:3] for k, h, li, d, a in call]
        assert got == want
        crossed += ring.forwarded(call, PEERS[0])
    assert 0 < crossed < sum(len(c) for c in calls)
    with pytest.raises(ValueError):
        ring.call(calls[0], 1_000, asked="10.9.9.9:81")
    with pytest.raises(ValueError):
        reference_ring4.Ring([])


@pytest.mark.parametrize("seed", range(8))
def test_whichever_node_is_asked_the_ring_is_one_limiter(seed):
    rng = random.Random(seed)
    calls = check.checked_sequence(seed, [0, 1], n_calls=30, items=20)
    asked = [rng.choice(PEERS) for _ in calls]
    now = 1_700_000_000_000
    assert reference_ring4.same_as_one_limiter(calls, PEERS, now, asked=asked)
    assert reference_ring4.ring_answers(calls, PEERS, now, asked=asked) == (
        reference_ring4.ring_answers(calls, PEERS, now, asked=PEERS[0])
    ) == check.reference_answers(calls, now)


# -- whole runs on the CPU ------------------------------------------------------


def _copy(tmp_path):
    """The benchmark beside the program, the cell cut to a CPU's size:
    2 connections x 4 frames of 200 items, 5,000 keys in four
    20,000-key stores (the same ways, ladder and sketch tier)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "gubernator_tpu"), root / "gubernator_tpu")
    for rel, changes in (
        ("traffic/geb-frames.json", dict(
            workers=2, inflight=4, items_per_frame=200, prebuilt_frames_per_s=20,
            warmup_s=1.0)),
        (f"cells/{CELL}.json", dict(trace_ms=500)),
        ("configs/ring4.json", dict(
            env={"GUBER_STORE_TARGET_KEYS": "20000", "GUBER_SKETCH_MIB": "1"},
            key_population=5000, preload_keys=5000)),
    ):
        path = root / "benchmark" / rel
        obj = json.loads(path.read_text())
        for key, value in changes.items():
            obj[key] = dict(obj[key], **value) if isinstance(value, dict) else value
        path.write_text(json.dumps(obj))
    return root


def _run(root, *extra, trace=0, seed=2**31 + 41):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "5", "--trace", str(trace), *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=1500)
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    return p, lines


def test_a_traced_rehearsal_reads_every_program_side_metric(tmp_path):
    p, lines = _run(_copy(tmp_path), trace=1)
    assert p.returncode == 3, p.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] == "cpu" and last["device"]["nodes"] == 4
    assert last["attempted"] > 0 and last["metrics"] == {}  # no timing
    boots = [x for x in lines if x.get("phase") == "boot"]
    assert sorted(x["node"] for x in boots if not x["boots_again"]) == [0, 1, 2, 3]
    pre = next(x for x in lines if x.get("phase") == "pre_window_check")
    assert pre["door"] == "geb" and pre["differ"] == 0 and pre["compared"] == 300
    trace = next(x for x in lines if x.get("phase") == "trace")
    want = cell_metrics.rehearsed(CELL)
    assert set(trace["layer_metrics_read"]) == want and want
    assert set(cell_metrics.files(CELL)) - want == {
        "decide_step_us.ring4", "device_idle_share.ring4"}
    # node 0, the one door the clients dial, owns least of the zipf
    # head and never key id 1: the run says which ids each node owns
    head = next(x for x in lines if x.get("phase") == "ring")["head_ids_owned"]
    assert sorted(sum(head, [])) == list(range(1, 17)) and 1 not in head[0]
    assert all(len(head[0]) <= len(h) for h in head[1:] if 1 not in h)
    window = next(x for x in lines if x.get("phase") == "window")
    assert window["node_exits"] == [0, 0, 0, 0]
    post = next(x for x in lines if x.get("phase") == "post_window_check")
    assert post["canaries"]["replies"] > 0 and post["canaries"]["differ"] == 0
    assert post["tallies"]["outside_bounds"] == 0 == post["malformed"]["replies"]
    assert not any(post["counters_whole_run"].values())
    assert post["programs_compiled_in_window"] == 0


def test_correct_is_false_when_an_owner_loses_writes(tmp_path):
    faulty = {"2": ["benchmark/tests/faulty_daemon.py", "lost_writes"]}
    p, lines = _run(_copy(tmp_path), "--daemon-argv", json.dumps(faulty))
    assert p.returncode == 3, p.stderr[-2000:]
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    pre = next(x for x in lines if x.get("phase") == "pre_window_check")
    post = next(x for x in lines if x.get("phase") == "post_window_check")
    assert (pre["differ"] + post["tallies"]["outside_bounds"]
            + post["canaries"]["differ"]) > 0
