"""PR 46's deployment `ring4-lb` and its cell
`ring4-lb.geb-frames-all-doors` (CPU, by hand like the rest of this
directory):

- the configuration equals `ring4.json` key for key except `name`,
  `source`, `why`, `reduced*` and `assumed`: the same environment,
  nodes, store, ring, guarantees and never-cut list, no forwarder
  option, no address; `client_load` is no longer reduced;
- the traffic file equals `geb-frames.json` but for the generator, the
  workers (8), the seed and the words; the generator maps worker w to
  node w mod n, changes nothing but the address, and refuses a
  configuration of one node;
- the cell's metrics are the `BENCHMARK.json` entries that list it =
  the files that list it, each with a reader that exists and a node; a
  `.ring4lb` file reads as its `.ring4` twin but for the node, `.ring`
  is ONE file for both ring cells; the four new readings by hand on
  made-up snapshots, and nothing from a program without the counters;
- `reference_ring4_doors` over 200 seeded interleavings of four doors
  equals ONE `reference.Limiter`, and for single hits every
  interleaving gives a key one summary;
- the cell rehearsed traced on the CPU at a CPU's size: exit 3,
  `correct: true`, every program-side metric read, every node
  forwarded and served, no forward failed.
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
import reference_ring4_doors
from generators import closed_loop_frames, closed_loop_frames_all_doors
from readers import generator, prom_sum, stage_quantile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "ring4-lb.geb-frames-all-doors"
SUFFIX = ".ring4lb"
FROM_THE_TRACE = {"decide_step_us", "device_idle_share"}
#: what reads PR 46's counters, the pooled loop lag, the generator and
#: (PR 47) the owners' queue: no `.ring4` twin
NEW = {"peer_rows_pct", "mixed_batches_pct", "loop_lag_p99_ms", "door_skew_pct",
       "call_queue_ms"}
NOT_RING4S = ("name", "source", "why", "reduced", "reduced_detail", "assumed")


def load(*rel):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


# -- the files ----------------------------------------------------------------


def test_the_configuration_is_ring4_with_the_client_load_no_longer_cut():
    from test_ring4 import ADDRESSES, FORWARDER_OPTIONS

    from harness import daemon

    config, ring4 = load("configs", "ring4-lb.json"), load("configs", "ring4.json")
    assert set(ring4) <= set(config)
    for key in ring4:
        if key not in NOT_RING4S:
            assert config[key] == ring4[key], key
    for key in ("env", "nodes", "store", "key_population", "preload_keys",
                "guarantees", "guarantees_through_the_forward", "ring",
                "never_cut", "chips"):
        assert key not in NOT_RING4S and config[key] == ring4[key]
    assert config["name"] == "ring4-lb" and config["chips"] == 4
    for spec in daemon.node_specs(config):
        assert not set(spec["env"]) & set(FORWARDER_OPTIONS + ADDRESSES)
    assert config["reduced"] == ["peers", "live_keys_at_start"]
    assert "client_load" in ring4["reduced"]
    detail = config["reduced_detail"]
    assert set(detail) == set(config["reduced"]) | {"client_load"}
    assert detail["client_load"].startswith("NOT reduced")
    for key in config["reduced"]:
        assert detail[key] == ring4["reduced_detail"][key]
    assumed = config["assumed"]
    assert "EVENLY" in assumed["client_spread"]
    assert assumed["batch_wait"] == ring4["assumed"]["batch_wait"]
    assert "PORTS" in assumed["zipf_head"] and "door_skew_pct" in assumed["zipf_head"]
    assert len(config["source"]) <= 200 and "any node" in config["source"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "ring4-lb")
    assert entry == {"name": "ring4-lb", "source": config["source"],
                     "file": "benchmark/configs/ring4-lb.json",
                     "reduced": config["reduced"], "why": entry["why"]}
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == {"name": CELL, "config": "ring4-lb",
                    "traffic": "geb-frames-all-doors", "chips": 4,
                    "why": work["why"]} and len(work["why"]) <= 200
    # four of eight cells on four chips: the rule's half, rounded down
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(bench["workloads"]) // 2
    cell = load("cells", CELL + ".json")
    assert (cell["config"], cell["traffic"], cell["trace_node"], cell["trace_ms"],
            cell["trace_match"]) == ("ring4-lb", "geb-frames-all-doors", 0, 2000, "decide")
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "decisions_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.1


def test_the_traffic_is_geb_frames_at_every_door():
    mine, plain = load("traffic", "geb-frames-all-doors.json"), load("traffic", "geb-frames.json")
    assert set(mine) == set(plain)
    for key in plain:
        if key not in ("generator", "why", "workers", "base_seed", "assumed"):
            assert mine[key] == plain[key], key
    assert (mine["generator"], mine["workers"]) == ("closed_loop_frames_all_doors", 8)
    assert mine["base_seed"] != plain["base_seed"]
    seeds = [load("traffic", f)["base_seed"] for f in os.listdir(os.path.join(BENCH, "traffic"))]
    assert len(set(seeds)) == len(seeds)


def test_the_generator_changes_the_address_and_nothing_else():
    gen = closed_loop_frames_all_doors
    assert gen.DOOR == closed_loop_frames.DOOR == "geb"
    assert gen.build is closed_loop_frames.build
    nodes = [{"grpc": f"g{i}", "http": f"h{i}", "geb": f"door{i}"} for i in range(4)]
    for w in range(8):
        spec = {"worker": w, "nodes": nodes, "geb": "door0", "seed": 7, "x": [1]}
        at = gen.at_its_door(spec)
        assert at["geb"] == f"door{w % 4}" and gen.node_of(w, 4) == w % 4
        assert {k: v for k, v in at.items() if k != "geb"} == {
            k: v for k, v in spec.items() if k != "geb"}
        assert spec["geb"] == "door0"  # the parent's spec is left as it was
    assert [gen.node_of(w, 3) for w in range(5)] == [0, 1, 2, 0, 1]
    with pytest.raises(ValueError, match="several"):
        gen.at_its_door({"worker": 0, "nodes": nodes[:1], "geb": "door0"})
    with pytest.raises(ValueError):
        gen.node_of(3, 0)


def test_the_summary_adds_each_doors_pace_and_the_workers_cpu():
    import numpy as np

    def result(frames, cpu):
        return {"frames_in_window": frames, "failed": 0, "wrapped": 0,
                "frame_ms": np.array([5.0] * frames),
                "frames_by_second": np.array([frames // 2, frames - frames // 2]),
                "client": {"transport": "tcp"}, "cpu_share": cpu}

    nodes = [{"geb": f"door{i}"} for i in range(4)]
    spec = {"traffic": {"items_per_frame": 1000}, "seconds": 2.0, "nodes": nodes}
    frames = [100, 80, 60, 40, 20, 40, 60, 80]  # worker w at door w mod 4
    results = [result(n, 0.1 * (w + 1)) for w, n in enumerate(frames)]
    plain = closed_loop_frames.summarize(results, spec)
    mine = closed_loop_frames_all_doors.summarize(results, spec)
    assert mine["end_to_end"] == plain["end_to_end"] == {
        "decisions_per_s": (480 * 1000 / 2.0, "decisions/s")}
    assert (mine["attempted"], mine["failed"]) == (480_000, 0)
    g = mine["generator"]
    assert {k: g[k] for k in plain["generator"]} == plain["generator"]
    assert g["frames_per_s_by_node"] == [60.0, 60.0, 60.0, 60.0]
    assert g["door_skew_pct"] == 0.0
    assert g["worker_cpu_share"] == [round(0.1 * (w + 1), 4) for w in range(8)]
    results[0] = result(140, 0.5)  # door 0 faster: (80 - 60) / 65 x 100
    g = closed_loop_frames_all_doors.summarize(results, spec)["generator"]
    assert g["frames_per_s_by_node"] == [80.0, 60.0, 60.0, 60.0]
    assert g["door_skew_pct"] == pytest.approx(20.0 / 65.0 * 100.0)


def test_every_metric_of_the_cell_is_an_entry_a_file_a_reader_and_a_node():
    mine = cell_metrics.held_together(CELL)
    declared = cell_metrics.declared(CELL)
    ring4 = cell_metrics.declared("ring4.geb-frames")
    assert {n.split(".")[0] for n in mine} >= NEW
    for name, spec in mine.items():
        assert spec["moves"] == "decisions_per_s"
        assert name.endswith((SUFFIX, ".ring")), name
        assert (spec["cells"] == [CELL]) == name.endswith(SUFFIX), name
        base = name.rsplit(".", 1)[0]
        if spec["reader"] != "generator":
            # every node is a door and an owner: pooled, but the capture
            assert spec["node"] == (0 if base in FROM_THE_TRACE else "all")
        if base in NEW or name.endswith(".ring"):
            continue
        twin = cell_metrics.spec(base + ".ring4")  # reads as its twin
        assert ring4[base + ".ring4"]["better"] == declared[name]["better"]
        for key in set(twin) - {"cells", "what", "node"}:
            assert spec[key] == twin[key], (name, key)


def _node(prom, lag_buckets=None, scale=2):
    stages = {"loop_lag": {"buckets": lag_buckets}} if lag_buckets else {}
    stages1 = ({"loop_lag": {"buckets": [scale * n for n in lag_buckets]}}
               if lag_buckets else {})
    return {"stages0": {"stages": stages, "bucket_edges_s": [0.001, 0.01, 0.1, 1.0]},
            "stages1": {"stages": stages1, "bucket_edges_s": [0.001, 0.01, 0.1, 1.0]},
            "prom0": dict(prom), "prom1": {k: scale * v for k, v in prom.items()}}


def test_the_new_readings_by_hand_and_on_a_program_without_the_counters():
    door, peer = 'device_batch_rows_total{source="door"}', 'device_batch_rows_total{source="peer"}'
    node = _node({door: 250.0, peer: 750.0, "device_batches_mixed_total": 30.0,
                  "device_batch_size_count": 40.0, "device_batch_size_sum": 1000.0},
                 lag_buckets=[0, 90, 8, 2, 0])
    quiet = _node({door: 1000.0, peer: 0.0, "device_batches_mixed_total": 0.0,
                   "device_batch_size_count": 40.0, "device_batch_size_sum": 1000.0},
                  lag_buckets=[0, 100, 0, 0, 0])
    ctx = dict(node, nodes=[node, node, node, quiet],
               generator={"door_skew_pct": 12.5, "frames_per_s": 400.0})
    old = _node({"device_batch_size_count": 40.0, "device_batch_size_sum": 1000.0})
    parent = dict(old, nodes=[old] * 4, generator={"frames_per_s": 400.0})
    readers = {"prom_sum": prom_sum, "stage_quantile": stage_quantile,
               "generator": generator}

    def read(name):
        spec = load("layer_metrics", name + SUFFIX + ".json")
        reader = readers[spec["reader"]]
        return reader.read(spec, ctx), reader.read(spec, parent)

    # three nodes at 750 of 1000 and one at 0 of 1000: 2250 / 4000
    assert read("peer_rows_pct") == (pytest.approx(56.25), None)
    assert read("mixed_batches_pct") == (pytest.approx(90.0 / 160.0 * 100.0), None)
    # 400 samples pooled: 370 under 10 ms, 24 in 10-100 ms, 6 in 0.1-1 s;
    # the 396th lies 2 into the last six: 0.1 + 0.9 * 2 / 6 s
    assert read("loop_lag_p99_ms") == (pytest.approx(400.0), None)
    assert read("door_skew_pct") == (12.5, None)
    # one node's own scrape (the acceptance reads each node's): the same spec
    spec = dict(load("layer_metrics", "peer_rows_pct" + SUFFIX + ".json"), node=3)
    assert prom_sum.read(spec, ctx) == 0.0
    assert prom_sum.read(dict(spec, node=1), ctx) == pytest.approx(75.0)


# -- the plain reference --------------------------------------------------------

PEERS = ["10.0.0.%d:81" % i for i in range(1, 5)]
NOW = 1_700_000_000_000


def _calls_by_door(seed, n_calls=6, items=15):
    calls = check.checked_sequence(seed, [0, 1], n_calls=4 * n_calls, items=items)
    return {p: calls[i::4] for i, p in enumerate(PEERS)}


def _orders(calls_by_door, rng, n):
    base = reference_ring4_doors.round_robin(calls_by_door)
    out = [base]
    for _ in range(n - 1):
        order = list(base)
        rng.shuffle(order)
        out.append(order)
    return out


@pytest.mark.parametrize("seed", range(8))
def test_any_interleaving_of_four_doors_is_one_limiter(seed):
    """25 interleavings a seed, 200 in all; peeks, two-hit items and
    in-batch duplicates included (check.checked_sequence)."""
    rng = random.Random(seed)
    by_door = _calls_by_door(seed)
    for order in _orders(by_door, rng, 25):
        assert reference_ring4_doors.same_as_one_limiter(by_door, PEERS, order, NOW)
        merged = reference_ring4_doors.interleave(by_door, order)
        assert [c for d, c in merged if d == PEERS[2]] == by_door[PEERS[2]]
        # and as the plain reference of ONE door's sequence says of it
        assert reference_ring4_doors.ring_answers(by_door, PEERS, order, NOW) == (
            check.reference_answers([c for _, c in merged], NOW))


@pytest.mark.parametrize("seed", range(4))
def test_single_hits_give_a_key_one_summary_whatever_the_order(seed):
    rng = random.Random(100 + seed)
    by_door = {p: [[(k, 1, li, d, a) for k, _h, li, d, a in call] for call in calls]
               for p, calls in _calls_by_door(seed, n_calls=8, items=20).items()}
    orders = _orders(by_door, rng, 12)
    assert reference_ring4_doors.same_for_every_order(by_door, PEERS, orders, NOW)
    summary = reference_ring4_doors.key_summaries(by_door, PEERS, orders[-1], NOW)
    over = [k for k, (answers, admitted, peek) in summary.items()
            if sum(answers.values()) > check.CHECK_LIMIT]
    assert over  # keys offered more than their limit at four doors together
    for k in over:
        answers, admitted, peek = summary[k]
        assert admitted == check.CHECK_LIMIT and peek[2] == 0
        assert sorted(a[2] for a in answers if a[0] == 0) == list(range(check.CHECK_LIMIT))
    # with a two-hit item the multiset does follow the order: the
    # statement is for single hits, and says so
    with pytest.raises(ValueError, match="one hit"):
        reference_ring4_doors.same_for_every_order(_calls_by_door(seed), PEERS, orders, NOW)


def test_an_order_must_send_every_call_once():
    by_door = _calls_by_door(1)
    order = reference_ring4_doors.round_robin(by_door)
    assert len(order) == sum(len(c) for c in by_door.values())
    with pytest.raises(ValueError, match="unsent"):
        reference_ring4_doors.interleave(by_door, order[:-1])
    with pytest.raises(ValueError, match="more often"):
        reference_ring4_doors.interleave(by_door, order + [PEERS[0]])


# -- a whole run on the CPU ------------------------------------------------------


def _copy(tmp_path):
    """The benchmark beside the program, the cell cut to a CPU's size:
    8 workers (two a door) x 2 frames of 200 items, 5,000 keys in four
    20,000-key stores (the same ways, ladder and sketch tier)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "gubernator_tpu"), root / "gubernator_tpu")
    for rel, changes in (
        ("traffic/geb-frames-all-doors.json", dict(
            inflight=2, items_per_frame=200, prebuilt_frames_per_s=20, warmup_s=1.0)),
        (f"cells/{CELL}.json", dict(trace_ms=500)),
        ("configs/ring4-lb.json", dict(
            env={"GUBER_STORE_TARGET_KEYS": "20000", "GUBER_SKETCH_MIB": "1"},
            key_population=5000, preload_keys=5000)),
    ):
        path = root / "benchmark" / rel
        obj = json.loads(path.read_text())
        for key, value in changes.items():
            obj[key] = dict(obj[key], **value) if isinstance(value, dict) else value
        path.write_text(json.dumps(obj))
    return root


def test_a_traced_rehearsal_reads_every_program_side_metric(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 46), "--seconds", "6", "--trace", "1"],
        cwd=_copy(tmp_path), env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 3, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] == "cpu" and last["device"]["nodes"] == 4
    assert last["attempted"] > 0 and last["metrics"] == {}  # no timing
    ready = next(x for x in lines if x.get("phase") == "generators_ready")
    assert len(ready["workers"]) == 8
    trace = next(x for x in lines if x.get("phase") == "trace")
    want = cell_metrics.rehearsed(CELL)
    assert set(trace["layer_metrics_read"]) == want and want
    assert set(cell_metrics.files(CELL)) - want == {
        n + SUFFIX for n in FROM_THE_TRACE}
    window = next(x for x in lines if x.get("phase") == "window")
    assert window["node_exits"] == [0, 0, 0, 0]
    g = window["generator"]
    assert len(g["frames_per_s_by_node"]) == 4 and min(g["frames_per_s_by_node"]) > 0
    assert len(g["worker_cpu_share"]) == 8 and g["door_skew_pct"] >= 0
    post = next(x for x in lines if x.get("phase") == "post_window_check")
    assert post["canaries"]["keys"] == 48 and post["canaries"]["differ"] == 0
    assert post["tallies"]["outside_bounds"] == 0 == post["malformed"]["replies"]
    assert not any(post["counters_whole_run"].values())
    assert post["programs_compiled_in_window"] == 0
