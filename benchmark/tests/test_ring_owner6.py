"""PR 32's deployment `ring-owner6` and its cell `ring-owner6.peer-batches`
(CPU, by hand like the rest of this directory):

- the configuration file states zipf10m's environment, store and
  guarantees word for word and the ring's shape; the traffic file
  geb-frames' limit classes and algorithm mix letter for letter;
- the cell's metrics are the `BENCHMARK.json` entries that list it =
  the files that list it; what it shares with `zipf10m.geb-frames`
  (batcher, engine, kernel, device) is ONE file that lists both, a
  `.peer` file is the peer door's own; the two that read PR 32's span
  and counters read nothing from a program that has neither (the
  parent), and the two guards read 100 where the native paths serve;
- the cell rehearsed traced on the CPU with the key budget cut to a
  CPU's size: exit 3, `correct: true`, every program-side metric read,
  the generator's own check of the peer door printed;
- the control (`faulty_daemon.py lost_writes`) reads `correct: false`,
  and so does a daemon whose PEER door alone answers wrong
  (`faulty_peer_door.py`) while the harness's V1 check passes: the
  generator's check is what holds the door.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import cell_metrics
from readers import prom_sum, stages

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "ring-owner6.peer-batches"
#: the gRPC cell's metric each call-tile `.peer` metric reads like, at
#: 1000 items a call instead of 2 (another end-to-end metric: no merge)
TWIN = {
    "door_codec_us_per_batch.peer": "grpc_codec_us_per_call",
    "call_server_ms.peer": "call_server_ms", "call_queue_ms.peer": "call_queue_ms",
    "call_device_ms.peer": "call_device_ms", "call_wake_us.peer": "call_wake_us",
}


def load(*rel):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


def test_the_configuration_is_zipf10m_behind_another_door():
    from gubernator_tpu.core.store import derive_store_config

    config, zipf10m = load("configs", "ring-owner6.json"), load("configs", "zipf10m.json")
    for key in ("env", "store", "key_population", "preload_keys", "guarantees"):
        assert config[key] == zipf10m[key], key
    # one chip serves, as zipf10m's, and since PR 49 the cell asks for
    # the machine it uses (four before, for the host's sake alone)
    assert config["chips_used"] == config["chips"] == zipf10m["chips"] == 1
    assert "PR 49" in config["chips_why"]
    derived = derive_store_config(
        target_keys=int(config["env"]["GUBER_STORE_TARGET_KEYS"]))
    assert (config["store"]["ways"], config["store"]["rows"]) == (
        derived.rows, derived.slots) == (16, 1 << 20)
    assert config["ring"] == {
        "nodes": 6, "forwarders": 5, "batch_limit": 1000, "batch_wait_us": 500,
        "batches_in_flight_per_forwarder": 1, "behavior": "BATCHING"}
    assert config["reduced"] == ["nodes_run", "client_side", "live_keys_at_start"]
    assert set(config["reduced"]) == set(config["reduced_detail"])
    traffic, frames = load("traffic", "peer-batches.json"), load("traffic", "geb-frames.json")
    for key in ("key_classes", "algorithms", "warmup_s", "drain_timeout_s",
                "canary_every", "canaries_per_worker"):
        assert traffic[key] == frames[key], key
    assert traffic["workers"] == config["ring"]["forwarders"]
    assert traffic["items_per_batch"] == config["ring"]["batch_limit"]
    assert traffic["base_seed"] != frames["base_seed"]
    cell = load("cells", CELL + ".json")
    assert (cell["config"], cell["traffic"]) == ("ring-owner6", "peer-batches")


def test_every_metric_of_the_cell_is_an_entry_a_file_and_a_reader():
    mine = cell_metrics.held_together(CELL)
    assert all(s["moves"] == "decisions_per_s" for s in mine.values())
    for name, twin in TWIN.items():  # the call's tiles read as cell 1's do
        old = cell_metrics.spec(twin)
        for key in set(old) - {"cells", "what", "layer", "moves"}:
            assert mine[name][key] == old[key], (name, key)
    # below the door the cell is zipf10m.geb-frames: the same files
    for name in ("batch_fill_pct", "submit_host_us_per_batch", "jit_call_us_per_batch",
                 "decide_step_us", "decide_roofline", "device_idle_share"):
        assert "zipf10m.geb-frames" in mine[name]["cells"], name
    assert all(s["cells"] == [CELL] for n, s in mine.items() if n.endswith(".peer"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = next(m for m in bench["end_to_end"] if m["name"] == "decisions_per_s")
    assert CELL in e2e["workloads"] and e2e["bound"] == 0.1  # 0.05 until PR 38
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work["chips"] == 1


def test_the_new_readings_by_hand_and_on_a_program_without_them():
    span = load("layer_metrics", "peer_serve_us_per_batch.peer.json")
    share = load("layer_metrics", "peer_shed_hit_pct.peer.json")

    def snap(total_s, count):
        return {"stages": {"peer_serve": {"total_s": total_s, "count": count},
                           "call_e2e": {"total_s": 9.0, "count": count}}}

    ctx = {"stages0": snap(1.0, 100), "stages1": snap(1.6, 200),
           "prom0": {"peer_serve_items_total": 1000.0, "peer_serve_shed_hits_total": 100.0},
           "prom1": {"peer_serve_items_total": 5000.0, "peer_serve_shed_hits_total": 1300.0}}
    assert stages.read(span, ctx) == pytest.approx(6000.0)  # us a batch
    assert prom_sum.read(share, ctx) == pytest.approx(30.0)  # %
    # the parent of PR 32: calls, but no such span and no such counter
    bare = {"stages": {"call_e2e": {"total_s": 9.0, "count": 200}}}
    parent = {"stages0": bare, "stages1": bare, "prom0": {}, "prom1": {"shed_hits_total": 5.0}}
    assert stages.read(span, parent) is None
    assert prom_sum.read(share, parent) is None
    # PR 49's two guards: 100 where the native body and the fold serve,
    # less where the twin does, nothing from a program without the counters
    native = load("layer_metrics", "shed_native_consults_pct.json")
    folded = load("layer_metrics", "peer_folded_items_pct.json")
    assert CELL in native["cells"] and folded["cells"] == [CELL]
    ctx["prom0"].update(shed_index_uses_total=10.0, shed_native_consults_total=10.0,
                        peer_serve_folded_items_total=1000.0)
    ctx["prom1"].update(shed_index_uses_total=410.0, shed_native_consults_total=410.0,
                        peer_serve_folded_items_total=5000.0)
    assert prom_sum.read(native, ctx) == prom_sum.read(folded, ctx) == 100.0
    ctx["prom1"].update(shed_native_consults_total=110.0,  # the numpy twin took over
                        peer_serve_folded_items_total=4000.0)  # a batch in four declined
    assert prom_sum.read(native, ctx) == pytest.approx(25.0)
    assert prom_sum.read(folded, ctx) == pytest.approx(75.0)
    assert prom_sum.read(native, parent) is None
    assert prom_sum.read(folded, parent) is None


def _copy(tmp_path):
    """The benchmark beside the program, the cell cut to a CPU's size:
    2 forwarders x 200 items, 5,000 keys in a 20,000-key store (the
    same ways, ladder and sketch tier)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "gubernator_tpu"), root / "gubernator_tpu")
    for rel, changes in (
        ("traffic/peer-batches.json", dict(
            workers=2, items_per_batch=200, prebuilt_batches_per_s=20, warmup_s=1.0)),
        (f"cells/{CELL}.json", dict(trace_ms=500)),
        ("configs/ring-owner6.json", dict(
            env={"GUBER_STORE_TARGET_KEYS": "20000"}, key_population=5000,
            preload_keys=5000)),
    ):
        path = root / "benchmark" / rel
        obj = json.loads(path.read_text())
        for key, value in changes.items():
            obj[key] = dict(obj[key], **value) if isinstance(value, dict) else value
        path.write_text(json.dumps(obj))
    return root


def _run(root, *extra, trace=0, seed=2**31 + 32):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "4", "--trace", str(trace), *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=1500)
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    return p, lines


def test_a_traced_rehearsal_reads_the_program_side_metrics(tmp_path):
    p, lines = _run(_copy(tmp_path), trace=1)
    assert p.returncode == 3, p.stderr[-2000:]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] == "cpu" and last["device"]["count"] == 1
    assert last["attempted"] > 0 and last["metrics"] == {}  # no timing
    pre = next(x for x in lines if x.get("phase") == "pre_window_check")
    assert pre["door"] == "grpc" and pre["differ"] == 0
    ready = next(x for x in lines if x.get("phase") == "generators_ready")
    door = ready["workers"][0]["peer_door_check"]
    assert door["compared"] == 300 and door["differ"] == 0 == door["limit"]
    assert door["over_limit_answers"] > 0
    assert "peer_door_check" not in ready["workers"][1]
    trace = next(x for x in lines if x.get("phase") == "trace")
    want = cell_metrics.rehearsed(CELL)
    assert set(trace["layer_metrics_read"]) == want and want
    assert {"shed_native_consults_pct", "peer_folded_items_pct"} <= want
    assert set(cell_metrics.files(CELL)) - want == {
        "decide_step_us", "decide_roofline", "device_idle_share"}
    gen = next(x for x in lines if x.get("phase") == "window")["generator"]
    assert len(gen["worker_cpu_share"]) == 2 and gen["batches_per_s"] > 0
    assert "peer_door_check" not in gen  # said once, in the ready line
    post = next(x for x in lines if x.get("phase") == "post_window_check")
    assert post["canaries"]["replies"] > 0 and post["canaries"]["differ"] == 0
    assert post["tallies"]["outside_bounds"] == 0
    assert post["tallies"]["keys_held_exactly"] > 0
    assert not any(post["counters_whole_run"].values())


@pytest.mark.parametrize("daemon,held", [
    (["benchmark/tests/faulty_daemon.py", "lost_writes"], "harness"),
    (["benchmark/tests/faulty_peer_door.py"], "generator"),
])
def test_correct_is_false_when_a_guarantee_or_the_peer_door_is_broken(
        tmp_path, daemon, held):
    p, lines = _run(_copy(tmp_path), "--daemon-argv", json.dumps(daemon))
    assert p.returncode == 3, p.stderr[-2000:]
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    pre = next(x for x in lines if x.get("phase") == "pre_window_check")
    ready = next(x for x in lines if x.get("phase") == "generators_ready")
    post = next(x for x in lines if x.get("phase") == "post_window_check")
    if held == "generator":
        # V1 answers right, so the harness's check passes; the peer
        # door's own check differs, and is counted beside limit 0
        assert pre["differ"] == 0
        differ = ready["workers"][0]["peer_door_check"]["differ"]
        assert differ > 0 and post["malformed"]["replies"] >= differ
    else:
        # lost writes show wherever hits are read back, whatever the door
        assert (pre["differ"] + post["tallies"]["outside_bounds"]
                + post["canaries"]["differ"]) > 0
