"""Whole runs of the harness on the CPU (a named platform, so the look
for a chip is skipped and no timing is reported), about half a minute
each:

- a configuration, a traffic mix, a cell and a per-layer metric added
  as NEW FILES ONLY to a copy of the benchmark are found by name, on one
  device and on four (the mesh backend on forced host devices);
- the control (the daemon with "every acknowledged hit is read back"
  broken) and the timed path broken where answers are produced both
  come out `correct: false`; the sound daemon comes out true.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TINY_ENV = {"GUBER_STORE_TARGET_KEYS": "20000", "GUBER_SKETCH_MIB": "1"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark beside the program, with files ADDED."""
    root = tmp_path_factory.mktemp("checkout")
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
            "store": {"ways": 16, "rows": 2048, "entry_bytes": 32}}
    add("configs/dummy-one.json", dict(
        base, chips=1, env=dict(TINY_ENV, GUBER_BACKEND="tpu")))
    add("configs/dummy-mesh4.json", dict(
        base, chips=4, env=dict(
            TINY_ENV, GUBER_BACKEND="mesh", GUBER_SHARDS="4",
            XLA_FLAGS="--xla_force_host_platform_device_count=4")))
    mix = json.loads((b / "traffic" / "geb-frames.json").read_text())
    add("traffic/dummy-frames.json", dict(
        mix, workers=2, inflight=4, items_per_frame=200, warmup_s=1.0))
    calls = json.loads((b / "traffic" / "grpc-pairs.json").read_text())
    add("traffic/dummy-calls.json", dict(calls, workers=2, warmup_s=1.0,
                                         canary_every=10))
    for cell, config, traffic in (
        ("dummy-one.dummy-frames", "dummy-one", "dummy-frames"),
        ("dummy-one.dummy-calls", "dummy-one", "dummy-calls"),
        ("dummy-mesh4.dummy-frames", "dummy-mesh4", "dummy-frames"),
    ):
        add(f"cells/{cell}.json", {
            "config": config, "traffic": traffic, "rate": 300,
            "trace_ms": 500, "trace_match": "decide", "why": "a test"})
    add("layer_metrics/dummy_batch_items.json", {
        "layer": "DeviceBatcher", "unit": "items", "moves": "decisions_per_s",
        "source": "program_counter",
        "cells": ["dummy-one.dummy-frames", "dummy-mesh4.dummy-frames"],
        "reader": "prom", "delta": "device_batch_size_sum",
        "per_delta": "device_batch_size_count"})
    yield root
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there was edited


def run(root, cell, *extra, trace=0, seed=2**31 + 7):
    # a TMPDIR of the checkout's own: the daemon's profile capture lands
    # under <TMPDIR>/guber-profile/bench_<cell>, and two test files that
    # run one cell name side by side (`pytest -n 4`) met there
    os.makedirs(root / "tmp", exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(root / "tmp"))
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(seed), "--seconds", "3", "--trace", str(trace), *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    return p, lines


@pytest.mark.parametrize("cell,count", [
    ("dummy-one.dummy-frames", 1), ("dummy-mesh4.dummy-frames", 4)])
def test_new_files_are_found_by_name(checkout, cell, count):
    p, lines = run(checkout, cell, trace=1)
    assert p.returncode == 3, p.stderr[-2000:]  # a rehearsal: not a chip run
    last = lines[-1]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["rehearsal"] == "cpu" and last["metrics"] == {}  # no timing
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == count
    assert 0 < last["device"]["busy_s"] <= last["device"]["window_s"]
    assert last["breakdown"]["device_ops"] and last["breakdown"]["idle_gaps"]
    trace = next(x for x in lines if x.get("phase") == "trace")
    assert "dummy_batch_items" in trace["layer_metrics_read"]
    post = next(x for x in lines if x.get("phase") == "post_window_check")
    assert post["canaries"]["replies"] > 0
    assert post["tallies"]["keys_held_exactly"] > 0


@pytest.mark.parametrize("cell", ["dummy-one.dummy-frames", "dummy-one.dummy-calls"])
@pytest.mark.parametrize("fault,correct", [
    (None, True), ("lost_writes", False), ("altered_answers", False)])
def test_correct_is_false_when_a_guarantee_or_the_path_is_broken(
        checkout, cell, fault, correct):
    extra = []
    if fault:
        extra = ["--daemon-argv",
                 json.dumps(["benchmark/tests/faulty_daemon.py", fault])]
    p, lines = run(checkout, cell, *extra)
    assert p.returncode == 3, p.stderr[-2000:]
    assert lines[-1]["correct"] is correct
    assert lines[-1]["failed"] == 0


def test_no_result_without_the_program(tmp_path):
    """Only BENCHMARK.json and benchmark/: a non-zero exit, no result."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "upstream-node.grpc-pairs", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and not p.stdout.strip()
