"""The per-layer metrics PR 24 added: the `stage_quantile` reader on
hand-made snapshots, and a rehearsal of both cells' shapes on the CPU
(tiny store, fewer callers, the cells' own names) that finds every one
of the new metrics read. No timing is reported from here.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from readers import stage_quantile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
EDGES = [1e-6 * 2.0 ** (k / 2) for k in range(55)]
NEW = {
    "upstream-node.grpc-pairs": {
        "call_server_ms", "call_server_p50_ms", "call_server_p99_ms",
        "grpc_codec_us_per_call", "call_queue_ms", "call_queue_p50_ms",
        "call_device_ms", "call_wake_us", "jit_call_us_per_batch.rate",
        "batch_fill_pct.rate", "loop_lag_p99_ms"},
    "zipf10m.geb-frames": {"jit_call_us_per_batch", "batch_fill_pct"},
}


def bucket(seconds):
    return next((i for i, e in enumerate(EDGES) if seconds < e), len(EDGES))


def snap(samples, edges=EDGES):
    """A /v1/debug/stages body with `samples` seconds under call_e2e."""
    counts = [0] * (len(EDGES) + 1)
    for s in samples:
        counts[bucket(s)] += 1
    body = {"stages": {"call_e2e": {
        "total_s": sum(samples), "count": len(samples), "buckets": counts}}}
    if edges:
        body["bucket_edges_s"] = edges
    return body


def read(q, before, after, stage="call_e2e"):
    return stage_quantile.read(
        {"stage": stage, "q": q, "scale": 1e3},
        {"stages0": before, "stages1": after})


def test_quantiles_of_the_window_not_of_the_run():
    before = snap([0.5] * 50)  # what the run had seen before the window
    window = [0.012] * 97 + [0.150] * 3
    after = snap([0.5] * 50 + window)
    for q, want in ((0.5, 0.012), (0.9, 0.012), (0.99, 0.150)):
        got = read(q, before, after) / 1e3
        lo = max(e for e in [0.0] + EDGES if e <= want)
        hi = min(e for e in EDGES if e > want)
        assert lo <= got <= hi, (q, got)
    # linear inside the bucket: 97 samples in [11.585, 16.384) ms
    assert read(0.485, before, after) == pytest.approx(
        (11.585237502960396 + 16.384) / 2, rel=1e-6)
    assert read(1.0, before, after) <= EDGES[bucket(0.150)] * 1e3
    assert read(0.5, snap([]), snap([1000.0])) == EDGES[-1] * 1e3  # open end


def test_nothing_to_read_is_none():
    same = snap([0.012] * 10)
    assert read(0.5, same, same) is None  # an empty window
    assert read(0.5, snap([]), snap([0.01]), stage="call_queue") is None
    # the parent's program: totals and counts, no buckets
    old = {"stages": {"call_e2e": {"total_s": 1.0, "count": 9}}}
    assert read(0.5, old, old) is None
    assert read(0.5, snap([]), snap([0.01], edges=None)) is None


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The benchmark's files beside the program, with the two
    configurations cut to a CPU's size in the COPY (store, key
    population) and the mixes to two workers: the cells keep their
    names, so every per-layer metric that names them is read."""
    root = tmp_path_factory.mktemp("rehearsal")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "gubernator_tpu"), root / "gubernator_tpu")

    def edit(rel, **changes):
        path = root / "benchmark" / rel
        obj = json.loads(path.read_text())
        for key, value in changes.items():
            if isinstance(value, dict):
                obj[key] = dict(obj[key], **value)
            else:
                obj[key] = value
        path.write_text(json.dumps(obj))

    tiny = {"GUBER_STORE_TARGET_KEYS": "20000", "GUBER_SKETCH_MIB": "1"}
    for config in ("upstream-node", "zipf10m"):
        edit(f"configs/{config}.json", env=tiny, key_population=5000,
             preload_keys=5000)
    edit("traffic/grpc-pairs.json", workers=2, warmup_s=1.0, canary_every=10)
    edit("traffic/geb-frames.json", workers=2, inflight=4,
         items_per_frame=200, warmup_s=1.0)
    edit("cells/upstream-node.grpc-pairs.json", rate=200, trace_ms=500)
    edit("cells/zipf10m.geb-frames.json", trace_ms=500)
    return root


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_traced_rehearsal_reads_every_new_metric(checkout, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2**31 + 24), "--seconds", "4", "--trace", "1"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 3, p.stderr[-2000:]  # a rehearsal: not a chip run
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    trace = next(x for x in lines if x.get("phase") == "trace")
    read_ = set(trace["layer_metrics_read"])
    assert NEW[cell] <= read_, sorted(NEW[cell] - read_)
    # and nothing of the other cell's
    other = set().union(*(v for k, v in NEW.items() if k != cell))
    assert not other & read_
