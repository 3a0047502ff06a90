"""PR 30's two cells, `exact100m.geb-frames` and `upstream-node.geb-frames`
(CPU, by hand like the rest of this directory):

- the configuration file states the shape the program derives from the
  environment it names, and zipf10m's guarantees word for word;
- each cell's metrics are the `BENCHMARK.json` entries that list it =
  the files that list it, each with a reader that exists; what both
  cells share with `zipf10m.geb-frames` is ONE file that lists the three;
- the `gauge` reader by hand, and what it reads from a program that
  exports no such gauge (the parent) or no peak (the CPU): nothing;
- `decide_roofline` by hand: the bytes are keyed on rows touched,
  so a step of the same items needs the same bytes on 8 GiB as on 512 MiB;
- both cells rehearsed traced on the CPU: `upstream-node.geb-frames` as
  it is (its store is 16 MiB), `exact100m.geb-frames` with the key
  budget cut to a CPU's size; exit 3, `correct: true`, every program-side
  metric read.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import cell_metrics
import kernel_bytes
from readers import gauge

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
X100M, NODE = "exact100m.geb-frames", "upstream-node.geb-frames"
CONTROL = "zipf10m.geb-frames"  # the same door and traffic on 512 MiB


def load(*rel):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


def test_the_configuration_states_what_the_program_derives():
    from gubernator_tpu.core.store import (
        BYTES_PER_ENTRY, derive_store_config, store_footprint_bytes)

    config = load("configs", "exact100m.json")
    assert config["env"] == {"GUBER_BACKEND": "tpu",
                             "GUBER_STORE_TARGET_KEYS": "100000000"}
    derived = derive_store_config(
        target_keys=int(config["env"]["GUBER_STORE_TARGET_KEYS"]))
    store = config["store"]
    assert (store["ways"], store["rows"]) == (derived.rows, derived.slots)
    assert (store["ways"], store["rows"]) == (16, 1 << 24)
    assert store["entry_bytes"] == BYTES_PER_ENTRY
    assert store_footprint_bytes(derived) == 8 << 30
    assert config["key_population"] == 100_000_000
    assert config["preload_keys"] == 1_000_000
    zipf10m = load("configs", "zipf10m.json")
    assert config["guarantees"] == zipf10m["guarantees"]
    assert store["ladder"] == zipf10m["store"]["ladder"]
    assert store["sketch_mib"] == zipf10m["store"]["sketch_mib"]
    assert set(config["reduced"]) == set(config["reduced_detail"])
    # the same traffic file as zipf10m's cell, letter for letter
    assert load("cells", X100M + ".json")["traffic"] == load(
        "cells", "zipf10m.geb-frames.json")["traffic"] == load(
        "cells", NODE + ".json")["traffic"] == "geb-frames"


@pytest.mark.parametrize("cell", [X100M, NODE])
def test_every_metric_of_the_cell_is_an_entry_a_file_and_a_reader(cell):
    mine = cell_metrics.held_together(cell)
    assert all(s["moves"] == "decisions_per_s" for s in mine.values())
    # door, batcher, engine, kernel and device are read by the files
    # that read them in the control cell: one spec, the cells a list
    shared = {n for n, s in mine.items() if CONTROL in s["cells"]}
    assert {"door_codec_us_per_frame", "shed_us_per_frame", "batch_fill_pct",
            "submit_host_us_per_batch", "jit_call_us_per_batch",
            "decide_step_us", "device_idle_share"} <= shared
    own = set(mine) - shared
    if cell == X100M:
        # the one reading no other cell has: a second table alive
        assert own == {"hbm_peak_over_state.x100m"}
        assert mine["hbm_peak_over_state.x100m"]["reader"] == "gauge"
        assert {"decide_roofline", "dispatch_us_per_batch"} <= shared
    else:
        assert not own


def test_gauge_reader_by_hand():
    spec = load("layer_metrics", "hbm_peak_over_state.x100m.json")
    state = (8 << 30) + (16 << 20)
    ctx = {"prom1": {"device_memory_peak_bytes": 1.06 * state,
                     "store_state_bytes": float(state)}, "prom0": {}}
    assert gauge.read(spec, ctx) == pytest.approx(1.06)
    # two tables alive at some instant
    ctx["prom1"]["device_memory_peak_bytes"] = 2.0 * state + 3e6
    assert 2.0 < gauge.read(spec, ctx) < 2.001
    # the parent exports neither gauge; the CPU keeps no peak (0)
    assert gauge.read(spec, {"prom1": {}, "prom0": {}}) is None
    ctx["prom1"]["device_memory_peak_bytes"] = 0.0
    assert gauge.read(spec, ctx) is None


def test_roofline_bytes_follow_rows_touched_not_the_table():
    big = load("configs", "exact100m.json")["store"]
    small = load("configs", "zipf10m.json")["store"]
    items = 566.0
    need = kernel_bytes.decide_step_bytes(items, big)
    assert need == kernel_bytes.decide_step_bytes(items, small)
    assert need == items * (16 * 32 * 2 + 36 + 28)
    # a 26 ms step (the pass over 8 GiB at ~650 GB/s) reads 0.0029%
    share = kernel_bytes.roofline_share_pct(need, 26e-3, 819e9)
    assert share == pytest.approx(100 * need / 819e9 / 26e-3)
    assert 0.002 < share < 0.004


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "gubernator_tpu"), root / "gubernator_tpu")
    return root


def _edit(root, rel, **changes):
    path = root / "benchmark" / rel
    obj = json.loads(path.read_text())
    for key, value in changes.items():
        obj[key] = dict(obj[key], **value) if isinstance(value, dict) else value
    path.write_text(json.dumps(obj))


@pytest.mark.parametrize("cell", [NODE, X100M])
def test_a_traced_rehearsal_reads_the_program_side_metrics(tmp_path, cell):
    """`upstream-node.geb-frames` runs as it is but for the generators'
    size (2 workers x 4 frames of 200 items: a CPU answers them inside
    the frame timeout); `exact100m.geb-frames` also gets a key budget
    the CPU holds (the same ways, ladder and sketch tier). Exit 3
    (`rehearsal`), correct, every metric that reads a span or a counter
    is read. The three that read the device trace need a device plane;
    the CPU's allocator keeps no peak, so `hbm_peak_over_state` reads
    nothing here too (the chip runs read it)."""
    root = _copy(tmp_path)
    _edit(root, "traffic/geb-frames.json", workers=2, inflight=4,
          items_per_frame=200, warmup_s=1.0)
    _edit(root, f"cells/{cell}.json", trace_ms=500)
    if cell == X100M:
        _edit(root, "configs/exact100m.json",
              env={"GUBER_STORE_TARGET_KEYS": "20000"},
              key_population=5000, preload_keys=5000)
    else:
        _edit(root, "configs/upstream-node.json",
              key_population=20000, preload_keys=20000)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2**31 + 30), "--seconds", "4", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 3, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] == "cpu" and last["device"]["count"] == 1
    assert last["attempted"] > 0 and last["metrics"] == {}  # no timing
    trace = next(x for x in lines if x.get("phase") == "trace")
    want = cell_metrics.rehearsed(cell)
    assert set(trace["layer_metrics_read"]) == want and want
    post = next(x for x in lines if x.get("phase") == "post_window_check")
    assert post["tallies"]["outside_bounds"] == 0
    assert not any(post["counters_whole_run"].values())
