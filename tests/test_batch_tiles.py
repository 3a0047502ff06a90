"""A device batch's life tiled from collect to resolve, and the
serving threads' CPU clocks (PR 36).

- the seven `BATCH_TILES` are each recorded once a device batch, on
  every lane that launches one, `submit_host` is their three submit
  legs by construction, and `batch_coverage` is whole but for the
  resolve;
- `ThreadClocks` reads what a thread RAN (not what it slept), by role,
  from either source, and exports nothing where the host has neither;
- the clocks are read by the scrape and snapshot handlers and by no
  serving path (a grep-level pin);
- the 14 per-layer metric files that read all this agree with
  BENCHMARK.json, and a CPU rehearsal of the two cells that name them
  finds every one read.
"""

import asyncio
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from gubernator_tpu.api.types import RateLimitReq, RateLimitResp
from gubernator_tpu.serve import stages
from gubernator_tpu.serve.batcher import DeviceBatcher
from gubernator_tpu.serve.stages import BATCH_TILES, PER_BATCH, STAGES

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"

SUBMIT_S, WAIT_S = 0.020, 0.030
SUBMIT_TILES = ("submit_wake", "submit_call", "submit_return")
FETCH_TILES = ("fetch_wake", "fetch_wait", "fetch_return")


class SleepyDevice:
    """The launch surface over plain lists; the dispatch and the wait
    sleep known times OFF the GIL, as a device call does."""

    def prep_reqs(self, reqs, gnp):
        return [r.limit for r in reqs]

    def prep_group(self, fields):
        return [int(x) for x in fields["limit"]]

    def merge_prepped(self, runs):
        return [x for run in runs for x in run]

    def decide_submit_merged(self, merged):
        time.sleep(SUBMIT_S)
        return merged

    def decide_wait_arrays(self, handle):
        time.sleep(WAIT_S)
        n = len(handle)
        z = np.zeros(n, np.int64)
        return z, np.asarray(handle, np.int64), z, z

    @staticmethod
    def resps_from_arrays(status, limit, remaining, reset):
        return [RateLimitResp(limit=int(x)) for x in limit]

    def decide_chain(self, reqs):
        time.sleep(SUBMIT_S + WAIT_S)
        return [RateLimitResp(limit=r.limit) for r in reqs]


def _reqs(n):
    return [
        RateLimitReq(name="t", unique_key=f"k{i}", hits=1, limit=100 + i,
                     duration=1000)
        for i in range(n)
    ]


@pytest.mark.parametrize("lane", ["decide", "arrays", "chain"])
def test_one_batch_records_every_tile_once(lane):
    async def scenario():
        b = DeviceBatcher(SleepyDevice(), batch_wait=0, batch_limit=256)
        if lane == "decide":
            call = b.decide(_reqs(8), [False] * 8)
        elif lane == "arrays":
            fields = {
                k: np.arange(8, dtype=np.int64) + 100
                for k in ("key_hash", "hits", "limit", "duration", "algo")
            }
            call = b.decide_arrays(fields)
        else:
            call = b.decide_chain(_reqs(8))
        task = asyncio.ensure_future(call)
        await asyncio.sleep(0)  # enqueued: ONE batch
        STAGES.reset()
        b.start()
        out = await task
        snap = STAGES.snapshot()
        await b.stop()
        return out, snap

    out, snap = asyncio.run(scenario())
    limits = list(out[1]) if lane == "arrays" else [r.limit for r in out]
    assert limits == [100 + i for i in range(8)]

    st = snap["stages"]
    assert snap["per_batch_tiles"] == list(BATCH_TILES)
    assert set(BATCH_TILES) | {"batch_e2e", "submit_host"} <= set(PER_BATCH)
    fetched = lane != "chain"  # the chain call submits AND waits
    want = {"admit_wait", *SUBMIT_TILES, "submit_host", "batch_e2e"}
    want |= set(FETCH_TILES) if fetched else set()
    counts = {
        name: st.get(name, {"count": 0})["count"]
        for name in (*BATCH_TILES, "submit_host", "batch_e2e")
    }
    assert counts == {name: int(name in want) for name in counts}, counts
    assert snap["batches"] == 1

    def total(*names):
        return sum(st[n]["total_s"] for n in names if n in st)

    # the three legs ARE submit_host: the same three stamps (totals are
    # rounded to the microsecond in a snapshot)
    assert total(*SUBMIT_TILES) == pytest.approx(
        total("submit_host"), abs=5e-6
    )
    # the thread-bound spans hold the sleeps; the legs between threads
    # hold none of them
    if fetched:
        assert total("submit_call") >= SUBMIT_S
        assert total("fetch_wait") >= WAIT_S
    else:
        assert total("submit_call") >= SUBMIT_S + WAIT_S
    tiles = total(*BATCH_TILES)
    assert tiles <= total("batch_e2e") + 1e-5
    assert snap["batch_coverage"] >= 0.95, snap["batch_coverage"]
    assert snap["batch_coverage"] == pytest.approx(
        tiles / total("batch_e2e"), abs=1e-3
    )


def test_a_host_backend_records_no_batch_tile():
    """A blocking decide is no device batch: outside the tiling."""

    class Host:
        def decide(self, reqs, gnp):
            return [RateLimitResp(limit=r.limit) for r in reqs]

    async def scenario():
        b = DeviceBatcher(Host(), batch_wait=0)
        task = asyncio.ensure_future(b.decide(_reqs(3), [False] * 3))
        await asyncio.sleep(0)
        STAGES.reset()
        b.start()
        await task
        snap = STAGES.snapshot()
        await b.stop()
        return snap

    snap = asyncio.run(scenario())
    assert not set(snap["stages"]) & ({*BATCH_TILES, "batch_e2e"})
    assert snap["batches"] == 0 and snap["batch_coverage"] == 0.0


def _spin_then_sleep(ran, spun, release):
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.2:
        pass
    ran.append(time.thread_time())  # what this thread ran, by itself
    spun.set()
    time.sleep(0.2)
    release.wait(timeout=30)


@pytest.mark.parametrize("source", ["pthread", "proc_stat"])
def test_thread_clocks_read_what_a_thread_ran(source):
    read = dict(stages.ThreadClocks.SOURCES)[source]
    clocks = stages.ThreadClocks(sources=((source, read),))
    if clocks.source == "none":
        pytest.skip(f"this host has no {source} thread clock")
    assert clocks.source == source
    before = clocks.snapshot()
    ran, spun, release = [], threading.Event(), threading.Event()
    t = threading.Thread(
        target=_spin_then_sleep, args=(ran, spun, release),
        name="guber-submit_0",
    )
    t.start()
    assert spun.wait(timeout=30)
    time.sleep(0.25)  # it has slept its 0.2 s and waits: still alive
    after = clocks.snapshot()
    release.set()
    t.join(timeout=10)
    assert not t.is_alive()
    assert set(after["cpu_s"]) == set(stages.THREAD_ROLES)
    grew = {k: after["cpu_s"][k] - before["cpu_s"][k] for k in after["cpu_s"]}
    # the spin and not the sleep, to three 10 ms ticks
    assert grew["submit"] == pytest.approx(ran[0], abs=0.03), grew
    assert grew["fetch"] < 0.05 and grew["prep"] < 0.05
    assert 0.0 <= grew["loop"] < 0.1  # this thread slept
    assert after["wall_s"] - before["wall_s"] >= 0.45
    step = clocks.measure_granularity()
    assert step is not None and 0 < step <= 0.05
    assert clocks.snapshot()["granularity_s"] == step


def test_no_thread_clock_exports_no_series():
    refuses = (("pthread", lambda t: None), ("proc_stat", lambda t: None))
    clocks = stages.ThreadClocks(sources=refuses)
    snap = clocks.snapshot()
    assert clocks.source == snap["thread_clock"] == "none"
    assert snap["cpu_s"] == {} and snap["wall_s"] > 0
    assert clocks.measure_granularity() is None
    # and the benchmark's reader then reads nothing
    sys.path.insert(0, str(BENCH))
    try:
        from readers import prom_sum
    finally:
        sys.path.pop(0)
    spec = json.loads(
        (BENCH / "layer_metrics" / "loop_cpu_pct.json").read_text()
    )
    prom = {"thread_wall_seconds_total": 12.0}
    assert prom_sum.read(spec, {"prom0": prom, "prom1": prom}) is None


def test_thread_clocks_are_read_at_scrape_and_nowhere_else():
    """No clock_gettime, thread_time or /proc read on a serving path:
    the readers live in serve/stages.py's ThreadClocks alone, and the
    only callers are the two handlers and the boot's one measurement."""
    reads = re.compile(
        r"clock_gettime|thread_time|pthread_getcpuclockid|/proc/self/task"
    )
    pkg = ROOT / "gubernator_tpu"
    holders = {
        str(p.relative_to(pkg))
        for p in pkg.rglob("*.py")
        if reads.search(p.read_text())
    }
    assert holders == {"serve/stages.py"}, holders
    uses = {}
    for p in pkg.rglob("*.py"):
        for m in re.finditer(
            r"(?:thread_clocks|ThreadClocks\(\))\.(\w+)\(", p.read_text()
        ):
            uses.setdefault(str(p.relative_to(pkg)), []).append(m.group(1))
    assert uses == {"serve/server.py": ["snapshot", "snapshot"]}, uses
    server = (pkg / "serve" / "server.py").read_text()
    for handler in ("_refresh_store_metrics", "_http_debug_stages"):
        body = server.split(f"def {handler}(")[1].split("\n    def ")[0]
        body = body.split("\n    async def ")[0]
        assert "self.thread_clocks.snapshot()" in body, handler
    assert server.count("measure_granularity()") == 1
    assert "measure_granularity()" in server.split("async def run_daemon")[1]


def test_gap_threads_names_the_line_an_idle_gap_goes_to():
    """scripts/gap_threads.py keeps trace_reduce's attribution (the
    shortest host event over half a gap) and adds the thread's line:
    a pool worker's idle `get` is told from the loop's own work."""
    sys.path[:0] = [str(ROOT / "scripts"), str(BENCH)]
    try:
        import gap_threads
        import trace_reduce
    finally:
        del sys.path[:2]
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("%fusion.1", 0.0, 1e6), ("%fusion.1", 5e6, 1e6),
            ("%fusion.1", 9e6, 1e6)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ("get", 1.0e6, 3.9e6), ("get", 6.1e6, 2.8e6),
                ("fetch_wait", 0.0, 0.9e6)]},
            {"name": "python3", "events": [
                ("$base_events.py:1922 _run_once", 0.5e6, 9e6),
                ("shed", 1.2e6, 3.0e6)]},
        ]},
    ]
    out = gap_threads.gaps_by_thread(planes)
    same = dict(map(tuple, trace_reduce.reduce_planes(planes, "x")["idle_gaps"]))
    assert dict(map(tuple, out["idle_gaps"])) == pytest.approx(same)
    # every Python thread's line is named "python3": told apart by
    # index, and by the serving thread their own events give away
    assert out["thread_of"]["get"] == [["python3#0 (guber-fetch)", 0.003]]
    assert out["thread_of"]["shed"] == [["python3#1 (loop)", 0.004]]
    assert out["covering_on_other_threads"]["get"] == [
        ["python3#1 (loop) :: $base_events.py:1922 _run_once", 0.003]]
    assert "error" in gap_threads.gaps_by_thread(planes[1:])  # no device


NEW_METRICS = (
    "submit_wake_us_per_batch", "submit_return_us_per_batch",
    "fetch_handoff_us_per_batch", "admit_wait_us_per_batch",
    "submit_cpu_us_per_batch", "loop_cpu_pct", "serving_threads_cpu_pct",
)
CELLS = {"zipf10m.geb-frames": "", "upstream-node.grpc-pairs": ".rate"}


def test_the_fourteen_metric_files_agree_with_benchmark_json():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell, suffix in CELLS.items():
        for base in NEW_METRICS:
            name = base + suffix
            spec = json.loads(
                (BENCH / "layer_metrics" / f"{name}.json").read_text()
            )
            entry = entries[name]
            for key in ("layer", "unit", "moves", "source"):
                assert spec[key] == entry[key], (name, key)
            assert spec["cells"] == entry["workloads"] == [cell]
            assert cell in e2e[entry["moves"]]["workloads"]
            assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
            assert spec["reader"] in ("stages", "prom_sum")  # no new reader
            named = spec.get("sum_total_s", []) + [
                spec.get("per_count_of", "batch_e2e")
            ]
            assert set(named) <= set(PER_BATCH), name
    # cells 3-6 hold their files to set equality in benchmark/tests
    assert not [
        n for n in entries
        if n.split(".")[0] in NEW_METRICS
        and n.endswith((".mesh4", ".x100m", ".node", ".peer"))
    ]


@pytest.fixture(scope="module")
def rehearsal_checkout(tmp_path_factory):
    """The benchmark's files beside the program, the two
    configurations cut to a CPU's size in the COPY (as
    benchmark/tests/test_stage_metrics.py cuts them): the cells keep
    their names, so every per-layer metric that names them is read."""
    root = tmp_path_factory.mktemp("rehearsal")
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "gubernator_tpu", root / "gubernator_tpu")

    def edit(rel, **changes):
        path = root / "benchmark" / rel
        obj = json.loads(path.read_text())
        for key, value in changes.items():
            obj[key] = dict(obj[key], **value) if isinstance(value, dict) else value
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


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_traced_rehearsal_reads_the_cells_seven(rehearsal_checkout, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         str(2**31 + 36), "--seconds", "4", "--trace", "1"],
        cwd=rehearsal_checkout, env=env, capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 3, p.stderr[-2000:]  # a rehearsal: not a chip run
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0
    trace = next(x for x in lines if x.get("phase") == "trace")
    read_ = set(trace["layer_metrics_read"])
    mine = {base + CELLS[cell] for base in NEW_METRICS}
    assert mine <= read_, sorted(mine - read_)
    other = {
        base + suffix for base in NEW_METRICS
        for c, suffix in CELLS.items() if c != cell
    }
    assert not other & read_
