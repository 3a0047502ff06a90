"""What PR 26 added to the yardstick for `global-mesh4.geb-frames-global`
(CPU, by hand: `pytest benchmark/tests/test_global_mesh4.py`): the
generator's GLOBAL pick by key id, its GLOBAL canaries, the two new
readers on hand-worked numbers, the one-node GLOBAL reference against
the program's oracle, and a traced rehearsal of the cell at a CPU's size
that finds every metric of the cell with a program-side source read
(the cell's metrics = the `BENCHMARK.json` entries that list it = the
files that list it: a `.mesh4` suffix is left only where the spec is
the mesh's own).
"""

import json
import os
import random
import shutil
import subprocess
import sys

import numpy as np
import pytest

import cell_metrics
import kernel_bytes
import reference_global
from generators import closed_loop_frames_global as gen
from harness import keyspace
from readers import prom_sum, roofline_sharded

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "global-mesh4.geb-frames-global"


def load(*rel):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


TRAFFIC = load("traffic", "geb-frames-global.json")
SHARE = TRAFFIC["global_share"]


# -- the generator ------------------------------------------------------------


def test_the_mix_is_geb_frames_plus_the_global_share():
    plain = load("traffic", "geb-frames.json")
    differ = {k for k in set(plain) | set(TRAFFIC) if plain.get(k) != TRAFFIC.get(k)}
    assert differ == {"generator", "why", "base_seed", "assumed", "global_share"}
    assert SHARE == 0.10 and TRAFFIC["generator"] == "closed_loop_frames_global"


@pytest.mark.parametrize("start", [0, 1, 37, 99, 100, 4242, 9_999_900])
def test_global_share_is_exact_over_any_100_consecutive_ids(start):
    ids = np.arange(start, start + 100)
    assert gen.is_global(ids, SHARE).sum() == 10
    assert gen.is_global(ids, 0.25).sum() == 25


def test_the_zipf_head_holds_both_kinds_and_the_pick_is_independent():
    head = gen.is_global(np.arange(1, 11), SHARE)
    assert head.any() and not head.all()
    assert np.flatnonzero(head).tolist() == [7]  # id 8
    # independent of the class: every limit class holds exactly its 10%
    # of GLOBAL ids; of the algorithm: each holds 10% to within a point
    ids = np.arange(1, 10_001)
    limit, _, algo = keyspace.KeyRules(TRAFFIC).of(ids)
    g = gen.is_global(ids, SHARE)
    for cls in TRAFFIC["key_classes"]:
        mine = limit == cls["limit"]
        assert mine.sum() == round(cls["share"] * 10_000)
        assert g[mine].sum() == round(cls["share"] * 1_000), cls
    for a in (0, 1):
        assert abs(g[algo == a].mean() - SHARE) < 0.01, a


def test_requests_carry_the_behaviour_of_their_id():
    from gubernator_tpu.api.types import Behavior

    rules = keyspace.KeyRules(TRAFFIC)
    ids = np.arange(1, 201)
    reqs = gen.global_reqs("t", ids, rules, 1, SHARE)
    plain = keyspace.make_reqs("t", ids, rules, 1)
    g = gen.is_global(ids, SHARE)
    for r, p, is_g in zip(reqs, plain, g.tolist()):
        assert r.behavior == (Behavior.GLOBAL if is_g else Behavior.BATCHING)
        r.behavior = p.behavior
        assert r == p  # nothing else differs from the plain mix's request


def test_every_second_canary_is_global():
    from gubernator_tpu.api.types import Behavior

    spec = {"traffic": TRAFFIC, "seed": 2**31 + 5, "worker": 2}
    tally = gen.Tally(spec, np.arange(1, 50))
    kinds = [tally.canary_req(j).behavior for j in range(len(tally.canaries))]
    assert kinds == [Behavior.BATCHING, Behavior.GLOBAL] * 3
    for j, (key, limit, algo) in enumerate(tally.canaries):
        r = tally.canary_req(j)
        assert (r.unique_key, r.limit, int(r.algorithm), r.hits) == (key, limit, algo, 1)


# -- the readers --------------------------------------------------------------


def test_roofline_of_one_shard_by_hand():
    store = {"ways": 16, "rows": 262144, "entry_bytes": 32, "shards": 4}
    # 600 items a device batch -> 150 a shard; a bucket row is 16 x 32 =
    # 512 B, read and written back: 150 x 1024 = 153,600 B; request and
    # response arrays 150 x (36 + 28) = 9,600 B; nothing sketched
    need = 153_600 + 9_600
    assert kernel_bytes.decide_step_bytes(150, store) == need
    got = roofline_sharded.shard_share_pct(
        kernel_bytes.decide_step_bytes, 600, 0.0, store, 500e-6, 819e9)
    assert got == pytest.approx(100 * (need / 819e9) / 500e-6)
    assert got == pytest.approx(0.03985, rel=1e-3)
    # sketched items add 2 rows x 4 B, read and written: 40 a batch -> 10
    # a shard x 16 B = 160 B
    more = roofline_sharded.shard_share_pct(
        kernel_bytes.decide_step_bytes, 600, 40.0, store, 500e-6, 819e9)
    assert more == pytest.approx(100 * ((need + 160) / 819e9) / 500e-6)
    # the flat reader's arithmetic on the same step flatters fourfold
    flat = kernel_bytes.roofline_share_pct(
        kernel_bytes.decide_step_bytes(600, store), 500e-6, 819e9)
    assert flat == pytest.approx(4 * got)


def trace_ctx(step_s, executions, batches, items, kind="TPU v5 lite"):
    return {
        "trace": {"stand_in": False,
                  "step": {"executions": executions, "seconds": step_s * executions}},
        "prom0": {}, "prom1": {"device_batch_size_count": batches,
                               "device_batch_size_sum": items},
        "device_kind": kind, "config": load("configs", "global-mesh4.json"),
    }


def test_roofline_sharded_reads_the_configuration_and_the_trace():
    spec = load("layer_metrics", "decide_roofline.mesh4.json")
    # four planes x 100 executions of 500 us; 100 batches of 600 items
    got = roofline_sharded.read(spec, trace_ctx(500e-6, 400, 100, 60_000))
    assert got == pytest.approx(0.03985, rel=1e-3)
    none = trace_ctx(500e-6, 400, 0, 0)
    assert roofline_sharded.read(spec, none) is None  # no batch in the window
    stand_in = trace_ctx(500e-6, 400, 100, 60_000)
    stand_in["trace"]["stand_in"] = True
    assert roofline_sharded.read(spec, stand_in) is None  # the CPU rehearsal
    with pytest.raises(KeyError):
        roofline_sharded.read(spec, trace_ctx(500e-6, 400, 100, 60_000, "TPU v9"))


def test_prom_sum_shares_and_what_a_parent_reads():
    spec = load("layer_metrics", "object_path_items_pct.mesh4.json")
    before = {"edge_object_items_total": 100.0, "edge_fast_items_total": 1000.0,
              "edge_folded_items_total": 0.0}
    after = {"edge_object_items_total": 9100.0, "edge_fast_items_total": 2000.0,
             "edge_folded_items_total": 0.0}
    assert prom_sum.read(spec, {"prom0": before, "prom1": after}) == 90.0
    assert prom_sum.read(spec, {"prom0": after, "prom1": after}) is None
    # the parent of PR 26 exports no edge_object_items_total: nothing
    parent = {k: v for k, v in after.items() if k != "edge_object_items_total"}
    assert prom_sum.read(spec, {"prom0": parent, "prom1": parent}) is None
    skew = load("layer_metrics", "shard_skew_pct.mesh4.json")
    ctx = {"prom0": {}, "prom1": {"mesh_shard_max_rows_total": 385.0,
                                  "mesh_shard_rows_total": 1000.0}}
    assert prom_sum.read(skew, ctx) == pytest.approx(154.0)
    assert prom_sum.read(skew, {"prom0": {}, "prom1": {}}) is None


def test_every_metric_of_the_cell_is_an_entry_a_file_and_a_reader():
    mine = cell_metrics.held_together(CELL)
    assert all(s["moves"] == "decisions_per_s" for s in mine.values())
    # the sharded roofline has a reader of its own (the flat one would
    # flatter fourfold); step and idle share are the flat cells' files
    assert mine["decide_roofline.mesh4"]["reader"] == "roofline_sharded"
    assert "decide_roofline" not in mine
    for name in ("decide_step_us", "device_idle_share", "shed_us_per_frame",
                 "submit_host_us_per_batch", "jit_call_us_per_batch",
                 "dispatch_us_per_batch", "door_codec_us_per_frame"):
        assert "zipf10m.geb-frames" in mine[name]["cells"], name
        assert CELL.split(".")[0] in mine[name]["what"], name  # its own sentence
    # a suffix only where the file is this cell's alone
    assert all(s["cells"] == [CELL] for n, s in mine.items() if n.endswith(".mesh4"))


# -- the reference ------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
def test_one_node_global_reference_equals_the_oracle(seed):
    """An owner decides a GLOBAL item as the oracle decides any item;
    broadcasts peek every marked key, move no window with the clock
    still and, with no peer, send nothing. With two peers each peeked
    status goes to both."""
    from gubernator_tpu.api.types import Algorithm, RateLimitReq
    from gubernator_tpu.core import oracle
    from gubernator_tpu.core.cache import LRUCache

    rng = random.Random(seed)
    node, cache = reference_global.OwnerNode(peers=seed % 2 * 2), LRUCache(100_000)
    now, marked = 1_700_000_000_000, set()
    for step in range(4000):
        if rng.random() < 0.3:
            now += rng.choice((1, 3, 50, 400, 2500))
        kid = rng.randrange(40)
        algo, limit, dur = kid % 2, (10, 100)[kid % 3 == 0], (1000, 5000)[kid % 4 == 0]
        behavior = reference_global.GLOBAL if kid % 10 == 3 else reference_global.BATCHING
        hits = rng.choice((0, 1, 1, 1, 2, 7, 12))
        o = oracle.get_rate_limit(cache, RateLimitReq(
            name="n", unique_key=f"k{kid}", hits=hits, limit=limit,
            duration=dur, algorithm=Algorithm(algo)), now)
        got = node.decide(f"k{kid}", hits, limit, dur, algo, behavior, now)
        assert got == (int(o.status), o.limit, o.remaining, o.reset_time)
        if behavior == reference_global.GLOBAL:
            marked.add(f"k{kid}")
        if step % 50 == 49:
            assert set(node.marked) == marked
            held, sent = node.windows(), len(node.sent)
            # a token bucket never moves under a peek; a leaky one takes
            # the leak any request would apply, so compare those after
            # the oracle has been peeked at the same instant
            statuses = node.broadcast(now)
            assert set(statuses) == marked and not node.marked
            for key in sorted(marked):
                kid_ = int(key[1:])
                o = oracle.get_rate_limit(cache, RateLimitReq(
                    name="n", unique_key=key, hits=0,
                    limit=(10, 100)[kid_ % 3 == 0],
                    duration=(1000, 5000)[kid_ % 4 == 0],
                    algorithm=Algorithm(kid_ % 2)), now)
                assert statuses[key] == (int(o.status), o.limit, o.remaining)
            tokens = {k for k, w in held.items() if w[0] == 0 and k in node.windows()}
            assert all(node.windows()[k] == held[k] for k in tokens)
            assert len(node.sent) - sent == node.peers * len(marked)
            marked = set()
    assert node.peeks > 0


# -- the cell, rehearsed ------------------------------------------------------


def test_a_traced_rehearsal_reads_the_program_side_metrics(tmp_path):
    """The cell at a CPU's size (tiny store, two workers, 200-item
    frames) on four simulated devices: exit 3 (`rehearsal`), correct,
    and every metric of the cell that reads a span or a counter is
    read; the three that read the device trace need a device plane."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "gubernator_tpu"), root / "gubernator_tpu")

    def edit(rel, **changes):
        path = root / "benchmark" / rel
        obj = json.loads(path.read_text())
        for key, value in changes.items():
            obj[key] = dict(obj[key], **value) if isinstance(value, dict) else value
        path.write_text(json.dumps(obj))

    edit("configs/global-mesh4.json",
         env={"GUBER_STORE_TARGET_KEYS": "20000", "GUBER_SKETCH_MIB": "1"},
         key_population=5000, preload_keys=5000)
    edit("traffic/geb-frames-global.json", workers=2, inflight=4,
         items_per_frame=200, warmup_s=1.0)
    edit(f"cells/{CELL}.json", trace_ms=500)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 26), "--seconds", "4", "--trace", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 3, p.stderr[-2000:]
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.startswith("{")]
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["rehearsal"] == "cpu" and last["device"]["count"] == 4
    window = next(x for x in lines if x.get("phase") == "window")
    assert window["generator"]["frames_without_global_pct"] < 5.0
    assert 4.0 < window["generator"]["global_items_pct"] < 12.0
    trace = next(x for x in lines if x.get("phase") == "trace")
    want = cell_metrics.rehearsed(CELL)
    assert set(trace["layer_metrics_read"]) == want and want
    assert set(cell_metrics.files(CELL)) - want == {
        "decide_step_us", "device_idle_share", "decide_roofline.mesh4"}
