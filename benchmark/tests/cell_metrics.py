"""One cell's per-layer metrics, looked up by the cell's NAME: the
entries of BENCHMARK.json whose `workloads` name it = the files under
layer_metrics/ whose `cells` name it = what a traced run of it reads. A
reading is one file with its cells as a list, so nothing here counts
files or entries and nothing tells a cell by a suffix."""

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
#: readers that find nothing on the CPU: it has no device plane for the
#: trace's three, and its backend reports no memory peak for `gauge`
NOT_ON_A_CPU = {"trace", "roofline", "roofline_sharded", "gauge"}


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def spec(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def all_files() -> dict:
    """{metric name: its file's spec} for every file there is."""
    return {f[:-5]: spec(f[:-5])
            for f in sorted(os.listdir(os.path.join(BENCH, "layer_metrics")))}


def declared(cell: str) -> dict:
    """{name: entry} of the per_layer entries that list the cell."""
    return {m["name"]: m for m in bench()["per_layer"] if cell in m["workloads"]}


def files(cell: str) -> dict:
    """{name: spec} of the metric files that list the cell."""
    return {n: s for n, s in all_files().items() if cell in s["cells"]}


def rehearsed(cell: str) -> set:
    """The names a traced CPU rehearsal of the cell must read: all of
    the cell's but those whose reader needs the device."""
    return {n for n, s in files(cell).items() if s["reader"] not in NOT_ON_A_CPU}


def held_together(cell: str) -> dict:
    """The cell's files, after holding them to BENCHMARK.json: the same
    names on both sides and each entry's `workloads` its file's `cells`
    (test_benchmark_json.py holds every file's other keys, its reader
    and the end-to-end metric it moves)."""
    mine, entries = files(cell), declared(cell)
    assert set(mine) == set(entries), set(mine) ^ set(entries)
    for name, s in mine.items():
        assert entries[name]["workloads"] == s["cells"], name
    return mine
