"""BENCHMARK.json and the files the harness finds by name say the same."""

import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load(*rel):
    with open(os.path.join(*rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    return load(ROOT, "BENCHMARK.json")


def test_configs_and_cells_have_their_files(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        f = load(ROOT, c["file"])
        assert f["source"] == c["source"] and len(c["source"]) <= 200
        assert f["reduced"] == c["reduced"] and f["name"] == c["name"]
    for w in bench["workloads"]:
        cell = load(BENCH, "cells", w["name"] + ".json")
        assert (cell["config"], cell["traffic"]) == (w["config"], w["traffic"])
        assert load(BENCH, "configs", w["config"] + ".json")["chips"] == w["chips"]
        load(BENCH, "traffic", w["traffic"] + ".json")
        assert len(w["why"]) <= 200 and NAME.match(w["name"])


def test_per_layer_metrics_have_their_readers(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))}
    assert files == {m["name"] for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        f = load(BENCH, "layer_metrics", m["name"] + ".json")
        for key in ("layer", "unit", "moves", "source"):
            assert f[key] == m[key], (m["name"], key)
        assert f["cells"] == m["workloads"] and set(f["cells"]) <= cells
        assert os.path.isfile(os.path.join(BENCH, "readers", f["reader"] + ".py"))
        # every cell that reports the metric reports what it moves
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        assert NAME.match(m["name"]) and len(m["unit"]) <= 16


def test_a_reading_is_one_file_with_its_cells_as_a_list(bench):
    """No two metric files say the same but for `cells` and `what`: a
    reading that several cells report is ONE file that lists them, and
    a suffixed name is left only where the spec differs (`moves`, a
    `node`, a reader of its own). So twins do not come back, and the
    128 entries `per_layer` may hold go to readings, not to cells."""
    by_spec = {}
    for name in sorted(os.listdir(os.path.join(BENCH, "layer_metrics"))):
        spec = load(BENCH, "layer_metrics", name)
        key = json.dumps({k: v for k, v in spec.items() if k not in ("cells", "what")},
                         sort_keys=True)
        by_spec.setdefault(key, []).append(name)
    assert not [names for names in by_spec.values() if len(names) > 1]
    names = [m["name"] for m in bench["per_layer"]]
    assert len(set(names)) == len(names) <= 128
    order = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        # a list in BENCHMARK.json's cell order, no cell twice
        assert m["workloads"] == sorted(set(m["workloads"]), key=order.index), m["name"]


def test_every_cell_reports_setup_and_one_more(bench):
    for w in bench["workloads"]:
        mine = [m["name"] for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in m["workloads"] for m in bench["per_layer"])
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
