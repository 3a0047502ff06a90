"""trace_reduce on traces whose busy intervals are known: the interval
arithmetic on plain lists, and the whole reduction on a small recorded
trace (tests/small_trace.textproto, an XSpace the profiler's own reader
parses)."""

import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_merge_and_gaps():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (20, 20)])
    assert merged == [[0, 3], [5, 8]]
    assert tr.busy_ns(merged) == 6
    assert tr.gaps(merged, 0, 10) == [(3, 5), (8, 10)]
    assert tr.gaps(merged, -2, 6) == [(-2, 0), (3, 5)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_program_name():
    assert tr.program_name("jit__decide_packed_sketch_jit(1234567)") == "jit__decide_packed_sketch_jit"
    assert tr.program_name("jit_f") == "jit_f"


def test_attribute_picks_the_most_specific_host_event():
    host = [("run_forever", 0.0, 1000.0), ("submit_call", 100.0, 200.0),
            ("tiny", 150.0, 10.0)]
    out = tr.attribute([(120.0, 280.0), (600.0, 700.0)], host)
    assert out == {"submit_call": pytest.approx(160e-9), "run_forever": pytest.approx(100e-9)}
    assert tr.attribute([(0.0, 50.0)], []) == {"unattributed": pytest.approx(50e-9)}


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "small_trace.textproto")) as f:
        pd = ProfileData.from_text_proto(f.read())
    return [
        {"name": p.name, "lines": [
            {"name": ln.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in ln.events]}
            for ln in p.lines]}
        for p in pd.planes
    ]


def test_small_recorded_trace(planes):
    # times in us. window: start_trace ends at 1,000, stop_trace begins at 11,000.
    # device 0 ops: [2000,4000) [3000,5000) [7000,8000) -> busy 4000
    # device 1 ops: [2000,4000)                          -> busy 2000
    out = tr.reduce_planes(planes, "decide")
    assert out["device_planes"] == ["/device:TPU:0", "/device:TPU:1"]
    assert not out["stand_in"]
    assert out["window_s"] == pytest.approx(10_000e-6)
    assert out["busy_s"] == pytest.approx(3000e-6)  # the mean over the chips
    assert out["step"] == {"executions": 3, "seconds": pytest.approx(6500e-6),
                           "programs": ["jit__decide_packed_sketch_jit"]}
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(6000e-6)]  # summed over the chips
    idle = dict(out["idle_gaps"])
    # device 0's longest gap [8000,11000) lies under fetch_wait
    assert idle["$batcher.py:900 fetch_wait"] >= 1500e-6 - 1e-9


def test_trace_without_device_plane_is_a_stand_in(planes):
    host_only = [p for p in planes if not p["name"].startswith("/device:")]
    host_only[0]["lines"].append({"name": "tf_XLAPjRtCpuClient/1", "events": [
        ("dot.1", 2_000_000.0, 1_000_000.0)]})
    out = tr.reduce_planes(host_only, "decide")
    assert out["stand_in"] and out["busy_s"] == pytest.approx(1000e-6)
