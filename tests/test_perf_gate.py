"""Perf-gate tests (r12, scripts/perf_gate.py + PERF_GATE_BASELINE.json).

Tier-1 part: the gate's comparison logic and the committed baseline
manifest's integrity (every guarded workload names an existing BENCH_*
artifact — the manifest is the map from gate workloads to the wins
they guard).

Slow part (deselected from tier-1): the gate end to end on the real
serving stack — it must PASS against a baseline it just measured, and
provably FAIL when a real per-frame delay is injected into the guarded
feature paths (`--inject-frame-ms`, the r8 fault injector).
"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _gate_module():
    spec = importlib.util.spec_from_file_location(
        "perf_gate", ROOT / "scripts" / "perf_gate.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_evaluate_gate_logic():
    ev = _gate_module().evaluate_gate
    baseline = {
        "workloads": {
            "a": {"committed": 2.0, "artifact": "X.json"},
            "b": {"committed": 1.0, "artifact": "Y.json"},
        }
    }
    # within threshold: pass (a dropped 5%, b improved)
    ok, rows = ev(baseline, {"a": 1.9, "b": 1.2}, 0.10)
    assert ok, rows
    # >10% regression on one workload: fail, and the row says which
    ok, rows = ev(baseline, {"a": 1.7, "b": 1.2}, 0.10)
    assert not ok
    bad = [r for r in rows if r["status"] == "FAIL"]
    assert [r["workload"] for r in bad] == ["a"]
    assert bad[0]["floor"] == pytest.approx(1.8)
    # exactly at the floor: pass (fail is strictly below)
    ok, _ = ev(baseline, {"a": 1.8, "b": 0.9}, 0.10)
    assert ok
    # a workload the gate stopped measuring must FAIL, not skip
    ok, rows = ev(baseline, {"a": 2.0}, 0.10)
    assert not ok
    assert any(
        r["workload"] == "b" and r["status"] == "FAIL" for r in rows
    )
    # a measured-but-unguarded workload is reported, never fails
    ok, rows = ev(baseline, {"a": 2.0, "b": 1.0, "new": 0.1}, 0.10)
    assert ok
    assert any(r["status"] == "unguarded" for r in rows)


def test_baseline_manifest_guards_the_committed_artifacts():
    manifest = json.loads(
        (ROOT / "PERF_GATE_BASELINE.json").read_text()
    )
    assert manifest["schema"] == "perf_gate_baseline_r12"
    wl = manifest["workloads"]
    # the interior wins (incl. the r13 sketch pair) + the two
    # public-door ratios are guarded
    for name in (
        "shed_r10", "stages_r7", "sketch_r13",
        "shard_r14",
        "frontdoor_geb_over_grpc", "frontdoor_http_over_grpc",
    ):
        assert name in wl, f"workload {name} missing from the manifest"
        entry = wl[name]
        assert (ROOT / entry["artifact"]).exists(), (
            f"{name} cites a non-committed artifact "
            f"{entry['artifact']}"
        )
        assert entry["committed"] > 0
    # the acceptance headline is durable: the committed GEB-over-gRPC
    # paired ratio stays >= 2.5x even at the gate's failure floor
    assert wl["frontdoor_geb_over_grpc"]["committed"] * (
        1 - manifest["threshold_default"]
    ) >= 2.5


def test_frontdoor_artifact_headline():
    doc = json.loads((ROOT / "BENCH_FRONTDOOR_r12.json").read_text())
    assert doc["schema"] == "bench_frontdoor_r12"
    assert doc["acceptance"]["met"] is True
    assert doc["paired"]["geb_over_grpc"]["median"] >= 2.5
    assert doc["gate"]["passed"] is True
    lad = doc["ladder_median_decisions_per_sec"]
    assert lad["geb"] > lad["grpc"]
    assert lad["http"] > lad["grpc"]


@pytest.mark.slow
def test_perf_gate_end_to_end_and_injected_slowdown():
    """The full gate on the real stack, small settings: (1) measure a
    fresh baseline; (2) a clean run against it PASSES; (3) a run with
    a real injected per-frame delay in the guarded paths FAILS."""
    env = dict(os.environ, PYTHONPATH=str(ROOT), JAX_PLATFORMS="cpu")
    base = "/tmp/guber-perf-gate-test-baseline.json"
    art = "/tmp/guber-perf-gate-test-front.json"

    def run(*extra):
        return subprocess.run(
            [
                sys.executable, "scripts/perf_gate.py",
                "--seconds", "1", "--rounds", "2",
                "--device-batch-limit", "1024",
                "--concurrency", "8",
                "--baseline", base, *extra,
            ],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=580,
        )

    r = run("--update-baseline")
    assert r.returncode == 0, r.stderr[-3000:]

    # clean run: generous threshold (short rounds are noisier than the
    # shipped settings; the mechanism, not the margin, is under test)
    r = run("--threshold", "0.5", "--json", art)
    assert r.returncode == 0, r.stderr[-3000:]
    doc = json.loads(pathlib.Path(art).read_text())
    assert doc["gate"]["passed"] is True

    # injected regression: a real per-frame delay in the guarded
    # feature paths. Pipelining absorbs small delays (frames sleep
    # concurrently), so the self-test injects one far past the
    # absorption bound — the paired ratios must collapse below any
    # threshold and the gate has to fail loudly
    r = run("--threshold", "0.5", "--inject-frame-ms", "1000")
    assert r.returncode == 1, (
        f"gate passed despite the injected slowdown:\n{r.stderr[-3000:]}"
    )
    assert "FAIL" in r.stderr
