"""/metrics endpoint contract (r16 satellite): scrape a LIVE daemon,
parse the Prometheus exposition text, and assert the documented metric
families are present with SANE label cardinality — the `peer` and
`stage` label sets must stay bounded by cluster membership and the
fixed stage list, never grow per-key or per-request.

The family list is derived from serve/metrics.py via the same AST
scanner the doc drift gate uses (scripts/check_metrics.py), so a newly
declared metric is automatically held to this contract too.
"""

import pathlib
import sys
import time
import urllib.request

from prometheus_client.parser import text_string_to_metric_families

from _util import free_ports
from gubernator_tpu.api.types import RateLimitReq
from gubernator_tpu.client import V1Client
from gubernator_tpu.cluster import LocalCluster

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _declared():
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import check_metrics
    finally:
        sys.path.pop(0)
    return check_metrics.declared_metrics()


def _scrape(http_port) -> dict:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{http_port}/metrics", timeout=10
    ) as r:
        text = r.read().decode()
    fams = {}
    for fam in text_string_to_metric_families(text):
        fams[fam.name] = fam
    return fams


def test_metrics_endpoint_families_and_label_cardinality():
    g1, g2, http = free_ports(3)
    addrs = [f"127.0.0.1:{g1}", f"127.0.0.1:{g2}"]
    cluster = LocalCluster(
        addrs,
        http_addresses=[f"127.0.0.1:{http}", ""],
        trace_sample=1.0,  # exercise the trace counters too
    )
    cluster.start()
    try:
        # drive real traffic: owned + forwarded keys through the gRPC
        # door so per-peer series and the device/stage paths populate
        with V1Client(addrs[0]) as client:
            for i in range(30):
                resps = client.get_rate_limits(
                    [
                        RateLimitReq(
                            name="m", unique_key=f"mk{i}", hits=1,
                            limit=100, duration=60_000,
                        )
                    ],
                    timeout=10,
                )
                assert not resps[0].error
        time.sleep(0.1)
        fams = _scrape(http)

        # prometheus_client strips the _total suffix into family
        # names; accept either spelling like the doc gate does
        present = set(fams)
        for name in _declared():
            base = name[:-6] if name.endswith("_total") else name
            # label-carrying families only exist once a label value
            # was observed; the always-set and traffic-driven ones
            # must be there
            if name in (
                "grpc_request_counts",
                "grpc_request_duration_milliseconds",
                "cache_access_count",
                "device_batch_size",
                "device_launch_milliseconds",
                "distinct_keys_estimate",
                "serving_stage_seconds_total",
                "serving_stage_samples_total",
                "thread_wall_seconds_total",
                "batcher_queue_depth",
                "batcher_queue_oldest_age_seconds",
                "prep_pool_backlog",
                "shed_hits_total",
                "shed_lookups_total",
                "shed_entries",
                "traces_started_total",
                "traces_recorded_total",
                "traces_tail_captured_total",
                "traces_dropped_total",
                "trace_slow_threshold_ms",
                "cache_size",
                "drain_duration_seconds",
                "peer_breaker_state",
            ):
                assert base in present or name in present, (
                    name, sorted(present),
                )

        # traffic really flowed through the metered doors
        grpc_counts = {
            tuple(sorted(s.labels.items())): s.value
            for s in fams["grpc_request_counts"].samples
        }
        assert sum(grpc_counts.values()) >= 30

        # bounded `peer` label set: THIS cluster's members are present,
        # and every series is labelled by a peer ADDRESS (host:port) —
        # never a per-key or per-request value. (The registry is
        # process-global, so a full-suite run legitimately carries
        # other tests' cluster addresses too.)
        import re

        for fam_name in ("peer_breaker_state",):
            if fam_name in fams:
                peers = {
                    s.labels["peer"] for s in fams[fam_name].samples
                }
                assert set(addrs) <= peers, (peers, addrs)
                assert all(
                    re.fullmatch(r"[\w.\-]+:\d{1,5}", p) for p in peers
                ), peers

        # bounded `stage` label set: exactly the stage clock's names
        from gubernator_tpu.serve.stages import (
            PER_BATCH,
            PER_CALL,
            PER_FLUSH,
            PER_FORWARD,
            PER_FRAME,
        )

        known = (
            set(PER_FRAME) | set(PER_BATCH) | set(PER_CALL)
            | set(PER_FLUSH) | set(PER_FORWARD)
        )
        stages = {
            s.labels["stage"]
            for s in fams["serving_stage_seconds_total"].samples
        }
        assert stages <= known, stages
        assert "instance_route" in stages  # traffic populated it

        # bounded `thread` label set (PR 36): the five roles, fixed —
        # never a thread's name or id (absent only where the host has
        # no thread CPU clock, and then the whole family is)
        from gubernator_tpu.serve.stages import THREAD_ROLES, ThreadClocks

        if ThreadClocks().source != "none":
            threads = {
                s.labels["thread"]
                for s in fams["thread_cpu_seconds_total"].samples
            }
            assert threads == set(THREAD_ROLES) and len(threads) == 5
            wall = next(
                s.value for s in fams["thread_wall_seconds_total"].samples
            )
            assert wall > 0

        # trace counters moved (trace_sample=1.0 on every node)
        started = next(
            s.value for s in fams["traces_started_total"].samples
        )
        assert started >= 30
    finally:
        cluster.stop()
