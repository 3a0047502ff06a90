"""Distributed-tracing overhead A/B: decisions/s OFF vs sampled — r16.

Replays the keyspace-30k zipf GEB workload (the shard_r14/trace_r16
perf-gate shape) against one resident serving stack with the tracer
flipped between INTERLEAVED short rounds: A = tracing fully off (the
shipped default — every instrumented site pays one branch), B =
GUBER_TRACE_SAMPLE at --sample (default 0.01, the documented
production setting). Load is generated OUT of process
(`cli.loadgen --protocol geb`; in-process clients thrash the serving
GIL), rounds alternate within-pair order, and the paired per-round
ratio is the drift-robust headline (the r9 methodology) — the number
the `trace_r16` perf-gate pair then guards against decay.

The run also sanity-checks the feature actually engaged: the flight
recorder must have retained traces after the sampled rounds, and a
retained trace must carry a device span with batch/rung annotations
(a gate that measured an accidentally-disabled tracer would "pass"
forever).

Usage:
  python scripts/profile_trace.py [--seconds 3] [--rounds 6]
      [--sample 0.01] [--json BENCH_TRACE_r16.json]
  make profile-trace
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

SOCK = "/tmp/guber-profile-trace.sock"


def _loadgen(seconds: float, concurrency: int, batch: int) -> float:
    args = [
        sys.executable, "-m", "gubernator_tpu.cli.loadgen", SOCK,
        "--protocol", "geb", "--duration", str(seconds),
        "--share", "0.0", "--concurrency", str(concurrency),
        "--batch", str(batch), "--keyspace", "30000", "--json",
    ]
    out = subprocess.run(
        args, capture_output=True, text=True, timeout=seconds + 120,
        cwd=ROOT, env=dict(os.environ, PYTHONPATH=str(ROOT)),
    )
    if out.returncode != 0:
        raise RuntimeError(f"loadgen failed: {out.stderr[-800:]}")
    r = json.loads(out.stdout.strip().splitlines()[-1])
    if r["errors"]:
        raise RuntimeError(f"loadgen saw {r['errors']} errors")
    return r["decisions_per_sec"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--sample", type=float, default=0.01,
                    help="GUBER_TRACE_SAMPLE for the ON side")
    ap.add_argument("--concurrency", type=int, default=24)
    ap.add_argument("--batch", type=int, default=1000)
    ap.add_argument(
        "--device-batch-limit", type=int,
        default=int(os.environ.get("GUBER_DEVICE_BATCH_LIMIT", "8192")),
    )
    ap.add_argument("--json", default="", help="artifact path")
    args = ap.parse_args()

    import jax

    from gubernator_tpu.jaxenv import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from gubernator_tpu.cluster import LocalCluster
    from gubernator_tpu.core.engine import buckets_for_limit
    from gubernator_tpu.core.store import StoreConfig
    from gubernator_tpu.serve.backends import TpuBackend
    from gubernator_tpu.serve.edge_bridge import EdgeBridge

    cluster = LocalCluster(
        ["127.0.0.1:29891"],
        backend_factory=lambda: TpuBackend(
            StoreConfig(rows=16, slots=1 << 12),
            buckets=buckets_for_limit(args.device_batch_limit),
        ),
        device_batch_limit=args.device_batch_limit,
    )
    print("profile-trace: starting serving stack (device warmup)...",
          file=sys.stderr)
    cluster.start(timeout=600)
    pathlib.Path(SOCK).unlink(missing_ok=True)
    instance = cluster.servers[0].instance
    tracer = instance.tracer

    async def attach():
        bridge = EdgeBridge(instance, SOCK)
        await bridge.start()
        return bridge

    bridge = cluster.run(attach())

    def flip(p: float):
        async def f():
            tracer.sample = p

        cluster.run(f())

    rows = []
    try:
        # warm both modes
        for p in (0.0, args.sample):
            flip(p)
            _loadgen(min(2.0, args.seconds), args.concurrency,
                     args.batch)
        flip(0.0)
        ratios = []
        for rnd in range(args.rounds):
            order = (
                ("off", "on") if rnd % 2 == 0 else ("on", "off")
            )
            rates = {}
            for which in order:
                flip(args.sample if which == "on" else 0.0)
                rates[which] = _loadgen(
                    args.seconds, args.concurrency, args.batch
                )
            flip(0.0)
            ratio = rates["on"] / rates["off"]
            ratios.append(ratio)
            rows.append(dict(round=rnd, off=round(rates["off"], 1),
                             on=round(rates["on"], 1),
                             ratio=round(ratio, 4)))
            print(
                f"  round {rnd}: off {rates['off']:>11,.0f} "
                f"on {rates['on']:>11,.0f} dec/s  ratio {ratio:.3f}",
                file=sys.stderr,
            )
        snap = tracer.recorder.snapshot(limit=4)
        assert snap["counters"]["recorded"] > 0, (
            "sampled rounds retained no traces — the tracer never "
            "engaged and this measured nothing"
        )
        dev = [
            s
            for t in snap["traces"]
            for s in t["spans"]
            if s["name"] == "device"
        ]
        assert dev and "rung" in dev[-1].get("annotations", {}), (
            "retained traces carry no annotated device span"
        )
    finally:
        try:
            cluster.run(bridge.stop())
        except Exception:
            pass
        cluster.stop()
        pathlib.Path(SOCK).unlink(missing_ok=True)

    med = statistics.median(ratios)
    print(f"paired median ratio (on/off): {med:.4f}", file=sys.stderr)
    if args.json:
        doc = {
            "schema": "bench_trace_r16",
            "scope": (
                "single node, tpu backend on this host's CPU; "
                "keyspace-30k zipf GEB workload via out-of-process "
                "cli.loadgen on the bridge socket; INTERLEAVED paired "
                "rounds with alternating order, tracer flipped at "
                "runtime (A = tracing off, B = GUBER_TRACE_SAMPLE="
                f"{args.sample}). The paired median seeds/refreshes "
                "the trace_r16 perf-gate pair "
                "(PERF_GATE_BASELINE.json)."
            ),
            "host_cpus": os.cpu_count(),
            "seconds_per_round": args.seconds,
            "rounds": args.rounds,
            "sample": args.sample,
            "batch_items": args.batch,
            "concurrency": args.concurrency,
            "device_batch_limit": args.device_batch_limit,
            "env_knobs": {
                "GUBER_TRACE_SAMPLE": str(args.sample),
                "GUBER_TRACE_SLOW_MS": "0",
            },
            "paired_rounds": rows,
            "ratio_median_on_over_off": round(med, 4),
            "recorder_after_run": snap["counters"],
            "acceptance": {
                "target_max_paired_regression": 0.10,
                "met": med >= 0.90,
            },
        }
        pathlib.Path(args.json).write_text(
            json.dumps(doc, indent=1) + "\n"
        )
        print(f"wrote {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
