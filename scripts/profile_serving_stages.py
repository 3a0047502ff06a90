"""Stage-attribution profile of the served (edge) pipeline — r7.

Boots the single-node serving stack (device backend + edge bridge +
the compiled guber-edge front door), drives concurrent 1000-item
batches through the edge gRPC door, and reports WHERE a served
decision's wall time went, from the stage clock the serving path now
carries (serve/stages.py): edge->bridge transit (windowed frames stamp
CLOCK_MONOTONIC at send), frame decode, batcher queue, device span
(with the submit/fetch interior split), response encode — plus the
coverage of those stages against frame end-to-end time.

The snapshot is pulled over HTTP from `/v1/debug/stages` — the same
surface an operator scrapes in production (`?reset=1` scopes the
measurement window) — so this script also e2e-tests that endpoint.

Usage:
  python scripts/profile_serving_stages.py [--seconds 10] [--json OUT]

The --json artifact (BENCH_STAGES_r<N>.json) is the decomposition the
next optimisation round starts from; gen_readme_tables and
docs/p99_breakdown.md read from it.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

HTTP_ADDR = "127.0.0.1:29761"
GRPC_ADDR = "127.0.0.1:29760"
EDGE_PORT = 29764
EDGE_GRPC_PORT = 29765
SOCK = "/tmp/guber-profile-stages.sock"
EDGE_BIN = ROOT / "gubernator_tpu" / "native" / "edge" / "guber-edge"


def _get(path: str) -> dict:
    with urllib.request.urlopen(
        f"http://{HTTP_ADDR}{path}", timeout=10
    ) as r:
        return json.loads(r.read())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--batch-items", type=int, default=1000)
    ap.add_argument(
        "--device-batch-limit",
        type=int,
        default=int(os.environ.get("GUBER_DEVICE_BATCH_LIMIT", "8192")),
        help="co-batch depth (the ladder compiles to it at warmup)",
    )
    ap.add_argument("--json", default="", help="write the artifact here")
    args = ap.parse_args()

    if not EDGE_BIN.exists():
        print(
            "edge binary missing; make -C gubernator_tpu/native/edge",
            file=sys.stderr,
        )
        return 1

    import jax

    from gubernator_tpu.jaxenv import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import grpc

    from gubernator_tpu.api.grpc_glue import V1Stub
    from gubernator_tpu.api.proto.gen import gubernator_pb2
    from gubernator_tpu.cluster import LocalCluster
    from gubernator_tpu.core.engine import buckets_for_limit
    from gubernator_tpu.core.store import StoreConfig
    from gubernator_tpu.serve.backends import TpuBackend

    cluster = LocalCluster(
        [GRPC_ADDR],
        backend_factory=lambda: TpuBackend(
            StoreConfig(rows=16, slots=1 << 12),
            buckets=buckets_for_limit(args.device_batch_limit),
        ),
        http_addresses=[HTTP_ADDR],
        device_batch_limit=args.device_batch_limit,
    )
    print("starting serving stack (device warmup)...", file=sys.stderr)
    cluster.start(timeout=600)

    async def attach(server, sock):
        from gubernator_tpu.serve.edge_bridge import EdgeBridge

        bridge = EdgeBridge(server.instance, sock)
        await bridge.start()
        return bridge

    pathlib.Path(SOCK).unlink(missing_ok=True)
    bridge = cluster.run(attach(cluster.servers[0], SOCK))
    edge = subprocess.Popen(
        [str(EDGE_BIN), "--listen", str(EDGE_PORT), "--grpc-listen",
         str(EDGE_GRPC_PORT), "--backend", SOCK],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        import socket as sl

        deadline = time.monotonic() + 10
        while True:
            try:
                sl.create_connection(
                    ("127.0.0.1", EDGE_GRPC_PORT), timeout=1
                ).close()
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise RuntimeError("edge did not listen")
                time.sleep(0.05)

        req = gubernator_pb2.GetRateLimitsReq(
            requests=[
                gubernator_pb2.RateLimitReq(
                    name="stages", unique_key=f"k{i}", hits=1,
                    limit=1_000_000_000, duration=60_000,
                )
                for i in range(args.batch_items)
            ]
        )
        stubs = [
            V1Stub(
                grpc.insecure_channel(f"127.0.0.1:{EDGE_GRPC_PORT}")
            )
            for _ in range(args.workers)
        ]
        for s in stubs:
            s.GetRateLimits(req)  # warm channels + ladder

        # scope the measurement window via the production endpoint
        _get("/v1/debug/stages?reset=1")

        stop = time.monotonic() + args.seconds
        counts = [0] * args.workers

        def worker(w):
            while time.monotonic() < stop:
                stubs[w].GetRateLimits(req)
                counts[w] += 1

        threads = [
            threading.Thread(target=worker, args=(w,))
            for w in range(args.workers)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        snap = _get("/v1/debug/stages")

        n = sum(counts)
        dec_s = n * args.batch_items / wall
        print(
            f"\n{n} batches in {wall:.1f}s = {dec_s:,.0f} decisions/s "
            f"({args.workers} workers x {args.batch_items}-item frames "
            f"through the edge gRPC door)"
        )
        print(
            f"\n{'stage':16s} {'total_s':>9} {'count':>7} "
            f"{'mean_ms':>9}  family"
        )
        fam = {
            s: "per-frame" for s in snap.get("per_frame_stages", [])
        }
        fam.update(
            {s: "per-batch" for s in snap.get("per_batch_stages", [])}
        )
        fam.update(
            {s: "per-call" for s in snap.get("per_call_stages", [])}
        )
        for name, s in snap["stages"].items():
            print(
                f"{name:16s} {s['total_s']:9.2f} {s['count']:7d} "
                f"{s['mean_ms']:9.2f}  {fam.get(name, '?')}"
            )
        print(
            f"\nframes={snap['frames']} "
            f"frame_e2e_total_s={snap['frame_e2e_total_s']} "
            f"attributed_total_s={snap['attributed_total_s']} "
            f"coverage={snap['coverage']:.1%}"
        )

        if args.json:
            doc = {
                "schema": "bench_stages_r7",
                "scope": (
                    "single-node serving stack on this host's CPU "
                    "(JAX_PLATFORMS governs the backend device); "
                    f"{args.workers} workers x {args.batch_items}-item "
                    "batches through the compiled edge gRPC door, "
                    "windowed GEB7 frames end-to-end. Stage spans from "
                    "serve/stages.py via /v1/debug/stages; per-frame "
                    "stages tile one frame's e2e span (send stamp -> "
                    "response written), per-batch stages split the "
                    "device span's interior."
                ),
                "host_cpus": os.cpu_count(),
                "seconds": args.seconds,
                "workers": args.workers,
                "batch_items": args.batch_items,
                "device_batch_limit": args.device_batch_limit,
                "decisions_per_sec": round(dec_s, 1),
                "snapshot": snap,
            }
            pathlib.Path(args.json).write_text(
                json.dumps(doc, indent=1) + "\n"
            )
            print(f"wrote {args.json}", file=sys.stderr)
        return 0
    finally:
        edge.kill()
        try:
            cluster.run(bridge.stop())
        except Exception:
            pass
        cluster.stop()
        pathlib.Path(SOCK).unlink(missing_ok=True)


if __name__ == "__main__":
    sys.exit(main())
