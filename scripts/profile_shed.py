"""Over-limit shed cache A/B: decisions/s OFF vs ON across skew — r10.

Drives the over-limit-heavy `bench_serving` shed workload (hot
limit-1 keys frozen over limit, mixed with never-over keys at a
controlled share) at the BRIDGE TIER: the out-of-process loadgen
speaks the edge wire protocol itself — windowed pre-hashed GEB7
frames on the bridge socket, exactly what the compiled edge ships —
so the screen under test is serve/edge_bridge.py _decide_arrays_shed
in front of the device batcher. Speaking GEB7 directly (numpy record
frames, no protobuf) keeps the CLIENT off the critical path: through
the edge's gRPC door this box's ceiling is the loadgen's own
per-item protobuf work (~110k dec/s regardless of serving-side
changes — the r7 any-protocol ceiling), which would mask the device
work the shed removes.

Methodology is the r9 paired-rounds recipe: load generated OUT of
process (in-process client threads thrash the serving GIL), the shed
flipped at runtime between INTERLEAVED short OFF/ON rounds with
alternating within-round order (ambient throttling on a shared box
drifts on ~minute scales; paired per-round ratios cancel it), one
share series per target over-limit share so the paired win's
MONOTONICITY in skew is part of the artifact.

Usage:
  python scripts/profile_shed.py [--seconds 3] [--rounds 6]
                                 [--shares 0.0,0.5,0.9]
                                 [--json BENCH_SHED_r10.json]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
import time
import urllib.request

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

HTTP_ADDR = "127.0.0.1:29871"
GRPC_ADDR = "127.0.0.1:29870"
SOCK = "/tmp/guber-profile-shed.sock"

HOT, COLD = 512, 4096


def _get(path: str) -> dict:
    with urllib.request.urlopen(
        f"http://{HTTP_ADDR}{path}", timeout=10
    ) as r:
        return json.loads(r.read())


def _loadgen(args) -> int:
    """Child-process load generator speaking the bridge's own wire
    protocol: windowed pre-hashed GEB7 frames (what the compiled edge
    ships) over N connections to the bridge socket, `window` frames in
    flight each. One JSON line out: frames, items, measured over-limit
    share."""
    import asyncio
    import struct

    import numpy as np

    from gubernator_tpu.core.hashing import slot_hash_batch
    from gubernator_tpu.serve.edge_bridge import (
        MAGIC_WFAST_REQ,
        MAGIC_WFAST_RESP,
        _fast_dtypes,
    )

    req_dt, resp_dt = _fast_dtypes()
    cut = int(args.share * args.batch_items)

    def payloads():
        """Pre-built frame payload rotation: hot limit-1 rows up to
        the share cut, never-over rows after (the bench_serving shed
        workload shape, pre-hashed like edge.cc would)."""
        out = []
        for i in range(8):
            rec = np.zeros(args.batch_items, req_dt)
            hot = [
                f"shed_h{(i * 31 + j) % HOT}" for j in range(cut)
            ]
            cold = [
                f"shed_c{(i * args.batch_items + j) % COLD}"
                for j in range(cut, args.batch_items)
            ]
            rec["key_hash"] = slot_hash_batch(hot + cold)
            rec["hits"] = 1
            rec["limit"][:cut] = 1
            rec["limit"][cut:] = 1_000_000_000
            rec["duration"] = 600_000
            out.append(rec.tobytes())
        return out

    frames_done = [0]
    over = [0]
    items = [0]

    async def run_conn(cid: int, stop_at: float):
        reader, writer = await asyncio.open_unix_connection(SOCK)
        # hello: flags carry the credit window (flags >> 16)
        magic, flags, rhash, n_nodes = struct.unpack(
            "<IIII", await reader.readexactly(16)
        )
        for _ in range(n_nodes):
            _s, glen = struct.unpack(
                "<BH", await reader.readexactly(3)
            )
            await reader.readexactly(glen)
            (blen,) = struct.unpack(
                "<H", await reader.readexactly(2)
            )
            await reader.readexactly(blen)
        window = max(1, min(flags >> 16, 32))
        pls = payloads()
        sem = asyncio.Semaphore(window)
        n_rec = args.batch_items
        hdr = struct.Struct("<II")

        async def read_loop():
            while True:
                magic, n = hdr.unpack(await reader.readexactly(8))
                assert magic == MAGIC_WFAST_RESP, hex(magic)
                await reader.readexactly(4)  # frame id
                body = await reader.readexactly(n * resp_dt.itemsize)
                rec = np.frombuffer(body, dtype=resp_dt)
                frames_done[0] += 1
                items[0] += n
                over[0] += int((rec["status"] == 1).sum())
                sem.release()

        rt = asyncio.ensure_future(read_loop())
        fid = 0
        try:
            while time.monotonic() < stop_at:
                await sem.acquire()
                pl = pls[(cid + fid) % len(pls)]
                writer.write(
                    hdr.pack(MAGIC_WFAST_REQ, n_rec)
                    + struct.pack("<IIQ", fid + 1, rhash, 0)
                    + struct.pack("<I", len(pl))
                    + pl
                )
                await writer.drain()
                fid += 1
            # drain the window so every sent frame is counted
            for _ in range(window):
                await sem.acquire()
        finally:
            rt.cancel()
            writer.close()

    async def main_async():
        stop_at = time.monotonic() + args.seconds
        t0 = time.monotonic()
        await asyncio.gather(
            *[run_conn(c, stop_at) for c in range(args.conns)]
        )
        return time.monotonic() - t0

    elapsed = asyncio.run(main_async())
    print(json.dumps({
        "ops": frames_done[0],
        "items": items[0],
        "seconds": elapsed,
        "over_limit_share": (
            over[0] / items[0] if items[0] else 0.0
        ),
    }))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--seconds", type=float, default=3.0,
        help="per-mode window per round (short micro-rounds: paired "
        "adjacent OFF/ON medians beat long windows under ambient "
        "drift — the r9 methodology)",
    )
    ap.add_argument("--rounds", type=int, default=6,
                    help="interleaved OFF/ON pairs per share series")
    ap.add_argument("--conns", type=int, default=2,
                    help="loadgen bridge connections (each keeps the "
                    "advertised credit window of frames in flight)")
    ap.add_argument("--batch-items", type=int, default=1000)
    ap.add_argument("--shares", default="0.0,0.5,0.9",
                    help="target over-limit traffic shares, one "
                    "interleaved series each (monotonicity check)")
    ap.add_argument("--share", type=float, default=0.9,
                    help="internal: loadgen child's share")
    ap.add_argument(
        "--device-batch-limit", type=int,
        default=int(os.environ.get("GUBER_DEVICE_BATCH_LIMIT", "8192")),
    )
    ap.add_argument("--json", default="", help="write the artifact here")
    ap.add_argument("--loadgen", action="store_true",
                    help="internal: run as the load generator")
    args = ap.parse_args()
    if args.loadgen:
        return _loadgen(args)

    import jax

    from gubernator_tpu.jaxenv import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

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
    instance = cluster.servers[0].instance
    shed_obj = instance.shed
    assert shed_obj is not None, "boot the stack with GUBER_SHED_CACHE=1"
    try:
        def set_mode(on: bool):
            async def flip():
                instance.shed = shed_obj if on else None

            cluster.run(flip())

        def drive(share: float, seconds: float) -> dict:
            out = subprocess.run(
                [sys.executable, __file__, "--loadgen",
                 "--seconds", str(seconds),
                 "--conns", str(args.conns),
                 "--batch-items", str(args.batch_items),
                 "--share", str(share)],
                capture_output=True, text=True, timeout=seconds + 60,
            )
            if out.returncode != 0:
                raise RuntimeError(f"loadgen failed: {out.stderr[-500:]}")
            r = json.loads(out.stdout.strip().splitlines()[-1])
            r["decisions_per_sec"] = r["items"] / r["seconds"]
            return r

        shares = [float(s) for s in args.shares.split(",") if s.strip()]
        rows = []
        for share in shares:
            # per-share warm: freeze this share's hot pool over limit
            # and let both modes touch their code paths
            for on in (True, False):
                set_mode(on)
                drive(share, min(2.0, args.seconds))
            for rnd in range(args.rounds):
                order = (False, True) if rnd % 2 == 0 else (True, False)
                for on in order:
                    set_mode(on)
                    _get("/v1/debug/stages?reset=1")
                    r = drive(share, args.seconds)
                    snap = _get("/v1/debug/stages")
                    rows.append(
                        dict(
                            share=share,
                            round=rnd,
                            shed=on,
                            decisions_per_sec=round(
                                r["decisions_per_sec"], 1
                            ),
                            over_limit_share=round(
                                r["over_limit_share"], 4
                            ),
                            shed_cache=snap.get("shed_cache"),
                            stage_means_ms={
                                s: snap["stages"].get(s, {}).get(
                                    "mean_ms", 0.0
                                )
                                for s in ("shed", "device",
                                          "batch_queue")
                            },
                        )
                    )
                    print(
                        f"share {share:.2f} round {rnd} "
                        f"shed={'ON ' if on else 'OFF'} "
                        f"{r['decisions_per_sec']:>12,.0f} dec/s "
                        f"(over {r['over_limit_share']:.2f})",
                        file=sys.stderr,
                    )

        # paired per-round ratios per share (the drift-robust stat)
        series = {}
        for share in shares:
            by_round = {}
            for r in rows:
                if r["share"] == share:
                    by_round.setdefault(r["round"], {})[
                        "on" if r["shed"] else "off"
                    ] = r
            ratios = [
                p["on"]["decisions_per_sec"]
                / p["off"]["decisions_per_sec"]
                for p in by_round.values()
            ]
            series[share] = dict(
                paired_speedup=round(statistics.median(ratios), 4),
                ratios=[round(x, 4) for x in ratios],
                median_decisions_per_sec=dict(
                    off=statistics.median(
                        r["decisions_per_sec"] for r in rows
                        if r["share"] == share and not r["shed"]
                    ),
                    on=statistics.median(
                        r["decisions_per_sec"] for r in rows
                        if r["share"] == share and r["shed"]
                    ),
                ),
                median_over_limit_share=statistics.median(
                    r["over_limit_share"] for r in rows
                    if r["share"] == share
                ),
            )
        speedups = [series[s]["paired_speedup"] for s in shares]
        monotone = all(
            b >= a - 0.02 for a, b in zip(speedups, speedups[1:])
        )
        top = speedups[-1]
        for share in shares:
            s = series[share]
            print(
                f"share {share:.2f}: paired speedup "
                f"{s['paired_speedup']:.2f}x  "
                f"(OFF {s['median_decisions_per_sec']['off']:,.0f} -> "
                f"ON {s['median_decisions_per_sec']['on']:,.0f} dec/s)",
                file=sys.stderr,
            )
        print(
            f"monotone in over-limit share: {monotone}; top-share "
            f"speedup {top:.2f}x",
            file=sys.stderr,
        )

        if args.json:
            doc = {
                "schema": "bench_shed_r10",
                "scope": (
                    "single-node serving stack on this host's CPU; "
                    f"{args.conns} connections x "
                    f"{args.batch_items}-item windowed pre-hashed "
                    "GEB7 frames spoken DIRECTLY on the bridge socket "
                    "by an out-of-process loadgen (the compiled "
                    "edge's wire protocol, minus the edge binary — "
                    "through the edge's gRPC door this box ceilings "
                    "on the loadgen's own protobuf work at ~110k "
                    "dec/s in both modes, masking the serving-side "
                    "change under test). Workload: "
                    f"{HOT} hot limit-1 keys frozen over limit mixed "
                    f"with {COLD} never-over keys at each series' "
                    "target share (the bench_serving shed scenario's "
                    "shape). INTERLEAVED short OFF/ON rounds flip "
                    "instance.shed in-process (alternating order); "
                    "paired per-round ratios are the drift-robust "
                    "headline, per the r9 paired-rounds methodology."
                ),
                "host_cpus": os.cpu_count(),
                "seconds_per_round": args.seconds,
                "rounds_per_share": args.rounds,
                "conns": args.conns,
                "batch_items": args.batch_items,
                "device_batch_limit": args.device_batch_limit,
                "env_knobs": {
                    "GUBER_SHED_CACHE": "<flipped per round>",
                    "GUBER_SHED_CACHE_KEYS": str(shed_obj.capacity),
                    "GUBER_DEVICE_BATCH_LIMIT": str(
                        args.device_batch_limit
                    ),
                },
                "series": {str(k): v for k, v in series.items()},
                "paired_speedup_by_share": {
                    str(s): series[s]["paired_speedup"] for s in shares
                },
                "monotone_in_over_limit_share": monotone,
                "top_share_paired_speedup": top,
                "rows": rows,
            }
            pathlib.Path(args.json).write_text(
                json.dumps(doc, indent=1) + "\n"
            )
            print(f"wrote {args.json}", file=sys.stderr)
        return 0
    finally:
        try:
            cluster.run(bridge.stop())
        except Exception:
            pass
        cluster.stop()
        pathlib.Path(SOCK).unlink(missing_ok=True)


if __name__ == "__main__":
    sys.exit(main())
