"""Continuous front-door perf gate + protocol ladder — r12.

Seven PRs of serving-tier wins (windowed frames r7, arrival prep r9,
shed cache r10) had no guard against silent decay: nothing failed when
a change quietly gave their speedups back. This gate replays the
committed workload SHAPES with interleaved paired A/B rounds (the r9
methodology: short adjacent rounds, alternating within-round order, so
ambient drift on a shared box cancels in the per-round ratio) and
fails when a guarded paired ratio falls more than PERF_GATE_THRESHOLD
below the committed baseline (PERF_GATE_BASELINE.json — the manifest
names which BENCH_* artifact motivates each workload):

  shed_r10    shed-share-0.9 workload, shed cache OFF vs ON
              (BENCH_SHED_r10.json's screen, bridge tier)
  stages_r7   saturation workload, credit window 1 (round-trip, the
              pre-r7 shape) vs the full advertised window
              (BENCH_STAGES_r7/BENCH_SERVING_DEVICE_r7's pipelining)
  shm_r18     shed shape through the SAME bridge unix socket, GEB
              frames over the control socket vs the mapped
              shared-memory ring (BENCH_FRONTDOOR_r18.json's lane)
  clientroute_r18
              shed shape against a resident 3-node ring, auto-mode
              string downgrade (single connection, full instance
              routing) vs r18 client-side per-owner fast routing
  frontdoor_geb_over_grpc / _http_over_grpc
              the r12 public-door ladder (below; r18 adds the shm
              rung to the ladder artifact)

Paired ratios are deliberately box-speed-invariant: a uniformly slower
container moves both sides of a pair; only a regression in the guarded
feature path moves the ratio. `--inject-frame-ms N` adds a real
per-frame delay (the r8 fault injector, edge_frame point) to the
B/feature side only — the self-test that proves the gate FAILS when
the guarded path slows down (tests/test_perf_gate.py).

The same run measures the public front-door ladder on the shed-r10
workload shape — the gRPC protobuf door vs the GEB client protocol
(daemon GUBER_GEB_PORT door, gubernator_tpu.client_geb) vs the HTTP
binary door (POST /v1/geb) — with each generator OUT of process
(`cli.loadgen --protocol ...`; in-process clients thrash the serving
GIL, and r10 showed the gRPC generator's own protobuf encode IS the
ceiling being measured). Writes BENCH_FRONTDOOR_r12.json.

Usage:
  python scripts/perf_gate.py [--seconds 3] [--rounds 4]
      [--threshold 0.10] [--baseline PERF_GATE_BASELINE.json]
      [--json BENCH_FRONTDOOR.json] [--update-baseline]
      [--inject-frame-ms 0]
  make perf-gate   # PERF_GATE_THRESHOLD=0.10 overridable
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

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

HTTP_ADDR = "127.0.0.1:29881"
GRPC_ADDR = "127.0.0.1:29880"
GRPC_ADDR_MESH = "127.0.0.1:29883"
GEB_PORT = 29882
SOCK = "/tmp/guber-perf-gate.sock"
SOCK_MESH = "/tmp/guber-perf-gate-mesh.sock"

# resident 3-node ring for the clientroute_r18 pair: every node serves
# its own GEB door (distinct ports on one host, wired to the hello via
# the cluster's GUBER_GEB_PEER_DOORS map) so the ring-routing client
# has a routable frame door per owner
RING_GRPC = [f"127.0.0.1:{p}" for p in (29884, 29885, 29886)]
RING_GEB = [29887, 29888, 29889]

# simulated host devices for the shard_r14 pair (r14): the same
# XLA_FLAGS mechanism tests/conftest.py uses — the N-shard partitioned
# engine runs on N virtual CPU devices, so the gate prices the
# partitioned dispatch overhead (host shard routing + shard_map
# program) against the flat single-device policy on identical hardware.
SHARDS = 4

GATED = (
    "shed_r10",
    "stages_r7",
    "sketch_r13",
    "sketch2_r21",
    "shard_r14",
    "chain_r15",
    "trace_r16",
    "rescale_r17",
    "checkpoint_r19",
    "global_mesh",
    "shm_r18",
    "clientroute_r18",
    "frontdoor_geb_over_grpc",
    "frontdoor_http_over_grpc",
)


def evaluate_gate(baseline: dict, measured: dict, threshold: float):
    """Compare measured paired ratios against the committed manifest.
    Returns (passed, rows); a workload fails when its measured ratio
    is more than `threshold` below the committed value. Workloads in
    the manifest but not measured (or vice versa) are reported, not
    silently skipped — a gate that quietly stopped measuring a
    workload would pass for the wrong reason."""
    rows = []
    passed = True
    base_wl = baseline.get("workloads", {})
    for name in sorted(set(base_wl) | set(measured)):
        b = base_wl.get(name)
        m = measured.get(name)
        if b is None:
            rows.append(
                dict(workload=name, status="unguarded",
                     measured=m, note="not in the baseline manifest")
            )
            continue
        if m is None:
            passed = False
            rows.append(
                dict(workload=name, status="FAIL", committed=b["committed"],
                     note="workload not measured this run")
            )
            continue
        floor = b["committed"] * (1.0 - threshold)
        ok = m >= floor
        if not ok:
            passed = False
        rows.append(
            dict(
                workload=name,
                status="ok" if ok else "FAIL",
                committed=b["committed"],
                measured=round(m, 4),
                floor=round(floor, 4),
                artifact=b.get("artifact", ""),
            )
        )
    return passed, rows


def _loadgen(
    protocol: str,
    address: str,
    seconds: float,
    share: float,
    concurrency: int,
    batch: int,
    window: int = 0,
    keyspace: int = 0,
    chain_depth: int = 0,
    ring_route: int = 0,
) -> dict:
    """One out-of-process load window via the real CLI generator."""
    args = [
        sys.executable, "-m", "gubernator_tpu.cli.loadgen", address,
        "--protocol", protocol, "--duration", str(seconds),
        "--share", str(share), "--concurrency", str(concurrency),
        "--batch", str(batch), "--window", str(window),
        "--keyspace", str(keyspace),
        "--chain-depth", str(chain_depth),
        "--ring-route", str(ring_route), "--json",
    ]
    out = subprocess.run(
        args,
        capture_output=True,
        text=True,
        timeout=seconds + 120,
        cwd=ROOT,
        env=dict(os.environ, PYTHONPATH=str(ROOT)),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"loadgen {protocol} failed: {out.stderr[-800:]}"
        )
    r = json.loads(out.stdout.strip().splitlines()[-1])
    if r["errors"]:
        raise RuntimeError(
            f"loadgen {protocol} saw {r['errors']} errors: "
            f"{out.stderr[-800:]}"
        )
    return r


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="per-mode window per round (short micro-"
                    "rounds per the r9 methodology)")
    ap.add_argument("--rounds", type=int, default=4,
                    help="interleaved A/B pairs per workload")
    ap.add_argument("--threshold", type=float,
                    default=float(os.environ.get(
                        "PERF_GATE_THRESHOLD", "0.10")),
                    help="paired-regression budget (0.10 = fail on a "
                    ">10%% drop below the committed ratio)")
    ap.add_argument("--baseline", default=str(ROOT / "PERF_GATE_BASELINE.json"))
    ap.add_argument("--json", default="", help="write the front-door "
                    "ladder artifact here")
    ap.add_argument("--global-artifact", default="",
                    help="write the r20 global_mesh pair artifact "
                    "(BENCH_GLOBAL_r20.json shape) here")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline manifest from this "
                    "run's measurements instead of gating")
    ap.add_argument("--inject-frame-ms", type=float, default=0.0,
                    help="self-test: inject this per-frame delay into "
                    "the FEATURE side of every pair (edge_frame fault "
                    "point) — the gate must then fail")
    ap.add_argument("--share", type=float, default=0.9,
                    help="over-limit share of the shed/ladder shape")
    ap.add_argument(
        "--device-batch-limit", type=int,
        default=int(os.environ.get("GUBER_DEVICE_BATCH_LIMIT", "8192")),
    )
    ap.add_argument("--concurrency", type=int, default=24,
                    help="loadgen workers (geb: pipelined frames on "
                    "one connection)")
    ap.add_argument("--batch", type=int, default=1000)
    args = ap.parse_args()

    # simulated devices for the N-shard side, BEFORE any jax client
    # initializes (XLA_FLAGS is read lazily at CPU-client init — the
    # tests/conftest.py pattern). The flat side keeps using the default
    # (first) device, so both sides of every pair share the box. An
    # inherited device-count flag (e.g. from a test-suite env) is
    # OVERRIDDEN, not kept: the committed shard_r14 baseline says
    # "{SHARDS}-shard mesh" and must measure exactly that.
    import re

    _flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        os.environ.get("XLA_FLAGS", ""),
    )
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={SHARDS}"
    ).strip()

    import jax

    from gubernator_tpu.jaxenv import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from gubernator_tpu.cluster import LocalCluster
    from gubernator_tpu.core.engine import buckets_for_limit
    from gubernator_tpu.core.sketches import derive_sketch_config
    from gubernator_tpu.core.store import StoreConfig
    from gubernator_tpu.serve.backends import TpuBackend
    from gubernator_tpu.serve.faults import FAULTS

    cluster = LocalCluster(
        [GRPC_ADDR],
        backend_factory=lambda: TpuBackend(
            StoreConfig(rows=16, slots=1 << 12),
            buckets=buckets_for_limit(args.device_batch_limit),
            # small cold tier so the sketch_r13 pair flips a real path
            # (engine.sketch_on); the other workloads' key sets fit the
            # exact tier, where ON is byte-identical to OFF
            sketch=derive_sketch_config(mib=8),
        ),
        http_addresses=[HTTP_ADDR],
        device_batch_limit=args.device_batch_limit,
        geb_ports=[GEB_PORT],
    )
    print("perf-gate: starting serving stack (device warmup)...",
          file=sys.stderr)
    cluster.start(timeout=600)

    # second resident stack for the shard_r14 pair: the SAME
    # partitioned engine under an N-shard mesh policy on the simulated
    # devices (store geometry and ladder identical to the flat side, so
    # the paired ratio isolates the partitioned dispatch overhead)
    from gubernator_tpu.serve.backends import MeshBackend

    if len(jax.devices()) < SHARDS:
        raise SystemExit(
            f"perf-gate: only {len(jax.devices())} devices visible but "
            f"the shard_r14 pair is committed as {SHARDS}-shard — a jax "
            "client initialized before the XLA_FLAGS override landed"
        )
    mesh_cluster = LocalCluster(
        [GRPC_ADDR_MESH],
        backend_factory=lambda: MeshBackend(
            StoreConfig(rows=16, slots=1 << 12),
            devices=jax.devices()[:SHARDS],
            buckets=buckets_for_limit(args.device_batch_limit),
            sketch=derive_sketch_config(mib=8),
        ),
        device_batch_limit=args.device_batch_limit,
    )
    print(
        f"perf-gate: starting {SHARDS}-shard mesh stack "
        "(sub-rung warmup)...",
        file=sys.stderr,
    )

    async def attach(server, sock, shm=False):
        from gubernator_tpu.serve.edge_bridge import EdgeBridge

        # the flat bridge carries the shm_r18 pair: lane granted only
        # to clients that ASK (GEBM), so the A side (`--protocol geb`,
        # shm pinned off) still measures the plain control socket
        bridge = EdgeBridge(
            server.instance, sock,
            shm_enabled=shm, shm_ring_kib=1024,
        )
        await bridge.start()
        return bridge

    # the flat stack is already serving: a mesh boot/attach failure
    # must tear it down rather than leak its threads and sockets
    # third resident stack (r18): the 3-node ring the clientroute_r18
    # pair drives through its node-0 GEB door — A downgrades to string
    # frames on the multi-node ring, B routes fast frames per owner
    ring_cluster = LocalCluster(
        RING_GRPC,
        backend_factory=lambda: TpuBackend(
            StoreConfig(rows=16, slots=1 << 12),
            buckets=buckets_for_limit(args.device_batch_limit),
            sketch=derive_sketch_config(mib=8),
        ),
        device_batch_limit=args.device_batch_limit,
        geb_ports=RING_GEB,
    )
    print("perf-gate: starting 3-node ring stack (clientroute "
          "warmup)...", file=sys.stderr)
    try:
        mesh_cluster.start(timeout=600)
        ring_cluster.start(timeout=600)
        pathlib.Path(SOCK).unlink(missing_ok=True)
        pathlib.Path(SOCK_MESH).unlink(missing_ok=True)
        bridge = cluster.run(attach(cluster.servers[0], SOCK, shm=True))
        mesh_bridge = mesh_cluster.run(
            attach(mesh_cluster.servers[0], SOCK_MESH)
        )
    except BaseException:
        for c in (cluster, mesh_cluster, ring_cluster):
            try:
                c.stop()
            except Exception:
                pass
        raise
    instance = cluster.servers[0].instance
    shed_obj = instance.shed
    assert shed_obj is not None, "gate expects the shipped defaults"

    inject_spec = (
        f"edge_frame:delay={args.inject_frame_ms}ms"
        if args.inject_frame_ms > 0
        else ""
    )

    def set_inject(on: bool) -> None:
        FAULTS.configure(inject_spec if on and inject_spec else "")

    def flip_shed(on: bool):
        async def f():
            instance.shed = shed_obj if on else None

        cluster.run(f())

    def paired(name, drive_a, drive_b, seconds, rounds):
        """Interleaved paired rounds: per-round ratio B/A with
        alternating within-round order; the median paired ratio is
        the gate metric. `drive_*` run one load window and return
        decisions/s; the injected self-test delay (if any) applies to
        the B side only."""
        rows = []
        for which, drive in (("a", drive_a), ("b", drive_b)):
            set_inject(which == "b")
            drive(min(2.0, seconds))  # warm both paths
        ratios = []
        for rnd in range(rounds):
            order = ("a", "b") if rnd % 2 == 0 else ("b", "a")
            rates = {}
            for which in order:
                set_inject(which == "b")
                drive = drive_a if which == "a" else drive_b
                rates[which] = drive(seconds)
            set_inject(False)
            ratios.append(rates["b"] / rates["a"])
            rows.append(dict(round=rnd, a=rates["a"], b=rates["b"],
                             ratio=round(ratios[-1], 4)))
            print(
                f"  {name} round {rnd}: A {rates['a']:>11,.0f} "
                f"B {rates['b']:>11,.0f} dec/s  ratio "
                f"{ratios[-1]:.3f}",
                file=sys.stderr,
            )
        return statistics.median(ratios), rows

    measured = {}
    detail = {}
    try:
        def bridge_drive(share, window=0, batch=None):
            def d(seconds):
                r = _loadgen(
                    "geb", SOCK, seconds, share,
                    args.concurrency, batch or args.batch,
                    window=window,
                )
                return r["decisions_per_sec"]

            return d

        # -- shed_r10: shed cache OFF vs ON, over-limit-heavy shape --
        print("workload shed_r10 (shed OFF vs ON)...", file=sys.stderr)
        drive = bridge_drive(args.share)

        def shed_off(s):
            flip_shed(False)
            try:
                return drive(s)
            finally:
                flip_shed(True)

        m, rows = paired("shed_r10", shed_off, drive,
                         args.seconds, args.rounds)
        measured["shed_r10"], detail["shed_r10"] = m, rows

        # -- stages_r7: window 1 (round-trip) vs full window ---------
        # smaller frames than the saturation shape: at 1000-item
        # frames the device work hides the protocol round trip; 100
        # items makes frame-rate (the thing windowing pipelines) the
        # measured quantity
        print("workload stages_r7 (window 1 vs full)...", file=sys.stderr)
        m, rows = paired(
            "stages_r7",
            bridge_drive(0.0, window=1, batch=100),
            bridge_drive(0.0, batch=100),
            args.seconds, args.rounds,
        )
        measured["stages_r7"], detail["stages_r7"] = m, rows

        # -- sketch_r13: cold tier OFF vs ON, high-cardinality shape -
        # cold keyspace ~5x the exact tier's entry capacity + a hot
        # over-limit head: at deep batches multiple fresh keys land in
        # one bucket, so creates drop and the pair exercises the
        # sketch path (OFF: silent over-admission; ON: count-min
        # decisions). Gates the sketch kernel's cost from decaying.
        print("workload sketch_r13 (sketch OFF vs ON)...",
              file=sys.stderr)
        engine = instance.backend.engine

        def flip_sketch(on: bool):
            async def f():
                engine.sketch_on = on

            cluster.run(f())

        def sketch_drive(s):
            return _loadgen(
                "geb", SOCK, s, 0.5, args.concurrency, args.batch,
                keyspace=300_000,
            )["decisions_per_sec"]

        def sketch_off(s):
            flip_sketch(False)
            try:
                return sketch_drive(s)
            finally:
                flip_sketch(True)

        m, rows = paired("sketch_r13", sketch_off, sketch_drive,
                         args.seconds, args.rounds)
        measured["sketch_r13"], detail["sketch_r13"] = m, rows

        # -- sketch2_r21: r13 vs v2 derivation at the SAME budget ----
        # In-process decide_arrays drive on two standalone engines
        # whose exact buckets are pinned full of immortal fillers, so
        # every measured key's create drops and decides from the
        # sketch over the r21 window-ring sliding path. A = the
        # committed r13 counter geometry (4 rows of int64), B = v2
        # (2 rows of saturating int32, 4x the width) at the identical
        # byte budget. Both sides share the host prep and the store
        # probe; the ratio prices the v2 kernel — fewer hash lanes,
        # narrower counters — and the committed baseline pins that the
        # 4x-tighter error bound was not bought with decide throughput.
        print(
            "workload sketch2_r21 (r13 vs v2 derivation, same "
            "budget)...",
            file=sys.stderr,
        )
        import numpy as np

        from gubernator_tpu.cli import keystreams
        from gubernator_tpu.cli.bench_serving import _filler_hashes
        from gubernator_tpu.core.engine import TpuEngine
        from gubernator_tpu.core.sketches import derive_sketch_config
        from gubernator_tpu.core.store import StoreConfig

        sk2_cfg = StoreConfig(rows=1, slots=64)
        sk2_fill = _filler_hashes(sk2_cfg.slots)
        sk2_nf = sk2_fill.shape[0]
        SK2_B, SK2_T0 = 4096, 1_700_000_000_000
        sk2_rng = np.random.default_rng(21)
        sk2_keys = [
            np.concatenate([
                sk2_fill,
                keystreams.hash_ids(
                    keystreams.zipf_ids(
                        100_000, SK2_B - sk2_nf, sk2_rng
                    )
                ),
            ])
            for _ in range(16)
        ]
        sk2_hits = np.concatenate([
            np.zeros(sk2_nf, np.int64),
            np.ones(SK2_B - sk2_nf, np.int64),
        ])
        sk2_lim = np.full(SK2_B, 1000, np.int64)
        sk2_dur = np.full(SK2_B, 60_000, np.int64)
        sk2_algo = np.full(SK2_B, 2, np.int32)  # sliding: window-ring
        sk2_algo[:sk2_nf] = 0
        sk2_gnp = np.zeros(SK2_B, bool)

        def sk2_engine(derivation):
            eng = TpuEngine(
                sk2_cfg, buckets=(4096,),
                sketch=derive_sketch_config(
                    mib=8, derivation=derivation
                ),
            )
            ones = np.ones(sk2_nf, np.int64)
            eng.decide_arrays(
                sk2_fill, ones, ones * 1000, ones * 1_000_000_000,
                np.zeros(sk2_nf, np.int32), np.zeros(sk2_nf, bool),
                SK2_T0,
            )
            return eng

        sk2_engines = {
            "r13": sk2_engine("r13"), "v2": sk2_engine("v2")
        }
        sk2_step = {"i": 0}

        def sk2_drive(which):
            eng = sk2_engines[which]

            def d(seconds):
                n = 0
                deadline = time.monotonic() + seconds
                while time.monotonic() < deadline:
                    i = sk2_step["i"] = sk2_step["i"] + 1
                    eng.decide_arrays(
                        sk2_keys[i % len(sk2_keys)], sk2_hits,
                        sk2_lim, sk2_dur, sk2_algo, sk2_gnp,
                        SK2_T0 + i,
                    )
                    n += SK2_B
                return n / seconds

            return d

        m, rows = paired(
            "sketch2_r21", sk2_drive("r13"), sk2_drive("v2"),
            args.seconds, args.rounds,
        )
        measured["sketch2_r21"], detail["sketch2_r21"] = m, rows

        # -- shard_r14: 1-shard flat vs N-shard mesh, zipf keyspace --
        # Same GEB workload against two RESIDENT stacks (identical
        # store geometry/ladder/sketch): A = the flat single-device
        # policy, B = the N-shard partitioned policy on simulated
        # devices. On one CPU the mesh buys no parallelism, so the
        # ratio IS the partitioned dispatch price (host shard routing
        # + shard_map program) the unification must not let decay —
        # its value (per-chip scaling) only exists on real meshes.
        print(
            f"workload shard_r14 (flat vs {SHARDS}-shard mesh)...",
            file=sys.stderr,
        )

        def shard_drive(sock):
            def d(seconds):
                return _loadgen(
                    "geb", sock, seconds, 0.0, args.concurrency,
                    args.batch, keyspace=30_000,
                )["decisions_per_sec"]

            return d

        m, rows = paired(
            "shard_r14", shard_drive(SOCK), shard_drive(SOCK_MESH),
            args.seconds, args.rounds,
        )
        measured["shard_r14"], detail["shard_r14"] = m, rows

        # -- chain_r15: plain vs depth-3 quota chains, zipf shape ----
        # Same GEB workload against the flat stack, A = plain items
        # (fold/fast path), B = every item carrying a depth-3 chain
        # (GEBC string frames -> the batcher's dedicated chain lane ->
        # one chain-coupled kernel pass per flush, 4x the device rows).
        # The ratio IS the chain expansion price the r15 subsystem
        # must not let decay; generous level limits keep refusals out
        # of the measured quantity.
        print(
            "workload chain_r15 (plain vs depth-3 chains)...",
            file=sys.stderr,
        )

        def chain_drive(depth):
            def d(seconds):
                return _loadgen(
                    "geb", SOCK, seconds, 0.0, args.concurrency,
                    args.batch, keyspace=30_000, chain_depth=depth,
                )["decisions_per_sec"]

            return d

        m, rows = paired(
            "chain_r15", chain_drive(0), chain_drive(3),
            args.seconds, args.rounds,
        )
        measured["chain_r15"], detail["chain_r15"] = m, rows

        # -- trace_r16: sampling OFF vs 1%, zipf shape ---------------
        # Same GEB workload against the flat stack; A = tracing fully
        # off (the default), B = GUBER_TRACE_SAMPLE=0.01. The ratio
        # prices the whole r16 instrumentation envelope — the
        # per-site branches every request pays plus span collection
        # for the sampled 1% — and the committed baseline pins the
        # "disabled is ~zero-cost / 1% is <=10%" contract.
        print(
            "workload trace_r16 (sampling off vs 1%)...",
            file=sys.stderr,
        )
        tracer = instance.tracer

        def flip_trace(p):
            async def f():
                tracer.sample = p

            cluster.run(f())

        def trace_drive(s):
            return _loadgen(
                "geb", SOCK, s, 0.0, args.concurrency, args.batch,
                keyspace=30_000,
            )["decisions_per_sec"]

        def trace_on(s):
            flip_trace(0.01)
            try:
                return trace_drive(s)
            finally:
                flip_trace(0.0)

        m, rows = paired("trace_r16", trace_drive, trace_on,
                         args.seconds, args.rounds)
        measured["trace_r16"], detail["trace_r16"] = m, rows

        # -- rescale_r17: tracking off vs on, STATIC ring ------------
        # Same GEB workload against the flat stack; A = rescale off,
        # B = a live RescaleManager attached (instance.rescale). With
        # a static single-node ring the manager's only hot-path work
        # is the owned-key tracking (dict ops per folded frame item) —
        # the "ON is byte-identical and ~free on a static ring"
        # contract the committed baseline pins.
        print(
            "workload rescale_r17 (tracking off vs on)...",
            file=sys.stderr,
        )
        from gubernator_tpu.serve.rescale import RescaleManager

        resc_obj = RescaleManager(
            cluster.servers[0].conf, instance
        )

        def flip_rescale(on: bool):
            async def f():
                instance.rescale = resc_obj if on else None

            cluster.run(f())

        def rescale_drive(s):
            return _loadgen(
                "geb", SOCK, s, 0.0, args.concurrency, args.batch,
                keyspace=30_000,
            )["decisions_per_sec"]

        def rescale_on(s):
            flip_rescale(True)
            try:
                return rescale_drive(s)
            finally:
                flip_rescale(False)

        m, rows = paired("rescale_r17", rescale_drive, rescale_on,
                         args.seconds, args.rounds)
        measured["rescale_r17"], detail["rescale_r17"] = m, rows

        # -- checkpoint_r19: checkpointing off vs on -----------------
        # Same GEB workload against the flat stack; A = checkpoint
        # off, B = a live CheckpointManager attached (tracking dict
        # ops per folded frame item) WITH its flush loop running
        # against a real directory on a 1 s cadence — so the B side
        # prices both the hot-path tracking and the periodic
        # off-path snapshot + fsync'd write sharing the submit
        # thread. The committed baseline pins the "checkpointing a
        # live node is ~free for serving" contract.
        print(
            "workload checkpoint_r19 (checkpoint off vs on)...",
            file=sys.stderr,
        )
        import copy as _copy
        import tempfile as _tempfile

        from gubernator_tpu.serve.checkpoint import CheckpointManager

        ckpt_conf = _copy.copy(cluster.servers[0].conf)
        ckpt_conf.checkpoint_dir = _tempfile.mkdtemp(
            prefix="guber-perfgate-ckpt-"
        )
        ckpt_conf.checkpoint_interval = 1.0
        ckpt_obj = CheckpointManager(ckpt_conf, instance)

        def flip_ckpt(on: bool):
            async def f():
                if on:
                    instance.checkpoint = ckpt_obj
                    ckpt_obj.start()
                else:
                    instance.checkpoint = None
                    await ckpt_obj.stop()

            cluster.run(f())

        def ckpt_drive(s):
            return _loadgen(
                "geb", SOCK, s, 0.0, args.concurrency, args.batch,
                keyspace=30_000,
            )["decisions_per_sec"]

        def ckpt_on(s):
            flip_ckpt(True)
            try:
                return ckpt_drive(s)
            finally:
                flip_ckpt(False)

        m, rows = paired("checkpoint_r19", ckpt_drive, ckpt_on,
                         args.seconds, args.rounds)
        measured["checkpoint_r19"], detail["checkpoint_r19"] = m, rows

        # -- global_mesh (r20): RPC-gossip loopback vs in-mesh psum --
        # GLOBAL flush throughput on the resident mesh stack's
        # GlobalManager. The single-node ring owns every key, so A
        # (GUBER_GLOBAL_MESH=0) sends each flush chunk back through
        # its OWN gRPC gossip door — the pre-r20 fan-out, which priced
        # mesh-local peers as remote — while B applies the same chunk
        # as ONE in-mesh psum collective (apply_global_hits on the
        # submit thread). One traced flush per side afterwards records
        # the per-path hop counts: the r20 claim is hops_mesh=1 per
        # flush vs >=1 RPC hop per (peer, chunk).
        print(
            "workload global_mesh (RPC loopback vs psum collective)...",
            file=sys.stderr,
        )
        from gubernator_tpu.api.types import Behavior, RateLimitReq

        inst_mesh = mesh_cluster.servers[0].instance
        gmgr = inst_mesh.global_mgr
        g_reqs = [
            RateLimitReq(
                name="pg", unique_key=f"g{i}", hits=1,
                limit=1_000_000, duration=60_000,
                behavior=Behavior.GLOBAL,
            )
            for i in range(args.batch)
        ]

        def gm_drive(on):
            def d(seconds):
                async def run():
                    gmgr.conf.global_mesh = on
                    try:
                        keys = 0
                        deadline = time.monotonic() + seconds
                        while time.monotonic() < deadline:
                            for r in g_reqs:
                                gmgr.queue_hit(r)
                            await gmgr.drain()
                            keys += len(g_reqs)
                        return keys / seconds
                    finally:
                        gmgr.conf.global_mesh = True

                return mesh_cluster.run(run())

            return d

        m, rows = paired("global_mesh", gm_drive(False), gm_drive(True),
                         args.seconds, args.rounds)
        measured["global_mesh"], detail["global_mesh"] = m, rows

        def traced_flush(on):
            async def run():
                tr = inst_mesh.tracer
                old = tr.sample
                tr.sample = 1.0
                gmgr.conf.global_mesh = on
                try:
                    for r in g_reqs:
                        gmgr.queue_hit(r)
                    await gmgr.drain()
                finally:
                    tr.sample = old
                    gmgr.conf.global_mesh = True
                for t in reversed(tr.recorder.snapshot()["traces"]):
                    if t["door"] != "global_flush":
                        continue
                    for sp in t["spans"]:
                        if sp["name"] == "global_flush_hits":
                            return sp["annotations"]
                raise RuntimeError("no global_flush_hits span recorded")

            return mesh_cluster.run(run())

        ann_rpc = traced_flush(False)
        ann_mesh = traced_flush(True)
        # hop-count evidence, asserted: the collective side must be
        # exactly one in-mesh hop and zero gossip sends
        assert ann_rpc["hops_rpc"] >= 1 and ann_rpc["hops_mesh"] == 0, (
            ann_rpc
        )
        assert ann_mesh["hops_mesh"] == 1 and ann_mesh["hops_rpc"] == 0, (
            ann_mesh
        )
        detail["global_mesh_trace"] = {"rpc": ann_rpc, "mesh": ann_mesh}
        print(
            f"  flush spans: rpc={ann_rpc} mesh={ann_mesh}",
            file=sys.stderr,
        )

        # -- shm_r18: control socket vs shared-memory lane -----------
        # Same bridge unix socket, same shed shape, same client: A
        # pins shm negotiation off (every frame write()/read() on the
        # socket), B requires the mapped ring (frame bytes through
        # shared memory, wakeups via futex). The paired ratio prices
        # the per-frame syscall + copy the lane removes; `mechanism`
        # records the negotiated transports so the artifact proves
        # which lane carried each side.
        print("workload shm_r18 (GEB-TCP vs GEB-shm lane)...",
              file=sys.stderr)
        mech_shm = {}

        def shm_side(protocol, slot):
            def d(s):
                r = _loadgen(
                    protocol, SOCK, s, args.share,
                    args.concurrency, args.batch,
                )
                mech_shm[slot] = r.get("client", {})
                return r["decisions_per_sec"]

            return d

        m, rows = paired(
            "shm_r18", shm_side("geb", "socket"),
            shm_side("shm", "shm"), args.seconds, args.rounds,
        )
        measured["shm_r18"], detail["shm_r18"] = m, rows

        # -- clientroute_r18: string downgrade vs ring routing -------
        # Same shed shape against the resident 3-node ring's node-0
        # GEB door: A is the pre-r18 client (auto mode downgrades to
        # string frames on a multi-node ring — every item through
        # instance routing + peer forwarding), B turns on client-side
        # per-owner fast routing (crc32 shards across per-node
        # connections, fast frames pinned to the router's ring
        # fingerprint).
        print(
            "workload clientroute_r18 (string downgrade vs ring "
            "routing)...",
            file=sys.stderr,
        )
        mech_route = {}

        def route_side(rr, slot):
            def d(s):
                r = _loadgen(
                    "geb", f"127.0.0.1:{RING_GEB[0]}", s, args.share,
                    args.concurrency, args.batch, ring_route=rr,
                )
                mech_route[slot] = r.get("client", {})
                return r["decisions_per_sec"]

            return d

        m, rows = paired(
            "clientroute_r18", route_side(0, "string"),
            route_side(1, "routed"), args.seconds, args.rounds,
        )
        measured["clientroute_r18"], detail["clientroute_r18"] = m, rows

        # -- front-door ladder: grpc vs geb vs http vs shm -----------
        print("front-door ladder (grpc / geb / http / shm)...",
              file=sys.stderr)
        doors = {
            "grpc": lambda s: _loadgen(
                "grpc", GRPC_ADDR, s, args.share,
                min(args.concurrency, 16), args.batch,
            ),
            "geb": lambda s: _loadgen(
                "geb", f"127.0.0.1:{GEB_PORT}", s, args.share,
                args.concurrency, args.batch,
            ),
            "http": lambda s: _loadgen(
                "http", HTTP_ADDR, s, args.share,
                min(args.concurrency, 10), args.batch,
            ),
            # r18 top rung: the same frames through the bridge's
            # mapped shared-memory ring (co-located client)
            "shm": lambda s: _loadgen(
                "shm", SOCK, s, args.share,
                args.concurrency, args.batch,
            ),
        }
        for door, d in doors.items():
            set_inject(door != "grpc")
            d(min(2.0, args.seconds))  # warm
        ladder_rows = []
        for rnd in range(args.rounds):
            order = (
                list(doors) if rnd % 2 == 0 else list(reversed(doors))
            )
            rates = {}
            for door in order:
                # the injected self-test delay slows the frame doors
                # (geb/http), not the gRPC baseline — a regression in
                # the new fast paths, which the gate must catch
                set_inject(door != "grpc")
                r = doors[door](args.seconds)
                rates[door] = r["decisions_per_sec"]
            set_inject(False)
            ladder_rows.append(dict(round=rnd, **{
                k: round(v, 1) for k, v in rates.items()
            }))
            print(
                f"  ladder round {rnd}: "
                + "  ".join(
                    f"{k} {v:>11,.0f}" for k, v in rates.items()
                ),
                file=sys.stderr,
            )
        geb_ratios = [r["geb"] / r["grpc"] for r in ladder_rows]
        http_ratios = [r["http"] / r["grpc"] for r in ladder_rows]
        measured["frontdoor_geb_over_grpc"] = statistics.median(geb_ratios)
        measured["frontdoor_http_over_grpc"] = statistics.median(
            http_ratios
        )
    finally:
        FAULTS.configure("")
        try:
            cluster.run(bridge.stop())
        except Exception:
            pass
        try:
            mesh_cluster.run(mesh_bridge.stop())
        except Exception:
            pass
        try:
            cluster.stop()
        finally:
            try:
                mesh_cluster.stop()
            finally:
                ring_cluster.stop()
        pathlib.Path(SOCK).unlink(missing_ok=True)
        pathlib.Path(SOCK_MESH).unlink(missing_ok=True)

    for k, v in measured.items():
        print(f"measured {k}: {v:.3f}", file=sys.stderr)

    if args.global_artifact and "global_mesh" in measured:
        doc = {
            "scenario": "global_mesh_r20",
            "scope": "cpu-simulated-devices",
            "shards": SHARDS,
            "batch_keys": args.batch,
            "seconds_per_round": args.seconds,
            "pair": (
                "A = GUBER_GLOBAL_MESH=0: every flush chunk loops back "
                "through the node's own gRPC gossip door (the pre-r20 "
                "per-peer fan-out, mesh-local self priced as remote); "
                "B = one in-mesh psum collective per chunk "
                "(apply_global_hits)"
            ),
            "median_ratio_mesh_over_rpc": round(
                measured["global_mesh"], 4
            ),
            "rounds": detail["global_mesh"],
            "flush_trace_spans": detail["global_mesh_trace"],
            "notes": (
                "Flush throughput (keys/s) of the GlobalManager hits "
                "loop on the resident mesh stack. The hop-count span "
                "annotations are the r20 acceptance evidence: the "
                "collective side flushes hops_mesh=1 regardless of "
                "peer count while the RPC side pays one gossip send "
                "per (peer, chunk). Simulated CPU devices — the ratio "
                "prices the serialize+loopback-RPC+door-decode the "
                "collective removes, not chip parallelism."
            ),
        }
        pathlib.Path(args.global_artifact).write_text(
            json.dumps(doc, indent=1) + "\n"
        )
        print(f"global_mesh artifact written: {args.global_artifact}",
              file=sys.stderr)

    baseline_path = pathlib.Path(args.baseline)
    if args.update_baseline:
        manifest = {
            "schema": "perf_gate_baseline_r12",
            "comment": (
                "Committed paired-ratio baselines for `make "
                "perf-gate` (scripts/perf_gate.py). Each workload "
                "replays the SHAPE of the named BENCH_* artifact "
                "with interleaved paired A/B rounds; the gate fails "
                "when a measured ratio falls more than "
                "PERF_GATE_THRESHOLD below `committed`. Ratios are "
                "box-speed-invariant (both sides of a pair share "
                "the box), so these values transfer across "
                "containers far better than absolute dec/s."
            ),
            "threshold_default": args.threshold,
            "seconds_per_round": args.seconds,
            "rounds": args.rounds,
            "workloads": {
                "shed_r10": {
                    "artifact": "BENCH_SHED_r10.json",
                    "pair": "shed cache OFF vs ON, share "
                            f"{args.share} shed workload",
                    "committed": round(measured["shed_r10"], 4),
                },
                "stages_r7": {
                    "artifact": "BENCH_STAGES_r7.json",
                    "pair": "credit window 1 (round-trip) vs full "
                            "window, saturation workload",
                    "committed": round(measured["stages_r7"], 4),
                },
                "sketch_r13": {
                    "artifact": "BENCH_SKETCH_r13.json",
                    "pair": "sketch cold tier OFF vs ON, share 0.5 "
                            "keyspace-300k drop-heavy workload",
                    "committed": round(measured["sketch_r13"], 4),
                },
                "sketch2_r21": {
                    "artifact": "BENCH_SKETCH_r21.json",
                    "pair": "sketch derivation r13 (4 rows int64) vs "
                            "v2 (2 rows saturating int32, 4x width) "
                            "at the same 8 MiB budget, pinned-bucket "
                            "sliding-window decide drive",
                    "committed": round(measured["sketch2_r21"], 4),
                },
                "shard_r14": {
                    "artifact": "BENCH_SHARD_r14.json",
                    "pair": f"flat 1-shard vs {SHARDS}-shard "
                            "simulated-device mesh, keyspace-30k zipf "
                            "shape (partitioned dispatch price)",
                    "committed": round(measured["shard_r14"], 4),
                },
                "chain_r15": {
                    "artifact": "BENCH_ALGO_r15.json",
                    "pair": "plain items vs depth-3 quota chains, "
                            "keyspace-30k zipf shape (chain-lane "
                            "expansion price)",
                    "committed": round(measured["chain_r15"], 4),
                },
                "trace_r16": {
                    "artifact": "BENCH_TRACE_r16.json",
                    "pair": "tracing off vs GUBER_TRACE_SAMPLE=0.01, "
                            "keyspace-30k zipf shape (distributed-"
                            "tracing instrumentation price)",
                    "committed": round(measured["trace_r16"], 4),
                },
                "rescale_r17": {
                    "artifact": "BENCH_RESCALE_r17.json",
                    "pair": "rescale tracking off vs on, static "
                            "ring, keyspace-30k zipf shape (owned-"
                            "window tracking price)",
                    "committed": round(measured["rescale_r17"], 4),
                },
                "checkpoint_r19": {
                    "artifact": "BENCH_RESTORE_r19.json",
                    "pair": "checkpointing off vs on (tracking + "
                            "1 s flush loop to a real dir), static "
                            "ring, keyspace-30k zipf shape",
                    "committed": round(measured["checkpoint_r19"], 4),
                },
                "global_mesh": {
                    "artifact": "BENCH_GLOBAL_r20.json",
                    "pair": "GLOBAL flush loopback over the gossip "
                            "gRPC door (GUBER_GLOBAL_MESH=0) vs ONE "
                            f"in-mesh psum collective, {SHARDS}-shard "
                            "mesh stack, 1000-key flush chunks",
                    "committed": round(measured["global_mesh"], 4),
                },
                "shm_r18": {
                    "artifact": "BENCH_FRONTDOOR_r18.json",
                    "pair": "GEB frames over the bridge unix control "
                            "socket vs the mapped shared-memory ring, "
                            "shed-r10 shape (co-located client)",
                    "committed": round(measured["shm_r18"], 4),
                },
                "clientroute_r18": {
                    "artifact": "BENCH_FRONTDOOR_r18.json",
                    "pair": "3-node ring, auto-mode string downgrade "
                            "vs client-side per-owner fast routing, "
                            "shed-r10 shape",
                    "committed": round(measured["clientroute_r18"], 4),
                },
                "frontdoor_geb_over_grpc": {
                    "artifact": "BENCH_FRONTDOOR_r12.json",
                    "pair": "GEB client door vs gRPC protobuf door, "
                            "shed-r10 shape",
                    "committed": round(
                        measured["frontdoor_geb_over_grpc"], 4
                    ),
                },
                "frontdoor_http_over_grpc": {
                    "artifact": "BENCH_FRONTDOOR_r12.json",
                    "pair": "HTTP binary /v1/geb door vs gRPC "
                            "protobuf door, shed-r10 shape",
                    "committed": round(
                        measured["frontdoor_http_over_grpc"], 4
                    ),
                },
            },
        }
        baseline_path.write_text(json.dumps(manifest, indent=1) + "\n")
        print(f"baseline manifest written: {baseline_path}",
              file=sys.stderr)
        passed, rows = True, []
    else:
        baseline = json.loads(baseline_path.read_text())
        passed, rows = evaluate_gate(baseline, measured, args.threshold)
        for r in rows:
            print(f"gate {r['workload']}: {r['status']} "
                  f"(measured {r.get('measured')} vs committed "
                  f"{r.get('committed')}, floor {r.get('floor')})",
                  file=sys.stderr)
        print(
            f"perf-gate: {'PASS' if passed else 'FAIL'} "
            f"(threshold {args.threshold:.0%})",
            file=sys.stderr,
        )

    if args.json:
        geb_med = statistics.median(
            r["geb"] for r in ladder_rows
        )
        shm_med = statistics.median(
            r["shm"] for r in ladder_rows
        )
        shm_ratios = [r["shm"] / r["geb"] for r in ladder_rows]
        doc = {
            "schema": "bench_frontdoor_r18",
            "scope": (
                "single node, tpu backend on this host's CPU; each "
                "door driven by an OUT-of-process "
                "`cli.loadgen --protocol {grpc,geb,http,shm}` on the "
                f"shed-r10 workload shape (share {args.share}: hot "
                "limit-1 keys frozen over limit + never-over keys), "
                f"{args.batch}-item batches. gRPC = the protobuf "
                "door (AsyncV1Client); geb = the windowed GEB client "
                "protocol against the daemon's GUBER_GEB_PORT door "
                "(gubernator_tpu.client_geb, credit-window "
                "pipelining); http = binary GEB frames POSTed to "
                "/v1/geb; shm = the SAME GEB frames through the "
                "bridge's mapped shared-memory ring (r18 lane, "
                "co-located client, unix socket kept as the control "
                "channel). INTERLEAVED rounds with alternating "
                "order; paired per-round ratios vs the gRPC door "
                "are the drift-robust headline (r9 methodology). "
                "The same run replays the r7..r18 paired workloads "
                "as the perf gate (see `gate`); the r18 pairs "
                "(`shm_r18`, `clientroute_r18`) carry the "
                "mechanism-evidence client stats in `acceptance`."
            ),
            "host_cpus": os.cpu_count(),
            "seconds_per_round": args.seconds,
            "rounds": args.rounds,
            "share": args.share,
            "batch_items": args.batch,
            "concurrency": args.concurrency,
            "device_batch_limit": args.device_batch_limit,
            "env_knobs": {
                "GUBER_GEB_PORT": str(GEB_PORT),
                "GUBER_SHED_CACHE": "1",
                "GUBER_DEVICE_BATCH_LIMIT": str(
                    args.device_batch_limit
                ),
            },
            "ladder_rows": ladder_rows,
            "ladder_median_decisions_per_sec": {
                door: statistics.median(
                    r[door] for r in ladder_rows
                )
                for door in ("grpc", "geb", "http", "shm")
            },
            "paired": {
                "geb_over_grpc": {
                    "ratios": [round(x, 4) for x in geb_ratios],
                    "median": round(
                        measured["frontdoor_geb_over_grpc"], 4
                    ),
                },
                "http_over_grpc": {
                    "ratios": [round(x, 4) for x in http_ratios],
                    "median": round(
                        measured["frontdoor_http_over_grpc"], 4
                    ),
                },
                # r18 ladder rung, same-round ratio (context only;
                # the gated number is shm_over_geb_socket below)
                "shm_over_geb_ladder": {
                    "ratios": [round(x, 4) for x in shm_ratios],
                    "median": round(
                        statistics.median(shm_ratios), 4
                    ),
                },
                # the two r18 paired A/B measurements (perf-gated)
                "shm_over_geb_socket": {
                    "rounds": detail["shm_r18"],
                    "median": round(measured["shm_r18"], 4),
                },
                "clientroute_routed_over_string": {
                    "rounds": detail["clientroute_r18"],
                    "median": round(measured["clientroute_r18"], 4),
                },
            },
            "acceptance": {
                "target_geb_over_grpc": 2.5,
                "met": measured["frontdoor_geb_over_grpc"] >= 2.5,
                "geb_median_decisions_per_sec": geb_med,
                "shm_median_decisions_per_sec": shm_med,
                "r18": {
                    "target_shm_over_geb_socket": 1.5,
                    "shm_over_geb_socket": round(
                        measured["shm_r18"], 4
                    ),
                    "shm_met": measured["shm_r18"] >= 1.5,
                    "target_clientroute_routed_over_string": 2.0,
                    "clientroute_routed_over_string": round(
                        measured["clientroute_r18"], 4
                    ),
                    "clientroute_met": (
                        measured["clientroute_r18"] >= 2.0
                    ),
                    "acceptance_note": (
                        "targets are stated for multi-core hosts "
                        "where the paper's headroom exists; on this "
                        f"{os.cpu_count()}-CPU container the client, "
                        "loadgen subprocess, and every server share "
                        "one core, so wake-up latency — not frame "
                        "transport or routing — floors both sides "
                        "of each pair (r9/r13 convention). The "
                        "MECHANISM is asserted instead: "
                        "`mechanism.shm` proves the B side carried "
                        "its frames over the mapped ring "
                        "(transport=shm, frames_shm>0), and "
                        "`mechanism.clientroute` proves the B side "
                        "ring-routed with zero downgrades while the "
                        "A side took the multi-node string "
                        "downgrade. Identity, hostile-peer, and "
                        "soak tests (tests/test_shm_lane.py, "
                        "tests/test_shm_hostile.py, "
                        "tests/test_ring_route.py) pin correctness."
                    ),
                    "mechanism": {
                        "shm": mech_shm,
                        "clientroute": mech_route,
                    },
                },
            },
            "gate": {
                "threshold": args.threshold,
                "measured": {
                    k: round(v, 4) for k, v in measured.items()
                },
                "paired_rounds": detail,
                "rows": rows,
                "passed": passed,
            },
            "injected_frame_delay_ms": args.inject_frame_ms,
        }
        pathlib.Path(args.json).write_text(
            json.dumps(doc, indent=1) + "\n"
        )
        print(f"wrote {args.json}", file=sys.stderr)

    return 0 if (passed or args.update_baseline) else 1


if __name__ == "__main__":
    sys.exit(main())
