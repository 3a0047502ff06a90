.PHONY: all native proto test bench readme readme-check profile-stages \
	profile-shed profile-trace chaos chaos-rolling \
	chaos-restore \
	perf-gate clean

all: native proto

native:
	$(MAKE) -C gubernator_tpu/native
	$(MAKE) -C gubernator_tpu/native/edge

proto:
	./scripts/gen_protos.sh

test:
	python -m pytest tests/ -x -q

bench:
	python bench.py

# README perf tables are GENERATED from the committed BENCH_* artifacts;
# `readme` rewrites them, `readme-check` is the CI drift gate (also run
# as a tier-1 test, tests/test_readme_tables.py)
readme:
	python scripts/gen_readme_tables.py

readme-check:
	python scripts/gen_readme_tables.py --check

# stage-attribution profile of the served pipeline (serve/stages.py via
# /v1/debug/stages): boots the device serving stack + compiled edge,
# drives the batched saturation shape, prints where the wall time goes.
# SECONDS/OUT are overridable: make profile-stages SECONDS=30 OUT=x.json
SECONDS ?= 10
OUT ?= BENCH_STAGES.json
profile-stages: native
	python scripts/profile_serving_stages.py --seconds $(SECONDS) \
	  --json $(OUT)

# over-limit shed cache A/B (r10): the bench_serving shed workload
# through the compiled edge door with instance.shed flipped between
# interleaved rounds, one series per over-limit share; reports paired
# per-round speedups + monotonicity. Overridable:
# make profile-shed SHED_SECONDS=5 SHED_ROUNDS=8 SHED_OUT=x.json
SHED_SECONDS ?= 3
SHED_ROUNDS ?= 6
SHED_SHARES ?= 0.0,0.5,0.9
SHED_OUT ?= BENCH_SHED.json
profile-shed: native
	python scripts/profile_shed.py --seconds $(SHED_SECONDS) \
	  --rounds $(SHED_ROUNDS) --shares $(SHED_SHARES) \
	  --json $(SHED_OUT)

# distributed-tracing overhead A/B (r16): the keyspace-30k zipf GEB
# workload with the tracer flipped between interleaved rounds (off vs
# GUBER_TRACE_SAMPLE=0.01); the paired median seeds the trace_r16
# perf-gate pair. Overridable:
# make profile-trace TRACE_SECONDS=5 TRACE_ROUNDS=8 TRACE_OUT=x.json
TRACE_SECONDS ?= 3
TRACE_ROUNDS ?= 6
TRACE_SAMPLE ?= 0.01
TRACE_OUT ?= BENCH_TRACE_r16.json
profile-trace:
	python scripts/profile_trace.py --seconds $(TRACE_SECONDS) \
	  --rounds $(TRACE_ROUNDS) --sample $(TRACE_SAMPLE) \
	  --json $(TRACE_OUT)

# continuous front-door perf gate (r12): replays the committed workload
# shapes (stages r7, submit r9, shed r10) with interleaved paired A/B
# rounds plus the public-door ladder (gRPC vs GEB client vs HTTP
# binary), and FAILS on a paired ratio more than PERF_GATE_THRESHOLD
# below the committed PERF_GATE_BASELINE.json manifest. Overridable:
# make perf-gate PERF_GATE_THRESHOLD=0.15 PERF_SECONDS=5 PERF_ROUNDS=6
PERF_GATE_THRESHOLD ?= 0.10
PERF_SECONDS ?= 3
PERF_ROUNDS ?= 4
PERF_OUT ?= BENCH_FRONTDOOR.json
perf-gate:
	python scripts/perf_gate.py --seconds $(PERF_SECONDS) \
	  --rounds $(PERF_ROUNDS) --threshold $(PERF_GATE_THRESHOLD) \
	  --json $(PERF_OUT) --global-artifact BENCH_GLOBAL_r20.json

# chaos soak (r8, + r11 quota-amnesia phase): 3-node cluster under load
# with a peer killed + restarted mid-run and GUBER_FAULT_SPEC injection
# active; asserts bounded error rate, breaker recovery, graceful drain,
# and that a tracked over-limit key STAYS over-limit across owner
# SIGKILL -> successor takeover -> restart -> reconcile
# (GUBER_REPLICATION bucket replication). SECONDS/OUT overridable:
# make chaos SECONDS=60 OUT=chaos.json
CHAOS_SECONDS ?= 30
CHAOS_OUT ?= BENCH_CHAOS_r11.json
chaos:
	python scripts/chaos_soak.py --seconds $(CHAOS_SECONDS) \
	  --json $(CHAOS_OUT)

# rolling-deploy soak (r17): the same 3 daemons on etcd discovery
# (in-process fake, real gRPC) with GUBER_RESCALE=1, every node
# SIGTERMed + restarted in sequence under live load; asserts ZERO
# under-admissions on a tracked over-limit canary through all six
# membership changes and handoff lag under 2 flush windows.
# make chaos-rolling ROLL_SECONDS=30 ROLL_OUT=x.json
ROLL_SECONDS ?= 20
ROLL_OUT ?= BENCH_RESCALE_r17.json
chaos-rolling:
	python scripts/chaos_soak.py --mode rolling \
	  --seconds $(ROLL_SECONDS) --json $(ROLL_OUT)

# full-fleet restore soak (r19): 3 daemons checkpointing to per-node
# GUBER_CHECKPOINT_DIR on a 250 ms cadence, the WHOLE fleet SIGKILLed
# at once (power event: no drain, no survivor) and restarted against
# the same directories under live load; asserts ZERO under-admissions
# on a tracked over-limit canary across every restore, nonzero
# restored_windows_total on every cycle, and restore lag within the
# staleness bound. make chaos-restore RESTORE_SECONDS=30 RESTORE_OUT=x.json
RESTORE_SECONDS ?= 20
RESTORE_OUT ?= BENCH_RESTORE_r19.json
chaos-restore:
	python scripts/chaos_soak.py --mode restore \
	  --seconds $(RESTORE_SECONDS) --json $(RESTORE_OUT)

clean:
	$(MAKE) -C gubernator_tpu/native clean
	$(MAKE) -C gubernator_tpu/native/edge clean
