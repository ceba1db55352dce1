# Developer entry points (reference: go-ibft Makefile — lint / builds-dummy /
# protoc targets).  Translated to this build's toolchain.
.PHONY: test test-fast test-slow test-device lint native bench dryrun clean \
	warm cluster-bench cluster-soak obs-report chain-soak mesh-bench compile-budget \
	compile-budget-check ab-keccak tenant-bench sched-soak latency-smoke \
	serve-bench timeline-smoke slo-gates multipair-bench cost-report \
	boot-bench boot-check byzantine-smoke byzantine-soak fleet-bench \
	fleet-smoke checkpoint-smoke

test:
	python -m pytest tests/ -q

test-fast:
	python -m pytest tests/ -q -m "not slow"

test-slow:
	python -m pytest tests/ -q -m slow

# Device suites on real hardware (opt-in, see tests/conftest.py)
test-device:
	GO_IBFT_TPU_TESTS=1 python -m pytest tests/ -q

lint:
	ruff check go_ibft_tpu/ tests/ scripts/ examples/ bench.py __graft_entry__.py
	python -m compileall -q go_ibft_tpu/ tests/ scripts/ examples/ bench.py

# Build the native C++ runtime baseline (also auto-built on first import)
native:
	python -c "from go_ibft_tpu import native; assert native.load() is not None, native.build_error()"

bench:
	python bench.py

# Mesh-sharding bench (config #8) on forced host devices: exercises the
# SHARDED verify route in CI without TPU hardware.  The persistent XLA
# cache absorbs the shard_map compiles after the first run.  Budget
# note: the XLA:CPU ladder costs ~69 ms/lane on a 1-core host, so the
# default 2048-lane sweep runs ~25 min cold; the 1800 s budget skips
# whatever doesn't fit with explicit notes (rc stays 0).
# GO_IBFT_MESH_LANES=8192 opts into the full acceptance shape.
mesh-bench:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	GO_IBFT_MESH_BENCH=1 GO_IBFT_BENCH_BUDGET_S=1800 \
	python bench.py --mesh-only

# Multi-tenant bench (config #10): N concurrent real-crypto chains
# through ONE process-wide TenantScheduler vs the same chains run
# serially.  GO_IBFT_TENANTS overrides the 8-chain default.
tenant-bench:
	JAX_PLATFORMS=cpu GO_IBFT_BENCH_BUDGET_S=900 \
	python bench.py --tenant-only

# Commit-critical-path latency smoke (config #11): proposal-accept ->
# finalize p50/p99 at 100 validators on the host route, speculation +
# early-exit ON vs OFF under a byte-identical lagging-replica arrival
# schedule.  Fast-tier CI entry; verdicts oracle-gated per height.
latency-smoke:
	JAX_PLATFORMS=cpu GO_IBFT_BENCH_BUDGET_S=600 \
	python bench.py --latency-only

# Light-client proof serving (config #12): cold/warm ProofCache, M
# concurrent clients through the coalesced read plane vs per-client
# sequential verification, and the consensus-vs-proof-flood QoS bound.
# Fast-tier CI entry; lane verdicts oracle-gated before timing.
# GO_IBFT_SERVE_CLIENTS overrides the client count.
serve-bench:
	JAX_PLATFORMS=cpu GO_IBFT_BENCH_BUDGET_S=600 \
	python bench.py --serve-only

# Batched multi-pairing (config #13): N-cert batched certificate verify
# (ONE dispatch, oracle-gated against the per-cert loop incl. seeded
# corrupt certs) vs sequential aggregate_check, plus the
# 100/300/1000-validator committee sweep.  GO_IBFT_MULTIPAIR_BENCH=1
# additionally runs the vmapped g2 merge-tree KERNEL on forced host
# devices (the mesh-bench posture: exercise the real device route
# without TPU hardware; the merge program is small, unlike the pairing).
# GO_IBFT_MULTIPAIR_CERTS / GO_IBFT_MULTIPAIR_SIZES scale the run.
multipair-bench:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	GO_IBFT_MULTIPAIR_BENCH=1 GO_IBFT_BENCH_BUDGET_S=900 \
	python bench.py --multipair-only

# Boot warm-start bench (config #14): restart-to-first-finalized in
# REAL child processes, cold persistent cache vs warm (>=5x acceptance,
# zero cold-compile events on the second boot), plus the tenant-churn
# soak (live add/remove/reconfigure; survivors miss no heights).
# GO_IBFT_BOOT_BENCH_PROGRAM / GO_IBFT_BOOT_BENCH_CACHED_RUNS scale it.
boot-bench:
	JAX_PLATFORMS=cpu GO_IBFT_BENCH_BUDGET_S=600 \
	python bench.py --boot-only

# Fast second-boot cache proof (CI fast tier, ~15 s): warm the cheap
# digest family twice against one FRESH cache dir under the checkout's
# ignored .cache/, handed over as JAX_COMPILATION_CACHE_DIR.  Run 1 must
# classify + record the cold compile (GO_IBFT_BOOT_COLD_S lowered under
# the digest's ~0.4 s compile; GO_IBFT_CACHE_MIN_COMPILE_S=0 persists
# it past jax's 1 s floor); run 2 must pay zero cold compiles
# (--assert-warm) AND cost <50% of run 1 per family (scripts/
# boot_check.py — ratio, not absolute, so runner speed can't flake it).
boot-check:
	rm -rf .cache/boot_check && mkdir -p .cache/boot_check
	JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR=$(CURDIR)/.cache/boot_check/xla \
	GO_IBFT_CACHE_MIN_COMPILE_S=0 GO_IBFT_BOOT_COLD_S=0.15 \
	python scripts/warm_kernels.py --aot-only --programs digest_words_8l \
		--manifest .cache/boot_check/m1.json
	JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR=$(CURDIR)/.cache/boot_check/xla \
	GO_IBFT_CACHE_MIN_COMPILE_S=0 GO_IBFT_BOOT_COLD_S=0.15 \
	python scripts/warm_kernels.py --aot-only --no-skip --assert-warm \
		--programs digest_words_8l \
		--manifest .cache/boot_check/m2.json
	python scripts/boot_check.py .cache/boot_check/m1.json \
		.cache/boot_check/m2.json

# Multi-tenant fairness soak: hot + slow chains sharing one scheduler
# under seeded chaos (tests/test_sched_consensus.py, slow tier included)
sched-soak:
	python -m pytest tests/test_sched.py tests/test_sched_consensus.py -q

# Stablehlo-line budgets for the hot programs, incl. the mesh program at
# dp=2/4/8 (trace size IS cold-compile time on XLA:CPU).  CI runs the
# --check ratchet (>2% growth fails); the bare target keeps 10% local
# slack.
compile-budget:
	python scripts/compile_budget.py

compile-budget-check:
	python scripts/compile_budget.py --check

# Pallas keccak A/B in CI's forced-host mode: interpret-mode execution +
# bit-exact parity vs the XLA route (skips with reason when Pallas is
# unavailable on the pinned jax); real perf numbers need a live TPU.
ab-keccak:
	python scripts/ab_keccak.py --cpu --sizes 8,64 --reps 3

# Regression gates: fresh bench evidence (bench_evidence.jsonl) vs the
# best prior BENCH_r*.json on the same backend (go_ibft_tpu/obs/gates.py)
obs-report:
	python scripts/obs_report.py

# Runtime cost-ledger smoke (ISSUE 14, fast-tier CI): a small host-route
# drain with the ledger on must render the per-program report (top
# programs by device time, live-vs-padded occupancy, compile table) with
# every pinned compile-budget family that ran appearing in it.  After a
# bench run, `python scripts/cost_report.py` (no --drain) reports over
# the run's cost_ledger.json / compile_ledger.jsonl instead.
cost-report:
	JAX_PLATFORMS=cpu python scripts/cost_report.py --drain --check

# Telemetry-plane smoke (ISSUE 11, fast-tier CI): a 4-node loopback chain
# with /metrics,/healthz,/statusz mounted is scraped WHILE finalizing,
# its flight-recorder trace is reconstructed into the per-height
# consensus critical path, and the run's SLO records are graded.
timeline-smoke:
	rm -f slo.jsonl
	JAX_PLATFORMS=cpu GO_IBFT_SLO_PATH=slo.jsonl \
	python scripts/timeline_smoke.py

# SLO gates over soak-emitted records (missed_heights, finalize p99,
# shed/quarantine counts): liveness regressions fail CI exactly like
# perf regressions (go_ibft_tpu/obs/gates.py::gate_slo_records)
slo-gates:
	python scripts/slo_gates.py

# Pre-warm the expensive kernel compiles into the persistent XLA cache
# (CI slow tier runs this before pytest so no compile hits a test timeout)
warm:
	python scripts/warm_kernels.py

# Chain-layer soaks: the tier-1 smoke plus the slow 30-node/20-height
# ChainRunner soak under seeded chaos drops (tests/test_chain_soak.py)
chain-soak:
	python -m pytest tests/test_chain_soak.py tests/test_chain.py \
		tests/test_chain_sync.py -q

# Lock-step cluster bench (config #15): 100-validator lock-step cluster
# vs threaded loopback at matched size (chain-identity oracle gated
# before timing, >=3x acceptance) plus the 1000-validator one-dispatch
# structural tick.  GO_IBFT_CLUSTER_NODES / GO_IBFT_CLUSTER_HEIGHTS /
# GO_IBFT_CLUSTER_STRUCT_NODES scale it; scripts/cluster_bench.py is
# the exploratory one-transport sweep driver.
cluster-bench:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	GO_IBFT_BENCH_BUDGET_S=600 \
	python bench.py --cluster-only

# Byzantine adversary smoke (config #16, fast-tier CI): one 100-
# validator lock-step cluster over the wan3 geo-latency preset, run
# clean then degraded by a seeded 30%-power strategy mix (equivocating
# proposers, COMMIT withholders, round-change spammers, stale-height
# replayers) with the invariant harness checking agreement / validity /
# bounded-rounds-after-GST on every tick of both runs.  Any violation
# or missed honest height fails; the printed CHAOS-REPLAY line re-runs
# the exact scenario via scripts/chaos_replay.py --line.
# GO_IBFT_BYZ_NODES / _HEIGHTS / _SEED / _POWER / _PRESET scale it.
byzantine-smoke:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	GO_IBFT_BENCH_BUDGET_S=600 \
	python bench.py --byzantine-only

# Multi-process fleet bench (config #17): 4 REAL `python -m
# go_ibft_tpu.node` validator subprocesses gossiping IBFT over TCP
# while a concurrent client fleet + seeded churn/slowloris adversaries
# flood their proof APIs.  QoS-gated before timing (no missed height,
# no cross-process chain divergence, every slowloris socket cut);
# metric = proofs/s.  GO_IBFT_FLEET_NODES / _HEIGHTS / _CONNS / _CHURN
# / _SLOW / _SEED / _THINK_S scale it.
fleet-bench:
	JAX_PLATFORMS=cpu \
	GO_IBFT_BENCH_BUDGET_S=600 \
	python bench.py --fleet-only

# Fleet smoke (fast-tier CI, every push): 2 validator processes over
# real sockets under a small proof flood, SLO-gated (scripts/fleet.py
# exits nonzero on any gate breach or missing drain report).
fleet-smoke:
	rm -f slo.jsonl
	JAX_PLATFORMS=cpu GO_IBFT_SLO_PATH=slo.jsonl \
	python scripts/fleet.py --nodes 2 --heights 2 --connections 16 \
		--churn-clients 1 --slowloris-clients 1 --think-s 0.2 \
		--min-flood-s 1.5

# Checkpoint cold-sync smoke (config #18, fast-tier CI): real-crypto
# epoch checkpoint certificates + O(log n) skip sync over a live HTTP
# proof API, SLO-gated before timing — <= 4 batched pairing dispatches,
# checkpoint bytes <= 1% of the same-run linear diff-walk baseline, and
# the fabricated-diff splice attack rejected at the commitment check.
# Scaled down for the fast tier (the 1M-height structural shape runs at
# the bench defaults); GO_IBFT_CKPT_HEIGHTS / _SPACING / _CLIENTS /
# _DEPTH_POOL / _SEED scale it.
checkpoint-smoke:
	JAX_PLATFORMS=cpu \
	GO_IBFT_BENCH_BUDGET_S=600 \
	GO_IBFT_CKPT_HEIGHTS=100000 GO_IBFT_CKPT_SPACING=500 \
	GO_IBFT_CKPT_CLIENTS=2000 GO_IBFT_CKPT_DEPTH_POOL=4 \
	python bench.py --checkpoint-only

# Slow-tier byzantine soak: 3 seeds x the full strategy matrix at 12
# validators over WAN chaos, every invariant checked every tick
# (tests/test_adversary.py slow tier)
byzantine-soak:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	python -m pytest tests/test_adversary.py -q -m slow

# Slow-tier cluster soak: the 1000-validator lock-step smoke plus the
# seeded 100-validator chaos-mask runs (tests/test_cluster_sim.py)
cluster-soak:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	python -m pytest tests/test_cluster_sim.py -q -m slow

dryrun:
	python __graft_entry__.py

clean:
	rm -rf go_ibft_tpu/native/_build
	find . -name __pycache__ -type d -prune -exec rm -rf {} +
