# Developer entry points (reference: go-ibft Makefile — lint / builds-dummy /
# protoc targets).  Translated to this build's toolchain.
.PHONY: test test-fast test-slow test-device lint native benchmark dryrun \
	clean warm cluster-soak chain-soak compile-budget compile-budget-check \
	ab-keccak sched-soak timeline-smoke slo-gates cost-report boot-check \
	byzantine-soak fleet-smoke

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
	ruff check go_ibft_tpu/ tests/ scripts/ examples/ __graft_entry__.py
	python -m compileall -q go_ibft_tpu/ tests/ scripts/ examples/

# Build the native C++ runtime baseline (also auto-built on first import)
native:
	python -c "from go_ibft_tpu import native; assert native.load() is not None, native.build_error()"

# The benchmark (BENCHMARK.json, PERF.md §2): one cell once, in a new
# process, on a TPU (rc 3 without one; there is no CPU mode).
# make benchmark CELL=ecdsa-100v.flood [SEED=7] [TRACE=1]
SEED ?= 7
TRACE ?= 0
benchmark:
	python3 benchmark/run.py --workload $(CELL) --seed $(SEED) --seconds 20 --trace $(TRACE)

# Fast second-boot cache proof (CI fast tier, ~15 s): warm the cheap
# digest family twice against one FRESH cache dir under the checkout's
# ignored .cache/, handed over as JAX_COMPILATION_CACHE_DIR.  Run 1 must
# classify + record the cold compile (GO_IBFT_CACHE_MIN_COMPILE_S=0
# persists the digest past jax's 1 s floor, so the cache reports its
# miss); run 2 must pay zero cold compiles (--assert-warm) AND cost <50%
# of run 1 per family (scripts/boot_check.py — ratio, not absolute).
boot-check:
	rm -rf .cache/boot_check && mkdir -p .cache/boot_check
	JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR=$(CURDIR)/.cache/boot_check/xla \
	GO_IBFT_CACHE_MIN_COMPILE_S=0 \
	python scripts/warm_kernels.py --aot-only --programs digest_words_8l \
		--manifest .cache/boot_check/m1.json
	JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR=$(CURDIR)/.cache/boot_check/xla \
	GO_IBFT_CACHE_MIN_COMPILE_S=0 \
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

# Runtime cost-ledger smoke (ISSUE 14, fast-tier CI): a small host-route
# drain with the ledger on must render the per-program report (top
# programs by wall time, live-vs-padded occupancy, compile table) with
# every pinned compile-budget family that ran appearing in it.
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
# shed/quarantine counts): a liveness regression fails CI
# (go_ibft_tpu/obs/gates.py::gate_slo_records)
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

# Fleet smoke (fast-tier CI, every push): 2 validator processes over
# real sockets under a small proof flood, SLO-gated (scripts/fleet.py
# exits nonzero on any gate breach or missing drain report).
fleet-smoke:
	rm -f slo.jsonl
	JAX_PLATFORMS=cpu GO_IBFT_SLO_PATH=slo.jsonl \
	python scripts/fleet.py --nodes 2 --heights 2 --connections 16 \
		--churn-clients 1 --slowloris-clients 1 --think-s 0.2 \
		--min-flood-s 1.5

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
