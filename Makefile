# Developer entry points. Tier-1 gate command lives in ROADMAP.md.

PY ?= python

.PHONY: lint analyze gen-registry test test-slow tier1 chip-smoke bench trace-report ckpt-bench serve-bench spec-bench pipeline-bench degrade-bench policy-bench sim-bench grow-bench overlap-bench master-bench goodput-bench pool-bench router-bench

# Lint = the project-native analyzer (always available, stdlib-only)
# plus ruff (config in pyproject.toml). Ruff degrades to a skip when not
# installed — the hermetic CI image does not ship it, and the gate must
# not fail on a missing optional tool.
lint: analyze
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check . && echo "lint OK"; \
	elif $(PY) -c "import ruff" >/dev/null 2>&1; then \
		$(PY) -m ruff check . && echo "lint OK"; \
	else \
		echo "ruff not installed; skipping lint (config: pyproject.toml [tool.ruff])"; \
	fi

# oobleck-lint: rules OBL001-OBL006 (oobleck_tpu/analysis). Exit nonzero
# on any finding that is neither suppressed inline nor baselined. Also
# verifies the generated observability registry is fresh.
analyze:
	$(PY) -m oobleck_tpu.analysis
	$(PY) -m oobleck_tpu.analysis.genregistry --check

# Regenerate oobleck_tpu/obs/registry.py from the tree's literal metric/
# flight-event/span names (rule OBL005 checks against it; strict runtime
# enforcement via OOBLECK_STRICT_REGISTRY=1).
gen-registry:
	$(PY) -m oobleck_tpu.analysis.genregistry

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

test-slow:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m slow -p no:cacheprovider

# The exact tier-1 gate command from ROADMAP.md (timeout, log tee, dot
# count and all), so "make tier1" and the driver can never diverge.
tier1:
	bash -c "set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=\$${PIPESTATUS[0]}; echo DOTS_PASSED=\$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?\$$' /tmp/_t1.log | tr -cd . | wc -c); exit \$$rc"

# The quickest proof that the system still starts on the chip: kernels vs
# the XLA reference, gpt2 124M through master -> agent -> worker, then the
# server on the checkpoint it wrote. Needs a TPU (one chip; fails without).
# The cross-chip path is `$(PY) chip_smoke.py --chips 4` on a four-chip host.
chip-smoke:
	$(PY) chip_smoke.py

# Needs an accelerator: measures in-process and names the device on its
# line; exits non-zero with no number when JAX finds only the CPU.
bench:
	$(PY) bench.py

# Incident forensics report: phase breakdowns of every committed
# incident-<n>.json under $$OOBLECK_METRICS_DIR (or ./metrics), plus a
# merged Perfetto trace when TRACE_OUT is set.
# Usage: make trace-report [OOBLECK_METRICS_DIR=...] [TRACE_OUT=trace.json]
trace-report:
	JAX_PLATFORMS=cpu $(PY) -m oobleck_tpu.obs.report \
		$(if $(TRACE_OUT),--trace $(TRACE_OUT),)

# Checkpoint-stall microbench: async writer vs sync baseline p50/p99
# (oobleck_tpu/ckpt/bench.py; also folded into bench.py's "ckpt" key).
ckpt-bench:
	JAX_PLATFORMS=cpu $(PY) -m oobleck_tpu.ckpt.bench

# Serving-plane microbench: tokens/sec, TTFT p50/p99, hot-reload pause vs
# full restore (oobleck_tpu/serve/bench.py; also under bench.py's "serve"
# key).
serve-bench:
	JAX_PLATFORMS=cpu $(PY) -m oobleck_tpu.serve.bench

# Speculative-decode microbench: lookup-draft + multi-token verify vs the
# k=0 one-token baseline on an acceptance-friendly workload
# (oobleck_tpu/serve/spec_bench.py; also under bench.py's "spec" key).
spec-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= $(PY) -m oobleck_tpu.serve.spec_bench

# Pipeline-schedule microbench: 1F1B vs interleaved tokens/sec and
# schedule-replay bubble on 2 virtual CPU devices (also under bench.py's
# "pipeline" key). Pure CPU — runs the same with or without a TPU.
pipeline-bench:
	JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=2" \
		$(PY) bench.py --pipeline

# Degraded-mode recovery microbench: reroute vs template re-instantiation
# recovery-to-next-step latency + throughput retention on 4 virtual CPU
# devices (2 hosts x 2 chips; also under bench.py's "degrade" key).
degrade-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		XLA_FLAGS="--xla_force_host_platform_device_count=4" \
		$(PY) -m oobleck_tpu.degrade.bench

# Simulated-SLO bench: every scenario family at 64 hosts plus the
# 1024-host churn storm, with an in-run determinism check (also under
# bench.py's "sim" key, diffed by bench --diff). Jax-free, CPU-only,
# bounded well under a minute.
sim-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		$(PY) -m oobleck_tpu.sim.bench

# Adaptive recovery policy vs each forced mechanism under scripted churn
# (single-host loss + correlated double loss). 8 virtual devices: 4 hosts.
policy-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PY) -m oobleck_tpu.policy.bench

# Collective/compute overlap: comm-hidden fraction (overlapped vs
# compute-only vs ring-alone arms), serialized vs overlapped tokens/sec,
# bucketed-ring grad parity, flash-vs-xla pallas-interpret sub-key on 8
# virtual CPU devices (also under bench.py's "overlap" key, diffed by
# bench --diff). CPU numbers are a scheduling proxy; device truth is TPU.
overlap-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PY) -m oobleck_tpu.parallel.overlap_bench

# Grow plane: join-to-first-post-grow-step per grow arm (absorb_spare /
# grow_dp / grow_reshape / adaptive) on a 2-host rig growing by 2
# joiners. 8 virtual devices: 4 bound at start, 4 free for the arrivals
# (also under bench.py's "grow" key, diffed by bench --diff).
grow-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PY) -m oobleck_tpu.policy.grow_bench

# Fleet-health/goodput plane: straggler scenario through the real
# detector + policy chain (goodput fraction, detect-to-drain latency)
# plus the telemetry ring's and goodput ledger's per-step overhead vs a
# pessimistic 1 ms synthetic step — the < 1% hot-path bar (also under
# bench.py's "goodput" key, diffed by bench --diff). Jax-free, CPU-only.
goodput-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		$(PY) -m oobleck_tpu.obs.goodput_bench

# Control-plane outage: journaling master killed mid-job, restarted
# against its journal — restart-to-reconciled latency (replay + every
# REATTACH + the reattach window) and the stale-membership case where a
# host died DURING the outage and recovery must come from the journal
# alone. Real sockets, scripted agent clients, no workers (also under
# bench.py's "master" key, diffed by bench --diff).
master-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		$(PY) -m oobleck_tpu.elastic.master_bench

# Shared chip pool: one full borrow/return cycle under a traffic_wave
# chaos peak — serve pressure prices the peak as SLO debt, the arbiter
# grants a lease off the training fleet (proactive drain, zero
# respawns), and the chips ride the grow path home off-peak. Real
# sockets + a real serve plane on a tiny model (also under bench.py's
# "pool" key, diffed by bench --diff).
pool-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		$(PY) -m oobleck_tpu.pool.bench

# Multi-replica serving router: 1-vs-3 replica scaling through one
# router address, prefix-affine vs random routing hit rates, a chaos
# kill_replica absorbed mid-traffic with zero failed idempotent
# requests, and a pool borrow -> replica scale-out -> reclaim -> drain
# cycle against a scripted-agent training master. Real sockets + a
# tiny model (also under bench.py's "router" key, diffed by --diff).
router-bench:
	JAX_PLATFORMS=cpu OOBLECK_METRICS_DIR= \
		$(PY) -m oobleck_tpu.serve.router.bench
