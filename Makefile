# Developer entry points. Tier-1 gate command lives in ROADMAP.md.

PY ?= python

.PHONY: lint analyze gen-registry test test-slow tier1 chip-smoke trace-report

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

# Incident forensics report: phase breakdowns of every committed
# incident-<n>.json under $$OOBLECK_METRICS_DIR (or ./metrics), plus a
# merged Perfetto trace when TRACE_OUT is set.
# Usage: make trace-report [OOBLECK_METRICS_DIR=...] [TRACE_OUT=trace.json]
trace-report:
	JAX_PLATFORMS=cpu $(PY) -m oobleck_tpu.obs.report \
		$(if $(TRACE_OUT),--trace $(TRACE_OUT),)
