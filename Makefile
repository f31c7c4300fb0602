# Developer entry points. `make tier1` is the command the driver gates a PR on.

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

# The command the driver runs after every PR (`commands` of its
# /root/TESTS_LAST_RUN.json: six xdist workers, a file a worker, 1,470 s,
# the junit file's count), so "make tier1" and the driver do not diverge.
tier1: SHELL := /bin/bash
tier1:
	@set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; said=$$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$$1-$$2-$$3-$$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=$${said:-$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $$rc

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
