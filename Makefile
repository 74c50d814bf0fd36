PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: lint test test-all test-fuzz bench-smoke bench bench-compare calibrate ci

# static invariant analysis (repro.analysis): retrace-hazard,
# oracle-twin, dtype-packing and sweep-registry passes; writes the
# findings summary to ANALYSIS.json and fails on any finding
lint:
	$(PYTHON) -m repro.analysis --fail-on-findings --json ANALYSIS.json

# fast suite (<1 min): everything except the @slow big-model smokes and
# exhaustive grids
test:
	$(PYTHON) -m pytest -x -q -m "not slow"

# tier-1 verify (ROADMAP.md): the whole suite, slow tests included
test-all:
	$(PYTHON) -m pytest -x -q

# differential crash-point conformance fuzzing at a raised budget
# (engine <-> oracle; see tests/test_crash_differential.py)
test-fuzz:
	CRASH_FUZZ_SEEDS=20 CRASH_FUZZ_EXAMPLES=150 \
	$(PYTHON) -m pytest -x -q tests/test_crash_differential.py

# each figure on a tiny trace (<60s); writes BENCH_engine.json
bench-smoke:
	$(PYTHON) -m benchmarks.run --smoke

# regenerate the smoke report and diff it against the committed
# baseline (git show HEAD:BENCH_engine.json): prints per-sweep speedup
# ratios, fails on a >1.25x regression of ANY numeric *_wall_s
# (total_wall_s included); rows without a numeric baseline warn
bench-compare: bench-smoke
	$(PYTHON) -m benchmarks.compare

# full paper-budget benchmark CSV
bench:
	$(PYTHON) -m benchmarks.run

calibrate:
	$(PYTHON) -m benchmarks._calibrate

# CI lane: static invariant analysis first (seconds; fails fast on a
# broken contract), then fast tests (including the depth differential's
# fast chain matrix; the >=500-cell depth-4 matrix runs behind the
# `slow` marker in `test-all`), then the smoke benchmarks + wall-clock
# regression diff
# against the committed report (benchmarks/compare.py), then the
# compile-count regression guard (the shared grid / recovery sweep /
# tenant sweep / QoS sweep / chain depth sweep must each stay exactly
# ONE XLA program, macro-stepping enabled, with per-sweep macro hit
# rates recorded — see benchmarks/check_compiles.py)
ci: lint test bench-compare
	$(PYTHON) -m benchmarks.check_compiles
