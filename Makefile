# Developer entry points.  PYTHONPATH=src is the only environment the repo
# needs (ROADMAP.md "Tier-1 verify").

PY := PYTHONPATH=src python

.PHONY: verify test bench bench-risk bench-serve bench-chaos bench-region \
        docs-check check-skips

## tier-1 gate: full test suite (junitxml-audited: every skip must be in
## tests/skip_registry.py) + the docs gate (README quickstart runs,
## DESIGN.md refs resolve)
verify:
	$(PY) -m pytest -x -q --junitxml=.pytest-report.xml
	$(PY) tools/check_skips.py .pytest-report.xml
	$(PY) tools/docs_check.py

## audit the last test run's skips against the registered-skip table
check-skips:
	$(PY) tools/check_skips.py .pytest-report.xml

## smoke-run README quickstart code blocks; fail on dangling DESIGN.md §refs
docs-check:
	$(PY) tools/docs_check.py

test:
	$(PY) -m pytest -q

## full paper figure/table sweep (slow; compiles dry-run cells)
bench:
	$(PY) -m benchmarks.run

## risk-subsystem backtest (kubepacs_risk vs kubepacs + forecast
## calibration); refreshes BENCH_risk.json
bench-risk:
	$(PY) -m benchmarks.bench_risk --json BENCH_risk.json

## serving co-simulation (serving_slo vs karpenter_like/kubepacs/… on
## diurnal/bursty/flash; in-bench determinism + zero-infeasibility
## verification); refreshes BENCH_serve.json
bench-serve:
	$(PY) -m benchmarks.bench_serve --json BENCH_serve.json

## chaos fault-storm sweep (hardened degradation ladder vs naive plane on
## feed/ice/solver/combined storms; in-bench determinism + inertness
## verification); refreshes BENCH_chaos.json
bench-chaos:
	$(PY) -m benchmarks.bench_chaos --json BENCH_chaos.json

## multi-region failover sweep (hardened failover rung vs region-pinned
## strawman through the correlated regional storm; in-bench determinism +
## single-region/identity-config inertness verification); refreshes
## BENCH_region.json
bench-region:
	$(PY) -m benchmarks.bench_region --json BENCH_region.json
