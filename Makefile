# Test tiers.  `make smoke` is the tier-1 inner loop (<60 s): core
# semantics, kernel parity smoke, golden regressions, roofline.  `make test`
# is the full suite (~10 min; the slow tier spawns multi-device
# subprocesses and training loops).  `make lint` runs ruff when installed
# plus the stdlib fallback linter (tools/lint.py) always, so the gate works
# in the minimal container too.

PYTHONPATH_SRC := PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH))
PYTEST := $(PYTHONPATH_SRC) python -m pytest
LINT_PATHS := src tests benchmarks examples tools

.PHONY: smoke train-smoke serve-smoke chaos-smoke obs-smoke test lint \
	tune tune-smoke

# `smoke`, `train-smoke`, and `serve-smoke` partition the fast tier
# (silicon-training tests are owned by `train-smoke`, serving-engine and
# preemption tests by `serve-smoke`), so CI can run all three without
# executing anything twice; together they are the whole tier-1 set.
smoke:
	$(PYTEST) -q -m "fast and not slow" \
		--ignore=tests/test_silicon_train.py \
		--ignore=tests/test_serve_engine.py \
		--ignore=tests/test_serve_preempt.py

# Tier-1 silicon-training gate: the 20-step loss-decrease smoke plus the
# fast-marked gradient-parity subset of tests/test_silicon_train.py.
train-smoke:
	$(PYTEST) -q -m "fast and not slow" tests/test_silicon_train.py

# Tier-1 serving gate: continuous-batching engine parity (bitwise vs the
# one-shot forward, clean and noisy), scheduler/bucketing bugfix pins,
# the BatchedEngine rng/round accounting tests, and the preemptive-
# scheduling suite (checkpoint/restore parity, shedding, validation).
serve-smoke:
	$(PYTEST) -q -m "fast and not slow" tests/test_serve_engine.py \
		tests/test_serve_preempt.py

# Chaos gate: adversarial serving traces (oversized bursts, malformed
# tensors, randomized mid-round preemptions, hog+shorts fairness,
# deadline storms) with hard invariant assertions; nonzero on violation.
chaos-smoke:
	$(PYTHONPATH_SRC) python tools/chaos_serve.py --smoke

# Observability gate: a traced 6-request engine run must export a
# Perfetto-loadable timeline (slot residency + scheduler phases +
# checkpoint transfers) whose metric counters equal the engine ledgers;
# the exported file is then re-validated by the standalone checker.
obs-smoke:
	$(PYTHONPATH_SRC) python tools/obs_report.py --smoke \
		--trace-out /tmp/obs_smoke_trace.json \
		--metrics-out /tmp/obs_smoke_metrics.json
	python tools/obs_report.py --check /tmp/obs_smoke_trace.json

test:
	$(PYTEST) -x -q

lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check $(LINT_PATHS); \
	else \
		echo "ruff not installed; stdlib fallback only (CI runs ruff)"; \
	fi
	python tools/lint.py $(LINT_PATHS)

# Regenerate the persistent tile-plan cache (PLAN_CACHE_fused_macro.json):
# autotune the canonical launch shapes on this machine and persist the
# winners plan_tiles will consume.  OBJECTIVE: ms | pj_per_sop | blend.
OBJECTIVE := ms
tune:
	$(PYTHONPATH_SRC) python tools/tune_plans.py --objective $(OBJECTIVE)

# CI smoke for the tune subsystem: one tiny cell, 2 timed iters, written
# to a throwaway path, asserting the cache round-trips into plan_tiles.
tune-smoke:
	$(PYTHONPATH_SRC) python tools/tune_plans.py --smoke \
		--out /tmp/plan_cache_smoke.json
