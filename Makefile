# Developer/CI entry points. The lint gate is the same analyzer the
# fast pytest lane runs (tests/test_analysis.py); see
# docs/static_analysis.md for the rule catalog and baseline workflow.

PY ?= python

.PHONY: lint lint-changed lint-ci lint-baseline test test-fast serve-fleet

# whole package, all rules (per-file + the cross-module concurrency
# tier); the project index is cached in .fslint_cache.json
lint:
	$(PY) -m fengshen_tpu.analysis --json

# hot-loop variant: lint only files dirty vs HEAD (plus untracked) —
# the concurrency rules still index the whole package for context
lint-changed:
	$(PY) -m fengshen_tpu.analysis --changed

# CI surface: a SARIF 2.1.0 log for code-scanning upload (hashseed
# pinned so the artifact is byte-stable run to run) plus ::error
# workflow annotations inline in the job log; fails on any
# non-baselined finding, like `lint`
lint-ci:
	PYTHONHASHSEED=0 $(PY) -m fengshen_tpu.analysis \
		--format=sarif --stats > fslint.sarif
	$(PY) -m fengshen_tpu.analysis --format=github

# local fleet: spawn $(N) api replicas from the api config
# $(CONFIG) and front them with the router on port $(PORT)
# (docs/fleet.md), e.g.
#     make serve-fleet CONFIG=generation.json N=3 PORT=8080
serve-fleet:
	@test -n "$(CONFIG)" || \
		{ echo "usage: make serve-fleet CONFIG=<api config json> [N=3] [PORT=8080]"; exit 2; }
	$(PY) -m fengshen_tpu.fleet \
		--spawn $(or $(N),3) --config $(CONFIG) \
		--port $(or $(PORT),8080)

lint-baseline:
	$(PY) -m fengshen_tpu.analysis --write-baseline

test-fast:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q
