# Developer/CI entry points. The lint gate is the same analyzer the
# fast pytest lane runs (tests/test_analysis.py); see
# docs/static_analysis.md for the rule catalog and baseline workflow.

PY ?= python

.PHONY: lint lint-changed lint-ci lint-baseline test test-fast \
	serve-bench \
	serve-bench-parity serve-bench-spec serve-bench-fleet \
	serve-bench-disagg serve-bench-evac serve-bench-multimodal \
	serve-bench-stream \
	serve-fleet \
	kernel-bench benchdiff

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

# offline serving microbench (docs/serving.md): continuous batching vs
# sequential per-request decode, one JSON line. Pinned to the CPU: it
# checks the harness and the scheduler's counts, its rates say nothing
# about the chip
serve-bench:
	JAX_PLATFORMS=cpu $(PY) -m fengshen_tpu.serving.bench

# KV memory-parity mode (docs/performance.md): slot vs paged vs
# paged+int8 at the SAME KV byte budget — max concurrent admitted and
# aggregate tokens/s per variant, one BENCH-schema JSON line
serve-bench-parity:
	JAX_PLATFORMS=cpu SERVE_BENCH_MODE=memory_parity \
		SERVE_BENCH_BUCKETS=32,128 SERVE_BENCH_NEW_TOKENS=32 \
		$(PY) -m fengshen_tpu.serving.bench

# speculative-decode microbench (docs/serving.md "Speculative
# decoding"): committed tokens per target forward + aggregate tokens/s
# of the prompt-lookup engine vs the same engine with spec off, on a
# self-repetitive workload — one BENCH-schema JSON line on CPU
serve-bench-spec:
	JAX_PLATFORMS=cpu SERVE_BENCH_MODE=spec \
		SERVE_BENCH_BUCKETS=32,64 SERVE_BENCH_NEW_TOKENS=96 \
		$(PY) -m fengshen_tpu.serving.bench

# multimodal micro-batch engines (docs/serving.md "Multimodal
# engines"): batch_image (Taiyi-SD denoise loop) and embedding
# (Taiyi-CLIP text tower) engine requests/s vs the sequential
# one-call-per-request path, on the small-test towers — one
# BENCH-schema JSON line per engine type, each carrying `engine_type`
serve-bench-multimodal:
	JAX_PLATFORMS=cpu SERVE_BENCH_MODE=multimodal \
		$(PY) -m fengshen_tpu.serving.bench

# streaming-tier microbench (docs/streaming.md): TTFT first-byte vs
# last-byte at 8 concurrent SSE streams, self-draft committed tokens
# per target forward vs prompt-lookup on NON-repetitive traffic, and
# the kill-mid-stream gapless rung through the real fleet router —
# one BENCH-schema JSON line carrying `stream`/`spec_mode`
serve-bench-stream:
	JAX_PLATFORMS=cpu SERVE_BENCH_MODE=stream \
		$(PY) -m fengshen_tpu.streaming.bench

# fleet-router microbench (docs/fleet.md): aggregate tokens/s over
# N=3 stdlib api replica subprocesses vs one, plus the
# kill-one-replica-mid-run rung (must finish with zero failed
# requests) — one BENCH-schema JSON line carrying the replica count
serve-bench-fleet:
	JAX_PLATFORMS=cpu $(PY) -m fengshen_tpu.fleet.bench

# prefill/decode disaggregation microbench (docs/disaggregation.md):
# aggregate tokens/s of a prefill-tier + decode-tier fleet (KV handoff
# through the real router placement + redirect/collect path) vs a
# homogeneous 3-replica fleet on a long-prompt/short-decode workload,
# plus the adopt-decline fallback rung — one BENCH-schema JSON line
# carrying the phase topology
serve-bench-disagg:
	JAX_PLATFORMS=cpu SERVE_BENCH_MODE=disagg \
		$(PY) -m fengshen_tpu.disagg.bench

# preemption-tolerance drills (docs/fault_tolerance.md "Preemption
# runbook"): SIGTERM-mid-decode (live lane evacuation — every
# in-flight request answers 200 token-identical via a peer, zero lost
# work) and SIGKILL-mid-decode (the adopter dies; requests resume from
# token k out of the commit journal, never from token 0) over a
# 3-replica fleet — one BENCH-schema JSON line carrying the drill
# identity so it never diffs against undisturbed fleet rounds
serve-bench-evac:
	JAX_PLATFORMS=cpu $(PY) -m fengshen_tpu.fleet.evac_bench

# local fleet: spawn $(N) stdlib api replicas from the api config
# $(CONFIG) and front them with the router on port $(PORT)
# (docs/fleet.md), e.g.
#     make serve-fleet CONFIG=generation.json N=3 PORT=8080
serve-fleet:
	@test -n "$(CONFIG)" || \
		{ echo "usage: make serve-fleet CONFIG=<api config json> [N=3] [PORT=8080]"; exit 2; }
	$(PY) -m fengshen_tpu.fleet \
		--spawn $(or $(N),3) --config $(CONFIG) \
		--port $(or $(PORT),8080)

# kernel-layer microbench (docs/kernels.md): the Pallas dispatch seam
# A/B'd against the stock XLA lowerings (paged decode read, fused CE
# grad step) plus the configs/long_context_32k.json trainer config on
# a sequence-sharded mesh. One BENCH-schema JSON line per rung, each
# carrying the `kernel` dispatch decision (pallas|xla) that benchdiff
# folds into the row identity. CPU-shrunk width; hardware rounds drop
# the KERNEL_BENCH_* overrides for the full 32k shape.
kernel-bench:
	JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		BENCH_DEGRADED=1 KERNEL_BENCH_SEQ=2048 \
		KERNEL_BENCH_HIDDEN=64 KERNEL_BENCH_INTER=128 \
		KERNEL_BENCH_LAYERS=2 KERNEL_BENCH_HEADS=4 \
		KERNEL_BENCH_KV=4 KERNEL_BENCH_VOCAB=512 \
		KERNEL_BENCH_FUSED_CE=4 KERNEL_BENCH_STEPS=2 \
		KERNEL_BENCH_DTYPE=float32 \
		$(PY) -m fengshen_tpu.ops.pallas.bench

# bench trajectory comparator (docs/observability.md "benchdiff"):
# classifies each BENCH_r*.json round in --dir (ok / failed), diffs
# every metric against the previous round carrying it (and
# BASELINE.json's published table), and prints a deterministic verdict
benchdiff:
	$(PY) -m fengshen_tpu.observability.benchdiff

lint-baseline:
	$(PY) -m fengshen_tpu.analysis --write-baseline

test-fast:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q
