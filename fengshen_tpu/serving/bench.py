"""Offline serving throughput microbench: continuous batching vs the
legacy one-request-at-a-time path.

Runs entirely offline (no HTTP) on whatever backend JAX picks — the
`make serve-bench` target pins CPU, where it checks the harness and
the scheduler's counts (a CPU rate is not a device number). Prints ONE JSON
line in the BENCH schema ({"metric", "value", "unit", "vs_baseline"},
value = engine tokens/s, vs_baseline = speedup over sequential) plus
ttft and config echo keys.

    make serve-bench
    SERVE_BENCH_NEW_TOKENS=128 python -m fengshen_tpu.serving.bench

`SERVE_BENCH_MODE=memory_parity` (`make serve-bench-parity`) switches
to the KV **memory-parity** comparison (docs/performance.md): the slot
pool's byte budget is held FIXED and re-carved as paged fp32 and
paged+int8 pools; each variant reports the max concurrent requests it
admitted and its aggregate tokens/s. The paged pool admits by ACTUAL
footprint (bucket + max_new blocks) instead of worst-case max_len
lanes, and int8 stores ~3-4x more KV tokens per byte, so `value` /
`vs_baseline` become the paged-over-slot concurrency ratio (the >= 2x
acceptance bar of ISSUE 6).

`SERVE_BENCH_MODE=spec` (`make serve-bench-spec`) benches the
**speculative decode tick** (docs/serving.md "Speculative decoding"):
the same engine/workload with `spec_mode="off"` vs `"prompt_lookup"`.
The workload is repetitive TEXT by construction — a random-init model
has no real language to copy, so the bench probes candidate tokens
with one short batched generate and keeps the ones whose greedy
continuations are the most self-repetitive (the synthetic stand-in
for the extractive/summarisation regime where prompt lookup pays).
`value` = committed tokens per target forward (1 + gamma x
acceptance_rate; the non-spec tick is exactly 1.0), `vs_baseline` the
same ratio; the row also carries `acceptance_rate`, both engines'
tokens/s, and `token_identical` (greedy spec output must equal the
non-spec engine's).

`SERVE_BENCH_MODE=multimodal` (`make serve-bench-multimodal`) benches
the **micro-batch multimodal engines** (docs/serving.md "Multimodal
engines") on the small-test towers: one row per engine type
(`batch_image`, `embedding`), each carrying `engine_type`; `value` =
engine requests/s with all requests co-arriving, `vs_baseline` the
speedup over sequential one-per-call pipeline invocations.

Env knobs (SERVE_BENCH_*): SLOTS, REQUESTS, NEW_TOKENS, VOCAB, HIDDEN,
INTER, LAYERS, HEADS, BUCKETS (comma list), SEED, MODE, BLOCK_SIZE,
MAX_SLOTS (paged concurrency cap in parity mode), SPEC_GAMMA,
SPEC_NGRAM, PROBE (spec-workload candidate count), MAX_BATCH
(multimodal micro-batch width).

Why batching wins even here: batch-1 decode is weight-memory-bound —
every generated token streams the full weight matrices for ONE row.
The slot pool streams them once per tick for `num_slots` rows, so
aggregate tokens/s scales with occupancy until compute saturates
(PAPERS.md: "Dissecting the Runtime Performance …" — batched decode is
the dominant inference-throughput lever).
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _env(name: str, default: int) -> int:
    return int(os.environ.get(f"SERVE_BENCH_{name}", default))


def _emit(row: dict) -> None:
    from fengshen_tpu.observability import JsonlSink
    if os.environ.get("BENCH_DEGRADED", "0") == "1":
        row["degraded"] = True
    JsonlSink(stream=sys.stdout, only_process_zero=False)(row)


def _sequential_tps(model, params, prompts, new_tokens: int) -> float:
    """The legacy api path: one jitted batch-1 generate per request
    (compiles excluded via per-shape warmup)."""
    from fengshen_tpu.utils.generate import generate

    @jax.jit
    def _gen(params, ids):
        return generate(model, params, ids, max_new_tokens=new_tokens,
                        eos_token_id=None, pad_token_id=0)

    for n in sorted({len(p) for p in prompts}):
        jax.block_until_ready(_gen(params, jnp.ones((1, n), jnp.int32)))
    t0 = time.perf_counter()
    for p in prompts:
        jax.block_until_ready(_gen(params, jnp.asarray(p)[None]))
    return len(prompts) * new_tokens / (time.perf_counter() - t0)


def _run_engine(model, params, prompts, cfg) -> dict:
    """Warm up, drain `prompts`, return throughput + pool stats."""
    from fengshen_tpu.serving import ContinuousBatchingEngine
    engine = ContinuousBatchingEngine(model, params, cfg)
    engine.warmup()
    t0 = time.perf_counter()
    outs = engine.generate_all(prompts)
    dt = time.perf_counter() - t0
    stats = engine.stats()
    return {"tokens_per_sec": round(sum(len(t) for t in outs) / dt, 1),
            "stats": stats, "outputs": outs}


def _memory_parity(model, params, config, buckets, new_tokens) -> None:
    """Same KV byte budget, three carvings: slot fp32 (the reference),
    paged fp32, paged int8. Deterministic concurrency: every variant
    gets enough requests and slots to hit its admission bound."""
    from fengshen_tpu.serving import EngineConfig

    slots_ref = _env("SLOTS", 8)
    block = _env("BLOCK_SIZE", 16)
    slot_cap = _env("MAX_SLOTS", 32)
    max_len = buckets[-1] + new_tokens
    kv = config.num_key_value_heads
    hd = config.head_dim
    layers = config.num_hidden_layers
    budget = slots_ref * max_len * kv * hd * 2 * 4 * layers

    # all requests land in the SMALLEST bucket — the realistic skew the
    # paged pool exploits (the ladder still serves the big bucket; the
    # slot pool pays its worst case for every lane regardless)
    prompt_len = max(buckets[0] // 2, 1)
    bucket = buckets[0]
    need_tokens = bucket + new_tokens
    need_blocks = -(-need_tokens // block)

    def blocks_for(budget_bytes: int, int8: bool) -> int:
        per_tok = kv * hd * 2 * (1 if int8 else 4) * layers
        if int8:
            per_tok += kv * 2 * 4 * layers        # absmax scales
        return budget_bytes // (block * per_tok)

    variants = {
        "slot": dict(num_slots=slots_ref),
        "paged": dict(kv_layout="paged", kv_block_size=block,
                      kv_num_blocks=blocks_for(budget, False)),
        "paged_int8": dict(kv_layout="paged", kv_dtype="int8",
                           kv_block_size=block,
                           kv_num_blocks=blocks_for(budget, True)),
    }
    bounds = {"slot": slots_ref}
    for name in ("paged", "paged_int8"):
        nb = variants[name]["kv_num_blocks"]
        bound = max((nb - 1) // need_blocks, 1)
        bounds[name] = min(bound, slot_cap)
        variants[name]["num_slots"] = bounds[name]

    n_req = max(_env("REQUESTS", 0), max(bounds.values()) + 2)
    rng = np.random.RandomState(_env("SEED", 0))
    prompts = [rng.randint(3, config.vocab_size - 1,
                           prompt_len).astype(np.int32)
               for _ in range(n_req)]
    seq_tps = _sequential_tps(model, params,
                              prompts[:min(n_req, 8)], new_tokens)

    results = {}
    for name, overrides in variants.items():
        cfg = EngineConfig(buckets=buckets, max_new_tokens=new_tokens,
                           max_queue=n_req, eos_token_id=None,
                           pad_token_id=0, **overrides)
        run = _run_engine(model, params, prompts, cfg)
        st = run["stats"]
        results[name] = {
            "max_concurrent": st["slots_active_peak"],
            "tokens_per_sec": run["tokens_per_sec"],
            "vs_sequential": round(run["tokens_per_sec"] / seq_tps, 3),
            "kv_cache_bytes": st["kv_cache_bytes"],
            "kv_blocks_total": st["kv_blocks_total"],
            "num_slots": cfg.num_slots,
            "deferred_admissions": st["deferred_admissions"],
        }

    slot_peak = max(results["slot"]["max_concurrent"], 1)
    best = max(results["paged"]["max_concurrent"],
               results["paged_int8"]["max_concurrent"])
    _emit({
        "metric": "serving_kv_memory_parity_max_concurrent",
        "value": best,
        "unit": "concurrent_requests",
        "vs_baseline": round(best / slot_peak, 3),
        "mode": "memory_parity",
        "kv_budget_bytes": budget,
        "block_size": block,
        "requests": n_req,
        "new_tokens": new_tokens,
        "prompt_tokens": prompt_len,
        "sequential_tokens_per_sec": round(seq_tps, 1),
        "variants": results,
        "backend": jax.default_backend(),
    })


def _multimodal_bench() -> None:
    """`SERVE_BENCH_MODE=multimodal` (`make serve-bench-multimodal`):
    the micro-batch engines (docs/serving.md "Multimodal engines") vs
    the legacy one-call-per-request path, on the small-test towers —
    no checkpoint or tokenizer dependency. One BENCH row per engine
    type, each carrying `engine_type` (benchdiff treats rows at
    different engine types as incomparable, like offload placements).
    `value` = engine requests/s with all requests co-arriving,
    `vs_baseline` = speedup over sequential `pipeline(text)` calls —
    the micro-batching win: co-riders share ONE jitted forward (or
    denoise loop) instead of paying a batch-1 launch each."""
    from fengshen_tpu.serving.multimodal import create_multimodal_engine

    n_req = max(_env("REQUESTS", 8), 1)
    max_batch = max(_env("MAX_BATCH", 4), 1)
    prompts = [f"多模态 bench prompt {i}" for i in range(n_req)]

    jobs = (("batch_image", "image_generation"),
            ("embedding", "embedding"))
    for engine_name, task in jobs:
        import importlib
        mod = importlib.import_module(f"fengshen_tpu.pipelines.{task}")
        pipeline = mod.Pipeline(small_test=True,
                                seed=_env("SEED", 0))

        # compile both shapes outside the timed windows
        pipeline.run_batch([pipeline.warmup_input()] * max_batch)
        pipeline(pipeline.warmup_input())

        t0 = time.perf_counter()
        for p in prompts:
            pipeline(p)
        seq_rps = n_req / (time.perf_counter() - t0)

        engine = create_multimodal_engine(
            engine_name, pipeline,
            {"max_batch": max_batch, "gather_ms": 2.0,
             "max_queue": n_req})
        engine.start()
        t0 = time.perf_counter()
        reqs = [engine.submit(p) for p in prompts]
        for r in reqs:
            if not r.wait(timeout=300):
                raise RuntimeError(f"{engine_name} bench request "
                                   f"{r.request_id} never finished")
        eng_rps = n_req / (time.perf_counter() - t0)
        stats = engine.stats()
        engine.stop()

        _emit({
            "metric": f"serving_{engine_name}_requests_per_sec",
            "value": round(eng_rps, 2),
            "unit": "requests/s",
            "vs_baseline": round(eng_rps / seq_rps, 3),
            "mode": "multimodal",
            "engine_type": engine_name,
            "sequential_requests_per_sec": round(seq_rps, 2),
            "avg_batch": stats["avg_batch"],
            "batches_total": stats["batches_total"],
            "requests": n_req,
            "max_batch": max_batch,
            "backend": jax.default_backend(),
        })


def committed_per_forward(gamma: int, acceptance_rate: float) -> float:
    """Committed tokens per target forward per lane: every verify
    commits the accepted prefix plus one correction, so the mean is
    `1 + gamma * acceptance_rate` (an identity over the engine's
    spec_drafted/spec_accepted counters — the fast-lane smoke pins the
    math without a model forward). The non-spec tick is exactly 1.0."""
    if gamma < 0 or not 0.0 <= acceptance_rate <= 1.0:
        raise ValueError(f"bad spec stats: gamma={gamma} "
                         f"acceptance_rate={acceptance_rate}")
    return 1.0 + gamma * acceptance_rate


def _spec_prompts(model, params, vocab: int, prompt_len: int,
                  n_req: int, seed: int, probe: int,
                  probe_new: int = 32):
    """The repetitive-text workload: probe `probe` candidate tokens
    with ONE batched short generate and keep the `n_req` whose greedy
    continuations are most self-repetitive (fraction of positions
    matching one of the two previous tokens — what an ngram<=2 lookup
    can exploit). A random-init model has no real text to copy; this
    selects the rows where its greedy decode actually loops, the
    synthetic stand-in for extractive/repetitive serving traffic."""
    from fengshen_tpu.utils.generate import generate
    rng = np.random.RandomState(seed)
    cands = rng.randint(3, vocab - 1, probe).astype(np.int32)
    ids = jnp.asarray(np.repeat(cands[:, None], prompt_len, axis=1))
    out = np.asarray(generate(model, params,
                              max_new_tokens=probe_new,
                              input_ids=ids))[:, prompt_len:]
    rep = ((out[:, 2:] == out[:, 1:-1]) |
           (out[:, 2:] == out[:, :-2])).mean(1)
    best = cands[np.argsort(-rep, kind="stable")[:n_req]]
    return [np.full(prompt_len, int(t), np.int32) for t in best]


def _spec_bench(model, params, config, buckets, new_tokens) -> None:
    """Same engine, same prompts, spec off vs prompt_lookup: committed
    tokens per target forward (the >=1.8x bar), aggregate tokens/s
    (the >=1.3x bar), greedy token identity."""
    from fengshen_tpu.serving import EngineConfig

    slots = _env("SLOTS", 8)
    gamma = _env("SPEC_GAMMA", 4)
    ngram = _env("SPEC_NGRAM", 2)
    n_req = max(_env("REQUESTS", 8), 1)
    prompt_len = max(buckets[0] - 4, 1)
    max_len = int(model.config.max_position_embeddings)
    prompts = _spec_prompts(model, params, config.vocab_size,
                            prompt_len, n_req, _env("SEED", 0),
                            probe=_env("PROBE", 64),
                            probe_new=min(32, max_len - prompt_len))

    base_kw = dict(num_slots=slots, buckets=buckets,
                   max_new_tokens=new_tokens, max_queue=n_req,
                   eos_token_id=None, pad_token_id=0)
    off = _run_engine(model, params, prompts, EngineConfig(**base_kw))
    spec = _run_engine(
        model, params, prompts,
        EngineConfig(spec_mode="prompt_lookup", spec_gamma=gamma,
                     spec_ngram=ngram, **base_kw))
    st = spec["stats"]
    cpf = committed_per_forward(gamma, st["spec_acceptance_rate"])
    _emit({
        "metric": "serving_spec_committed_per_forward",
        "value": round(cpf, 3),
        "unit": "tokens/forward",
        # the non-spec tick commits exactly one token per lane per
        # weight stream, so cpf IS the vs-baseline ratio
        "vs_baseline": round(cpf, 3),
        "mode": "spec",
        "acceptance_rate": st["spec_acceptance_rate"],
        "spec_gamma": gamma,
        "spec_ngram": ngram,
        "tokens_per_sec": spec["tokens_per_sec"],
        "tokens_per_sec_off": off["tokens_per_sec"],
        "speedup_vs_off": round(spec["tokens_per_sec"] /
                                off["tokens_per_sec"], 3),
        "token_identical": spec["outputs"] == off["outputs"],
        "decode_ticks": st["decode_ticks"],
        "decode_ticks_off": off["stats"]["decode_ticks"],
        "requests": n_req,
        "num_slots": slots,
        "new_tokens": new_tokens,
        "prompt_tokens": prompt_len,
        "backend": jax.default_backend(),
    })


def main() -> None:
    mode = os.environ.get("SERVE_BENCH_MODE", "throughput")
    if mode == "multimodal":
        # no llama tower to build — the multimodal engines bench their
        # own small-test pipelines
        _multimodal_bench()
        return

    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.serving import EngineConfig

    slots = _env("SLOTS", 8)
    n_req = _env("REQUESTS", 8)
    new_tokens = _env("NEW_TOKENS", 48)
    buckets = tuple(int(b) for b in os.environ.get(
        "SERVE_BENCH_BUCKETS", "32,64").split(","))
    # the spec verify scatters a gamma-wide tail past the cursor, so
    # the lane needs gamma extra positions (engine admission headroom)
    spec_headroom = _env("SPEC_GAMMA", 4) if mode == "spec" else 0
    # default shape sits in the weight-memory-bound decode regime (the
    # 300M-bench hidden/intermediate at 4 layers): batch-1 GEMV and
    # batch-8 GEMM stream the same weights, so the slot pool's batching
    # win is visible even on the CPU backend — tiny hidden sizes are
    # elementwise/dispatch-bound and hide it
    config = LlamaConfig(
        vocab_size=_env("VOCAB", 4096),
        hidden_size=_env("HIDDEN", 1024),
        intermediate_size=_env("INTER", 2816),
        num_hidden_layers=_env("LAYERS", 4),
        num_attention_heads=_env("HEADS", 8),
        max_position_embeddings=buckets[-1] + new_tokens + spec_headroom,
        dtype="float32")
    model = LlamaForCausalLM(config)
    params = jax.jit(lambda r: model.init(
        r, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(_env("SEED", 0)))

    if mode == "memory_parity":
        _memory_parity(model, params, config, buckets, new_tokens)
        return
    if mode == "spec":
        _spec_bench(model, params, config, buckets, new_tokens)
        return

    rng = np.random.RandomState(_env("SEED", 0))
    span = max(buckets[-1] - 11, 1)  # varied lengths, any ladder size
    lengths = [min(buckets[-1], 12 + (i * 7) % span)
               for i in range(n_req)]
    prompts = [rng.randint(3, config.vocab_size - 1, n).astype(np.int32)
               for n in lengths]

    # sequential baseline (the legacy api/main.py path) vs the
    # continuous engine with all requests in flight together — the
    # same helpers the memory-parity mode times with
    seq_tps = _sequential_tps(model, params, prompts, new_tokens)
    run = _run_engine(
        model, params, prompts,
        EngineConfig(num_slots=slots, buckets=buckets,
                     max_new_tokens=new_tokens,
                     max_queue=max(n_req, 1),
                     eos_token_id=None, pad_token_id=0))
    eng_tps = run["tokens_per_sec"]
    stats = run["stats"]

    row = {
        "metric": "serving_engine_tokens_per_sec",
        "value": round(eng_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(eng_tps / seq_tps, 3),
        "sequential_tokens_per_sec": round(seq_tps, 1),
        "ttft_avg_s": stats["ttft_avg_s"],
        "ttft_p95_s": stats["ttft_p95_s"],
        "slot_occupancy": stats["slot_occupancy"],
        "requests": n_req,
        "num_slots": slots,
        "new_tokens": new_tokens,
        "backend": jax.default_backend(),
    }
    # utilization column (docs/observability.md): forward-only FLOPs —
    # decode does no backward; present whenever the estimator supports
    # the benched model (it does: llama-shaped config)
    from fengshen_tpu.observability import (estimate_flops_per_token,
                                            peak_flops_per_chip)
    f_tok = estimate_flops_per_token(config, include_backward=False)
    if f_tok:
        peak = peak_flops_per_chip(jax.devices()[0].device_kind)
        row["mfu"] = float(f"{eng_tps * f_tok / (peak * len(jax.devices())):.4g}")
    _emit(row)


if __name__ == "__main__":
    main()
