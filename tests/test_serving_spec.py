"""Speculative decode tick for the continuous-batching engine (ISSUE 7).

The load-bearing contracts:

- greedy spec-engine output is TOKEN-IDENTICAL to the non-spec engine
  and to sequential `utils.generate.generate` — staggered admission,
  slot AND paged layouts, scan_layers + GQA covered (fp32); the int8
  pools must agree spec-vs-non-spec (same quantized entries, same
  reads);
- ONE decode compilation per (layout, dtype, spec_mode, gamma) engine
  — the draft/verify tick must not reintroduce per-request retraces;
- admission reserves gamma EXTRA lane positions (the verify scatters a
  gamma-wide rejected tail past the cursor): the boundary prompt 413s
  on the spec engine and admits on the non-spec one, and the paged
  charge is ceil((bucket + max_new + gamma) / block_size) so
  over-scattered tails never cross into a block the lane doesn't own;
- /stats grows the spec section (mode, gamma, drafted/accepted totals,
  acceptance rate) while the non-spec payload keeps its exact pre-spec
  key set.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.serving import (ContinuousBatchingEngine, EngineConfig,
                                  PromptTooLong)
from fengshen_tpu.utils.generate import generate


def _make(scan=False, kv_heads=None, max_len=64):
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=kv_heads,
                      max_position_embeddings=max_len, dtype="float32",
                      scan_layers=scan)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def tiny():
    return _make()


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, 96, n).astype(np.int32) for n in lengths]


def _rep_prompts(n, length, seed=0):
    """Repetitive prompts (short-period tiling) — the workload where
    the drafter actually gets proposals accepted."""
    rng = np.random.RandomState(seed)
    return [np.tile(rng.randint(3, 96, 3).astype(np.int32),
                    length)[:length] for _ in range(n)]


def _ref(model, params, prompt, max_new, **kw):
    out = np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                              max_new_tokens=max_new, **kw))
    toks = out[0, len(prompt):].tolist()
    eos = kw.get("eos_token_id")
    if eos is not None and eos in toks:
        toks = toks[:toks.index(eos) + 1]
    return toks


SPEC = dict(spec_mode="prompt_lookup", spec_gamma=4)
PAGED = dict(kv_layout="paged", kv_block_size=16)


# ---- greedy parity (the tentpole contract) ------------------------------

@pytest.mark.parametrize("layout_kw", [{}, PAGED], ids=["slot", "paged"])
def test_spec_greedy_parity_staggered_admission(tiny, layout_kw):
    """Requests admitted at different ticks, spanning both buckets,
    more requests than slots (reclaim mid-stream), decode
    token-identical to sequential generate — lanes at DIFFERENT
    accept counts advance independently."""
    model, params = tiny
    prompts = _prompts((5, 11, 16, 7))
    refs = [_ref(model, params, p, 10) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=10, max_queue=16,
                                    **SPEC, **layout_kw))
    r0 = eng.submit(prompts[0])
    r1 = eng.submit(prompts[1])
    for _ in range(3):
        eng.step()
    r2 = eng.submit(prompts[2])
    r3 = eng.submit(prompts[3])
    eng.run_until_idle()
    for req, ref in zip((r0, r1, r2, r3), refs):
        assert req.tokens == ref
        assert req.state == "finished"


@pytest.mark.parametrize("layout_kw", [{}, PAGED], ids=["slot", "paged"])
def test_spec_parity_on_repetitive_prompts_with_acceptance(tiny,
                                                           layout_kw):
    """On the workload the drafter targets, proposals must actually be
    ACCEPTED (else the parity above is vacuous — pure correction-path)
    and the output still token-identical."""
    model, params = tiny
    prompts = _rep_prompts(3, 14, seed=2)
    refs = [_ref(model, params, p, 24) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=3, buckets=(16,),
                                    max_new_tokens=24, max_queue=8,
                                    **SPEC, **layout_kw))
    assert eng.generate_all(prompts) == refs
    st = eng.stats()
    assert st["spec_accepted_total"] > 0
    assert 0.0 < st["spec_acceptance_rate"] <= 1.0
    # accepted proposals = fewer verify forwards than committed tokens
    assert st["decode_ticks"] < st["decode_tokens"]


@pytest.mark.parametrize("scan,kv_heads", [(True, 2), (False, 2),
                                           (True, None)])
def test_spec_parity_scan_and_gqa(scan, kv_heads):
    model, params = _make(scan=scan, kv_heads=kv_heads)
    prompts = _prompts((5, 11, 16), seed=1)
    refs = [_ref(model, params, p, 8) for p in prompts]
    for layout_kw in ({}, PAGED):
        eng = ContinuousBatchingEngine(
            model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                        max_new_tokens=8, max_queue=8,
                                        **SPEC, **layout_kw))
        assert eng.generate_all(prompts) == refs


def test_spec_parity_with_eos(tiny):
    """eos inside an accepted window must cut exactly where the
    non-spec engine cuts (eos included, tail discarded)."""
    model, params = tiny
    prompt = _prompts((9,), seed=3)[0]
    free_run = _ref(model, params, prompt, 12)
    eos = free_run[3]
    ref = _ref(model, params, prompt, 12, eos_token_id=eos)
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(16,),
                                    max_new_tokens=12, max_queue=4,
                                    eos_token_id=eos, **SPEC))
    req = eng.submit(prompt)
    eng.run_until_idle()
    assert req.tokens == ref
    assert req.tokens[-1] == eos
    assert req.finish_reason == "eos"


@pytest.mark.parametrize("layout_kw", [{}, PAGED], ids=["slot", "paged"])
def test_spec_int8_identical_to_nonspec_engine(tiny, layout_kw):
    """int8 pools: the verify window quantizes the SAME per-(token,
    head) values the plain tick would, so spec output must equal the
    non-spec int8 engine token for token (the fp32 sequential ref is
    compared margin-aware elsewhere — here the contract is
    spec-vs-non-spec equality)."""
    model, params = tiny
    prompts = _prompts((5, 11, 16), seed=11) + _rep_prompts(1, 10,
                                                            seed=4)
    kw = dict(num_slots=2, buckets=(8, 16), max_new_tokens=10,
              max_queue=8, kv_dtype="int8", **layout_kw)
    base = ContinuousBatchingEngine(model, params, EngineConfig(**kw))
    spec = ContinuousBatchingEngine(model, params,
                                    EngineConfig(**SPEC, **kw))
    assert spec.generate_all(prompts) == base.generate_all(prompts)


# ---- compile counts -----------------------------------------------------

@pytest.mark.parametrize("layout_kw,gamma",
                         [({}, 4), (PAGED, 4), ({}, 2),
                          (dict(kv_dtype="int8", **PAGED), 3)],
                         ids=["slot-g4", "paged-g4", "slot-g2",
                              "paged-int8-g3"])
def test_spec_decode_compiles_once_across_reclaim(tiny, layout_kw,
                                                  gamma):
    """One decode program per (layout, dtype, spec_mode, gamma) engine
    for its whole lifetime — staggered admission, reclaim, and both
    prefill buckets (one compile each); assign compiles once."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=16,
                                    spec_mode="prompt_lookup",
                                    spec_gamma=gamma, **layout_kw))
    if not hasattr(eng._decode_jit, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    eng.warmup()
    prompts = _prompts((5, 11, 16, 7, 3, 9))
    reqs = [eng.submit(p) for p in prompts[:3]]
    for _ in range(4):
        eng.step()
    reqs += [eng.submit(p) for p in prompts[3:]]
    eng.run_until_idle()
    assert all(r.state == "finished" for r in reqs)
    assert eng._decode_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 2
    assert eng._assign_jit._cache_size() == 1


# ---- admission: the gamma headroom boundary -----------------------------

def test_spec_headroom_boundary_rejects_413(tiny):
    """capacity 64, bucket 60, gamma 4: 64 - 60 - 4 = 0 decode room →
    the spec engine must 413; the SAME prompt admits on the non-spec
    engine (this is exactly the off-by-gamma that would otherwise
    silently clamp the verify window into corrupting the lane)."""
    model, params = tiny
    prompt = _prompts((58,), seed=5)[0]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8, 56, 60),
                                    max_new_tokens=8, max_queue=4,
                                    **SPEC))
    with pytest.raises(PromptTooLong, match="gamma=4"):
        eng.submit(prompt)
    assert eng.stats()["rejected_prompt_too_long"] == 1
    off = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8, 56, 60),
                                    max_new_tokens=8, max_queue=4))
    req = off.submit(prompt)
    off.run_until_idle()
    assert req.state == "finished"
    # one bucket below the boundary the spec engine admits, with the
    # window clamped to the remaining headroom
    ref = _ref(model, params, _prompts((50,), seed=6)[0], 4)
    req = eng.submit(_prompts((50,), seed=6)[0], max_new_tokens=8)
    eng.run_until_idle()
    assert req.state == "finished"
    assert req.tokens == ref  # clamped to 64 - 56 - 4 = 4 tokens


def test_spec_paged_charge_includes_gamma(tiny):
    """Paged admission must charge ceil((bucket + max_new + gamma) /
    block_size): at bucket 8, max_new 8, gamma 4 → 20 tokens → 2
    blocks of 16, where the gamma-less charge would be 1 — pinned via
    the allocator accounting."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8,),
                                    max_new_tokens=8, max_queue=8,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=6, **SPEC))
    eng.submit(_prompts((6,), seed=7)[0])
    eng.step()
    assert eng.stats()["kv_blocks_used"] == 2
    off = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8,),
                                    max_new_tokens=8, max_queue=8,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=6))
    off.submit(_prompts((6,), seed=7)[0])
    off.step()
    assert off.stats()["kv_blocks_used"] == 1


def test_spec_paged_tight_pool_no_cross_lane_corruption(tiny):
    """Adjacent lanes on a pool with EXACTLY the charged blocks: an
    over-scattered rejected tail crossing into a neighbour's block
    would corrupt its committed K/V and break token identity."""
    model, params = tiny
    prompts = _rep_prompts(3, 8, seed=8)
    refs = [_ref(model, params, p, 12) for p in prompts]
    # charge per request: ceil((8 + 12 + 4) / 8) = 3 blocks; pool holds
    # exactly 3 requests' worth (+ null block)
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=3, buckets=(8,),
                                    max_new_tokens=12, max_queue=8,
                                    kv_layout="paged", kv_block_size=8,
                                    kv_num_blocks=10, **SPEC))
    assert eng.generate_all(prompts) == refs
    assert eng.stats()["kv_blocks_used"] == 0


def test_spec_unsatisfiable_paged_footprint_rejected(tiny):
    """The gamma-inclusive footprint can exceed a pool the gamma-less
    one fits into — submit must 413 instead of livelocking the FIFO."""
    model, params = tiny
    # bucket 8 + max_new 8 + gamma 4 = 20 tokens = 2 blocks of 16, but
    # the pool has only 1 allocatable block (fits the gamma-less 16)
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8,),
                                    max_new_tokens=8, max_queue=8,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=2, **SPEC))
    with pytest.raises(PromptTooLong, match="KV blocks"):
        eng.submit(_prompts((6,))[0])
    off = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8,),
                                    max_new_tokens=8, max_queue=8,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=2))
    req = off.submit(_prompts((6,))[0])
    off.run_until_idle()
    assert req.state == "finished"


# ---- config surface -----------------------------------------------------

def test_spec_config_validation(tiny):
    with pytest.raises(ValueError, match="spec_mode"):
        EngineConfig(spec_mode="prompt_lookupp")
    with pytest.raises(ValueError, match="spec_gamma"):
        EngineConfig(spec_mode="prompt_lookup", spec_gamma=0)
    with pytest.raises(ValueError, match="spec_ngram"):
        EngineConfig(spec_mode="prompt_lookup", spec_ngram=0)
    with pytest.raises(ValueError, match="greedy-only"):
        EngineConfig(spec_mode="prompt_lookup", do_sample=True)
    with pytest.raises(ValueError, match="logits controls"):
        EngineConfig(spec_mode="prompt_lookup", repetition_penalty=1.5)
    # a ladder whose smallest bucket fills the lane minus gamma must
    # fail at CONSTRUCTION (no admissible prompt exists)
    model, params = tiny
    with pytest.raises(ValueError, match="gamma=4"):
        ContinuousBatchingEngine(
            model, params, EngineConfig(buckets=(60,), **SPEC))


# ---- /stats + registry --------------------------------------------------

def test_spec_stats_keys_and_nonspec_shape_unchanged(tiny):
    model, params = tiny
    spec = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(16,),
                                    max_new_tokens=16, max_queue=4,
                                    **SPEC))
    spec.generate_all(_rep_prompts(2, 12, seed=9))
    st = spec.stats()
    assert st["spec_mode"] == "prompt_lookup"
    assert st["spec_gamma"] == 4
    assert st["spec_drafted_total"] > 0
    assert 0 <= st["spec_accepted_total"] <= st["spec_drafted_total"]
    assert st["spec_acceptance_rate"] == round(
        st["spec_accepted_total"] / st["spec_drafted_total"], 4)
    from fengshen_tpu.observability import render_prometheus
    text = render_prometheus(spec.metrics.registry)
    assert "fstpu_serving_spec_drafted_total" in text
    assert "fstpu_serving_spec_accepted_total" in text
    assert "fstpu_spec_accepted_ratio" in text
    # the non-spec engine's payload keeps its exact pre-spec key set
    off = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(16,),
                                    max_new_tokens=4, max_queue=4))
    off_keys = set(off.stats())
    assert not any(k.startswith("spec_") for k in off_keys)
    assert set(st) == off_keys | {
        "spec_mode", "spec_gamma", "spec_drafted_total",
        "spec_accepted_total", "spec_acceptance_rate"}


def test_spec_metrics_count_only_delivered_tokens(tiny):
    """A lane finishing mid-window (length cap / eos) discards the
    window tail — decode_tokens must equal the tokens requests
    actually received (minus the prefill token), not the raw committed
    windows, else tokens/s and the committed tokens a target forward
    (`decode_tokens / decode_ticks`) inflate by up to gamma per
    request."""
    model, params = tiny
    prompts = _rep_prompts(3, 14, seed=2)   # high-acceptance workload
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=3, buckets=(16,),
                                    max_new_tokens=6, max_queue=8,
                                    **SPEC))
    outs = eng.generate_all(prompts)
    st = eng.stats()
    # the first token of each request comes from prefill, the rest
    # from decode ticks — exactly, despite truncated final windows
    assert st["decode_tokens"] == sum(len(t) - 1 for t in outs)
    assert st["spec_accepted_total"] <= st["decode_tokens"]
    # drafted = gamma per active lane per tick
    assert 0 < st["spec_drafted_total"] <= 4 * st["decode_ticks"] * 3
