"""Paged + int8-quantized KV cache for the serving engine (ISSUE 6).

The load-bearing contracts:

- greedy decode through the paged fp32 pool is TOKEN-IDENTICAL to
  sequential `utils.generate.generate` — staggered admission, block
  reclaim, scan_layers and GQA covered;
- ONE decode compilation per (layout, dtype) engine and one prefill
  per bucket — paging must not reintroduce per-request retraces;
- int8 KV never flips a CONFIDENT fp decision (the margin-aware bar:
  a disagreement is only legal where the fp top-2 logit gap is within
  the measured int8 rounding noise);
- admission switches from free-slot to enough-free-blocks, with
  deferral (not loss) when the pool is exhausted and block reclaim on
  completion/cancel;
- the host allocator is exact: no double-free, deterministic ids,
  null block never handed out.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from fengshen_tpu.ops.int8_matmul import dequantize_kv, quantize_kv
from fengshen_tpu.serving import (BlockAllocator, ContinuousBatchingEngine,
                                  EngineConfig, QueueFull,
                                  init_pool_cache, reset_free_slots)
from fengshen_tpu.utils.generate import generate


def _make(scan=False, kv_heads=None, layers=2, dtype="float32"):
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=layers, num_attention_heads=4,
                      num_key_value_heads=kv_heads,
                      max_position_embeddings=64, dtype=dtype,
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


def _ref(model, params, prompt, max_new, **kw):
    out = np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                              max_new_tokens=max_new, **kw))
    return out[0, len(prompt):].tolist()


PAGED = dict(kv_layout="paged", kv_block_size=16)


# ---- allocator ----------------------------------------------------------

def test_block_allocator_exact_accounting():
    a = BlockAllocator(6)            # block 0 reserved → 5 usable
    assert a.total_blocks == 5 and a.free_blocks == 5
    first = a.alloc(2)
    assert first == [1, 2]           # deterministic lowest-first
    assert 0 not in first            # the null block is never handed out
    assert a.alloc(4) is None        # 3 left — all-or-nothing
    assert a.free_blocks == 3
    a.free(first)
    assert a.free_blocks == 5 and a.used_blocks == 0
    with pytest.raises(ValueError):
        a.free([1])                  # double-free must raise
    with pytest.raises(ValueError):
        a.alloc(0)
    with pytest.raises(ValueError):
        BlockAllocator(1)            # null block + nothing allocatable


# ---- greedy parity (the tentpole contract) ------------------------------

def test_paged_greedy_parity_staggered_admission(tiny):
    """Requests admitted at different ticks, spanning both buckets,
    more requests than slots (block reclaim in the middle), decode
    token-identical to sequential generate."""
    model, params = tiny
    prompts = _prompts((5, 11, 16, 7))
    refs = [_ref(model, params, p, 10) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=10, max_queue=16,
                                    **PAGED))
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


def test_paged_parity_virtual_lane_shorter_than_max_len(tiny):
    """kv_max_blocks_per_slot below max_len/block_size shrinks the
    virtual lane (the gather is over fewer positions than the slot
    pool reads) — tokens must not change."""
    model, params = tiny
    prompts = _prompts((5, 9), seed=7)
    refs = [_ref(model, params, p, 8) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(16,),
                                    max_new_tokens=8, max_queue=4,
                                    kv_layout="paged", kv_block_size=8,
                                    kv_max_blocks_per_slot=3))
    assert eng.seq_capacity == 24 < eng.max_len
    assert eng.generate_all(prompts) == refs


@pytest.mark.parametrize("scan,kv_heads", [(True, 2), (False, 2),
                                           (True, None)])
def test_paged_parity_scan_and_gqa(scan, kv_heads):
    model, params = _make(scan=scan, kv_heads=kv_heads)
    prompts = _prompts((5, 11, 16), seed=1)
    refs = [_ref(model, params, p, 8) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=8, max_queue=8,
                                    **PAGED))
    assert eng.generate_all(prompts) == refs


def test_paged_parity_with_eos_and_controls(tiny):
    """eos mid-stream and repetition penalty both ride the paged path
    unchanged (per-slot cursors into the [S, virt_len] history)."""
    model, params = tiny
    prompt = _prompts((9,), seed=3)[0]
    free_run = _ref(model, params, prompt, 12)
    eos = free_run[3]
    ref = _ref(model, params, prompt, 12, eos_token_id=eos)
    ref = ref[:ref.index(eos) + 1]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(16,),
                                    max_new_tokens=12, max_queue=4,
                                    eos_token_id=eos, **PAGED))
    req = eng.submit(prompt)
    eng.run_until_idle()
    assert req.tokens == ref and req.finish_reason == "eos"

    pen_ref = [_ref(model, params, p, 8, repetition_penalty=1.5)
               for p in _prompts((6, 13), seed=5)]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=8, max_queue=4,
                                    repetition_penalty=1.5, **PAGED))
    assert eng.generate_all(_prompts((6, 13), seed=5)) == pen_ref


# ---- compile counts (no per-request retraces) ---------------------------

@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_paged_decode_compiles_once_across_reclaim(tiny, kv_dtype):
    """One decode program per (layout, dtype) engine for its whole
    lifetime — across staggered admission, block reclaim, and both
    prefill buckets (one compile each); assign compiles once."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=16,
                                    kv_dtype=kv_dtype, **PAGED))
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


# ---- int8 KV: the margin-aware agreement bar ----------------------------

def _kv_roundtrip_noise(model, params, seq):
    """Direct measurement of the int8-KV logit perturbation: prime a
    fp cache on `seq[:-1]`, round-trip its K/V through the pool's
    per-(token, head) quantization, decode one step both ways, and
    return the max |logit| difference. This is the noise floor any
    margin must beat before a flipped argmax counts as a bug."""
    from fengshen_tpu.utils.generate import _prefill_cache

    ids = jnp.asarray(seq[:-1], jnp.int32)[None]
    mask = jnp.ones_like(ids)
    pos = jnp.arange(ids.shape[1])[None]
    _, cache = _prefill_cache(model, params, ids, mask, pos)

    def roundtrip(path, leaf):
        name = getattr(path[-1], "key", "")
        if name in ("cached_key", "cached_value"):
            return dequantize_kv(*quantize_kv(leaf), leaf.dtype)
        return leaf
    cache_q = jax.tree_util.tree_map_with_path(roundtrip, cache)

    def step(cache):
        logits, _ = model.apply(
            {"params": params, "cache": cache},
            jnp.asarray(seq[-1:], jnp.int32)[None],
            attention_mask=mask,
            position_ids=jnp.asarray([[len(seq) - 1]]),
            init_cache=True, mutable=["cache"])
        return logits[0, -1]
    return float(jnp.max(jnp.abs(step(cache) - step(cache_q))))


def assert_margin_aware_agreement(model, params, prompt, ref_tokens,
                                  test_tokens, noise_scale=4.0):
    """int8 noise must never flip a CONFIDENT decision: walk both
    streams; positions after the first divergence are autoregressive
    drift and not comparable, so only the first disagreement is
    judged — the fp top-2 logit margin there (teacher-forced on the
    shared prefix) must sit within `noise_scale` x the measured
    round-trip noise."""
    assert len(ref_tokens) == len(test_tokens)
    for t, (a, b) in enumerate(zip(ref_tokens, test_tokens)):
        if a == b:
            continue
        seq = np.concatenate([prompt, ref_tokens[:t + 1]])
        logits = np.asarray(model.apply(
            {"params": params}, jnp.asarray(seq, jnp.int32)[None]))[0]
        step = logits[len(prompt) + t - 1]
        top2 = np.sort(step)[-2:]
        margin = float(top2[1] - top2[0])
        noise = _kv_roundtrip_noise(model, params, seq[:len(prompt) + t])
        assert margin <= noise_scale * noise, (
            f"int8 KV flipped a confident position {t}: fp margin "
            f"{margin:.4f} vs noise floor {noise:.4f}")
        return
    # full agreement: the bar is trivially met


@pytest.mark.parametrize("layout_kw", [PAGED, {}],
                         ids=["paged", "slot"])
def test_int8_kv_margin_aware_agreement(tiny, layout_kw):
    model, params = tiny
    prompts = _prompts((5, 11, 16, 7), seed=11)
    refs = [_ref(model, params, p, 10) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=10, max_queue=16,
                                    kv_dtype="int8", **layout_kw))
    outs = eng.generate_all(prompts)
    for prompt, ref, out in zip(prompts, refs, outs):
        assert_margin_aware_agreement(model, params, prompt, ref, out)


# ---- scheduler: blocks as the admission currency ------------------------

def test_block_exhaustion_defers_then_serves(tiny):
    """4 slots but only 2 requests' worth of blocks: admission is
    bounded by the pool, deferred requests are NOT lost, and reclaim
    drains the queue with token-identical results."""
    model, params = tiny
    prompts = _prompts((6, 6, 6, 6), seed=2)
    refs = [_ref(model, params, p, 8) for p in prompts]
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=4, buckets=(8,),
                                    max_new_tokens=8, max_queue=16,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=3))
    reqs = [eng.submit(p) for p in prompts]
    eng.step()
    st = eng.stats()
    assert st["slots_active"] == 2          # pool-bounded, not slots
    assert st["kv_blocks_used"] == 2
    assert st["deferred_admissions"] == 1
    eng.step()
    # the same waiting head is ONE deferral event, not one per tick
    assert eng.stats()["deferred_admissions"] == 1
    eng.run_until_idle()
    assert [r.tokens for r in reqs] == refs
    st = eng.stats()
    assert st["kv_blocks_used"] == 0        # everything reclaimed
    assert st["slots_active_peak"] == 2
    # r2 and r3 both fit after the first reclaim: one deferral total
    assert st["deferred_admissions"] == 1


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_paged_pool_admits_twice_the_slot_pool_at_equal_kv_bytes(
        tiny, kv_dtype):
    """What the paged pool is for. A slot pool pays every lane its
    worst case (the largest bucket + max_new_tokens); blocks are paid
    as a request needs them, so when the traffic sits in the small
    bucket the same KV bytes hold at least twice the concurrent
    requests. Admission capacity is allocator arithmetic, not timing."""
    model, params = tiny
    common = dict(buckets=(8, 32), max_new_tokens=8, max_queue=32)
    slot = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, **common))
    budget = slot.stats()["kv_cache_bytes"]
    # [K and V] x 4 kv heads x 8 head_dim x 2 layers a token; int8
    # adds a float32 scale a (token, head)
    per_token = 2 * 4 * 8 * 2 * (1 if kv_dtype == "int8" else 4) + \
        (2 * 4 * 2 * 4 if kv_dtype == "int8" else 0)
    block = 8
    blocks = budget // (block * per_token)
    lanes = (blocks - 1) // 2       # (bucket 8 + 8 new) / 8 = 2 blocks
    paged = ContinuousBatchingEngine(
        model, params, EngineConfig(
            num_slots=lanes, kv_layout="paged", kv_dtype=kv_dtype,
            kv_block_size=block, kv_num_blocks=blocks, **common))
    assert paged.stats()["kv_cache_bytes"] <= budget
    prompts = _prompts((4,) * (lanes + 2), seed=5)
    outs = paged.generate_all(prompts)
    slot_outs = slot.generate_all(prompts)
    assert slot.stats()["slots_active_peak"] == 2
    assert paged.stats()["slots_active_peak"] >= 4
    assert paged.stats()["slots_active_peak"] == lanes
    if kv_dtype == "fp32":
        assert outs == slot_outs


def test_block_exhaustion_backpressures_submit_as_queue_full(tiny):
    """OOM-of-blocks maps onto the existing QueueFull path: with no
    engine thread draining, a full pool leaves requests queued and the
    bounded queue 429s the next submit."""
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=4, buckets=(8,),
                                    max_new_tokens=8, max_queue=2,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=2))
    p = _prompts((6,))[0]
    eng.submit(p)
    eng.step()                   # head admitted, pool now exhausted
    eng.submit(p)
    eng.submit(p)                # queue at max_queue=2
    with pytest.raises(QueueFull):
        eng.submit(p)
    assert eng.stats()["rejected_queue_full"] == 1


def test_unsatisfiable_footprint_rejected_not_livelocked(tiny):
    """A request needing more blocks than the POOL has can never be
    admitted by any amount of reclaim — submit must 413 it instead of
    parking it at the queue head forever (which would also starve
    every request behind it)."""
    from fengshen_tpu.serving import PromptTooLong
    model, params = tiny
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 32),
                                    max_new_tokens=32, max_queue=8,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=4))
    # bucket 32 + 32 new = 64 tokens = 4 blocks > 3 allocatable
    with pytest.raises(PromptTooLong, match="KV blocks"):
        eng.submit(_prompts((20,))[0])
    assert eng.stats()["rejected_prompt_too_long"] == 1
    # a satisfiable request still sails through
    req = eng.submit(_prompts((6,))[0], max_new_tokens=4)
    eng.run_until_idle()
    assert req.state == "finished"


def test_cancel_running_paged_request_frees_blocks(tiny):
    model, params = tiny
    prompts = _prompts((5, 6), seed=4)
    ref1 = _ref(model, params, prompts[1], 4)
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=1, buckets=(8,),
                                    max_new_tokens=50, max_queue=4,
                                    kv_layout="paged", kv_block_size=16,
                                    kv_num_blocks=5))
    r0 = eng.submit(prompts[0], max_new_tokens=50)
    r1 = eng.submit(prompts[1], max_new_tokens=4)
    eng.step()
    assert r0.state == "running"
    assert eng.stats()["kv_blocks_used"] == 4   # ceil((8+48... capped
    eng.cancel(r0.request_id)
    eng.run_until_idle()
    assert r0.state == "cancelled"
    assert r1.tokens == ref1     # reclaimed blocks decode untainted
    assert eng.stats()["kv_blocks_used"] == 0


# ---- pool state & config surface ----------------------------------------

def test_kv_stats_shape_on_stats(tiny):
    """The /stats KV-utilization keys (satellite: blocks, bytes,
    fragmentation, dtype) for both layouts."""
    model, params = tiny
    slot = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8,),
                                    max_new_tokens=4, max_queue=4))
    st = slot.stats()
    assert st["kv_layout"] == "slot" and st["kv_dtype"] == "fp32"
    assert st["kv_blocks_total"] == 2 and st["kv_block_tokens"] == 64
    # [2 slots, 64 max_len, 4 kv heads, 8 head_dim] x K+V x 2 layers
    assert st["kv_cache_bytes"] == 2 * 64 * 4 * 8 * 4 * 2 * 2

    paged = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8,),
                                    max_new_tokens=4, max_queue=4,
                                    kv_dtype="int8", **PAGED))
    st = paged.stats()
    assert st["kv_layout"] == "paged" and st["kv_dtype"] == "int8"
    assert st["kv_blocks_total"] == paged.num_blocks - 1
    assert st["kv_block_tokens"] == 16
    # int8 pool + fp32 per-(token, head) scales
    tokens = paged.num_blocks * 16
    assert st["kv_cache_bytes"] == \
        tokens * 4 * 8 * 1 * 2 * 2 + tokens * 4 * 4 * 2 * 2
    req = paged.submit(_prompts((6,))[0])
    paged.step()
    st = paged.stats()
    assert st["kv_blocks_used"] == 1          # ceil((8 + 4) / 16)
    assert st["kv_blocks_free"] == st["kv_blocks_total"] - \
        st["kv_blocks_used"]
    assert 0.0 <= st["kv_fragmentation"] < 1.0
    paged.cancel(req.request_id)
    paged.run_until_idle()


def test_engine_config_validates_kv_knobs(tiny):
    model, params = tiny
    with pytest.raises(ValueError, match="kv_layout"):
        EngineConfig(kv_layout="pagedd")
    with pytest.raises(ValueError, match="kv_dtype"):
        EngineConfig(kv_dtype="int4")
    with pytest.raises(ValueError, match="kv_block_size"):
        EngineConfig(kv_layout="paged", kv_block_size=0)
    with pytest.raises(ValueError, match="kv_max_blocks_per_slot"):
        ContinuousBatchingEngine(
            model, params,
            EngineConfig(buckets=(8,), kv_layout="paged",
                         kv_block_size=16, kv_max_blocks_per_slot=100))
    with pytest.raises(ValueError, match="kv_block_size"):
        ContinuousBatchingEngine(
            model, params, EngineConfig(buckets=(8,), kv_layout="paged",
                                        kv_block_size=128))


def test_reset_free_slots_parks_block_tables(tiny):
    """The paged analog of the free-lane clamp: inactive lanes' table
    rows are parked on the null block so their stray writes cannot
    land in reallocated blocks."""
    model, _ = tiny
    cache = init_pool_cache(model, 3, layout="paged", kv_dtype="fp32",
                            num_blocks=9, block_size=8,
                            max_blocks_per_slot=4)

    def fill(path, leaf):
        name = getattr(path[-1], "key", "")
        if name in ("block_table", "cache_index"):
            return leaf + 5
        return leaf
    cache = jax.tree_util.tree_map_with_path(fill, cache)
    out = reset_free_slots(cache, jnp.asarray([True, False, True]))

    def check(path, leaf):
        name = getattr(path[-1], "key", "")
        if name == "block_table":
            np.testing.assert_array_equal(np.asarray(leaf)[1], 0)
            np.testing.assert_array_equal(np.asarray(leaf)[0], 5)
        elif name == "cache_index":
            np.testing.assert_array_equal(np.asarray(leaf), [5, 0, 5])
        return leaf
    jax.tree_util.tree_map_with_path(check, out)


# ---- the pool is the layer loop's state under scan_layers (ISSUE 25) ----

TICKS = {
    "plain": {},
    "prompt_lookup": dict(spec_mode="prompt_lookup", spec_gamma=3),
    "self_draft": dict(spec_mode="self_draft", spec_gamma=3,
                       spec_draft_layers=1),
}
#: pool name -> (model dtype, EngineConfig.kv_dtype); "fp32" there means
#: "the model's own dtype"
POOLS = {"bf16": ("bfloat16", "fp32"), "fp32": ("float32", "fp32"),
         "int8": ("float32", "int8")}


def _decode_args(eng):
    """The engine's own decode call (`ContinuousBatchingEngine._tick`)."""
    head = (eng.params, eng._draft_params, eng._cache, eng._draft_cache) \
        if eng.self_draft else (eng.params, eng._cache)
    return head + (eng._history, eng._mask, eng._last_tok, eng._pos,
                   eng._phys, eng._active, eng._keys)


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("tick", sorted(TICKS))
def test_scanned_paged_decode_updates_pool_in_place(tick, pool):
    """Under scan_layers the paged pool is carried through the layer
    loop, not scanned: the compiled decode program holds no copy,
    dynamic-slice or dynamic-update-slice of the stack's or of one
    layer's pool shape, no second pool's worth of temporaries, and
    its donated pool is aliased to the returned one. The property,
    not a speed: as xs/ys of the scan every tick copied the whole pool
    six times on the chip (PERF.md, PR 25)."""
    import re
    dtype, kv_dtype = POOLS[pool]
    model, params = _make(scan=True, kv_heads=2, layers=4, dtype=dtype)
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=8, max_queue=8,
                                    kv_dtype=kv_dtype, kv_num_blocks=33,
                                    **TICKS[tick], **PAGED))
    stacks = [leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(eng._cache)[0]
              if getattr(path[-1], "key", "").startswith("cached_")]
    assert stacks[0].shape == (4, 33, 16, 2, 8)
    assert stacks[0].dtype.name == {"bf16": "bfloat16", "fp32": "float32",
                                    "int8": "int8"}[pool]
    pool_bytes = sum(leaf.nbytes for leaf in stacks)
    compiled = eng._decode_jit.lower(*_decode_args(eng)).compile()

    shapes = set()
    for leaf in stacks:
        shapes |= {leaf.shape, (1,) + leaf.shape[1:], leaf.shape[1:]}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"(copy|dynamic-slice|dynamic-update-slice)\(", line)
        if m and tuple(int(d) for d in m.group(1).split(",") if d) \
                in shapes:
            found.append(line.strip()[:120])
    assert not found, found
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    if pool != "bf16":
        # (the CPU backend widens a bf16 model's weights into float32
        # temporaries that outweigh a pool this small)
        assert mem.temp_size_in_bytes < pool_bytes


def _stack_layers(params, n_layers):
    """Unrolled `layers_<i>` params in the scanned layout."""
    model_p = {k: v for k, v in params["model"].items()
               if not k.startswith("layers_")}
    model_p["layers"] = {"layer": jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[params["model"][f"layers_{i}"] for i in range(n_layers)])}
    return {**params, "model": model_p}


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("tick", sorted(TICKS))
def test_scanned_and_unrolled_paged_engines_agree(tick, kv_dtype):
    """The same weights, the same staggered admissions, lanes reclaimed
    mid-run: the scanned engine (pool carried through the layer loop)
    and the unrolled one (a pool a layer) return identical tokens and
    leave identical pools, block tables and cursors."""
    flat_model, flat_params = _make(kv_heads=2, layers=4)
    scan_model, _ = _make(scan=True, kv_heads=2, layers=4)
    prompts = _prompts((5, 11, 16, 7, 9), seed=2)

    def run(model, params):
        eng = ContinuousBatchingEngine(
            model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                        max_new_tokens=9, max_queue=16,
                                        kv_dtype=kv_dtype,
                                        **TICKS[tick], **PAGED))
        reqs = [eng.submit(p) for p in prompts[:2]]
        for _ in range(3):
            eng.step()
        reqs += [eng.submit(p) for p in prompts[2:]]
        eng.run_until_idle()
        assert all(r.state == "finished" for r in reqs)
        return [r.tokens for r in reqs], eng._cache

    flat_tokens, flat_cache = run(flat_model, flat_params)
    scan_tokens, scan_cache = run(scan_model, _stack_layers(flat_params, 4))
    assert scan_tokens == flat_tokens
    if tick == "plain" and kv_dtype == "fp32":
        assert flat_tokens == [_ref(flat_model, flat_params, p, 9)
                               for p in prompts]
    scanned = scan_cache["model"]["layers"]["layer"]["self_attn"]
    for i in range(4):
        unrolled = flat_cache["model"][f"layers_{i}"]["self_attn"]
        assert sorted(unrolled) == sorted(scanned)
        for name, leaf in unrolled.items():
            np.testing.assert_array_equal(
                np.asarray(scanned[name][i]), np.asarray(leaf),
                err_msg=f"layer {i} {name}")
