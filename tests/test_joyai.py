"""JoyAI-LLM-Flash (`models/joyai`): latent attention on a one-leaf
cache and sigmoid-routed experts, through the serving engine's paged
pool, against the benchmark's plain float32 reference
(`benchmarks/references/joyai.py`, which imports nothing of the
program) on seeded random weights at a tiny size.

Tolerance: program and reference both compute in float32 here and
differ by summation order only, 2e-7 to 6e-7 at this size; the limit is
5e-5. The same program in bfloat16 reads 1e-2 to 4e-2 (asserted
below), so a bf16-for-float32 swap fails it by two orders.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.models.joyai import JoyAIConfig, JoyAIForCausalLM
from fengshen_tpu.models.joyai import modeling_joyai
from fengshen_tpu.models.joyai.modeling_joyai import expert_share
from fengshen_tpu.serving import (ContinuousBatchingEngine, EngineConfig,
                                  init_pool_cache)
from fengshen_tpu.serving.paged_cache import assign_paged, row_leaves
from fengshen_tpu.utils.generate import _prefill_cache, generate

reference = importlib.import_module("benchmarks.references.joyai")
weights = importlib.import_module("benchmarks.lib.weights")

TOLERANCE = 5e-5
SEED = 2 ** 31 + 26
REF_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta")
PAGED = dict(kv_layout="paged", kv_block_size=16)


def _ref_cfg(cfg: JoyAIConfig) -> dict:
    out = {k: getattr(cfg, k) for k in REF_KEYS}
    out.update(param_dtype=cfg.param_dtype, expert_block=4)
    return out


def _make(dtype="float32"):
    """(model, params, reference config, reference params): the
    program's tree is filled from the seed, leaf by leaf as the
    benchmark fills it, and the reference regenerates the same values
    from the seed alone."""
    cfg = JoyAIConfig.small_test_config(dtype=dtype)
    model = JoyAIForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    key = weights.base_key(SEED)
    params = weights.fill_like(key, shapes)
    rcfg = _ref_cfg(cfg)
    rparams = weights.fill(key, reference.param_shapes(rcfg))
    return model, params, rcfg, rparams


@pytest.fixture(scope="module")
def made():
    return _make()


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(1, 256, n).astype(np.int32)


def _reference_logits(rcfg, rparams, ids):
    return np.asarray(reference.forward_logits(
        rcfg, "highest", rparams, ids, np.arange(len(ids))))


# ---- against the reference ------------------------------------------------

def test_full_forward_agrees_with_the_reference(made):
    model, params, rcfg, rparams = made
    ids = _ids(24)
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    want = _reference_logits(rcfg, rparams, ids)
    assert np.abs(got - want).max() < TOLERANCE
    assert np.abs(want).max() > 0.1      # not a comparison of zeros


def test_the_reference_names_and_shapes_every_leaf_as_the_program():
    model, params, rcfg, _ = _make()
    program = {weights.path_str(p): (leaf.shape, leaf.dtype) for p, leaf in
               jax.tree_util.tree_flatten_with_path(params)[0]}
    want = {k: (tuple(s), jnp.dtype(d))
            for k, (s, d) in reference.param_shapes(rcfg).items()}
    assert program == want


# ---- the rows the reference judges ----------------------------------------

def test_pick_margin_is_the_last_pick_over_the_first_left_out():
    """By hand, top-2 of `scores + bias`: the bias moves the margin as
    it moves the pick."""
    cfg = {"num_experts_per_tok": 2}
    scores = jnp.asarray([[0.9, 0.5, 0.45, 0.1], [0.2, 0.6, 0.3, 0.7]])
    zero = jnp.zeros((4,))
    np.testing.assert_allclose(
        reference.pick_margin(cfg, scores, zero), [0.05, 0.3], atol=1e-7)
    bias = jnp.asarray([0.0, 0.0, 0.25, 0.0])     # expert 2 overtakes 1
    np.testing.assert_allclose(
        reference.pick_margin(cfg, scores, bias), [0.2, 0.05], atol=1e-7)


def test_the_reference_abstains_where_a_pick_is_within_its_margin_of_a_tie(
        made):
    """With `pick_margin` the float32 pass zeroes exactly the rows whose
    top-k pick is within that margin of a tie in some expert layer
    (recomputed here layer by layer) and leaves the others as they
    were; some rows fall on either side at this threshold."""
    _, _, rcfg, rparams = made
    ids = _ids(40, seed=9)
    rows = np.arange(8, 40)
    full = np.asarray(reference.forward_logits(
        rcfg, "highest", rparams, ids, rows))
    tau = [0.004, 0.006]
    judged = np.asarray(reference.forward_logits(
        dict(rcfg, pick_margin=tau), "highest", rparams, ids, rows))
    mm = reference.MATMULS["highest"]
    x = rparams["model/embed_tokens/embedding"][jnp.asarray(ids)][None] \
        .astype(jnp.float32)
    clear = np.ones(len(ids), bool)
    for i in range(rcfg["num_hidden_layers"]):
        prefix = reference.layer_prefix(i)
        lp = {k[len(prefix):]: v for k, v in rparams.items()
              if k.startswith(prefix)}
        if i == 0:
            x = reference._first_layer(rcfg, mm, x, lp)
            continue
        x, margin = reference._expert_layer(rcfg, mm, x, lp)
        clear &= np.asarray(margin) >= tau[i - 1]
    clear = clear[rows]
    assert 0 < clear.sum() < len(rows)
    np.testing.assert_array_equal(judged[clear], full[clear])
    assert not judged[~clear].any() and np.abs(full[~clear]).max() > 0.1


def test_a_control_standing_in_for_the_program_never_abstains(made):
    """A pass in another precision answers for every row, whatever
    `pick_margin` says: the program it stands for does."""
    _, _, rcfg, rparams = made
    ids = _ids(24, seed=10)
    rows = np.arange(4, 24)
    huge = dict(rcfg, pick_margin=[1.0, 1.0])       # no pick is that clear
    assert not np.asarray(reference.forward_logits(
        huge, "highest", rparams, ids, rows)).any()
    low = np.asarray(reference.forward_logits(
        huge, "int8", rparams, ids, rows))
    np.testing.assert_array_equal(low, np.asarray(reference.forward_logits(
        rcfg, "int8", rparams, ids, rows)))
    assert (np.abs(low).max(-1) > 0.1).all()


def _serve_through_the_paged_pool(model, params, prompt, new_ids):
    """Logits at every served position the way the engine produces
    them: the prompt on a contiguous batch-1 cache (full form), the
    cache scattered into a paged pool by `assign_paged`, then one
    token a step through the pool (absorbed form), lane 1 of 3."""
    pool = init_pool_cache(model, 3, layout="paged", kv_dtype="fp32",
                           num_blocks=20, block_size=8,
                           max_blocks_per_slot=6)
    n = len(prompt)
    pos = jnp.arange(n)[None]
    logits, primed = _prefill_cache(model, params, prompt[None], None, pos)
    table_row = jnp.asarray([7, 3, 11, 2, 19, 5], jnp.int32)
    pool = assign_paged(pool, primed, 1, table_row)
    out = [np.asarray(logits[0])]
    for t, tok in enumerate(new_ids):
        tokens = jnp.asarray([0, tok, 0], jnp.int32)[:, None]
        position = jnp.asarray([0, n + t, 0], jnp.int32)[:, None]
        step, mutated = model.apply(
            {"params": params, "cache": pool}, tokens,
            position_ids=position, init_cache=True, mutable=["cache"])
        pool = mutated["cache"]
        out.append(np.asarray(step[1]))
    return np.concatenate(out), pool


def test_prefill_then_paged_decode_agrees_with_the_reference(made):
    model, params, rcfg, rparams = made
    ids = _ids(31, seed=1)
    got, pool = _serve_through_the_paged_pool(model, params,
                                              jnp.asarray(ids[:19]), ids[19:])
    want = _reference_logits(rcfg, rparams, ids)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < TOLERANCE
    # every layer's cursor of the lane moved with it (the free lanes
    # wrote on the null block; the engine clamps theirs every tick)
    index = np.asarray(pool["model"]["cache_index"])
    assert index[:, 1].tolist() == [31] * 3


def test_a_bfloat16_program_fails_the_float32_tolerance():
    model, params, rcfg, rparams = _make(dtype="bfloat16")
    ids = _ids(24)
    got = np.asarray(model.apply({"params": params}, ids[None]),
                     np.float32)[0]
    assert np.abs(got - _reference_logits(rcfg, rparams, ids)).max() > \
        20 * TOLERANCE


def test_engine_serves_the_references_greedy_tokens(made):
    """Staggered admissions and a reclaimed lane through the
    continuous-batching engine's paged latent pool: every served token
    is the reference's best at its position (teacher-forced), and the
    routing counters ride the tick."""
    model, params, rcfg, rparams = made
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=6, max_queue=8,
                                    kv_num_blocks=9, **PAGED))
    assert eng._moe_shape == (2, 8)
    pool = eng._cache["model"]["cached_latent"]
    assert pool.shape == (3, 9, 16, 1, 128) and eng._kv_bytes == pool.nbytes
    prompts = [_ids(n, seed=n) for n in (5, 11, 16)]
    reqs = [eng.submit(p) for p in prompts[:2]]
    for _ in range(3):
        eng.step()
    reqs.append(eng.submit(prompts[2]))
    eng.run_until_idle()
    assert all(r.state == "finished" and len(r.tokens) == 6 for r in reqs)
    for prompt, req in zip(prompts, reqs):
        ids = np.concatenate([prompt, req.tokens]).astype(np.int32)
        logits = _reference_logits(rcfg, rparams, ids)
        rows = logits[len(prompt) - 1:len(ids) - 1]
        gaps = rows.max(-1) - rows[np.arange(6), req.tokens]
        assert gaps.max() < TOLERANCE, gaps
    from fengshen_tpu.observability import render_prometheus
    text = render_prometheus(eng.metrics.registry)
    got = {name: float(value) for name, _, value in
           (line.partition(" ") for line in text.splitlines())
           if name.startswith("fstpu_moe_")}
    ticks = got["fstpu_moe_layer_ticks_total"] / 2
    assert ticks >= 10 and ticks == int(ticks)
    # every live lane's token makes top_k picks in each expert layer
    assert got["fstpu_moe_assignments_total"] == 2 * 2 * 15
    assert 0 < got["fstpu_moe_experts_touched_total"] <= \
        got["fstpu_moe_assignments_total"]
    assert got["fstpu_moe_max_expert_tokens_total"] >= \
        got["fstpu_moe_layer_ticks_total"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_engine_decodes_the_same_tokens_through_the_latent_kernel(
        monkeypatch, dtype):
    """Greedy decode through the continuous-batching engine's paged
    pool — staggered admissions over two buckets (left-padded prompts),
    a reclaimed lane, a lane parked on the null block — once with the
    seam's latent read forced to the Mosaic kernel (interpret mode: its
    arithmetic on the CPU) and once to the xla lowering: the same
    tokens, request by request."""
    import functools
    seam = modeling_joyai.mla_decode_attention
    model, params, _, _ = _make(dtype)
    prompts = [_ids(n, seed=n) for n in (5, 11, 16)]

    def served(**forced):
        calls = []

        def read(*args, block_table=None, **kw):
            if block_table is None:     # a contiguous cache: xla's
                return seam(*args, **kw)
            calls.append(args[2].shape)
            return seam(*args, block_table=block_table, **kw, **forced)
        monkeypatch.setattr(modeling_joyai, "mla_decode_attention", read)
        eng = ContinuousBatchingEngine(
            model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                        max_new_tokens=6, max_queue=8,
                                        kv_num_blocks=9, **PAGED))
        reqs = [eng.submit(p) for p in prompts[:2]]
        for _ in range(3):
            eng.step()
        reqs.append(eng.submit(prompts[2]))
        eng.run_until_idle()
        assert all(r.state == "finished" for r in reqs)
        assert (3, 9, 16, 1, 128) in calls     # the paged stack was read
        return [list(r.tokens) for r in reqs]
    kernel = served(impl="pallas", interpret=True)
    assert all(len(t) == 6 for t in kernel)
    assert kernel == served(impl="xla")


def test_lockstep_generate_runs_on_the_contiguous_latent_cache():
    model, params, rcfg, rparams = _make()
    prompt = _ids(9, seed=3)
    out = np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                              max_new_tokens=5))[0]
    logits = _reference_logits(rcfg, rparams, out)
    rows = logits[len(prompt) - 1:-1]
    assert (rows.max(-1) - rows[np.arange(5), out[len(prompt):]]).max() < \
        TOLERANCE


# ---- the two forms of the attention -----------------------------------------

def _dense_full_form(q_nope, q_rope, rows, w_kvb, start, *, key_valid=None,
                     scale):
    """The full form as the model had it inline before the seam
    (`latent_prefill_attention`'s arguments): EVERY row of the lane
    expanded, dense float32 scores over the lane's whole length, one
    softmax, the probabilities normalised and then rounded."""
    rank, dn, dr = w_kvb.shape[0], q_nope.shape[-1], q_rope.shape[-1]
    seq, total = q_nope.shape[1], rows.shape[1]
    mask = jnp.arange(total)[None, None, :] <= \
        (start + jnp.arange(seq))[None, :, None]
    if key_valid is not None:
        mask = mask & key_valid[:, None, :].astype(bool)
    kvb = jnp.einsum("btc,chd->bthd", rows[..., :rank], w_kvb)
    scores = (
        jnp.einsum("bshd,bthd->bhst", q_nope, kvb[..., :dn],
                   preferred_element_type=jnp.float32) +
        jnp.einsum("bshr,btr->bhst", q_rope, rows[..., rank:rank + dr],
                   preferred_element_type=jnp.float32)) * scale
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, kvb[..., dn:])


def _left_padded(model, params, n_real, bucket, cached):
    """Logits of a prompt left-padded to `bucket` the way the engine's
    prefill program runs it (mask-cumsum positions; `cached`: onto a
    fresh batch-1 cache of `max_position_embeddings` rows, else no
    cache at all), the real rows only, and the cache it left."""
    ids = np.zeros((1, bucket), np.int32)
    ids[0, bucket - n_real:] = _ids(n_real, seed=6)
    mask = jnp.asarray(ids > 0, jnp.int32)
    position_ids = jnp.clip(mask.cumsum(-1) - 1, 0, None)
    if cached:
        logits, cache = _prefill_cache(model, params, jnp.asarray(ids),
                                       mask, position_ids)
    else:
        logits, cache = model.apply({"params": params}, jnp.asarray(ids),
                                    mask, position_ids), None
    return np.asarray(logits, np.float32)[0, bucket - n_real:], cache


@pytest.mark.parametrize("cached", [False, True],
                         ids=["no_cache", "batch1_cache"])
def test_a_left_padded_prompt_through_the_seam_equals_the_dense_chain(
        made, monkeypatch, cached):
    """The whole-prompt prefill through `latent_prefill_attention` (on
    the CPU the walk: rows 0 .. S of the lane in blocks, the padding
    masked as keys) against the dense chain the model had inline, in
    float32: 17 real tokens behind 7 of padding, with no cache and onto
    the batch-1 cache of 128 rows; and the rows left in that cache."""
    model, params, _, _ = made
    got, cache = _left_padded(model, params, 17, 24, cached)
    monkeypatch.setattr(modeling_joyai, "latent_prefill_attention",
                        _dense_full_form)
    want, cache_d = _left_padded(model, params, 17, 24, cached)
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < TOLERANCE
    if cached:
        np.testing.assert_allclose(
            np.asarray(cache["model"]["cached_latent"]),
            np.asarray(cache_d["model"]["cached_latent"]), atol=1e-6)
        assert np.asarray(cache["model"]["cache_index"]).tolist() == [24] * 3


def test_a_left_padded_prompt_through_the_kernel_equals_the_walk(
        latent_kernel_interpreted, monkeypatch):
    """At widths the full form's kernel tiles (rank 128, heads of 128 +
    64 and 128, bfloat16, a 128-token bucket onto a cache of 256 rows)
    every layer's prefill takes the Mosaic kernel (interpret mode) with
    the padding as `key_valid`: the real rows' logits against the walk's
    and against the dense chain's, and the rows in the cache. The
    three round the same bfloat16 operands; the dense chain rounds the
    NORMALISED probabilities, the online forms the unnormalised ones."""
    model, params = _make_tiling()
    want, cache_w = _left_padded(model, params, 91, 128, True)
    with latent_kernel_interpreted() as took:
        got, cache_k = _left_padded(model, params, 91, 128, True)
    assert took == ["q=(1, 128, 2, 128)+64:bfloat16 "
                    "rows=(1, 256, 256):bfloat16 key_valid"] * 3
    monkeypatch.setattr(modeling_joyai, "latent_prefill_attention",
                        _dense_full_form)
    dense, _ = _left_padded(model, params, 91, 128, True)
    top = np.abs(want).max()
    assert np.isfinite(got).all() and top > 0.1
    np.testing.assert_allclose(got, want, atol=0.02 * top)
    np.testing.assert_allclose(got, dense, atol=0.03 * top)
    rows_k, rows_w = (np.asarray(c["model"]["cached_latent"], np.float32)
                      for c in (cache_k, cache_w))
    # layer 0's rows are written before any attention ran; the later
    # layers' follow the layers before them to bfloat16's last place
    np.testing.assert_array_equal(rows_k[0], rows_w[0])
    np.testing.assert_allclose(rows_k, rows_w, atol=0.02 * np.abs(rows_w).max())


def _make_tiling():
    """The tiny model at latent widths the full form's kernel tiles."""
    cfg = JoyAIConfig.small_test_config(
        dtype="bfloat16", kv_lora_rank=128, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_attention_heads=2,
        max_position_embeddings=256)
    model = JoyAIForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    return model, weights.fill_like(weights.base_key(SEED), shapes)


def test_absorbed_decode_equals_the_full_form_on_the_same_cache(
        monkeypatch):
    """One more token on a primed contiguous cache, once absorbed (the
    seam's latent read over the rows) and once in the full form (every
    cached row expanded into per-head keys and values)."""
    model, params, _, _ = _make()
    ids = jnp.asarray(_ids(14, seed=4))
    pos = jnp.arange(13)[None]
    _, primed = _prefill_cache(model, params, ids[None, :13], None, pos)

    def step():
        logits, mutated = model.apply(
            {"params": params, "cache": primed}, ids[None, 13:],
            position_ids=jnp.asarray([[13]]), init_cache=True,
            mutable=["cache"])
        return np.asarray(logits), mutated["cache"]
    absorbed, cache_a = step()
    monkeypatch.setattr(modeling_joyai, "ABSORBED_WINDOW", 0)
    full, cache_f = step()
    assert np.abs(absorbed - full).max() < TOLERANCE
    assert np.abs(absorbed).max() > 0.1
    np.testing.assert_allclose(
        np.asarray(cache_a["model"]["cached_latent"]),
        np.asarray(cache_f["model"]["cached_latent"]), atol=1e-6)


def test_a_cache_row_is_the_normed_latent_the_rotated_key_and_zeros():
    model, params, _, _ = _make()
    cfg = model.config
    assert cfg.latent_width == 128
    assert JoyAIConfig(num_nextn_predict_layers=0).latent_width == 640
    _, primed = _prefill_cache(model, params, jnp.asarray(_ids(6))[None],
                               None, jnp.arange(6)[None])
    rows = np.asarray(primed["model"]["cached_latent"])
    assert rows.shape == (3, 1, 128, 1, 128)
    used = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    assert np.abs(rows[:, 0, :6, 0, :used]).min(axis=-1).max() > 0
    assert not rows[:, 0, :, 0, used:].any() and not rows[:, 0, 6:].any()
    assert np.asarray(primed["model"]["cache_index"]).tolist() == [6] * 3


def test_a_long_window_on_the_paged_pool_is_refused():
    model, params, _, _ = _make()
    pool = init_pool_cache(model, 2, layout="paged", kv_dtype="fp32",
                           num_blocks=9, block_size=8,
                           max_blocks_per_slot=4)
    with pytest.raises(ValueError, match="contiguous batch-1 cache"):
        model.apply({"params": params, "cache": pool},
                    jnp.zeros((2, 12), jnp.int32), init_cache=True,
                    mutable=["cache"])


# ---- the pool built from the declared leaves ------------------------------

def test_the_pool_is_built_from_the_one_leaf_the_model_declares():
    model, _, _, _ = _make()
    pool = init_pool_cache(model, 4, layout="paged", kv_dtype="fp32",
                           num_blocks=9, block_size=8,
                           max_blocks_per_slot=4)
    assert row_leaves(pool["model"]) == ["cached_latent"]
    assert {k: v.shape for k, v in pool["model"].items()} == {
        "cached_latent": (3, 9, 8, 1, 128), "cache_index": (3, 4),
        "block_table": (3, 4, 4)}
    slot = init_pool_cache(model, 4, layout="slot", kv_dtype="fp32")
    assert slot["model"]["cached_latent"].shape == (3, 4, 128, 1, 128)


def test_assign_paged_scatters_a_one_leaf_cache_into_its_blocks():
    model, params, _, _ = _make()
    pool = init_pool_cache(model, 3, layout="paged", kv_dtype="fp32",
                           num_blocks=9, block_size=8,
                           max_blocks_per_slot=2)
    _, primed = _prefill_cache(model, params, jnp.asarray(_ids(11))[None],
                               None, jnp.arange(11)[None])
    out = assign_paged(pool, primed, 2, jnp.asarray([6, 4], jnp.int32))
    rows = np.asarray(primed["model"]["cached_latent"])[:, 0, :16]
    got = np.asarray(out["model"]["cached_latent"])
    np.testing.assert_array_equal(got[:, 6], rows[:, :8])
    np.testing.assert_array_equal(got[:, 4], rows[:, 8:])
    untouched = [b for b in range(9) if b not in (4, 6)]
    assert not got[:, untouched].any()
    assert np.asarray(out["model"]["block_table"])[:, 2].tolist() == \
        [[6, 4]] * 3
    assert np.asarray(out["model"]["cache_index"])[:, 2].tolist() == [11] * 3


def test_an_int8_latent_pool_is_refused_by_name():
    model, params, _, _ = _make()
    with pytest.raises(ValueError, match="cached_latent.*no int8 form"):
        ContinuousBatchingEngine(
            model, params, EngineConfig(num_slots=2, buckets=(8,),
                                        max_new_tokens=4, kv_dtype="int8",
                                        **PAGED)).warmup()


def test_handoff_refuses_a_latent_cache_by_name():
    from fengshen_tpu.serving.handoff import (AdoptDecline, HandoffError,
                                              _scatter_payload, export_lane)
    model, params, _, _ = _make()
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8,),
                                    max_new_tokens=8, **PAGED))
    req = eng.submit(_ids(5))
    eng.step()
    with pytest.raises(HandoffError, match=r"\['cached_latent'\]"):
        export_lane(eng, req.request_id)
    with pytest.raises(AdoptDecline, match=r"\['cached_latent'\]"):
        _scatter_payload(eng, {"layers": []}, 0, 4, None, None)


def test_the_decode_program_carries_the_latent_stack_through_every_layer():
    """One `[L, ...]` stack for the dense layer and the expert layers:
    the compiled decode program holds no dynamic-slice or
    dynamic-update-slice of the stack's or of one layer's pool shape
    (no layer's pool is sliced out or written back) and its donated
    pool is aliased to the returned one. The CPU backend re-lays a
    `[.., 1, width]` array out around its gather, so the copies that
    tests/test_serving_paged.py also rules out are ruled out where
    they matter, on the program compiled for a described v5e
    (tests/test_compile_for_v5e.py)."""
    model, params, _, _ = _make()
    eng = ContinuousBatchingEngine(
        model, params, EngineConfig(num_slots=2, buckets=(8, 16),
                                    max_new_tokens=8, kv_num_blocks=33,
                                    **PAGED))
    pool = eng._cache["model"]["cached_latent"]
    assert pool.shape == (3, 33, 16, 1, 128)
    compiled = eng._decode_jit.lower(
        eng.params, eng._cache, eng._history, eng._mask, eng._last_tok,
        eng._pos, eng._phys, eng._active, eng._keys).compile()
    shapes = {pool.shape, (1,) + pool.shape[1:], pool.shape[1:]}
    found = []
    for line in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"(dynamic-slice|dynamic-update-slice)\(", line)
        if m and tuple(int(d) for d in m.group(1).split(",") if d) \
                in shapes:
            found.append(line.strip()[:120])
    assert not found, found
    assert compiled.memory_analysis().alias_size_in_bytes >= pool.nbytes


# ---- a chip's share of the experts ---------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_references_layer():
    """Four shares of two experts each route over all eight, compute
    their own experts' part, and one of them the shared expert: summed,
    they are the uncut reference's expert feed-forward."""
    from fengshen_tpu.ops.moe import RoutedExperts
    model, params, rcfg, rparams = _make()
    cfg = model.config
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 13, cfg.hidden_size))
    layer_p = params["model"]["layers_1"]["mlp"]
    prefix = reference.layer_prefix(1)
    lp = {k[len(prefix):]: v for k, v in rparams.items()
          if k.startswith(prefix + "mlp/")}
    want = np.asarray(reference.routed(
        rcfg, reference.MATMULS["highest"], h[0], lp))

    def share(first, count):
        layer = RoutedExperts(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            num_experts=cfg.n_routed_experts,
            top_k=cfg.num_experts_per_tok, scoring="sigmoid",
            score_bias=True, norm_topk_prob=True,
            routed_scaling_factor=cfg.routed_scaling_factor,
            n_shared_experts=1, experts_held=(first, count),
            shared_here=first == 0, dtype=jnp.float32)
        held = dict(layer_p, **{k: layer_p[k][first:first + count]
                                for k in ("experts_gate", "experts_up",
                                          "experts_down")})
        if first:
            held.pop("shared_experts")
        return np.asarray(layer.apply({"params": held}, h))[0]
    parts = [share(first, 2) for first in (0, 2, 4, 6)]
    assert np.abs(sum(parts) - want).max() < TOLERANCE
    assert all(np.abs(p).max() > 1e-4 for p in parts)


def test_a_share_of_the_model_is_the_reference_given_the_same_share():
    """`experts_held` through the whole model: the program holding
    experts 2..5 of every expert layer equals the reference told the
    same, and differs from the uncut model."""
    model, params, rcfg, rparams = _make()
    cfg, held = expert_share(model.config, params, 2, 4)
    ids = _ids(17, seed=6)
    got = np.asarray(JoyAIForCausalLM(cfg).apply({"params": held},
                                                 ids[None]))[0]
    share_cfg = dict(rcfg, experts_held=[2, 4], expert_block=2)
    share_params = {
        k: (v[2:6] if "/mlp/experts_" in k else v)
        for k, v in rparams.items()}
    want = _reference_logits(share_cfg, share_params, ids)
    assert np.abs(got - want).max() < TOLERANCE
    assert np.abs(want - _reference_logits(rcfg, rparams, ids)).max() > 1e-3


def test_the_config_reads_the_published_keys_and_says_what_it_lacks(capsys):
    cfg = JoyAIConfig()
    assert (cfg.num_hidden_layers, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.kv_lora_rank,
            cfg.qk_rope_head_dim) == (40, 256, 8, 512, 64)
    with pytest.raises(ValueError, match="rope_scaling"):
        JoyAIConfig(rope_scaling={"type": "yarn"})
    with pytest.raises(ValueError, match="group-limited"):
        JoyAIConfig(n_group=8, topk_group=4)


def test_the_hf_key_table_fills_the_programs_tree():
    """A state dict under the published names, torch's [out, in]
    layout and one module an expert converts to the tree `init` builds,
    leaf for leaf, and the converted model computes what the source
    weights say (the router's row e is expert e's)."""
    from fengshen_tpu.models.joyai.convert import torch_to_params
    cfg = JoyAIConfig.small_test_config(dtype="float32")
    model = JoyAIForCausalLM(cfg)
    want = model.init(jax.random.PRNGKey(3),
                      jnp.zeros((1, 4), jnp.int32))["params"]
    flat = want
    state = {"model.embed_tokens.weight":
             flat["model"]["embed_tokens"]["embedding"],
             "model.norm.weight": flat["model"]["norm"]["scale"],
             "lm_head.weight": flat["lm_head"]["kernel"].T,
             # the multi-token-prediction module's keys are passed over
             f"model.layers.{cfg.num_hidden_layers}.eh_proj.weight":
             np.zeros((2, 2))}
    for i in range(cfg.num_hidden_layers):
        layer, pre = flat["model"][f"layers_{i}"], f"model.layers.{i}"
        for name, leaf in layer["self_attn"].items():
            state[f"{pre}.self_attn.{name}.weight"] = leaf["scale"] \
                if "scale" in leaf else leaf["kernel"].T
        for norm in ("input_layernorm", "post_attention_layernorm"):
            state[f"{pre}.{norm}.weight"] = layer[norm]["scale"]
        mlp = layer["mlp"]
        if i == 0:
            for proj, leaf in mlp.items():
                state[f"{pre}.mlp.{proj}.weight"] = leaf["kernel"].T
            continue
        state[f"{pre}.mlp.gate.weight"] = mlp["router"]["kernel"].T
        state[f"{pre}.mlp.gate.e_score_correction_bias"] = \
            mlp["e_score_correction_bias"]
        for proj in ("gate", "up", "down"):
            for e in range(cfg.n_routed_experts):
                state[f"{pre}.mlp.experts.{e}.{proj}_proj.weight"] = \
                    mlp[f"experts_{proj}"][e].T
            state[f"{pre}.mlp.shared_experts.{proj}_proj.weight"] = \
                mlp["shared_experts"][f"{proj}_proj"]["kernel"].T
    got = torch_to_params({k: np.asarray(v) for k, v in state.items()}, cfg)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)), got, want)
