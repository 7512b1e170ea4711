"""Kimi-Linear through the engine against its plain reference
(`benchmarks/references/kimi_linear.py`), at a tiny size on the CPU (the
dense layer and one period: kda, kda, kda, latent, kda; 8 experts top-2,
4 heads of 16, vocabulary 64): windowed prefill and decode through the
paged latent pool and both recurrent states of a lane, latent
attention's two forms, a chip's share of the experts, and the assumed
checkpoint layout."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.references import kimi_linear as reference
from fengshen_tpu.models.kimi_linear import (KimiLinearConfig,
                                             KimiLinearForCausalLM)
from fengshen_tpu.models.model_utils import expert_share
from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                         EngineConfig)
from fengshen_tpu.serving.paged_cache import (assign_paged, init_pool_cache,
                                              positional_leaves)

REFERENCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "linear_attn_config",
    "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "num_experts",
    "num_experts_per_token", "num_shared_experts", "first_k_dense_replace",
    "moe_renormalize", "routed_scaling_factor", "rms_norm_eps")

#: float32 on both sides, the same mathematics in another order of
#: operations (anchored chunks against a token-by-token scan, a walk
#: with an online softmax against whole rows, a sorted grouped matmul
#: against every expert weighed densely): rounding only
ATOL = 1e-5


def _reference_config(cfg):
    rcfg = {k: getattr(cfg, k) for k in REFERENCE_KEYS}
    rcfg.update(param_dtype="float32", shared_here=cfg.shared_here)
    if cfg.experts_held:
        rcfg["experts_held"] = list(cfg.experts_held)
    return rcfg


@pytest.fixture(scope="module")
def tiny():
    """(config, model, params, the reference's config and params): the
    same seeded values under the same leaf names on both sides."""
    cfg = KimiLinearConfig.small_test_config()
    model = KimiLinearForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    key = weights.base_key(3)
    params = weights.fill_like(key, shapes)
    rcfg = _reference_config(cfg)
    rshapes = reference.param_shapes(rcfg)
    assert set(weights.flat(params)) == set(rshapes)
    return cfg, model, params, rcfg, weights.fill(key, rshapes)


def _reference_logits(tiny, ids):
    _, _, _, rcfg, rparams = tiny
    pad = np.zeros((128,), np.int32)
    pad[:len(ids)] = ids
    return np.asarray(reference.forward_logits(
        rcfg, "highest", rparams, pad, np.arange(len(ids))))


def _engine(model, params, **kw):
    base = dict(num_slots=3, buckets=(16,), max_new_tokens=24,
                kv_layout="paged", kv_block_size=32, max_queue=8)
    base.update(kw)
    return ContinuousBatchingEngine(model, params, EngineConfig(**base))


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, 64, size=(n,)).astype(np.int32)


# ---- the model against the reference ----------------------------------

def test_layer_types_follow_the_two_lists_counted_from_one():
    cfg = KimiLinearConfig.small_test_config()
    assert cfg.layer_types == ("kda", "kda", "kda", "full_attention", "kda")
    assert reference.layer_types(_reference_config(cfg)) == \
        list(cfg.layer_types)
    published = KimiLinearConfig()
    kinds = published.layer_types
    assert len(kinds) == 27 and kinds.count("full_attention") == 7
    assert [i + 1 for i, k in enumerate(kinds) if k == "full_attention"] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert (published.kda_dim, published.latent_width) == (4096, 640)
    with pytest.raises(ValueError, match="name every layer once"):
        KimiLinearConfig.small_test_config(num_hidden_layers=6)
    with pytest.raises(ValueError, match="full-rank query"):
        KimiLinearConfig.small_test_config(q_lora_rank=16)


def test_auto_resolves_the_published_model_type(tmp_path):
    from fengshen_tpu.models.auto import auto_factory
    module, config_name, heads = auto_factory._resolve("kimi_linear")
    assert getattr(module, config_name) is KimiLinearConfig
    assert getattr(module, heads["causal_lm"]) is KimiLinearForCausalLM
    cfg = KimiLinearConfig.small_test_config()
    cfg.save_pretrained(str(tmp_path))
    again = KimiLinearConfig.from_pretrained(str(tmp_path))
    assert again.linear_attn_config == cfg.linear_attn_config
    assert again.layer_types == cfg.layer_types


def test_plain_forward_matches_reference(tiny):
    _, model, params, _, _ = tiny
    ids = _prompt(100)
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    np.testing.assert_allclose(got, _reference_logits(tiny, ids), atol=ATOL)


def test_a_head_gated_by_the_mean_of_its_channels_is_not_the_model(
        tiny, monkeypatch):
    """The comparison above refuses a program that gives every channel
    of a head the mean of their gates."""
    from fengshen_tpu.models.kimi_linear import modeling_kimi_linear as m
    _, model, params, _, _ = tiny
    real = m.gated_delta_prefill

    def averaged(q, k, v, g, *rest, **kw):
        mean = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        return real(q, k, v, mean, *rest, **kw)
    monkeypatch.setattr(m, "gated_delta_prefill", averaged)
    ids = _prompt(100)
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    assert np.abs(got - _reference_logits(tiny, ids)).max() > 20 * ATOL


@pytest.mark.parametrize("layout", ["paged", "slot"])
@pytest.mark.parametrize("n_prompt", [12, 37, 80])
def test_engine_serves_reference_argmax(tiny, layout, n_prompt):
    """One window (12), three with the last partial (37), five whole
    (80: three blocks of 32 and a fourth with the output); then 24
    ticks through the pool, the latent layer read absorbed. Every served
    token is the reference's best at its position."""
    _, model, params, _, _ = tiny
    eng = _engine(model, params, kv_layout=layout)
    prompt = _prompt(n_prompt)
    out, = eng.generate_all([prompt], 24)
    logits = _reference_logits(tiny, np.concatenate([prompt, out]))[
        n_prompt - 1:-1]
    gaps = logits.max(-1) - logits[np.arange(24), out]
    assert gaps.max() <= 2e-5
    assert eng.stats()["prefills_per_bucket"] == {16: -(-n_prompt // 16)}


def test_window_and_tick_logits_match_reference_and_both_states(tiny):
    """The logits themselves, on a contiguous cache: windows of 16 (the
    last holds 11 real tokens, padded on the right), then one token at a
    time; and BOTH states and the latent rows after the windows are
    those of the whole prompt in one window."""
    from fengshen_tpu.serving.cache import abstract_init
    from fengshen_tpu.utils.generate import _rollback_cache
    cfg, model, params, _, _ = tiny
    ids = _prompt(100)
    want = _reference_logits(tiny, ids)

    def fresh():
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            abstract_init(model, 1)["cache"])

    @jax.jit
    def call(cache, chunk, start, n_valid):
        mask = (jnp.arange(cfg.max_position_embeddings) <
                start + n_valid)[None]
        logits, mut = model.apply(
            {"params": params, "cache": cache}, chunk, attention_mask=mask,
            position_ids=start + jnp.arange(chunk.shape[1])[None],
            init_cache=True, mutable=["cache"])
        return logits[0], _rollback_cache(mut["cache"],
                                          chunk.shape[1] - n_valid)

    n_prompt, width = 75, 16
    cache = fresh()
    for start in range(0, n_prompt, width):
        n_valid = min(width, n_prompt - start)
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :n_valid] = ids[start:start + n_valid]
        logits, cache = call(cache, chunk, start, n_valid)
        np.testing.assert_allclose(logits[:n_valid],
                                   want[start:start + n_valid], atol=ATOL)
    whole = np.zeros((1, 80), np.int32)
    whole[0, :n_prompt] = ids[:n_prompt]
    _, at_once = call(fresh(), whole, 0, n_prompt)
    for name in ("state_delta", "state_conv"):
        np.testing.assert_allclose(cache["model"][name],
                                   at_once["model"][name], atol=ATOL)
    np.testing.assert_allclose(
        cache["model"]["cached_latent"][:, :, :n_prompt],
        at_once["model"]["cached_latent"][:, :, :n_prompt], atol=ATOL)
    assert int(cache["model"]["cache_index"][0]) == n_prompt
    for t in range(n_prompt, 100):
        logits, cache = call(cache, ids[None, t:t + 1], t, 1)
        np.testing.assert_allclose(logits[0], want[t], atol=ATOL)


# ---- latent attention's two forms --------------------------------------

def _latent_case(seed=0, B=2, T=96, S=16, H=4, rank=32, dn=16, dr=8, dv=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    rows = jnp.concatenate([f(B, T, rank + dr), jnp.zeros((B, T, 24))], -1)
    return f(B, S, H, dn), f(B, S, H, dr), rows, f(rank, H, dn + dv)


def _dense_latent(qn, qs, rows, w_kvb, at, scale):
    """Keys and values of every head expanded whole, then plain causal
    softmax: `at` `[B, S]` the queries' positions."""
    rank, dn = w_kvb.shape[0], qn.shape[-1]
    dr = qs.shape[-1]
    kv = jnp.einsum("btc,chd->bthd", rows[..., :rank], w_kvb)
    s = (jnp.einsum("bshd,bthd->bhst", qn, kv[..., :dn]) +
         jnp.einsum("bshr,btr->bhst", qs, rows[..., rank:rank + dr])) * scale
    ok = jnp.arange(rows.shape[1])[None, None, None] <= at[:, None, :, None]
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", p, kv[..., dn:])


@pytest.mark.parametrize("q_tile,key_block", [(2048, 512), (8, 32), (4, 96)])
def test_the_latent_walk_equals_dense_attention(q_tile, key_block):
    """A window of 16 queries at position 40 of a lane of 96 rows, in
    one tile or in tiles of queries, blocks of keys of any size."""
    from fengshen_tpu.ops.latent_attention import latent_prefill_walk
    qn, qs, rows, w_kvb = _latent_case()
    got = latent_prefill_walk(qn, qs, rows, w_kvb, jnp.int32(40),
                              scale=0.2, q_tile=q_tile, key_block=key_block)
    at = jnp.broadcast_to(40 + jnp.arange(16), (2, 16))
    np.testing.assert_allclose(
        got, _dense_latent(qn, qs, rows, w_kvb, at, 0.2), rtol=2e-5,
        atol=1e-5)


def test_windows_through_the_kernel_equal_windows_through_the_walk(
        latent_kernel_interpreted):
    """Three windows of 128 tokens onto a carried batch-1 cache of 512
    rows, at widths the full form's kernel tiles (rank 128, heads of
    128 + 64 and 128, bfloat16): the model's logits with the seam on
    the Mosaic kernel (interpret mode) against the same windows on
    `latent_prefill_walk`, the path before the seam, and the rows both
    leave in the cache. Kernel and walk round the same operands and
    partition the keys differently (blocks of 512 against the walk's
    own): bfloat16's last place in one layer's output."""
    from fengshen_tpu.serving.cache import abstract_init
    cfg = KimiLinearConfig.small_test_config(
        dtype="bfloat16", kv_lora_rank=128, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_attention_heads=2,
        max_position_embeddings=512)
    model = KimiLinearForCausalLM(cfg)
    params = weights.fill_like(weights.base_key(5), jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]))
    ids = _prompt(384, seed=2)

    def windows():
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            abstract_init(model, 1)["cache"])
        out = []
        for start in range(0, 384, 128):
            logits, mut = model.apply(
                {"params": params, "cache": cache},
                ids[None, start:start + 128],
                attention_mask=(jnp.arange(512) < start + 128)[None],
                init_cache=True, mutable=["cache"])
            cache = mut["cache"]
            out.append(np.asarray(logits[0], np.float32))
        return np.concatenate(out), cache
    want, cache_w = windows()
    with latent_kernel_interpreted() as took:
        got, cache_k = windows()
    assert took == ["q=(1, 128, 2, 128)+64:bfloat16 "
                    "rows=(1, 512, 256):bfloat16"] * 3
    assert np.isfinite(got).all() and np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=0.02 * np.abs(want).max())
    np.testing.assert_array_equal(
        np.asarray(cache_k["model"]["cached_latent"], np.float32),
        np.asarray(cache_w["model"]["cached_latent"], np.float32))


def test_the_absorbed_tick_equals_the_full_form():
    """One query a lane read through the seam's latent entry (the query
    multiplied into the latent space, the heads attending over the rows
    themselves) gives the full form's heads of values."""
    from fengshen_tpu.ops.pallas.decode_attention import mla_decode_attention
    qn, qs, rows, w_kvb = _latent_case(seed=1, S=1)
    t = jnp.asarray([13, 90])
    valid = jnp.arange(96)[None, None, :] <= t[:, None, None]
    out = mla_decode_attention(
        jnp.einsum("bshd,chd->bshc", qn, w_kvb[..., :16]), qs,
        rows[:, :, None], valid, scale=0.2)
    got = jnp.einsum("bshc,chd->bshd", out, w_kvb[..., 16:])
    np.testing.assert_allclose(
        got, _dense_latent(qn, qs, rows, w_kvb, t[:, None], 0.2), rtol=2e-5,
        atol=1e-5)


def test_a_window_onto_a_pool_of_lanes_is_refused(tiny):
    _, model, params, _, _ = tiny
    pool = init_pool_cache(model, 2, layout="paged", num_blocks=9,
                           block_size=32, max_blocks_per_slot=4)
    with pytest.raises(ValueError, match="contiguous batch-1 cache"):
        model.apply({"params": params, "cache": pool},
                    jnp.zeros((2, 4), jnp.int32), init_cache=True,
                    mutable=["cache"])


# ---- rows and both states in the pool ----------------------------------

def _lane_states(eng, slot):
    tree = eng._cache["model"]
    return (np.asarray(tree["state_delta"][:, slot]),
            np.asarray(tree["state_conv"][:, slot]))


def test_dead_lane_keeps_both_states_bit_for_bit(tiny):
    """Assign, release, a dead lane under the tick's live mask, and the
    lane taken again."""
    _, model, params, _, _ = tiny
    eng = _engine(model, params, num_slots=2)
    long_req = eng.submit(_prompt(40), 24)
    short_req = eng.submit(_prompt(20, seed=1), 3)
    while not short_req.done:
        with eng._cv:
            eng._tick_locked(ahead=True)    # one tick in flight
    slot = 1 - long_req.slot
    before = _lane_states(eng, slot)
    assert all(np.abs(s).max() > 0 for s in before)
    assert eng.stats()["kv_blocks_used"] == len(eng._slot_blocks[
        long_req.slot])                     # the short lane's went back
    for _ in range(6):
        with eng._cv:
            eng._tick_locked(ahead=True)
    for was, now in zip(before, _lane_states(eng, slot)):
        np.testing.assert_array_equal(now, was)
    # the freed lane is taken again: what it serves is what a fresh
    # engine serves, so it started from the states it was assigned
    again = eng.submit(_prompt(50, seed=2), 8)
    eng.run_until_idle()
    assert len(again.tokens) == 8
    alone, = _engine(model, params, num_slots=2).generate_all(
        [_prompt(50, seed=2)], 8)
    assert again.tokens == alone
    assert len(long_req.tokens) == 24


def test_pool_holds_latent_rows_and_two_states(tiny):
    """No new kind of leaf: `row_leaves` pages the `cached_latent`,
    `state_leaves` holds the two states a lane; the gauges count both,
    the attended counter one latent layer's tokens a lane."""
    cfg, model, params, _, _ = tiny
    bf16 = KimiLinearForCausalLM(dataclasses.replace(cfg, dtype="bfloat16"))
    pool = init_pool_cache(bf16, 3, layout="paged", num_blocks=9,
                           block_size=32, max_blocks_per_slot=4)["model"]
    # 32 + 8 values padded to a whole 128
    assert pool["cached_latent"].shape == (1, 9, 32, 1, 128)
    assert pool["cached_latent"].dtype == jnp.bfloat16
    assert pool["block_table"].shape == (1, 3, 4)
    assert pool["state_delta"].shape == (4, 3, 4, 16, 16)
    assert pool["state_delta"].dtype == jnp.float32
    assert pool["state_conv"].shape == (4, 3, 3, 3 * 64)
    assert pool["state_conv"].dtype == jnp.bfloat16
    assert positional_leaves({"model": pool}) == ["state_conv",
                                                  "state_delta"]
    rng = np.random.default_rng(0)
    primed = {"model": {
        "cached_latent": jnp.asarray(rng.normal(size=(1, 1, 128, 1, 128)),
                                     jnp.bfloat16),
        "cache_index": jnp.full((1,), 70, jnp.int32),
        "state_delta": jnp.asarray(rng.normal(size=(4, 1, 4, 16, 16)),
                                   jnp.float32),
        "state_conv": jnp.asarray(rng.normal(size=(4, 1, 3, 192)),
                                  jnp.bfloat16)}}
    table = jnp.asarray([4, 2, 7, 0], jnp.int32)
    out = assign_paged({"model": pool}, primed, 1, table)["model"]
    src = primed["model"]
    # token 40 is row 8 of the lane's 2nd block
    np.testing.assert_array_equal(out["cached_latent"][:, 2, 8],
                                  src["cached_latent"][:, 0, 40])
    for name in ("state_delta", "state_conv"):
        np.testing.assert_array_equal(out[name][:, 1], src[name][:, 0])
        assert not np.asarray(out[name][:, 0]).any()
    assert int(out["cache_index"][0, 1]) == 70

    eng = _engine(model, params)
    assert eng._kv_bytes == eng.num_blocks * 32 * 128 * 4
    assert eng._state_bytes == 4 * 3 * (4 * 16 * 16 + 3 * 192) * 4
    assert eng.stats()["state_bytes"] == eng._state_bytes
    eng.generate_all([_prompt(20)], 5)
    from fengshen_tpu.observability import render_prometheus
    text = render_prometheus(eng.metrics.registry)
    assert f"fstpu_serving_state_bytes {eng._state_bytes}\n" in text
    # the first token comes from the last window; four ticks of one
    # lane read 21 + 22 + 23 + 24 cached tokens: the ONE latent layer's
    # count a lane (not times four KDA layers, not times five layers)
    assert "fstpu_serving_kv_tokens_attended_total 90\n" in text


def test_handoff_speculation_and_int8_refuse_by_leaf_name(tiny):
    from fengshen_tpu.serving.handoff import HandoffError, export_lane
    _, model, params, _, _ = tiny
    eng = _engine(model, params)
    eng.submit(_prompt(20), 8, request_id="r")
    eng.step()
    with pytest.raises(HandoffError, match="state_conv.*state_delta"):
        export_lane(eng, "r")
    with pytest.raises(ValueError, match="state_conv"):
        _engine(model, params, spec_mode="prompt_lookup", spec_gamma=2)
    with pytest.raises(ValueError, match="no int8 form"):
        _engine(model, params, kv_dtype="int8").generate_all(
            [_prompt(20)], 2)


# ---- a chip's share of the experts ------------------------------------

def test_two_shares_add_up_to_the_uncut_layer(tiny):
    """The shares `(0, E/2)` and `(E/2, E/2)` of one layer's experts,
    the ONE shared expert counted once (on the first), add up to the
    uncut reference's layer; the reference given a share gives that
    share's part."""
    from fengshen_tpu.ops.moe import RoutedExperts
    cfg, _, params, rcfg, rparams = tiny
    E = cfg.num_experts
    layer = RoutedExperts(
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.moe_intermediate_size, num_experts=E,
        top_k=cfg.num_experts_per_token, scoring="sigmoid", score_bias=True,
        norm_topk_prob=True, routed_scaling_factor=cfg.routed_scaling_factor,
        n_shared_experts=1, dtype=jnp.float32)
    mlp = params["model"]["layers_1"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.hidden_size))
    lp = {k[len("model/layers_1/"):]: v for k, v in rparams.items()
          if k.startswith("model/layers_1/")}
    mm = reference.MATMULS["highest"]
    whole = reference._mlp(rcfg, mm, False, x[0], lp)
    np.testing.assert_allclose(layer.apply({"params": mlp}, x)[0], whole,
                               atol=ATOL)
    parts = []
    for first, shared in ((0, True), (E // 2, False)):
        _, cut = expert_share(cfg, {"mlp": mlp}, first, E // 2)
        share = layer.clone(experts_held=(first, E // 2), shared_here=shared)
        tree = dict(cut["mlp"])
        if not shared:
            tree.pop("shared_experts")
        got = share.apply({"params": tree}, x)[0]
        ref_lp = dict(lp, **{k: v[first:first + E // 2]
                             for k, v in lp.items()
                             if k.startswith("mlp/experts_")})
        ref_part = reference._mlp(
            dict(rcfg, experts_held=[first, E // 2], shared_here=shared),
            mm, False, x[0], ref_lp)
        np.testing.assert_allclose(got, ref_part, atol=ATOL)
        parts.append(got)
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=ATOL)
    assert np.abs(parts[1]).max() > 1e-4     # the other share is not idle


def test_held_counters_count_the_share(tiny):
    """The routing histogram spans the router's 8 outputs over the four
    expert layers; touched and straggler counters are over the 4 held."""
    cfg, _, params, _, _ = tiny
    share_cfg, cut = expert_share(cfg, params, 0, 4)
    eng = _engine(KimiLinearForCausalLM(share_cfg), cut, num_slots=2)
    assert eng._moe_shape == (4, 8) and eng._experts_held == (0, 4)
    eng.generate_all([_prompt(20), _prompt(30, seed=1)], 6)
    from fengshen_tpu.observability import render_prometheus
    stats = {line.split()[0]: float(line.split()[1]) for line in
             render_prometheus(eng.metrics.registry).splitlines()
             if line.startswith("fstpu_moe_")}
    total = stats["fstpu_moe_assignments_total"]
    held = stats["fstpu_moe_assignments_held_total"]
    ticks = stats["fstpu_moe_layer_ticks_total"]
    assert total == ticks / 4 * 2 * 2 * 4     # 2 lanes x top-2 x 4 layers
    assert 0 < held < total
    assert stats["fstpu_moe_experts_touched_total"] <= 4 * ticks
    assert stats["fstpu_moe_max_expert_tokens_total"] <= held


# ---- the published checkpoint's layout (ASSUMED) ----------------------

def test_convert_reads_the_assumed_key_names(tiny):
    """A state dict laid out as `convert.py` assumes the published one
    is (q, k, v and their convolutions apart, `Conv1d` weights `[C, 1,
    K]`, `A_log` `[1, 1, H, 1]`, one module an expert) converts back to
    the parameters it was made from, a share of the experts and a slice
    of the vocabulary included."""
    from fengshen_tpu.models.kimi_linear.convert import torch_to_params
    cfg, _, params, _, _ = tiny
    W = cfg.kda_dim
    sd = {}
    lin = lambda tree: np.asarray(tree["kernel"]).T  # noqa: E731
    for i, kind in enumerate(cfg.layer_types):
        tree, pre = params["model"][f"layers_{i}"], f"model.layers.{i}"
        for n in ("input_layernorm", "post_attention_layernorm"):
            sd[f"{pre}.{n}.weight"] = np.asarray(tree[n]["scale"])
        a, at = tree["self_attn"], f"{pre}.self_attn"
        if kind == "kda":
            for j, x in enumerate("qkv"):
                cols = slice(j * W, (j + 1) * W)
                sd[f"{at}.{x}_proj.weight"] = np.asarray(
                    a["qkv_proj"]["kernel"])[:, cols].T
                sd[f"{at}.{x}_conv1d.weight"] = np.asarray(
                    a["conv1d"])[:, cols].T[:, None, :]
            sd[f"{at}.A_log"] = np.asarray(a["A_log"]).reshape(1, 1, -1, 1)
            sd[f"{at}.dt_bias"] = np.asarray(a["dt_bias"])
            sd[f"{at}.o_norm.weight"] = np.asarray(a["o_norm_scale"])
            for p in ("f_a_proj", "f_b_proj", "b_proj", "g_a_proj",
                      "g_b_proj", "o_proj"):
                sd[f"{at}.{p}.weight"] = lin(a[p])
        else:
            for p in ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj"):
                sd[f"{at}.{p}.weight"] = lin(a[p])
            sd[f"{at}.kv_a_layernorm.weight"] = np.asarray(
                a["kv_a_layernorm"]["scale"])
        mlp = tree["mlp"]
        if i == 0:
            for p in ("gate_proj", "up_proj", "down_proj"):
                sd[f"{pre}.mlp.{p}.weight"] = lin(mlp[p])
            continue
        moe = f"{pre}.block_sparse_moe"
        sd[f"{moe}.gate.weight"] = lin(mlp["router"])
        sd[f"{moe}.gate.e_score_correction_bias"] = np.asarray(
            mlp["e_score_correction_bias"])
        for p in ("gate_proj", "up_proj", "down_proj"):
            sd[f"{moe}.shared_experts.{p}.weight"] = lin(
                mlp["shared_experts"][p])
        for name, w in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
            for e in range(cfg.num_experts):
                sd[f"{moe}.experts.{e}.{w}.weight"] = np.asarray(
                    mlp[f"experts_{name}"][e]).T
    sd["model.embed_tokens.weight"] = np.asarray(
        params["model"]["embed_tokens"]["embedding"])
    sd["model.norm.weight"] = np.asarray(params["model"]["norm"]["scale"])
    sd["lm_head.weight"] = np.asarray(params["lm_head"]["kernel"]).T
    back = torch_to_params(sd, cfg)
    jax.tree_util.tree_map(np.testing.assert_array_equal, back,
                           jax.tree_util.tree_map(np.asarray, params))
    share_cfg, cut = expert_share(cfg, params, 4, 4)
    half = torch_to_params(sd, dataclasses.replace(share_cfg, vocab_size=32))
    np.testing.assert_array_equal(
        half["model"]["layers_2"]["mlp"]["experts_up"],
        cut["model"]["layers_2"]["mlp"]["experts_up"])
    assert half["lm_head"]["kernel"].shape == (32, 32)
    assert half["model"]["embed_tokens"]["embedding"].shape == (32, 32)
