"""Qwen3-Next through the engine against its plain reference
(`benchmarks/references/qwen3_next.py`), at a tiny size on the CPU
(2 periods, 8 experts top-2, 2 / 4 linear heads of 16, vocabulary 64):
windowed prefill and decode through the pool, the delta rule's two
forms, both recurrent states of a lane, a chip's share of the experts,
and the full layer's pieces against hand-written values."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.references import qwen3_next as reference
from fengshen_tpu.models.qwen3_next import (Qwen3NextConfig,
                                            Qwen3NextForCausalLM,
                                            expert_share)
from fengshen_tpu.ops.gated_attention import (folded_decode_walk,
                                              folded_prefill_walk)
from fengshen_tpu.ops.gated_delta import (gated_delta_decode,
                                          gated_delta_prefill, l2norm,
                                          short_conv_decode,
                                          short_conv_prefill)
from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                         EngineConfig)
from fengshen_tpu.serving.paged_cache import (assign_paged, init_pool_cache,
                                              positional_leaves)

REFERENCE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta",
    "full_attention_interval", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
    "linear_conv_kernel_dim", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_experts", "num_experts_per_tok",
    "norm_topk_prob", "rms_norm_eps")

#: float32 on both sides, the same mathematics in another order of
#: operations (chunks against a token-by-token scan, a sorted grouped
#: matmul against every expert weighed densely): rounding only
ATOL = 5e-6


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
    cfg = Qwen3NextConfig.small_test_config()
    model = Qwen3NextForCausalLM(cfg)
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

def test_layer_types_follow_the_interval():
    cfg = Qwen3NextConfig.small_test_config()
    assert cfg.layer_types == ("linear_attention",) * 3 + (
        "full_attention",) + ("linear_attention",) * 3 + ("full_attention",)
    assert reference.layer_types(_reference_config(cfg)) == \
        list(cfg.layer_types)


def test_plain_forward_matches_reference(tiny):
    _, model, params, _, _ = tiny
    ids = _prompt(100)
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    np.testing.assert_allclose(got, _reference_logits(tiny, ids), atol=ATOL)


@pytest.mark.parametrize("layout", ["paged", "slot"])
@pytest.mark.parametrize("n_prompt", [12, 37, 80])
def test_engine_serves_reference_argmax(tiny, layout, n_prompt):
    """One window (12), three with the last partial (37), five whole
    (80); then 24 ticks through the pool. Every served token is the
    reference's best at its position."""
    _, model, params, _, _ = tiny
    eng = _engine(model, params, kv_layout=layout)
    prompt = _prompt(n_prompt)
    out, = eng.generate_all([prompt], 24)
    logits = _reference_logits(tiny, np.concatenate([prompt, out]))[
        n_prompt - 1:-1]
    gaps = logits.max(-1) - logits[np.arange(24), out]
    assert gaps.max() <= 1e-5
    assert eng.stats()["prefills_per_bucket"] == {16: -(-n_prompt // 16)}


def test_window_and_tick_logits_match_reference_and_both_states(tiny):
    """The logits themselves, on a contiguous cache: windows of 16 (the
    last holds 11 real tokens, padded on the right), then one token at a
    time; and BOTH states after the windows are those of the whole
    prompt in one window."""
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
    assert int(cache["model"]["cache_index"][0]) == n_prompt
    for t in range(n_prompt, 100):
        logits, cache = call(cache, ids[None, t:t + 1], t, 1)
        np.testing.assert_allclose(logits[0], want[t], atol=ATOL)


# ---- the delta rule's two forms, the convolution's ---------------------

def _delta_case(seq, seed=0, batch=2, heads=3, dim=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = l2norm(jax.random.normal(ks[0], (batch, seq, heads, dim)))
    k = l2norm(jax.random.normal(ks[1], (batch, seq, heads, dim)))
    v = jax.random.normal(ks[2], (batch, seq, heads, dim))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (batch, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    state = jax.random.normal(ks[5], (batch, heads, dim, dim))
    return q, k, v, g, beta, state


def _recurrence(q, k, v, g, beta, state, mask=None):
    outs = []
    for t in range(q.shape[1]):
        o, state = gated_delta_decode(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state,
            None if mask is None else mask[:, t])
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("chunk,seq", [(1, 12), (16, 32), (64, 64),
                                       (16, 45), (64, 70)])
def test_delta_chunks_equal_recurrence(chunk, seq):
    case = _delta_case(seq)
    want, want_state = _recurrence(*case)
    got, state = gated_delta_prefill(*case, chunk=chunk)
    np.testing.assert_allclose(got, want, atol=ATOL)
    np.testing.assert_allclose(state, want_state, atol=ATOL)


@pytest.mark.parametrize("side", ["right", "left"])
def test_delta_padding_enters_no_state(side):
    q, k, v, g, beta, state = _delta_case(20, seed=1)
    real = slice(0, 13) if side == "right" else slice(7, 20)
    mask = jnp.zeros((2, 20), bool).at[:, real].set(True)
    got, got_state = gated_delta_prefill(q, k, v, g, beta, state, mask,
                                         chunk=8)
    want, want_state = gated_delta_prefill(
        q[:, real], k[:, real], v[:, real], g[:, real], beta[:, real],
        state, chunk=8)
    np.testing.assert_allclose(got[:, real], want, atol=ATOL)
    np.testing.assert_allclose(got_state, want_state, atol=ATOL)


def test_delta_rule_by_hand():
    """One head of two dims, two tokens: the state subtracts what it
    already predicts for a key before it writes."""
    k = jnp.asarray([[[1.0, 0.0]], [[1.0, 0.0]]])[None]        # [1,2,1,2]
    v = jnp.asarray([[[2.0, 4.0]], [[6.0, 0.0]]])[None]
    q = k
    g = jnp.log(jnp.asarray([[[0.5], [0.5]]]))                  # [1,2,1]
    beta = jnp.asarray([[[1.0], [0.5]]])
    out, state = gated_delta_prefill(q, k, v, g, beta,
                                     jnp.zeros((1, 1, 2, 2)), chunk=2)
    # t0: S = k^T v = [[2, 4], [0, 0]]; t1: S' = [[1, 2], [0, 0]],
    # d = 0.5 ([6, 0] - [1, 2]) = [2.5, -1], S = [[3.5, 1], [0, 0]]
    np.testing.assert_allclose(out[0, :, 0], [[2.0, 4.0], [3.5, 1.0]],
                               atol=1e-6)
    np.testing.assert_allclose(state[0, 0], [[3.5, 1.0], [0.0, 0.0]],
                               atol=1e-6)


def test_short_conv_windows_and_ticks_equal_one_pass():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(2, 23, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    zero = jnp.zeros((2, 3, 8))
    want, _ = short_conv_prefill(u, w, zero)
    # by hand at t = 5: sum_j c_j u_{t - 3 + j}, then SiLU
    np.testing.assert_allclose(
        want[:, 5], jax.nn.silu(sum(w[j] * u[:, 2 + j] for j in range(4))),
        atol=1e-6)
    np.testing.assert_allclose(want[:, 0], jax.nn.silu(w[3] * u[:, 0]),
                               atol=1e-6)
    # a window of 10, a window of 8 of which 2 are real (lane 1: 6),
    # then ticks
    y1, s = short_conv_prefill(u[:, :10], w, zero, jnp.asarray([10, 10]))
    pad = jnp.concatenate([u[:, 10:16], jnp.full((2, 2, 8), 9.0)], axis=1)
    y2, s = short_conv_prefill(pad, w, s, jnp.asarray([2, 6]))
    np.testing.assert_allclose(y1, want[:, :10], atol=1e-6)
    np.testing.assert_allclose(y2[:, :2], want[:, 10:12], atol=1e-6)
    np.testing.assert_allclose(s[0], u[0, 9:12], atol=0)
    np.testing.assert_allclose(s[1], u[1, 13:16], atol=0)
    # a window shorter than the state keeps what the state still holds
    _, s3 = short_conv_prefill(u[:, 10:12], w, u[:, 7:10],
                               jnp.asarray([1, 2]))
    np.testing.assert_allclose(s3[0], u[0, 8:11], atol=0)
    state = s[:1]
    for t in range(12, 16):
        y, state = short_conv_decode(u[:1, t], w, state)
        np.testing.assert_allclose(y, want[:1, t], atol=1e-6)
    kept, same = short_conv_decode(u[:, 0], w, s, jnp.asarray([False, True]))
    np.testing.assert_array_equal(same[0], s[0])
    assert not np.array_equal(same[1], s[1])


# ---- both states in the pool ------------------------------------------

def _lane_states(eng, slot):
    tree = eng._cache["model"]
    return (np.asarray(tree["state_delta"][:, slot]),
            np.asarray(tree["state_conv"][:, slot]))


def test_dead_lane_keeps_both_states_bit_for_bit(tiny):
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


def test_pool_holds_rows_and_two_states_of_another_shape_and_dtype(tiny):
    cfg, model, params, _, _ = tiny
    bf16 = Qwen3NextForCausalLM(dataclasses.replace(cfg, dtype="bfloat16"))
    pool = init_pool_cache(bf16, 3, layout="paged", num_blocks=9,
                           block_size=32, max_blocks_per_slot=4)["model"]
    # a token's two KV heads of 16 are one row of 32
    assert pool["cached_key"].shape == (2, 9, 32, 1, 32)
    assert pool["state_delta"].shape == (6, 3, 4, 16, 16)
    assert pool["state_delta"].dtype == jnp.float32
    assert pool["state_conv"].shape == (6, 3, 3, 2 * 32 + 64)
    assert pool["state_conv"].dtype == jnp.bfloat16
    assert positional_leaves({"model": pool}) == ["state_conv",
                                                  "state_delta"]
    rng = np.random.default_rng(0)
    primed = {"model": {
        "cached_key": jnp.asarray(rng.normal(size=(2, 1, 128, 1, 32)),
                                  jnp.bfloat16),
        "cached_value": jnp.zeros((2, 1, 128, 1, 32), jnp.bfloat16),
        "cache_index": jnp.full((2,), 70, jnp.int32),
        "state_delta": jnp.asarray(rng.normal(size=(6, 1, 4, 16, 16)),
                                   jnp.float32),
        "state_conv": jnp.asarray(rng.normal(size=(6, 1, 3, 128)),
                                  jnp.bfloat16)}}
    table = jnp.asarray([4, 2, 7, 0], jnp.int32)
    out = assign_paged({"model": pool}, primed, 1, table)["model"]
    src = primed["model"]
    # token 40 is row 8 of the lane's 2nd block, in both full layers
    np.testing.assert_array_equal(out["cached_key"][:, 2, 8],
                                  src["cached_key"][:, 0, 40])
    for name in ("state_delta", "state_conv"):
        np.testing.assert_array_equal(out[name][:, 1], src[name][:, 0])
        assert not np.asarray(out[name][:, 0]).any()
    assert int(out["cache_index"][0, 1]) == 70

    eng = _engine(model, params)
    assert eng._kv_bytes == eng.num_blocks * 2 * 2 * 32 * 32 * 4
    assert eng._state_bytes == 6 * 3 * (4 * 16 * 16 + 3 * 128) * 4
    assert eng.stats()["state_bytes"] == eng._state_bytes
    from fengshen_tpu.observability import render_prometheus
    assert f"fstpu_serving_state_bytes {eng._state_bytes}\n" in \
        render_prometheus(eng.metrics.registry)


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
    the gated shared expert counted once (on the first), add up to the
    uncut reference's layer; the reference given a share gives that
    share's part."""
    from fengshen_tpu.ops.moe import RoutedExperts
    cfg, _, params, rcfg, rparams = tiny
    E = cfg.num_experts
    layer = RoutedExperts(
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.moe_intermediate_size, num_experts=E,
        top_k=cfg.num_experts_per_tok, norm_topk_prob=True,
        n_shared_experts=1, shared_gate=True, dtype=jnp.float32)
    mlp = params["model"]["layers_0"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 24, cfg.hidden_size))
    lp = {k[len("model/layers_0/"):]: v for k, v in rparams.items()
          if k.startswith("model/layers_0/")}
    mm = reference.MATMULS["highest"]
    whole = reference.routed(rcfg, mm, x[0], lp)
    np.testing.assert_allclose(layer.apply({"params": mlp}, x)[0], whole,
                               atol=ATOL)
    parts = []
    for first, shared in ((0, True), (E // 2, False)):
        _, cut = expert_share(cfg, {"mlp": mlp}, first, E // 2)
        share = layer.clone(experts_held=(first, E // 2), shared_here=shared)
        got = share.apply({"params": cut["mlp"]}, x)[0]
        ref_lp = dict(lp, **{k: v[first:first + E // 2]
                             for k, v in lp.items()
                             if k.startswith("mlp/experts_")})
        ref_part = reference.routed(
            dict(rcfg, experts_held=[first, E // 2]), mm, x[0], ref_lp,
            shared=shared)
        np.testing.assert_allclose(got, ref_part, atol=ATOL)
        parts.append(got)
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=ATOL)
    assert np.abs(parts[1]).max() > 1e-4     # the other share is not idle


def test_held_counters_count_the_share(tiny):
    """The routing histogram spans the router's 8 outputs; touched and
    straggler counters are over the 4 held, and the held assignments
    beside all of them."""
    cfg, _, params, _, _ = tiny
    share_cfg, cut = expert_share(cfg, params, 0, 4)
    eng = _engine(Qwen3NextForCausalLM(share_cfg), cut, num_slots=2)
    assert eng._moe_shape == (8, 8) and eng._experts_held == (0, 4)
    eng.generate_all([_prompt(20), _prompt(30, seed=1)], 6)
    from fengshen_tpu.observability import render_prometheus
    stats = {line.split()[0]: float(line.split()[1]) for line in
             render_prometheus(eng.metrics.registry).splitlines()
             if line.startswith("fstpu_moe_")}
    total = stats["fstpu_moe_assignments_total"]
    held = stats["fstpu_moe_assignments_held_total"]
    ticks = stats["fstpu_moe_layer_ticks_total"]
    assert total == ticks / 8 * 2 * 2 * 8     # 2 lanes x top-2 x 8 layers
    assert 0 < held < total
    assert stats["fstpu_moe_experts_touched_total"] <= 4 * ticks
    assert stats["fstpu_moe_max_expert_tokens_total"] <= held


# ---- the full layer's pieces, by hand ---------------------------------

def test_partial_rotary_turns_the_first_quarter_only():
    from fengshen_tpu.ops.rotary import apply_rotary_pos_emb
    x = jnp.arange(2 * 16, dtype=jnp.float32).reshape(1, 2, 1, 16) + 1.0
    got, _ = apply_rotary_pos_emb(x, x, jnp.asarray([[0, 3]]),
                                  rotary_dim=4, base=1e7)
    want = reference.rope_partial(
        jnp.zeros((4, 1, 16)).at[0].set(x[0, 0]).at[3].set(x[0, 1]), 1e7, 4)
    np.testing.assert_allclose(got[0, 0], want[0], atol=1e-6)
    np.testing.assert_allclose(got[0, 1], want[3], atol=1e-6)
    # position 3, dims (0, 2) and (1, 3) are the rotated pairs
    a, b, c, d = np.asarray(x[0, 1, 0, :4])
    f = 1.0 / 1e7 ** 0.5
    np.testing.assert_allclose(
        got[0, 1, 0, :4],
        [a * np.cos(3) - c * np.sin(3), b * np.cos(3 * f) - d * np.sin(3 * f),
         c * np.cos(3) + a * np.sin(3), d * np.cos(3 * f) + b * np.sin(3 * f)],
        rtol=1e-6)
    np.testing.assert_array_equal(got[0, 1, 0, 4:], x[0, 1, 0, 4:])
    np.testing.assert_array_equal(got[0, 0], x[0, 0])       # position 0


def test_q_proj_splits_each_head_into_query_and_gate(tiny):
    """`q_proj`'s columns are `[head 0: query | gate], [head 1: ...]`: a
    kernel that writes 1 into head 1's gate columns moves head 1's
    output gate only."""
    from fengshen_tpu.models.qwen3_next.modeling_qwen3_next import (
        GatedAttention)
    cfg, _, params, _, _ = tiny
    D, H = cfg.head_dim, cfg.num_attention_heads
    attn = jax.tree_util.tree_map(
        jnp.asarray, params["model"]["layers_3"]["self_attn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, cfg.hidden_size))
    pos = jnp.arange(6)[None]

    def heads(tree):
        # o_proj = identity-like read-out of the gated heads
        tree = dict(tree, o_proj={"kernel": jnp.eye(
            H * D, cfg.hidden_size)})
        out, _ = GatedAttention(cfg).apply({"params": tree}, x, pos, None, 0)
        return np.asarray(out)[0, :, :cfg.hidden_size].reshape(6, -1, D)

    base = heads(attn)
    kernel = attn["q_proj"]["kernel"]
    gate_cols = slice(1 * 2 * D + D, 2 * 2 * D)              # head 1's gate
    opened = heads(dict(attn, q_proj={
        "kernel": kernel.at[:, gate_cols].set(0.0)}))        # sigmoid(0)
    seen = cfg.hidden_size // D                              # heads read out
    for h in range(seen):
        if h == 1:
            assert not np.allclose(opened[:, h], base[:, h])
        else:
            np.testing.assert_allclose(opened[:, h], base[:, h], atol=1e-6)


def test_folded_walks_equal_dense_attention():
    """Both reads of rows that fold 2 KV heads against plain grouped
    softmax attention: a window at an offset onto a cache, and one
    query a lane through a block table, lanes of different lengths."""
    rng = np.random.default_rng(0)
    B, T, H, G, D = 2, 96, 4, 2, 16
    k = jnp.asarray(rng.normal(size=(B, T, G, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, G, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, 16, H, D)), jnp.float32)

    def dense(q, at):
        kk, vv = (jnp.repeat(x, H // G, axis=2) for x in (k, v))
        s = jnp.einsum("bshd,bthd->bhst", q, kk) * D ** -0.5
        ok = jnp.arange(T)[None, None, None] <= at[:, None, :, None]
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhst,bthd->bshd", p, vv)

    rows = lambda x: x.reshape(B, T, G * D)  # noqa: E731
    got = folded_prefill_walk(q, rows(k), rows(v), jnp.int32(40),
                              scale=D ** -0.5, key_block=32)
    at = jnp.broadcast_to(40 + jnp.arange(16), (B, 16))
    np.testing.assert_allclose(got, dense(q, at), atol=1e-5)
    # paged: lane b's blocks are scattered in a pool of 8-token blocks
    order = rng.permutation(B * T // 8) + 1
    pool_k = jnp.zeros((B * T // 8 + 1, 8, 1, G * D)).at[order].set(
        rows(k).reshape(-1, 8, 1, G * D))
    pool_v = jnp.zeros_like(pool_k).at[order].set(
        rows(v).reshape(-1, 8, 1, G * D))
    table = jnp.asarray(order.reshape(B, T // 8), jnp.int32)
    t = jnp.asarray([13, 90])
    got = folded_decode_walk(q[:, :1], pool_k, pool_v, table, t,
                             scale=D ** -0.5, chunk_blocks=5)
    np.testing.assert_allclose(got, dense(q[:, :1], t[:, None]), atol=1e-5)


# ---- the published checkpoint's layout (ASSUMED) ----------------------

def test_convert_undoes_the_per_group_layout(tiny):
    """A state dict laid out as `convert.py` assumes the published one
    is (projections per key-head group, conv `[C, 1, K]`, one module an
    expert) converts back to the parameters it was made from, a share
    of the experts and a slice of the vocabulary included."""
    from fengshen_tpu.models.qwen3_next.convert import torch_to_params
    cfg, _, params, _, _ = tiny
    Hk, rep, Dk, Dv = 2, 2, 16, 16
    sd = {}

    def group(kernel, widths):
        edges = np.cumsum((0,) + tuple(w * Hk for w in widths))
        parts = [np.asarray(kernel)[:, a:b].reshape(kernel.shape[0], Hk, -1)
                 for a, b in zip(edges[:-1], edges[1:])]
        return np.concatenate(parts, axis=2).reshape(kernel.shape[0], -1).T

    for i, kind in enumerate(cfg.layer_types):
        tree, pre = params["model"][f"layers_{i}"], f"model.layers.{i}"
        for n in ("input_layernorm", "post_attention_layernorm"):
            sd[f"{pre}.{n}.weight"] = np.asarray(tree[n]["weight"])
        mlp = tree["mlp"]
        sd[f"{pre}.mlp.gate.weight"] = np.asarray(mlp["router"]["kernel"]).T
        sd[f"{pre}.mlp.shared_expert_gate.weight"] = np.asarray(
            mlp["shared_expert_gate"]["kernel"]).T
        for p in ("gate", "up", "down"):
            sd[f"{pre}.mlp.shared_expert.{p}_proj.weight"] = np.asarray(
                mlp["shared_experts"][f"{p}_proj"]["kernel"]).T
            for e in range(cfg.num_experts):
                sd[f"{pre}.mlp.experts.{e}.{p}_proj.weight"] = np.asarray(
                    mlp[f"experts_{p}"][e]).T
        if kind == "full_attention":
            a = tree["self_attn"]
            for p in ("q_proj", "k_proj", "v_proj", "o_proj"):
                sd[f"{pre}.self_attn.{p}.weight"] = np.asarray(
                    a[p]["kernel"]).T
            for n in ("q_norm", "k_norm"):
                sd[f"{pre}.self_attn.{n}.weight"] = np.asarray(a[n]["weight"])
        else:
            a, la = tree["linear_attn"], f"{pre}.linear_attn"
            sd[f"{la}.in_proj_qkvz.weight"] = group(
                a["in_proj_qkvz"]["kernel"], (Dk, Dk, rep * Dv, rep * Dv))
            sd[f"{la}.in_proj_ba.weight"] = group(
                a["in_proj_ba"]["kernel"], (rep, rep))
            sd[f"{la}.conv1d.weight"] = np.asarray(a["conv1d"]).T[:, None, :]
            sd[f"{la}.A_log"] = np.asarray(a["A_log"])
            sd[f"{la}.dt_bias"] = np.asarray(a["dt_bias"])
            sd[f"{la}.norm.weight"] = np.asarray(a["norm_scale"])
            sd[f"{la}.out_proj.weight"] = np.asarray(a["out_proj"]["kernel"]).T
    sd["model.embed_tokens.weight"] = np.asarray(
        params["model"]["embed_tokens"]["embedding"])
    sd["model.norm.weight"] = np.asarray(params["model"]["norm"]["weight"])
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
