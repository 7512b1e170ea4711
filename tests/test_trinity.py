"""Trinity (window layers beside full layers) through the engine against
its plain reference (`benchmarks/references/trinity.py`), at a tiny size
on the CPU: a full forward, prefill in windows then decode through the
RING past two wraps, a sliding layer against a full one under the
window, the eight expert shares' sum, the allocator's two free lists,
the four reads against dense forms, the seam's decision for the
published expert tables, the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.references import trinity as reference
from fengshen_tpu.models.trinity import TrinityConfig, TrinityForCausalLM
from fengshen_tpu.models.trinity.configuration_trinity import FULL, SLIDING
from fengshen_tpu.ops.window_attention import (banded_prefill_walk,
                                               full_decode_attention,
                                               full_prefill_walk,
                                               ring_decode_attention,
                                               ring_live_table)
from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                         EngineConfig, PromptTooLong)
from fengshen_tpu.serving.paged_cache import (positional_leaves,
                                              ring_leaves)

WINDOW = 8
RING = ["cached_window_key", "cached_window_value"]
_REF_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
             "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rope_theta",
             "sliding_window", "num_dense_layers", "moe_intermediate_size",
             "num_experts", "num_experts_per_tok", "num_shared_experts",
             "route_norm", "route_scale", "rms_norm_eps")


def _build(seed=5, **overrides):
    """(config, model, params, the reference's config and params): the
    same seeded values under the same leaf names on both sides."""
    cfg = TrinityConfig.small_test_config(max_position_embeddings=128,
                                          **overrides)
    model = TrinityForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    key = weights.base_key(seed)
    params = weights.fill_like(key, shapes)
    rcfg = {k: getattr(cfg, k) for k in _REF_KEYS}
    rcfg.update(layer_types=list(cfg.layer_types), param_dtype="float32",
                expert_block=4, shared_here=cfg.shared_here)
    if cfg.experts_held:
        rcfg["experts_held"] = list(cfg.experts_held)
    rshapes = reference.param_shapes(rcfg)
    assert set(weights.flat(params)) == set(rshapes)
    return cfg, model, params, rcfg, weights.fill(key, rshapes)


@pytest.fixture(scope="module")
def tiny():
    return _build()


def _reference_logits(tiny, ids):
    _, _, _, rcfg, rparams = tiny
    pad = np.zeros((128,), np.int32)
    pad[:len(ids)] = ids
    return np.asarray(reference.forward_logits(
        rcfg, "highest", rparams, pad, np.arange(len(ids))))


def _engine(model, params, **kw):
    """Blocks of 4 and a ring of 3: 12 tokens hold the window of 8."""
    base = dict(num_slots=3, buckets=(8,), max_new_tokens=48,
                kv_layout="paged", kv_block_size=4,
                kv_ring_blocks_per_slot=3, max_queue=8)
    base.update(kw)
    return ContinuousBatchingEngine(model, params, EngineConfig(**base))


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, 64, size=(n,)).astype(np.int32)


def _counters(eng) -> dict:
    from fengshen_tpu.observability import render_prometheus
    out = {}
    for line in render_prometheus(eng.metrics.registry).splitlines():
        if line.startswith("fstpu_") and "{" not in line:
            name, _, value = line.partition(" ")
            out[name] = float(value)
    return out


# ---- (a) the model, windows and the ring against the reference ---------

def test_plain_forward_matches_reference(tiny):
    _, model, params, _, _ = tiny
    ids = _prompt(100)
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    np.testing.assert_allclose(got, _reference_logits(tiny, ids),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("layout", ["paged", "slot"])
@pytest.mark.parametrize("n_prompt", [5, 19, 44])
def test_engine_serves_reference_argmax(tiny, layout, n_prompt):
    """Prefill in windows of 8, then 48 ticks: under the window all the
    way into decode (5), across it in the third window (19), six windows
    (44). A ring of 12 tokens wraps at positions 12, 24, ...: every lane
    decodes past two wraps, the longest to position 91. Every served
    token is the reference's best at its position."""
    _, model, params, _, _ = tiny
    eng = _engine(model, params, kv_layout=layout)
    prompt = _prompt(n_prompt)
    out, = eng.generate_all([prompt], 48)
    logits = _reference_logits(tiny, np.concatenate([prompt, out]))[
        n_prompt - 1:-1]
    gaps = logits.max(-1) - logits[np.arange(48), out]
    assert gaps.max() <= 1e-5
    assert eng.stats()["prefills_per_bucket"] == {8: -(-n_prompt // 8)}
    assert eng._positional == RING and eng._ring == RING
    assert eng.ring_blocks == (3 if layout == "paged" else 0)


def test_window_and_tick_logits_match_reference(tiny):
    """The logits themselves, not their argmax: three windows onto a
    contiguous cache, then ticks through a paged ring past a wrap."""
    cfg, model, params, _, _ = tiny
    ids = _prompt(60, 3)
    want = _reference_logits(tiny, ids)
    eng = _engine(model, params, num_slots=1)
    primed = eng._fresh_jit()
    for start in range(0, 24, 8):
        logits, mutated = model.apply(
            {"params": params, "cache": primed}, ids[None, start:start + 8],
            position_ids=start + jnp.arange(8)[None], init_cache=True,
            mutable=["cache"])
        primed = mutated["cache"]
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   want[start:start + 8], atol=2e-5,
                                   rtol=1e-4)
    from fengshen_tpu.serving.paged_cache import assign_paged
    lane = np.arange(1, 1 + eng.max_blocks_per_slot, dtype=np.int32)
    pool = assign_paged(eng._cache, primed, 0, lane,
                        np.asarray([3, 1, 2], np.int32))
    for t in range(24, 60):
        logits, mutated = model.apply(
            {"params": params, "cache": pool}, ids[None, t:t + 1],
            position_ids=jnp.full((1, 1), t), init_cache=True,
            mutable=["cache", "moe_stats"])
        pool = mutated["cache"]
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[t],
                                   atol=2e-5, rtol=1e-4)


def test_a_sliding_layer_equals_a_full_layer_under_the_window():
    """Positions aside (a full layer has none: one pair of a head turns
    with the position whatever theta is), a sliding layer IS a full
    layer while the context is within its window: the same weights with
    a window of 8 and with one no context reaches agree on the first 8
    rows and part beyond them; and the band read with such a window is
    the full read."""
    ids = _prompt(24, 9)
    out = {w: np.asarray(b[1].apply({"params": b[2]}, ids[None]))[0]
           for w, b in ((w, _build(sliding_window=w)) for w in (WINDOW, 128))}
    np.testing.assert_allclose(out[WINDOW][:WINDOW], out[128][:WINDOW],
                               atol=1e-5)
    assert np.abs(out[WINDOW][WINDOW:] - out[128][WINDOW:]).max() > 1e-3
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, 8, 4, 8))
    k = jax.random.normal(keys[1], (1, 32, 2, 8))
    v = jax.random.normal(keys[2], (1, 32, 2, 8))
    np.testing.assert_allclose(
        np.asarray(banded_prefill_walk(q, k, v, jnp.int32(16), window=64,
                                       q_tile=4, key_block=8)),
        np.asarray(full_prefill_walk(q, k, v, jnp.int32(16))), atol=1e-5)


def test_the_eight_shares_and_one_shared_expert_add_up():
    """An expert layer's routed part is the sum of its EIGHT
    expert-parallel shares' (here one expert each of 8), the shared
    expert added by ONE of them: program and reference, share by share."""
    from benchmarks.references.common import MATMULS
    whole = _build()
    cfg, _, _, rcfg, rparams = whole
    pre = "model/layers_1/"
    lp = {p[len(pre):]: w for p, w in rparams.items() if p.startswith(pre)}
    h = jax.random.normal(jax.random.PRNGKey(2), (16, cfg.hidden_size))
    mm = MATMULS["highest"]
    uncut = reference._mlp(rcfg, mm, False, h, lp)
    total = jnp.zeros_like(uncut)
    for share in range(8):
        part = dict(rcfg, experts_held=[share, 1],
                    shared_here=share == 0)
        cut = dict(lp, **{"mlp/experts_" + n: lp["mlp/experts_" + n][
            share:share + 1] for n in ("gate", "up", "down")})
        total = total + reference._mlp(part, mm, False, h, cut)
        # the program's share against the reference's
        from fengshen_tpu.ops.moe import RoutedExperts
        layer = RoutedExperts(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            scoring="sigmoid", score_bias=True,
            norm_topk_prob=cfg.route_norm,
            routed_scaling_factor=cfg.route_scale, n_shared_experts=1,
            experts_held=(share, 1), shared_here=share == 0,
            dtype=jnp.float32)
        tree = {"router": {"kernel": lp["mlp/router/kernel"]},
                "e_score_correction_bias":
                    lp["mlp/e_score_correction_bias"],
                **{"experts_" + n: cut["mlp/experts_" + n]
                   for n in ("gate", "up", "down")}}
        if share == 0:
            tree["shared_experts"] = {
                n: {"kernel": lp[f"mlp/shared_experts/{n}/kernel"]}
                for n in ("gate_proj", "up_proj", "down_proj")}
        got = layer.apply({"params": tree}, h[None])[0]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(reference._mlp(part, mm, False, h,
                                                        cut)),
            atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=1e-5, rtol=1e-4)


# ---- (b) the four reads against dense forms -----------------------------

def _dense(q, k, v, at, window=None):
    """q `[S, H, D]` at positions `at` over k, v `[T, G, D]`."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(q.shape[-1])
    pos = jnp.arange(k.shape[0])[None]
    ok = pos <= at[:, None]
    if window is not None:
        ok &= pos > at[:, None] - window
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hst,thd->shd", p, v)


@pytest.mark.parametrize("start", [0, 8, 40])
def test_window_reads_equal_the_dense_forms(start):
    keys = jax.random.split(jax.random.PRNGKey(start), 3)
    q = jax.random.normal(keys[0], (1, 16, 4, 8))
    k = jax.random.normal(keys[1], (1, 64, 2, 8))
    v = jax.random.normal(keys[2], (1, 64, 2, 8))
    at = start + jnp.arange(16)
    got = banded_prefill_walk(q, k, v, jnp.int32(start), window=12,
                              q_tile=8, key_block=8)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(
        _dense(q[0], k[0], v[0], at, 12)), atol=1e-5)
    got = full_prefill_walk(q, k, v, jnp.int32(start))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(
        _dense(q[0], k[0], v[0], at)), atol=1e-5)


@pytest.mark.parametrize("layered", [False, True])
def test_tick_reads_equal_the_dense_forms(layered):
    """Three lanes at cursors under the window, past it and past a wrap
    of a ring of 4 blocks of 4 (window 10: three live blocks at most
    plus the cursor's); the ring's rows written where the model writes
    them."""
    block, ring, window, lanes = 4, 4, 10, 3
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    hist_k = jax.random.normal(keys[0], (lanes, 40, 2, 8))
    hist_v = jax.random.normal(keys[1], (lanes, 40, 2, 8))
    q = jax.random.normal(keys[2], (lanes, 1, 4, 8))
    t = jnp.asarray([5, 14, 37])
    layer = 1 if layered else None
    shape = ((2,) if layered else ()) + (1 + lanes * ring, block, 2, 8)
    pool_k, pool_v = np.zeros(shape, np.float32), np.zeros(shape, np.float32)
    table = 1 + np.arange(lanes * ring).reshape(lanes, ring)
    for lane in range(lanes):
        for p in range(int(t[lane]) + 1):       # later rows overwrite
            at = (table[lane, (p // block) % ring], p % block)
            at = (layer,) + at if layered else at
            pool_k[at], pool_v[at] = hist_k[lane, p], hist_v[lane, p]
    got = ring_decode_attention(q, jnp.asarray(pool_k), jnp.asarray(pool_v),
                                jnp.asarray(table), t, window=window,
                                layer=layer)
    for lane in range(lanes):
        want = _dense(q[lane], hist_k[lane], hist_v[lane], t[lane:lane + 1],
                      window)
        np.testing.assert_allclose(np.asarray(got[lane]), np.asarray(want),
                                   atol=1e-5)
    blocks, valid = ring_live_table(jnp.asarray(table), t, window=window,
                                    block_size=block)
    assert blocks.shape == (lanes, 4) and valid.sum(-1).tolist() == \
        [[6], [10], [10]]
    # a lane held from position 0 behind a table that counts
    full = 1 + np.arange(lanes * 10).reshape(lanes, 10)
    shape = shape[:-4] + (1 + lanes * 10,) + shape[-3:]
    pool_k, pool_v = np.zeros(shape, np.float32), np.zeros(shape, np.float32)
    for lane in range(lanes):
        for p in range(40):
            at = (full[lane, p // block], p % block)
            at = (layer,) + at if layered else at
            pool_k[at], pool_v[at] = hist_k[lane, p], hist_v[lane, p]
    got = full_decode_attention(q, jnp.asarray(pool_k), jnp.asarray(pool_v),
                                jnp.asarray(full), t, layer=layer)
    for lane in range(lanes):
        want = _dense(q[lane], hist_k[lane], hist_v[lane], t[lane:lane + 1])
        np.testing.assert_allclose(np.asarray(got[lane]), np.asarray(want),
                                   atol=1e-5)


# ---- (c) the pool's two kinds of row ------------------------------------

def test_the_cache_declares_a_ring_beside_plain_rows(tiny):
    _, model, params, _, _ = tiny
    eng = _engine(model, params)
    cache = eng._cache["model"]
    assert ring_leaves(cache["window_rows"]) == RING
    assert ring_leaves(cache["full_rows"]) == []
    assert positional_leaves(eng._cache) == RING
    # 4 window layers behind a table of 3, 1 full layer behind one of 32
    assert cache["window_rows"]["block_table"].shape == (4, 3, 3)
    assert cache["window_rows"]["cached_window_key"].shape == \
        (4, 10, 4, 2, 16)
    assert cache["full_rows"]["block_table"].shape == (1, 3, 32)
    with pytest.raises(ValueError, match="share one block table"):
        ring_leaves({"cache_index": 0, "cached_key": 0,
                     "cached_window_key": 0})


def test_admission_takes_both_kinds_and_defers_on_either(tiny):
    """Two free lists: a request takes `blocks_for(prompt + max_new)` of
    the lane-long kind and `min(that, ring)` of the ring kind, waits
    when EITHER is short, and gives both back."""
    _, model, params, _, _ = tiny
    # the ring list holds one ring and a third: the second long request
    # is deferred on it although the lane-long list has room
    eng = _engine(model, params, kv_ring_num_blocks=5)
    long_a = eng.submit(_prompt(20), 12)        # 8 blocks, ring 3
    short = eng.submit(_prompt(3), 2)           # 2 blocks, ring 2
    eng.step()
    assert len(eng._slot_ring[0]) == 3 and len(eng._slot_blocks[0]) == 8
    long_b = eng.submit(_prompt(20), 12)
    eng.run_until_idle()
    assert all(r.finish_reason == "length" for r in (long_a, short, long_b))
    assert eng.stats()["deferred_admissions"] >= 1
    assert eng._allocator.used_blocks == 0
    assert eng._ring_allocator.used_blocks == 0
    assert eng._ring_allocator.free_blocks == 4
    kv = eng._kv_stats_locked()
    assert kv["ring_blocks_total"] == 4 and kv["ring_blocks_used"] == 0
    # the lane-long list short instead
    eng = _engine(model, params, kv_num_blocks=12)
    a, b = eng.submit(_prompt(20), 12), eng.submit(_prompt(20), 12)
    eng.run_until_idle()
    assert a.finish_reason == b.finish_reason == "length"
    assert eng.stats()["deferred_admissions"] >= 1
    assert eng._ring_allocator.used_blocks == 0


def test_submit_refuses_by_the_full_table(tiny):
    _, model, params, _, _ = tiny
    eng = _engine(model, params, kv_num_blocks=9)
    with pytest.raises(PromptTooLong, match="needs 10 KV blocks"):
        eng.submit(_prompt(20), 20)
    eng.submit(_prompt(20), 12)
    eng.run_until_idle()


def test_a_ring_too_short_for_the_window_is_refused(tiny):
    _, model, params, _, _ = tiny
    with pytest.raises(ValueError, match="must hold the 8 tokens"):
        _engine(model, params, kv_ring_blocks_per_slot=1)
    with pytest.raises(ValueError, match="cannot hold one lane's ring"):
        _engine(model, params, kv_ring_num_blocks=3)
    # the default: window + the largest bucket, in blocks
    assert _engine(model, params,
                   kv_ring_blocks_per_slot=None).ring_blocks == 4


def test_a_reused_lanes_ring_is_the_new_requests(tiny):
    """Two requests one after the other through ONE lane: the second
    reuses the first's ring blocks and is served as if alone."""
    _, model, params, _, _ = tiny
    eng = _engine(model, params, num_slots=1)
    first, second = _prompt(30, 1), _prompt(13, 2)
    a, = eng.generate_all([first], 20)
    b, = eng.generate_all([second], 20)
    for prompt, out in ((first, a), (second, b)):
        logits = _reference_logits(tiny, np.concatenate([prompt, out]))[
            len(prompt) - 1:-1]
        assert (logits.max(-1) - logits[np.arange(20), out]).max() <= 1e-5


def test_handoff_refuses_by_leaf_name(tiny):
    from fengshen_tpu.serving.handoff import HandoffError, export_lane
    _, model, params, _, _ = tiny
    eng = _engine(model, params)
    eng.submit(_prompt(20), 8, request_id="r")
    eng.step()
    with pytest.raises(HandoffError, match="cached_window_key"):
        export_lane(eng, "r")


@pytest.mark.parametrize("mode", ["prompt_lookup", "self_draft"])
def test_speculative_modes_refuse_by_leaf_name(tiny, mode):
    _, model, params, _, _ = tiny
    with pytest.raises(ValueError, match="cached_window_key.*ring"):
        _engine(model, params, spec_mode=mode, spec_gamma=2)


def test_int8_pool_is_refused_at_construction(tiny):
    _, model, params, _, _ = tiny
    with pytest.raises(ValueError, match="int8.*cached_window_key"):
        _engine(model, params, kv_dtype="int8")


# ---- (d) the counters ---------------------------------------------------

def test_window_counters_follow_the_cursors(tiny):
    _, model, params, _, _ = tiny
    eng = _engine(model, params, num_slots=1)
    eng.generate_all([_prompt(5)], 7)
    c = _counters(eng)
    # six ticks at contexts 6..11: a window layer reads up to 8 keys, a
    # full layer all of them
    assert c["fstpu_serving_kv_window_tokens_attended_total"] == \
        6 + 7 + 8 + 8 + 8 + 8
    assert c["fstpu_serving_kv_tokens_attended_total"] == \
        6 + 7 + 8 + 9 + 10 + 11
    # 5 + 7 tokens: 3 blocks of 4 of each kind, six ticks
    assert c["fstpu_serving_kv_blocks_held_total"] == 18
    assert c["fstpu_serving_kv_ring_blocks_held_total"] == 18
    # the live-block share's table is the lane-long one, not the ring
    assert c["fstpu_serving_kv_blocks_tabled_total"] == 6 * 32
    assert model.window_tokens(np.asarray([8, 9])).tolist() == [8, 8]
    eng.stats()
    assert _counters(eng)["fstpu_kv_ring_blocks_total"] == 3


def test_moe_counters_count_the_held_experts():
    cfg, model, params, _, _ = _build(experts_held=(0, 4))
    eng = _engine(model, params, num_slots=2)
    eng.generate_all([_prompt(20), _prompt(9)], 5)
    c = _counters(eng)
    assert 0 < c["fstpu_moe_assignments_held_total"] < \
        c["fstpu_moe_assignments_total"]
    # four expert layers of the five run a tick
    assert c["fstpu_moe_layer_ticks_total"] == \
        4 * c["fstpu_serving_decode_ticks_total"]


# ---- (e) the seam's decision for the published expert tables ------------

@pytest.mark.parametrize("rows,reason", [
    (8192, "3 slots of gate and up (113246208 B) outgrow VMEM"),
    (64, "64 rows % 128 != 0")])
def test_the_seam_sends_the_published_tables_to_ragged_dot(rows, reason):
    """A 2,048-token window's 8,192 assignments and a 16-lane tick's 64
    against this chip's `[32, 3072, 3072]` tables: both stay on
    `ragged_dot`, each for the reason the seam gives."""
    from fengshen_tpu.ops.pallas import grouped_matmul
    got = grouped_matmul._ineligible_reason(
        jax.ShapeDtypeStruct((rows, 3072), jnp.bfloat16),
        jax.ShapeDtypeStruct((32, 3072, 3072), jnp.bfloat16))
    assert got == reason


# ---- (f) the family behind models/auto and the converter ----------------

def test_auto_builds_the_family_from_a_published_style_config(tmp_path):
    from fengshen_tpu.models.auto import AutoConfig, AutoModel
    TrinityConfig.small_test_config().save_pretrained(str(tmp_path))
    cfg = AutoConfig.from_pretrained(str(tmp_path))
    assert isinstance(cfg, TrinityConfig) and cfg.sliding_window == WINDOW
    assert isinstance(AutoModel.from_config(cfg, "causal_lm"),
                      TrinityForCausalLM)


def test_convert_reads_the_assumed_key_layout(tiny):
    from fengshen_tpu.models.trinity.convert import torch_to_params
    cfg, _, params, _, _ = tiny
    flat = weights.flat(params)
    state = {}
    for path, leaf in flat.items():
        leaf = np.asarray(leaf)
        parts = path.split("/")
        if parts[0] == "lm_head":
            state["lm_head.weight"] = leaf.T
            continue
        name = ".".join(parts[1:]).replace("layers_", "layers.")
        if parts[-1].startswith("experts_"):
            kind = parts[-1][len("experts_"):] + "_proj"
            for e in range(leaf.shape[0]):
                state["model." + name.rsplit(".", 1)[0] +
                      f".experts.{e}.{kind}.weight"] = leaf[e].T
        elif "router" in parts:
            state["model." + name.replace("router.kernel",
                                          "router.gate.weight")] = leaf.T
        elif parts[-1] == "e_score_correction_bias":
            state["model." + name.replace("e_score_correction_bias",
                                          "expert_bias")] = leaf
        elif parts[-1] == "kernel":
            state["model." + name[:-len("kernel")] + "weight"] = leaf.T
        elif parts[-1] == "embedding":
            state["model.embed_tokens.weight"] = leaf
        else:
            state["model." + name[:-len("scale")] + "weight"] = leaf
    got = weights.flat(torch_to_params(state, cfg))
    assert set(got) == set(flat)
    for path in flat:
        np.testing.assert_array_equal(got[path], flat[path])


def test_the_config_reads_the_published_pattern_and_refuses_the_rest():
    cfg = TrinityConfig()
    assert cfg.layer_types.count(SLIDING) == 45 and \
        cfg.layer_types.count(FULL) == 15 and cfg.layer_types[3] == FULL
    assert cfg.layers_of(FULL)[:2] == (3, 7)
    with pytest.raises(ValueError, match="rope_scaling"):
        TrinityConfig.small_test_config(rope_scaling={"rope_type": "yarn"})
    with pytest.raises(ValueError, match="one group"):
        TrinityConfig.small_test_config(n_group=2)
    with pytest.raises(ValueError, match="names 5 layers"):
        TrinityConfig.small_test_config(layer_types=(SLIDING,))
