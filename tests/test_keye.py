"""Keye-VL-2.0's language model through the engine against its plain
reference (`benchmarks/references/keye.py`), at a tiny size on the CPU:
windowed prefill and decode through the pool, the chosen set against
the reference's, the selection on hand-worked cases, every lowering of
the chosen-row read against the `jax.numpy` form, the indexer's key
rows in the pool, the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.references import keye as reference
from fengshen_tpu.models.keye import KeyeConfig, KeyeForCausalLM
from fengshen_tpu.ops.pallas.decode_attention import (
    xla_indexed_decode_attention)
from fengshen_tpu.ops.sparse_attention import (index_extents, index_scores,
                                               indexed_prefill_attention,
                                               masked_attention_walk,
                                               topk_token_mask, topk_tokens)
from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                         EngineConfig)
from fengshen_tpu.serving.paged_cache import (INDEX_PREFIX, assign_paged,
                                              init_pool_cache,
                                              positional_leaves)

TOPK = 8


@pytest.fixture(scope="module")
def tiny():
    """(config, model, params, the reference's config and params): the
    same seeded values under the same leaf names on both sides."""
    cfg = KeyeConfig.small_test_config(max_position_embeddings=128)
    model = KeyeForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    key = weights.base_key(5)
    params = weights.fill_like(key, shapes)
    rcfg = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rope_theta", "moe_intermediate_size", "num_experts",
        "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")}
    rcfg.update(indexer_num_heads=cfg.index_heads,
                indexer_head_dim=cfg.index_head_dim, topk=cfg.index_topk,
                param_dtype="float32", expert_block=8)
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
    base = dict(num_slots=3, buckets=(16, 32), max_new_tokens=30,
                kv_layout="paged", kv_block_size=32, max_queue=8)
    base.update(kw)
    return ContinuousBatchingEngine(model, params, EngineConfig(**base))


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, 64, size=(n,)).astype(np.int32)


# ---- (a) windows then decode through the pool = the reference ----------

def test_plain_forward_matches_reference(tiny):
    _, model, params, _, _ = tiny
    ids = _prompt(100)
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    np.testing.assert_allclose(got, _reference_logits(tiny, ids),
                               atol=2e-6)


@pytest.mark.parametrize("layout", ["paged", "slot"])
@pytest.mark.parametrize("n_prompt", [6, 20, 75])
def test_engine_serves_reference_argmax(tiny, layout, n_prompt):
    """Within `topk` all the way into decode (6: the ticks cross it at
    9 tokens), one window past `topk` (20), three with the last partial
    (75). Every served token is the reference's best at its position."""
    _, model, params, _, _ = tiny
    eng = _engine(model, params, kv_layout=layout)
    prompt = _prompt(n_prompt)
    out, = eng.generate_all([prompt], 30)
    logits = _reference_logits(tiny, np.concatenate([prompt, out]))[
        n_prompt - 1:-1]
    gaps = logits.max(-1) - logits[np.arange(30), out]
    assert gaps.max() <= 1e-5
    assert eng.stats()["prefills_per_bucket"] == {
        16 if n_prompt <= 16 else 32: -(-n_prompt // 32)}
    assert eng._positional == ["cached_index_key"]


def test_window_and_tick_logits_match_reference(tiny):
    """The logits themselves, on a contiguous cache: windows of 16 (the
    last holds 11 real tokens; the second crosses `topk` 8 and a window
    edge), then one token at a time."""
    from fengshen_tpu.serving.cache import abstract_init
    from fengshen_tpu.utils.generate import _rollback_cache
    cfg, model, params, _, _ = tiny
    ids = _prompt(120)
    want = _reference_logits(tiny, ids)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        abstract_init(model, 1)["cache"])
    n_prompt, width = 91, 16

    @jax.jit
    def call(cache, chunk, start, n_valid):
        logits, mut = model.apply(
            {"params": params, "cache": cache}, chunk,
            position_ids=start + jnp.arange(chunk.shape[1])[None],
            init_cache=True, mutable=["cache"])
        return logits[0], _rollback_cache(mut["cache"],
                                          chunk.shape[1] - n_valid)

    for start in range(0, n_prompt, width):
        n_valid = min(width, n_prompt - start)
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :n_valid] = ids[start:start + n_valid]
        logits, cache = call(cache, chunk, start, n_valid)
        np.testing.assert_allclose(logits[:n_valid],
                                   want[start:start + n_valid], atol=2e-6)
    for t in range(n_prompt, 120):
        logits, cache = call(cache, ids[None, t:t + 1], t, 1)
        np.testing.assert_allclose(logits[0], want[t], atol=2e-6)


# ---- (b) the selection ------------------------------------------------

def _case(seed, batch=2, seq=24, extent=48, heads=4, groups=2, dim=16,
          index_heads=4, index_dim=8):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return dict(q=f(batch, seq, heads, dim), k=f(batch, extent, groups, dim),
                v=f(batch, extent, groups, dim),
                qi=f(batch, seq, index_heads, index_dim),
                w=f(batch, seq, index_heads), ki=f(batch, extent, index_dim))


def _dense_indexed(c, t0, topk, scale):
    """The `jax.numpy` form every lowering is held to: all scores, a
    sort a query, a dense mask, a dense softmax."""
    q, k, v = c["q"], c["k"], c["v"]
    batch, seq, heads, dim = q.shape
    extent, groups = k.shape[1], k.shape[2]
    scores = np.asarray(index_scores(c["qi"], c["w"], c["ki"], scale))
    t = t0 + np.arange(seq)
    allowed = np.zeros((batch, seq, extent), bool)
    for b in range(batch):
        for s in range(seq):
            order = sorted(range(t[s] + 1),
                           key=lambda i: (-scores[b, s, i], i))
            allowed[b, s, order[:topk]] = True
    kk = jnp.repeat(k, heads // groups, axis=2)
    vv = jnp.repeat(v, heads // groups, axis=2)
    sc = jnp.einsum("bshd,bthd->bhst", q, kk) * dim ** -0.5
    sc = jnp.where(jnp.asarray(allowed)[:, None], sc, -jnp.inf)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), vv), \
        allowed


@pytest.mark.parametrize("topk", [1, 5, 8, 64])
@pytest.mark.parametrize("ties", [False, True])
def test_topk_token_mask_takes_the_largest_and_of_equals_the_lowest(
        topk, ties):
    rng = np.random.default_rng([topk, ties])
    x = rng.normal(size=(3, 7, 50))
    if ties:
        x = np.round(x * 2) / 2 + 0.0       # a handful of values
    x = jnp.asarray(x, jnp.float32)
    last = rng.integers(0, 50, size=(3, 7, 1))
    valid = jnp.arange(50)[None, None, :] <= jnp.asarray(last)
    got = np.asarray(topk_token_mask(x, valid, topk))
    masked = np.where(np.asarray(valid), np.asarray(x), -np.inf)
    for b in range(3):
        for s in range(7):
            n = int(last[b, s, 0]) + 1
            order = sorted(range(n), key=lambda i: (-masked[b, s, i], i))
            assert set(np.nonzero(got[b, s])[0]) == set(order[:topk])
    # the tick's form of the same choice, a list (no longer than a row)
    index, ok = topk_tokens(x, valid, min(topk, 50))
    for b in range(3):
        for s in range(7):
            listed = {int(i) for i, o in zip(np.asarray(index)[b, s],
                                             np.asarray(ok)[b, s]) if o}
            assert listed == set(np.nonzero(got[b, s])[0].tolist())


def test_a_negative_weight_on_a_relus_zero_is_an_ordinary_zero():
    """`-0.0` would sort under `0.0` by its bits; the scores hold none."""
    qi = jnp.ones((1, 1, 1, 2))
    ki = jnp.asarray([[[-1.0, 0.0], [0.0, -1.0], [1.0, 0.0]]])
    scores = index_scores(qi, jnp.asarray([[[-1.0]]]), ki, 1.0)
    assert not np.signbit(np.asarray(scores)[0, 0, :2]).any()
    got = topk_token_mask(scores, jnp.ones((1, 1, 3), bool), 2)
    assert np.asarray(got)[0, 0].tolist() == [True, True, False]


def test_index_extents_cover_every_window():
    assert index_extents(2048, 33280, 2048, 4096) == (
        2048, 4096, 8192, 12288, 16384, 20480, 24576, 28672, 32768, 33280)
    assert index_extents(16, 64, 8, 16) == (16, 32, 48, 64)
    assert index_extents(4, 40, 8, 16) == (8, 16, 32, 40)


@pytest.mark.parametrize("t0", [0, 8, 24])
def test_window_read_equals_the_dense_form(t0):
    """A window of 24 queries at positions `t0 ..` over a cache of 48:
    inside `topk` the read is plain causal attention; past it, the
    chosen set is the dense form's, whichever extent's branch runs."""
    c = _case(t0)
    scale = 0.17
    want, allowed = _dense_indexed(c, t0, TOPK, scale)
    got = jax.jit(lambda t: indexed_prefill_attention(
        c["q"], c["k"], c["v"], c["qi"], c["w"], c["ki"], t, topk=TOPK,
        index_scale=scale, extent_step=16, q_tile=8, k_tile=8))(
            jnp.int32(t0))
    np.testing.assert_allclose(got, want, atol=2e-6)
    if t0 == 0:
        causal = np.tril(np.ones((24, 48), bool))
        assert (allowed[:, :TOPK] == causal[None, :TOPK]).all()


def test_walk_skips_the_tiles_past_the_last_query():
    c = _case(3)
    allowed = jnp.asarray(np.tril(np.ones((24, 48), bool), k=10))[None]
    allowed = jnp.broadcast_to(allowed, (2, 24, 48))
    full = masked_attention_walk(c["q"], c["k"], c["v"], allowed, 47,
                                 k_tile=8)
    cut = masked_attention_walk(c["q"], c["k"], c["v"], allowed, 33,
                                k_tile=8)
    np.testing.assert_allclose(full, cut, atol=1e-6)


@pytest.mark.parametrize("layered", [False, True])
def test_tick_read_equals_the_dense_form(layered):
    """One query a lane through a block table in scrambled order, lanes
    at different cursors (one within `topk`): the gathered rows give
    what the dense form gives over the lane's own tokens."""
    rng = np.random.default_rng(7)
    lanes, blocks, bs, G, D, J, Di = 3, 4, 8, 2, 16, 4, 8
    nb = lanes * blocks + 1
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    layers = 2 if layered else 1
    pool_k, pool_v = f(layers, nb, bs, 1, G * D), f(layers, nb, bs, 1, G * D)
    pool_i = f(layers, nb, bs, 1, Di)
    table = jnp.asarray(rng.permutation(np.arange(1, nb)).reshape(
        lanes, blocks), jnp.int32)
    t = jnp.asarray([5, 17, 30], jnp.int32)
    q, qi, w = f(lanes, 1, 4, D), f(lanes, J, Di), f(lanes, J)
    layer = 1 if layered else None
    args = (pool_i, pool_k, pool_v) if layered else \
        (pool_i[0], pool_k[0], pool_v[0])
    got = xla_indexed_decode_attention(
        q, qi, w, *args, table, t, topk=TOPK, index_scale=0.2, layer=layer)
    at = layer or 0
    lane = lambda pool, b: pool[at][table[b]].reshape(  # noqa: E731
        1, blocks * bs, -1)
    for b in range(lanes):
        c = dict(q=q[b:b + 1], qi=qi[b:b + 1, None], w=w[b:b + 1, None],
                 k=lane(pool_k, b).reshape(1, -1, G, D),
                 v=lane(pool_v, b).reshape(1, -1, G, D),
                 ki=lane(pool_i, b))
        want, _ = _dense_indexed(c, int(t[b]), TOPK, 0.2)
        np.testing.assert_allclose(got[b], want[0], atol=2e-6)


def test_the_programs_chosen_set_is_the_references(tiny):
    """Layer 0 of the tiny model on a seeded prompt: the mask the
    program's selection builds from its own indexer equals the
    reference's `choose` over the reference's scores, query by query,
    across a window edge (16) and `topk`."""
    cfg, model, params, rcfg, rparams = tiny
    ids = _prompt(40)
    x = np.asarray(rparams["model/embed_tokens/embedding"])[ids]
    lp = {p[len("model/layers_0/"):]: w for p, w in rparams.items()
          if p.startswith("model/layers_0/")}
    h = reference._rms(jnp.asarray(x), lp["input_layernorm/scale"], 1e-6)
    mm = reference.MATMULS["highest"]
    pre = "self_attn/indexer/"
    theta = rcfg["rope_theta"]
    qi = reference._rope(mm(h, lp[pre + "q_proj/kernel"]).reshape(
        40, 4, 8), theta)
    ki = reference._layer_norm(mm(h, lp[pre + "k_proj/kernel"]),
                               lp[pre + "k_norm/scale"],
                               lp[pre + "k_norm/bias"], 1e-6)
    ki = reference._rope(ki[:, None], theta)[:, 0]
    w = mm(h, lp[pre + "weights_proj/kernel"])
    want = reference.choose(
        rcfg, reference.index_scores(rcfg, qi, w, ki), jnp.arange(40))
    scores = index_scores(qi[None], w[None], ki[None], cfg.index_scale)
    causal = jnp.tril(jnp.ones((40, 40), bool))[None]
    got = topk_token_mask(scores, causal, cfg.index_topk)
    assert (np.asarray(got[0]) == np.asarray(want)).all()
    assert np.asarray(want).sum(-1).tolist() == \
        [min(t + 1, TOPK) for t in range(40)]


# ---- (c) a third kind of row in the pool -------------------------------

def test_pool_holds_the_indexers_key_rows(tiny):
    cfg, model, params, _, _ = tiny
    pool = init_pool_cache(model, 3, layout="paged", num_blocks=9,
                           block_size=32, max_blocks_per_slot=4)["model"]
    # a token's two KV heads of 16 are one row of 32; its indexer key 8
    assert pool["cached_key"].shape == (2, 9, 32, 1, 32)
    assert pool["cached_index_key"].shape == (2, 9, 32, 1, 8)
    assert pool["block_table"].shape == (2, 3, 4)
    assert [n for n in pool if n.startswith(INDEX_PREFIX)] == [
        "cached_index_key"]
    assert positional_leaves({"model": pool}) == ["cached_index_key"]
    rng = np.random.default_rng(0)
    primed = {"model": {
        "cached_key": jnp.zeros((2, 1, 128, 1, 32)),
        "cached_value": jnp.zeros((2, 1, 128, 1, 32)),
        "cached_index_key": jnp.asarray(
            rng.normal(size=(2, 1, 128, 1, 8)), jnp.float32),
        "cache_index": jnp.full((2,), 70, jnp.int32)}}
    table = jnp.asarray([4, 2, 7, 0], jnp.int32)
    out = assign_paged({"model": pool}, primed, 1, table)["model"]
    # token 40 is row 8 of the lane's 2nd block, in either layer
    for layer in (0, 1):
        np.testing.assert_array_equal(
            out["cached_index_key"][layer, 2, 8],
            primed["model"]["cached_index_key"][layer, 0, 40])
    assert int(out["cache_index"][1, 1]) == 70

    eng = _engine(model, params)
    nb = eng.num_blocks
    assert eng._kv_bytes == 2 * nb * 32 * 2 * 32 * 4
    assert eng._state_bytes == 2 * nb * 32 * 8 * 4
    assert eng.stats()["state_bytes"] == eng._state_bytes
    from fengshen_tpu.observability import render_prometheus
    assert f"fstpu_serving_state_bytes {eng._state_bytes}\n" in \
        render_prometheus(eng.metrics.registry)


def test_a_reused_lanes_rows_are_the_new_requests(tiny):
    """Two requests one after the other through ONE lane: the second
    reuses the first's blocks (and, on the slot layout, its lane) and
    is served as if alone; its ticks' index keys land in its own rows."""
    _, model, params, _, _ = tiny
    eng = _engine(model, params, num_slots=1, kv_num_blocks=5,
                  kv_max_blocks_per_slot=4)
    first, second = _prompt(60, 1), _prompt(45, 2)
    a, = eng.generate_all([first], 12)
    assert eng.stats()["kv_blocks_used"] == 0
    b, = eng.generate_all([second], 12)
    for prompt, out in ((first, a), (second, b)):
        logits = _reference_logits(tiny, np.concatenate([prompt, out]))[
            len(prompt) - 1:-1]
        assert (logits.max(-1) - logits[np.arange(12), out]).max() <= 1e-5


def test_handoff_refuses_by_leaf_name(tiny):
    from fengshen_tpu.serving.handoff import HandoffError, export_lane
    _, model, params, _, _ = tiny
    eng = _engine(model, params)
    eng.submit(_prompt(20), 8, request_id="r")
    eng.step()
    with pytest.raises(HandoffError, match="cached_index_key"):
        export_lane(eng, "r")


@pytest.mark.parametrize("mode", ["prompt_lookup", "self_draft"])
def test_speculative_modes_refuse_by_leaf_name(tiny, mode):
    _, model, params, _, _ = tiny
    with pytest.raises(ValueError, match="cached_index_key"):
        _engine(model, params, spec_mode=mode, spec_gamma=2)


def test_int8_pool_is_refused(tiny):
    _, model, params, _, _ = tiny
    eng = _engine(model, params, kv_dtype="int8")
    with pytest.raises(ValueError, match="no int8 form"):
        eng.generate_all([_prompt(20)], 2)


# ---- (d) the counters ---------------------------------------------------

def test_index_counters_follow_the_cursors(tiny):
    cfg, model, params, _, _ = tiny
    eng = _engine(model, params, num_slots=1)
    eng.generate_all([_prompt(5)], 7)
    from fengshen_tpu.observability import render_prometheus
    text = render_prometheus(eng.metrics.registry)
    # six ticks at contexts 6..11: everything up to topk 8, then 8
    assert f"fstpu_index_tokens_scored_total {6 + 7 + 8 + 9 + 10 + 11}\n" \
        in text
    assert f"fstpu_index_tokens_selected_total {6 + 7 + 8 + 8 + 8 + 8}\n" \
        in text
    assert "fstpu_serving_prefill_windows_total 1\n" in text
    assert "fstpu_sparse_tokens_cached_total 0\n" in text
    assert model.indexed_tokens(np.asarray([8, 9])).tolist() == [8, 8]


def test_moe_counters_count_every_expert_as_held(tiny):
    cfg, model, params, _, _ = tiny
    eng = _engine(model, params, num_slots=2)
    eng.generate_all([_prompt(20), _prompt(9)], 5)
    from fengshen_tpu.observability import render_prometheus
    counters = {}
    for line in render_prometheus(eng.metrics.registry).splitlines():
        if line.startswith("fstpu_moe_") and "{" not in line:
            name, _, value = line.partition(" ")
            counters[name] = float(value)
    assert counters["fstpu_moe_assignments_total"] > 0
    assert counters["fstpu_moe_assignments_held_total"] == \
        counters["fstpu_moe_assignments_total"]


# ---- (e) the family behind models/auto and the converter ----------------

def test_auto_builds_the_family_from_a_published_style_config(tmp_path):
    from fengshen_tpu.models.auto import AutoConfig, AutoModel
    KeyeConfig.small_test_config().save_pretrained(str(tmp_path))
    cfg = AutoConfig.from_pretrained(str(tmp_path))
    assert isinstance(cfg, KeyeConfig) and cfg.index_topk == TOPK
    assert isinstance(AutoModel.from_config(cfg, "causal_lm"),
                      KeyeForCausalLM)


def test_convert_reads_the_assumed_key_layout(tiny):
    from fengshen_tpu.models.keye.convert import torch_to_params
    cfg, model, params, _, _ = tiny
    flat = weights.flat(params)
    state = {}
    for path, leaf in flat.items():
        leaf = np.asarray(leaf)
        parts = path.split("/")
        if parts[0] == "lm_head":
            state["lm_head.weight"] = leaf.T
            continue
        name = ".".join(parts[1:]).replace("layers_", "layers.")
        name = name.replace("indexer.q_proj", "indexer.wq").replace(
            "indexer.k_proj", "indexer.wk")
        if parts[-1].startswith("experts_"):
            kind = parts[-1][len("experts_"):] + "_proj"
            for e in range(leaf.shape[0]):
                state["model." + name.rsplit(".", 1)[0] +
                      f".experts.{e}.{kind}.weight"] = leaf[e].T
        elif "router" in parts:
            state["model." + name.replace("router.kernel",
                                          "gate.weight")] = leaf.T
        elif parts[-1] == "kernel":
            state["model." + name[:-len("kernel")] + "weight"] = leaf.T
        elif parts[-1] == "embedding":
            state["model.embed_tokens.weight"] = leaf
        elif parts[-1] == "scale":
            state["model." + name[:-len("scale")] + "weight"] = leaf
        else:
            state["model." + name] = leaf
    got = weights.flat(torch_to_params(state, cfg))
    assert set(got) == set(flat)
    for path in flat:
        np.testing.assert_array_equal(got[path], flat[path])


def test_the_config_refuses_what_is_not_built():
    with pytest.raises(ValueError, match="rope_scaling"):
        KeyeConfig.small_test_config(rope_scaling={"rope_type": "yarn"})
    with pytest.raises(ValueError, match="one key head"):
        KeyeConfig.small_test_config(sa_config={
            "indexer_head_dim": 8, "indexer_num_heads": 4,
            "indexer_num_kv_heads": 2, "topk": 8})
    cfg = KeyeConfig.small_test_config(rope_scaling={
        "mrope_section": [2, 3, 3], "rope_type": "default",
        "type": "default"})
    assert cfg.index_scale == (4 * 8) ** -0.5
