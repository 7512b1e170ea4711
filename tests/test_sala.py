"""MiniCPM-SALA through the engine against its plain reference
(`benchmarks/references/sala.py`), at a tiny size on the CPU: windowed
prefill and decode through the pool, the two forms of the linear
layer, the selection on hand-worked cases, state and a second row rate
in the pool, and windows for a model of plain rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import weights
from benchmarks.references import sala as reference
from fengshen_tpu.models.sala import SalaConfig, SalaForCausalLM
from fengshen_tpu.ops.lightning_attention import (lightning_decode,
                                                  lightning_prefill,
                                                  lightning_slopes)
from fengshen_tpu.ops.sparse_attention import (SparseSpec, chosen_mask,
                                               pool_window, select_blocks,
                                               sparse_prefill_attention)
from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                         EngineConfig)
from fengshen_tpu.serving.paged_cache import (assign_paged, init_pool_cache,
                                              positional_leaves)

REFERENCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "lightning_nh", "lightning_head_dim",
    "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
    "dim_model_base", "kernel_size", "kernel_stride", "block_size", "topk",
    "init_blocks", "window_size", "dense_len")


@pytest.fixture(scope="module")
def tiny():
    """(config, model, params, the reference's config and params): the
    same seeded values under the same leaf names on both sides."""
    cfg = SalaConfig.small_test_config()
    model = SalaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    key = weights.base_key(3)
    params = weights.fill_like(key, shapes)
    rcfg = {k: getattr(cfg, k) for k in REFERENCE_KEYS}
    rcfg.update(mixer_types=list(cfg.mixer_types), param_dtype="float32",
                residual_depth=cfg.num_hidden_layers)
    rshapes = reference.param_shapes(rcfg)
    assert set(weights.flat(params)) == set(rshapes)
    return cfg, model, params, rcfg, weights.fill(key, rshapes)


def _reference_logits(tiny, ids):
    _, _, _, rcfg, rparams = tiny
    pad = np.zeros((256,), np.int32)
    pad[:len(ids)] = ids
    return np.asarray(reference.forward_logits(
        rcfg, "highest", rparams, pad, np.arange(len(ids))))


def _engine(model, params, **kw):
    base = dict(num_slots=3, buckets=(16, 32), max_new_tokens=40,
                kv_layout="paged", kv_block_size=32, max_queue=8)
    base.update(kw)
    return ContinuousBatchingEngine(model, params, EngineConfig(**base))


def _prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(
        1, 128, size=(n,)).astype(np.int32)


# ---- (a) windows then decode through the pool = the reference ----------

def test_plain_forward_matches_reference(tiny):
    _, model, params, _, _ = tiny
    ids = _prompt(200)
    got = np.asarray(model.apply({"params": params}, ids[None]))[0]
    np.testing.assert_allclose(got, _reference_logits(tiny, ids),
                               atol=2e-6)


@pytest.mark.parametrize("layout", ["paged", "slot"])
@pytest.mark.parametrize("n_prompt", [20, 75, 130])
def test_engine_serves_reference_argmax(tiny, layout, n_prompt):
    """One window (20), three with the last partial (75), five past
    `dense_len` (130); 40 new tokens carry 75 across `dense_len` 96.
    Every served token is the reference's best at its position."""
    _, model, params, _, _ = tiny
    eng = _engine(model, params, kv_layout=layout)
    prompt = _prompt(n_prompt)
    out, = eng.generate_all([prompt], 40)
    logits = _reference_logits(tiny, np.concatenate([prompt, out]))[
        n_prompt - 1:-1]
    gaps = logits.max(-1) - logits[np.arange(40), out]
    assert gaps.max() <= 1e-5
    stats = eng.stats()
    assert stats["prefills_per_bucket"] == {
        16 if n_prompt <= 16 else 32: -(-n_prompt // 32)}


def test_window_and_tick_logits_match_reference(tiny):
    """The logits themselves, on a contiguous cache: windows of 16 (the
    last holds 11 real tokens), then one token at a time past
    `dense_len`."""
    from fengshen_tpu.serving.cache import abstract_init
    from fengshen_tpu.utils.generate import _rollback_cache
    cfg, model, params, _, _ = tiny
    ids = _prompt(140)
    want = _reference_logits(tiny, ids)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        abstract_init(model, 1)["cache"])
    n_prompt, width = 91, 16

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

    for start in range(0, n_prompt, width):
        n_valid = min(width, n_prompt - start)
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :n_valid] = ids[start:start + n_valid]
        logits, cache = call(cache, chunk, start, n_valid)
        np.testing.assert_allclose(logits[:n_valid],
                                   want[start:start + n_valid], atol=2e-6)
    for t in range(n_prompt, 140):
        logits, cache = call(cache, ids[None, t:t + 1], t, 1)
        np.testing.assert_allclose(logits[0], want[t], atol=2e-6)
    # (e) the pooled keys the ticks appended = pooling from scratch
    tree = cache["model"]
    spec = cfg.sparse
    n = (140 - spec.kernel_size) // spec.kernel_stride + 1
    np.testing.assert_allclose(
        tree["cached_key_pooled"][0, :, :n],
        pool_window(tree["cached_key"][0], spec, n), atol=1e-6)


# ---- (b) the linear layer's two forms --------------------------------

def _recurrence(q, k, v, slopes, mask=None):
    batch, seq, heads, dim = q.shape
    state = jnp.zeros((batch, heads, dim, dim), jnp.float32)
    outs = []
    for t in range(seq):
        live = None if mask is None else jnp.asarray(mask[:, t])
        o, state = lightning_decode(q[:, t], k[:, t], v[:, t], state,
                                    slopes, live)
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize("chunk,seq", [(1, 12), (8, 16), (8, 19), (5, 19)])
def test_lightning_chunks_equal_recurrence(chunk, seq):
    rng = np.random.default_rng(seq)
    q, k, v = (jnp.asarray(rng.normal(size=(2, seq, 4, 16)), jnp.float32)
               for _ in range(3))
    slopes = lightning_slopes(4)
    want, want_state = _recurrence(q, k, v, slopes)
    got, state = lightning_prefill(
        q, k, v, jnp.zeros((2, 4, 16, 16), jnp.float32), slopes,
        chunk=chunk)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)


@pytest.mark.parametrize("side", ["right", "left"])
def test_lightning_padding_enters_no_state(side):
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 16, 4, 16)), jnp.float32)
               for _ in range(3))
    slopes = lightning_slopes(4)
    real = slice(0, 11) if side == "right" else slice(5, 16)
    mask = np.zeros((1, 16), bool)
    mask[:, real] = True
    start = jnp.asarray(rng.normal(size=(1, 4, 16, 16)), jnp.float32)
    got, state = lightning_prefill(q, k, v, start, slopes, mask, chunk=8)
    want, want_state = lightning_prefill(
        q[:, real], k[:, real], v[:, real], start, slopes, chunk=8)
    np.testing.assert_allclose(got[:, real], want, atol=2e-5)
    np.testing.assert_allclose(state, want_state, atol=2e-5)
    # a window of nothing but padding moves nothing
    _, idle = lightning_prefill(q, k, v, start, slopes,
                                np.zeros((1, 16), bool), chunk=8)
    np.testing.assert_array_equal(idle, start)


# ---- (c) the live mask and a reused lane -----------------------------

def _lane_state(eng, slot):
    return np.asarray(eng._cache["model"]["state_lightning"][:, slot])


def test_dead_lane_keeps_state_and_reused_lane_starts_assigned(tiny):
    _, model, params, _, _ = tiny
    eng = _engine(model, params, num_slots=2)
    long_req = eng.submit(_prompt(40), 30)
    short_req = eng.submit(_prompt(20, seed=1), 3)
    while not short_req.done:
        with eng._cv:
            eng._tick_locked(ahead=True)    # one tick in flight
    slot = 1 - long_req.slot
    before = _lane_state(eng, slot)
    assert np.abs(before).max() > 0
    for _ in range(6):
        with eng._cv:
            eng._tick_locked(ahead=True)
    np.testing.assert_array_equal(_lane_state(eng, slot), before)
    # the freed lane is taken again: what it serves is what a fresh
    # engine serves, so it started from the state it was assigned
    again = eng.submit(_prompt(50, seed=2), 8)
    eng.run_until_idle()
    assert again.slot is None and len(again.tokens) == 8
    alone, = _engine(model, params, num_slots=2).generate_all(
        [_prompt(50, seed=2)], 8)
    assert again.tokens == alone
    assert len(long_req.tokens) == 30


# ---- (d) the selection, by hand --------------------------------------

#: kernel 8, stride 4, blocks of 16: pooled window j covers tokens
#: [4j, 4j+8); block m is overlapped by windows 4m-1 .. 4m+3
HAND = SparseSpec(kernel_size=8, kernel_stride=4, block_size=16, topk=5,
                  init_blocks=1, window_size=16, dense_len=80)


def _one_hot_case(t, hot):
    """One KV head with two query heads over 40 pooled keys, key j the
    unit vector e_j (so head h's score of window j is q_h[j]); `hot`:
    {head: {window: score}}."""
    pooled = jnp.eye(40, 64)[None, :, None, :]              # [1, J, 1, D]
    q = np.zeros((1, 1, 2, 64), np.float32)
    for head, scores in hot.items():
        for j, s in scores.items():
            q[0, 0, head, j] = s * 8.0                      # sqrt(D) = 8
    rank = select_blocks(jnp.asarray(q), pooled, jnp.full((1, 1), t),
                         HAND, 10)
    return np.asarray(rank)[0, 0, 0], np.asarray(
        chosen_mask(rank, jnp.full((1, 1), t), HAND))[0, 0, 0]


def test_selection_forced_blocks_and_window_to_block_map():
    # t = 130: own block 8; the last 16 tokens [115, 130] touch blocks
    # 7 and 8; block 0 is the init block. Windows that END by 130:
    # 4j + 7 <= 130, j <= 30. Head 0 likes window 11 (tokens 44..51:
    # blocks 2 AND 3, the shared window), head 1 likes window 20
    # (tokens 80..87: block 5 only)
    rank, chosen = _one_hot_case(130, {0: {11: 6.0}, 1: {20: 5.0}})
    assert list(np.nonzero(rank >= 1e30)[0]) == [0, 7, 8]
    assert np.isneginf(rank[9])
    # window 11 is block 2's last (4*2+3) and block 3's first (4*3-1):
    # both blocks score it; block 5 scores window 20
    assert rank[2] == rank[3] > rank[5] > rank[1] > 0
    # 5 in all: three forced, then the two best — of the equal pair
    # the lower block first
    assert list(np.nonzero(chosen)[0]) == [0, 2, 3, 7, 8]


def test_selection_sums_the_group_and_ignores_unfinished_windows():
    # each head alone prefers another window; the SUM over the group's
    # two heads decides: window 16 (block 4) gets 0.5 from both heads,
    # windows 8 (block 2) and 24 (block 6) ~1 from one head each ...
    rank, chosen = _one_hot_case(
        130, {0: {8: 9.0, 16: 9.0}, 1: {24: 9.0, 16: 9.0}})
    assert rank[4] > rank[2] and rank[4] > rank[6]
    assert abs(rank[4] - 1.0) < 5e-3 and abs(rank[2] - 0.5) < 5e-3
    # ... and a window that ends after t is not there: window 31
    # covers tokens 124..131, past t = 130, whatever it would score
    rank, _ = _one_hot_case(130, {0: {31: 50.0, 8: 3.0}, 1: {31: 50.0}})
    assert rank[2] == rank[1:7].max() and rank[2] > 0.4


def test_dense_below_dense_len_equals_causal_attention():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
            for _ in range(2))
    pooled = pool_window(jnp.pad(k, ((0, 0), (0, 8), (0, 0), (0, 0))),
                         HAND, 16)
    got = sparse_prefill_attention(q, k, v, pooled, jnp.int32(0), HAND,
                                   q_tile=16, k_tile=32)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk",
                        q.reshape(1, 64, 2, 2, 16), k) / 4.0
    scores = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), scores, -jnp.inf)
    want = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(got, want.reshape(1, 64, 4, 16), atol=1e-5)
    # t + 1 <= dense_len reads every block up to its own
    t = jnp.asarray([[10, 79, 80]])
    rank = select_blocks(jnp.zeros((1, 3, 4, 16)), jnp.zeros((1, 40, 2, 16)),
                         t, HAND, 10)
    counts = np.asarray(chosen_mask(rank, t, HAND)).sum(-1)[0, :, 0]
    assert list(counts) == [1, 5, 5]        # 80: past dense_len, topk 5


# ---- (f) state and a second row rate in the pool ----------------------

def test_pool_holds_rows_of_two_rates_and_state(tiny):
    cfg, model, params, _, _ = tiny
    pool = init_pool_cache(model, 3, layout="paged", num_blocks=9,
                           block_size=32, max_blocks_per_slot=8)["model"]
    # a token's two KV heads of 16 are one row of 32
    assert pool["cached_key"].shape == (1, 9, 32, 1, 32)
    assert pool["cached_key_pooled"].shape == (1, 9, 8, 1, 32)
    assert pool["state_lightning"].shape == (3, 3, 4, 16, 16)
    assert pool["state_lightning"].dtype == jnp.float32
    assert pool["block_table"].shape == (1, 3, 8)
    assert positional_leaves({"model": pool}) == [
        "state_lightning", "cached_key_pooled"]
    rng = np.random.default_rng(0)
    primed = {"model": {
        "cached_key": jnp.asarray(rng.normal(size=(1, 1, 256, 1, 32)),
                                  jnp.float32),
        "cached_value": jnp.zeros((1, 1, 256, 1, 32)),
        "cached_key_pooled": jnp.asarray(
            rng.normal(size=(1, 1, 64, 1, 32)), jnp.float32),
        "cache_index": jnp.full((1,), 70, jnp.int32),
        "state_lightning": jnp.asarray(
            rng.normal(size=(3, 1, 4, 16, 16)), jnp.float32)}}
    table = jnp.asarray([4, 2, 7, 0, 0, 0, 0, 0], jnp.int32)
    out = assign_paged({"model": pool}, primed, 1, table)["model"]
    src = primed["model"]
    # token 40 is row 8 of the lane's 2nd block; pooled key 10 (tokens
    # 40..) is row 2 of the same block
    np.testing.assert_array_equal(out["cached_key"][0, 2, 8],
                                  src["cached_key"][0, 0, 40])
    np.testing.assert_array_equal(out["cached_key_pooled"][0, 2, 2],
                                  src["cached_key_pooled"][0, 0, 10])
    np.testing.assert_array_equal(out["state_lightning"][:, 1],
                                  src["state_lightning"][:, 0])
    assert not np.asarray(out["state_lightning"][:, 0]).any()
    assert int(out["cache_index"][0, 1]) == 70

    eng = _engine(model, params)
    nb = eng.num_blocks
    assert eng._kv_bytes == nb * (2 * 32 + 8) * 2 * 16 * 4
    assert eng._state_bytes == 3 * 3 * 4 * 16 * 16 * 4
    assert eng.stats()["state_bytes"] == eng._state_bytes
    from fengshen_tpu.observability import render_prometheus
    assert f"fstpu_serving_state_bytes {eng._state_bytes}\n" in \
        render_prometheus(eng.metrics.registry)


def test_handoff_refuses_by_leaf_name(tiny):
    from fengshen_tpu.serving.handoff import HandoffError, export_lane
    _, model, params, _, _ = tiny
    eng = _engine(model, params)
    eng.submit(_prompt(20), 8, request_id="r")
    eng.step()
    with pytest.raises(HandoffError, match="state_lightning"):
        export_lane(eng, "r")


@pytest.mark.parametrize("mode", ["prompt_lookup", "self_draft"])
def test_speculative_modes_refuse_by_leaf_name(tiny, mode):
    _, model, params, _, _ = tiny
    with pytest.raises(ValueError, match="state_lightning"):
        _engine(model, params, spec_mode=mode, spec_gamma=2)


def test_int8_pool_is_refused(tiny):
    _, model, params, _, _ = tiny
    eng = _engine(model, params, kv_dtype="int8")
    with pytest.raises(ValueError, match="no int8 form"):
        eng.generate_all([_prompt(20)], 2)


def test_sparse_counters_follow_the_cursors(tiny):
    cfg, model, params, _, _ = tiny
    eng = _engine(model, params, num_slots=1)
    eng.generate_all([_prompt(94)], 6)
    from fengshen_tpu.observability import render_prometheus
    text = render_prometheus(eng.metrics.registry)
    # five ticks at contexts 95..99: dense up to 96, then topk 6
    # blocks of 16, the own block holding (c - 1) % 16 + 1 tokens
    cached = 95 + 96 + 97 + 98 + 99
    attended = 95 + 96 + (5 * 16 + 1) + (5 * 16 + 2) + (5 * 16 + 3)
    assert f"fstpu_sparse_tokens_cached_total {cached}\n" in text
    assert f"fstpu_sparse_tokens_attended_total {attended}\n" in text
    assert "fstpu_serving_prefill_windows_total 3\n" in text
    assert "fstpu_serving_prefill_padded_tokens_total 96\n" in text
    assert cfg.sparse.attended_tokens(96) == 96


# ---- (g) windows for a model of plain rows ----------------------------

@pytest.mark.parametrize("layout", ["paged", "slot"])
def test_llama_prompt_past_the_ladder_goes_by_windows(layout):
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.utils.generate import generate
    cfg = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=128, dtype="float32")
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=2, buckets=(8, 16), max_new_tokens=6, max_queue=4,
        kv_layout=layout, kv_block_size=16))
    prompt = np.random.RandomState(0).randint(3, 96, 43).astype(np.int32)
    short = prompt[:12]
    got_long, got_short = eng.generate_all([prompt, short], 6)
    for ids, got in ((prompt, got_long), (short, got_short)):
        whole = generate(model, params, jnp.asarray(ids[None]),
                         max_new_tokens=6)
        assert got == [int(t) for t in whole[0, len(ids):]]
    stats = eng.stats()
    # 43 tokens: three windows of 16; 12 tokens: its bucket, as before
    assert stats["prefills_per_bucket"] == {16: 4}
    assert stats["rejected_prompt_too_long"] == 0
    assert eng._positional == []
