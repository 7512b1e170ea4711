"""`models/sdar`: the Qwen3-MoE block under a block-causal mask, against
the plain reference (`benchmarks/references/sdar.py`, which imports
nothing of the program) in float32 at a tiny size: the cache-less
forward, and prefill + block forwards through a paged pool against the
reference's generation loop, forward by forward."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import sdar as family
from benchmarks.lib import weights
from benchmarks.references import sdar as reference
from fengshen_tpu.models.sdar import SdarConfig, SdarForCausalLM
from fengshen_tpu.serving.cache import abstract_init, rollback_slots
from fengshen_tpu.serving.paged_cache import assign_paged, init_pool_cache

#: what the reference reads of a configuration (the benchmark's list)
REFERENCE_KEYS = family.REFERENCE_KEYS + family.GENERATION_KEYS + (
    "param_dtype",)


def tiny(**overrides):
    cfg = SdarConfig.small_test_config(**overrides)
    model = SdarForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    # norm scales away from one, so a norm left out shows
    params = weights.fill_like(weights.base_key(11), jax.eval_shape(
        lambda: params))
    ref_cfg = {k: getattr(cfg, k) for k in REFERENCE_KEYS}
    return cfg, model, params, ref_cfg, weights.flat(params)


@pytest.fixture(scope="module")
def sdar():
    return tiny()


def test_reference_names_the_programs_leaves(sdar):
    _, _, params, ref_cfg, flat = sdar
    shapes = reference.param_shapes(ref_cfg)
    assert {k: (tuple(v.shape), v.dtype) for k, v in flat.items()} == {
        k: (tuple(s), jnp.dtype(d)) for k, (s, d) in shapes.items()}


@pytest.mark.parametrize("seq", [8, 10, 16])
def test_plain_forward_is_block_causal(sdar, seq):
    """The cache-less call against the reference's whole-sequence
    forward, a last block cut short among the lengths; and the mask is
    the block's: a later token of a query's own block moves its logits,
    a token of the next block does not."""
    cfg, model, params, ref_cfg, flat = sdar
    ids = np.random.RandomState(seq).randint(1, cfg.vocab_size - 1, (seq,))
    got = np.asarray(model.apply({"params": params}, ids[None])[0])
    want = np.asarray(reference.forward_logits(ref_cfg, "highest", flat, ids,
                                               np.arange(seq)))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-4)
    moved = ids.copy()
    moved[7] += 1            # the last position of block 1
    other = np.asarray(model.apply({"params": params}, moved[None])[0])
    assert np.abs(other[4] - got[4]).max() > 1e-4     # same block, earlier
    np.testing.assert_array_equal(other[:4], got[:4])  # the block before


def test_masked_positions_are_fed_the_mask_token_by_flag(sdar):
    cfg, model, params, _, _ = sdar
    ids = np.arange(1, 9)[None]
    flags = np.zeros((1, 8), bool)
    flags[0, 5:] = True
    by_flag = model.apply({"params": params}, ids, masked=flags)
    by_id = model.apply({"params": params},
                        np.where(flags, cfg.mask_token_id, ids))
    np.testing.assert_array_equal(np.asarray(by_flag), np.asarray(by_id))
    # a token that IS the mask id and is not flagged stays a token
    assert model.generation_block() == (4, cfg.mask_token_id)
    hidden = model.apply({"params": params}, ids, head=False)
    assert hidden.shape == (1, 8, cfg.hidden_size)


def test_block_length_one_is_causal_qwen3_moe():
    """`L = 1`: the same parameters through `models/keye` with an
    indexer that keeps every cached token (`topk` >= the sequence) are a
    causal Qwen3-MoE forward; SDAR's must equal it, and declares no
    generation block."""
    from fengshen_tpu.models.keye import KeyeConfig, KeyeForCausalLM
    cfg, model, params, _, _ = tiny(block_length=1)
    assert model.generation_block() is None
    keye_cfg = KeyeConfig.small_test_config(
        rope_theta=cfg.rope_theta,
        sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                   "q_chunk_size": 8, "topk": 64})
    keye = KeyeForCausalLM(keye_cfg)
    keye_params = keye.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]

    def graft(path, leaf):
        at = params
        for k in path:
            if k.key not in at:
                return leaf                 # the indexer's own
            at = at[k.key]
        return at
    keye_params = jax.tree_util.tree_map_with_path(graft, keye_params)
    ids = np.random.RandomState(5).randint(1, 60, (2, 12))
    got = model.apply({"params": params}, ids)
    want = keye.apply({"params": keye_params}, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


_JITTED = {}


def _jitted(model, name, fn):
    """One compiled program a (model, call) for the whole file."""
    key = (id(model), name)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(fn)
    return _JITTED[key]


def _windows(model, params, cfg, ids, width):
    """Prefill `ids` (a whole number of blocks) in windows of `width`
    onto a batch-1 cache, padded on the right; no head."""
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        abstract_init(model, 1)["cache"])
    window = _jitted(model, "window", lambda params, cache, chunk, at:
                     model.apply({"params": params, "cache": cache}, chunk,
                                 position_ids=at, init_cache=True,
                                 mutable=["cache"], head=False))
    for start in range(0, len(ids), width):
        chunk = np.zeros((1, width), np.int32)
        n = min(width, len(ids) - start)
        chunk[0, :n] = ids[start:start + n]
        hidden, mutated = window(params, cache, chunk,
                                 start + np.arange(width)[None])
        assert hidden.shape == (1, width, cfg.hidden_size)
        cache = jax.tree_util.tree_map_with_path(
            lambda p, x: x - (width - n) if p[-1].key == "cache_index"
            else x, mutated["cache"])
    return cache


@pytest.mark.parametrize("remasking", ["sequential", "low_confidence"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_prefill_and_block_forwards_through_a_paged_pool(sdar, steps,
                                                         remasking):
    """Four lanes, prompt tails 0-3, each prefilled in windows and laid
    into scattered blocks of one paged pool; then the lanes' block
    forwards TOGETHER (per-lane cursors and phases: a lane with a tail
    takes fewer reveal forwards and commits earlier), a commit forward
    keeping the cursor's advance and every other rolled back. Every
    reveal forward's logits and every token against the reference's
    loop."""
    cfg, model, params, ref_cfg, flat = sdar
    L, n_new, lanes, bs, per = cfg.block_length, 7, 4, 8, 4
    rng = np.random.RandomState(17 + steps)
    prompts = [rng.randint(1, cfg.vocab_size - 1, (9 + tail + 4 * tail,))
               for tail in range(4)]          # lengths 9+5t: tails 1,2,3,0
    assert sorted(len(p) % L for p in prompts) == [0, 1, 2, 3]
    pool = init_pool_cache(model, lanes, layout="paged",
                           num_blocks=lanes * per + 1, block_size=bs,
                           max_blocks_per_slot=per)
    order = rng.permutation(lanes * per) + 1
    for lane, prompt in enumerate(prompts):
        p0 = len(prompt) // L * L
        primed = _windows(model, params, cfg, prompt[:p0], 8)
        pool = assign_paged(pool, primed, lane,
                            jnp.asarray(order[lane * per:(lane + 1) * per],
                                        jnp.int32))
    want = [reference.generate(ref_cfg, "highest", flat, p, n_new, steps,
                               remasking) for p in prompts]
    cursor = np.array([len(p) // L * L for p in prompts])
    tokens = np.zeros((lanes, L), np.int64)
    masked = np.ones((lanes, L), bool)
    for lane, p in enumerate(prompts):
        tail = len(p) % L
        tokens[lane, :tail] = p[len(p) - tail:]
        masked[lane, :tail] = False
    out = [[] for _ in prompts]
    seen = [0] * lanes
    forward = _jitted(model, "block", lambda params, pool, tokens, at, masked:
                      model.apply({"params": params, "cache": pool}, tokens,
                                  position_ids=at, masked=masked,
                                  init_cache=True, mutable=["cache"]))
    for _ in range(3 * (steps + 1)):
        logits, mutated = forward(params, pool, tokens,
                                  cursor[:, None] + np.arange(L)[None],
                                  masked)
        logits = np.asarray(logits)
        commit = ~masked.any(-1)
        pool = rollback_slots(mutated["cache"], np.where(commit, 0, L))
        for lane in range(lanes):
            if len(out[lane]) >= len(prompts[lane]) % L + n_new:
                continue            # the reference stopped here
            if commit[lane]:
                out[lane].extend(tokens[lane])
                cursor[lane] += L
                tokens[lane], masked[lane] = 0, True
                continue
            record = want[lane][1][seen[lane]]
            seen[lane] += 1
            assert record["block"] * L == cursor[lane]
            np.testing.assert_array_equal(record["masked"], masked[lane])
            np.testing.assert_allclose(logits[lane], record["logits"],
                                       atol=3e-5, rtol=3e-4)
            chosen = reference.pick(masked[lane], logits[lane],
                                    L // steps, remasking)
            np.testing.assert_array_equal(chosen, record["revealed"])
            tokens[lane, chosen] = logits[lane, chosen].argmax(-1)
            masked[lane, chosen] = False
    index = jax.tree_util.tree_leaves(jax.tree_util.tree_map_with_path(
        lambda p, x: x if p[-1].key == "cache_index" else None, pool))[0]
    assert (np.asarray(index)[0] >= cursor).all() and all(
        n == len(w[1]) for n, w in zip(seen, want))
    for lane, p in enumerate(prompts):
        tail = len(p) % L
        np.testing.assert_array_equal(out[lane][tail:tail + n_new],
                                      want[lane][0])


def _dense_block_attention(q, k_rows, v_rows, start, scale, block):
    """Every score formed, masked at the end of each query's block."""
    batch, seq, heads, dim = q.shape
    groups = k_rows.shape[-1] // dim
    k = np.repeat(k_rows.reshape(batch, -1, groups, dim), heads // groups, 2)
    v = np.repeat(v_rows.reshape(batch, -1, groups, dim), heads // groups, 2)
    s = np.einsum("bshd,bthd->bhst", q, k) * scale
    end = ((start + np.arange(seq)) // block + 1) * block
    s = np.where(np.arange(k.shape[1])[None] < end[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhst,bthd->bshd", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("q_tile,key_block", [(512, 1024), (8, 16), (4, 48)])
@pytest.mark.parametrize("start", [0, 24])
def test_the_block_walk_equals_dense_attention(q_tile, key_block, start):
    """`block_prefill_walk` in one tile and in several of queries and of
    keys (the served sizes are one pair, 512 x 1,024, where a tiny model
    is one tile): a window at `start` onto a lane with rows past its
    last block, which no query may read."""
    from fengshen_tpu.ops.gated_attention import block_prefill_walk
    rng = np.random.RandomState(start + q_tile)
    seq, total, heads, groups, dim, block = 24, 96, 4, 2, 16, 4
    q = rng.randn(2, seq, heads, dim).astype(np.float32)
    k_rows = rng.randn(2, total, groups * dim).astype(np.float32)
    v_rows = rng.randn(2, total, groups * dim).astype(np.float32)
    got = jax.jit(lambda *a: block_prefill_walk(
        *a, scale=0.25, block=block, q_tile=q_tile, key_block=key_block))(
            q, k_rows, v_rows, jnp.int32(start))
    want = _dense_block_attention(q, k_rows, v_rows, start, 0.25, block)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-4)


def test_a_draft_window_onto_a_pool_is_refused(sdar):
    cfg, model, params, _, _ = sdar
    pool = init_pool_cache(model, 2, layout="paged", num_blocks=9,
                           block_size=8, max_blocks_per_slot=4)
    with pytest.raises(ValueError, match="share an extent"):
        model.apply({"params": params, "cache": pool},
                    np.ones((2, 3), np.int32), init_cache=True,
                    mutable=["cache"])


def test_config_round_trip_and_auto(tmp_path):
    from fengshen_tpu.models.auto import AutoConfig
    cfg = SdarConfig.small_test_config(block_length=8)
    cfg.save_pretrained(str(tmp_path))
    again = SdarConfig.from_pretrained(str(tmp_path))
    assert dataclasses.asdict(again) == dataclasses.asdict(cfg)
    assert isinstance(AutoConfig.from_pretrained(str(tmp_path)), SdarConfig)
    with pytest.raises(ValueError, match="1 to 8"):
        SdarConfig.small_test_config(block_length=16)


def test_convert_reads_the_assumed_key_layout(sdar):
    """A `state_dict` under the ASSUMED names, written from the flax
    tree, converts back to that tree leaf for leaf and serves the same
    logits: a kernel left untransposed or experts stacked out of order
    would show."""
    from fengshen_tpu.models.sdar.convert import torch_to_params
    cfg, model, params, _, flat = sdar
    state = {}
    for path, leaf in flat.items():
        leaf = np.asarray(leaf)
        parts = path.split("/")
        if parts[0] == "lm_head":
            state["lm_head.weight"] = leaf.T
            continue
        name = "model." + ".".join(parts[1:-1]).replace("layers_", "layers.")
        if parts[-1].startswith("experts_"):
            kind = parts[-1][len("experts_"):] + "_proj"
            for e in range(leaf.shape[0]):
                state[f"{name}.experts.{e}.{kind}.weight"] = leaf[e].T
        elif parts[-2] == "router":
            state[name[:-len("router")] + "gate.weight"] = leaf.T
        elif parts[-1] == "kernel":
            state[name + ".weight"] = leaf.T
        else:                       # an embedding or a norm's scale
            state[name + ".weight"] = leaf
    assert "model.layers.1.mlp.experts.7.down_proj.weight" in state
    assert state["model.layers.0.self_attn.q_proj.weight"].shape == (
        cfg.num_attention_heads * cfg.head_dim, cfg.hidden_size)
    got = torch_to_params(state, cfg)
    got_flat = weights.flat(got)
    assert set(got_flat) == set(flat)
    for path in flat:
        np.testing.assert_array_equal(got_flat[path], flat[path])
    ids = np.random.RandomState(2).randint(1, cfg.vocab_size - 1, (1, 12))
    np.testing.assert_array_equal(
        np.asarray(model.apply({"params": got}, ids)),
        np.asarray(model.apply({"params": params}, ids)))


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_block_logits_are_generates_per_step_logits(sdar, steps):
    """The teacher-forced doubled forward (`block_logits`: the clean
    sequence followed by a step's noised copy) against the generation
    loop's own forwards under the `sequential` rule, record by record:
    what the benchmark scores a served token on is what the procedure
    saw when it revealed it."""
    cfg, _, _, ref_cfg, flat = sdar
    L = cfg.block_length
    for prompt_len, n_new in ((9, 11), (8, 8), (3, 6), (6, 5)):
        prompt = np.random.RandomState(prompt_len).randint(
            1, cfg.vocab_size - 1, (prompt_len,))
        tokens, records = reference.generate(
            ref_cfg, "highest", flat, prompt, n_new, steps, "sequential")
        ids = np.zeros((24,), np.int64)
        ids[:prompt_len] = prompt
        # the last block is generated whole: score what was served
        ids[prompt_len:prompt_len + n_new] = tokens
        by_step = [np.asarray(reference.block_logits(
            ref_cfg, "highest", flat, ids, prompt_len, n_new, s, steps,
            rows=12)) for s in range(steps)]
        revealed_at = reference.step_of(prompt_len, n_new, L, steps)
        checked = 0
        for r in records:
            for i in r["revealed"]:
                j = r["block"] * L + int(i) - prompt_len
                if j >= n_new:
                    continue        # past the cut
                assert revealed_at[j] == r["step"]
                np.testing.assert_allclose(by_step[r["step"]][j],
                                           r["logits"][i], atol=2e-5,
                                           rtol=2e-4)
                assert by_step[r["step"]][j].argmax() == tokens[j]
                checked += 1
        assert checked == n_new
