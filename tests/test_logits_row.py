"""`logits_row`: a causal LM's caller names the ONE row whose logits it
keeps, and the model slices its hidden states to that row before the
head (`models/model_utils.head_rows`; PERF.md, PR 46). The seven
serving families at tiny sizes on the CPU: the one row's logits are the
row of the all-rows product, the cache a cached call leaves is the
same, the parameter tree does not know the argument, and the engine's
window program holds no `[width, vocab]` array."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from fengshen_tpu.observability import render_prometheus
from fengshen_tpu.serving.engine import (ContinuousBatchingEngine,
                                         EngineConfig)
from fengshen_tpu.utils.generate import _prefill_cache, model_takes

FAMILIES = ["llama", "joyai", "sala", "qwen3_next", "keye", "trinity",
            "kimi_linear"]
#: no other width of a tiny configuration, so a shape names the head's
VOCAB = 104
SEQ = 16


def _model(family):
    if family == "llama":
        from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=VOCAB, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            scan_layers=True))
    from tests.test_serving_programs_unchanged import _model as tiny
    model = tiny(family)
    return type(model)(dataclasses.replace(
        model.config, vocab_size=VOCAB, dtype="float32"))


@pytest.fixture(scope="module")
def built():
    """{family: (model, params, ids)}, each family built once."""
    made = {}

    def of(family):
        if family not in made:
            model = _model(family)
            ids = jax.random.randint(jax.random.PRNGKey(1), (1, SEQ), 1,
                                     VOCAB)
            params = jax.jit(lambda: model.init(
                jax.random.PRNGKey(0), ids)["params"])()
            made[family] = (model, params, ids)
        return made[family]
    return of


@pytest.mark.parametrize("cached", [False, True], ids=["plain", "cached"])
@pytest.mark.parametrize("family", FAMILIES)
def test_one_rows_logits_are_that_row_of_all(built, family, cached):
    """One program holds both calls: every row's logits, and the rows
    0, 8 and 15 asked for one at a time; a cached call (the engine's
    own `_prefill_cache`) leaves the same cache either way."""
    model, params, ids = built(family)
    assert model_takes(model, "logits_row")
    rows = jnp.array([0, SEQ // 2, SEQ - 1], jnp.int32)

    def call(row=None):
        if cached:
            return _prefill_cache(model, params, ids, jnp.ones_like(ids),
                                  jnp.arange(SEQ)[None], logits_row=row)
        return model.apply({"params": params}, ids, logits_row=row), None

    (every, cache), (ones, caches) = jax.jit(
        lambda: (call(), jax.lax.map(call, rows)))()
    assert every.shape == (1, SEQ, VOCAB)
    assert ones.shape == (3, 1, 1, VOCAB)
    np.testing.assert_allclose(ones[:, 0, 0], every[0, rows], rtol=1e-5,
                               atol=1e-6)
    if cached:
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda some, one: bool((some == one[None]).all()),
            caches, cache))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_parameter_tree_does_not_know_the_argument(built, family):
    model, params, ids = built(family)
    shapes = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (a.shape, a.dtype), tree)
    asked = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), ids, logits_row=jnp.int32(3))["params"])
    assert shapes(asked) == shapes(params)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_window_program_holds_no_width_by_vocab_array(built, family):
    """The lowered `window_fn` of a 16-token window: the head's product
    is `[1, 1, vocab]`, and nothing in the program is `[..., 16,
    vocab]` (before PR 46 the logits of all 16 rows were)."""
    model, params, _ = built(family)
    eng = ContinuousBatchingEngine(model, params, EngineConfig(
        num_slots=2, buckets=(SEQ,), max_new_tokens=8,
        kv_layout="paged", kv_block_size=16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    text = eng._window_jit.lower(
        params, jax.eval_shape(eng._fresh_jit), i32(1, SEQ),
        i32(1, eng.seq_capacity), i32(), i32(),
        jax.ShapeDtypeStruct((2,), jnp.uint32)).as_text()
    assert re.search(rf"tensor<1x1x{VOCAB}x", text)
    wide = re.findall(rf"tensor<(?:\d+x)*{SEQ}x{VOCAB}x\w+>", text)
    assert not wide, sorted(set(wide))


class _CannotBeAsked(nn.Module):
    """A causal LM whose `__call__` has no `logits_row`."""

    inner: nn.Module

    @property
    def config(self):
        return self.inner.config

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True):
        return self.inner(input_ids, attention_mask, position_ids,
                          init_cache, deterministic)


@pytest.mark.parametrize("asked", [True, False], ids=["row", "every_row"])
def test_engine_serves_the_same_tokens_and_counts_the_heads_rows(asked):
    """A prompt of three windows and one inside a bucket through the
    engine against `generate()`, by a model that takes `logits_row` and
    by one that does not: the same tokens, and the counter reads one
    row a prefill program (3 + 1 of 64 padded rows) or all 64."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.utils.generate import generate
    llama = LlamaForCausalLM(LlamaConfig(
        vocab_size=97, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=128, dtype="float32"))
    params = llama.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    model, served = (llama, params) if asked else \
        (_CannotBeAsked(llama), {"inner": params})
    assert model_takes(model, "logits_row") == asked
    eng = ContinuousBatchingEngine(model, served, EngineConfig(
        num_slots=2, buckets=(8, 16), max_new_tokens=6, max_queue=4,
        kv_layout="paged", kv_block_size=16))
    prompt = np.random.RandomState(0).randint(3, 96, 43).astype(np.int32)
    short = prompt[:12]
    got = eng.generate_all([prompt, short], 6)
    for ids, tokens in zip((prompt, short), got):
        whole = generate(llama, params, jnp.asarray(ids[None]),
                         max_new_tokens=6)
        assert tokens == [int(t) for t in whole[0, len(ids):]]
    assert eng.stats()["prefills_per_bucket"] == {16: 4}
    text = render_prometheus(eng.metrics.registry)
    assert "fstpu_serving_prefill_padded_tokens_total 64\n" in text
    assert "fstpu_serving_prefill_head_rows_total " \
        f"{4 if asked else 64}\n" in text
