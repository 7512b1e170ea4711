"""Declarative logical-axis sharding subsystem (fengshen_tpu/sharding/,
docs/sharding.md).

The load-bearing contracts:

- the vocabulary + rules-table validators reject typos loudly (the
  runtime mirror of fslint's ``partition-spec-axes`` checks);
- ``resolve_spec`` / ``to_partition_rules`` produce the exact
  PartitionSpecs the hand-written per-model tables used to declare
  (the migration-equivalence pins below — regressing one silently
  changes how a fleet shards);
- ``use_rules`` scopes an alternative table without leaking across the
  default;
- the rule-driven parity matrix: llama, transfo_xl, sd_unet and clip
  run SHARDED on the virtual 8-device mesh numerically equal to
  replicated — including the two towers whose divergences this
  subsystem root-caused (the concat-contraction mispartition,
  docs/sharding.md "Root cause");
- llama greedy decode is token-identical sharded vs replicated after
  the migration.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from fengshen_tpu.sharding import (DEFAULT_LOGICAL_AXIS_RULES,
                                   LOGICAL_AXES, LOGICAL_AXIS_SET,
                                   get_rules, resolve_spec, set_rules,
                                   to_partition_rules, use_rules,
                                   validate_rules)


# ---- vocabulary + validation -------------------------------------------

def test_vocabulary_is_flat_and_frozen():
    assert isinstance(LOGICAL_AXES, tuple)
    assert all(isinstance(a, str) for a in LOGICAL_AXES)
    assert LOGICAL_AXIS_SET == frozenset(LOGICAL_AXES)
    assert len(set(LOGICAL_AXES)) == len(LOGICAL_AXES)
    # the default table maps every role exactly once
    assert {k for k, _ in DEFAULT_LOGICAL_AXIS_RULES} == LOGICAL_AXIS_SET


def test_validate_rules_rejects_malformed_tables():
    validate_rules(DEFAULT_LOGICAL_AXIS_RULES)  # must not raise
    with pytest.raises(ValueError, match="unknown logical axis"):
        validate_rules((("head", "tensor"),))
    with pytest.raises(ValueError, match="unknown mesh axis"):
        validate_rules((("heads", "tenosr"),))
    with pytest.raises(ValueError, match="unknown mesh axis"):
        validate_rules((("batch", ("data", "fsp")),))
    with pytest.raises(ValueError, match="mapped twice"):
        validate_rules((("heads", "tensor"), ("heads", None)))
    with pytest.raises(ValueError, match="not a"):
        validate_rules((("heads",),))


def test_resolve_spec_default_table():
    assert resolve_spec(("embed", "heads")) == P("fsdp", "tensor")
    assert resolve_spec(("batch", "seq", "mlp")) == \
        P(("data", "fsdp"), "sequence", "tensor")
    # None entries and deliberately-unsharded roles stay replicated
    assert resolve_spec((None, "relpos")) == P(None, None)
    assert resolve_spec(("norm",)) == P(None)
    assert resolve_spec(()) == P(None)
    with pytest.raises(ValueError, match="unknown logical axis"):
        resolve_spec(("head",))


def test_use_rules_scoping_and_set_rules():
    custom = tuple((k, None) if k == "mlp" else (k, v)
                   for k, v in DEFAULT_LOGICAL_AXIS_RULES)
    assert resolve_spec(("embed", "mlp")) == P("fsdp", "tensor")
    with use_rules(custom):
        assert get_rules() == custom
        assert resolve_spec(("embed", "mlp")) == P("fsdp", None)
        with use_rules(None):
            # nested scope back to the default
            assert resolve_spec(("embed", "mlp")) == P("fsdp", "tensor")
        assert resolve_spec(("embed", "mlp")) == P("fsdp", None)
    assert get_rules() == DEFAULT_LOGICAL_AXIS_RULES
    with pytest.raises(ValueError, match="unknown logical axis"):
        set_rules((("head", "tensor"),))
    assert get_rules() == DEFAULT_LOGICAL_AXIS_RULES


# ---- migration-equivalence pins ----------------------------------------

def _first(rules, path):
    """First-match semantics, exactly like
    parallel.partition.match_partition_rules (re.search, order wins)."""
    for pattern, spec in rules:
        if re.search(pattern, path):
            return pattern, spec
    raise AssertionError(f"no rule matched {path!r}")


def test_llama_partition_rule_pins():
    """The specs the hand-written LLAMA_PARTITION_RULES table used to
    pin — the migration must not have changed a single one."""
    from fengshen_tpu.models.llama.modeling_llama import (
        PARTITION_RULES, SCAN_PARTITION_RULES)
    pins = {
        "model/embed_tokens/embedding": P("tensor", "fsdp"),
        "model/layers_0/self_attn/q_proj/kernel": P("fsdp", "tensor"),
        "model/layers_0/self_attn/o_proj/kernel": P("tensor", "fsdp"),
        "model/layers_0/mlp/gate_proj/kernel": P("fsdp", "tensor"),
        "model/layers_0/mlp/down_proj/kernel": P("tensor", "fsdp"),
        "model/layers_0/mlp/experts_gate": P("expert", None, "tensor"),
        "model/layers_0/input_layernorm/scale": P(None),
        "lm_head/kernel": P("fsdp", "tensor"),
    }
    for path, want in pins.items():
        assert _first(PARTITION_RULES, path)[1] == want, path
    scan_pins = {
        "model/layers/self_attn/q_proj/kernel":
            P(None, "fsdp", "tensor"),
        "model/layers/mlp/down_proj/kernel": P(None, "tensor", "fsdp"),
        "model/layers/mlp/experts_down": P(None, "expert", "tensor",
                                           None),
    }
    for path, want in scan_pins.items():
        assert _first(SCAN_PARTITION_RULES, path)[1] == want, path


def test_encoder_family_partition_rule_pins():
    from fengshen_tpu.models.bert.modeling_bert import (
        PARTITION_RULES as BERT)
    from fengshen_tpu.models.clip.modeling_taiyi_clip import (
        PARTITION_RULES as CLIP)
    from fengshen_tpu.models.t5.modeling_t5 import (
        PARTITION_RULES as T5)
    pins = [
        (BERT, "bert/embeddings/word_embeddings/embedding",
         P("tensor", None)),
        (BERT, "encoder/layer_0/attention/self/query/kernel",
         P("fsdp", "tensor")),
        (BERT, "encoder/layer_0/attention_output_dense/kernel",
         P("tensor", "fsdp")),
        (CLIP, "text_model/embeddings/word_embeddings/embedding",
         P("tensor", None)),
        (CLIP, "vision_model/layers_0/self_attn/q_proj/kernel",
         P("fsdp", "tensor")),
        (CLIP, "vision_model/layers_0/self_attn/out_proj/kernel",
         P("tensor", "fsdp")),
        (T5, "shared/embedding", P("tensor", "fsdp")),
        (T5, "encoder/block_0/layer_0/SelfAttention/o/kernel",
         P("tensor", "fsdp")),
        (T5, "lm_head/kernel", P("fsdp", "tensor")),
    ]
    for rules, path, want in pins:
        assert _first(rules, path)[1] == want, path


def test_t5_wo_rule_ordering_pin():
    """`re.search("o/kernel")` matches INSIDE "wo/kernel", so the
    feed-forward `wo` rule must sit before the attention `o` rule —
    this pin keeps the ordering load-bearing fact from regressing
    (the resolved specs coincide under the DEFAULT table, but a table
    sharding heads differently from mlp would miscategorize wo)."""
    from fengshen_tpu.models.t5.modeling_t5 import PARAM_LOGICAL_AXES
    pattern, axes = _first(PARAM_LOGICAL_AXES,
                           "block_0/layer_1/DenseReluDense/wo/kernel")
    assert pattern == r"wo/kernel" and tuple(axes) == ("mlp", "embed")


def test_gpt2_c_proj_rule_ordering_pin():
    """gpt2 reuses the name `c_proj` for the attention output AND the
    MLP output; the path-qualified attn rule must win for attention
    paths. Pinned under a table that shards heads and mlp differently
    so a regression cannot hide behind coinciding default specs."""
    from fengshen_tpu.models.gpt2.modeling_gpt2 import PARAM_LOGICAL_AXES
    custom = tuple((k, None) if k == "mlp" else (k, v)
                   for k, v in DEFAULT_LOGICAL_AXIS_RULES)
    rules = to_partition_rules(PARAM_LOGICAL_AXES, rules=custom)
    assert _first(rules, "h_0/attn/c_proj/kernel")[1] == \
        P("tensor", "fsdp")
    assert _first(rules, "h_0/mlp/c_proj/kernel")[1] == P(None, "fsdp")


def test_root_cause_tower_rule_pins():
    """The two root-caused towers (docs/sharding.md "Root cause"):
    transfo_xl's `relative` is column-parallel with a REPLICATED
    contraction dim (relpos), and the SD UNet convs shard only their
    output channels — both keep concat outputs away from sharded
    matmul contractions."""
    from fengshen_tpu.models.stable_diffusion.unet_sd import (
        SD_PARTITION_RULES)
    from fengshen_tpu.models.transfo_xl_denoise.modeling_transfo_xl \
        import XL_PARTITION_RULES
    assert _first(XL_PARTITION_RULES,
                  "layer_0/attention/relative/kernel")[1] == \
        P(None, "tensor")
    assert _first(XL_PARTITION_RULES,
                  "layer_0/attention/query_key_value/kernel")[1] == \
        P("fsdp", "tensor")
    assert _first(SD_PARTITION_RULES,
                  "down_blocks_0/resnets_0/conv1/kernel")[1] == \
        P(None, None, None, "fsdp")
    assert _first(
        SD_PARTITION_RULES,
        "down_blocks_0/attentions_0/transformer_blocks_0/attn2/"
        "to_q/kernel")[1] == P(None, "tensor")


# ---- rule-driven parity matrix (sharded == replicated) -----------------

def _parity(model, params, apply_fn, mesh, atol, shard_probe):
    """Shared harness: replicated reference vs the same program on
    params sharded through the model's (rule-driven) partition table."""
    from fengshen_tpu.parallel import make_shardings
    ref = apply_fn(params)
    shardings = make_shardings(model.partition_rules(), params, mesh)
    sharded = jax.device_put(params, shardings)
    probe = shard_probe(sharded)
    assert any(e is not None for e in probe.sharding.spec), \
        "the rules did not actually shard the probe kernel"
    out = jax.jit(apply_fn)(sharded)
    for r, o in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   atol=atol)


def test_parity_matrix_llama(mesh8):
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=128, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=32, dtype="float32")
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(3, 127, (2, 8)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    _parity(model, params,
            lambda p: model.apply({"params": p}, ids), mesh8, 2e-5,
            lambda s: s["model"]["layers_0"]["self_attn"]["q_proj"][
                "kernel"])


def test_parity_matrix_transfo_xl(mesh8):
    from fengshen_tpu.models.transfo_xl_denoise.modeling_transfo_xl \
        import TransfoXLConfig, TransfoXLModel
    cfg = TransfoXLConfig.small_test_config()
    model = TransfoXLModel(cfg)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 127, (2, 8)))
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    _parity(model, params,
            lambda p: model.apply({"params": p}, ids)[0], mesh8, 2e-5,
            lambda s: s["layer_0"]["attention"]["query_key_value"][
                "kernel"])


def test_parity_matrix_sd_unet(mesh8):
    from fengshen_tpu.models.stable_diffusion.unet_sd import (
        SDUNetConfig, SDUNet2DConditionModel)
    cfg = SDUNetConfig.small_test_config(block_out_channels=(32, 64),
                                         cross_attention_dim=32)
    model = SDUNet2DConditionModel(cfg)
    rng = np.random.RandomState(2)
    lat = jnp.asarray(rng.randn(2, 8, 8, 4), jnp.float32)
    t = jnp.asarray([3, 411])
    ctx = jnp.asarray(rng.randn(2, 5, 32), jnp.float32)
    params = model.init(jax.random.PRNGKey(2), lat, t, ctx)["params"]
    _parity(model, params,
            lambda p: model.apply({"params": p}, lat, t, ctx), mesh8,
            2e-4,
            lambda s: s["down_blocks_0"]["attentions_0"][
                "transformer_blocks_0"]["attn2"]["to_q"]["kernel"])


def test_parity_matrix_clip(mesh8):
    from fengshen_tpu.models.bert import BertConfig
    from fengshen_tpu.models.clip.modeling_taiyi_clip import (
        CLIPVisionConfig, TaiyiCLIPModel)
    text_cfg = BertConfig(vocab_size=128, hidden_size=32,
                          num_hidden_layers=2, num_attention_heads=2,
                          intermediate_size=64,
                          max_position_embeddings=64, dtype="float32")
    model = TaiyiCLIPModel(text_cfg, CLIPVisionConfig.small_test_config())
    rng = np.random.RandomState(3)
    ids = jnp.asarray(rng.randint(3, 127, (2, 8)))
    pixels = jnp.asarray(rng.randn(2, 32, 32, 3), jnp.float32)
    params = model.init(jax.random.PRNGKey(3), ids, pixels)["params"]
    _parity(model, params,
            lambda p: model.apply({"params": p}, ids, pixels), mesh8,
            2e-5,
            lambda s: s["text_model"]["layer_0"]["query"]["kernel"])


def test_llama_greedy_decode_token_identity_sharded(mesh8):
    """The end-to-end acceptance pin: greedy decode over sharded params
    emits the exact token sequence the replicated model does."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.parallel import make_shardings
    from fengshen_tpu.utils.generate import generate
    cfg = LlamaConfig(vocab_size=128, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=48, dtype="float32")
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(4).randint(3, 127, (2, 8)))
    params = model.init(jax.random.PRNGKey(4), ids)["params"]
    ref = np.asarray(generate(model, params, ids, max_new_tokens=12,
                              eos_token_id=None, pad_token_id=0))
    shardings = make_shardings(model.partition_rules(), params, mesh8)
    sharded = jax.device_put(params, shardings)
    out = np.asarray(generate(model, sharded, ids, max_new_tokens=12,
                              eos_token_id=None, pad_token_id=0))
    np.testing.assert_array_equal(out, ref)
