"""Routed experts in the slow lane: expert-parallel sharded training on
the virtual mesh and the llama `moe_experts` wiring (the Switch setting
of `ops/moe.py RoutedExperts`: softmax router, top-1). The layer's own
mathematics is tier-1: tests/test_routed_experts.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops import RoutedExperts

pytestmark = pytest.mark.slow  # full-fit/e2e lane: run with -m slow or no -m filter


def _aux_by_hand(model, params, ids, num_experts):
    """Each layer's Switch aux loss recomputed from the router's own
    logits: softmax, argmax pick, `load_balancing_loss`. `[layers]`, in
    layer order. The logits are captured on a pass of the UNROLLED
    model (nn.scan drops the intermediates collection); a scanned
    model's `[L, ...]` stack is cut into `layers_<i>` first."""
    import dataclasses
    from fengshen_tpu.ops.moe import load_balancing_loss
    cfg = model.config
    if cfg.scan_layers:
        stack = params["model"]["layers"]["layer"]
        inner = {k: v for k, v in params["model"].items() if k != "layers"}
        for i in range(cfg.num_hidden_layers):
            inner[f"layers_{i}"] = jax.tree_util.tree_map(
                lambda a: a[i], stack)
        params = dict(params, model=inner)
        model = type(model)(dataclasses.replace(cfg, scan_layers=False))
    _, state = model.apply(
        {"params": params}, ids, mutable=["intermediates"],
        capture_intermediates=lambda mdl, _: mdl.name == "router")
    by_layer = state["intermediates"]["model"]
    out = []
    for i in range(cfg.num_hidden_layers):
        logits, = jax.tree_util.tree_leaves(by_layer[f"layers_{i}"])
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        out.append(load_balancing_loss(probs, probs.argmax(-1),
                                       num_experts))
    return jnp.stack(out)


@pytest.fixture
def mesh_exp2():
    """1x1x2(expert)x1x1x2(tensor) mesh exercising expert parallelism."""
    from fengshen_tpu.parallel import MeshConfig, make_mesh, set_mesh
    mesh = make_mesh(MeshConfig(data=2, fsdp=1, expert=2, sequence=1,
                                tensor=2))
    set_mesh(mesh)
    yield mesh
    set_mesh(None)


def test_moe_trains_sharded_with_expert_axis(mesh_exp2):
    """Expert-parallel training: jit a loss step with experts sharded over
    the 'expert' axis; loss must decrease and grads must flow through
    both the routed path and the router."""
    import optax
    from fengshen_tpu.parallel import (match_partition_rules,
                                       make_shardings)
    from fengshen_tpu.ops.moe import MOE_PARTITION_RULES

    moe = RoutedExperts(hidden_size=8, intermediate_size=16, num_experts=4,
                        aux_loss=True, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8))
    params = moe.init(jax.random.PRNGKey(2), x)["params"]
    specs = match_partition_rules(
        MOE_PARTITION_RULES + [(".*", None)], params)
    shardings = make_shardings(specs, params, mesh_exp2)
    params = jax.device_put(params, shardings)
    tx = optax.adam(3e-3)
    ost = tx.init(params)

    @jax.jit
    def step(p, o, x, y):
        def loss_fn(p):
            out, sown = moe.apply({"params": p}, x, mutable=["losses"])
            return jnp.mean((out - y) ** 2) + \
                0.01 * sown["losses"]["moe_aux_loss"][0]
        l, g = jax.value_and_grad(loss_fn)(p)
        u, o = tx.update(g, o)
        return optax.apply_updates(p, u), o, l

    losses = []
    for _ in range(60):
        params, ost, l = step(params, ost, x, y)
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.8, losses


def test_llama_moe_wiring(mesh_exp2):
    """cfg.moe_experts routes the decoder MLP through RoutedExperts; forward
    works under jit on the expert mesh and the aux loss is sowable."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=16,
                      dtype="float32", moe_experts=4)
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 8)),
                      jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    assert "experts_gate" in str(jax.tree_util.tree_structure(
        variables["params"]))
    # pass params only: init's own sowed losses must not accumulate
    logits, state = model.apply({"params": variables["params"]}, ids,
                                mutable=["losses"])
    assert logits.shape == (2, 8, 64)
    aux = jax.tree_util.tree_leaves(state["losses"])
    assert len(aux) == cfg.num_hidden_layers
    np.testing.assert_allclose(
        np.asarray(aux, np.float32),
        _aux_by_hand(model, variables["params"], ids, cfg.moe_experts),
        rtol=1e-5)


def test_llama_moe_scan_layers_losses_survive():
    """scan_layers=True must still expose the sowed aux losses (stacked
    along the layer axis by nn.scan)."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=3, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=16,
                      dtype="float32", moe_experts=4, scan_layers=True)
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 8)),
                      jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids)
    logits, state = model.apply({"params": variables["params"]}, ids,
                                mutable=["losses"])
    leaves = jax.tree_util.tree_leaves(state["losses"])
    assert leaves, "losses collection dropped under nn.scan"
    stacked = leaves[0]
    assert stacked.shape[0] == cfg.num_hidden_layers
    np.testing.assert_allclose(
        stacked, _aux_by_hand(model, variables["params"], ids,
                              cfg.moe_experts), rtol=1e-5)


def test_llama_moe_cached_decode():
    """Cached generation with a MoE llama: the decode step feeds 1-token
    hidden states with the full-prompt attention mask — the layer must not
    try to reshape the mask onto the 1-token batch (regression)."""
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.utils.generate import generate

    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=32,
                      dtype="float32", moe_experts=2)
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 6)),
                      jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    out = generate(model, params, ids, max_new_tokens=4)
    assert out.shape == (2, 10)


def test_causal_lm_module_collects_moe_aux():
    """CausalLMModule.training_loss must fold the sowed load-balance loss
    into the objective (weighted by cfg.moe_aux_weight) and report it
    (regression: the sow used to be silently dropped)."""
    import argparse

    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from fengshen_tpu.trainer.modules import CausalLMModule

    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=16,
                      dtype="float32", moe_experts=4, moe_aux_weight=0.5)
    model = LlamaForCausalLM(cfg)
    module = CausalLMModule(argparse.Namespace(), model, cfg)
    ids = jnp.asarray(np.random.RandomState(2).randint(0, 64, (2, 8)),
                      jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    batch = {"input_ids": ids}
    loss, metrics = module.training_loss(params, batch,
                                         jax.random.PRNGKey(1))
    assert "aux_loss" in metrics
    aux = float(metrics["aux_loss"])
    np.testing.assert_allclose(
        aux, float(_aux_by_hand(model, params, ids, cfg.moe_experts).sum()),
        rtol=1e-5)
    # the weighted aux is part of the loss: recompute without it
    logits = model.apply({"params": params}, ids)
    from fengshen_tpu.parallel.cross_entropy import \
        vocab_parallel_cross_entropy
    ce, _ = vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:])
    np.testing.assert_allclose(float(loss), float(ce) + 0.5 * aux,
                               rtol=1e-5)
