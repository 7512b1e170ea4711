"""`ops/moe.py RoutedExperts`: top-k routing without dropped tokens.

The router against hand-worked cases, the grouped (ragged) product
against a per-token loop over dense experts, no token dropped however
the routing collapses, and the `experts_held` shares of a layer adding
up to the whole of it.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops import moe
from fengshen_tpu.ops.moe import (RoutedExperts, grouped_swiglu,
                                  load_balancing_loss, route)

JOYAI = dict(scoring="sigmoid", score_bias=True, norm_topk_prob=True,
             routed_scaling_factor=2.5, n_shared_experts=1)


def _layer(**kw):
    base = dict(hidden_size=16, intermediate_size=8, num_experts=8,
                top_k=2, dtype=jnp.float32)
    base.update(kw)
    return RoutedExperts(**base)


def _init(layer, shape=(2, 5, 16), seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape)
    params = layer.init(jax.random.PRNGKey(seed + 1), x)["params"]
    if "e_score_correction_bias" in params:
        params = dict(params, e_score_correction_bias=0.3 * jax.random.normal(
            jax.random.PRNGKey(seed + 2), (layer.num_experts,)))
    return x, params


def _dense_loop(layer, params, x):
    """Every token through each of its picks, one dense expert at a
    time: the layer's mathematics with no sort and no grouping."""
    xt = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = xt @ np.asarray(params["router"]["kernel"], np.float64)
    if layer.scoring == "sigmoid":
        scores = 1 / (1 + np.exp(-logits))
    else:
        e = np.exp(logits - logits.max(-1, keepdims=True))
        scores = e / e.sum(-1, keepdims=True)
    choice = scores + np.asarray(
        params.get("e_score_correction_bias", np.zeros(scores.shape[-1])))
    out = np.zeros_like(xt)
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    for t in range(xt.shape[0]):
        picks = np.argsort(-choice[t], kind="stable")[:layer.top_k]
        w = scores[t, picks]
        if layer.norm_topk_prob:
            w = w / (w.sum() + 1e-20)
        for e, we in zip(picks, w * layer.routed_scaling_factor):
            g, u, d = (np.asarray(params[k][e], np.float64) for k in
                       ("experts_gate", "experts_up", "experts_down"))
            out[t] += we * ((silu(xt[t] @ g) * (xt[t] @ u)) @ d)
    if layer.n_shared_experts:
        sh = {k: np.asarray(v["kernel"], np.float64)
              for k, v in params["shared_experts"].items()}
        out += (silu(xt @ sh["gate_proj"]) * (xt @ sh["up_proj"])) @ \
            sh["down_proj"]
    return out.reshape(x.shape)


# ---- the router -----------------------------------------------------------

def test_bias_changes_the_pick_not_the_weight():
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2]])
    bias = jnp.asarray([0.0, 0.0, 1.0, 0.0])
    index, weight = route(scores, bias, 2, False, 1.0)
    # 0.1 + 1.0 wins the first pick; its weight is the unbiased 0.1
    assert index.tolist() == [[2, 0]]
    np.testing.assert_allclose(np.asarray(weight), [[0.1, 0.9]], rtol=1e-6)
    index, _ = route(scores, None, 2, False, 1.0)
    assert index.tolist() == [[0, 1]]


def test_weights_are_normalised_then_scaled_by_two_and_a_half():
    scores = jnp.asarray([[0.6, 0.2, 0.1, 0.05], [0.1, 0.1, 0.4, 0.4]])
    index, weight = route(scores, jnp.zeros(4), 2, True, 2.5)
    assert index.tolist() == [[0, 1], [2, 3]]
    np.testing.assert_allclose(
        np.asarray(weight),
        [[2.5 * 0.6 / 0.8, 2.5 * 0.2 / 0.8], [1.25, 1.25]], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-6)


def test_unnormalised_weights_are_the_scores():
    scores = jnp.asarray([[0.6, 0.2, 0.1, 0.05]])
    _, weight = route(scores, None, 3, False, 1.0)
    np.testing.assert_allclose(np.asarray(weight), [[0.6, 0.2, 0.1]],
                               rtol=1e-6)


def test_the_router_runs_in_float32_whatever_the_layer_computes_in():
    layer = _layer(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **JOYAI)
    x, params = _init(layer)
    assert params["router"]["kernel"].dtype == jnp.float32
    assert params["e_score_correction_bias"].dtype == jnp.float32
    assert params["experts_gate"].dtype == jnp.bfloat16
    out = layer.apply({"params": params}, x.astype(jnp.bfloat16))
    assert out.dtype == jnp.bfloat16


# ---- the grouped product --------------------------------------------------

@pytest.mark.parametrize("setting", [
    dict(scoring="softmax", top_k=1), dict(scoring="softmax", top_k=3),
    dict(top_k=2, **JOYAI), dict(top_k=8, **JOYAI)],
    ids=["switch", "softmax-top3", "joyai-top2", "joyai-all8"])
def test_sorted_grouped_product_equals_the_dense_loop(setting):
    layer = _layer(**setting)
    x, params = _init(layer, shape=(3, 7, 16))
    got = layer.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got), _dense_loop(layer, params, x),
                               atol=2e-5)


def test_single_expert_is_dense_swiglu():
    layer = _layer(num_experts=1, top_k=1)
    x, params = _init(layer, shape=(2, 6, 16))
    out = layer.apply({"params": params}, x)
    wg, wu, wd = (params[k][0] for k in
                  ("experts_gate", "experts_up", "experts_down"))
    ref = (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("top_k", [1, 2])
def test_no_token_is_dropped_when_every_token_picks_the_same_experts(top_k):
    """All 40 tokens on experts 0 (and 1): a capacity would drop most
    of them; here every token gets its experts' output."""
    layer = _layer(top_k=top_k, **JOYAI)
    x, params = _init(layer, shape=(4, 10, 16))
    bias = jnp.zeros(8).at[0].set(10.0).at[1].set(5.0)
    params = dict(params, e_score_correction_bias=bias)
    out, sown = layer.apply({"params": params}, x, mutable=["moe_stats"])
    picks = np.asarray(sown["moe_stats"]["assignments"])
    assert picks.shape == (40, 8)
    assert picks[:, :top_k].sum() == 40 * top_k and picks.sum() == 40 * top_k
    np.testing.assert_allclose(np.asarray(out), _dense_loop(layer, params, x),
                               atol=2e-5)
    no_shared = layer.clone(n_shared_experts=0)
    routed = no_shared.apply(
        {"params": {k: v for k, v in params.items()
                    if k != "shared_experts"}}, x)
    assert np.all(np.abs(np.asarray(routed)).max(-1) > 0)


def test_assignments_to_experts_not_held_contribute_nothing():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 16))
    tables = [0.1 * jax.random.normal(jax.random.PRNGKey(i), s)
              for i, s in enumerate([(8, 16, 8), (8, 16, 8), (8, 8, 16)])]
    index = jnp.asarray([[0, 5], [5, 6], [1, 2], [7, 0], [3, 4], [6, 7]])
    weight = jnp.full((6, 2), 0.5)
    whole = grouped_swiglu(x, index, weight, *tables)
    low = grouped_swiglu(x, index, weight, *[t[:3] for t in tables], first=0)
    mid = grouped_swiglu(x, index, weight, *[t[3:5] for t in tables], first=3)
    high = grouped_swiglu(x, index, weight, *[t[5:] for t in tables], first=5)
    np.testing.assert_allclose(np.asarray(low + mid + high),
                               np.asarray(whole), atol=1e-6)
    # token 2 picked experts 1 and 2 only: nothing of it in the others
    assert np.all(np.asarray(mid)[2] == 0) and np.all(np.asarray(high)[2] == 0)


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_shares_of_a_layer_add_up_to_the_layer(shares):
    """Each share routes over all 8 experts, holds 8 / shares of them
    and computes their part; one share adds the shared expert."""
    layer = _layer(**JOYAI)
    x, params = _init(layer, shape=(2, 9, 16))
    whole = layer.apply({"params": params}, x)
    count = 8 // shares
    total = 0
    for i in range(shares):
        share = layer.clone(experts_held=(i * count, count),
                            shared_here=i == 0)
        held = dict(params, **{k: params[k][i * count:(i + 1) * count]
                               for k in ("experts_gate", "experts_up",
                                         "experts_down")})
        if i:
            held.pop("shared_experts")
        total = total + share.apply({"params": held}, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-6)


def test_pad_tokens_give_zeros_and_stay_out_of_the_aux_loss():
    layer = _layer(scoring="softmax", top_k=1, aux_loss=True)
    x, params = _init(layer, shape=(1, 16, 16))
    mask = jnp.asarray([[1] * 8 + [0] * 8], jnp.int32)
    out_m, sown_m = layer.apply({"params": params}, x, token_mask=mask,
                                mutable=["losses"])
    np.testing.assert_allclose(np.asarray(out_m[0, 8:]), 0.0)
    out_u, sown_u = layer.apply({"params": params}, x[:, :8],
                                mutable=["losses"])
    np.testing.assert_allclose(np.asarray(out_m[0, :8]), np.asarray(out_u[0]),
                               atol=1e-6)
    (aux_m,), (aux_u,) = (sown["losses"]["moe_aux_loss"]
                          for sown in (sown_m, sown_u))
    np.testing.assert_allclose(float(aux_m), float(aux_u), atol=1e-6)


def test_load_balancing_loss_values():
    T, E = 64, 4
    probs = jnp.full((T, E), 1.0 / E)
    idx = jnp.asarray(np.arange(T) % E, jnp.int32)
    np.testing.assert_allclose(
        float(load_balancing_loss(probs, idx, E)), 1.0, atol=1e-6)
    probs = jnp.zeros((T, E)).at[:, 0].set(1.0)
    idx = jnp.zeros((T,), jnp.int32)
    np.testing.assert_allclose(
        float(load_balancing_loss(probs, idx, E)), float(E), atol=1e-6)


def test_gradients_reach_router_experts_and_shared_expert():
    layer = _layer(**JOYAI)
    x, params = _init(layer)
    grads = jax.grad(lambda p: jnp.sum(
        layer.apply({"params": p}, x) ** 2))(params)
    for name in ("experts_gate", "experts_up", "experts_down"):
        assert float(jnp.abs(grads[name]).sum()) > 0
    assert float(jnp.abs(grads["router"]["kernel"]).sum()) > 0
    assert float(jnp.abs(
        grads["shared_experts"]["down_proj"]["kernel"]).sum()) > 0


def test_no_capacity_and_no_dispatch_tensor_are_left():
    source = inspect.getsource(moe)
    assert "capacity" not in source.replace("no\ncapacity", "").replace(
        "no capacity", "")
    assert not hasattr(moe, "SwitchMoE")
    assert "tec," not in source and "ech" not in source
