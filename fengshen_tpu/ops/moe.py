"""Routed experts: top-k routing without dropped tokens.

No reference equivalent — the reference framework has no MoE. One layer
serves every routed-expert model here: the router runs in float32 over
ALL `num_experts` outputs, picks `top_k` of them per token, and the
`tokens x top_k` assignments are sorted by expert so that the experts
held here run as one grouped (ragged) matrix product over their stacked
`[E_held, ...]` tables — every token reaches every expert it picked, no
capacity, no `[T, E, C]` dispatch tensor. The published DeepSeek-V3
router (sigmoid scores, a selection-only correction bias, normalised
and scaled weights, a shared expert), the Switch router (softmax,
top-1) and Qwen's (softmax, top-k renormalised, a sigmoid-gated shared expert:
`shared_gate`) are settings of it.

`experts_held = (first, count)` is a chip's share of an expert-parallel
deployment (docs/sharding.md): the router keeps all `num_experts`
outputs and its `top_k`, the tables hold `count` experts, assignments to
experts outside `[first, first + count)` contribute nothing, and the
shared expert is computed wherever `shared_here` says (once over all
shares). On one chip the layer runs without its exchange; nothing here
stands in for absent chips.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from fengshen_tpu.parallel.mesh import EXPERT_AXIS

#: partition rules for the stacked expert tables ([E, in, out]) and router
MOE_PARTITION_RULES: list[tuple[str, P]] = [
    (r".*router/kernel", P(None, None)),
    (r".*experts_(gate|up)", P(EXPERT_AXIS, None, "tensor")),
    (r".*experts_down", P(EXPERT_AXIS, "tensor", None)),
]

#: device scopes the trace's operations carry (docs/observability.md)
ROUTE_SCOPE = "fstpu_moe_route"
EXPERTS_SCOPE = "fstpu_moe_experts"
SHARED_SCOPE = "fstpu_moe_shared"
#: collection each layer's picks are sowed under for whoever asks
#: (`mutable=["moe_stats"]`): "assignments", `[T, E]` int32, how many
#: of token t's `top_k` picks went to expert e (0 or 1)
STATS_COLLECTION = "moe_stats"


def load_balancing_loss(router_probs: jax.Array,
                        expert_index: jax.Array,
                        num_experts: int,
                        token_mask: jax.Array | None = None) -> jax.Array:
    """Switch aux loss: E * sum_e f_e * P_e, minimized at uniform routing
    (Switch Transformer eq. 4). router_probs [T, E] fp32; expert_index
    [T] int32; token_mask [T] (1 = real token) excludes pads from the
    routing statistics."""
    onehot = jax.nn.one_hot(expert_index, num_experts, dtype=jnp.float32)
    if token_mask is None:
        f = jnp.mean(onehot, axis=0)                            # [E]
        p = jnp.mean(router_probs, axis=0)                      # [E]
    else:
        tm = token_mask.astype(jnp.float32)[:, None]
        denom = jnp.maximum(tm.sum(), 1.0)
        f = (onehot * tm).sum(axis=0) / denom
        p = (router_probs * tm).sum(axis=0) / denom
    return num_experts * jnp.sum(f * p)


def route(scores: jax.Array, bias: Optional[jax.Array], top_k: int,
          norm_topk_prob: bool, scaling: float
          ) -> Tuple[jax.Array, jax.Array]:
    """(`[T, top_k]` expert ids, `[T, top_k]` float32 weights) from the
    router's `[T, E]` float32 scores. The `top_k` largest of `scores +
    bias` are picked; the weights are the picked entries of `scores`
    itself (the bias changes the pick, never the weight), divided by
    their sum + 1e-20 when `norm_topk_prob`, times `scaling`."""
    choice = scores if bias is None else scores + bias
    _, index = jax.lax.top_k(choice, top_k)
    weight = jnp.take_along_axis(scores, index, axis=-1)
    if norm_topk_prob:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return index.astype(jnp.int32), weight * scaling


def xla_grouped_swiglu(rows: jax.Array, w_gate: jax.Array,
                       w_up: jax.Array, w_down: jax.Array,
                       sizes: jax.Array) -> jax.Array:
    """`down(silu(gate(r)) * up(r))` of each row through its group's
    tables: `rows` `[assignments, hidden]` sorted by group, `sizes`
    `[count]` int32 rows a group, the tables `[count, ...]`. Three
    `jax.lax.ragged_dot` (on a TPU one native grouped matmul each);
    rows past the last group are not to be trusted (zeros on the CPU,
    undefined on the chip)."""
    gate = jax.lax.ragged_dot(rows, w_gate, sizes)
    up = jax.lax.ragged_dot(rows, w_up, sizes)
    return jax.lax.ragged_dot(nn.silu(gate) * up, w_down, sizes)


def grouped_swiglu(x: jax.Array, index: jax.Array, weight: jax.Array,
                   w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   first: int = 0) -> jax.Array:
    """`sum_k weight[t, k] * E_{index[t, k]}(x[t])` over the experts the
    `[count, ...]` tables hold (`first ...`), float32 `[T, H]`.

    The `T * top_k` assignments are sorted by expert (stable, so each
    expert sees its tokens in token order), the rows gathered once, and
    the three SwiGLU products run over the group sizes. The call's
    shape picks how (`ops.pallas.grouped_matmul._ineligible_reason`):
    the Mosaic grouped matmul that reads each touched table once (a
    prefill window's many rows an expert and a decode tick's row or
    two alike), else :func:`xla_grouped_swiglu` (any call under a
    device mesh or off the TPU, rows that are not whole tiles, tables
    that outgrow VMEM). Assignments to experts not held sort past the
    last group; their rows are zeroed rather than trusted."""
    # here, not at the top: the registry imports this module's xla form
    from fengshen_tpu.ops.pallas import grouped_matmul as kernel
    from fengshen_tpu.ops.pallas import resolve_dispatch
    tokens, top_k = index.shape
    count = w_gate.shape[0]
    local = index.reshape(-1) - first
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=count + 1)[:count].astype(jnp.int32)
    rows = x[order // top_k]
    impl = resolve_dispatch(
        "grouped_matmul",
        f"rows={tuple(rows.shape)}:{rows.dtype.name} "
        f"tables={tuple(w_gate.shape)}:{w_gate.dtype.name}",
        kernel._ineligible_reason(rows, w_gate))
    products = kernel.pallas_grouped_swiglu if impl == "pallas" \
        else xla_grouped_swiglu
    out = products(rows, w_gate, w_up, w_down, sizes)
    # back to the tokens, pick-major: ONE gather of the rows in the
    # products' own dtype into `[top_k, T, H]` (a pick a slab: no
    # `[T, top_k]` tile to re-lay where top_k is not whole sublanes),
    # then weight, mask and sum over the picks in one pass
    place = jnp.argsort(order).reshape(tokens, top_k).T
    picked, held = out[place], held.reshape(tokens, top_k)
    return sum(jnp.where(held[:, k, None], picked[k].astype(jnp.float32) *
                         weight[:, k, None], 0.0) for k in range(top_k))


class SwiGLU(nn.Module):
    """`down(silu(gate(x)) * up(x))`, no biases."""

    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    initializer_range: float = 0.02

    @nn.compact
    def __call__(self, x):
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name,
            kernel_init=nn.initializers.normal(self.initializer_range))
        h = nn.silu(dense(self.intermediate_size, "gate_proj")(x)) * \
            dense(self.intermediate_size, "up_proj")(x)
        return dense(self.hidden_size, "down_proj")(h)


class RoutedExperts(nn.Module):
    """Top-k routed SwiGLU experts (+ shared experts), drop-in for a
    dense MLP. Returns the layer's output; with `aux_loss` the Switch
    load-balancing term is sowed under ("losses", "moe_aux_loss"), and
    the picks always under (`STATS_COLLECTION`, "assignments")."""

    hidden_size: int
    intermediate_size: int            # one expert's width
    num_experts: int
    top_k: int = 1
    scoring: str = "softmax"          # softmax | sigmoid
    score_bias: bool = False          # e_score_correction_bias
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    n_shared_experts: int = 0
    #: (first, count) of the experts held here; None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    #: whether this share adds the shared experts (one share does)
    shared_here: bool = True
    #: the shared experts' output times `sigmoid(x w_sg)`, one learned
    #: `[hidden, 1]` gate a layer (Qwen's `shared_expert_gate`)
    shared_gate: bool = False
    aux_loss: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    initializer_range: float = 0.02

    @nn.compact
    def __call__(self, x: jax.Array,
                 token_mask: jax.Array | None = None) -> jax.Array:
        """x: [B, S, H]; token_mask: [B, S] (1 = real token) — pads
        give zeros and stay out of the aux statistics."""
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r}")
        batch, seq, hidden = x.shape
        E, F = self.num_experts, self.intermediate_size
        first, count = self.experts_held or (0, E)
        xt = x.reshape(batch * seq, hidden)
        init = nn.initializers.normal(self.initializer_range)

        with jax.named_scope(ROUTE_SCOPE):
            # float32 with every pass: on a TPU a float32 matmul takes
            # fewer bf16 passes unless asked, and a rounded score flips
            # picks
            logits = nn.Dense(
                E, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32, kernel_init=init,
                precision=jax.lax.Precision.HIGHEST,
                name="router")(xt.astype(jnp.float32))
            scores = jax.nn.sigmoid(logits) if self.scoring == "sigmoid" \
                else jax.nn.softmax(logits, axis=-1)
            bias = self.param("e_score_correction_bias",
                              nn.initializers.zeros, (E,), jnp.float32) \
                if self.score_bias else None
            index, weight = route(scores, bias, self.top_k,
                                  self.norm_topk_prob,
                                  self.routed_scaling_factor)
        self.sow(STATS_COLLECTION, "assignments",
                 jax.nn.one_hot(index, E, dtype=jnp.int32).sum(axis=1),
                 init_fn=lambda: None, reduce_fn=lambda _, new: new)
        tm = None if token_mask is None else \
            token_mask.reshape(-1).astype(jnp.float32)
        if self.aux_loss:
            self.sow("losses", "moe_aux_loss", load_balancing_loss(
                scores, index[:, 0], E, token_mask=tm))

        w_gate = self.param("experts_gate", init, (count, hidden, F),
                            self.param_dtype)
        w_up = self.param("experts_up", init, (count, hidden, F),
                          self.param_dtype)
        w_down = self.param("experts_down", init, (count, F, hidden),
                            self.param_dtype)
        with jax.named_scope(EXPERTS_SCOPE):
            out = grouped_swiglu(
                xt.astype(self.dtype), index, weight,
                w_gate.astype(self.dtype), w_up.astype(self.dtype),
                w_down.astype(self.dtype), first)
        if self.n_shared_experts and self.shared_here:
            with jax.named_scope(SHARED_SCOPE):
                shared = SwiGLU(
                    hidden, F * self.n_shared_experts, self.dtype,
                    self.param_dtype, self.initializer_range,
                    name="shared_experts")(xt).astype(jnp.float32)
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(nn.Dense(
                        1, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype, kernel_init=init,
                        name="shared_expert_gate")(xt).astype(jnp.float32))
                out = out + shared
        if tm is not None:
            out = out * tm[:, None]
        return out.reshape(batch, seq, hidden).astype(x.dtype)
