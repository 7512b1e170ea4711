"""Qwen3-Next in flax: two kinds of mixer in a period of four, routed
experts in every layer.

Stream: `x0 = Embed(ids)`; each layer, pre-norm, `x += Mixer(norm(x))`,
`x += Experts(norm(x))`; `logits = Head(norm(x))`, head untied. `norm`
is the ZERO-CENTRED RMSNorm, `x * rsqrt(mean(x^2) + eps) * (1 + w)` in
float32 (`w` starts at zero).

- `linear_attention` (Gated DeltaNet; `ops/gated_delta.py`): `[q | k |
  v | z] = x W_qkvz`, `[b | a] = x W_ba`; a depthwise causal
  convolution of kernel 4 and SiLU over `[q | k | v]`; `beta =
  sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)` a value head
  (float32); each key head serves `value heads / key heads`
  consecutive value heads; `q <- l2norm(q) / sqrt(Dk)`, `k <-
  l2norm(k)`; the gated delta rule's `[Dk, Dv]` state a value head;
  `out = W_o (rmsnorm_Dv(o; weight w, NOT 1 + w) * silu(z))`.
- `full_attention` (every `full_attention_interval`-th layer): `q_proj`
  gives each query head `[query | gate]`; zero-centred RMSNorm over a
  head on q and k; rotary on the first `partial_rotary_factor` of a
  head (rotate-half); grouped-query causal softmax; `out =
  W_o (attn * sigmoid(gate))`.
- experts (`ops/moe.py RoutedExperts`): softmax over ALL `num_experts`
  router outputs in float32, top-k renormalised, the experts held here
  (`experts_held`), plus `sigmoid(x w_sg) * shared(x)` where
  `shared_here`.

The cache lives at the model, not in the layers (as `models/sala`
keeps its own), in the leaf layout `serving/` builds for any model —
rows a token for the full layers, TWO states a lane for the linear ones:

    cached_key / cached_value  [Lf, B, max_len, 1, KVH * D]  (a row a token)
    cache_index                [Lf]  (`[Lf, B]` in the engine's pool)
    state_delta                [Ll, B, Hv, Dk, Dv]  float32
    state_conv                 [Ll, B, K - 1, conv_dim]  (the last K - 1
                                           inputs of the convolution)

(`Lf` full layers, `Ll` linear; a token's two KV heads are folded into
ONE row of 512 values, read as it lies: `ops/gated_attention.py`.) The
paged pool swaps the first two for `[Lf, num_blocks, block_size, 1,
KVH * D]` behind one `block_table [Lf, B, max_blocks]`. The layer loop
hands the stacks from layer to layer as values; each layer writes its
own index in place.

Three calls, told apart by what the cache shows (static under jit): no
cache (a plain forward); one token a lane onto any cache (the decode
tick: the linear layers step both states where `live` is set, the full
layers read through the `decode_attention` seam's folded entry); a
WINDOW of tokens onto a contiguous cache with a scalar cursor (prefill:
the first or a later window of a prompt — both states and the rows so
far are whatever the cache holds). Positions are physical: a lane is
filled from position 0 and padded on the RIGHT; `attention_mask`, over
cache positions, says which of a window's tokens are real (a padded
token enters neither state).

Layers are unrolled: a scan over periods would slice each layer's
three `[E, ...]` expert tables out of a stack, and XLA:TPU copies a
sliced table whole before its grouped matmul reads it (PERF.md, PR 26;
ROADMAP M3).

The multi-token-prediction module of the published checkpoint is not
built: it adds nothing to the main model's logits.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from fengshen_tpu.models.model_utils import (expert_share, head_rows,
                                             token_mask)
from fengshen_tpu.models.model_utils import write_rows as _write_rows
from fengshen_tpu.models.qwen3_next.configuration_qwen3_next import (
    FULL, LINEAR, Qwen3NextConfig)
from fengshen_tpu.ops.embedding import VocabParallelEmbed
from fengshen_tpu.ops.gated_attention import folded_prefill_walk
from fengshen_tpu.ops.gated_delta import (a_log_init, gated_delta_decode,
                                          gated_delta_prefill, l2norm,
                                          short_conv_decode,
                                          short_conv_prefill)
from fengshen_tpu.ops.moe import RoutedExperts
from fengshen_tpu.ops.norms import ZeroCentredRMSNorm
from fengshen_tpu.ops.pallas.decode_attention import folded_decode_attention
from fengshen_tpu.ops.rotary import apply_rotary_pos_emb
from fengshen_tpu.sharding import to_partition_rules, with_logical_constraint

__all__ = ["Qwen3NextModel", "Qwen3NextForCausalLM", "expert_share"]

#: logical axes of the parameters. The `[E, ...]` expert tables shard
#: over 'expert' (docs/sharding.md)
PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    ("embed_tokens/embedding", ("vocab", "embed")),
    (r"experts_(gate|up)", ("expert", None, "mlp")),
    (r"experts_down", ("expert", "mlp", None)),
    (r"(q_proj|k_proj|v_proj|in_proj_qkvz)/kernel", ("embed", "heads")),
    (r"(o_proj|out_proj)/kernel", ("heads", "embed")),
    (r"(gate_proj|up_proj)/kernel", ("embed", "mlp")),
    (r"down_proj/kernel", ("mlp", "embed")),
    ("lm_head/kernel", ("embed", "vocab")),
    ("norm", ("norm",)),
    (".*", (None,)),
]


def _dt(config: Qwen3NextConfig):
    return jnp.dtype(config.dtype)


class NextCache(NamedTuple):
    """The stacks the layer loop carries (module docstring). `start` is
    each lane's cursor when the call began: `[]` on a contiguous cache
    with a scalar cursor, else `[B]`."""

    k: jax.Array
    v: jax.Array
    table: Optional[jax.Array]
    delta: jax.Array
    conv: jax.Array
    start: jax.Array


def _dense(cfg: Qwen3NextConfig, feats: int, name: str):
    return nn.Dense(
        feats, use_bias=False, dtype=_dt(cfg),
        param_dtype=jnp.dtype(cfg.param_dtype),
        kernel_init=nn.initializers.normal(cfg.initializer_range), name=name)


def _no_window_on_a_pool(cache: NextCache, seq: int):
    if cache.start.ndim:
        raise ValueError(
            f"a window of {seq} tokens onto a pool of lanes: a recurrent "
            "state takes one token a lane a tick (a rejected draft cannot "
            "be rolled back out of it); prefill runs on a contiguous "
            "batch-1 cache")


class GatedDeltaNet(nn.Module):
    """`linear_attention`. Returns (output, cache)."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, hidden, attention_mask, cache: Optional[NextCache],
                 layer: int, live):
        cfg = self.config
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        Dk, Dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        taps = cfg.linear_conv_kernel_dim
        batch, seq, _ = hidden.shape
        qkvz = _dense(cfg, cfg.conv_dim + cfg.value_dim,
                      "in_proj_qkvz")(hidden)
        u, z = qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:]
        ba = _dense(cfg, 2 * Hv, "in_proj_ba")(hidden).astype(jnp.float32)
        w_conv = self.param(
            "conv1d", nn.initializers.normal(cfg.initializer_range),
            (taps, cfg.conv_dim), jnp.dtype(cfg.param_dtype))
        a_log = self.param("A_log", a_log_init, (Hv,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,),
                             jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., Hv:] + dt_bias)

        tick = cache is not None and seq == 1
        mask = None
        if cache is None:
            conv_state = jnp.zeros((batch, taps - 1, cfg.conv_dim), u.dtype)
            state = jnp.zeros((batch, Hv, Dk, Dv), jnp.float32)
            if attention_mask is not None:
                mask = attention_mask.astype(bool)
        else:
            conv_state, state = cache.conv[layer], cache.delta[layer]
            if not tick:
                _no_window_on_a_pool(cache, seq)
                mask = token_mask(attention_mask, cache.start, seq,
                                   cfg.max_position_embeddings)
        if tick:
            y, conv_state = short_conv_decode(u[:, 0], w_conv, conv_state,
                                              live)
            y = y[:, None]
        else:
            y, conv_state = short_conv_prefill(
                u, w_conv, conv_state,
                None if mask is None else mask.sum(-1))

        rep = Hv // Hk
        q = y[..., :cfg.key_dim].reshape(batch, seq, Hk, Dk)
        k = y[..., cfg.key_dim:2 * cfg.key_dim].reshape(batch, seq, Hk, Dk)
        v = y[..., 2 * cfg.key_dim:].reshape(batch, seq, Hv, Dv)
        q, k = l2norm(q) * Dk ** -0.5, l2norm(k)
        if tick:
            q, k = (jnp.repeat(x[:, 0], rep, axis=1) for x in (q, k))
            out, state = gated_delta_decode(q, k, v[:, 0], g[:, 0],
                                            beta[:, 0], state, live)
            out = out[:, None]
        else:
            # a key head's rows as they are: the prefill form reads them
            # for the value heads that share it
            out, state = gated_delta_prefill(q, k, v, g, beta, state, mask,
                                             chunk=cfg.delta_chunk)
        if cache is not None:
            cache = cache._replace(
                delta=cache.delta.at[layer].set(state),
                conv=cache.conv.at[layer].set(
                    conv_state.astype(cache.conv.dtype)))
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        # the gated norm: over a head, weight w (not 1 + w), times silu(z)
        o32 = out.astype(jnp.float32)
        o32 = o32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(o32), axis=-1, keepdims=True) +
            cfg.rms_norm_eps)
        scale = self.param("norm_scale", nn.initializers.ones, (Dv,),
                           jnp.float32)
        gate = jax.nn.silu(z.astype(jnp.float32)).reshape(
            batch, seq, Hv, Dv)
        out = (o32 * scale * gate).astype(_dt(cfg)).reshape(
            batch, seq, Hv * Dv)
        return _dense(cfg, cfg.hidden_size, "out_proj")(out), cache


class GatedAttention(nn.Module):
    """`full_attention`. Returns (output, cache)."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, hidden, position_ids, cache: Optional[NextCache],
                 layer: int):
        cfg = self.config
        H, G, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        batch, seq, _ = hidden.shape
        eps = cfg.rms_norm_eps
        qg = _dense(cfg, H * 2 * D, "q_proj")(hidden).reshape(
            batch, seq, H, 2 * D)
        q, gate = qg[..., :D], qg[..., D:]
        k = _dense(cfg, G * D, "k_proj")(hidden).reshape(batch, seq, G, D)
        v = _dense(cfg, G * D, "v_proj")(hidden).reshape(batch, seq, G, D)
        q = ZeroCentredRMSNorm(eps, name="q_norm")(q)
        k = ZeroCentredRMSNorm(eps, name="k_norm")(k)
        q, k = apply_rotary_pos_emb(q, k, position_ids,
                                    rotary_dim=cfg.rotary_dim,
                                    base=cfg.rope_theta)
        scale = D ** -0.5
        if cache is None:
            out = folded_prefill_walk(
                q, k.reshape(batch, seq, G * D), v.reshape(batch, seq, G * D),
                jnp.int32(0), scale=scale)
        elif seq == 1:
            cache = _write_rows(cache, layer, k=k, v=v)
            out = self._tick(q, cache, layer, scale)
        else:
            _no_window_on_a_pool(cache, seq)
            cache = _write_rows(cache, layer, k=k, v=v)
            lane = lambda x: x[layer].reshape(  # noqa: E731
                batch, -1, G * D)
            out = folded_prefill_walk(q, lane(cache.k), lane(cache.v),
                                      cache.start, scale=scale)
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        out = (out * jax.nn.sigmoid(gate)).reshape(batch, seq, H * D)
        return _dense(cfg, cfg.hidden_size, "o_proj")(out), cache

    def _tick(self, q, cache: NextCache, layer: int, scale: float):
        """One query a lane through the folded entry of the seam. No
        mask: positions are physical and every cached row is real (a
        lane is filled from 0, never left-padded)."""
        batch = q.shape[0]
        t = jnp.broadcast_to(cache.start, (batch,))
        if cache.table is not None:
            return folded_decode_attention(
                q, cache.k, cache.v, cache.table[layer], t, scale=scale,
                layer=layer)
        # a contiguous lane is whole blocks in a row: a free reshape and
        # a table that counts
        lanes, lane_len = cache.k.shape[1:3]
        block = math.gcd(lane_len, 128)
        per = lane_len // block
        pools = tuple(x.reshape((-1, block) + x.shape[3:])
                      for x in (cache.k, cache.v))
        table = (layer * lanes + jnp.arange(batch)[:, None]) * per + \
            jnp.arange(per)[None]
        return folded_decode_attention(q, *pools, table, t, scale=scale)


class Qwen3NextDecoderLayer(nn.Module):
    config: Qwen3NextConfig
    mixer: str

    @nn.compact
    def __call__(self, hidden, attention_mask, position_ids, cache, layer,
                 live):
        cfg = self.config
        eps = cfg.rms_norm_eps
        h = ZeroCentredRMSNorm(eps, name="input_layernorm")(hidden)
        if self.mixer == FULL:
            h, cache = GatedAttention(cfg, name="self_attn")(
                h, position_ids, cache, layer)
        else:
            h, cache = GatedDeltaNet(cfg, name="linear_attn")(
                h, attention_mask, cache, layer, live)
        hidden = hidden + h
        h = ZeroCentredRMSNorm(eps, name="post_attention_layernorm")(hidden)
        h = RoutedExperts(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            scoring="softmax", norm_topk_prob=cfg.norm_topk_prob,
            n_shared_experts=cfg.shared_expert_intermediate_size //
            cfg.moe_intermediate_size,
            experts_held=cfg.experts_held, shared_here=cfg.shared_here,
            shared_gate=True, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            initializer_range=cfg.initializer_range, name="mlp")(h)
        return hidden + h, cache


class Qwen3NextModel(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, live=None):
        del deterministic                       # no dropout anywhere
        cfg = self.config
        batch, seq = input_ids.shape
        kinds = cfg.layer_types
        n_full, n_linear = kinds.count(FULL), kinds.count(LINEAR)
        hidden = VocabParallelEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            name="embed_tokens")(input_ids)
        hidden = with_logical_constraint(hidden, ("batch", "seq", None))
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(seq)[None],
                                            (batch, seq))

        # the per-layer state this model declares (module docstring);
        # on the pass that creates the leaves nothing is cached yet
        cache = None
        if init_cache or self.has_variable("cache", "cached_key"):
            primed = self.has_variable("cache", "cached_key")
            if self.has_variable("cache", "cached_key_scale"):
                raise ValueError(
                    "this cache has no int8 form: the folded read takes "
                    "the rows as they lie; use kv_dtype='fp32'")
            rows = (n_full, batch, cfg.max_position_embeddings, 1,
                    cfg.num_key_value_heads * cfg.head_dim)
            k_var = self.variable("cache", "cached_key", jnp.zeros, rows,
                                  _dt(cfg))
            v_var = self.variable("cache", "cached_value", jnp.zeros, rows,
                                  _dt(cfg))
            index_var = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((n_full,), jnp.int32))
            d_var = self.variable(
                "cache", "state_delta", jnp.zeros,
                (n_linear, batch, cfg.linear_num_value_heads,
                 cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                jnp.float32)
            c_var = self.variable(
                "cache", "state_conv", jnp.zeros,
                (n_linear, batch, cfg.linear_conv_kernel_dim - 1,
                 cfg.conv_dim), _dt(cfg))
            if primed:
                table = self.get_variable("cache", "block_table") \
                    if self.has_variable("cache", "block_table") else None
                cache = NextCache(k_var.value, v_var.value, table,
                                  d_var.value, c_var.value,
                                  index_var.value[0])

        seen = {FULL: 0, LINEAR: 0}
        for i, kind in enumerate(kinds):
            hidden, cache = Qwen3NextDecoderLayer(
                cfg, kind, name=f"layers_{i}")(
                hidden, attention_mask, position_ids, cache, seen[kind],
                live)
            seen[kind] += 1
        if cache is not None:
            k_var.value, v_var.value = cache.k, cache.v
            d_var.value, c_var.value = cache.delta, cache.conv
            index_var.value = index_var.value + seq
        return ZeroCentredRMSNorm(cfg.rms_norm_eps, name="norm")(hidden)


class Qwen3NextForCausalLM(nn.Module):
    """Untied LM head on the stack; the serving engine's cache contract
    (`init_cache`, a mutable "cache" collection) as `LlamaForCausalLM`,
    and `live`: the decode tick's `[B]` mask of the lanes whose states
    may move."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, live=None,
                 logits_row=None):
        """`logits_row`: the one row whose logits the caller keeps
        (`[B, 1, V]`), or None for every row's (`head_rows`)."""
        cfg = self.config
        hidden = Qwen3NextModel(cfg, name="model")(
            input_ids, attention_mask, position_ids, init_cache,
            deterministic, live)
        return _dense(cfg, cfg.vocab_size, "lm_head")(
            head_rows(hidden, logits_row))

    def init_params(self, rng, seq_len: int = 8):
        return self.init(rng, jnp.zeros((1, seq_len), jnp.int32))["params"]

    def partition_rules(self):
        return to_partition_rules(PARAM_LOGICAL_AXES)
