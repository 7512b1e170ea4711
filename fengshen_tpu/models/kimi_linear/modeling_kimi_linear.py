"""Kimi-Linear in flax: two kinds of mixer, three to one, routed experts
in every layer after the first.

Stream: `x0 = Embed(ids)`; each layer, pre-norm, `x += Mixer(norm(x))`,
`x += MLP(norm(x))`; `logits = Head(norm(x))`, head untied. `norm` is
RMSNorm with a learned gain, eps `rms_norm_eps`.

- `kda` (Kimi Delta Attention, arXiv:2510.26692; `ops/gated_delta.py`
  with its gate PER KEY CHANNEL): `[q | k | v] = x W_qkv`, a depthwise
  causal convolution of kernel 4 and SiLU over the `3 H D` channels;
  per head `q <- l2norm(q) / sqrt(D)`, `k <- l2norm(k)`; `g = -exp(A_log
  [head]) softplus((x W_fa) W_fb + dt_bias)` in `R^{H x D}`, float32,
  one log-decay a key channel; `beta = sigmoid(x W_b)` a head; the
  delta rule's `[D, D]` state a head, row `d` decayed by `exp(g[d])`;
  `out = W_o (rmsnorm_D(o) * w * sigmoid((x W_ga) W_gb))`. The two
  gates are low-rank (`H D` outputs through `kda_head_dim` values).
- `full_attention` (latent attention WITHOUT positions): `q = x W_q`
  (full rank, no norm) -> heads of `[q_nope | q_shared]`; `[c | k_shared]
  = x W_kva`, `c <- RMSNorm(c)`; nothing is rotated; head `h`: `[k_nope
  | v] = c W_kvb[h]`, `k = [k_nope | k_shared]`; causal softmax, scale
  `(dn + dr)^-0.5`; `W_o`. The cached row is `[c | k_shared | zeros]`,
  `KimiLinearConfig.latent_width` values a token, JoyAI's row: a tick
  reads it ABSORBED through the `decode_attention` seam's latent entry,
  a window of a prompt in the FULL form through
  `ops/latent_attention.latent_prefill_attention` (the lane's rows
  expanded a block of keys at a time, onto the carried batch-1 cache:
  a Mosaic kernel where the window's shape tiles, else the walk).
- MLP: layer 0 a dense SwiGLU; after it `ops/moe.py RoutedExperts`:
  sigmoid scores over ALL `num_experts` router outputs in float32, the
  `num_experts_per_token` largest of `scores + bias` picked, their
  weights renormalised and scaled, the experts held here
  (`experts_held`), plus one shared expert where `shared_here`.

The cache lives at the model, not in the layers, in the leaf layout
`serving/` builds for any model: rows a token for the latent layers,
TWO states a lane for the KDA ones:

    cached_latent  [Lf, B, max_len, 1, latent_width]  (a row a token)
    cache_index    [Lf]  (`[Lf, B]` in the engine's pool)
    state_delta    [Lk, B, H, D, D]  float32
    state_conv     [Lk, B, K - 1, 3 H D]  (the last K - 1 inputs of the
                                           convolution)

(`Lf` latent layers, `Lk` KDA.) The paged pool swaps the first for
`[Lf, num_blocks, block_size, 1, latent_width]` behind one `block_table
[Lf, B, max_blocks]`. The layer loop hands the stacks from layer to
layer as values; each layer writes its own index in place.

Three calls, told apart by what the cache shows (static under jit): no
cache (a plain forward); one token a lane onto any cache (the decode
tick: the KDA layers step both states where `live` is set, the latent
layers read absorbed); a WINDOW of tokens onto a contiguous cache with a
scalar cursor (prefill: the first or a later window of a prompt). The
model has no positions anywhere; a lane is filled from position 0 and
padded on the RIGHT, and `attention_mask`, over cache positions, says
which of a window's tokens are real (a padded token enters neither
state).

Layers are unrolled, as in the other expert models (ROADMAP M3).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from fengshen_tpu.models.kimi_linear.configuration_kimi_linear import (
    FULL, KDA, KimiLinearConfig)
from fengshen_tpu.models.model_utils import (LatentCache, expert_share,
                                             head_rows, token_mask,
                                             write_latent)
from fengshen_tpu.ops.embedding import VocabParallelEmbed
from fengshen_tpu.ops.gated_delta import (a_log_init, gated_delta_decode,
                                          gated_delta_prefill, l2norm,
                                          short_conv_decode,
                                          short_conv_prefill)
from fengshen_tpu.ops.latent_attention import (RawKernel,
                                               latent_prefill_attention)
from fengshen_tpu.ops.moe import RoutedExperts, SwiGLU
from fengshen_tpu.ops.norms import RMSNorm
from fengshen_tpu.ops.pallas.decode_attention import mla_decode_attention
from fengshen_tpu.sharding import to_partition_rules, with_logical_constraint

__all__ = ["KimiLinearModel", "KimiLinearForCausalLM", "expert_share"]

#: logical axes of the parameters. The `[E, ...]` expert tables shard
#: over 'expert' (docs/sharding.md)
PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    ("embed_tokens/embedding", ("vocab", "embed")),
    (r"experts_(gate|up)", ("expert", None, "mlp")),
    (r"experts_down", ("expert", "mlp", None)),
    (r"(q_proj|qkv_proj)/kernel", ("embed", "heads")),
    (r"(f_b_proj|g_b_proj|kv_b_proj)/kernel", (None, "heads")),
    (r"o_proj/kernel", ("heads", "embed")),
    (r"(gate_proj|up_proj)/kernel", ("embed", "mlp")),
    (r"down_proj/kernel", ("mlp", "embed")),
    ("lm_head/kernel", ("embed", "vocab")),
    ("norm", ("norm",)),
    (".*", (None,)),
]


def _dt(config: KimiLinearConfig):
    return jnp.dtype(config.dtype)


class KimiCache(NamedTuple):
    """The stacks the layer loop carries (module docstring). `start` is
    each lane's cursor when the call began: `[]` on a contiguous cache
    with a scalar cursor, else `[B]`."""

    kv: jax.Array
    table: Optional[jax.Array]
    delta: jax.Array
    conv: jax.Array
    start: jax.Array


def _dense(cfg: KimiLinearConfig, feats: int, name: str):
    return nn.Dense(
        feats, use_bias=False, dtype=_dt(cfg),
        param_dtype=jnp.dtype(cfg.param_dtype),
        kernel_init=nn.initializers.normal(cfg.initializer_range), name=name)


def _no_window_on_a_pool(cache: KimiCache, seq: int):
    if cache.start.ndim:
        raise ValueError(
            f"a window of {seq} tokens onto a pool of lanes: a recurrent "
            "state takes one token a lane a tick, and the latent rows of "
            "a window are read in the full form; prefill runs on a "
            "contiguous batch-1 cache")


class KimiDeltaAttention(nn.Module):
    """`kda`. Returns (output, cache)."""

    config: KimiLinearConfig

    @nn.compact
    def __call__(self, hidden, attention_mask, cache: Optional[KimiCache],
                 layer: int, live):
        cfg = self.config
        H, D, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel
        width = cfg.kda_dim
        batch, seq, _ = hidden.shape
        u = _dense(cfg, 3 * width, "qkv_proj")(hidden)
        w_conv = self.param(
            "conv1d", nn.initializers.normal(cfg.initializer_range),
            (taps, 3 * width), jnp.dtype(cfg.param_dtype))
        a_log = self.param("A_log", a_log_init, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (width,),
                             jnp.float32)
        # the forget gate, one log-decay a key channel, float32
        f = _dense(cfg, width, "f_b_proj")(
            _dense(cfg, D, "f_a_proj")(hidden)).astype(jnp.float32)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (f + dt_bias).reshape(batch, seq, H, D))
        beta = jax.nn.sigmoid(
            _dense(cfg, H, "b_proj")(hidden).astype(jnp.float32))
        out_gate = jax.nn.sigmoid(_dense(cfg, width, "g_b_proj")(
            _dense(cfg, D, "g_a_proj")(hidden)).astype(jnp.float32))

        tick = cache is not None and seq == 1
        mask = None
        if cache is None:
            conv_state = jnp.zeros((batch, taps - 1, 3 * width), u.dtype)
            state = jnp.zeros((batch, H, D, D), jnp.float32)
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

        q, k, v = (y[..., i * width:(i + 1) * width].reshape(
            batch, seq, H, D) for i in range(3))
        q, k = l2norm(q) * D ** -0.5, l2norm(k)
        if tick:
            out, state = gated_delta_decode(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state, live)
            out = out[:, None]
        else:
            out, state = gated_delta_prefill(q, k, v, g, beta, state, mask,
                                             chunk=cfg.delta_chunk)
        if cache is not None:
            cache = cache._replace(
                delta=cache.delta.at[layer].set(state),
                conv=cache.conv.at[layer].set(
                    conv_state.astype(cache.conv.dtype)))
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        # the gated norm: over a head, weight w, times sigmoid(gate)
        o32 = out.astype(jnp.float32)
        o32 = o32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(o32), axis=-1, keepdims=True) +
            cfg.rms_norm_eps)
        scale = self.param("o_norm_scale", nn.initializers.ones, (D,),
                           jnp.float32)
        out = (o32 * scale * out_gate.reshape(batch, seq, H, D)) \
            .astype(_dt(cfg)).reshape(batch, seq, width)
        return _dense(cfg, cfg.hidden_size, "o_proj")(out), cache


class KimiLatentAttention(nn.Module):
    """`full_attention`: latent attention without positions, full and
    absorbed (module docstring). Returns (output, cache)."""

    config: KimiLinearConfig

    @nn.compact
    def __call__(self, hidden, attention_mask, cache: Optional[KimiCache],
                 layer: int):
        cfg = self.config
        H, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        batch, seq, _ = hidden.shape
        q = _dense(cfg, H * (dn + dr), "q_proj")(hidden).reshape(
            batch, seq, H, dn + dr)
        q_nope, q_shared = q[..., :dn], q[..., dn:]
        ckv = _dense(cfg, rank + dr, "kv_a_proj_with_mqa")(hidden)
        c_kv = RMSNorm(epsilon=cfg.rms_norm_eps,
                       name="kv_a_layernorm")(ckv[..., :rank])
        pad = jnp.zeros((batch, seq, cfg.latent_width - rank - dr),
                        c_kv.dtype)
        rows = jnp.concatenate([c_kv, ckv[..., rank:], pad], axis=-1)
        w_kvb = RawKernel(
            (rank, H * (dn + dv)), _dt(cfg), jnp.dtype(cfg.param_dtype),
            cfg.initializer_range, name="kv_b_proj")().reshape(
            rank, H, dn + dv)
        scale = (dn + dr) ** -0.5

        if cache is not None and seq == 1:
            # the tick: the row in at the cursor, the read absorbed
            index = jnp.broadcast_to(
                cache.start, cache.kv.shape[:1] + cache.start.shape)
            latent, valid = write_latent(
                LatentCache(cache.kv, index, cache.table), rows, layer,
                attention_mask)
            cache = cache._replace(kv=latent.kv)
            out = mla_decode_attention(
                jnp.einsum("bshd,chd->bshc", q_nope, w_kvb[..., :dn]),
                q_shared, cache.kv, valid, scale=scale,
                block_table=None if cache.table is None
                else cache.table[layer], layer=layer)
            out = jnp.einsum("bshc,chd->bshd", out, w_kvb[..., dn:])
        else:
            start, lane = jnp.int32(0), rows
            if cache is not None:
                _no_window_on_a_pool(cache, seq)
                start = cache.start
                cache = cache._replace(kv=jax.lax.dynamic_update_slice(
                    cache.kv, rows[None, :, :, None].astype(cache.kv.dtype),
                    (layer, 0, start, 0, 0)))
                lane = cache.kv[layer][:, :, 0]            # [B, T, R]
            out = latent_prefill_attention(q_nope, q_shared, lane, w_kvb,
                                           start, scale=scale)
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        out = out.reshape(batch, seq, H * dv)
        return _dense(cfg, cfg.hidden_size, "o_proj")(out), cache


class KimiDecoderLayer(nn.Module):
    config: KimiLinearConfig
    mixer: str
    #: the leading layer's feed-forward is a dense SwiGLU
    dense_mlp: bool = False

    @nn.compact
    def __call__(self, hidden, attention_mask, cache, layer, live):
        cfg = self.config
        eps = cfg.rms_norm_eps
        h = RMSNorm(epsilon=eps, name="input_layernorm")(hidden)
        if self.mixer == FULL:
            h, cache = KimiLatentAttention(cfg, name="self_attn")(
                h, attention_mask, cache, layer)
        else:
            h, cache = KimiDeltaAttention(cfg, name="self_attn")(
                h, attention_mask, cache, layer, live)
        hidden = hidden + h
        h = RMSNorm(epsilon=eps, name="post_attention_layernorm")(hidden)
        kw = dict(dtype=_dt(cfg), param_dtype=jnp.dtype(cfg.param_dtype),
                  initializer_range=cfg.initializer_range, name="mlp")
        if self.dense_mlp:
            h = SwiGLU(cfg.hidden_size, cfg.intermediate_size, **kw)(h)
        else:
            h = RoutedExperts(
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.moe_intermediate_size,
                num_experts=cfg.num_experts,
                top_k=cfg.num_experts_per_token, scoring="sigmoid",
                score_bias=True, norm_topk_prob=cfg.moe_renormalize,
                routed_scaling_factor=cfg.routed_scaling_factor,
                n_shared_experts=cfg.num_shared_experts,
                experts_held=cfg.experts_held, shared_here=cfg.shared_here,
                **kw)(h)
        return hidden + h, cache


class KimiLinearModel(nn.Module):
    config: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, live=None):
        del deterministic, position_ids   # no dropout, no positions
        cfg = self.config
        batch, seq = input_ids.shape
        kinds = cfg.layer_types
        n_full, n_kda = kinds.count(FULL), kinds.count(KDA)
        hidden = VocabParallelEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            name="embed_tokens")(input_ids)
        hidden = with_logical_constraint(hidden, ("batch", "seq", None))

        # the per-layer state this model declares (module docstring);
        # on the pass that creates the leaves nothing is cached yet
        cache = None
        if init_cache or self.has_variable("cache", "cached_latent"):
            primed = self.has_variable("cache", "cached_latent")
            if self.has_variable("cache", "cached_latent_scale"):
                raise ValueError(
                    "the latent cache (cached_latent) has no int8 form: "
                    "one absmax scale over a row would mix the normed "
                    "latent with the shared key; use kv_dtype='fp32'")
            kv_var = self.variable(
                "cache", "cached_latent", jnp.zeros,
                (n_full, batch, cfg.max_position_embeddings, 1,
                 cfg.latent_width), _dt(cfg))
            index_var = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((n_full,), jnp.int32))
            d_var = self.variable(
                "cache", "state_delta", jnp.zeros,
                (n_kda, batch, cfg.kda_heads, cfg.kda_head_dim,
                 cfg.kda_head_dim), jnp.float32)
            c_var = self.variable(
                "cache", "state_conv", jnp.zeros,
                (n_kda, batch, cfg.conv_kernel - 1, 3 * cfg.kda_dim),
                _dt(cfg))
            if primed:
                table = self.get_variable("cache", "block_table") \
                    if self.has_variable("cache", "block_table") else None
                cache = KimiCache(kv_var.value, table, d_var.value,
                                  c_var.value, index_var.value[0])

        seen = {FULL: 0, KDA: 0}
        for i, kind in enumerate(kinds):
            hidden, cache = KimiDecoderLayer(
                cfg, kind, dense_mlp=i < cfg.first_k_dense_replace,
                name=f"layers_{i}")(
                hidden, attention_mask, cache, seen[kind], live)
            seen[kind] += 1
        if cache is not None:
            kv_var.value = cache.kv
            d_var.value, c_var.value = cache.delta, cache.conv
            index_var.value = index_var.value + seq
        return RMSNorm(epsilon=cfg.rms_norm_eps, name="norm")(hidden)


class KimiLinearForCausalLM(nn.Module):
    """Untied LM head on the stack; the serving engine's cache contract
    (`init_cache`, a mutable "cache" collection) as `LlamaForCausalLM`,
    and `live`: the decode tick's `[B]` mask of the lanes whose states
    may move."""

    config: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, live=None,
                 logits_row=None):
        """`logits_row`: the one row whose logits the caller keeps
        (`[B, 1, V]`), or None for every row's (`head_rows`)."""
        cfg = self.config
        hidden = KimiLinearModel(cfg, name="model")(
            input_ids, attention_mask, position_ids, init_cache,
            deterministic, live)
        return _dense(cfg, cfg.vocab_size, "lm_head")(
            head_rows(hidden, logits_row))

    def init_params(self, rng, seq_len: int = 8):
        return self.init(rng, jnp.zeros((1, seq_len), jnp.int32))["params"]

    def partition_rules(self):
        return to_partition_rules(PARAM_LOGICAL_AXES)
