"""JoyAI-LLM-Flash in flax: the published DeepSeek-V3 layer.

Pre-norm residual blocks of multi-head latent attention (MLA) and a
feed-forward that is a dense SwiGLU in layer 0 and sigmoid-routed
experts with a shared expert after it (`ops/moe.py RoutedExperts`).

The cache is ONE latent row a token a layer, `[c_kv (after its norm) |
k_rope (after RoPE) | zeros]`, `kv_lora_rank + qk_rope_head_dim` values
shared by every head and padded to whole lanes
(`JoyAIConfig.latent_width`) — no `cached_value`. It lives at the
model, not in the layers, as one `[L, ...]` stack with the cursors
(and, paged, the block tables) beside it, the same leaf layout
`serving/` builds for any model: `cached_latent [L, B, max_len, 1, R]`
or `[L, num_blocks, block_size, 1, R]`, `cache_index [L]` / `[L, B]`,
`block_table [L, B, max_blocks]`. The layer loop hands the stack from
layer to layer as a value; each layer scatters its step's rows into it
in place at its own index and reads through `layer`; no layer's pool is
sliced out or written back.

The layers are unrolled. A scan over the expert layers (layer 0
differs and would stay outside it) slices each layer's three `[E, ...]`
expert tables out of their `[L - 1, E, ...]` stacks, and XLA:TPU copies
a sliced table whole before its grouped matmul reads it (9.7 GB a tick
at four layers; PERF.md, PR 26). Until the scan body is handed the
stacks and the layer index, so that the grouped matmul reads `w[layer]`
in place as the cache is read, there is no scanned path (ROADMAP M3).

Attention has two forms of the same mathematics, chosen by what the
code can observe: a cache read by a window of at most `ABSORBED_WINDOW`
queries (the decode tick, a speculative verify) runs ABSORBED — the
no-position query is multiplied into the latent space and the heads
attend over the cached rows themselves through the
`decode_attention` seam's latent entry; everything else (no cache, the
prompt on its contiguous batch-1 cache) runs the FULL form through
`ops/latent_attention.latent_prefill_attention`: the rows from 0 to the
window's last query expanded into per-head keys and values a block at
a time (a Mosaic kernel where the shapes tile, else the `jax.numpy`
walk), a left-padded prompt's padding masked as keys.

Parameter names follow HF's `DeepseekV3ForCausalLM` (see convert.py).
The multi-token-prediction module (`num_nextn_predict_layers`) is read
and not built: it adds nothing to the main model's logits.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from flax import linen as nn

from fengshen_tpu.models.joyai.configuration_joyai import JoyAIConfig
from fengshen_tpu.models.llama.modeling_llama import LlamaMLP
from fengshen_tpu.models.model_utils import (LatentCache,  # noqa: F401
                                             expert_share, head_rows,
                                             key_mask, write_latent)
from fengshen_tpu.ops.embedding import VocabParallelEmbed
from fengshen_tpu.ops.latent_attention import latent_prefill_attention
from fengshen_tpu.ops.moe import RoutedExperts
from fengshen_tpu.ops.norms import RMSNorm
from fengshen_tpu.ops.pallas.decode_attention import mla_decode_attention
from fengshen_tpu.ops.rotary import apply_rotary_pos_emb
from fengshen_tpu.sharding import to_partition_rules, with_logical_constraint

#: longest query window that reads the cache absorbed (the decode tick
#: and any speculative gamma; `decode_attention._MAX_QUERY_WINDOW`)
ABSORBED_WINDOW = 8

#: logical axes of the parameters. The `[E, ...]` expert tables shard
#: over 'expert' (docs/sharding.md)
PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    ("embed_tokens/embedding", ("vocab", "embed")),
    (r"experts_(gate|up)", ("expert", None, "mlp")),
    (r"experts_down", ("expert", "mlp", None)),
    (r"(q_b_proj|kv_b_proj)/kernel", (None, "heads")),
    (r"o_proj/kernel", ("heads", "embed")),
    (r"(gate_proj|up_proj)/kernel", ("embed", "mlp")),
    (r"down_proj/kernel", ("mlp", "embed")),
    ("lm_head/kernel", ("embed", "vocab")),
    ("norm", ("norm",)),
    (".*", (None,)),
]


def _dt(config: JoyAIConfig):
    return jnp.dtype(config.dtype)


def _deinterleave(x):
    """`rope_interleave`: the published code views the rope dims as
    adjacent pairs and moves them to the rotate-half layout
    (`[..., d/2, 2]` -> `[..., 2, d/2]`) before rotating."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


class _Kernel(nn.Module):
    """A bias-free projection's `kernel`, handed out raw: the absorbed
    form multiplies by slices of `kv_b_proj` instead of applying it."""

    shape: tuple
    config: JoyAIConfig

    @nn.compact
    def __call__(self):
        cfg = self.config
        return self.param(
            "kernel", nn.initializers.normal(cfg.initializer_range),
            self.shape, jnp.dtype(cfg.param_dtype)).astype(_dt(cfg))


class JoyAIAttention(nn.Module):
    """Multi-head latent attention, full and absorbed (module
    docstring). Returns (output, cache)."""

    config: JoyAIConfig

    @nn.compact
    def __call__(self, hidden, attention_mask, position_ids,
                 cache: Optional[LatentCache], layer):
        cfg = self.config
        H, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        batch, seq, _ = hidden.shape
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name=name)
        norm = lambda name: RMSNorm(  # noqa: E731
            epsilon=cfg.rms_norm_eps, name=name)

        q = dense(H * (dn + dr), "q_b_proj")(norm("q_a_layernorm")(
            dense(cfg.q_lora_rank, "q_a_proj")(hidden)))
        q = q.reshape(batch, seq, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        ckv = dense(rank + dr, "kv_a_proj_with_mqa")(hidden)
        c_kv = norm("kv_a_layernorm")(ckv[..., :rank])
        k_rope = ckv[..., None, rank:]                     # one shared head
        if cfg.rope_interleave:
            q_rope, k_rope = _deinterleave(q_rope), _deinterleave(k_rope)
        q_rope, k_rope = apply_rotary_pos_emb(
            q_rope, k_rope, position_ids, base=cfg.rope_theta)
        k_rope = k_rope[:, :, 0]
        w_kvb = _Kernel((rank, H * (dn + dv)), cfg, name="kv_b_proj")() \
            .reshape(rank, H, dn + dv)
        scale = (dn + dr) ** -0.5

        pad = jnp.zeros((batch, seq, cfg.latent_width - rank - dr),
                        c_kv.dtype)
        rows = jnp.concatenate([c_kv, k_rope, pad], axis=-1)
        start = jnp.int32(0)
        if cache is not None:
            start = cache.index[layer]       # before the write moves it
            cache, mask = write_latent(cache, rows, layer, attention_mask)

        if cache is not None and seq <= ABSORBED_WINDOW:
            q_latent = jnp.einsum("bshd,chd->bshc", q_nope,
                                  w_kvb[..., :dn])
            out = mla_decode_attention(
                q_latent, q_rope, cache.kv, mask, scale=scale,
                block_table=None if cache.table is None
                else cache.table[layer], layer=layer)
            out = jnp.einsum("bshc,chd->bshd", out, w_kvb[..., dn:])
        else:
            if cache is not None:
                if cache.table is not None:
                    raise ValueError(
                        f"a window of {seq} > {ABSORBED_WINDOW} queries "
                        "reads the cache in the full form, which "
                        "expands every cached row: prefill runs on a "
                        "contiguous batch-1 cache, not the paged pool")
                rows = cache.kv[layer][:, :, 0]            # [B, T, R]
            out = latent_prefill_attention(
                q_nope, q_rope, rows, w_kvb, start, scale=scale,
                key_valid=key_mask(attention_mask, rows.shape[1]))
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        out = out.reshape(batch, seq, H * dv)
        return dense(cfg.hidden_size, "o_proj")(out), cache


class JoyAIDecoderLayer(nn.Module):
    config: JoyAIConfig
    #: the leading layer's feed-forward is a dense SwiGLU
    dense_mlp: bool = False

    @nn.compact
    def __call__(self, hidden, attention_mask, position_ids, cache, layer):
        cfg = self.config
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="input_layernorm")(hidden)
        h, cache = JoyAIAttention(cfg, name="self_attn")(
            h, attention_mask, position_ids, cache, layer)
        hidden = hidden + h
        h = RMSNorm(epsilon=cfg.rms_norm_eps,
                    name="post_attention_layernorm")(hidden)
        if self.dense_mlp:
            h = LlamaMLP(cfg, name="mlp")(h)
        else:
            h = RoutedExperts(
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.moe_intermediate_size,
                num_experts=cfg.n_routed_experts,
                top_k=cfg.num_experts_per_tok, scoring=cfg.scoring_func,
                score_bias=cfg.topk_method == "noaux_tc",
                norm_topk_prob=cfg.norm_topk_prob,
                routed_scaling_factor=cfg.routed_scaling_factor,
                n_shared_experts=cfg.n_shared_experts,
                experts_held=cfg.experts_held, dtype=_dt(cfg),
                param_dtype=jnp.dtype(cfg.param_dtype),
                initializer_range=cfg.initializer_range, name="mlp")(h)
        return hidden + h, cache


class JoyAIModel(nn.Module):
    config: JoyAIConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True):
        del deterministic                       # no dropout anywhere
        cfg = self.config
        batch, seq = input_ids.shape
        L = cfg.num_hidden_layers
        hidden = VocabParallelEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            name="embed_tokens")(input_ids)
        hidden = with_logical_constraint(hidden, ("batch", "seq", None))
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(seq)[None],
                                            (batch, seq))

        # the per-layer state this model declares: one latent row a
        # token (`serving/paged_cache.py` builds its pool from these
        # leaves). On the pass that creates them nothing is cached yet.
        cache = kv_var = None
        if init_cache or self.has_variable("cache", "cached_latent"):
            primed = self.has_variable("cache", "cached_latent")
            kv_var = self.variable(
                "cache", "cached_latent", jnp.zeros,
                (L, batch, cfg.max_position_embeddings, 1,
                 cfg.latent_width), _dt(cfg))
            index_var = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((L,), jnp.int32))
            if self.has_variable("cache", "cached_latent_scale"):
                raise ValueError(
                    "the latent cache (cached_latent) has no int8 form: "
                    "one absmax scale over a row would mix the normed "
                    "latent with the rotated key; use kv_dtype='fp32'")
            if primed:
                table = self.get_variable("cache", "block_table") \
                    if self.has_variable("cache", "block_table") else None
                cache = LatentCache(kv_var.value, index_var.value, table)

        hidden, cache = JoyAIDecoderLayer(
            cfg, dense_mlp=True, name="layers_0")(
            hidden, attention_mask, position_ids, cache, 0)
        for i in range(1, L):
            hidden, cache = JoyAIDecoderLayer(cfg, name=f"layers_{i}")(
                hidden, attention_mask, position_ids, cache, i)
        if cache is not None:
            kv_var.value, index_var.value = cache.kv, cache.index
        return RMSNorm(epsilon=cfg.rms_norm_eps, name="norm")(hidden)


class JoyAIForCausalLM(nn.Module):
    """Untied LM head on the stack; the serving engine's cache contract
    (`init_cache`, a mutable "cache" collection) as `LlamaForCausalLM`."""

    config: JoyAIConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, logits_row=None):
        """`logits_row`: the one row whose logits the caller keeps
        (`[B, 1, V]`), or None for every row's (`head_rows`)."""
        cfg = self.config
        hidden = JoyAIModel(cfg, name="model")(
            input_ids, attention_mask, position_ids, init_cache,
            deterministic)
        return nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="lm_head")(head_rows(hidden, logits_row))

    def init_params(self, rng, seq_len: int = 8):
        return self.init(rng, jnp.zeros((1, seq_len), jnp.int32))["params"]

    def partition_rules(self):
        return to_partition_rules(PARAM_LOGICAL_AXES)
