"""Transformer-XL backbone with relative position encoding.

Faithful flax port of the reference's GLM-style GPT2Transformer
(reference: fengshen/models/transfo_xl_denoise/
modeling_transfo_xl_denoise.py — PositionalEmbedding :106-122, fused-qkv
relative attention with _rel_shift :190-340, pre-LN layer :370-470,
transformer + memory :520-660, tied output head :681-770). The published
Bigan/Transformer-XL checkpoints (denoise / paraphrase / reasoning, all
three families share this one backbone per the reference __init__ files)
are trained with relative_encoding=True, so this module is the import
target; the attention is MXU-dense (one fused qkv matmul + two batched
matmuls per layer) and the rel-shift is a static gather, so XLA fuses the
whole layer.

Memory (the XL segment recurrence) is a per-layer list of past hidden
states with static length, attended as read-only keys — pass `mems` and
collect `new_mems` exactly like the reference's update_mems.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from fengshen_tpu.sharding import (to_partition_rules,
                                   with_logical_constraint)


@dataclasses.dataclass
class TransfoXLConfig:
    """Field names follow the reference configuration
    (configuration_transfo_xl_denoise.py:91-118; published 1.1B:
    32 layers, hidden 1600, 25 heads, vocab 50048)."""

    vocab_size: int = 50048
    hidden_size: int = 1600
    num_layers: int = 32
    num_attention_heads: int = 25
    max_sequence_length: int = 512
    max_memory_length: int = 512
    embedding_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    output_dropout_prob: float = 0.1
    layernorm_epsilon: float = 1e-5
    relative_encoding: bool = True
    initializer_range: float = 0.02
    dtype: str = "float32"
    param_dtype: str = "float32"

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "TransfoXLConfig":
        base = dict(vocab_size=128, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_sequence_length=64,
                    max_memory_length=16)
        base.update(overrides)
        return cls(**base)


def xl_positional_embedding(pos_seq: jnp.ndarray,
                            hidden_size: int) -> jnp.ndarray:
    """[sin | cos] concat over inv_freq = 10000^(-2i/H) (reference
    PositionalEmbedding :106-122). pos_seq is DESCENDING key distances."""
    inv_freq = 1.0 / (10000 ** (np.arange(0, hidden_size, 2,
                                          dtype=np.float32) /
                                hidden_size))
    ang = pos_seq[:, None] * jnp.asarray(inv_freq)[None, :]
    # keep the sin|cos concat replicated: GSPMD must never turn it into
    # a sharded matmul contraction (docs/sharding.md "Root cause")
    return with_logical_constraint(
        jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1),
        ("seq", "relpos"))


def rel_shift(bd: jnp.ndarray) -> jnp.ndarray:
    """The reference's pad-reshape-slice `_rel_shift` (:234-249), verbatim
    in jnp — pure reshapes, so XLA lowers it to a layout change."""
    batch, n_head, qlen, klen = bd.shape
    zero_pad = jnp.zeros((batch, n_head, qlen, 1), bd.dtype)
    padded = jnp.concatenate([zero_pad, bd], axis=-1)
    padded = padded.reshape(batch, n_head, klen + 1, qlen)
    return padded[:, :, 1:, :].reshape(batch, n_head, qlen, klen)


class XLSelfAttention(nn.Module):
    """Fused-qkv relative attention (reference GPT2SelfAttention
    :190-340). r_w/r_r biases are shared across layers and passed in."""

    config: TransfoXLConfig

    @nn.compact
    def __call__(self, hidden, ltor_mask, pos_emb, r_w_bias, r_r_bias,
                 mem=None, deterministic=True):
        cfg = self.config
        batch, qlen, h = hidden.shape
        n_head = cfg.num_attention_heads
        hd = h // n_head
        dt = jnp.dtype(cfg.dtype)

        cat = hidden if mem is None else jnp.concatenate([mem, hidden], 1)
        klen = cat.shape[1]
        qkv = nn.Dense(3 * h, dtype=dt,
                       param_dtype=jnp.dtype(cfg.param_dtype),
                       kernel_init=nn.initializers.normal(
                           cfg.initializer_range),
                       name="query_key_value")(cat)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q[:, -qlen:]

        def heads(t):
            return t.reshape(batch, t.shape[1], n_head, hd).transpose(
                0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)

        # relative projection of the positional basis (klen rows)
        rel = nn.Dense(h, dtype=dt,
                       param_dtype=jnp.dtype(cfg.param_dtype),
                       kernel_init=nn.initializers.normal(
                           cfg.initializer_range),
                       name="relative")(pos_emb)
        rel = rel.reshape(klen, n_head, hd).transpose(1, 0, 2)  # [n, k, d]

        ac = jnp.einsum("bnqd,bnkd->bnqk",
                        q + r_w_bias[None, :, None].astype(q.dtype), k,
                        preferred_element_type=jnp.float32)
        bd = jnp.einsum("bnqd,nkd->bnqk",
                        q + r_r_bias[None, :, None].astype(q.dtype), rel,
                        preferred_element_type=jnp.float32)
        bd = rel_shift(bd)

        scores = (ac + bd) / jnp.sqrt(jnp.asarray(hd, jnp.float32))
        mask = ltor_mask.astype(scores.dtype)
        scores = scores * mask - 10000.0 * (1.0 - mask)
        probs = jax.nn.softmax(scores, axis=-1)
        probs = nn.Dropout(cfg.attention_dropout_prob)(
            probs, deterministic=deterministic)
        ctx = jnp.einsum("bnqk,bnkd->bnqd", probs.astype(v.dtype), v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(batch, qlen, h)
        out = nn.Dense(h, dtype=dt,
                       param_dtype=jnp.dtype(cfg.param_dtype),
                       kernel_init=nn.initializers.normal(
                           cfg.initializer_range /
                           np.sqrt(2.0 * cfg.num_layers)),
                       name="dense")(ctx)
        return nn.Dropout(cfg.output_dropout_prob)(
            out, deterministic=deterministic)


class XLLayer(nn.Module):
    """Pre-LN layer (reference GPT2TransformerLayer :370-470): the memory
    is normalised with the SAME input_layernorm before attention."""

    config: TransfoXLConfig

    @nn.compact
    def __call__(self, hidden, ltor_mask, pos_emb, r_w_bias, r_r_bias,
                 mem=None, deterministic=True):
        cfg = self.config
        h = cfg.hidden_size
        dt = jnp.dtype(cfg.dtype)
        ln_in = nn.LayerNorm(epsilon=cfg.layernorm_epsilon, dtype=dt,
                             name="input_layernorm")
        x = ln_in(hidden)
        m = ln_in(mem) if mem is not None else None
        attn = XLSelfAttention(cfg, name="attention")(
            x, ltor_mask, pos_emb, r_w_bias, r_r_bias, m, deterministic)
        hidden = hidden + attn
        y = nn.LayerNorm(epsilon=cfg.layernorm_epsilon, dtype=dt,
                         name="post_attention_layernorm")(hidden)
        mid = nn.Dense(4 * h, dtype=dt,
                       param_dtype=jnp.dtype(cfg.param_dtype),
                       kernel_init=nn.initializers.normal(
                           cfg.initializer_range),
                       name="dense_h_to_4h")(y)
        # OpenAI tanh gelu (reference gelu_impl :156-162)
        mid = jax.nn.gelu(mid, approximate=True)
        out = nn.Dense(h, dtype=dt,
                       param_dtype=jnp.dtype(cfg.param_dtype),
                       kernel_init=nn.initializers.normal(
                           cfg.initializer_range /
                           np.sqrt(2.0 * cfg.num_layers)),
                       name="dense_4h_to_h")(mid)
        out = nn.Dropout(cfg.output_dropout_prob)(
            out, deterministic=deterministic)
        return hidden + out


class TransfoXLModel(nn.Module):
    """Word embeddings + relative transformer + tied output head
    (reference TransfoXLDenoiseModel :681-770). Returns (logits,
    new_mems); feed `mems` (list of [B, M, H], one per layer) for the XL
    segment recurrence.

    With `latent_size > 0` the model is the reference's
    GPT2ModelForLatent (DAVAE/GPT2ModelForLatent.py:500-575): `latent`
    [B, latent_size] is projected by a bias-free `linear_emb` and added
    after the embedding and after EVERY layer."""

    config: TransfoXLConfig
    latent_size: int = 0

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, mems=None,
                 latent=None, deterministic=True):
        cfg = self.config
        batch, qlen = input_ids.shape
        mem_len = mems[0].shape[1] if mems else 0
        klen = qlen + mem_len

        wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                       dtype=jnp.dtype(cfg.dtype),
                       param_dtype=jnp.dtype(cfg.param_dtype),
                       embedding_init=nn.initializers.normal(
                           cfg.initializer_range),
                       name="word_embeddings")
        hidden = wte(input_ids)
        latent_emb = None
        if self.latent_size > 0:
            assert latent is not None, "latent_size>0 requires `latent`"
            latent_emb = nn.Dense(cfg.hidden_size, use_bias=False,
                                  name="linear_emb")(latent)[:, None, :]
            hidden = hidden + latent_emb.astype(hidden.dtype)

        # causal mask over memory+current keys: query i attends keys
        # <= mem_len + i; multiplied by any padding mask
        ltor = jnp.tril(jnp.ones((qlen, klen), jnp.float32),
                        k=mem_len)[None, None]
        if attention_mask is not None:
            if attention_mask.ndim == 2:  # [B, S] padding mask
                pad = jnp.concatenate(
                    [jnp.ones((batch, mem_len), attention_mask.dtype),
                     attention_mask], axis=1)
                ltor = ltor * pad[:, None, None, :]
            else:
                ltor = attention_mask

        # descending key distances (reference :588-591)
        pos_seq = jnp.arange(klen - 1, -1, -1, dtype=jnp.float32)
        pos_emb = xl_positional_embedding(pos_seq, cfg.hidden_size)
        pos_emb = nn.Dropout(cfg.embedding_dropout_prob)(
            pos_emb, deterministic=deterministic)
        hidden = nn.Dropout(cfg.embedding_dropout_prob)(
            hidden, deterministic=deterministic)

        n_head = cfg.num_attention_heads
        hd = cfg.hidden_size // n_head
        r_w_bias = self.param("r_w_bias", nn.initializers.zeros,
                              (n_head, hd), jnp.float32)
        r_r_bias = self.param("r_r_bias", nn.initializers.zeros,
                              (n_head, hd), jnp.float32)

        new_mems = []
        mem_keep = cfg.max_memory_length
        for i in range(cfg.num_layers):
            if mem_keep > 0:
                prev = hidden if mems is None else jnp.concatenate(
                    [mems[i], hidden], axis=1)
                new_mems.append(
                    jax.lax.stop_gradient(prev[:, -mem_keep:]))
            mem_i = mems[i] if mems else None
            hidden = XLLayer(cfg, name=f"layer_{i}")(
                hidden, ltor, pos_emb, r_w_bias, r_r_bias, mem_i,
                deterministic)
            if latent_emb is not None:
                hidden = hidden + latent_emb.astype(hidden.dtype)
        hidden = nn.LayerNorm(epsilon=cfg.layernorm_epsilon,
                              dtype=jnp.dtype(cfg.dtype),
                              name="final_layernorm")(hidden)
        logits = hidden @ wte.embedding.T.astype(hidden.dtype)
        return logits, new_mems

    def partition_rules(self):
        # resolved at call time so a `use_rules` scope takes effect
        return to_partition_rules(XL_PARAM_LOGICAL_AXES)


#: Logical-axis annotations (docs/sharding.md). The fused qkv is
#: column-parallel on its OUTPUT (heads) dim — the head split happens
#: after the matmul, so sharding the 3h output dim over `heads` IS the
#: split-heads-before-the-shard Megatron layout (each tensor shard
#: holds whole heads of each of q/k/v). `relative` must be
#: column-parallel too: its input is the sin|cos positional concat,
#: and a concatenate consumed through a sharded matmul contraction
#: mispartitions on this XLA build (docs/sharding.md "Root cause") —
#: hence `relpos` (→ None), never `embed`, on its contraction dim.
XL_PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    (r"word_embeddings/embedding", ("vocab", "embed")),
    (r"layer_\d+/attention/query_key_value/kernel", ("embed", "heads")),
    (r"layer_\d+/attention/relative/kernel", ("relpos", "heads")),
    (r"layer_\d+/attention/dense/kernel", ("heads", "embed")),
    (r"layer_\d+/dense_h_to_4h/kernel", ("embed", "mlp")),
    (r"layer_\d+/dense_4h_to_h/kernel", ("mlp", "embed")),
    (r".*", (None,)),
]

XL_PARTITION_RULES = to_partition_rules(XL_PARAM_LOGICAL_AXES)
