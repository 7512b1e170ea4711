"""SDAR's decoder in flax: one kind of layer, 48 times, under a
BLOCK-causal mask.

Stream: `x0 = Embed(ids)`, a position flagged `masked` fed the row of
`mask_token_id` whatever its id; each layer, pre-norm (RMSNorm, eps
`rms_norm_eps`), `x += Attention(norm(x))`, `x += Experts(norm(x))`;
`logits = Head(norm(x))`, head untied. The logits at position `i`
predict the token AT position `i` (no shift).

- attention: q of `num_attention_heads`, k and v of
  `num_key_value_heads` heads, no bias; RMSNorm over a head on q and on
  k (one learned `[D]` each a layer) before rotary over the whole head
  (rotate-half, theta `rope_theta`, no scaling) at the token's own
  position. With `L = block_length`, the query at position `p` reads
  every key at a position `< (p // L + 1) * L`: all earlier blocks and
  the WHOLE of its own, later positions of it included. `L = 1` is
  causal attention and the model is Qwen3-MoE.
- experts (`ops/moe.py RoutedExperts`): softmax over all `num_experts`
  router outputs in float32, top-k renormalised, no shared expert.

The cache lives at the model, not in the layers (as `models/keye`
keeps its own), in the leaf layout `serving/` builds for any model —
ONE row a token a layer for K and one for V, a token's KV heads folded
into it (read as it lies):

    cached_key / cached_value  [L, B, max_len, 1, KVH * D]
    cache_index                [L]  (`[L, B]` in the engine's pool)

The paged pool swaps them for `[L, num_blocks, block_size, 1, KVH * D]`
behind one `block_table [L, B, max_blocks]`. The layer loop hands the
stacks from layer to layer as values; each layer writes its own index
in place.

Three calls, told apart by what the cache shows (static under jit), the
mask block-causal in all three:

- no cache: a plain forward, the rows just projected standing in for
  the cache;
- a BLOCK of `S <= 8` tokens a lane onto a pool of lanes (per-lane
  cursors): the serving engine's block tick. The block's rows are
  written at the lane's cursor and all `S` queries read the lane to
  `cursor + S - 1` — one extent a lane — through the `decode_attention`
  seam's folded entry (`fstpu_block_decode_attention`). The cursor
  moves on by `S`; a tick that is not the block's last rolls it back
  (`serving/cache.rollback_slots`) and the next overwrites the rows;
- a WINDOW of tokens onto a contiguous batch-1 cache with a scalar
  cursor (prefill): a walk in tiles of queries over the carried rows
  (`fstpu_block_prefill_attention`, `ops/gated_attention
  .block_prefill_walk`).

Positions are physical: the block grid is counted from position 0 of
the sequence, so a lane is filled from position 0 and padded on the
RIGHT (the engine declares a cache of a model with a generation block
positional). `generation_block()` is how a model declares one
(docs/serving.md "Block generation").

Layers are unrolled: a scan would slice each layer's three `[E, ...]`
expert tables out of a stack, and XLA:TPU copies a sliced table whole
before its grouped matmul reads it (PERF.md, PR 26; ROADMAP M3).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from fengshen_tpu.models.model_utils import write_rows
from fengshen_tpu.models.sdar.configuration_sdar import SdarConfig
from fengshen_tpu.ops.embedding import VocabParallelEmbed
from fengshen_tpu.ops.gated_attention import block_prefill_walk
from fengshen_tpu.ops.moe import RoutedExperts
from fengshen_tpu.ops.norms import RMSNorm
from fengshen_tpu.ops.pallas.decode_attention import (
    folded_decode_attention)
from fengshen_tpu.ops.rotary import apply_rotary_pos_emb
from fengshen_tpu.sharding import to_partition_rules, with_logical_constraint

DECODE_SCOPE = "fstpu_block_decode_attention"
PREFILL_SCOPE = "fstpu_block_prefill_attention"

#: logical axes of the parameters. The `[E, ...]` expert tables shard
#: over 'expert' (docs/sharding.md)
PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    ("embed_tokens/embedding", ("vocab", "embed")),
    (r"experts_(gate|up)", ("expert", None, "mlp")),
    (r"experts_down", ("expert", "mlp", None)),
    (r"self_attn/(q_proj|k_proj|v_proj)/kernel", ("embed", "heads")),
    (r"o_proj/kernel", ("heads", "embed")),
    ("lm_head/kernel", ("embed", "vocab")),
    ("norm", ("norm",)),
    (".*", (None,)),
]


def _dt(config: SdarConfig):
    return jnp.dtype(config.dtype)


class SdarCache(NamedTuple):
    """The stacks the layer loop carries (module docstring). `start` is
    each lane's cursor when the call began: `[]` on a contiguous cache
    with a scalar cursor, else `[B]`."""

    k: jax.Array
    v: jax.Array
    table: Optional[jax.Array]
    start: jax.Array


def _dense(cfg: SdarConfig, feats: int, name: str):
    return nn.Dense(
        feats, use_bias=False, dtype=_dt(cfg),
        param_dtype=jnp.dtype(cfg.param_dtype),
        kernel_init=nn.initializers.normal(cfg.initializer_range), name=name)


class SdarAttention(nn.Module):
    """Grouped-query attention under the block-causal mask. Returns
    (output, cache)."""

    config: SdarConfig

    @nn.compact
    def __call__(self, hidden, position_ids, cache: Optional[SdarCache],
                 layer: int):
        cfg = self.config
        H, G, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        batch, seq, _ = hidden.shape
        eps = cfg.rms_norm_eps
        q = _dense(cfg, H * D, "q_proj")(hidden).reshape(batch, seq, H, D)
        k = _dense(cfg, G * D, "k_proj")(hidden).reshape(batch, seq, G, D)
        v = _dense(cfg, G * D, "v_proj")(hidden).reshape(batch, seq, G, D)
        q = RMSNorm(epsilon=eps, name="q_norm")(q)
        k = RMSNorm(epsilon=eps, name="k_norm")(k)
        q, k = apply_rotary_pos_emb(q, k, position_ids, base=cfg.rope_theta)
        walk = dict(scale=D ** -0.5, block=cfg.block_length)
        if cache is None:
            with jax.named_scope(PREFILL_SCOPE):
                out = block_prefill_walk(
                    q, k.reshape(batch, seq, G * D),
                    v.reshape(batch, seq, G * D), jnp.int32(0), **walk)
        elif cache.start.ndim:
            cache = write_rows(cache, layer, k=k, v=v)
            out = self._tick(q, cache, layer)
        else:
            cache = write_rows(cache, layer, k=k, v=v)
            lane = lambda x: x[layer].reshape(  # noqa: E731
                batch, -1, G * D)
            with jax.named_scope(PREFILL_SCOPE):
                out = block_prefill_walk(q, lane(cache.k), lane(cache.v),
                                         cache.start, **walk)
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(batch, seq, H * D)), cache

    def _tick(self, q, cache: SdarCache, layer: int):
        """A block's `S` queries a lane, every one reading the lane to
        the block's last row, through the folded entry of the seam. No
        mask: positions are physical and every cached row is real (a
        lane is filled from 0, never left-padded)."""
        batch, seq = q.shape[:2]
        if seq != self.config.block_length:
            raise ValueError(
                f"{seq} tokens a lane onto a pool of lanes: only the "
                f"{self.config.block_length} queries of one generation "
                "block share an extent (a draft window is causal); prefill "
                "runs on a contiguous batch-1 cache")
        t = cache.start + (seq - 1)
        scale = q.shape[-1] ** -0.5
        with jax.named_scope(DECODE_SCOPE):
            if cache.table is not None:
                return folded_decode_attention(
                    q, cache.k, cache.v, cache.table[layer], t, scale=scale,
                    layer=layer)
            # a contiguous lane is whole blocks in a row: a free reshape
            # and a table that counts
            lanes, lane_len = cache.k.shape[1:3]
            block = math.gcd(lane_len, 128)
            per = lane_len // block
            pools = tuple(x.reshape((-1, block) + x.shape[3:])
                          for x in (cache.k, cache.v))
            table = (layer * lanes + jnp.arange(batch)[:, None]) * per + \
                jnp.arange(per)[None]
            return folded_decode_attention(q, *pools, table, t, scale=scale)


class SdarDecoderLayer(nn.Module):
    config: SdarConfig

    @nn.compact
    def __call__(self, hidden, position_ids, cache, layer):
        cfg = self.config
        eps = cfg.rms_norm_eps
        h = RMSNorm(epsilon=eps, name="input_layernorm")(hidden)
        h, cache = SdarAttention(cfg, name="self_attn")(
            h, position_ids, cache, layer)
        hidden = hidden + h
        h = RMSNorm(epsilon=eps, name="post_attention_layernorm")(hidden)
        h = RoutedExperts(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            scoring="softmax", norm_topk_prob=cfg.norm_topk_prob,
            dtype=_dt(cfg), param_dtype=jnp.dtype(cfg.param_dtype),
            initializer_range=cfg.initializer_range, name="mlp")(h)
        return hidden + h, cache


class SdarModel(nn.Module):
    config: SdarConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, masked=None):
        # positions are physical and padding is on the right: a padded
        # token lies in a later block than any real query's, so the
        # mask is not consulted
        del deterministic, attention_mask
        cfg = self.config
        batch, seq = input_ids.shape
        L = cfg.num_hidden_layers
        if masked is not None:
            # by the caller's flag, never by the id: a prompt may hold
            # `mask_token_id` as a token
            input_ids = jnp.where(masked, cfg.mask_token_id, input_ids)
        hidden = VocabParallelEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            name="embed_tokens")(input_ids)
        hidden = with_logical_constraint(hidden, ("batch", "seq", None))
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(seq)[None],
                                            (batch, seq))

        # the per-layer rows this model declares (module docstring); on
        # the pass that creates the leaves nothing is cached yet
        cache = None
        if init_cache or self.has_variable("cache", "cached_key"):
            primed = self.has_variable("cache", "cached_key")
            if self.has_variable("cache", "cached_key_scale"):
                raise ValueError(
                    "this cache has no int8 form: a block's rows are "
                    "rewritten every forward and read as they lie; use "
                    "kv_dtype='fp32'")
            rows = (L, batch, cfg.max_position_embeddings, 1,
                    cfg.num_key_value_heads * cfg.head_dim)
            k_var = self.variable("cache", "cached_key", jnp.zeros, rows,
                                  _dt(cfg))
            v_var = self.variable("cache", "cached_value", jnp.zeros, rows,
                                  _dt(cfg))
            index_var = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((L,), jnp.int32))
            if primed:
                table = self.get_variable("cache", "block_table") \
                    if self.has_variable("cache", "block_table") else None
                cache = SdarCache(k_var.value, v_var.value, table,
                                  index_var.value[0])

        for i in range(L):
            hidden, cache = SdarDecoderLayer(cfg, name=f"layers_{i}")(
                hidden, position_ids, cache, i)
        if cache is not None:
            k_var.value, v_var.value = cache.k, cache.v
            index_var.value = index_var.value + seq
        return RMSNorm(epsilon=cfg.rms_norm_eps, name="norm")(hidden)


class SdarForCausalLM(nn.Module):
    """Untied LM head on the stack; the serving engine's cache contract
    (`init_cache`, a mutable "cache" collection) as `LlamaForCausalLM`,
    and the generation block's (`generation_block`, `masked`, `head`)."""

    config: SdarConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, masked=None,
                 head=True):
        """`masked` `[B, S]` bool: the positions fed the mask token.
        `head=False` returns the final hidden states: a prefill of
        whole blocks reads no logits."""
        cfg = self.config
        hidden = SdarModel(cfg, name="model")(
            input_ids, attention_mask, position_ids, init_cache,
            deterministic, masked)
        lm_head = _dense(cfg, cfg.vocab_size, "lm_head")
        if not head and not self.is_initializing():
            return hidden
        return lm_head(hidden)

    def init_params(self, rng, seq_len: int = 8):
        return self.init(rng, jnp.zeros((1, seq_len), jnp.int32))["params"]

    def partition_rules(self):
        return to_partition_rules(PARAM_LOGICAL_AXES)

    def generation_block(self):
        """(block_length, mask_token_id) where this model is generated
        by diffusion over blocks of more than one position, else None
        (one token a tick, causally): what the serving engine asks."""
        cfg = self.config
        return (cfg.block_length, cfg.mask_token_id) \
            if cfg.block_length > 1 else None
