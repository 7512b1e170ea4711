"""Keye-VL-2.0's language model in flax: one kind of layer, 48 times.

Stream: `x0 = Embed(ids)`; each layer, pre-norm (RMSNorm, eps
`rms_norm_eps`), `x += Attention(norm(x))`, `x += Experts(norm(x))`;
`logits = Head(norm(x))`, head untied.

- attention: q of `num_attention_heads`, k and v of
  `num_key_value_heads` heads, no bias; RMSNorm over a head on q and on
  k (one learned `[D]` each a layer); rotary over the whole head
  (rotate-half, theta `rope_theta`, no scaling; `mrope_section` is
  ordinary rotary for text, whose three position ids are equal); each
  query reads the `topk` cached tokens its layer's INDEXER chose, all
  its heads the same ones.
- indexer (`ops/sparse_attention.py`, the learned selector): from the
  layer's normed input `x`, `qI = R(W_Iq x)` `[J, Di]`, `w = W_w x`
  `[J]`, and one key a token `kI = R(LayerNorm(W_Ik x))` `[Di]`; `I_{t,s}
  = (J Di)^(-1/2) sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)`; a query reads
  the `topk` positions `s <= t` of largest `I`.
- experts (`ops/moe.py RoutedExperts`): softmax over all `num_experts`
  router outputs in float32, top-k renormalised, no shared expert.

The cache lives at the model, not in the layers (as `models/sala`
keeps its own), in the leaf layout `serving/` builds for any model —
THREE rows a token a layer:

    cached_key / cached_value  [L, B, max_len, 1, KVH * D]
    cached_index_key           [L, B, max_len, 1, Di]   (the indexer's key)
    cache_index                [L]  (`[L, B]` in the engine's pool)

(a token's KV heads are folded into ONE row, read as it lies.) The
paged pool swaps them for `[L, num_blocks, block_size, 1, ...]` behind
one `block_table [L, B, max_blocks]`. The layer loop hands the stacks
from layer to layer as values; each layer writes its own index in place.

Three calls, told apart by what the cache shows (static under jit): no
cache (a plain forward: the rows just projected stand in for the
cache); one token a lane onto any cache (the decode tick: through the
`decode_attention` seam's indexed entry); a WINDOW of tokens onto a
contiguous cache with a scalar cursor (prefill: the first or a later
window of a prompt — each query selects over the rows the cache holds
and the window's own). Positions are physical: the chosen set is a list
of cache positions, so a lane is filled from position 0 and padded on
the RIGHT (`serving/paged_cache.positional_leaves` tells the engine).

Layers are unrolled: a scan would slice each layer's three `[E, ...]`
expert tables out of a stack, and XLA:TPU copies a sliced table whole
before its grouped matmul reads it (PERF.md, PR 26; ROADMAP M3).

The vision tower and the three-axis positions of image tokens are not
built.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from fengshen_tpu.models.keye.configuration_keye import KeyeConfig
from fengshen_tpu.models.model_utils import head_rows
from fengshen_tpu.models.model_utils import write_rows as _write_rows
from fengshen_tpu.ops.embedding import VocabParallelEmbed
from fengshen_tpu.ops.moe import RoutedExperts
from fengshen_tpu.ops.norms import LayerNorm, RMSNorm
from fengshen_tpu.ops.pallas.decode_attention import (
    indexed_decode_attention)
from fengshen_tpu.ops.rotary import apply_rotary_pos_emb
from fengshen_tpu.ops.sparse_attention import indexed_prefill_attention
from fengshen_tpu.sharding import to_partition_rules, with_logical_constraint

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


def _dt(config: KeyeConfig):
    return jnp.dtype(config.dtype)


class KeyeCache(NamedTuple):
    """The stacks the layer loop carries (module docstring). `start` is
    each lane's cursor when the call began: `[]` on a contiguous cache
    with a scalar cursor, else `[B]`."""

    k: jax.Array
    v: jax.Array
    ik: jax.Array
    table: Optional[jax.Array]
    start: jax.Array


def _dense(cfg: KeyeConfig, feats: int, name: str):
    return nn.Dense(
        feats, use_bias=False, dtype=_dt(cfg),
        param_dtype=jnp.dtype(cfg.param_dtype),
        kernel_init=nn.initializers.normal(cfg.initializer_range), name=name)


def _write_index_key(cache: KeyeCache, layer: int, ki):
    """One token's indexer key a lane, `[B, Di]`, into layer `layer` of
    the stack at each lane's cursor, addressed by the stack's own axes
    (layer, block or lane, row). XLA:TPU keeps a 64-wide row leaf with
    its TOKENS minor-most (the layout the scores' product wants of it)
    and re-lays the stack out and back around any write of a row — this
    scatter, a slice update a lane alike — ONCE a tick for all layers:
    two copies of the indexer keys' pool, 0.55 GB moved at the cell's
    size. A scatter into the stack viewed flat, as `_write_rows` does,
    paid that every layer (PERF.md, PR 36; section 7 has the cure: two
    tokens a 128-wide row)."""
    batch = ki.shape[0]
    t = jnp.broadcast_to(cache.start, (batch,))
    if cache.table is not None:
        block = cache.ik.shape[2]
        at = jnp.take_along_axis(cache.table[layer], (t // block)[:, None],
                                 axis=-1)[:, 0]
        row = t % block
    else:
        at, row = jnp.arange(batch), t
    return cache._replace(ik=cache.ik.at[layer, at, row, 0].set(
        ki.astype(cache.ik.dtype)))


class KeyeIndexer(nn.Module):
    """The indexer's three projections of a layer's normed input.
    Returns (queries `[B, S, J, Di]`, head weights `[B, S, J]` float32,
    keys `[B, S, Di]`)."""

    config: KeyeConfig

    @nn.compact
    def __call__(self, hidden, position_ids):
        cfg = self.config
        J, Di = cfg.index_heads, cfg.index_head_dim
        batch, seq, _ = hidden.shape
        qi = _dense(cfg, J * Di, "q_proj")(hidden).reshape(batch, seq, J, Di)
        ki = _dense(cfg, Di, "k_proj")(hidden)
        ki = LayerNorm(epsilon=cfg.rms_norm_eps, name="k_norm")(ki)
        ki = ki[:, :, None, :]
        qi, ki = apply_rotary_pos_emb(qi, ki, position_ids,
                                      base=cfg.rope_theta)
        w = _dense(cfg, J, "weights_proj")(hidden).astype(jnp.float32)
        return qi, w, ki[:, :, 0]


class KeyeAttention(nn.Module):
    """Grouped-query attention over the tokens the indexer chose.
    Returns (output, cache)."""

    config: KeyeConfig

    @nn.compact
    def __call__(self, hidden, position_ids, cache: Optional[KeyeCache],
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
        qi, w, ki = KeyeIndexer(cfg, name="indexer")(hidden, position_ids)
        window = dict(topk=cfg.index_topk, index_scale=cfg.index_scale,
                      extent_step=cfg.index_extent_step,
                      q_tile=cfg.index_q_tile, k_tile=cfg.index_k_tile)
        if cache is None:
            out = indexed_prefill_attention(q, k, v, qi, w, ki,
                                            jnp.int32(0), **window)
        elif seq == 1:
            cache = _write_index_key(_write_rows(cache, layer, k=k, v=v),
                                     layer, ki[:, 0])
            out = self._tick(q, qi[:, 0], w[:, 0], cache, layer)
        else:
            if cache.start.ndim:
                raise ValueError(
                    f"a window of {seq} tokens onto a pool of lanes: the "
                    "chosen set is a list of one lane's positions; "
                    "prefill runs on a contiguous batch-1 cache")
            cache = _write_rows(cache, layer, k=k, v=v, ik=ki)
            lane = lambda x: x[layer].reshape(  # noqa: E731
                batch, -1, G, D)
            out = indexed_prefill_attention(
                q, lane(cache.k), lane(cache.v), qi, w,
                cache.ik[layer][:, :, 0], cache.start, **window)
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            out.reshape(batch, seq, H * D)), cache

    def _tick(self, q, qi, w, cache: KeyeCache, layer: int):
        """One query a lane through the indexed entry of the seam. No
        mask: positions are physical and every cached row is real (a
        lane is filled from 0, never left-padded)."""
        cfg = self.config
        batch = q.shape[0]
        t = jnp.broadcast_to(cache.start, (batch,))
        how = dict(topk=cfg.index_topk, index_scale=cfg.index_scale)
        if cache.table is not None:
            return indexed_decode_attention(
                q, qi, w, cache.ik, cache.k, cache.v, cache.table[layer], t,
                layer=layer, **how)
        # a contiguous lane is whole blocks in a row: a free reshape and
        # a table that counts
        lanes, lane_len = cache.k.shape[1:3]
        block = math.gcd(lane_len, 128)
        per = lane_len // block
        pools = tuple(x.reshape((-1, block) + x.shape[3:])
                      for x in (cache.ik, cache.k, cache.v))
        table = (layer * lanes + jnp.arange(batch)[:, None]) * per + \
            jnp.arange(per)[None]
        return indexed_decode_attention(q, qi, w, *pools, table, t, **how)


class KeyeDecoderLayer(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, hidden, position_ids, cache, layer):
        cfg = self.config
        eps = cfg.rms_norm_eps
        h = RMSNorm(epsilon=eps, name="input_layernorm")(hidden)
        h, cache = KeyeAttention(cfg, name="self_attn")(
            h, position_ids, cache, layer)
        hidden = hidden + h
        h = RMSNorm(epsilon=eps, name="post_attention_layernorm")(hidden)
        h = RoutedExperts(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            scoring="softmax", norm_topk_prob=cfg.norm_topk_prob,
            experts_held=cfg.experts_held, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            initializer_range=cfg.initializer_range, name="mlp")(h)
        return hidden + h, cache


class KeyeModel(nn.Module):
    config: KeyeConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True):
        # positions are physical and padding is on the right: a padded
        # token is a query nothing real reads (attention is causal, a
        # token's experts are its own), so the mask is not consulted
        del deterministic, attention_mask
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

        # the per-layer rows this model declares (module docstring); on
        # the pass that creates the leaves nothing is cached yet
        cache = None
        if init_cache or self.has_variable("cache", "cached_key"):
            primed = self.has_variable("cache", "cached_key")
            if self.has_variable("cache", "cached_key_scale"):
                raise ValueError(
                    "this cache has no int8 form: the indexer scores and "
                    "the chosen rows are read as they lie; use "
                    "kv_dtype='fp32'")
            max_len = cfg.max_position_embeddings
            rows = (L, batch, max_len, 1,
                    cfg.num_key_value_heads * cfg.head_dim)
            k_var = self.variable("cache", "cached_key", jnp.zeros, rows,
                                  _dt(cfg))
            v_var = self.variable("cache", "cached_value", jnp.zeros, rows,
                                  _dt(cfg))
            i_var = self.variable(
                "cache", "cached_index_key", jnp.zeros,
                (L, batch, max_len, 1, cfg.index_head_dim), _dt(cfg))
            index_var = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((L,), jnp.int32))
            if primed:
                table = self.get_variable("cache", "block_table") \
                    if self.has_variable("cache", "block_table") else None
                cache = KeyeCache(k_var.value, v_var.value, i_var.value,
                                  table, index_var.value[0])

        for i in range(L):
            hidden, cache = KeyeDecoderLayer(cfg, name=f"layers_{i}")(
                hidden, position_ids, cache, i)
        if cache is not None:
            k_var.value, v_var.value = cache.k, cache.v
            i_var.value = cache.ik
            index_var.value = index_var.value + seq
        return RMSNorm(epsilon=cfg.rms_norm_eps, name="norm")(hidden)


class KeyeForCausalLM(nn.Module):
    """Untied LM head on the stack; the serving engine's cache contract
    (`init_cache`, a mutable "cache" collection) as `LlamaForCausalLM`."""

    config: KeyeConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, logits_row=None):
        """`logits_row`: the one row whose logits the caller keeps
        (`[B, 1, V]`), or None for every row's (`head_rows`)."""
        cfg = self.config
        hidden = KeyeModel(cfg, name="model")(
            input_ids, attention_mask, position_ids, init_cache,
            deterministic)
        return _dense(cfg, cfg.vocab_size, "lm_head")(
            head_rows(hidden, logits_row))

    def init_params(self, rng, seq_len: int = 8):
        return self.init(rng, jnp.zeros((1, seq_len), jnp.int32))["params"]

    def partition_rules(self):
        return to_partition_rules(PARAM_LOGICAL_AXES)

    def indexed_tokens(self, context):
        """Host arithmetic for the engine's counters: tokens a query
        with `context` cached tokens (itself included) is left with by
        the indexer's choice. Plain arithmetic, numpy or python ints."""
        topk = self.config.index_topk
        return context * (context <= topk) + topk * (context > topk)
