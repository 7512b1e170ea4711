"""MiniCPM-SALA in flax: two kinds of layer in the published order.

Stream: `x0 = scale_emb * Embed(ids)`; each layer, pre-norm,
`x += a * Mixer(RMSNorm(x))`, `x += a * MLP(RMSNorm(x))` with `a =
scale_depth / sqrt(depth)` (`SalaConfig.residual_scale`); `logits =
Head(RMSNorm(x) / (hidden_size / dim_model_base))`, head untied.

- `lightning-attn` (`ops/lightning_attention.py`): per-head RMSNorm on
  q and k, RoPE on all of a head, the decayed `[D, D]` state a head,
  then `(RMSNorm(concat o) * sigmoid(h W_z)) W_o`;
- `minicpm4` (`ops/sparse_attention.py`): grouped-query attention with
  per-head RMSNorm on q and k and NO positions; up to `dense_len`
  tokens of context plain causal attention, past it each query reads
  `topk` blocks chosen by its scores over pooled keys; then
  `(concat o * sigmoid(h W_z)) W_o`.

The cache lives at the model, not in the layers (as `models/joyai`
keeps its latent rows), in the leaf layout `serving/` builds for any
model — rows a token for the sparse layers, a state a lane for the
linear ones:

    cached_key / cached_value  [Ls, B, max_len, 1, KVH * D]  (a row a token)
    cached_key_pooled          [Ls, B, max_len // s, 1, KVH * D]  (a row
                                           every `kernel_stride` tokens)
    cache_index                [Ls]  (`[Ls, B]` in the engine's pool)
    state_lightning            [Ll, B, H, D, D]  float32

(`Ls` sparse layers, `Ll` linear; a token's two KV heads are folded
into ONE row of 256 values: whole 128-value lanes whichever head is
read, so no read re-lays the pool out — `sparse_attention.gather_blocks`.)
The paged pool swaps the first three
for `[Ls, num_blocks, block_size (// s), 1, KVH * D]` behind one
`block_table [Ls, B, max_blocks]`: a pool block holds its tokens' K/V
rows and the pooled keys whose window STARTS in it. The layer loop
hands the stacks from layer to layer as values; each layer writes its
own index in place.

Three calls, told apart by what the cache shows (static under jit):
no cache (a plain forward: the rows just projected stand in for the
cache); one token a lane onto any cache (the decode tick: the linear
layers step their state where `live` is set, the sparse layers append
a pooled key whenever a `kernel_size` window completes and read
through the `decode_attention` seam's sparse entry, which is given
room for a lane still within `dense_len` only on a tick that has one);
a WINDOW of tokens onto a
contiguous cache with a scalar cursor (prefill: the first or a later
window of a prompt — the state and the rows so far are whatever the
cache holds). Positions are physical: a lane is filled from position 0
and padded on the right; `attention_mask`, over cache positions, says
which of a window's tokens are real (a padded token enters no state).

Layers are unrolled: two kinds in an irregular order, and a scan over
one kind would slice its stacked weights (PERF.md, PR 26).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from fengshen_tpu.models.llama.modeling_llama import LlamaMLP
from fengshen_tpu.models.model_utils import flat_rows as _flat_rows
from fengshen_tpu.models.model_utils import head_rows
from fengshen_tpu.models.model_utils import token_mask as _token_mask
from fengshen_tpu.models.model_utils import write_rows as _write_rows
from fengshen_tpu.models.sala.configuration_sala import (LINEAR, SPARSE,
                                                         SalaConfig)
from fengshen_tpu.ops.embedding import VocabParallelEmbed
from fengshen_tpu.ops.lightning_attention import (DECODE_SCOPE,
                                                  PREFILL_SCOPE,
                                                  lightning_decode,
                                                  lightning_prefill,
                                                  lightning_slopes)
from fengshen_tpu.ops.norms import RMSNorm
from fengshen_tpu.ops.pallas.decode_attention import (
    sparse_decode_attention)
from fengshen_tpu.ops.rotary import apply_rotary_pos_emb
from fengshen_tpu.ops.sparse_attention import (POOL_SCOPE, pool_window,
                                               sparse_prefill_attention)
from fengshen_tpu.sharding import to_partition_rules, with_logical_constraint

PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    ("embed_tokens/embedding", ("vocab", "embed")),
    (r"(q_proj|k_proj|v_proj|z_proj)/kernel", ("embed", "heads")),
    (r"o_proj/kernel", ("heads", "embed")),
    (r"(gate_proj|up_proj)/kernel", ("embed", "mlp")),
    (r"down_proj/kernel", ("mlp", "embed")),
    ("lm_head/kernel", ("embed", "vocab")),
    ("norm", ("norm",)),
    (".*", (None,)),
]

#: out of every pool's range: a scatter to it is dropped
_NOWHERE = jnp.iinfo(jnp.int32).max


def _dt(config: SalaConfig):
    return jnp.dtype(config.dtype)


class SalaCache(NamedTuple):
    """The stacks the layer loop carries (module docstring). `start` is
    each lane's cursor when the call began: `[]` on a contiguous cache
    with a scalar cursor, else `[B]`."""

    k: jax.Array
    v: jax.Array
    pooled: jax.Array
    table: Optional[jax.Array]
    state: jax.Array
    start: jax.Array


class _Projections(nn.Module):
    """What both mixers share: bias-free projections and the per-head
    RMSNorm of q and k (one learned `[D]` a layer)."""

    config: SalaConfig

    def dense(self, feats, name):
        cfg = self.config
        return nn.Dense(
            feats, use_bias=False, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name=name)

    def qkv(self, hidden, heads, kv_heads, dim):
        batch, seq, _ = hidden.shape
        eps = self.config.rms_norm_eps
        q = self.dense(heads * dim, "q_proj")(hidden).reshape(
            batch, seq, heads, dim)
        k = self.dense(kv_heads * dim, "k_proj")(hidden).reshape(
            batch, seq, kv_heads, dim)
        v = self.dense(kv_heads * dim, "v_proj")(hidden).reshape(
            batch, seq, kv_heads, dim)
        return (RMSNorm(epsilon=eps, name="q_norm")(q),
                RMSNorm(epsilon=eps, name="k_norm")(k), v)

    def gated_out(self, out, hidden):
        gate = nn.sigmoid(self.dense(out.shape[-1], "z_proj")(hidden))
        return self.dense(self.config.hidden_size, "o_proj")(out * gate)


class SalaLinearAttention(_Projections):
    """`lightning-attn`. Returns (output, cache)."""

    @nn.compact
    def __call__(self, hidden, attention_mask, position_ids,
                 cache: Optional[SalaCache], layer: int, live):
        cfg = self.config
        H, D = cfg.lightning_nh, cfg.lightning_head_dim
        batch, seq, _ = hidden.shape
        q, k, v = self.qkv(hidden, H, H, D)
        q, k = apply_rotary_pos_emb(q, k, position_ids, base=cfg.rope_theta)
        slopes = lightning_slopes(H)
        tick = cache is not None and seq == 1
        # the layer's slice of the state stack is read and written back
        # under the form's own scope: XLA fuses the decay-and-add into
        # the in-place update, and a trace must find that time there
        with jax.named_scope(DECODE_SCOPE if tick else PREFILL_SCOPE):
            if cache is None:
                state = jnp.zeros((batch, H, D, D), jnp.float32)
                mask = None if attention_mask is None else \
                    attention_mask.astype(bool)
            else:
                state = cache.state[layer]
            if tick:
                out, state = lightning_decode(q[:, 0], k[:, 0], v[:, 0],
                                              state, slopes, live)
                out = out[:, None]
            else:
                if cache is not None:
                    if cache.start.ndim:
                        raise ValueError(
                            f"a window of {seq} tokens onto a pool of "
                            "lanes: a recurrent state takes one token a "
                            "lane a tick (a rejected draft cannot be "
                            "rolled back out of it); prefill runs on a "
                            "contiguous batch-1 cache")
                    mask = _token_mask(attention_mask, cache.start, seq,
                                       cfg.max_position_embeddings)
                out, state = lightning_prefill(q, k, v, state, slopes, mask,
                                               chunk=cfg.lightning_chunk)
            if cache is not None:
                cache = cache._replace(
                    state=cache.state.at[layer].set(state))
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        out = RMSNorm(epsilon=cfg.rms_norm_eps, name="o_norm")(
            out.reshape(batch, seq, H * D))
        return self.gated_out(out, hidden), cache


def _append_pooled(cache: SalaCache, layer: int, spec):
    """Decode: where the token at a lane's cursor completes a pooled
    window, pool the window's rows (the one just written among them)
    and write the key where the window starts. The same float32 mean
    over the same cached rows as a prefill window takes."""
    K, s = spec.kernel_size, spec.kernel_stride
    p = jnp.broadcast_to(cache.start, cache.k.shape[1:2] if
                         cache.table is None else
                         cache.table.shape[1:2])           # [B]
    first = p + 1 - K
    done = (first >= 0) & (first % s == 0)
    with jax.named_scope(POOL_SCOPE):
        rows = _flat_rows(cache, layer, jnp.maximum(
            first[:, None] + jnp.arange(K)[None], 0))
        flat_k = cache.k.reshape((-1,) + cache.k.shape[3:])
        key = flat_k[rows].astype(jnp.float32).mean(axis=1)    # [B, 1, GD]
        to = jnp.where(done, _flat_rows(
            cache, layer, jnp.maximum(first, 0)[:, None], s)[:, 0],
            _NOWHERE)
        flat_p = cache.pooled.reshape((-1,) + cache.pooled.shape[3:])
        return cache._replace(pooled=flat_p.at[to].set(
            key.astype(flat_p.dtype), mode="drop"
        ).reshape(cache.pooled.shape))


def _pool_window(cache: SalaCache, layer: int, seq: int, spec):
    """Prefill: every pooled window that ends inside this window of
    `seq` tokens (a few that do not yet: they hold garbage no query may
    read and are written again when they complete)."""
    K, s = spec.kernel_size, spec.kernel_stride
    batch, lane_len = cache.k.shape[1:3]
    n_pooled = cache.pooled.shape[2]
    count = -(-seq // s) + spec.reach + 1
    j0 = jnp.maximum(cache.start // s - spec.reach, 0)
    with jax.named_scope(POOL_SCOPE):
        tokens = j0 * s + jnp.arange(s * (count - 1) + K)
        lane = (layer * batch + jnp.arange(batch))[:, None]
        flat_k = cache.k.reshape((-1,) + cache.k.shape[3:])
        rows = flat_k[lane * lane_len +
                      jnp.minimum(tokens, lane_len - 1)[None]]
        keys = pool_window(rows, spec, count)              # [B, count, 1, GD]
        j = j0 + jnp.arange(count)
        to = jnp.where(j < n_pooled, lane * n_pooled + j[None], _NOWHERE)
        flat_p = cache.pooled.reshape((-1,) + cache.pooled.shape[3:])
        return cache._replace(pooled=flat_p.at[to.reshape(-1)].set(
            keys.reshape((-1,) + keys.shape[2:]).astype(flat_p.dtype),
            mode="drop").reshape(cache.pooled.shape))


class SalaSparseAttention(_Projections):
    """`minicpm4` (InfLLM-V2). Returns (output, cache)."""

    @nn.compact
    def __call__(self, hidden, attention_mask, position_ids,
                 cache: Optional[SalaCache], layer: int, live):
        del position_ids                        # attn_use_rope false
        cfg, spec = self.config, self.config.sparse
        H, G, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        batch, seq, _ = hidden.shape
        q, k, v = self.qkv(hidden, H, G, D)
        if cache is None:
            out = _uncached_sparse(q, k, v, spec)
        elif seq == 1:
            cache = _append_pooled(_write_rows(cache, layer, k=k, v=v), layer,
                                   spec)
            out = self._tick(q, cache, layer, live)
        else:
            if cache.start.ndim:
                raise ValueError(
                    f"a window of {seq} tokens onto a pool of lanes: the "
                    "pooled keys take one token a lane a tick; prefill "
                    "runs on a contiguous batch-1 cache")
            cache = _pool_window(_write_rows(cache, layer, k=k, v=v), layer,
                                 seq, spec)
            lane = lambda x: x[layer].reshape(  # noqa: E731
                batch, -1, G, D)
            out = sparse_prefill_attention(
                q, lane(cache.k), lane(cache.v), lane(cache.pooled),
                cache.start, spec)
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        return self.gated_out(out.reshape(batch, seq, H * D), hidden), cache

    def _tick(self, q, cache: SalaCache, layer: int, live):
        """One query a lane through the sparse entry of the seam, with
        room for a whole `dense_len` context only on a tick that has a
        live lane still within it. No mask: positions are physical and
        every cached row is real (a lane is filled from 0, never
        left-padded)."""
        spec = self.config.sparse
        batch = q.shape[0]
        t = jnp.broadcast_to(cache.start, (batch,))
        if cache.table is not None:
            pools, table, at = (cache.pooled, cache.k, cache.v), \
                cache.table[layer], layer
        else:
            # a contiguous lane is `max_len // block_size` blocks in a
            # row: a free reshape and a table that counts
            B = spec.block_size
            lanes, lane_len = cache.k.shape[1:3]
            if lane_len % B:
                raise ValueError(f"cache length {lane_len} must be whole "
                                 f"{B}-token blocks")
            per = lane_len // B
            pools = tuple(x.reshape((-1, x.shape[2] // per) + x.shape[3:])
                          for x in (cache.pooled, cache.k, cache.v))
            table = (layer * lanes + jnp.arange(batch)[:, None]) * per + \
                jnp.arange(per)[None]
            at = None
        short = t + 1 <= spec.dense_len
        if live is not None:
            short = short & live
        return jax.lax.cond(
            short.any(),
            lambda: sparse_decode_attention(q, *pools, table, t, spec,
                                            layer=at, dense=True),
            lambda: sparse_decode_attention(q, *pools, table, t, spec,
                                            layer=at))


def _uncached_sparse(q, k, v, spec):
    """A plain forward: the rows just projected, padded to whole blocks,
    are the cache."""
    seq, B = q.shape[1], spec.block_size
    extent = -(-seq // B) * B
    rows = ((0, 0), (0, extent + spec.kernel_size - seq), (0, 0), (0, 0))
    with jax.named_scope(POOL_SCOPE):
        pooled = pool_window(jnp.pad(k, rows), spec,
                             extent // spec.kernel_stride).astype(k.dtype)
    fit = ((0, 0), (0, extent - seq), (0, 0), (0, 0))
    return sparse_prefill_attention(q, jnp.pad(k, fit), jnp.pad(v, fit),
                                    pooled, jnp.int32(0), spec)


class SalaDecoderLayer(nn.Module):
    config: SalaConfig
    mixer: str

    @nn.compact
    def __call__(self, hidden, attention_mask, position_ids, cache, layer,
                 live):
        cfg = self.config
        a = cfg.residual_scale
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="input_layernorm")(hidden)
        kind = SalaSparseAttention if self.mixer == SPARSE else \
            SalaLinearAttention
        h, cache = kind(cfg, name="self_attn")(
            h, attention_mask, position_ids, cache, layer, live)
        hidden = hidden + (a * h).astype(hidden.dtype)
        h = RMSNorm(epsilon=cfg.rms_norm_eps,
                    name="post_attention_layernorm")(hidden)
        h = LlamaMLP(cfg, name="mlp")(h)
        return hidden + (a * h).astype(hidden.dtype), cache


class SalaModel(nn.Module):
    config: SalaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, live=None):
        del deterministic                       # no dropout anywhere
        cfg = self.config
        batch, seq = input_ids.shape
        kinds = cfg.mixer_types
        n_sparse, n_linear = kinds.count(SPARSE), kinds.count(LINEAR)
        hidden = VocabParallelEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            name="embed_tokens")(input_ids)
        hidden = (hidden * cfg.scale_emb).astype(_dt(cfg))
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
                    "this cache has no int8 form: the pooled keys are "
                    "means of the cached rows; use kv_dtype='fp32'")
            G, D = cfg.num_key_value_heads, cfg.head_dim
            max_len, s = cfg.max_position_embeddings, cfg.kernel_stride
            rows = (n_sparse, batch, max_len, 1, G * D)
            k_var = self.variable("cache", "cached_key", jnp.zeros, rows,
                                  _dt(cfg))
            v_var = self.variable("cache", "cached_value", jnp.zeros, rows,
                                  _dt(cfg))
            p_var = self.variable(
                "cache", "cached_key_pooled", jnp.zeros,
                (n_sparse, batch, max_len // s, 1, G * D), _dt(cfg))
            index_var = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((n_sparse,), jnp.int32))
            s_var = self.variable(
                "cache", "state_lightning", jnp.zeros,
                (n_linear, batch, cfg.lightning_nh, cfg.lightning_head_dim,
                 cfg.lightning_head_dim), jnp.float32)
            if primed:
                table = self.get_variable("cache", "block_table") \
                    if self.has_variable("cache", "block_table") else None
                cache = SalaCache(k_var.value, v_var.value, p_var.value,
                                  table, s_var.value, index_var.value[0])

        seen = {SPARSE: 0, LINEAR: 0}
        for i, kind in enumerate(kinds):
            hidden, cache = SalaDecoderLayer(cfg, kind, name=f"layers_{i}")(
                hidden, attention_mask, position_ids, cache, seen[kind],
                live)
            seen[kind] += 1
        if cache is not None:
            k_var.value, v_var.value = cache.k, cache.v
            p_var.value, s_var.value = cache.pooled, cache.state
            index_var.value = index_var.value + seq
        return RMSNorm(epsilon=cfg.rms_norm_eps, name="norm")(hidden)


class SalaForCausalLM(nn.Module):
    """Untied LM head on the stack; the serving engine's cache contract
    (`init_cache`, a mutable "cache" collection) as `LlamaForCausalLM`,
    and `live`: the decode tick's `[B]` mask of the lanes whose state
    may move."""

    config: SalaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, live=None,
                 logits_row=None):
        """`logits_row`: the one row whose logits the caller keeps
        (`[B, 1, V]`), or None for every row's (`head_rows`)."""
        cfg = self.config
        hidden = SalaModel(cfg, name="model")(
            input_ids, attention_mask, position_ids, init_cache,
            deterministic, live)
        hidden = head_rows(hidden, logits_row)
        hidden = hidden / (cfg.hidden_size / cfg.dim_model_base)
        return nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="lm_head")(hidden.astype(_dt(cfg)))

    def init_params(self, rng, seq_len: int = 8):
        return self.init(rng, jnp.zeros((1, seq_len), jnp.int32))["params"]

    def partition_rules(self):
        return to_partition_rules(PARAM_LOGICAL_AXES)

    def attended_tokens(self, context):
        """Host arithmetic for the engine's counters: tokens a query
        with `context` cached tokens reads in a sparse layer."""
        return self.config.sparse.attended_tokens(context)
