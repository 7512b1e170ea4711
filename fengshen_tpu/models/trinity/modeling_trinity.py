"""Trinity (arcee-ai `afmoe`) in flax: window layers beside full layers.

Stream: `x0 = Embed(ids) * sqrt(hidden)` (`mup_enabled`); each layer
with SANDWICH norms (all RMSNorm, eps `rms_norm_eps`, a learned gain):
`a = h + N2(Attn(N1(h)))`, `out = a + N4(MLP(N3(a)))`; `logits =
Head(norm(x))`, head untied.

- attention: q of `num_attention_heads`, k and v of
  `num_key_value_heads` heads and a gate as wide as q, no bias; RMSNorm
  over a head on q and on k; in a `sliding_attention` layer rotary over
  the whole head (rotate-half, theta `rope_theta`) and a query reads the
  last `sliding_window` keys, its own among them; in a `full_attention`
  layer NO positions and every key; `o = Wo(softmax(q.k / sqrt(D)) v *
  sigmoid(gate))`, the gate elementwise a head value.
- MLP: the first `num_dense_layers` layers a SwiGLU of
  `intermediate_size`; the rest `ops/moe.py RoutedExperts` as DeepSeek-V3
  routes (sigmoid scores in float32, the picks by `scores + bias`, the
  weights the picked scores over their sum times `route_scale`) plus
  `num_shared_experts` shared.

The cache lives at the model, in TWO dicts of the leaf layout
`serving/` builds for any model, because the two kinds of layer keep
their rows differently:

    full_rows:    cached_key / cached_value  [Lf, B, max_len, KVH, D]
    window_rows:  cached_window_key / cached_window_value
                                             [Lw, B, max_len, KVH, D]
    each with its cache_index  [L]  (`[L, B]` in the engine's pool)

The paged pool swaps a dict's rows for `[L, num_blocks, block_size, KVH,
D]` behind the dict's own `block_table`: the full layers' row holds a
lane from position 0, the window layers' is a RING of fixed width
addressed by position modulo its length (`serving/paged_cache.py`), so a
window layer holds and reads the same few blocks at any context. The
layer loop hands the stacks from layer to layer as values; each layer
writes its own index in place.

Three calls, told apart by what the cache shows (static under jit): no
cache (a plain forward: the rows just projected stand in for it); one
token a lane onto any cache (the decode tick: `ops/window_attention.py`
through the `decode_attention` seam); a WINDOW of tokens onto a
contiguous batch-1 cache with a scalar cursor (prefill: a window layer
reads a band, a full layer walks the carried rows). Positions are
physical: a ring is addressed by them, so a lane is filled from
position 0 and padded on the RIGHT
(`serving/paged_cache.positional_leaves` tells the engine).

Layers are unrolled: a scan would slice each layer's `[E, ...]` expert
tables out of a stack, and XLA:TPU copies a sliced table whole before
its grouped matmul reads it (PERF.md, PR 26; ROADMAP M3).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from fengshen_tpu.models.trinity.configuration_trinity import (
    FULL, SLIDING, TrinityConfig)
from fengshen_tpu.models.model_utils import head_rows
from fengshen_tpu.ops.embedding import VocabParallelEmbed
from fengshen_tpu.ops.moe import RoutedExperts, SwiGLU
from fengshen_tpu.ops.norms import RMSNorm
from fengshen_tpu.ops.rotary import apply_rotary_pos_emb
from fengshen_tpu.ops.window_attention import (banded_prefill_walk,
                                               full_decode_attention,
                                               full_prefill_walk,
                                               ring_decode_attention)
from fengshen_tpu.sharding import to_partition_rules, with_logical_constraint

#: logical axes of the parameters. The `[E, ...]` expert tables shard
#: over 'expert' (docs/sharding.md)
PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    ("embed_tokens/embedding", ("vocab", "embed")),
    (r"experts_(gate|up)", ("expert", None, "mlp")),
    (r"experts_down", ("expert", "mlp", None)),
    (r"(gate_proj|up_proj)/kernel", ("embed", "mlp")),
    (r"down_proj/kernel", ("mlp", "embed")),
    (r"self_attn/(q_proj|k_proj|v_proj|gate_proj)/kernel",
     ("embed", "heads")),
    (r"o_proj/kernel", ("heads", "embed")),
    ("lm_head/kernel", ("embed", "vocab")),
    ("norm", ("norm",)),
    (".*", (None,)),
]


def _dt(config: TrinityConfig):
    return jnp.dtype(config.dtype)


class Rows(NamedTuple):
    """One kind's stacks as the layer loop carries them (module
    docstring): `k`, `v` `[L, ...]`, `table` `[L, B, blocks]` or None."""

    k: jax.Array
    v: jax.Array
    table: Optional[jax.Array]


class TrinityCache(NamedTuple):
    """Both kinds' stacks. `start` is each lane's cursor when the call
    began: `[]` on a contiguous cache with a scalar cursor, else `[B]`."""

    full: Rows
    window: Rows
    start: jax.Array


def _dense(cfg: TrinityConfig, feats: int, name: str):
    return nn.Dense(
        feats, use_bias=False, dtype=_dt(cfg),
        param_dtype=jnp.dtype(cfg.param_dtype),
        kernel_init=nn.initializers.normal(cfg.initializer_range), name=name)


def _write(rows: Rows, index: int, start, k, v) -> Rows:
    """This step's K/V, `[B, S, KVH, D]`, into layer `index` of one
    kind's stacks at each lane's cursor, in place: one slice update a
    stack on a contiguous cache with a scalar cursor, else one scatter
    into the stack addressed flat (PERF.md, PR 25). A paged lane goes
    through its table row at `(p // block_size) % width`: the row's
    whole width for a lane held from position 0, whose positions never
    reach it, and the ring's length for a ring. Free lanes park on the
    null block."""
    batch, seq = k.shape[:2]
    if start.ndim == 0:
        at = (index, 0, start, 0, 0)
        return rows._replace(**{
            name: jax.lax.dynamic_update_slice(
                getattr(rows, name),
                x[None].astype(getattr(rows, name).dtype), at)
            for name, x in (("k", k), ("v", v))})
    p = start[:, None] + jnp.arange(seq)[None]                 # [B, S]
    if rows.table is not None:
        num_blocks, block = rows.k.shape[1:3]
        table = rows.table[index]
        blk = jnp.take_along_axis(
            table, (p // block) % table.shape[-1], axis=-1)
        pos = (index * num_blocks + blk) * block + p % block
    else:
        lanes, lane_len = rows.k.shape[1:3]
        pos = (index * lanes + jnp.arange(batch)[:, None]) * lane_len + p
    pos = pos.reshape(-1)

    def put(pool, x):
        flat = pool.reshape((-1,) + pool.shape[3:])
        return flat.at[pos].set(
            x.reshape((batch * seq,) + x.shape[2:]).astype(pool.dtype)
        ).reshape(pool.shape)
    return rows._replace(k=put(rows.k, k), v=put(rows.v, v))


class TrinityAttention(nn.Module):
    """Gated grouped-query attention of one kind of layer; `index` is
    the layer's place among its kind. Returns (output, cache)."""

    config: TrinityConfig
    kind: str

    @nn.compact
    def __call__(self, hidden, position_ids, cache: Optional[TrinityCache],
                 index: int):
        cfg = self.config
        H, G, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
        batch, seq, _ = hidden.shape
        eps = cfg.rms_norm_eps
        sliding = self.kind == SLIDING
        q = _dense(cfg, H * D, "q_proj")(hidden).reshape(batch, seq, H, D)
        k = _dense(cfg, G * D, "k_proj")(hidden).reshape(batch, seq, G, D)
        v = _dense(cfg, G * D, "v_proj")(hidden).reshape(batch, seq, G, D)
        gate = _dense(cfg, H * D, "gate_proj")(hidden)
        q = RMSNorm(epsilon=eps, name="q_norm")(q)
        k = RMSNorm(epsilon=eps, name="k_norm")(k)
        if sliding:
            q, k = apply_rotary_pos_emb(q, k, position_ids,
                                        base=cfg.rope_theta)
        which = "window" if sliding else "full"
        if cache is None:
            out = self._window(q, k, v, jnp.int32(0))
        else:
            rows = _write(getattr(cache, which), index, cache.start, k, v)
            cache = cache._replace(**{which: rows})
            if seq == 1:
                out = self._tick(q, rows, cache.start, index)
            elif cache.start.ndim:
                raise ValueError(
                    f"a window of {seq} tokens onto a pool of lanes: a "
                    "ring holds a lane's last tokens only; prefill runs "
                    "on a contiguous batch-1 cache")
            else:
                out = self._window(q, rows.k[index], rows.v[index],
                                   cache.start)
        out = with_logical_constraint(out, ("batch", "seq", "heads", None))
        out = (out.astype(jnp.float32).reshape(batch, seq, H * D) *
               jax.nn.sigmoid(gate.astype(jnp.float32))).astype(_dt(cfg))
        return _dense(cfg, cfg.hidden_size, "o_proj")(out), cache

    def _window(self, q, k_rows, v_rows, start):
        """A window of queries at `start ...` over a contiguous lane
        whose rows `start ...` are the window's own."""
        if self.kind == SLIDING:
            return banded_prefill_walk(q, k_rows, v_rows, start,
                                       window=self.config.sliding_window)
        return full_prefill_walk(q, k_rows, v_rows, start)

    def _tick(self, q, rows: Rows, start, index: int):
        """One query a lane, its own row already written. No mask but
        the cursor's: positions are physical and every cached row is
        real (a lane is filled from 0, never left-padded)."""
        batch = q.shape[0]
        t = jnp.broadcast_to(start, (batch,))
        window = self.config.sliding_window
        if rows.table is not None:
            if self.kind == SLIDING:
                return ring_decode_attention(
                    q, rows.k, rows.v, rows.table[index], t, window=window,
                    layer=index)
            return full_decode_attention(q, rows.k, rows.v,
                                         rows.table[index], t, layer=index)
        # a contiguous lane is whole blocks in a row: a free reshape and
        # a table that counts (a ring as long as the lane never wraps)
        lanes, lane_len = rows.k.shape[1:3]
        block = math.gcd(lane_len, 128)
        per = lane_len // block
        k, v = (x.reshape((-1, block) + x.shape[3:])
                for x in (rows.k, rows.v))
        table = (index * lanes + jnp.arange(batch)[:, None]) * per + \
            jnp.arange(per)[None]
        if self.kind == SLIDING:
            return ring_decode_attention(q, k, v, table, t, window=window)
        return full_decode_attention(q, k, v, table, t)


class TrinityDecoderLayer(nn.Module):
    config: TrinityConfig
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, hidden, position_ids, cache, index):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            epsilon=cfg.rms_norm_eps, name=name)
        h, cache = TrinityAttention(cfg, self.kind, name="self_attn")(
            norm("input_layernorm")(hidden), position_ids, cache, index)
        hidden = hidden + norm("post_attention_layernorm")(h)
        h = norm("pre_mlp_layernorm")(hidden)
        shared = dict(dtype=_dt(cfg), param_dtype=jnp.dtype(cfg.param_dtype),
                      initializer_range=cfg.initializer_range, name="mlp")
        if self.dense:
            h = SwiGLU(cfg.hidden_size, cfg.intermediate_size, **shared)(h)
        else:
            h = RoutedExperts(
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.moe_intermediate_size,
                num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
                scoring="sigmoid", score_bias=True,
                norm_topk_prob=cfg.route_norm,
                routed_scaling_factor=cfg.route_scale,
                n_shared_experts=cfg.num_shared_experts,
                experts_held=cfg.experts_held, shared_here=cfg.shared_here,
                **shared)(h)
        return hidden + norm("post_mlp_layernorm")(h), cache


class _RowStack(nn.Module):
    """One kind's cache dict (module docstring): declares the leaves
    and hands back their variables and the dict's table, if paged."""

    prefix: str

    @nn.compact
    def __call__(self, shape, dtype):
        if self.has_variable("cache", self.prefix + "key_scale"):
            raise ValueError(
                "this cache has no int8 form: a ring is read through a "
                "table of its live blocks as the rows lie; use "
                "kv_dtype='fp32'")
        primed = self.has_variable("cache", self.prefix + "key")
        k = self.variable("cache", self.prefix + "key", jnp.zeros, shape,
                          dtype)
        v = self.variable("cache", self.prefix + "value", jnp.zeros, shape,
                          dtype)
        index = self.variable("cache", "cache_index",
                              lambda: jnp.zeros(shape[:1], jnp.int32))
        table = self.get_variable("cache", "block_table") \
            if self.has_variable("cache", "block_table") else None
        return k, v, index, table, primed


class TrinityModel(nn.Module):
    config: TrinityConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True):
        # positions are physical and padding is on the right: a padded
        # token is a query nothing real reads (attention is causal, a
        # token's experts are its own), so the mask is not consulted
        del deterministic, attention_mask
        cfg = self.config
        batch, seq = input_ids.shape
        hidden = VocabParallelEmbed(
            cfg.vocab_size, cfg.hidden_size, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            name="embed_tokens")(input_ids)
        if cfg.mup_enabled:
            # in float32: bf16 would round the multiplier itself
            hidden = (hidden.astype(jnp.float32) *
                      math.sqrt(cfg.hidden_size)).astype(_dt(cfg))
        hidden = with_logical_constraint(hidden, ("batch", "seq", None))
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(seq)[None],
                                            (batch, seq))

        # the rows each kind declares (module docstring); on the pass
        # that creates the leaves nothing is cached yet
        kinds = {FULL: cfg.layers_of(FULL), SLIDING: cfg.layers_of(SLIDING)}
        cache, stacks = None, {}
        if init_cache or self.has_variable("cache", "full_rows") or \
                self.has_variable("cache", "window_rows"):
            for kind, name, prefix in ((FULL, "full_rows", "cached_"),
                                       (SLIDING, "window_rows",
                                        "cached_window_")):
                if kinds[kind]:
                    stacks[kind] = _RowStack(prefix, name=name)(
                        (len(kinds[kind]), batch,
                         cfg.max_position_embeddings,
                         cfg.num_key_value_heads, cfg.head_dim), _dt(cfg))
            if all(s[4] for s in stacks.values()):
                none = Rows(None, None, None)
                rows = {kind: Rows(s[0].value, s[1].value, s[3])
                        for kind, s in stacks.items()}
                cache = TrinityCache(
                    rows.get(FULL, none), rows.get(SLIDING, none),
                    next(iter(stacks.values()))[2].value[0])

        for i, kind in enumerate(cfg.layer_types):
            hidden, cache = TrinityDecoderLayer(
                cfg, kind, i < cfg.num_dense_layers, name=f"layers_{i}")(
                hidden, position_ids, cache, kinds[kind].index(i))
        if cache is not None:
            for kind, rows in ((FULL, cache.full), (SLIDING, cache.window)):
                if kind in stacks:
                    k_var, v_var, index_var = stacks[kind][:3]
                    k_var.value, v_var.value = rows.k, rows.v
                    index_var.value = index_var.value + seq
        return RMSNorm(epsilon=cfg.rms_norm_eps, name="norm")(hidden)


class TrinityForCausalLM(nn.Module):
    """Untied LM head on the stack; the serving engine's cache contract
    (`init_cache`, a mutable "cache" collection) as `LlamaForCausalLM`."""

    config: TrinityConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, logits_row=None):
        """`logits_row`: the one row whose logits the caller keeps
        (`[B, 1, V]`), or None for every row's (`head_rows`)."""
        cfg = self.config
        hidden = TrinityModel(cfg, name="model")(
            input_ids, attention_mask, position_ids, init_cache,
            deterministic)
        return _dense(cfg, cfg.vocab_size, "lm_head")(
            head_rows(hidden, logits_row))

    def init_params(self, rng, seq_len: int = 8):
        return self.init(rng, jnp.zeros((1, seq_len), jnp.int32))["params"]

    def partition_rules(self):
        return to_partition_rules(PARAM_LOGICAL_AXES)

    def window_tokens(self, context):
        """Host arithmetic for the engine's counters and its ring: keys
        a window layer's query with `context` cached tokens (itself
        included) reads. Plain arithmetic, numpy or python ints."""
        window = self.config.sliding_window
        return context * (context <= window) + window * (context > window)
