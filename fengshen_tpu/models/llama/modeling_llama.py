"""LLaMA in flax, TPU-first.

Functional parity with the reference's TP LLaMA
(reference: fengshen/models/llama/modeling_llama.py:97-405, built from
megatron ``Embedding`` + ``ParallelTransformerLayer`` + ``ParallelLinear``):
RMSNorm pre-norm, rotary, SwiGLU with `multiple_of` rounding, causal LM head,
KV-cache generation. The Megatron TP layer classes collapse into
PARTITION_RULES below — GSPMD inserts the collectives the reference coded as
autograd Functions (SURVEY.md §2.1), and `parallel_output` (reference:
modeling_llama.py:246-264) disappears: the loss consumes sharded logits via
vocab-parallel CE.

Parameter naming matches HF's LlamaForCausalLM so torch checkpoints import
by path mapping (see convert.py), replacing the reference's offline TP
resharding scripts (reference: fengshen/utils/llama_convert/*, SURVEY.md §5.4).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax.traverse_util import flatten_dict
from fengshen_tpu.models.llama.configuration_llama import LlamaConfig
from fengshen_tpu.models.model_utils import head_rows
from fengshen_tpu.ops.attention import dot_product_attention
from fengshen_tpu.ops.flash_attention import prefill_attention
from fengshen_tpu.ops.pallas.decode_attention import (_MAX_QUERY_WINDOW,
                                                      decode_attention)
from fengshen_tpu.ops.embedding import VocabParallelEmbed
from fengshen_tpu.ops.masks import causal_mask
from fengshen_tpu.ops.norms import RMSNorm
from fengshen_tpu.ops.rotary import apply_rotary_pos_emb
from fengshen_tpu.sharding import to_partition_rules, with_logical_constraint

#: Megatron-equivalent sharding layout (reference: mpu/layers.py:55-470 —
#: vocab-parallel embedding, column-parallel QKV/gate/up, row-parallel
#: o_proj/down) expressed as LOGICAL axes; the active rules table
#: (fengshen_tpu/sharding/rules.py) maps them onto the mesh. flax Dense
#: kernels are [in, out]: column-parallel shards out, row-parallel
#: shards in; 'embed' picks up ZeRO-3-style param sharding.
LLAMA_PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    ("embed_tokens/embedding", ("vocab", "embed")),
    (r"(q_proj|k_proj|v_proj)/kernel", ("embed", "heads")),
    (r"(gate_proj|up_proj)/kernel", ("embed", "mlp")),
    (r"o_proj/kernel", ("heads", "embed")),
    (r"down_proj/kernel", ("mlp", "embed")),
    (r"experts_(gate|up)", ("expert", None, "mlp")),
    (r"experts_down", ("expert", "mlp", None)),
    ("lm_head/kernel", ("embed", "vocab")),
    ("norm", ("norm",)),
    (".*", (None,)),
]

#: rules for scan_layers=True — stacked layer params carry a leading [L]
#: dim ('layers', never mesh-sharded), so layer-internal dims shift right
SCAN_PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    ("embed_tokens/embedding", ("vocab", "embed")),
    (r"layers/.*(q_proj|k_proj|v_proj)/kernel", ("layers", "embed", "heads")),
    (r"layers/.*(gate_proj|up_proj)/kernel", ("layers", "embed", "mlp")),
    (r"layers/.*o_proj/kernel", ("layers", "heads", "embed")),
    (r"layers/.*down_proj/kernel", ("layers", "mlp", "embed")),
    (r"layers/.*experts_(gate|up)", ("layers", "expert", None, "mlp")),
    (r"layers/.*experts_down", ("layers", "expert", "mlp", None)),
    ("lm_head/kernel", ("embed", "vocab")),
    ("norm", ("norm",)),
    (".*", (None,)),
]

#: resolved against the default rules table at import time for callers
#: that want concrete PartitionSpecs; `partition_rules()` re-resolves so
#: a `use_rules(...)` scope takes effect
PARTITION_RULES = to_partition_rules(LLAMA_PARAM_LOGICAL_AXES)
SCAN_PARTITION_RULES = to_partition_rules(SCAN_PARAM_LOGICAL_AXES)


def _dt(config: LlamaConfig):
    return jnp.dtype(config.dtype)


class CacheView(NamedTuple):
    """What `_update_cache` hands the decode_attention dispatch seam
    (fengshen_tpu/ops/pallas/decode_attention.py): the cache in its
    NATIVE layout — the paged pool stays `[num_blocks, block_size, kv,
    hd]` (or the `[L, ...]` stack of them a scan_layers model carries)
    behind its `block_table` (the Mosaic kernel reads it through
    the table; the xla lowering gathers), and int8 pools stay int8
    with their per-(token, head) scales (dequant happens inside the
    attention read on either path)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array]
    v_scale: Optional[jax.Array]
    block_table: Optional[jax.Array]
    #: [B, Sq, L] bool over the (virtual) lane
    valid: jax.Array
    #: set when k/v (and the scales) are the `[L, num_blocks, ...]`
    #: stacks a scan_layers model carries: the layer to read
    layer: Optional[jax.Array] = None


class LlamaMLP(nn.Module):
    """SwiGLU (reference: LLaMAParallelMLP,
    fengshen/models/megatron/layers/transformer.py:571-623)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        inter = cfg.intermediate_size
        if inter is None:
            # 2/3·4h rounded up to multiple_of (reference: :589-590)
            inter = int(2 * 4 * cfg.hidden_size / 3)
            inter = cfg.multiple_of * (
                (inter + cfg.multiple_of - 1) // cfg.multiple_of)
        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name=name)
        gate = dense(inter, "gate_proj")(x)
        up = dense(inter, "up_proj")(x)
        h = nn.silu(gate) * up
        h = with_logical_constraint(h, ("batch", "seq", "mlp"))
        return dense(cfg.hidden_size, "down_proj")(h)


class LlamaAttention(nn.Module):
    """Rotary MHA/GQA with KV cache (reference: ParallelSelfAttention,
    fengshen/models/megatron/layers/transformer.py:175-568; KV-cache concat
    for generation at :529-537)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, attention_mask=None, position_ids=None,
                 init_cache: bool = False, deterministic: bool = True,
                 cache_empty: bool = False, layer=None):
        cfg = self.config
        n_heads, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
        head_dim = cfg.head_dim
        batch, seq, _ = hidden.shape

        dense = lambda feats, name: nn.Dense(  # noqa: E731
            feats, use_bias=False, dtype=_dt(cfg),
            param_dtype=jnp.dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name=name)
        q = dense(n_heads * head_dim, "q_proj")(hidden)
        k = dense(n_kv * head_dim, "k_proj")(hidden)
        v = dense(n_kv * head_dim, "v_proj")(hidden)
        q = q.reshape(batch, seq, n_heads, head_dim)
        k = k.reshape(batch, seq, n_kv, head_dim)
        v = v.reshape(batch, seq, n_kv, head_dim)

        if position_ids is None:
            position_ids = jnp.arange(seq)[None, :]
        q, k = apply_rotary_pos_emb(q, k, position_ids, base=cfg.rope_theta)

        is_decode = self.has_variable("cache", "cached_key") or init_cache
        impl = cfg.attention_impl
        if is_decode:
            # every (layout, dtype, spec_mode) decode combo routes
            # through ONE dispatch seam (docs/kernels.md): the Mosaic
            # kernel reads paged pools through the block table with no
            # gather copy and dequantizes int8 in registers; the xla
            # lowering replays the stock gather → dequant → GQA repeat
            # → dense chain op-for-op, so CPU tier-1 pins decode
            # token-identical through the seam
            view = self._update_cache(k, v, attention_mask, layer)
            if cache_empty and seq > _MAX_QUERY_WINDOW and \
                    self._is_lockstep_cache():
                # a whole prompt onto an empty cache: the K/V just
                # projected ARE every key a query may see, so the read
                # skips the cache's extent (rows of zeros the mask
                # throws away) and the seam's dense lowering over it.
                # The write above is the same; the tick and the verify
                # windows (short, or onto a cache that holds a prefix)
                # stay on the seam
                out = prefill_attention(q, k, v, attention_mask)
            else:
                out = decode_attention(
                    q, view.k, view.v, view.valid,
                    k_scale=view.k_scale, v_scale=view.v_scale,
                    block_table=view.block_table, layer=view.layer,
                    dequant_dtype=_dt(cfg))
        else:
            mask = causal_mask(seq, k.shape[1])[None, None]
            if attention_mask is not None:
                if getattr(cfg, "packed_sequences", False):
                    # packed rows: attention_mask carries per-example
                    # segment ids (0 = pad) — block-diagonal causal mask
                    seg_m = attention_mask.astype(jnp.int32)
                    mask = mask & (seg_m[:, None, :, None] ==
                                   seg_m[:, None, None, :])
                else:
                    mask = mask & \
                        attention_mask[:, None, None, :].astype(bool)

            if n_kv != n_heads and impl != "flash":
                # GQA: repeat kv heads for the dense/ring paths; the
                # flash dispatch handles grouped KV natively (the Pallas
                # kernel reads each KV head once per group from HBM)
                rep = n_heads // n_kv
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)

            if impl in ("flash", "ring", "ulysses", "sequence"):
                # a padding mask maps to segment ids (pads = segment 0),
                # so padded SFT batches stay on the fused/ring paths
                seg = None if attention_mask is None else \
                    attention_mask.astype(jnp.int32)
                if impl == "flash":
                    from fengshen_tpu.ops.flash_attention import (
                        flash_attention)
                    out = flash_attention(q, k, v, causal=True,
                                          segment_ids=seg)
                else:
                    out = dot_product_attention(q, k, v, impl=impl,
                                                segment_ids=seg)
            else:
                out = dot_product_attention(q, k, v, mask=mask)

        out = with_logical_constraint(
            out, ("batch", "seq", "heads", None))
        out = out.reshape(batch, seq, n_heads * head_dim)
        return dense(cfg.hidden_size, "o_proj")(out)

    def _is_lockstep_cache(self) -> bool:
        """Whether the cache `_update_cache` has just written is the
        contiguous one with a scalar `cache_index` (`utils.generate`),
        not the slot pool or the paged pool. Static under jit: variable
        names and ranks."""
        return not self.has_variable("cache", "block_table") and \
            self.get_variable("cache", "cache_index").ndim == 0

    def _update_cache(self, k, v, attention_mask, layer=None):
        """flax mutable-cache decode (same role as the reference's KV concat,
        reference: transformer.py:529-537, but with static shapes for XLA:
        the cache is preallocated at max length and updated in place).

        Three physical layouts share this entry point, detected from the
        cache variables themselves (shapes are static under jit):

        - scalar `cache_index`: lockstep batch decode (`utils.generate`);
        - `[B]` vector index: the serving slot pool — every lane at its
          own position, optionally int8 (a `cached_key_scale` variable
          marks the quantized pool);
        - `block_table` present: the paged pool
          (`fengshen_tpu/serving/paged_cache.py`) — lanes indirect
          through per-slot block lists into a shared block pool.

        Returns a :class:`CacheView` in the cache's NATIVE layout; the
        decode_attention dispatch seam owns the read (gather/dequant on
        the xla lowering, table-indirect + in-register dequant in the
        Mosaic kernel).

        One caller does not read what this returns: a whole-prompt
        prefill onto an EMPTY lockstep cache (`__call__`'s
        `cache_empty`) takes the write alone and attends over the K/V
        it passed in, under the same law (causal, no pad keys) cut to
        the prompt's own `seq` keys. The view's `valid` spans the
        whole cache, `[B, seq, max_len]`, and its `k`/`v` ARE the whole
        cache: the seam's dense lowering over them is `[H, seq,
        max_len]` scores, which is why only short windows, and windows
        that must see a cached prefix, go there.
        """
        cfg = self.config
        batch, seq, n_kv, head_dim = k.shape
        max_len = cfg.max_position_embeddings
        if self.has_variable("cache", "block_table"):
            return self._update_paged_cache(k, v, attention_mask, layer)
        # when the variables are being created (the init_cache=True init
        # pass), skip the update so the returned cache starts at index 0
        is_initialized = self.has_variable("cache", "cached_key")
        cached_k = self.variable("cache", "cached_key", jnp.zeros,
                                 (batch, max_len, n_kv, head_dim), k.dtype)
        cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                 (batch, max_len, n_kv, head_dim), v.dtype)
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.zeros((), jnp.int32))
        if not is_initialized:
            valid = jnp.broadcast_to(
                (jnp.arange(max_len) < seq)[None, None],
                (batch, seq, max_len))
            return CacheView(k, v, None, None, None, valid[:, :, :seq])
        idx = cache_index.value
        ks_all = vs_all = None
        if idx.ndim == 1:
            # slot-pool decode (fengshen_tpu/serving): a [B] cache_index
            # gives every lane its own write position, so concurrently
            # served requests at different progress share ONE jitted step
            quantized = self.has_variable("cache", "cached_key_scale")
            if quantized:
                from fengshen_tpu.ops.int8_matmul import quantize_kv
                k_scale = self.variable(
                    "cache", "cached_key_scale", jnp.zeros,
                    (batch, max_len, n_kv), jnp.float32)
                v_scale = self.variable(
                    "cache", "cached_value_scale", jnp.zeros,
                    (batch, max_len, n_kv), jnp.float32)
                k, ks = quantize_kv(k)
                v, vs = quantize_kv(v)
                ks_all = jax.vmap(
                    lambda c, u, i: jax.lax.dynamic_update_slice(
                        c, u, (i, 0)))(k_scale.value, ks, idx)
                vs_all = jax.vmap(
                    lambda c, u, i: jax.lax.dynamic_update_slice(
                        c, u, (i, 0)))(v_scale.value, vs, idx)
                k_scale.value, v_scale.value = ks_all, vs_all
            k_all = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (i, 0, 0)))(cached_k.value, k, idx)
            v_all = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (i, 0, 0)))(cached_v.value, v, idx)
            cached_k.value, cached_v.value = k_all, v_all
            # int8 pools stay int8: the CacheView carries the raw pool
            # + scales and the attention read dequantizes (in registers
            # on the Mosaic kernel, via dequantize_kv on the lowering)
            cache_index.value = idx + seq
            # per-lane causal validity: lane b's query t (position
            # idx[b]+t) sees cache positions ≤ idx[b]+t
            q_pos = idx[:, None] + jnp.arange(seq)[None, :]
            valid = jnp.arange(max_len)[None, None, :] <= q_pos[:, :, None]
        else:
            k_all = jax.lax.dynamic_update_slice(cached_k.value, k,
                                                 (0, idx, 0, 0))
            v_all = jax.lax.dynamic_update_slice(cached_v.value, v,
                                                 (0, idx, 0, 0))
            cached_k.value, cached_v.value = k_all, v_all
            cache_index.value = idx + seq
            # per-query causal validity: query t (global position idx+t)
            # sees cache positions ≤ idx+t  → [B, Sq, max_len]
            q_pos = idx + jnp.arange(seq)
            valid = jnp.arange(max_len)[None, :] <= q_pos[:, None]
            valid = jnp.broadcast_to(valid[None], (batch, seq, max_len))
        if attention_mask is not None:
            # left-padded batches mask out pad positions of the prompt
            pad = jnp.ones((attention_mask.shape[0],
                            max_len - attention_mask.shape[1]),
                           attention_mask.dtype)
            full = jnp.concatenate([attention_mask, pad], axis=1)
            valid = valid & full[:, None, :].astype(bool)
        return CacheView(k_all, v_all, ks_all, vs_all, None, valid)

    def _update_paged_cache(self, k, v, attention_mask, layer=None):
        """Paged decode (fengshen_tpu/serving/paged_cache.py): K/V live
        in a shared `[num_blocks, block_size, kv, hd]` pool; each lane's
        logical positions map through its `block_table` row to physical
        blocks. The host scheduler owns the free list; this method only
        scatters the step's K/V at `table[lane, p // bs] * bs + p % bs`
        for each of the step's `seq` positions `p = idx + 0..seq-1`
        (seq == 1 for the plain decode tick; seq == gamma+1 for the
        speculative verify window, whose positions may CROSS a block
        boundary — hence the per-position block lookup). The READ moved
        into the decode_attention dispatch seam: the Mosaic kernel
        walks the block table directly (no gather copy), while the xla
        lowering reconstructs the stock contiguous-virtual-lane
        `jnp.take` gather, so the XLA-CPU tier-1 lane sees the same
        math it always ran. Inactive
        lanes are parked on block 0 (the null block, never allocated),
        which absorbs their stray writes; the engine's admission
        charges blocks for the speculative tail too
        (`serving/paged_cache.py blocks_for_tokens` over
        bucket + max_new + gamma), so an active lane's over-scattered
        window never reaches a block it does not own. Prefill still
        runs on a contiguous batch-1 cache and is scattered in by
        `assign_paged` — a whole prompt through this path would
        overrun the lane, hence the seq bound below.

        Under `scan_layers` the layer loop CARRIES the cache
        (`LlamaModel`): the variables here are then the whole stacks,
        `[L, num_blocks, ...]` pools and `[L, B, ...]` cursors/tables,
        and `layer` is this iteration's index. The stack is addressed
        as ONE pool of `L * num_blocks` blocks — a free reshape — in
        which layer `l` owns blocks `l * num_blocks ...`: the write
        scatters the step's rows at `layer * num_blocks * bs + ...`
        into the loop-carried buffer (in place), and the read gets the
        stacks and `layer` and reads through `block_table + layer *
        num_blocks` (`decode_attention`). No layer's pool is ever
        sliced out of, or written back into, the stack.

        An int8 pool (marked by `cached_key_scale`) stores per-(token,
        head) absmax scales alongside and dequantizes inside the read.
        """
        batch, seq, n_kv, head_dim = k.shape
        cached_k = self.variable("cache", "cached_key", jnp.zeros,
                                 (1, 1, n_kv, head_dim), k.dtype)
        cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                 (1, 1, n_kv, head_dim), v.dtype)
        cache_index = self.variable("cache", "cache_index",
                                    lambda: jnp.zeros((batch,), jnp.int32))
        table = self.variable("cache", "block_table",
                              lambda: jnp.zeros((batch, 1), jnp.int32))
        num_blocks, block_size = cached_k.value.shape[-4:-2]
        if layer is None:
            idx, lane_table, first_block = cache_index.value, table.value, 0
        else:
            idx, lane_table = cache_index.value[layer], table.value[layer]
            # layer `layer`'s block b is block `layer * num_blocks + b`
            # of the flat stack
            first_block = layer * num_blocks
        max_blocks = lane_table.shape[-1]
        virt_len = max_blocks * block_size   # the lane's logical extent
        if seq > virt_len:
            # a window that cannot fit any lane (e.g. prefilling a
            # long prompt through the paged path) must fail loudly —
            # the block lookup below would clamp its overflow
            # positions onto one block and silently corrupt it
            raise ValueError(
                f"paged cache updates take at most the virtual lane "
                f"length {virt_len} tokens per step (decode tick or "
                f"speculative verify window); got seq={seq}. Prefill "
                "runs on a contiguous batch-1 cache.")
        quantized = self.has_variable("cache", "cached_key_scale")

        # scatter this step's K/V at each lane's physical positions
        # (lanes parked on the null block collide there by design —
        # whichever garbage write wins is never read unmasked)
        p = idx[:, None] + jnp.arange(seq)[None, :]        # [B, seq]
        blk = jnp.take_along_axis(lane_table, p // block_size, axis=-1)
        pos = ((first_block + blk) * block_size +
               p % block_size).reshape(-1)

        def put(pool, rows):
            flat = pool.reshape((-1,) + rows.shape[2:])
            return flat.at[pos].set(
                rows.reshape(batch * seq, *rows.shape[2:]).astype(
                    flat.dtype)).reshape(pool.shape)

        if quantized:
            from fengshen_tpu.ops.int8_matmul import quantize_kv
            k_scale = self.variable(
                "cache", "cached_key_scale", jnp.zeros,
                (num_blocks, block_size, n_kv), jnp.float32)
            v_scale = self.variable(
                "cache", "cached_value_scale", jnp.zeros,
                (num_blocks, block_size, n_kv), jnp.float32)
            k, ks = quantize_kv(k)
            v, vs = quantize_kv(v)
            k_scale.value = put(k_scale.value, ks)
            v_scale.value = put(v_scale.value, vs)
        cached_k.value = put(cached_k.value, k)
        cached_v.value = put(cached_v.value, v)
        cache_index.value = idx + seq if layer is None else \
            cache_index.value.at[layer].add(seq)

        # NO gather: the pool stays put and the CacheView carries the
        # block table — the attention read resolves the indirection
        # (the Mosaic kernel's index maps walk the table per block; the
        # xla lowering reconstructs the stock jnp.take virtual lane)
        # per-lane causal validity over the virtual lane (same law as
        # the slot path: query at idx[b] sees positions <= idx[b])
        valid = jnp.arange(virt_len)[None, None, :] <= p[:, :, None]
        if attention_mask is not None:
            m = attention_mask[:, :virt_len]
            if m.shape[1] < virt_len:
                pad = jnp.ones((batch, virt_len - m.shape[1]), m.dtype)
                m = jnp.concatenate([m, pad], axis=1)
            valid = valid & m[:, None, :].astype(bool)
        return CacheView(cached_k.value, cached_v.value,
                         k_scale.value if quantized else None,
                         v_scale.value if quantized else None,
                         lane_table, valid, layer)


def _holds_block_table(cache) -> bool:
    """Whether a cache collection holds a paged pool (static under jit:
    it reads the pytree's keys, never a value)."""
    return any(path[-1] == "block_table"
               for path in flatten_dict(cache))


class LlamaDecoderLayer(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, cache_empty=False,
                 layer=None):
        cfg = self.config
        h = RMSNorm(epsilon=cfg.rms_norm_eps, name="input_layernorm")(hidden)
        h = LlamaAttention(cfg, name="self_attn")(
            h, attention_mask, position_ids, init_cache, deterministic,
            cache_empty, layer)
        hidden = hidden + h
        h = RMSNorm(epsilon=cfg.rms_norm_eps,
                    name="post_attention_layernorm")(hidden)
        if cfg.moe_experts > 0:
            # routed expert MLP instead of the dense one, in its Switch
            # setting: softmax router, top-1, no token dropped (aux
            # loss sowed under ("losses","moe_aux_loss"))
            from fengshen_tpu.ops.moe import RoutedExperts
            # cached decode feeds a 1-token hidden with the full-prompt
            # mask; the live decode token is always real, so no mask
            tok_mask = attention_mask
            if tok_mask is not None and tok_mask.shape[1] != h.shape[1]:
                tok_mask = None
            elif tok_mask is not None:
                # packed rows carry segment ids; MoE only needs real/pad
                tok_mask = (tok_mask > 0).astype(jnp.int32)
            h = RoutedExperts(
                hidden_size=cfg.hidden_size,
                intermediate_size=cfg.intermediate_size,
                num_experts=cfg.moe_experts, aux_loss=True,
                dtype=_dt(cfg),
                param_dtype=jnp.dtype(cfg.param_dtype),
                name="moe_mlp")(h, token_mask=tok_mask)
        else:
            h = LlamaMLP(cfg, name="mlp")(h)
        return hidden + h


class _ScanDecoderLayer(nn.Module):
    """nn.scan body: (carry, _) → (carry, None)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden, attention_mask, position_ids, init_cache,
                 deterministic, cache_empty=False, layer=None):
        out = LlamaDecoderLayer(self.config, name="layer")(
            hidden, attention_mask, position_ids, init_cache, deterministic,
            cache_empty, layer)
        return out, None


class LlamaModel(nn.Module):
    """Decoder stack (reference: fengshen/models/llama/modeling_llama.py:97-236)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True, cache_empty=False):
        cfg = self.config
        embed = VocabParallelEmbed(cfg.vocab_size, cfg.hidden_size,
                                   dtype=_dt(cfg),
                                   param_dtype=jnp.dtype(cfg.param_dtype),
                                   embedding_init=nn.initializers.normal(
                                       cfg.initializer_range),
                                   name="embed_tokens")
        hidden = embed(input_ids)
        hidden = with_logical_constraint(
            hidden, ("batch", "seq", None))

        remat_policy = {
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots_no_batch":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "checkpoint_dots": jax.checkpoint_policies.checkpoint_dots,
        }[getattr(cfg, "remat_policy", "nothing")]
        if cfg.scan_layers:
            body = _ScanDecoderLayer
            if cfg.gradient_checkpointing:
                body = nn.remat(
                    body, static_argnums=(4, 5, 6),
                    policy=remat_policy,
                    prevent_cse=False)
            scan_kw = dict(
                variable_axes={"params": 0, "cache": 0, "losses": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast,) * 5,
                length=cfg.num_hidden_layers)
            args = (hidden, attention_mask, position_ids, init_cache,
                    deterministic, cache_empty)
            if _holds_block_table(self.variables.get("cache", {})):
                # a paged KV pool is loop STATE, not a scanned
                # input/output: as xs/ys every iteration would slice a
                # layer's pool out of the stack and write it into a
                # second stack (two pool-sized copies a step, a third
                # to reconcile the donated argument); carried, the
                # step's rows are scattered into the one buffer in
                # place and the layer finds its blocks by its index
                # (`_update_paged_cache`)
                scan_kw.update(
                    variable_axes={"params": 0, "losses": 0},
                    variable_carry="cache",
                    in_axes=(nn.broadcast,) * 5 + (0,))
                args += (jnp.arange(cfg.num_hidden_layers),)
            hidden, _ = nn.scan(body, **scan_kw)(cfg, name="layers")(*args)
        else:
            layer_cls = LlamaDecoderLayer
            if cfg.gradient_checkpointing:
                layer_cls = nn.remat(
                    layer_cls, static_argnums=(4, 5, 6),
                    policy=remat_policy)
            for i in range(cfg.num_hidden_layers):
                hidden = layer_cls(cfg, name=f"layers_{i}")(
                    hidden, attention_mask, position_ids, init_cache,
                    deterministic, cache_empty)
        return RMSNorm(epsilon=cfg.rms_norm_eps, name="norm")(hidden)


class _Int8LMHead(nn.Module):
    """Dense-compatible LM head routed through the dynamic int8 matmul
    (ops/int8_matmul.py): same `kernel` param shape/path as nn.Dense so
    partition rules and checkpoint converters are unaffected."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden):
        from fengshen_tpu.ops.int8_matmul import int8_matmul
        cfg = self.config
        kernel = self.param("kernel",
                            nn.initializers.normal(cfg.initializer_range),
                            (cfg.hidden_size, cfg.vocab_size),
                            jnp.dtype(cfg.param_dtype))
        return int8_matmul(hidden, kernel.astype(_dt(cfg)))


class LlamaForCausalLM(nn.Module):
    """LM head on the stack (reference: modeling_llama.py:239-405)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 init_cache=False, deterministic=True,
                 return_hidden=False, cache_empty=False, logits_row=None):
        """`cache_empty` (static) says the cache this call writes holds
        nothing yet: a whole-prompt prefill, which then attends over the
        prompt's own keys (`LlamaAttention`). Only the caller that made
        the cache can know: `cache_index` is traced.

        `logits_row` (a traced int32 scalar, or None for every row):
        the one row whose logits the caller keeps, sliced out of the
        hidden states BEFORE the head, whichever form the head has; the
        logits are then `[B, 1, V]` (`model_utils.head_rows`)."""
        cfg = self.config
        hidden = LlamaModel(cfg, name="model")(
            input_ids, attention_mask, position_ids, init_cache,
            deterministic, cache_empty)
        if return_hidden:
            # the fused chunked LM-head+CE path (ops/fused_ce.py)
            # applies the head itself from the param tree (init always
            # runs the normal path, so lm_head params exist either way)
            return hidden
        hidden = head_rows(hidden, logits_row)
        if cfg.tie_word_embeddings:
            embedding = self.variables["params"]["model"]["embed_tokens"][
                "embedding"]
            if cfg.int8_lm_head:
                from fengshen_tpu.ops.int8_matmul import int8_matmul
                logits = int8_matmul(hidden,
                                     embedding.T.astype(hidden.dtype))
            else:
                logits = hidden @ embedding.T.astype(hidden.dtype)
        elif cfg.int8_lm_head:
            # same lm_head/kernel param path as the Dense branch, so
            # partition rules and converters apply unchanged
            logits = _Int8LMHead(cfg, name="lm_head")(hidden)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False,
                              dtype=_dt(cfg),
                              param_dtype=jnp.dtype(cfg.param_dtype),
                              kernel_init=nn.initializers.normal(
                                  cfg.initializer_range),
                              name="lm_head")(hidden)
        return logits

    # -- convenience -----------------------------------------------------
    def init_params(self, rng, seq_len: int = 8):
        ids = jnp.zeros((1, seq_len), jnp.int32)
        return self.init(rng, ids)["params"]

    def partition_rules(self):
        return to_partition_rules(
            SCAN_PARAM_LOGICAL_AXES if self.config.scan_layers
            else LLAMA_PARAM_LOGICAL_AXES)


def resize_token_embeddings(params: dict, config, new_num_tokens: int,
                            rng=None):
    """Grow/shrink the vocab dim of embed_tokens + lm_head, preserving the
    existing rows (reference: models/llama/modeling_llama.py:386-405 —
    there it rebuilds Embedding/ParallelLinear modules and copies the old
    weight rows; here params are a pytree, so this is a pure function
    returning (new_params, new_config)).

    New rows draw from N(0, config.initializer_range) like the
    reference's init_method. Works for both tied (no lm_head entry) and
    untied heads.
    """
    import dataclasses

    old = config.vocab_size
    if rng is None:
        rng = jax.random.PRNGKey(0)

    def _resize_rows(table, key):
        n, h = table.shape
        if new_num_tokens <= n:
            return table[:new_num_tokens]
        extra = (jax.random.normal(key, (new_num_tokens - n, h),
                                   jnp.float32)
                 * config.initializer_range).astype(table.dtype)
        return jnp.concatenate([table, extra], axis=0)

    k_embed, k_head = jax.random.split(rng)
    embed = params["model"]["embed_tokens"]["embedding"]
    assert embed.shape[0] == old, (embed.shape, old)
    new_params = {**params,
                  "model": {**params["model"],
                            "embed_tokens": {
                                "embedding": _resize_rows(embed, k_embed)}}}
    if "lm_head" in params:
        kernel = params["lm_head"]["kernel"]  # [H, V]
        new_params["lm_head"] = {
            "kernel": _resize_rows(kernel.T, k_head).T}
    return new_params, dataclasses.replace(config,
                                           vocab_size=new_num_tokens)


def make_self_draft(config: LlamaConfig, params: dict, n_layers: int):
    """Early-exit draft for SELF-speculative decoding: the target's own
    first `n_layers` decoder layers plus its shared embeddings, final
    norm, and LM head form the draft model — no second checkpoint
    needed (`utils/generate.py speculative_generate` stays exact
    regardless of draft quality, so the truncated tower only affects
    the acceptance rate, never the output law).

    Returns `(draft_config, draft_params)`. Shared leaves alias the
    target's arrays (no copy); under `scan_layers` the stacked layer
    leaves are sliced to the first `n_layers`.
    """
    import dataclasses

    if not 0 < n_layers < config.num_hidden_layers:
        raise ValueError(
            f"make_self_draft: n_layers={n_layers} must be in "
            f"(0, {config.num_hidden_layers})")
    model_p = dict(params["model"])
    if config.scan_layers:
        model_p["layers"] = jax.tree_util.tree_map(
            lambda x: x[:n_layers], params["model"]["layers"])
    else:
        kept = {f"layers_{i}" for i in range(n_layers)}
        model_p = {k: v for k, v in model_p.items()
                   if not k.startswith("layers_") or k in kept}
    draft_params = {**params, "model": model_p}
    return dataclasses.replace(config, num_hidden_layers=n_layers), \
        draft_params
