"""Memory-efficient (flash-style) exact attention.

TPU-native replacement for the reference's flash-attention CUDA binding
(reference: fengshen/models/megatron/layers/flash_attention.py:107-185 wraps
flash_attn_cuda.fwd/bwd). Two tiers:

1. `blockwise_attention` — O(S) memory exact attention via online softmax
   over k/v blocks with `lax.scan`. Pure XLA: runs on TPU and on the CPU
   test backend, differentiable, and XLA fuses each block's
   matmul→rescale→matmul chain onto the MXU. Causal masking is computed
   per k-block from indices — no dense [Sq, Sk] bias is ever materialised.
2. On real TPU, `flash_attention` prefers the Pallas fused kernel
   (fengshen_tpu.ops.pallas.flash_attention) when shapes are tile-aligned,
   mirroring the reference's `is_kernel_available` dispatch
   (reference: fengshen/models/megatron/layers/fused_softmax.py:148-168).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        bias: Optional[jax.Array] = None,
                        causal: bool = False,
                        block_size: int = 512,
                        q_segment_ids: Optional[jax.Array] = None,
                        kv_segment_ids: Optional[jax.Array] = None
                        ) -> jax.Array:
    """Online-softmax attention. q: [B, Sq, H, D], k/v: [B, Sk, H, D],
    bias broadcastable to [B, H, Sq, Sk]; segment ids int32 [B, S] (tokens
    attend only within equal ids). Returns [B, Sq, H, D].

    Prefer `causal=True` over passing a causal bias: the mask is then
    computed per block from indices, keeping memory O(Sq·block) instead of
    O(Sq·Sk).
    """
    batch, q_len, num_heads, head_dim = q.shape
    k_len = k.shape[1]
    blk = min(block_size, k_len)
    pad = (blk - k_len % blk) % blk
    if pad:  # pad k/v to a block multiple; padding is masked by position
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if bias is not None:
            bias = jnp.broadcast_to(
                bias.astype(jnp.float32),
                bias.shape[:-2] + (q_len, k_len))
            bias = jnp.pad(bias, ((0, 0),) * (bias.ndim - 1) + ((0, pad),),
                           constant_values=_NEG_INF)
    if kv_segment_ids is not None and (pad or kv_segment_ids.shape[1] <
                                       k_len + pad):
        kv_segment_ids = jnp.pad(
            kv_segment_ids, ((0, 0), (0, k_len + pad -
                                      kv_segment_ids.shape[1])),
            constant_values=-1)  # -1 never equals a real segment id
    padded_len = k_len + pad

    n_blocks = padded_len // blk
    scale = 1.0 / jnp.sqrt(head_dim).astype(jnp.float32)
    # global positions; q is assumed right-aligned with k (Sq suffix of Sk),
    # matching the KV-cache decode convention
    q_pos = jnp.arange(k_len - q_len, k_len)

    if bias is not None:
        bias = jnp.broadcast_to(
            bias.astype(jnp.float32),
            bias.shape[:-2] + (q_len, padded_len))
        bias_blocks = jnp.moveaxis(
            bias.reshape(bias.shape[:-1] + (n_blocks, blk)), -2, 0)
    k_blocks = jnp.moveaxis(
        k.reshape(batch, n_blocks, blk, num_heads, head_dim), 1, 0)
    v_blocks = jnp.moveaxis(
        v.reshape(batch, n_blocks, blk, num_heads, head_dim), 1, 0)
    if kv_segment_ids is not None:
        kv_seg_blocks = jnp.moveaxis(
            kv_segment_ids.reshape(batch, n_blocks, blk), 1, 0)
    blk_idx = jnp.arange(n_blocks)

    def step(carry, xs):
        acc, row_max, row_sum = carry
        seg_blk = None
        if bias is not None and kv_segment_ids is not None:
            bi, k_blk, v_blk, b_blk, seg_blk = xs
        elif bias is not None:
            bi, k_blk, v_blk, b_blk = xs
        elif kv_segment_ids is not None:
            bi, k_blk, v_blk, seg_blk = xs
        else:
            bi, k_blk, v_blk = xs
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                            preferred_element_type=jnp.float32) * scale
        if bias is not None:
            scores = scores + b_blk
        k_pos = bi * blk + jnp.arange(blk)
        if causal:
            allowed = (k_pos[None, :] <= q_pos[:, None]) & \
                (k_pos[None, :] < k_len)
        else:
            allowed = jnp.broadcast_to(k_pos[None, :] < k_len, (q_len, blk))
        allowed = jnp.broadcast_to(allowed[None, None],
                                   (batch, 1, q_len, blk))
        if seg_blk is not None:
            same = (q_segment_ids[:, :, None] ==
                    seg_blk[:, None, :])  # [B, Sq, blk]
            allowed = allowed & same[:, None]
        scores = jnp.where(allowed, scores, _NEG_INF)
        blk_max = scores.max(axis=-1)                       # [B,H,Sq]
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(scores - new_max[..., None])
        # fully-masked blocks contribute nothing (probs underflow to 0 at
        # exp(_NEG_INF - max))
        new_sum = row_sum * correction + probs.sum(axis=-1)
        blk_out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v_blk.dtype),
                             v_blk).astype(jnp.float32)
        acc = acc * correction.transpose(0, 2, 1)[..., None] + blk_out
        return (acc, new_max, new_sum), None

    acc0 = jnp.zeros((batch, q_len, num_heads, head_dim), jnp.float32)
    max0 = jnp.full((batch, num_heads, q_len), _NEG_INF, jnp.float32)
    sum0 = jnp.zeros((batch, num_heads, q_len), jnp.float32)

    xs = (blk_idx, k_blocks, v_blocks)
    if bias is not None:
        xs = xs + (bias_blocks,)
    if kv_segment_ids is not None:
        xs = xs + (kv_seg_blocks,)

    (acc, _, row_sum), _ = jax.lax.scan(step, (acc0, max0, sum0), xs)
    out = acc / jnp.maximum(row_sum, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    bias: Optional[jax.Array] = None,
                    dropout_rng=None, dropout_rate: float = 0.0,
                    deterministic: bool = True,
                    block_size: int = 512,
                    causal: bool = False,
                    segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Flash attention with kernel dispatch.

    `segment_ids`: int32 [B, S] (or a (q_ids, kv_ids) tuple) — tokens attend
    only within equal ids. A padded batch's attention_mask maps directly
    (pads become segment 0), which keeps padded SFT batches on the fused
    kernel instead of the dense O(S²) path.

    Attention dropout is not supported on the flash path (same restriction
    as the reference's flash branch, which bypasses the softmax-dropout,
    reference: layers/transformer.py:270-279) — callers fall back to dense
    when dropout is active.
    """
    if not deterministic and dropout_rate > 0.0:
        raise ValueError("flash attention path does not support attention "
                         "dropout; use impl='dense'")
    if isinstance(segment_ids, (tuple, list)):
        q_seg, kv_seg = segment_ids
    else:
        q_seg = kv_seg = segment_ids
    if q_seg is not None:
        q_seg = q_seg.astype(jnp.int32)
        kv_seg = kv_seg.astype(jnp.int32)
    from fengshen_tpu.ops.pallas import resolve_dispatch
    impl = resolve_dispatch(
        "flash_attention",
        f"q={tuple(q.shape)} kv={tuple(k.shape)}:{q.dtype.name} "
        f"causal={causal} segments={q_seg is not None}",
        _pallas_ineligible_reason(q, k, bias))
    if impl == "pallas":
        return _pallas_attention(q, k, v, q_seg, kv_seg, causal)
    if k.shape[2] != q.shape[2]:  # GQA on blockwise: repeat the KV heads
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return blockwise_attention(q, k, v, bias=bias, causal=causal,
                               block_size=block_size,
                               q_segment_ids=q_seg, kv_segment_ids=kv_seg)


def _pallas_attention(q, k, v, q_seg, kv_seg, causal):
    """The Mosaic kernel, per shard under a device mesh."""
    from fengshen_tpu.ops.pallas import run_per_shard
    from fengshen_tpu.ops.pallas.flash_attention import (
        pallas_flash_attention)
    if q_seg is None:
        return run_per_shard(
            lambda q, k, v: pallas_flash_attention(
                q, k, v, None, None, causal), q, k, v)
    return run_per_shard(
        lambda q, k, v, q_seg, kv_seg: pallas_flash_attention(
            q, k, v, q_seg, kv_seg, causal), q, k, v, q_seg, kv_seg)


def prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      attention_mask: Optional[jax.Array] = None
                      ) -> jax.Array:
    """Causal attention of a whole prompt over its OWN keys: what a
    prefill onto an empty KV cache computes, without reading the cache
    it fills (every cache row past the prompt has weight exactly 0).

    q: ``[B, S, H, D]``; k/v: ``[B, S, KVH, D]``, the rows just
    projected; ``attention_mask``: ``[B, S]``, 0 on the (left) pads, or
    None. One algorithm, two lowerings, chosen per traced call site
    through ``resolve_dispatch("flash_attention", ...)``: the Mosaic
    flash kernel where its tiling takes the shape (causal, the mask as
    segment ids, GQA read once per group), else the dense chain under
    the causal mask without the pad keys — the sums the decode seam's
    dense lowering computes over the cache's whole extent, without the
    zeros.

    Pad rows: under segment ids a pad query attends the pad keys at or
    before it (segment 0); under the dense mask it has no key and the
    softmax spreads it over all. Neither is read: no real query sees a
    pad key on either lowering, and a pad row's logits and cache rows
    are masked wherever they are used.
    """
    from fengshen_tpu.ops.attention import dot_product_attention
    from fengshen_tpu.ops.masks import causal_mask
    from fengshen_tpu.ops.pallas import resolve_dispatch
    impl = resolve_dispatch(
        "flash_attention",
        f"prefill q={tuple(q.shape)} kv={tuple(k.shape)}:{q.dtype.name} "
        f"causal=True segments={attention_mask is not None}",
        _pallas_ineligible_reason(q, k, None))
    if impl == "pallas":
        seg = None if attention_mask is None else \
            attention_mask.astype(bool).astype(jnp.int32)
        return _pallas_attention(q, k, v, seg, seg, True)
    mask = causal_mask(q.shape[1])[None, None]
    if attention_mask is not None:
        mask = mask & attention_mask[:, None, None, :].astype(bool)
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return dot_product_attention(q, k, v, mask=mask)


def _pallas_ineligible_reason(q, k, bias) -> Optional[str]:
    """Why these shapes cannot take the Pallas kernel, or None when
    they can — a kernel-eligibility check in the spirit of the reference's
    `FusedScaleMaskSoftmax.is_kernel_available`
    (reference: layers/fused_softmax.py:148-168). GQA (fewer KV heads)
    is kernel-native — the grid index maps read each KV head once per
    group — as long as the head counts divide."""
    if bias is not None:
        return "additive bias"
    _, q_len, n_heads, head_dim = q.shape
    k_len, kv_heads = k.shape[1], k.shape[2]
    if n_heads % kv_heads != 0:
        return f"{n_heads} heads not a multiple of {kv_heads} kv heads"
    if head_dim % 128 or q_len % 128 or k_len % 128:
        return (f"head_dim {head_dim} / q_len {q_len} / k_len {k_len} "
                "not all multiples of 128")
    return None
