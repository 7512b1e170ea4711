"""Scaled dot-product attention.

Replaces the reference's attention compute stack: baddbmm QK^T with a
preallocated buffer + FusedScaleMaskSoftmax CUDA kernel + context bmm
(reference: fengshen/models/megatron/layers/transformer.py:307-456 and
layers/fused_softmax.py:24-205), and the flash-attention CUDA binding
(reference: layers/flash_attention.py:107-185).

On TPU the dense path is a single fused XLA HLO chain (matmul→scale→mask→
softmax→matmul hits the MXU with the softmax fused in between); the
`impl="flash"` path dispatches to the Pallas flash kernel in
fengshen_tpu.ops.flash_attention for long sequences, and `impl="ring"` to
sequence-parallel ring attention in fengshen_tpu.ops.ring_attention.

Numerics: softmax statistics are always computed in fp32, mirroring the
reference's fp32-upcast fallback rule (reference:
layers/fused_softmax.py:184-200) so loss curves are comparable.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _dense_attention(q, k, v, bias, dropout_rng, dropout_rate, deterministic):
    """q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; bias broadcastable to
    [B, H, Sq, Sk]. Returns [B, Sq, H, D]."""
    head_dim = q.shape[-1]
    scale = 1.0 / jnp.sqrt(head_dim).astype(jnp.float32)
    # [B, H, Sq, Sk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    if not deterministic and dropout_rate > 0.0:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          bias: Optional[jax.Array] = None,
                          mask: Optional[jax.Array] = None,
                          dropout_rng: Optional[jax.Array] = None,
                          dropout_rate: float = 0.0,
                          deterministic: bool = True,
                          impl: str = "dense",
                          sparse_layout=None,
                          sparse_block_size: int = 128,
                          segment_ids: Optional[jax.Array] = None
                          ) -> jax.Array:
    """Attention entry point with per-layer impl dispatch.

    `impl` mirrors the reference's per-layer `attention_config` selection of
    dense / flash / sparse kernels
    (reference: layers/transformer.py:259-268).

    `impl="sparse"` takes `sparse_layout` — a STATIC (numpy) [nQ, nK] bool
    block-presence matrix with `sparse_block_size` tokens per block (build
    one with the `*_block_layout` helpers in fengshen_tpu.ops.masks) — and
    runs the Pallas block-sparse kernel when shapes are tile-aligned,
    skipping absent blocks entirely; otherwise it falls back to
    dense-with-expanded-mask (the layouts are also expressible as `mask`,
    which runs on any backend).

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; mask: bool broadcastable to
    [B, H, Sq, Sk] (True = attend); bias: additive, same broadcast.
    """
    if impl == "sparse" and sparse_layout is not None:
        import numpy as np

        from fengshen_tpu.ops.pallas import (resolve_dispatch,
                                             run_per_shard)
        layout = np.asarray(sparse_layout)
        blk = sparse_block_size
        eligible = (
            bias is None and mask is None and
            (deterministic or dropout_rate == 0.0) and
            q.shape[1] % blk == 0 and k.shape[1] % blk == 0 and
            blk % 128 == 0 and q.shape[-1] % 128 == 0 and
            layout.shape == (q.shape[1] // blk, k.shape[1] // blk))
        took = resolve_dispatch(
            "block_sparse_attention",
            f"q={tuple(q.shape)} kv={tuple(k.shape)}:{q.dtype.name} "
            f"block={blk}",
            None if eligible else "bias, mask, dropout or unaligned "
                                  "shapes")
        if took == "pallas":
            from fengshen_tpu.ops.pallas.block_sparse_attention import (
                block_sparse_attention)
            return run_per_shard(
                lambda q, k, v: block_sparse_attention(q, k, v, layout,
                                                       blk), q, k, v)
        # otherwise: the block layout expanded to a dense mask
        expanded = jnp.asarray(
            np.kron(layout, np.ones((blk, blk), dtype=bool)))
        mask = expanded[None, None] if mask is None else \
            (mask & expanded[None, None])

    if segment_ids is not None and impl in ("dense", "sparse"):
        # dense path honors segments as an explicit mask
        seg_mask = (segment_ids[:, None, None, :] ==
                    segment_ids[:, None, :, None])
        mask = seg_mask if mask is None else (mask & seg_mask)

    if mask is not None:
        neg = jnp.asarray(-1e9, dtype=jnp.float32)
        mask_bias = jnp.where(mask, 0.0, neg)
        bias = mask_bias if bias is None else bias + mask_bias

    if impl in ("dense", "sparse"):
        return _dense_attention(q, k, v, bias, dropout_rng, dropout_rate,
                                deterministic)
    if impl == "flash":
        from fengshen_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, bias=bias,
                               dropout_rng=dropout_rng,
                               dropout_rate=dropout_rate,
                               deterministic=deterministic,
                               segment_ids=segment_ids)
    if impl in ("ring", "ulysses", "sequence"):
        if bias is not None:
            raise ValueError(f"impl={impl!r} supports causal/segment "
                             "masking only; express other patterns via "
                             "impl='dense'")
        from fengshen_tpu.ops.ulysses_attention import (
            sequence_parallel_attention)
        prefer = {"ring": "ring", "ulysses": "ulysses",
                  "sequence": "auto"}[impl]
        return sequence_parallel_attention(q, k, v, segment_ids=segment_ids,
                                           causal=True, prefer=prefer)
    raise ValueError(f"unknown attention impl {impl!r}")
