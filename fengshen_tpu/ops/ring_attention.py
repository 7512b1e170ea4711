"""Ring attention — sequence/context parallelism over the ICI mesh.

The reference has **no** sequence parallelism (SURVEY.md §5.7: max context is
per-device, flash/sparse kernels only scale the constant factor). This module
fills that gap the TPU-native way: the sequence dim is sharded over the
'sequence' mesh axis, and k/v shards rotate around the ring with
`jax.lax.ppermute` while each device accumulates its queries' attention with
an online softmax — compute overlaps the ICI transfer and per-device memory
stays O(S/ring) (Liu et al., Ring Attention with Blockwise Transformers).

`ring_attention` is the shard_map-body (axis_name in scope);
`ring_attention_sharded` wraps it for callers holding globally-sharded
arrays.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from fengshen_tpu.parallel.mesh import BATCH_AXES, SEQUENCE_AXIS, get_mesh

_NEG_INF = -1e30


def _block_scores(q, k, scale):
    return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   segment_ids: Optional[jax.Array] = None,
                   axis_name: str = SEQUENCE_AXIS,
                   causal: bool = True) -> jax.Array:
    """Attention over a sequence-sharded batch; call inside shard_map.

    q/k/v: local shards [B, S_local, H, D]; segment_ids: local int32
    [B, S_local] shard (tokens attend only within equal ids — a padded
    batch's attention_mask maps directly, pads = segment 0; the kv-shard's
    ids rotate around the ring with k/v). The local shard index along
    `axis_name` determines global positions (contiguous layout: shard i
    holds positions [i*S_local, (i+1)*S_local)).
    """
    ring_size = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    batch, s_local, num_heads, head_dim = q.shape
    scale = 1.0 / jnp.sqrt(head_dim).astype(jnp.float32)

    q_pos = my_idx * s_local + jnp.arange(s_local)  # global query positions

    acc = jnp.zeros((batch, s_local, num_heads, head_dim), jnp.float32)
    row_max = jnp.full((batch, num_heads, s_local), _NEG_INF, jnp.float32)
    row_sum = jnp.zeros((batch, num_heads, s_local), jnp.float32)

    has_segments = segment_ids is not None
    seg_kv0 = segment_ids if has_segments else \
        jnp.zeros((batch, s_local), jnp.int32)

    def body(step, carry):
        acc, row_max, row_sum, k_cur, v_cur, seg_cur = carry
        # shard that k_cur originated from
        src_idx = (my_idx - step) % ring_size
        k_pos = src_idx * s_local + jnp.arange(s_local)

        scores = _block_scores(q, k_cur, scale)  # [B,H,Sq,Sk]
        allowed = None
        if causal:
            allowed = (k_pos[None, :] <= q_pos[:, None])[None]
        if has_segments:
            same = (segment_ids[:, :, None] ==
                    seg_cur[:, None, :])  # [B, Sq, Sk]
            allowed = same if allowed is None else (allowed & same)
        if allowed is not None:
            scores = jnp.where(allowed[:, None], scores, _NEG_INF)

        blk_max = scores.max(axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(scores - new_max[..., None])
        new_sum = row_sum * correction + probs.sum(axis=-1)
        blk_out = jnp.einsum("bhqk,bkhd->bqhd",
                             probs.astype(v_cur.dtype), v_cur
                             ).astype(jnp.float32)
        acc = acc * correction.transpose(0, 2, 1)[..., None] + blk_out

        # rotate k/v (+ their segment ids) to the next device; overlap
        # with the next step's compute
        perm = [(i, (i + 1) % ring_size) for i in range(ring_size)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        seg_next = jax.lax.ppermute(seg_cur, axis_name, perm) \
            if has_segments else seg_cur  # no dead collective without segs
        return (acc, new_max, new_sum, k_next, v_next, seg_next)

    carry = (acc, row_max, row_sum, k, v, seg_kv0)
    carry = jax.lax.fori_loop(0, ring_size, body, carry)
    acc, row_max, row_sum = carry[0], carry[1], carry[2]

    # fully-masked rows (can happen for the first queries under causal with
    # padding) keep sum==0; guard the divide
    denom = jnp.maximum(row_sum, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def sequence_sharded_call(body_fn, q: jax.Array, k: jax.Array, v: jax.Array,
                          segment_ids: Optional[jax.Array] = None,
                          mesh: Optional[Mesh] = None,
                          causal: bool = True) -> jax.Array:
    """Shared shard_map plumbing for context-parallel attention bodies
    (ring / Ulysses): shard the sequence dim over the 'sequence' axis and
    the batch over the batch axes, falling back to plain flash attention
    when the mesh has no usable sequence axis (or the shape doesn't fit —
    init passes batch=1, which is not divisible by the batch axes).

    `body_fn(q, k, v, segment_ids=..., axis_name=..., causal=...)` runs on
    local shards with `axis_name` in scope.
    """
    mesh = mesh or get_mesh()

    def _flash_fallback():
        from fengshen_tpu.ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal,
                               segment_ids=segment_ids)

    if mesh is None or SEQUENCE_AXIS not in mesh.shape or \
            mesh.shape[SEQUENCE_AXIS] == 1:
        return _flash_fallback()

    from fengshen_tpu.parallel.partition import _spec_fits
    spec = _spec_fits(P(BATCH_AXES, SEQUENCE_AXIS, None, None), mesh,
                      tuple(q.shape))
    if SEQUENCE_AXIS not in jax.tree_util.tree_leaves(tuple(spec)):
        return _flash_fallback()
    in_specs = (spec, spec, spec)
    args = (q, k, v)
    body = partial(body_fn, axis_name=SEQUENCE_AXIS, causal=causal)
    if segment_ids is None:
        body = partial(body, segment_ids=None)
    else:
        in_specs = in_specs + (P(*spec[:2]),)
        args = args + (segment_ids.astype(jnp.int32),)
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=spec,
                   check_vma=False)
    return fn(*args)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           segment_ids: Optional[jax.Array] = None,
                           mesh: Optional[Mesh] = None,
                           causal: bool = True) -> jax.Array:
    """shard_map wrapper: q/k/v globally [B, S, H, D], sequence dim sharded
    over the 'sequence' axis, batch over the batch axes; segment_ids
    int32 [B, S] (padded batches map their attention_mask here, so
    sequence parallelism no longer downgrades to dense under padding)."""
    return sequence_sharded_call(ring_attention, q, k, v,
                                 segment_ids=segment_ids, mesh=mesh,
                                 causal=causal)
