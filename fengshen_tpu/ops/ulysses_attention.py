"""Ulysses-style all-to-all sequence parallelism.

The second context-parallel scheme next to ring attention (the reference
has neither — SURVEY.md §5.7). Instead of rotating k/v shards around a
ring, two `all_to_all` collectives re-shard the activations from
sequence-sharded [B, S/sp, H, D] to head-sharded [B, S, H/sp, D], run an
ordinary (flash) attention over the FULL sequence on each device, and
shard back (DeepSpeed-Ulysses, Jacobs et al. 2023 — public technique,
re-implemented here with XLA collectives over the ICI mesh).

Trade-off vs ring: Ulysses moves each activation token exactly twice
(a2a in, a2a out — O(S·H·D/sp) per device) and keeps the attention kernel
untouched (the fused Pallas flash kernel runs as-is on the gathered
sequence), but requires num_heads % sp == 0 and materializes the full-S
kv on each device, so per-device attention memory is O(S) rather than
ring's O(S/sp). `sequence_parallel_attention` auto-picks: Ulysses when
heads divide (kernel-friendly), ring otherwise or when
`prefer="ring"` (longest contexts).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from fengshen_tpu.parallel.mesh import SEQUENCE_AXIS, get_mesh


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      segment_ids: Optional[jax.Array] = None,
                      axis_name: str = SEQUENCE_AXIS,
                      causal: bool = True) -> jax.Array:
    """Attention over a sequence-sharded batch; call inside shard_map.

    q/k/v: local shards [B, S_local, H, D] with contiguous sequence layout
    (shard i holds positions [i*S_local, (i+1)*S_local)) — the same
    contract as `ring_attention`. segment_ids: local int32 [B, S_local].
    Requires H % axis_size == 0.
    """
    from fengshen_tpu.ops.flash_attention import flash_attention

    sp = jax.lax.axis_size(axis_name)
    num_heads = q.shape[2]
    if num_heads % sp:
        raise ValueError(
            f"ulysses needs num_heads ({num_heads}) divisible by the "
            f"sequence-parallel degree ({sp}); use ring attention instead")

    # [B, S/sp, H, D] -> [B, S, H/sp, D]: head-chunk j goes to device j,
    # received sequence chunks concatenate in device order = global order
    a2a_in = partial(jax.lax.all_to_all, axis_name=axis_name,
                     split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = a2a_in(q), a2a_in(k), a2a_in(v)
    seg_g = None
    if segment_ids is not None:
        seg_g = jax.lax.all_gather(segment_ids, axis_name, axis=1,
                                   tiled=True)  # [B, S]

    out = flash_attention(qg, kg, vg, causal=causal, segment_ids=seg_g)

    # [B, S, H/sp, D] -> [B, S/sp, H, D]
    return jax.lax.all_to_all(out, axis_name=axis_name,
                              split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                              segment_ids: Optional[jax.Array] = None,
                              mesh: Optional[Mesh] = None,
                              causal: bool = True) -> jax.Array:
    """shard_map wrapper: q/k/v globally [B, S, H, D], sequence dim sharded
    over the 'sequence' axis, batch over the batch axes (shares the
    plumbing with `ring_attention_sharded`)."""
    from fengshen_tpu.ops.ring_attention import sequence_sharded_call
    return sequence_sharded_call(ulysses_attention, q, k, v,
                                 segment_ids=segment_ids, mesh=mesh,
                                 causal=causal)


def sequence_parallel_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                                segment_ids: Optional[jax.Array] = None,
                                mesh: Optional[Mesh] = None,
                                causal: bool = True,
                                prefer: str = "auto") -> jax.Array:
    """Context-parallel attention with scheme auto-selection.

    prefer: "auto" (Ulysses when num_heads divides the sequence degree —
    one fused kernel over the full sequence, 2 a2a hops; ring otherwise),
    "ring" (O(S/sp) per-device memory, any head count — the choice for
    the longest contexts), or "ulysses".
    """
    from fengshen_tpu.ops.ring_attention import ring_attention_sharded

    mesh = mesh or get_mesh()
    sp = mesh.shape.get(SEQUENCE_AXIS, 1) if mesh is not None else 1
    num_heads = q.shape[2]
    if prefer == "ring":
        use_ulysses = False
    elif prefer == "ulysses":
        use_ulysses = True
    elif prefer == "auto":
        use_ulysses = sp > 1 and num_heads % sp == 0
    else:
        raise ValueError(f"unknown prefer={prefer!r}")
    if use_ulysses:
        return ulysses_attention_sharded(q, k, v, segment_ids=segment_ids,
                                         mesh=mesh, causal=causal)
    return ring_attention_sharded(q, k, v, segment_ids=segment_ids,
                                  mesh=mesh, causal=causal)
