"""Slot-pool KV cache: `num_slots` preallocated lanes, per-slot indices.

The models' flax cache (modeling_llama.py `_update_cache`) preallocates
`[B, max_len, kv, hd]` lanes but advances ONE scalar `cache_index` for
the whole batch — right for lockstep batch decode, wrong for a serving
pool where every lane is a different request at different progress.
These helpers build a pool whose `cache_index` leaves are `[num_slots]`
vectors (the attention layer's vector-index path picks that up and
writes each lane at its own position), scatter a freshly prefilled
request into a free lane, and reset reclaimed lanes — all shape-static,
so ONE jitted decode step serves every in-flight mix.

Leaf layout contract (holds for the whole zoo, scan_layers or not):
the row leaves a model declares (`cached_key`/`cached_value`, or one
`cached_latent` under latent attention) end in (..., batch, max_len,
heads, dim) and `cache_index` is scalar per layer — identified by path
via `utils.generate.is_cache_index_path`, the same predicate
`_rollback_cache` keys on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fengshen_tpu.utils.generate import is_cache_index_path


def abstract_init(model, num_slots: int):
    """The model's `init_cache` pass over one token a lane, abstract:
    every collection it creates ("cache", and whatever it sows) as
    shapes, no param materialised (same trick as
    `utils.generate._prefill_cache`)."""
    return jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((num_slots, 1), jnp.int32),
                           init_cache=True))


def init_slot_cache(model, num_slots: int, abstract=None):
    """Zeros cache pytree with `num_slots` lanes and VECTOR cache_index
    leaves (`[num_slots]`, or `[layers, num_slots]` under scan_layers).
    `abstract`: an `abstract_init` the caller already made."""
    if abstract is None:
        abstract = abstract_init(model, num_slots)

    def build(path, leaf):
        if is_cache_index_path(path):
            # slotify: one write position per lane
            return jnp.zeros(leaf.shape + (num_slots,), jnp.int32)
        return jnp.zeros(leaf.shape, leaf.dtype)
    return jax.tree_util.tree_map_with_path(build, abstract["cache"])


def assign_slot(pool, primed, slot):
    """Scatter a single-request primed cache (batch 1, scalar index —
    the direct output of `_prefill_cache`) into lane `slot` of the pool.
    `slot` may be traced, so reclaiming a lane for the next queued
    request reuses the ONE compiled program. The full lane is
    overwritten, so stale K/V from the evicted request cannot leak."""
    def put(path, p, s):
        if is_cache_index_path(path):
            # p [..., S]; s scalar per layer
            return p.at[..., slot].set(s.astype(p.dtype))
        axis = p.ndim - 4  # (..., batch, max_len, kv, hd)
        start = [0] * p.ndim
        start[axis] = slot
        return jax.lax.dynamic_update_slice(p, s.astype(p.dtype),
                                            tuple(start))
    return jax.tree_util.tree_map_with_path(put, pool, primed)


def rollback_slots(cache, delta):
    """Per-slot analog of `utils.generate._rollback_cache`: lower each
    lane's cache_index by `delta` ([num_slots] vector). Sound for the
    same reason as the scalar version — entries past the index are
    masked out and overwritten in place. The engine's speculative tick
    leans on this every verify: the forward advances all lanes by
    gamma+1 and each lane rolls back its own rejected tail
    (serving/engine.py, docs/serving.md "Speculative decoding")."""
    def fix(path, leaf):
        if is_cache_index_path(path):
            return leaf - jnp.asarray(delta, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def reset_free_slots(cache, active):
    """Clamp the cache_index of inactive lanes to 0 (`active` is a
    [num_slots] bool vector). Free lanes still ride through every decode
    step (static shapes); without the clamp their index would creep one
    per tick and eventually walk the garbage writes off the end of the
    preallocated lane.

    On a paged pool (serving/paged_cache.py) the same clamp also parks
    inactive lanes' `block_table` rows on the null block — their blocks
    may already be reallocated to another lane, so a stale row would
    let the lane's garbage write corrupt a live request's K/V."""
    def fix(path, leaf):
        if is_cache_index_path(path):
            return jnp.where(active, leaf, 0)
        if any(getattr(k, "key", None) == "block_table" for k in path):
            return jnp.where(active[:, None], leaf, 0)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)
