"""Paged + optionally int8-quantized KV pool for the serving engine.

The slot pool (`serving/cache.py`) preallocates one `[max_len]` lane
per slot, so a replica's concurrency is bounded by WORST-CASE sequence
length even when most requests are short. This module carves the same
byte budget into fixed-size blocks instead (the paged-attention idea):

- device side: per layer, `cached_key`/`cached_value` become a shared
  `[num_blocks, block_size, kv_heads, head_dim]` pool plus a
  shape-static `[num_slots, max_blocks_per_slot]` `block_table` of
  block ids and a `[num_slots]` `cache_index` of physical cursors.
  `modeling_llama._update_paged_cache` scatters each decode step at
  `table[lane, idx // bs] * bs + idx % bs`; the read belongs to the
  `decode_attention` seam (the Mosaic kernel walks the table, the xla
  lowering gathers the lane's blocks into a contiguous virtual lane
  with `jnp.take` — pure gather/scatter, so XLA-CPU tier-1 runs it
  unchanged). Under `scan_layers` every leaf gains a leading `[L]`
  axis; the model's layer loop carries those stacks as its state and
  addresses them as one pool of `L * num_blocks` blocks, so the decode
  program, jitted with the pool donated, updates it in place;
- host side: `BlockAllocator`, a plain free list. ALL allocation math
  (alloc/free/accounting) stays in Python on the scheduler thread —
  nothing here is ever traced (the fslint fixture
  `tests/analysis_fixtures/paged_cache_clean.py` pins that split);
- block 0 is the NULL block: never allocated, parked-on by every free
  lane's table row. Stray writes from inactive lanes land there and
  are never read back unmasked.

The int8 mode stores the pools as int8 with fp32 per-(token, head)
absmax scales (`cached_key_scale`/`cached_value_scale`, the
`ops/int8_matmul.py` quantize idiom) — 1 byte/element + one float per
head per token, ~3.7x more KV tokens in the same bytes — and
dequantizes inside the attention read. The same scale layout works for
the slot layout (`init_pool_cache(layout="slot", kv_dtype="int8")`).
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from fengshen_tpu.ops.int8_matmul import quantize_kv

#: the reserved garbage block free lanes point at (never allocated)
NULL_BLOCK = 0


def blocks_for_tokens(n_tokens: int, block_size: int) -> int:
    """ceil(n_tokens / block_size): the engine's admission charge for a
    request footprint. The ONE place the rounding lives — a speculative
    engine must charge `bucket + max_new + gamma` tokens (the verify
    window over-scatters up to gamma rejected entries past the cursor,
    and those writes must land in blocks the lane owns, never in a
    neighbour's)."""
    return -(-int(n_tokens) // int(block_size))


class BlockAllocator:
    """Host-side free list over the paged KV pool.

    Deterministic allocation: lowest-id-first from a fresh pool, then
    LIFO reuse (most-recently-freed first — freed blocks go back on
    the tail). Double-free and foreign-id frees raise instead of
    silently corrupting the pool. Lives strictly on the scheduler
    thread — the traced decode only ever sees the resulting
    block-table rows as device arrays.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (block {NULL_BLOCK} is the reserved "
                f"null block), got {num_blocks}")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))
        self._used: set[int] = set()

    @property
    def total_blocks(self) -> int:
        """Allocatable blocks (the null block is not one)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return len(self._used)

    def alloc(self, n: int) -> Optional[List[int]]:
        """`n` block ids, or None when the pool can't serve them all —
        the caller requeues the request (admission backpressure)."""
        if n < 1:
            raise ValueError(f"alloc needs n >= 1, got {n}")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._used.update(blocks)
        return blocks

    def free(self, blocks: List[int]) -> None:
        for b in blocks:
            if b not in self._used:
                raise ValueError(
                    f"free of block {b} that is not allocated "
                    "(double-free or foreign id)")
            self._used.remove(b)
            self._free.append(b)


def _map_attn_dicts(tree, fn):
    """Rebuild a cache pytree, applying `fn` to every attention-cache
    dict (the one holding `cached_key`). Works for scan and non-scan
    layouts alike — the structure is nested plain dicts either way."""
    if isinstance(tree, dict):
        if "cached_key" in tree:
            return fn(tree)
        return {k: _map_attn_dicts(v, fn) for k, v in tree.items()}
    return tree


def _zip_attn_dicts(pool, primed, fn):
    """Like `_map_attn_dicts` but walks the pool and a primed batch-1
    cache (which lacks the paged/scale leaves) in lockstep."""
    if isinstance(pool, dict):
        if "cached_key" in pool:
            return fn(pool, primed)
        return {k: _zip_attn_dicts(v, primed[k], fn) for k, v in
                pool.items()}
    return pool


def _vmap_layers(fn, lead: int):
    """Map a per-layer function over `lead` leading layer axes (0 for
    unrolled layers, 1 under scan_layers)."""
    for _ in range(lead):
        fn = jax.vmap(fn)
    return fn


def init_pool_cache(model, num_slots: int, *, layout: str = "slot",
                    kv_dtype: str = "fp32", num_blocks: int = 0,
                    block_size: int = 0, max_blocks_per_slot: int = 0):
    """Zeros KV pool for the engine — the one constructor for all four
    (layout, dtype) combinations. Abstract-init only, like
    `cache.init_slot_cache` (which this generalizes; the fp32 slot
    result is structurally identical to it)."""
    if layout not in ("slot", "paged"):
        raise ValueError(f"unknown kv layout {layout!r}")
    if kv_dtype not in ("fp32", "int8"):
        raise ValueError(f"unknown kv dtype {kv_dtype!r}")
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((num_slots, 1), jnp.int32),
                           init_cache=True))

    def build(d):
        ck = d["cached_key"]
        lead = ck.shape[:-4]                 # (layers,) under scan
        n_kv, head_dim = ck.shape[-2:]
        pool_dt = jnp.int8 if kv_dtype == "int8" else ck.dtype
        if layout == "paged":
            val_shape = lead + (num_blocks, block_size, n_kv, head_dim)
            scale_shape = lead + (num_blocks, block_size, n_kv)
        else:
            val_shape = lead + d["cached_key"].shape[-4:]
            scale_shape = lead + ck.shape[-4:-1]
        out = {
            "cached_key": jnp.zeros(val_shape, pool_dt),
            "cached_value": jnp.zeros(val_shape, pool_dt),
            "cache_index": jnp.zeros(lead + (num_slots,), jnp.int32),
        }
        if kv_dtype == "int8":
            out["cached_key_scale"] = jnp.zeros(scale_shape, jnp.float32)
            out["cached_value_scale"] = jnp.zeros(scale_shape,
                                                  jnp.float32)
        if layout == "paged":
            out["block_table"] = jnp.zeros(
                lead + (num_slots, max_blocks_per_slot), jnp.int32)
        return out
    return _map_attn_dicts(abstract["cache"], build)


def assign_slot_quantized(pool, primed, slot):
    """int8 flavor of `cache.assign_slot`: quantize the fp32 primed
    lane (the direct `_prefill_cache` output) per (token, head) while
    scattering it into int8 lane `slot`. `slot` may be traced."""
    def put(pool_d, prim_d):
        lead = pool_d["cached_key"].ndim - 4

        def vals(pool_leaf, prim_leaf, pick):
            def one(p, s):
                return jax.lax.dynamic_update_slice(
                    p, pick(quantize_kv(s[0]))[None], (slot,) +
                    (0,) * (p.ndim - 1))
            return _vmap_layers(one, lead)(pool_leaf, prim_leaf)

        out = dict(pool_d)
        out["cached_key"] = vals(pool_d["cached_key"],
                                 prim_d["cached_key"], lambda qs: qs[0])
        out["cached_value"] = vals(pool_d["cached_value"],
                                   prim_d["cached_value"],
                                   lambda qs: qs[0])
        out["cached_key_scale"] = vals(pool_d["cached_key_scale"],
                                       prim_d["cached_key"],
                                       lambda qs: qs[1])
        out["cached_value_scale"] = vals(pool_d["cached_value_scale"],
                                         prim_d["cached_value"],
                                         lambda qs: qs[1])
        out["cache_index"] = pool_d["cache_index"].at[..., slot].set(
            prim_d["cache_index"].astype(pool_d["cache_index"].dtype))
        return out
    return _zip_attn_dicts(pool, primed, put)


def assign_paged(pool, primed, slot, table_row):
    """Scatter a primed batch-1 cache into the blocks of `table_row`
    (a `[max_blocks_per_slot]` int32 vector from the host allocator,
    padded with the null block) and point lane `slot` at them.

    The first `max_blocks * block_size` tokens of the primed lane are
    copied wholesale — unpadded-row entries land in the lane's real
    blocks, padding entries clobber the null block (by design: garbage
    that is never read unmasked). One compiled program for every
    bucket, mirroring `assign_slot`. Quantizes on the way in when the
    pool is int8."""
    def put(pool_d, prim_d):
        ck = pool_d["cached_key"]
        lead = ck.ndim - 4
        num_blocks, block_size = ck.shape[-4:-2]
        max_blocks = pool_d["block_table"].shape[-1]
        virt_len = max_blocks * block_size
        int8 = "cached_key_scale" in pool_d
        positions = ((table_row * block_size)[:, None] +
                     jnp.arange(block_size)[None, :]).reshape(-1)

        def vals(pool_leaf, prim_leaf, pick):
            def one(p, s):
                src = s[0, :virt_len]            # [V, kv, hd] fp32
                val = pick(quantize_kv(src)) if int8 else \
                    src.astype(p.dtype)
                flat = p.reshape((num_blocks * block_size,) + p.shape[2:])
                return flat.at[positions].set(val).reshape(p.shape)
            return _vmap_layers(one, lead)(pool_leaf, prim_leaf)

        out = dict(pool_d)
        out["cached_key"] = vals(pool_d["cached_key"],
                                 prim_d["cached_key"], lambda qs: qs[0])
        out["cached_value"] = vals(pool_d["cached_value"],
                                   prim_d["cached_value"],
                                   lambda qs: qs[0])
        if int8:
            out["cached_key_scale"] = vals(pool_d["cached_key_scale"],
                                           prim_d["cached_key"],
                                           lambda qs: qs[1])
            out["cached_value_scale"] = vals(
                pool_d["cached_value_scale"], prim_d["cached_value"],
                lambda qs: qs[1])
        out["cache_index"] = pool_d["cache_index"].at[..., slot].set(
            prim_d["cache_index"].astype(pool_d["cache_index"].dtype))
        out["block_table"] = pool_d["block_table"].at[
            ..., slot, :].set(table_row)
        return out
    return _zip_attn_dicts(pool, primed, put)
