"""Paged + optionally int8-quantized KV pool for the serving engine.

The slot pool (`serving/cache.py`) preallocates one `[max_len]` lane
per slot, so a replica's concurrency is bounded by WORST-CASE sequence
length even when most requests are short. This module carves the same
byte budget into fixed-size blocks instead (the paged-attention idea):

- device side: per layer, each row leaf the model declares
  (`row_leaves`: `cached_key`/`cached_value`, or one `cached_latent`
  under latent attention) becomes a shared
  `[num_blocks, block_size, heads, dim]` pool plus a
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
  are never read back unmasked;
- a cache dict may declare more than rows a token (`row_leaves`): a row
  leaf of another RATE (one row every `r` tokens, e.g. pooled keys:
  its length is the longest row leaf's over `r`) pages behind the same
  table, a block holding `block_size // r` of its rows; and a STATE
  leaf (`state_leaves`: constant size a lane, e.g. a linear-attention
  layer's `[heads, dim, dim]`) is not rows at all: the pool holds it
  `[num_slots, ...]`, an assignment copies the primed one into its
  lane, and a release zeroes nothing because the next assignment
  overwrites it. A state has no null block: the decode program keeps a
  dead lane's state by the tick's live mask (the model's `live`);
- a THIRD kind of row (`INDEX_PREFIX`): a `cached_index_*` leaf is not
  attended over but SCORED to choose which K/V rows a query reads (a
  learned indexer's key, 64 values a token beside the K/V row's 1,024):
  one row a token behind the same table, written, assigned and released
  as the K/V rows are. What it chooses is a list of the lane's token
  positions, so a cache that declares one is positional
  (`positional_leaves`);
- a FOURTH kind of row (`RING_PREFIX`): a dict of `cached_window_*`
  leaves declares that only a lane's last `W` tokens are ever read (a
  sliding-window layer's K/V). Such a dict pages behind a SECOND table
  a lane, of fixed width (`ring_blocks_per_slot`), drawn from a second
  free list (`ring_num_blocks`): token `p` lies in block `table[lane,
  (p // block_size) % width]`, so the lane holds and reads the same few
  blocks at any context, and a dict of plain rows in the same model
  keeps the table that grows with the lane. A ring is addressed by
  position, so it too is positional. The model masks its read by
  absolute position; nothing here knows `W` (the engine checks the
  ring is long enough).

The int8 mode stores the pools as int8 with fp32 per-(token, head)
absmax scales (`cached_key_scale`/`cached_value_scale`, the
`ops/int8_matmul.py` quantize idiom) — 1 byte/element + one float per
head per token, ~3.7x more KV tokens in the same bytes — and
dequantizes inside the attention read. The same scale layout works for
the slot layout (`init_pool_cache(layout="slot", kv_dtype="int8")`).
"""

from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from fengshen_tpu.ops.int8_matmul import quantize_kv
from fengshen_tpu.serving.cache import abstract_init

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


def row_leaves(d: dict) -> list:
    """The per-token state an attention-cache dict declares: its
    `cached_*` leaves (row shape `[heads, dim]`), the int8 scales
    beside them left out. A K/V model declares `cached_key` and
    `cached_value`, a latent-attention model one `cached_latent`; the
    pool is built, filled and read from whatever is declared here."""
    return sorted(k for k in d if k.startswith("cached_")
                  and not k.endswith("_scale"))


def state_leaves(d: dict) -> list:
    """The per-LANE state an attention-cache dict declares beside (or
    instead of) its rows: its `state_*` leaves, `[..., batch, ...]` of
    constant size whatever the tokens cached."""
    return sorted(k for k in d if k.startswith("state_"))


#: the row leaves a selection scores and attention does not read
#: (module docstring)
INDEX_PREFIX = "cached_index_"


#: the row leaves a lane holds only the last tokens of, as a ring
#: (module docstring)
RING_PREFIX = "cached_window_"


def ring_leaves(tree) -> list:
    """The ring leaves of an attention-cache dict, or of every such
    dict of a cache tree. A dict is a ring whole or not at all: its
    rows share one table."""
    if isinstance(tree, dict) and "cache_index" in tree:
        rows = row_leaves(tree)
        ring = [n for n in rows if n.startswith(RING_PREFIX)]
        if ring and ring != rows:
            raise ValueError(
                f"{ring} are ring leaves and {sorted(set(rows) - set(ring))}"
                " are not: the rows of one dict share one block table")
        return ring
    found: list = []
    _map_attn_dicts(tree, lambda d: found.extend(ring_leaves(d)) or d)
    return found


def _row_rate(d: dict, name: str) -> int:
    """Tokens a row of row leaf `name`: the longest row leaf holds one
    a token, a leaf `r` times shorter one every `r` tokens."""
    longest = max(d[n].shape[-3] for n in row_leaves(d))
    return longest // d[name].shape[-3]


def positional_leaves(cache) -> list:
    """The leaves of a cache tree that are defined on a lane's token
    POSITIONS counted from 0 — a state, a row leaf of another rate, a
    row leaf a selection scores, a ring — so that a lane holding them is
    filled from position 0 and padded on the right (a left-padded lane would
    shift every pooled window, and every chosen position, by its pad).
    Empty for a cache of plain rows."""
    found: list = []

    def look(d):
        rows = row_leaves(d)
        found.extend(state_leaves(d))
        found.extend(n for n in rows if _row_rate(d, n) != 1
                     or n.startswith((INDEX_PREFIX, RING_PREFIX)))
        return d
    _map_attn_dicts(cache, look)
    return found


def _map_attn_dicts(tree, fn):
    """Rebuild a cache pytree, applying `fn` to every attention-cache
    dict (the one holding `cache_index` beside its row leaves). Works
    for scan and non-scan layouts alike — the structure is nested plain
    dicts either way."""
    if isinstance(tree, dict):
        if "cache_index" in tree:
            return fn(tree)
        return {k: _map_attn_dicts(v, fn) for k, v in tree.items()}
    return tree


def _zip_attn_dicts(pool, primed, fn):
    """Like `_map_attn_dicts` but walks the pool and a primed batch-1
    cache (which lacks the paged/scale leaves) in lockstep."""
    if isinstance(pool, dict):
        if "cache_index" in pool:
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
                    block_size: int = 0, max_blocks_per_slot: int = 0,
                    ring_num_blocks: int = 0, ring_blocks_per_slot: int = 0,
                    abstract=None):
    """Zeros KV pool for the engine — the one constructor for all four
    (layout, dtype) combinations. Abstract-init only, like
    `cache.init_slot_cache` (which this generalizes; the fp32 slot
    result is structurally identical to it); `abstract` is a
    `cache.abstract_init` the caller already made. A ring dict's pool
    and table take `ring_num_blocks` and `ring_blocks_per_slot`."""
    if layout not in ("slot", "paged"):
        raise ValueError(f"unknown kv layout {layout!r}")
    if kv_dtype not in ("fp32", "int8"):
        raise ValueError(f"unknown kv dtype {kv_dtype!r}")
    if abstract is None:
        abstract = abstract_init(model, num_slots)

    def build(d):
        out = {}
        blocks, width = (ring_num_blocks, ring_blocks_per_slot) \
            if ring_leaves(d) else (num_blocks, max_blocks_per_slot)
        for name in row_leaves(d):
            leaf = d[name]
            lead = leaf.shape[:-4]           # (layers,) under scan
            heads, dim = leaf.shape[-2:]
            if layout == "paged":
                rate = _row_rate(d, name)
                if block_size % rate:
                    raise ValueError(
                        f"{name} holds a row every {rate} tokens: "
                        f"kv_block_size {block_size} must be a multiple")
                val_shape = lead + (blocks, block_size // rate, heads, dim)
            else:
                val_shape = leaf.shape
            out[name] = jnp.zeros(
                val_shape, jnp.int8 if kv_dtype == "int8" else leaf.dtype)
            if kv_dtype == "int8":
                out[name + "_scale"] = jnp.zeros(val_shape[:-1],
                                                 jnp.float32)
        for name in state_leaves(d):
            # abstract over `num_slots` lanes: already one a lane
            out[name] = jnp.zeros(d[name].shape, d[name].dtype)
        out["cache_index"] = jnp.zeros(lead + (num_slots,), jnp.int32)
        if layout == "paged":
            out["block_table"] = jnp.zeros(lead + (num_slots, width),
                                           jnp.int32)
        return out
    return _map_attn_dicts(abstract["cache"], build)


def assign_slot_quantized(pool, primed, slot):
    """int8 flavor of `cache.assign_slot`: quantize the fp32 primed
    lane (the direct `_prefill_cache` output) per (token, head) while
    scattering it into int8 lane `slot`. `slot` may be traced."""
    def put(pool_d, prim_d):
        def vals(pool_leaf, prim_leaf, pick):
            def one(p, s):
                return jax.lax.dynamic_update_slice(
                    p, pick(quantize_kv(s[0]))[None], (slot,) +
                    (0,) * (p.ndim - 1))
            return _vmap_layers(one, prim_leaf.ndim - 4)(pool_leaf,
                                                         prim_leaf)

        out = dict(pool_d)
        for name in row_leaves(pool_d):
            out[name] = vals(pool_d[name], prim_d[name],
                             lambda qs: qs[0])
            out[name + "_scale"] = vals(pool_d[name + "_scale"],
                                        prim_d[name], lambda qs: qs[1])
        out["cache_index"] = pool_d["cache_index"].at[..., slot].set(
            prim_d["cache_index"].astype(pool_d["cache_index"].dtype))
        return out
    return _zip_attn_dicts(pool, primed, put)


def assign_paged(pool, primed, slot, lane_row, ring_row=None):
    """Scatter a primed batch-1 cache into the blocks of `lane_row`
    (a `[max_blocks_per_slot]` int32 vector from the host allocator,
    padded with the null block) and point lane `slot` at them. A ring
    dict takes `ring_row` (`[ring_blocks_per_slot]`) instead, and into
    its entry `r` the LAST logical block `j <= cursor // block_size`
    with `j % width == r` of the primed lane: what the ring would hold
    had the lane been written token by token.

    The first `max_blocks * block_size` tokens of the primed lane are
    copied wholesale — unpadded-row entries land in the lane's real
    blocks, padding entries clobber the null block (by design: garbage
    that is never read unmasked). One compiled program for every
    bucket, mirroring `assign_slot`. Quantizes on the way in when the
    pool is int8."""
    def put(pool_d, prim_d):
        names = row_leaves(pool_d)
        first = pool_d[names[0]]
        lead = first.ndim - 4
        num_blocks, block_size = first.shape[-4:-2]
        max_blocks = pool_d["block_table"].shape[-1]
        ring = bool(ring_leaves(pool_d))
        table_row = ring_row if ring else lane_row
        if ring:
            # entry r <- logical block j_r; past the lane's last block
            # the entry's own number (rows no query may read yet)
            last = jnp.maximum(prim_d["cache_index"].reshape(-1)[0] - 1,
                               0) // block_size
            r = jnp.arange(max_blocks)
            logical = jnp.where(r <= last, last - (last - r) % max_blocks, r)
            tokens = (logical[:, None] * block_size +
                      jnp.arange(block_size)[None]).reshape(-1)

        # every layer's blocks in ONE scatter of whole blocks into the
        # stack viewed as `layers * num_blocks` blocks (layer l's block
        # b is block l * num_blocks + b): the buffer is updated in
        # place, and the scatter pays its per-index cost once a block.
        # A scatter mapped over the layers re-laid a one-head pool out
        # and back; one index a row took 3.6 times as long (PERF.md,
        # PR 26)
        layers = math.prod(first.shape[:lead])
        blocks = (jnp.arange(layers)[:, None] * num_blocks +
                  table_row[None, :]).reshape(-1)

        def vals(pool_leaf, prim_leaf, pick):
            # (rows a block, heads[, dim]): `block_size`, or fewer for
            # a leaf that holds a row every few tokens
            rest = pool_leaf.shape[lead + 1:]
            lanes = prim_leaf.reshape((layers,) + prim_leaf.shape[lead:])
            src = jnp.take(lanes[:, 0], tokens, axis=1, mode="clip") \
                if ring else lanes[
                :, 0, :max_blocks * rest[0]]     # [L, V, heads, dim] fp
            val = src.astype(pool_leaf.dtype) if pick is None else \
                pick(quantize_kv(src))
            flat = pool_leaf.reshape((layers * num_blocks,) + rest)
            return flat.at[blocks].set(
                val.reshape((layers * max_blocks,) + rest)
            ).reshape(pool_leaf.shape)

        out = dict(pool_d)
        for name in names:
            if name + "_scale" in pool_d:        # an int8 pool
                out[name] = vals(pool_d[name], prim_d[name],
                                 lambda qs: qs[0])
                out[name + "_scale"] = vals(
                    pool_d[name + "_scale"], prim_d[name],
                    lambda qs: qs[1])
            else:
                out[name] = vals(pool_d[name], prim_d[name], None)
        for name in state_leaves(pool_d):
            # [..., num_slots, ...] <- the primed [..., 1, ...], whole
            at = (0,) * lead + (slot,) + (0,) * (
                pool_d[name].ndim - lead - 1)
            out[name] = jax.lax.dynamic_update_slice(
                pool_d[name], prim_d[name].astype(pool_d[name].dtype), at)
        out["cache_index"] = pool_d["cache_index"].at[..., slot].set(
            prim_d["cache_index"].astype(pool_d["cache_index"].dtype))
        out["block_table"] = pool_d["block_table"].at[
            ..., slot, :].set(table_row)
        return out
    return _zip_attn_dicts(pool, primed, put)
