"""The four reads of a model that mixes WINDOW layers (a query reads
the last `window` keys, its own among them) with FULL layers, over K/V
rows `[..., tokens, KVH, D]` as the LLaMA family caches them.

Each read carries a device scope of its own, so that a share of
roofline reads the same work whatever implements it later
(docs/observability.md):

- :func:`full_decode_attention` — one query a lane over the lane's
  whole table row, through the `decode_attention` seam as it stands.
- :func:`ring_decode_attention` — one query a lane over a RING: the
  pool holds only the lane's last `ring_blocks * block_size` tokens,
  token `p` in block `table[lane, (p // block_size) % ring_blocks]`.
  The read hands the seam a table of the lane's LIVE blocks, newest
  first (at most `window / block_size + 1` of them at any context), and
  a mask by absolute position, `cursor - window < p <= cursor`; the
  Mosaic paged kernel then walks only as far as the last valid column,
  the xla lowering gathers that many blocks and no more.
- :func:`banded_prefill_walk` — a window of queries at positions `start
  ...` over a contiguous lane: a tile of queries reads the band of
  `window + tile` keys that ends at its last position, `key_block` keys
  a step under an online softmax; what lies further back is never
  touched.
- :func:`full_prefill_walk` — the same window over rows `0 .. start +
  S`, each tile as far as its own last position.

Plain `jax.numpy` but for what the seam picks; the CPU tier-1 truth.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from fengshen_tpu.ops.gated_attention import KEY_BLOCK, _online
from fengshen_tpu.ops.pallas.decode_attention import decode_attention

WINDOW_DECODE_SCOPE = "fstpu_window_decode_attention"
FULL_DECODE_SCOPE = "fstpu_full_decode_attention"
WINDOW_PREFILL_SCOPE = "fstpu_window_prefill_attention"
FULL_PREFILL_SCOPE = "fstpu_full_prefill_attention"

_NEG_INF = -1e30

#: queries a tile of the banded walk
Q_TILE = 512


def ring_live_table(table, t, *, window: int, block_size: int):
    """(`[B, n]` pool blocks, `[B, 1, n * block_size]` bool) of ring
    rows `table` `[B, ring_blocks]` at cursors `t` `[B]`: entry `e` is
    the block of logical block `t // block_size - e`, valid where its
    token's position `p` has `t - window < p <= t`. Entries before the
    lane's first block point at block 0 and are all invalid; they sort
    last, so a walk to the last valid column never reaches them."""
    ring = table.shape[-1]
    # the window's blocks and the one the cursor stands in
    n = min(ring, -(-window // block_size) + 1)
    logical = (t // block_size)[:, None] - jnp.arange(n)[None]     # [B, n]
    held = logical >= 0
    blocks = jnp.where(held, jnp.take_along_axis(
        table, jnp.where(held, logical, 0) % ring, axis=-1), 0)
    pos = (logical[:, :, None] * block_size +
           jnp.arange(block_size)[None, None]).reshape(t.shape[0], -1)
    valid = (pos >= 0) & (pos <= t[:, None]) & (pos > t[:, None] - window)
    return blocks, valid[:, None]


def ring_decode_attention(q, k, v, table, t, *, window: int,
                          layer=None) -> jax.Array:
    """q: `[B, 1, H, D]`; k, v: ring pools `[num_blocks, block_size,
    KVH, D]` (whole `[L, num_blocks, ...]` stacks with `layer`); table:
    `[B, ring_blocks]`; t: `[B]` int32 cursors, the query's own row
    already written. Returns `[B, 1, H, D]`."""
    with jax.named_scope(WINDOW_DECODE_SCOPE):
        blocks, valid = ring_live_table(table, t, window=window,
                                        block_size=k.shape[-3])
        return decode_attention(q, k, v, valid, block_table=blocks,
                                layer=layer)


def full_decode_attention(q, k, v, table, t, *, layer=None) -> jax.Array:
    """As :func:`ring_decode_attention` over a table row that holds
    the lane from position 0: every position up to the cursor."""
    with jax.named_scope(FULL_DECODE_SCOPE):
        reach = table.shape[-1] * k.shape[-3]
        valid = jnp.arange(reach)[None] <= t[:, None]
        return decode_attention(q, k, v, valid[:, None], block_table=table,
                                layer=layer)


def _prefill_walk(q, k_rows, v_rows, start, window, scope: str,
                  q_tile: int, key_block: int) -> jax.Array:
    """The two windowed reads: a tile of `q_tile` queries walks
    `key_block` keys a step under an online softmax, scores `[H, q_tile,
    key_block]` and never cache-long. With a `window` the walk starts
    where the tile's band does and takes a fixed number of steps; without
    one it starts at row 0 and goes as far as the tile's last position (a
    dynamic trip count). The rows are read as they lie, `[B, T, KVH, D]`:
    a view that folds a token's heads re-laid a 33,792-row lane out
    once a window (PERF.md, PR 41)."""
    batch, seq, heads, dim = q.shape
    total, groups = k_rows.shape[1:3]
    rep = heads // groups
    tq = math.gcd(seq, q_tile)
    kb = math.gcd(total, key_block)
    if window is not None:
        # keys a tile's band spans, in whole steps, inside the lane
        span = min(total, -(-(window + tq - 1) // kb) * kb)
    with jax.named_scope(scope):
        qg = (q * dim ** -0.5).reshape(batch, seq // tq, tq, groups, rep,
                                       dim)

        def tile(args):
            q_tile_, first = args                  # [B, tq, G, R, D], []
            at = first + jnp.arange(tq)
            if window is None:
                lo, steps = 0, (first + tq + kb - 1) // kb
            else:
                lo, steps = jnp.maximum(first + tq - span, 0), span // kb

            def step(j, carry):
                kj = jax.lax.dynamic_slice_in_dim(k_rows, lo + j * kb, kb,
                                                  axis=1)
                vj = jax.lax.dynamic_slice_in_dim(v_rows, lo + j * kb, kb,
                                                  axis=1)
                s = jnp.einsum("bsgrd,btgd->bgrst", q_tile_, kj,
                               preferred_element_type=jnp.float32)
                pos = lo + j * kb + jnp.arange(kb)
                ok = pos[None, :] <= at[:, None]                # [tq, kb]
                if window is not None:
                    ok &= pos[None, :] > at[:, None] - window
                s = jnp.where(ok[None, None, None], s, _NEG_INF)
                return _online(carry, s, vj, "bgrst,btgd->bgrsd")

            _, l, acc = jax.lax.fori_loop(0, steps, step, (
                jnp.full((batch, groups, rep, tq), _NEG_INF, jnp.float32),
                jnp.zeros((batch, groups, rep, tq), jnp.float32),
                jnp.zeros((batch, groups, rep, tq, dim), jnp.float32)))
            out = acc / jnp.maximum(l, 1e-30)[..., None]    # [B,G,R,tq,D]
            return jnp.moveaxis(out, 3, 1).reshape(
                batch, tq, heads, dim).astype(q.dtype)

        out = jax.lax.map(tile, (jnp.moveaxis(qg, 1, 0),
                                 start + jnp.arange(seq // tq) * tq))
        return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, dim)


def full_prefill_walk(q, k_rows, v_rows, start, *, q_tile: int = Q_TILE,
                      key_block: int = KEY_BLOCK) -> jax.Array:
    """q: `[B, S, H, D]` at positions `start + arange(S)`; k_rows,
    v_rows: `[B, T, KVH, D]`, a lane's rows with the window's own at
    `start ..`. Query `i` reads rows `0 .. start + i`."""
    return _prefill_walk(q, k_rows, v_rows, start, None, FULL_PREFILL_SCOPE,
                         q_tile, key_block)


def banded_prefill_walk(q, k_rows, v_rows, start, *, window: int,
                        q_tile: int = Q_TILE,
                        key_block: int = KEY_BLOCK) -> jax.Array:
    """As :func:`full_prefill_walk` where query `i` reads only rows
    `start + i - window + 1 .. start + i`."""
    return _prefill_walk(q, k_rows, v_rows, start, window,
                         WINDOW_PREFILL_SCOPE, q_tile, key_block)
