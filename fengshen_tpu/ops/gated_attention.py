"""Grouped-query softmax attention over K/V rows that FOLD a token's KV
heads into one row (`[..., tokens, KVH * D]`), for a model with few,
wide KV heads (2 of 256): the two reads of a full-attention layer
beside recurrent ones.

Neither read repeats K/V per query head, builds a score tensor as long
as the cache, or copies a pool: both walk the keys a block at a time
under an online softmax, only as far as the keys that exist (a dynamic
trip count), plain `jax.numpy` (the CPU tier-1 truth; each under its
own device scope).

- :func:`folded_decode_walk` — one query a lane (or a short window of
  them that share ONE extent: a generation block's, `fold_queries`)
  over the lane's LIVE pool blocks through its block-table row,
  `chunk_blocks` blocks a step. The xla lowering of the `decode_attention` seam's folded entry
  (`ops/pallas/decode_attention.folded_decode_attention`), which takes
  a Mosaic kernel where the rows' shape allows and this walk elsewhere.
  The query is laid into the folded row's width with zeros in the
  other heads' places (`q_h . k_row = q_h . k_g` exactly), so the
  gathered blocks are multiplied as they lie: no head is sliced out of
  a gathered row (PERF.md, PR 30: such a slice re-laid the pool, and
  once came back wrong) and nothing is transposed. What it costs, and
  the kernel does not pay: every lane walks to the LONGEST lane's
  cursor, the gather moves every block twice more, the zeros double
  the matmul work (PERF.md, PR 33).
- :func:`folded_prefill_walk` — a window of queries at positions `start
  ...` over a lane's rows `0 .. start + S` (the carried cache with the
  window's own keys already written, or the rows just projected),
  `key_block` keys a step: scores are `[H, S, key_block]`, never `[H,
  S, max_len]`.
- :func:`block_prefill_walk` — the same walk in tiles of queries under
  a BLOCK-causal mask (a query reads the whole of its own block of
  positions): the prefill of a model generated by diffusion over
  blocks (`models/sdar`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DECODE_SCOPE = "fstpu_gated_attention_decode"
PREFILL_SCOPE = "fstpu_gated_attention_prefill"

_NEG_INF = -1e30

#: pool blocks a step of the decode walk gathers, and of the folded
#: kernel fetches (1,024 tokens at 128)
CHUNK_BLOCKS = 8
#: keys a step of the prefill walk scores
KEY_BLOCK = 1024
#: queries a tile of the block-causal prefill walk scores at once
Q_TILE = 512


def _online(carry, scores, values, contract):
    """One step of the online softmax: `scores` float32 with -inf-like
    fill where masked, `values` the step's V."""
    m, l, acc = carry
    m_new = jnp.maximum(m, scores.max(-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l = l * corr + p.sum(-1)
    acc = acc * corr[..., None] + jnp.einsum(
        contract, p.astype(values.dtype), values,
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def fold_queries(q, groups: int):
    """`[B, S, H, D]` -> `[B, 1, S * H, D]` with the `S * H // G` rows
    that read KV head `g` adjacent (query-major inside a KV head): a
    window of `S` queries that all read the SAME keys is `S` times the
    query heads of one. `S = 1` is returned as it is."""
    batch, s, heads, dim = q.shape
    if s == 1:
        return q
    return q.reshape(batch, s, groups, heads // groups, dim).swapaxes(
        1, 2).reshape(batch, 1, s * heads, dim)


def unfold_queries(out, s: int, groups: int):
    """The inverse of :func:`fold_queries` on an output `[B, 1, S * H,
    D]`."""
    if s == 1:
        return out
    batch, _, rows, dim = out.shape
    return out.reshape(batch, groups, s, rows // (s * groups), dim).swapaxes(
        1, 2).reshape(batch, s, rows // s, dim)


def folded_decode_walk(q, k, v, block_table, t, *, scale: float,
                       chunk_blocks: int = CHUNK_BLOCKS):
    """q: `[B, S, H, D]`; k, v: pools `[num_blocks, block_size, 1, G *
    D]`; block_table: `[B, max_blocks]`; t: `[B]` int32, the LAST
    position every query of the lane reads (each reads positions `0 ..
    t`: one query at its own position, or a block's queries at the
    block's end). Returns `[B, S, H, D]`."""
    window = q.shape[1]
    q = fold_queries(q, k.shape[-1] // q.shape[-1])
    batch, _, heads, dim = q.shape
    block, width = k.shape[1], k.shape[-1]
    groups = width // dim
    n = min(chunk_blocks, block_table.shape[-1])
    pad = -block_table.shape[-1] % n
    if pad:             # a table entry past the row reads the null block
        block_table = jnp.pad(block_table, ((0, 0), (0, pad)))
    with jax.named_scope(DECODE_SCOPE):
        own = (jnp.arange(heads) // (heads // groups))[:, None] == \
            jnp.arange(groups)[None]                           # [H, G]
        q_row = (q[:, 0, :, None, :] * scale *
                 own[None, :, :, None].astype(q.dtype)
                 ).reshape(batch, heads, width)
        steps = (jnp.max(t) + n * block) // (n * block)

        def step(c, carry):
            blk = jax.lax.dynamic_slice_in_dim(block_table, c * n, n, axis=1)
            # table entries are pool blocks: no bounds check (the
            # default's select re-reads everything gathered)
            ks, vs = (jnp.take(x, blk, axis=0, mode="clip").reshape(
                batch, n * block, width) for x in (k, v))
            s = jnp.einsum("bhe,bte->bht", q_row, ks,
                           preferred_element_type=jnp.float32)
            pos = c * (n * block) + jnp.arange(n * block)
            s = jnp.where((pos[None] <= t[:, None])[:, None], s, _NEG_INF)
            return _online(carry, s, vs, "bht,bte->bhe")

        _, l, acc = jax.lax.fori_loop(0, steps, step, (
            jnp.full((batch, heads), _NEG_INF, jnp.float32),
            jnp.zeros((batch, heads), jnp.float32),
            jnp.zeros((batch, heads, width), jnp.float32)))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(
            batch, heads, groups, dim)
        out = (out * own[None, :, :, None]).sum(axis=2)
        return unfold_queries(out[:, None].astype(q.dtype), window, groups)


def folded_prefill_walk(q, k_rows, v_rows, start, *, scale: float,
                        key_block: int = KEY_BLOCK):
    """q: `[B, S, H, D]` at positions `start + arange(S)`; k_rows,
    v_rows: `[B, T, G * D]`, a lane's rows with the window's own at
    `start ..`; `start`: int32 scalar. Query `i` reads rows `0 .. start
    + i`. Returns `[B, S, H, D]`."""
    batch, seq, heads, dim = q.shape
    total, width = k_rows.shape[1:]
    groups = width // dim
    rep = heads // groups
    kb = math.gcd(total, key_block)
    with jax.named_scope(PREFILL_SCOPE):
        qg = (q * scale).reshape(batch, seq, groups, rep, dim)
        at = start + jnp.arange(seq)
        steps = (start + seq + kb - 1) // kb

        def step(j, carry):
            ks = jax.lax.dynamic_slice_in_dim(k_rows, j * kb, kb, axis=1) \
                .reshape(batch, kb, groups, dim)
            vs = jax.lax.dynamic_slice_in_dim(v_rows, j * kb, kb, axis=1) \
                .reshape(batch, kb, groups, dim)
            s = jnp.einsum("bsgrd,btgd->bgrst", qg, ks,
                           preferred_element_type=jnp.float32)
            ok = (j * kb + jnp.arange(kb))[None, :] <= at[:, None]  # [S,kb]
            s = jnp.where(ok[None, None, None], s, _NEG_INF)
            return _online(carry, s, vs, "bgrst,btgd->bgrsd")

        _, l, acc = jax.lax.fori_loop(0, steps, step, (
            jnp.full((batch, groups, rep, seq), _NEG_INF, jnp.float32),
            jnp.zeros((batch, groups, rep, seq), jnp.float32),
            jnp.zeros((batch, groups, rep, seq, dim), jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # [B,G,R,S,D]
        return jnp.moveaxis(out, 3, 1).reshape(
            batch, seq, heads, dim).astype(q.dtype)


def block_prefill_walk(q, k_rows, v_rows, start, *, scale: float,
                       block: int, q_tile: int = Q_TILE,
                       key_block: int = KEY_BLOCK):
    """:func:`folded_prefill_walk` under a BLOCK-causal mask, in tiles of
    queries: query `i`, at position `p = start + i`, reads rows `0 ..
    (p // block + 1) * block - 1` — every earlier block of `block`
    positions and the WHOLE of its own, the diagonal moved to the end of
    each query's block (`block = 1` is causal). A tile of `q_tile`
    queries walks the keys `key_block` at a time only as far as its own
    last block: scores are `[H, q_tile, key_block]`. No scope of its
    own: the caller names the read. Same arguments and result as
    :func:`folded_prefill_walk`."""
    batch, seq, heads, dim = q.shape
    total, width = k_rows.shape[1:]
    groups = width // dim
    rep = heads // groups
    qt = math.gcd(seq, q_tile)
    kb = math.gcd(total, key_block)
    qg = (q * scale).reshape(batch, seq // qt, qt, groups, rep, dim)

    def tile(i):
        at = start + i * qt + jnp.arange(qt)
        end = (at // block + 1) * block               # first row unread
        steps = jnp.minimum((end[-1] + kb - 1) // kb, total // kb)
        q_i = jax.lax.dynamic_index_in_dim(qg, i, axis=1, keepdims=False)

        def step(j, carry):
            ks = jax.lax.dynamic_slice_in_dim(k_rows, j * kb, kb, axis=1) \
                .reshape(batch, kb, groups, dim)
            vs = jax.lax.dynamic_slice_in_dim(v_rows, j * kb, kb, axis=1) \
                .reshape(batch, kb, groups, dim)
            s = jnp.einsum("bsgrd,btgd->bgrst", q_i, ks,
                           preferred_element_type=jnp.float32)
            ok = (j * kb + jnp.arange(kb))[None, :] < end[:, None]
            s = jnp.where(ok[None, None, None], s, _NEG_INF)
            return _online(carry, s, vs, "bgrst,btgd->bgrsd")

        _, l, acc = jax.lax.fori_loop(0, steps, step, (
            jnp.full((batch, groups, rep, qt), _NEG_INF, jnp.float32),
            jnp.zeros((batch, groups, rep, qt), jnp.float32),
            jnp.zeros((batch, groups, rep, qt, dim), jnp.float32)))
        return acc / jnp.maximum(l, 1e-30)[..., None]      # [B,G,R,qt,D]

    out = jax.lax.map(tile, jnp.arange(seq // qt))         # [N,B,G,R,qt,D]
    out = jnp.moveaxis(out, (0, 4), (1, 2))                # [B,N,qt,G,R,D]
    return out.reshape(batch, seq, heads, dim).astype(q.dtype)
