"""Learned block-sparse attention (InfLLM-V2, as in MiniCPM4,
arXiv:2506.07900): a query past `dense_len` reads `topk` blocks of
`block_size` cached tokens, chosen by its own scores over POOLED keys.

Per query `t` (0-based) and KV head `g` with its query heads `h in g`:

    pooled  k~_j = mean(k_{s j} .. k_{s j + K - 1})   for s j + K - 1 <= t
    p^h_j   = softmax_j(q^h . k~_j / sqrt(D))
    a_j     = sum_{h in g} p^h_j
    b_m     = max a_j over the pooled windows that overlap block m
    chosen  = the first `init_blocks` blocks, every block that overlaps
              the last `window_size` tokens, and the highest b_m among
              the rest until `topk` blocks in all
    o^h     = causal softmax attention over the tokens of the chosen
              blocks; one choice a KV head, shared by its query heads

(`K` kernel_size, `s` kernel_stride.) Up to `dense_len` tokens of
context the attention is plain causal attention over everything.

What lives here is the mathematics both the decode tick and a prefill
window share — the pooling, the choice — and the window form of the
read; the tick's read through the block table is the `decode_attention`
seam's sparse entry. All of it is `jax.numpy`: the CPU tier-1 truth and,
until a trace says the gather dominates, the chip's lowering too. The
choice is per QUERY everywhere (a choice shared by a tile of queries
would be another model).

Positions are physical cache positions counted from the first cached
token: the pooled windows and the blocks are defined from token 0, so a
lane is filled from position 0 and padded on the right.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

POOL_SCOPE = "fstpu_sparse_pool"
SELECT_SCOPE = "fstpu_sparse_select"
PREFILL_SCOPE = "fstpu_sparse_prefill_attention"

_NEG_INF = -1e30
#: a forced block's rank, above every score (scores are sums of
#: probabilities, at most the group's size)
_FORCED = 1e30


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The selection's sizes (MiniCPM4's `sparse_config`)."""

    kernel_size: int = 32       # tokens a pooled key averages
    kernel_stride: int = 16     # tokens between pooled keys
    block_size: int = 64        # tokens a selectable block
    topk: int = 64              # blocks a query reads, forced ones included
    init_blocks: int = 1        # leading blocks always read
    window_size: int = 2048     # trailing tokens always read
    dense_len: int = 8192       # contexts up to this long read everything

    def __post_init__(self):
        if self.block_size % self.kernel_stride:
            raise ValueError("block_size must be a multiple of kernel_stride")
        forced = self.init_blocks + self.window_size // self.block_size + 1
        if forced > self.topk:
            raise ValueError(
                f"{forced} forced blocks (init + window) exceed topk "
                f"{self.topk}")
        if self.dense_len < self.topk * self.block_size:
            raise ValueError(
                "dense_len must cover topk blocks: a query that selects "
                "has at least topk blocks to choose from")

    @property
    def reach(self) -> int:
        """Pooled windows before a block's first that still overlap it."""
        return (self.kernel_size - 1) // self.kernel_stride

    def attended_tokens(self, context):
        """Tokens a query with `context` cached tokens (itself included)
        attends: plain arithmetic, numpy or python ints. Past
        `dense_len` the chosen blocks are full except the query's own."""
        own = (context - 1) % self.block_size + 1
        sparse = (self.topk - 1) * self.block_size + own
        return context * (context <= self.dense_len) + \
            sparse * (context > self.dense_len)


def pool_window(rows, spec: SparseSpec, count: int):
    """`count` pooled keys from `rows` `[B, T, G, D]`: key `i` is the
    float32 mean of rows `[s i, s i + K)`. `T >= s (count - 1) + K`."""
    s, K = spec.kernel_stride, spec.kernel_size
    idx = (jnp.arange(count) * s)[:, None] + jnp.arange(K)[None, :]
    return rows.astype(jnp.float32)[:, idx].mean(axis=2)


def select_blocks(q, pooled, t, spec: SparseSpec, num_blocks: int):
    """Rank the blocks for each query. q: `[B, S, H, D]`; pooled:
    `[B, J, G, D]` (key `j` pools tokens `[s j, s j + K)`; entries
    whose window ends after a query's `t` are ignored, whatever they
    hold); t: `[B, S]` int32 query positions. Returns `rank`
    `[B, S, G, num_blocks]` float32: `_FORCED` on the forced blocks,
    the block score `b_m` on the other blocks a query may read, and
    `-inf` on the blocks past its own."""
    batch, seq, heads, dim = q.shape
    n_pooled, groups = pooled.shape[1], pooled.shape[2]
    s, K, B = spec.kernel_stride, spec.kernel_size, spec.block_size
    with jax.named_scope(SELECT_SCOPE):
        qg = q.reshape(batch, seq, groups, heads // groups, dim)
        scores = jnp.einsum("bsgrd,bjgd->bsgrj", qg, pooled.astype(q.dtype),
                            preferred_element_type=jnp.float32) * dim ** -0.5
        seen = (jnp.arange(n_pooled) * s + K - 1)[None, None, :] <= \
            t[:, :, None]                                      # [B, S, J]
        seen5 = seen[:, :, None, None, :]
        scores = jnp.where(seen5, scores, _NEG_INF)
        probs = jnp.exp(scores - scores.max(-1, keepdims=True))
        probs = jnp.where(seen5, probs, 0.0)
        probs = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-30)
        a = jnp.where(seen[:, :, None, :], probs.sum(axis=3), -1.0)
        # block m <- max over pooled windows [m r - reach, m r + r)
        r = B // s
        win = jnp.arange(num_blocks)[:, None] * r - spec.reach + \
            jnp.arange(r + spec.reach)[None, :]
        inside = (win >= 0) & (win < n_pooled)
        b = jnp.where(inside, a[..., jnp.clip(win, 0, n_pooled - 1)],
                      -1.0).max(-1)                            # [B,S,G,M]
        m = jnp.arange(num_blocks)[None, None, :]
        own = (t // B)[:, :, None]
        first_recent = (jnp.maximum(t - spec.window_size + 1, 0) // B
                        )[:, :, None]
        forced = (m < spec.init_blocks) | (m >= first_recent)
        rank = jnp.where(forced[:, :, None, :], _FORCED, b)
        return jnp.where((m <= own)[:, :, None, :], rank, -jnp.inf)


def chosen_mask(rank, t, spec: SparseSpec):
    """`[B, S, G, M]` bool: the blocks each query reads — every block up
    to its own while its context is within `dense_len`, else the `topk`
    of highest rank. Neighbouring blocks share a pooled window, so equal
    scores are common: the lower block wins, as `lax.top_k` orders equal
    values (and as the decode tick, which takes its indices, chooses)."""
    k = min(spec.topk, rank.shape[-1])
    value, index = jax.lax.top_k(rank, k)
    kth, last = value[..., -1:], index[..., -1:]
    m = jnp.arange(rank.shape[-1])
    chosen = (rank > kth) | ((rank == kth) & (m <= last))
    dense = (t + 1 <= spec.dense_len)[:, :, None, None]
    return jnp.where(dense, rank > -jnp.inf, chosen)


def sparse_prefill_attention(q, k, v, pooled, t0, spec: SparseSpec, *,
                             q_tile: int = 512, k_tile: int = 1024):
    """A window of queries over a contiguous cache that already holds
    the window's own rows. q: `[B, S, H, D]` at positions `t0 + 0..S-1`
    (`t0`: int32 scalar, may be traced); k, v: `[B, T, G, D]`; pooled:
    `[B, T // s, G, D]`. Returns `[B, S, H, D]`.

    Each tile of `q_tile` queries makes its per-query choice, then
    walks the cache in tiles of `k_tile` keys up to its own last
    position with an online softmax under the mask `causal & chosen`:
    no `[S, T]` array over the cache's extent exists, and the walk's
    length follows the tokens cached, not the cache's size."""
    batch, seq, heads, dim = q.shape
    extent, groups = k.shape[1], k.shape[2]
    B = spec.block_size
    q_tile = math.gcd(seq, q_tile)
    k_tile = math.gcd(extent, k_tile)
    if k_tile % B:
        raise ValueError(
            f"a cache of {extent} rows does not tile by whole {B}-token "
            f"blocks (largest tile {k_tile})")
    num_blocks = extent // B
    rep = heads // groups
    scale = dim ** -0.5

    def tile(args):
        qt, start = args                       # [B, Tq, H, D], scalar
        t = jnp.broadcast_to(start + jnp.arange(q_tile)[None],
                             (batch, q_tile))
        allowed = chosen_mask(
            select_blocks(qt, pooled, t, spec, num_blocks), t, spec)
        allowed = jnp.moveaxis(allowed, 2, 1)              # [B,G,Tq,M]
        qg = jnp.moveaxis(qt.reshape(batch, q_tile, groups, rep, dim),
                          1, 3)                            # [B,G,rep,Tq,D]

        def walk(i, carry):
            m_run, l_run, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(k, i * k_tile, k_tile, 1)
            vs = jax.lax.dynamic_slice_in_dim(v, i * k_tile, k_tile, 1)
            sc = jnp.einsum("bgrqd,bkgd->bgrqk", qg, ks,
                            preferred_element_type=jnp.float32) * scale
            pos = i * k_tile + jnp.arange(k_tile)
            blocks = jax.lax.dynamic_slice_in_dim(
                allowed, i * (k_tile // B), k_tile // B, 3)
            ok = jnp.repeat(blocks, B, axis=-1) & \
                (pos[None, None, None, :] <= t[:, None, :, None])
            sc = jnp.where(ok[:, :, None], sc, _NEG_INF)
            m_new = jnp.maximum(m_run, sc.max(-1))
            p = jnp.where(ok[:, :, None],
                          jnp.exp(sc - m_new[..., None]), 0.0)
            fix = jnp.exp(m_run - m_new)
            acc = acc * fix[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(v.dtype), vs,
                preferred_element_type=jnp.float32)
            return m_new, l_run * fix + p.sum(-1), acc

        shape = (batch, groups, rep, q_tile)
        init = (jnp.full(shape, _NEG_INF, jnp.float32),
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(shape + (dim,), jnp.float32))
        n_tiles = (start + q_tile + k_tile - 1) // k_tile
        _, l_run, acc = jax.lax.fori_loop(0, n_tiles, walk, init)
        out = acc / jnp.maximum(l_run, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1).reshape(batch, q_tile, heads, dim)

    with jax.named_scope(PREFILL_SCOPE):
        n = seq // q_tile
        tiles = jnp.moveaxis(q.reshape(batch, n, q_tile, heads, dim), 1, 0)
        starts = t0 + jnp.arange(n, dtype=jnp.int32) * q_tile
        out = jax.lax.map(tile, (tiles, starts))
        return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, dim) \
            .astype(q.dtype)


def gather_blocks(pool, block_ids, dim: int):
    """`pool`: `[N, B, 1, G * dim]` (N blocks of B tokens, a token's KV
    heads folded into one row); `block_ids`: `[b, G, K]` — for each KV
    head its own K blocks. Returns `[b, G, K, B, dim]`.

    Whole blocks are gathered (one contiguous slab an index, never a
    row at a time: twice the bytes a head needs, 0.5 GB a tick), the
    gathered rows are split into `[G, dim]` and each head keeps its
    own. Two other forms, both measured (my chip runs, PR 30; PERF.md):
    a gather that takes ONE head of a `[B, G, dim]` block makes XLA:TPU
    re-lay the whole pool out head-major first — eight pool-sized
    copies a tick at four layers, 22 of a 38 ms tick (5.5 ms against
    0.75 for this call alone); and slicing a head's `dim` values out of
    the gathered 256-value row (`blocks[..., g * dim:(g + 1) * dim]`)
    is right on the CPU and WRONG on the chip (half the values, at
    0.26 ms: the served tokens then sat as far off the reference as
    its int8 control). `tests/test_compile_for_v5e.py` holds the first
    off; only a chip run shows the second."""
    heads = pool.shape[-1] // dim
    blocks = jnp.take(pool[:, :, 0], block_ids, axis=0, mode="clip")
    blocks = blocks.reshape(blocks.shape[:-1] + (heads, dim))
    return jnp.stack([blocks[:, g, :, :, g] for g in range(heads)], axis=1)
