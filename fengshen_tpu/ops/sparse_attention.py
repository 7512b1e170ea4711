"""Sparse attention by selection: each query reads a chosen part of what
is cached. Two selectors, no code shared between their choices:

**Pooled blocks** (InfLLM-V2, as in MiniCPM4, arXiv:2506.07900; no
parameters): a query past `dense_len` reads `topk` blocks of
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

**A learned indexer** (DeepSeek-Sparse-Attention's, arXiv:2512.02556;
`index_scores` ... `indexed_prefill_attention` below): a few small
heads of their own score every cached token, and the query reads the
`topk` SINGLE tokens of highest score, one choice a layer shared by all
its heads:

    I_{t,s} = scale * sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)     for s <= t
    S_t     = the `topk` positions s <= t of largest I_{t,s} (all of
              them while t < topk; of equal scores the lower position)
    o^h_t   = softmax attention over the tokens of S_t

(`qI` `[J, Di]` and `w` `[J]` projected from the query's token, `kI`
`[Di]` one key a cached token.) The chosen set is a token list (a mask
over positions), not a block list.

What lives here is the mathematics both the decode tick and a prefill
window share — the pooling, the scores, the choice — and the window
form of the read; the tick's read through the block table is the
`decode_attention` seam's sparse entry (`sparse_decode_attention`,
`indexed_decode_attention`). All of it is `jax.numpy`: the CPU tier-1
truth and, until a trace says otherwise, the chip's lowering too. The
choice is per QUERY everywhere (a choice shared by a tile of queries
would be another model).

Positions are physical cache positions counted from the first cached
token: the pooled windows, the blocks and the chosen positions are
defined from token 0, so a lane is filled from position 0 and padded on
the right.
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


# ---- a learned indexer: single tokens chosen by their own small heads ----

INDEX_SCORE_SCOPE = "fstpu_index_score"
INDEX_TOPK_SCOPE = "fstpu_index_topk"
INDEXED_PREFILL_SCOPE = "fstpu_indexed_prefill_attention"


def weigh_heads(products, w, scale: float):
    """`scale * sum_j w_j ReLU(products_j)`: products `[B, S, J, ...]`
    float32 (a query head's dot with each key), w `[B, S, J]`. Returns
    `[B, S, ...]` float32. `+ 0.0` turns a `-0.0` (a negative weight
    times a ReLU's zero) into `0.0`, so that equal scores have equal
    bits for `topk_token_mask`."""
    w = w.astype(jnp.float32).reshape(w.shape + (1,) * (products.ndim - 3))
    return (jnp.maximum(products, 0.0) * w).sum(axis=2) * scale + 0.0


def index_scores(qi, w, ki, scale: float):
    """`I_{t,s}` for every query against every key handed in. qi: `[B,
    S, J, Di]`; w: `[B, S, J]`; ki: `[B, T, Di]`. Returns `[B, S, T]`
    float32. The products take the operands as they are cached (bf16 on
    the chip) and accumulate in float32; the ReLU, the weights and the
    sum over heads are float32 (`weigh_heads`)."""
    return weigh_heads(
        jnp.einsum("bsjd,btd->bsjt", qi, ki.astype(qi.dtype),
                   preferred_element_type=jnp.float32), w, scale)


def _order_key(x):
    """float32 -> uint32, the same order (no NaN; `-0.0` sorts under
    `0.0`, which `index_scores` never produces). Above 0 for every
    float, so 0 stands for "no key here"."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    bits = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ \
        jnp.uint32(0x80000000)


def topk_token_mask(scores, valid, topk: int):
    """`[..., T]` bool: the `topk` positions of largest score among the
    `valid` ones, all of them while they are no more than `topk`; of
    equal scores the lower position first (as `lax.top_k` orders equal
    values). scores: `[..., T]` float32; valid: broadcastable bool.

    No sort: the `topk`-th largest value is found bit by bit (32 counts
    of "how many keys are at least this"), then everything above it is
    taken and the lowest positions among its equals fill the rest. A
    sort of 33,000 scores a query, 2,048 queries a window, is a hundred
    compare-exchange passes; this is 32 reads of the plane."""
    u = jnp.where(valid, _order_key(scores), jnp.uint32(0))

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
            jnp.uint32)))
        enough = (u >= cand).sum(-1, keepdims=True, dtype=jnp.int32) >= topk
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(u.shape[:-1] + (1,), jnp.uint32))
    above = u > kth
    equal = (u == kth) & (u > 0)
    room = topk - above.sum(-1, keepdims=True, dtype=jnp.int32)
    # ties AT the threshold are rare (a score is a float32 sum of 16
    # products); the running count that ranks them is skipped without
    ties = equal.sum(-1, keepdims=True, dtype=jnp.int32) > room
    return above | jax.lax.cond(
        ties.any(),
        lambda: equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                         <= room),
        lambda: equal)


def topk_tokens(scores, valid, topk: int):
    """The same choice as `topk_token_mask` as a LIST: `[..., topk]`
    int32 positions, highest score first, beside `[..., topk]` bool
    (False past the count of `valid` positions; such an entry names no
    token and must be ignored). `lax.top_k` puts the lower position of
    equal scores first. For a few rows (a tick's one query a lane): a
    sort a row is cheaper there than the mask and a search of its
    running count for each of `topk` ranks (1.6 against 6.6 ms at 16
    rows of 33,280; at a window's 256 rows the mask is 1.1 ms against
    9.3: PERF.md, PR 36)."""
    values, index = jax.lax.top_k(
        jnp.where(valid, scores.astype(jnp.float32), -jnp.inf), topk)
    return index.astype(jnp.int32), values > -jnp.inf


def masked_attention_walk(q, k, v, allowed, t_last, *, k_tile: int = 1024):
    """Softmax attention of `q` `[B, Tq, H, D]` over the keys `allowed`
    `[B, Tq, E]` marks of k, v `[B, E, G, D]`, walked in tiles of
    `k_tile` keys with an online softmax up to position `t_last` (int32
    scalar, may be traced: no tile past it holds an allowed key). Every
    query allows at least one key. Returns float32 `[B, Tq, H, D]`."""
    batch, q_tile, heads, dim = q.shape
    extent, groups = k.shape[1], k.shape[2]
    k_tile = math.gcd(extent, k_tile)
    rep = heads // groups
    scale = dim ** -0.5
    qg = jnp.moveaxis(q.reshape(batch, q_tile, groups, rep, dim), 1, 3)

    def walk(i, carry):
        m_run, l_run, acc = carry
        ks = jax.lax.dynamic_slice_in_dim(k, i * k_tile, k_tile, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, i * k_tile, k_tile, 1)
        ok = jax.lax.dynamic_slice_in_dim(allowed, i * k_tile, k_tile, 2)
        ok = ok[:, None, None]                             # [B,1,1,Tq,k]
        sc = jnp.einsum("bgrqd,bkgd->bgrqk", qg, ks,
                        preferred_element_type=jnp.float32) * scale
        sc = jnp.where(ok, sc, _NEG_INF)
        m_new = jnp.maximum(m_run, sc.max(-1))
        p = jnp.where(ok, jnp.exp(sc - m_new[..., None]), 0.0)
        fix = jnp.exp(m_run - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(v.dtype), vs,
            preferred_element_type=jnp.float32)
        return m_new, l_run * fix + p.sum(-1), acc

    shape = (batch, groups, rep, q_tile)
    init = (jnp.full(shape, _NEG_INF, jnp.float32),
            jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (dim,), jnp.float32))
    n_tiles = jnp.minimum(t_last // k_tile + 1, extent // k_tile)
    _, l_run, acc = jax.lax.fori_loop(0, n_tiles, walk, init)
    out = acc / jnp.maximum(l_run, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(batch, q_tile, heads, dim)


def index_extents(seq: int, cache_len: int, topk: int, step: int) -> tuple:
    """The cache extents a window of `seq` queries is compiled for: a
    window whose last query stands at `t` reads `cache[:E]` for the
    smallest `E > t` here. `topk` itself where a window fits in it (a
    context within `topk` selects nothing), every multiple of `step`,
    and the cache's own length."""
    found = {e for e in range(step, cache_len, step)} | {cache_len}
    if seq <= topk <= cache_len:
        found.add(topk)
    return tuple(sorted(e for e in found if e >= seq))


def indexed_prefill_attention(q, k, v, qi, w, ki, t0, *, topk: int,
                              index_scale: float, extent_step: int = 4096,
                              q_tile: int = 256, k_tile: int = 1024):
    """A window of queries over a contiguous cache that already holds
    the window's own rows. q: `[B, S, H, D]` at positions `t0 + 0..S-1`
    (`t0`: int32 scalar, may be traced); k, v: `[B, T, G, D]`; qi:
    `[B, S, J, Di]`, w: `[B, S, J]`, ki: `[B, T, Di]` (the indexer's
    queries, weights and cached keys). Returns `[B, S, H, D]`.

    One branch a cache extent (`index_extents`), so that a window's
    scores, its selection and its walk cover the tokens cached and not
    the cache's size: the score plane of a tile of `q_tile` queries is
    `[q_tile, E]`, never `[S, T]`. Inside a branch each tile scores
    every key up to its own last position, takes its per-query choice
    (`topk_token_mask`) and walks the keys under it
    (`masked_attention_walk`); a branch within `topk` has nothing to
    choose and walks under the causal mask."""
    batch, seq, heads, dim = q.shape
    q_tile = math.gcd(seq, q_tile)
    n = seq // q_tile
    extents = index_extents(seq, k.shape[1], topk, extent_step)

    def tiles(x):
        return jnp.moveaxis(x.reshape((batch, n, q_tile) + x.shape[2:]),
                            1, 0)

    def branch(extent: int):
        def run(q, k, v, qi, w, ki, t0):
            k, v, ki = k[:, :extent], v[:, :extent], ki[:, :extent]
            pos = jnp.arange(extent)

            def tile(args):
                qt, qit, wt, start = args
                t = start + jnp.arange(q_tile)
                allowed = jnp.broadcast_to(
                    pos[None, None, :] <= t[None, :, None],
                    (batch, q_tile, extent))
                if extent > topk:
                    with jax.named_scope(INDEX_SCORE_SCOPE):
                        scores = index_scores(qit, wt, ki, index_scale)
                    with jax.named_scope(INDEX_TOPK_SCOPE):
                        allowed = topk_token_mask(scores, allowed, topk)
                with jax.named_scope(INDEXED_PREFILL_SCOPE):
                    return masked_attention_walk(
                        qt, k, v, allowed, start + q_tile - 1,
                        k_tile=k_tile)

            starts = t0 + jnp.arange(n, dtype=jnp.int32) * q_tile
            out = jax.lax.map(tile, (tiles(q), tiles(qi), tiles(w), starts))
            return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, dim)
        return run

    which = jnp.searchsorted(jnp.asarray(extents, jnp.int32), t0 + seq,
                             side="left")
    out = jax.lax.switch(jnp.minimum(which, len(extents) - 1),
                         [branch(e) for e in extents],
                         q, k, v, qi, w, ki, t0)
    return out.astype(q.dtype)
