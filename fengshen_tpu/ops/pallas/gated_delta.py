"""The chunked gated delta rule of a prefill window as one Mosaic kernel,
in two bodies: one scalar a token a head (Gated DeltaNet; first below),
or one value a key CHANNEL (Kimi Delta Attention; "The per-channel
body" further down), told apart by the gate's rank.

`ops/gated_delta.py` has the mathematics and its `jax.numpy` form (the
CPU tier-1 truth and this kernel's xla twin). That form, lowered by
XLA:TPU, spends its time on how it is lowered and not on its
arithmetic: a batched `[c, c]` triangular solve as a `custom-call` of
sequential substitution steps (8 of a window's 13 ms), a `lax.scan`
over the chunks whose `[H, Dk, Dv]` float32 state goes to HBM and back
every step (4 ms), float32 `[B, n, H, c, D]` transposes of q, k and v,
and q and k repeated per value head first (PERF.md, PR 35). Here:

- grid `(batch, key head, tiles of the window)`, the last axis
  sequential: a step takes a tile of whole chunks of q, k (`[tile, Dk]`
  at the key head's column offset of the model's own `[B, S, Hk * Dk]`)
  and of v (`[tile, rep * Dv]`: the `rep` value heads that share the key
  head lie side by side), so nothing is transposed or repeated in HBM
  and a key head's rows are read once for all its value heads;
- the group's `[rep, Dk, Dv]` float32 state lives in the output block,
  which stays in VMEM over the window's tiles: read from HBM once and
  written once a head a window;
- inside a chunk of `c = 128` tokens, all in VMEM: `Q K^T` and `K K^T`
  (one product, shared by the group), per value head the decays from
  the cumulative log-decay, `A`, and `T = (I - A)^-1` WITHOUT a loop
  over its rows (:func:`_chunk_inverse`): forward substitution by
  blocks. The 16-row diagonal blocks are solved along their DIAGONALS,
  the fifteen of a block as fifteen lane vectors over all eight blocks
  (`t_k[i] = a_k[i] + sum_p a_p[i] t_(k-p)[i - p]`: 105 multiply-adds
  on single registers); neighbouring blocks then merge by products,
  `[[T1, 0], [T2 A21 T1, T2]]`, two products a level for every pair of
  the level at once. The last merge is not formed: `V_new = T R` is
  solved through the two 64-row halves instead;
- then the `jax.numpy` form's products, with `U - W S` taken as `T
  (beta (V - exp(G) (K S)))`: `[K; Q exp(G)] S` in one product, the
  solve above, the output, the state's update;
- float32 operands, and every product at `Precision.HIGHEST`, as there.

What the chip's scheduler does not do by itself is run independent
chains of products side by side: two heads written one after the other
take half again as long as the same two interleaved level by level
(PERF.md, PR 35). So the body is written in LOCKSTEP: every chunk of
the tile and every value head of the group is a problem, the inverses
of all of them advance one level at a time, and only what reads the
state runs chunk after chunk.

The shorter `T = (I + A)(I + A^2)(I + A^4) ...` (squarings of the
nilpotent `A`) is not used: with keys that resemble each other `A^k`
grows by binomial coefficients before it cancels (1e10 in float32 at
64 rows; a prompt of one repeated token makes such keys). Every factor
of a block merge is a true inverse, whose entries the delta rule keeps
near 1; tests/test_pallas_kernels.py keeps the case.

`g` and `beta` ride in whole a head (`[rep, n, c]`, lanes along a
chunk), already masked, `g` already summed over its chunk; a token the
mask drops has `beta = 0`, `g = 0`, and its k and v rows are zeroed in
VMEM from the mask's own `[n, c]` block, so padding on either side
enters no state. A row vector becomes a column on the VPU (`_column`):
a select against the identity and a sum along the lanes, exact.

**The per-channel body** (`g` `[B, S, H, Dk]`, one value head a key
head: :func:`_channel_chunk_kernel`). The decay between tokens `i >= j`
of a chunk is `exp(G_i - G_j)` a channel: it scales the operands of
the two `[c, c]` products (`K K^T` for `A`, `Q K^T` for the chunk's own
read) and is no `[c, c]` mask on them, and `exp(-G_j)` alone overflows
(`ops/gated_delta.py`). The `jax.numpy` form lowers to a batched
triangular solve (four custom calls of 2.7 ms in Kimi's window), a
scan that carries the state through HBM and eight anchored copies of a
chunk's keys (PERF.md, PR 49). Here the grid, the state in the output
block, the blocks of q, k, v as they lie, the inverse by diagonals and
block merges and the LOCKSTEP are the scalar body's; what differs:

- the gate rides in as q and k do, a `[tile, Dk]` float32 block of `[B,
  S, H * Dk]` (a token's own log-decays); it is zeroed under the mask
  and summed along its chunk in VMEM, `G = L g` with `L` the lower
  triangle of ones (one product);
- BETWEEN the 16-row sub-blocks both products are anchored along the
  merge tree (:func:`_anchored_products`): a pair of `m`-row blocks (`m`
  = 16, 32, 64) refers the second block's rows and the first block's
  keys to the second block's first row `r`, `exp(G_i - G_r)` and
  `exp(G_r - G_j)` with `j < r <= i`, ONE array `exp(-|G - G_r|)` a
  level because `G` never rises. The second blocks' rows of q and of k,
  stacked, times every key is one `[c, Dk] x [Dk, c]` product a level:
  three a chunk for both reads (the `jax.numpy` form anchors a
  sub-block at its own first row: eight products of 16 rows). Every
  sub-block below the diagonal lies between the blocks of exactly one
  pair;
- the DIAGONAL sub-blocks are read along their sub-diagonals
  (:func:`_decayed_diagonals`), with q, k and `exp(g)` transposed
  (`[Dk, c]`, tokens along the lanes): `G_i - G_(i-p)` is the sum of
  the `p` tokens' own log-decays in between, so a key decayed over `p`
  tokens is the key decayed over `p - 1`, one lane further on, times
  that lane's own decay. A sub-diagonal is a roll by ONE lane and a
  multiply, the sum over a head's channels a sum of registers, and no
  exponent is formed at all (the first body of this kind took
  `exp(G_i - G_(i-p))` a sub-diagonal, two rolls by `p` lanes and 256
  registers of `exp` a chunk a head: 1.96 ms a layer-window against
  1.64 at one head a step; PERF.md, PR 49). Those lane
  vectors are what :func:`_diagonal_inverses` substitutes along (read
  from `A`'s own sub-diagonals: the decay is no factor of `K K^T`
  here), so `A`'s diagonal sub-blocks are never laid out as a matrix;
  the read's are, by a transpose and a roll of row `i` by `i` lanes
  (:func:`_onto_diagonals`);
- then, chunk after chunk, `[K exp(G); Q exp(G)] S` in one product,
  `V_new = T (beta (V - (K exp(G)) S))` through `T`'s two halves, the
  output, and `S <- Diag(exp(G_last)) S + (K exp(G_last - G))^T V_new`:
  five products in a row a chunk, each waiting for the last, and with
  one head a grid step that chain, not the MXU's rate, bounds the step
  (taking the three anchored products out of a timing-only copy moved
  nothing). So a grid step takes FOUR heads (`_channel_heads`: blocks
  `[tile, 4 * D]`, two chunks a tile), whose chains are written side by
  side as the scalar body writes a key head's value heads: 1.64 ms a
  layer-window with one head a step, 1.41 with two, 1.33 with four
  (forming `W = T (beta K exp(G))` and `U = T (beta V)` before the
  state is read leaves two products in a row and read 1.52 / 1.46 /
  1.45: more products, no shorter; PERF.md, PR 49).

No exponent is ever positive, every sub-block on or below the diagonal
is computed whatever the gate has decayed it to, and every product is
float32 at `HIGHEST`, as the twin's.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fengshen_tpu.ops.gated_delta import PREFILL_SCOPE

_HIGHEST = jax.lax.Precision.HIGHEST

#: tokens a chunk: the rows of an MXU pass and the lanes of a register
#: (the result does not depend on it; the diagonals' lane vectors do)
CHUNK = 128

#: rows of a diagonal block of `A` that is solved along its diagonals
_BLOCK = 16

#: (chunk, value head) problems a grid step advances side by side: the
#: chunks of a tile times the value heads of a key head (under a
#: per-channel gate, times the heads of a step; there too 8 read
#: fastest of 2, 4, 8 and 16: PERF.md, PR 49)
_LOCKSTEP = 8

#: heads a grid step takes under a per-channel gate (`_channel_heads`)
_CHANNEL_HEADS = 4

_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
#: what of it a step's blocks (two slots each), the state and the
#: problems' `[c, c]` matrices may take
_BLOCK_BYTES = 32 * 1024 * 1024


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _iotas(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _column(row, eye):
    """A `[1, c]` row as a `[c, 1]` column: exact, on the VPU."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _diagonals(kk):
    """`[_BLOCK, c]`, row `p` the `p`-th subdiagonal of the `[c, c]`
    `kk` as a lane vector over its ROW index: `out[p, i] = kk[i, i -
    p]` (junk where `i < p`). The columns are negated by a permutation
    product (exact: one 1 a column), row `i` is rolled `i` lanes (the
    chip rolls a row by a multiple of its index, forwards only), which
    brings subdiagonal `p` to column `p`, and a transpose lays it along
    the lanes."""
    c = kk.shape[0]
    rows, cols = _iotas((c, c))
    negated = _dot(kk, jnp.where((rows + cols) % c == 0, 1.0, 0.0))
    return pltpu.roll(negated, 0, 1, stride=1, stride_axis=0).T[:_BLOCK]


def _block_inverses(kk_diagonals, g_row, beta_row):
    """`(I - A_d)^-1 - I` for `A_d`, the `_BLOCK`-row diagonal blocks of
    the chunk's `A = -strict_tril(beta_i (k_i . k_j) exp(G_i - G_j))`,
    by forward substitution along the diagonals. `kk_diagonals`:
    :func:`_diagonals` of `K K^T`; `g_row`, `beta_row`: `[1, c]`.

    With `a_p[i] = A[i, i - p]` and `t_k[i] = T[i, i - k]` as lane
    vectors over the row `i`, `T = I + A T` reads `t_k[i] = a_k[i] +
    sum_(p < k) a_p[i] t_(k-p)[i - p]`: a roll by `p` lanes and a
    multiply-add on one register a term. `a_p` is zeroed where `i - p`
    leaves `i`'s block, which keeps every `t_k` inside it. The `t_k`
    go back as rows `c - k` of a `[c, c]` matrix, transposed and rolled
    row by row onto their diagonals."""
    c = g_row.shape[1]
    sub, lane = _iotas((_BLOCK, c))
    shifted = jnp.concatenate(
        [g_row] + [pltpu.roll(g_row, p, 1) for p in range(1, _BLOCK)],
        axis=0)                                     # row p: G[i - p]
    inside = (lane % _BLOCK >= sub) & (sub >= 1)
    a = jnp.where(inside, -beta_row * kk_diagonals * jnp.exp(
        jnp.where(inside, g_row - shifted, 0.0)), 0.0)
    return _diagonal_inverses([a[p:p + 1] for p in range(_BLOCK)])


def _diagonal_inverses(a):
    """The substitution of :func:`_block_inverses` on `a`, a list of
    `_BLOCK` `[1, c]` lane vectors (`a[p][i] = A[i, i - p]`, zeroed
    where `i - p` leaves `i`'s block; `a[0]` is not read): `(I -
    A_d)^-1 - I` as a `[c, c]` matrix."""
    c = a[1].shape[1]
    t = [None] * _BLOCK
    for k in range(1, _BLOCK):
        t[k] = a[k]
        for p in range(1, k):
            t[k] = t[k] + a[p] * pltpu.roll(t[k - p], p, 1)
    on_rows = jnp.concatenate(
        [jnp.zeros((c - _BLOCK + 1, c), jnp.float32)] +
        [t[k] for k in range(_BLOCK - 1, 0, -1)], axis=0)
    return pltpu.roll(on_rows.T, 0, 1, stride=1, stride_axis=0)


def _merge_masks(c, upto):
    """For block sizes `m = _BLOCK, 2 _BLOCK, ... < upto`: where of a
    `[c, c]` matrix the entries lie between two neighbouring blocks of
    `m` rows that merge into one of `2m`."""
    rows, cols = _iotas((c, c))
    m, out = _BLOCK, []
    while m < upto:
        out.append((rows // (2 * m) == cols // (2 * m)) &
                   (rows // m != cols // m))
        m *= 2
    return out


def _merged(inverses, lowers, upto):
    """The block inverses of several problems (`[c, c]` each, exact
    inside `_BLOCK`-row diagonal blocks, with their strictly lower
    triangular `A`s in `lowers`) merged pairwise up to blocks of `upto`
    rows: `T + T (A_between T)`, two products a level, every problem
    advancing a level before any starts the next (the chip's scheduler
    does not interleave independent chains of products by itself)."""
    for between in _merge_masks(lowers[0].shape[0], upto):
        inner = [_dot(jnp.where(between, a, 0.0), t)
                 for a, t in zip(lowers, inverses)]
        inverses = [t + _dot(t, y) for t, y in zip(inverses, inner)]
    return inverses


def _chunk_inverse(kk, a, g_row, beta_row, upto):
    """`(I - a)^-1` of ONE chunk's strictly lower triangular `[c, c]`
    `a` (built from `kk`, `g_row`, `beta_row` as
    :func:`_block_inverses` says), exact inside diagonal blocks of
    `upto` rows and zero between them, by the kernel's own steps: a
    test holds it against `solve_triangular`."""
    rows, cols = _iotas(a.shape)
    blocks = jnp.where(rows == cols, 1.0, 0.0) + _block_inverses(
        _diagonals(kk), g_row, beta_row)
    return _merged([blocks], [a], upto)[0]


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, keep_ref, s_ref,
                  o_ref, so_ref, *, rep, dk, dv):
    """One tile of one key head's window: `q_ref` / `k_ref` `[1, tile,
    Dk]`, `v_ref` / `o_ref` `[1, tile, rep * Dv]`, `g_ref` (cumulative
    inside a chunk) / `beta_ref` `[1, rep, n, c]`, `keep_ref` `[1, n,
    c]`, `s_ref` / `so_ref` `[1, rep, Dk, Dv]` float32."""
    tile = pl.program_id(2)
    c, half = CHUNK, CHUNK // 2
    per_tile = q_ref.shape[1] // c

    @pl.when(tile == 0)
    def _take_state():
        so_ref[...] = s_ref[...]

    rows, cols = _iotas((c, c))
    eye, lower, strict = rows == cols, rows >= cols, rows > cols
    last = cols == c - 1
    identity = jnp.where(eye, 1.0, 0.0)

    # what does not read the state, for every chunk of the tile
    chunks = []
    for i in range(per_tile):
        at = pl.ds(i * c, c)
        nth = pl.ds(tile * per_tile + i, 1)
        kept = _column(keep_ref[0, nth, :], eye) > 0.0      # [c, 1]
        q = q_ref[0, at, :].astype(jnp.float32)
        k = jnp.where(kept, k_ref[0, at, :].astype(jnp.float32), 0.0)
        # Q K^T over K K^T: what the group's heads share
        scores = _dot(jnp.concatenate([q, k], axis=0), k,
                      (((1,), (1,)), ((), ())))             # [2c, c]
        qk, kk = scores[:c], scores[c:]
        kk_diagonals = _diagonals(kk)
        chunk = dict(at=at, q=q, k=k, qk=qk, heads=[])
        for r in range(rep):
            lanes = slice(r * dv, (r + 1) * dv)
            g_row, beta_row = g_ref[0, r, nth, :], beta_ref[0, r, nth, :]
            g_col, beta_col = _column(g_row, eye), _column(beta_row, eye)
            # exp(G_i - G_j) for i >= j: every exponent <= 0
            decay = jnp.where(lower, jnp.exp(
                jnp.where(lower, g_col - g_row, 0.0)), 0.0)
            chunk["heads"].append(dict(
                r=r, lanes=lanes, g_row=g_row, g_col=g_col,
                beta_col=beta_col, decay=decay,
                v=jnp.where(kept, v_ref[0, at, lanes].astype(jnp.float32),
                            0.0),
                a=jnp.where(strict, -(beta_col * kk) * decay, 0.0),
                inverse=identity + _block_inverses(kk_diagonals, g_row,
                                                   beta_row)))
        chunks.append(chunk)
    problems = [head for chunk in chunks for head in chunk["heads"]]
    # up to the two 64-row blocks: the last merge is never formed
    for p, inverse in zip(problems, _merged(
            [p["inverse"] for p in problems], [p["a"] for p in problems],
            half)):
        p["inverse"] = inverse

    # what does, chunk after chunk, the group's heads side by side
    for chunk in chunks:
        at, q, k, qk = chunk["at"], chunk["q"], chunk["k"], chunk["qk"]
        for p in chunk["heads"]:
            p["state"] = so_ref[0, p["r"]]
            p["grown"] = jnp.exp(p["g_col"])
        for p in chunk["heads"]:
            p["from_state"] = _dot(jnp.concatenate(
                [k, q * p["grown"]], axis=0), p["state"])   # [2c, Dv]
        # V_new = U - W S = T (beta (v - exp(G) (k S))), solved through
        # T's two 64-row blocks: the top half, then the bottom half
        # with what the top half hands it through A's block between
        for p in chunk["heads"]:
            p["rhs"] = p["beta_col"] * (
                p["v"] - p["grown"] * p["from_state"][:c])
        for p in chunk["heads"]:
            p["top"] = _dot(p["inverse"][:half, :half], p["rhs"][:half])
        for p in chunk["heads"]:
            p["handed"] = p["rhs"][half:] + _dot(p["a"][half:, :half],
                                                 p["top"])
        for p in chunk["heads"]:
            p["v_new"] = jnp.concatenate(
                [p["top"], _dot(p["inverse"][half:, half:], p["handed"])],
                axis=0)
        for p in chunk["heads"]:
            out = p["from_state"][c:] + _dot(qk * p["decay"], p["v_new"])
            o_ref[0, at, p["lanes"]] = out.astype(o_ref.dtype)
        for p in chunk["heads"]:
            # the chunk's last G in every row of a column, and along a row
            g_last = jnp.sum(jnp.where(last, p["g_row"], 0.0), axis=1,
                             keepdims=True)                 # [c, 1]
            so_ref[0, p["r"]] = jnp.exp(jnp.broadcast_to(
                g_last[:1], (1, dv))) * p["state"] + _dot(
                k * jnp.exp(g_last - p["g_col"]), p["v_new"],
                (((0,), (0,)), ((), ())))


def _pair_anchors(x, m):
    """`[c, D]`, every row the first row of the SECOND `m`-row block of
    the row's pair of blocks: what both blocks of a merge refer their
    exponents to (the second block's rows from above, the first's keys
    from below)."""
    return jnp.concatenate(
        [jnp.broadcast_to(x[at + m:at + m + 1], (2 * m, x.shape[1]))
         for at in range(0, x.shape[0], 2 * m)], axis=0)


def _second_blocks(x, m):
    """The rows of `x` that lie in the second `m`-row block of a pair,
    `[c / 2, D]`."""
    return jnp.concatenate(
        [x[at:at + m] for at in range(m, x.shape[0], 2 * m)], axis=0)


def _onto_second_blocks(x, m):
    """:func:`_second_blocks` undone, zeros in the first blocks."""
    zeros = jnp.zeros((m, x.shape[1]), x.dtype)
    return jnp.concatenate(
        [part for at in range(0, x.shape[0], m)
         for part in (zeros, x[at:at + m])], axis=0)


def _anchor_masks(c):
    """For `m = _BLOCK, 2 _BLOCK, ... < c`: where of a `[c, c]` matrix
    row `i` lies in the second `m`-row block of a pair and column `j`
    in the first. Every sub-block of `_BLOCK` rows below the diagonal
    lies in exactly one."""
    rows, cols = _iotas((c, c))
    m, out = _BLOCK, []
    while m < c:
        out.append((m, (rows // (2 * m) == cols // (2 * m)) &
                    (rows // m > cols // m)))
        m *= 2
    return out


def _anchored_products(problems):
    """Per problem (`q`, `k` `[c, Dk]`, `G` the cumulative log-decays),
    `M[i, j] = sum_d r_i[d] k_j[d] exp(G_i[d] - G_j[d])` for `r = q` and
    `r = k` where `i` and `j` lie in different `_BLOCK`-row sub-blocks,
    `i` below `j` (0 elsewhere), with no exponent ever positive: what
    `ops.gated_delta._channel_decayed_products` computes below the
    diagonal, anchored along the merge tree instead of a sub-block's
    row. A pair of `m`-row blocks refers both factors to the second
    block's first row `r`: `exp(G_i - G_r)` for its rows, `exp(G_r -
    G_j)` for the first block's keys (`j < r <= i`), ONE `[c, Dk]`
    array `exp(-|G - G_r|)` a level since `G` never rises. The second
    blocks' rows of q and of k, stacked, times every key: one `[c, Dk]
    x [Dk, c]` product a level, three a chunk, every problem advancing
    a level before any starts the next. Sets `qk_off`, `kk_off`."""
    c = problems[0]["G"].shape[0]
    for p in problems:
        p["qk_off"] = p["kk_off"] = jnp.zeros((c, c), jnp.float32)
    for m, between in _anchor_masks(c):
        for p in problems:
            to_anchor = jnp.exp(-jnp.abs(p["G"] - _pair_anchors(p["G"], m)))
            keys = p["k"] * to_anchor
            p["stacked"] = _dot(jnp.concatenate(
                [_second_blocks(p["q"], m) * _second_blocks(to_anchor, m),
                 _second_blocks(keys, m)], axis=0), keys,
                (((1,), (1,)), ((), ())))                   # [c, c]
        for p in problems:
            qk, kk = (_onto_second_blocks(x, m) for x in (
                p["stacked"][:c // 2], p["stacked"][c // 2:]))
            p["qk_off"] = p["qk_off"] + jnp.where(between, qk, 0.0)
            p["kk_off"] = p["kk_off"] + jnp.where(between, kk, 0.0)


def _decayed_diagonals(qt, kt, decays):
    """The diagonal sub-blocks of the same two `[c, c]` reads, along
    their sub-diagonals: `[_BLOCK, c]` each, row `p` lane `i` = `sum_d
    r_i[d] k_(i-p)[d] exp(G_i[d] - G_(i-p)[d])`, zero where `i - p`
    leaves `i`'s sub-block (`r = q`, then `r = k`, whose row 0 is not
    formed). `qt`, `kt`: q and k TRANSPOSED, `[Dk, c]`, tokens along
    the lanes; `decays`: `exp(g)` likewise, a token's own decay. The
    difference `G_i - G_(i-p)` is the sum of the `p` tokens' own
    log-decays in between, so a key decayed over `p` tokens is the key
    decayed over `p - 1`, one lane further on, times that lane's own
    decay (the recurrence's own arithmetic: every factor <= 1, no
    exponent formed at all, nothing cancelled between two large `G`):
    a roll by ONE lane and a multiply a sub-diagonal, and the sum over a
    head's channels a sum of registers. A lane whose roll wraps or
    leaves the sub-block holds a finite product that the select
    drops."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, kt.shape[1]), 1)
    qk, kk = [], [jnp.zeros_like(lane, jnp.float32)]
    decayed = kt
    for p in range(_BLOCK):
        if p:
            decayed = pltpu.roll(decayed, 1, 1) * decays
        inside = lane % _BLOCK >= p
        qk.append(jnp.where(inside, jnp.sum(
            qt * decayed, axis=0, keepdims=True), 0.0))
        if p:
            kk.append(jnp.where(inside, jnp.sum(
                kt * decayed, axis=0, keepdims=True), 0.0))
    return jnp.concatenate(qk, axis=0), jnp.concatenate(kk, axis=0)


def _onto_diagonals(diagonals):
    """`[_BLOCK, c]`, row `p` lane `i`, as the `[c, c]` matrix whose
    entry `[i, i - p]` it is (zero elsewhere; a lane `i < p` must hold
    zero): rows `c - p`, transposed and rolled row by row, as
    :func:`_diagonal_inverses` lays its own."""
    c = diagonals.shape[1]
    on_rows = jnp.concatenate(
        [diagonals[:1], jnp.zeros((c - _BLOCK, c), jnp.float32)] +
        [diagonals[p:p + 1] for p in range(_BLOCK - 1, 0, -1)], axis=0)
    return pltpu.roll(on_rows.T, 0, 1, stride=1, stride_axis=0)


def _channel_chunk_matrices(problems, upto):
    """Per problem (`q`, `k`, `g`, `G` `[c, Dk]`: a token's own
    log-decays and their running sum; `beta_row` `[1, c]`, `beta_col`
    `[c, 1]`) what a chunk computes before it reads the state: `qk`,
    the read of the chunk's own tokens (`tril`, decays inside); `a`,
    the chunk's `A` BETWEEN its `_BLOCK`-row sub-blocks (the diagonal
    sub-blocks of `A` are read along their diagonals only; the merges
    and the hand-over read what lies between); `inverse`, `(I - A)^-1`
    exact inside diagonal blocks of `upto` rows and zero between them;
    `gt`, `g` transposed. In lockstep."""
    c = problems[0]["G"].shape[0]
    rows, cols = _iotas((c, c))
    identity = jnp.where(rows == cols, 1.0, 0.0)
    _anchored_products(problems)
    for p in problems:
        p["gt"] = p["g"].T
        qk_d, kk_d = _decayed_diagonals(p["q"].T, p["k"].T,
                                        jnp.exp(p["gt"]))
        p["qk"] = p["qk_off"] + _onto_diagonals(qk_d)
        a_d = -p["beta_row"] * kk_d
        p["inverse"] = identity + _diagonal_inverses(
            [a_d[d:d + 1] for d in range(_BLOCK)])
        p["a"] = -(p["beta_col"] * p["kk_off"])
    for p, inverse in zip(problems, _merged(
            [p["inverse"] for p in problems], [p["a"] for p in problems],
            upto)):
        p["inverse"] = inverse


def _channel_chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, keep_ref,
                          s_ref, o_ref, so_ref, *, heads, dk, dv):
    """One tile of `heads` heads' window under a per-channel gate:
    `q_ref` / `k_ref` / `g_ref` (a token's own log-decays, float32)
    `[1, tile, heads * Dk]`, `v_ref` / `o_ref` `[1, tile, heads * Dv]`
    (a step's heads lie side by side), `beta_ref` `[1, heads, n, c]`,
    `keep_ref` `[1, n, c]`, `s_ref` / `so_ref` `[1, heads, Dk, Dv]`
    float32."""
    tile = pl.program_id(2)
    c, half = CHUNK, CHUNK // 2
    per_tile = q_ref.shape[1] // c

    @pl.when(tile == 0)
    def _take_state():
        so_ref[...] = s_ref[...]

    rows, cols = _iotas((c, c))
    eye = rows == cols
    summed = jnp.where(rows >= cols, 1.0, 0.0)

    # what does not read the state, for every chunk of the tile and
    # every head of the step (up to the two 64-row blocks of T: the
    # last merge is never formed)
    chunks = []
    for i in range(per_tile):
        at = pl.ds(i * c, c)
        nth = pl.ds(tile * per_tile + i, 1)
        kept = _column(keep_ref[0, nth, :], eye) > 0.0      # [c, 1]
        chunk = dict(at=at, kept=kept, heads=[])
        for r in range(heads):
            keys = slice(r * dk, (r + 1) * dk)
            beta_row = beta_ref[0, r, nth, :]
            chunk["heads"].append(dict(
                r=r, lanes=slice(r * dv, (r + 1) * dv), beta_row=beta_row,
                beta_col=_column(beta_row, eye),
                q=q_ref[0, at, keys].astype(jnp.float32),
                k=jnp.where(kept, k_ref[0, at, keys].astype(jnp.float32),
                            0.0),
                # a masked token decays nothing
                g=jnp.where(kept, g_ref[0, at, keys].astype(jnp.float32),
                            0.0)))
        chunks.append(chunk)
    problems = [head for chunk in chunks for head in chunk["heads"]]
    for p in problems:
        p["G"] = _dot(summed, p["g"])       # G_i = sum of g up to i: <= 0
    _channel_chunk_matrices(problems, half)

    # what does, chunk after chunk, the step's heads side by side (a
    # head's chain of products is serial; the heads' chains interleave)
    for chunk in chunks:
        at, kept = chunk["at"], chunk["kept"]
        for p in chunk["heads"]:
            p["state"] = so_ref[0, p["r"]]
            grown = jnp.exp(p["G"])
            p["from_state"] = _dot(jnp.concatenate(
                [p["k"] * grown, p["q"] * grown], axis=0),
                p["state"])                                 # [2c, Dv]
        # V_new = T (beta (v - (k exp(G)) S)), through T's two blocks
        for p in chunk["heads"]:
            v = jnp.where(kept, v_ref[0, at, p["lanes"]].astype(jnp.float32),
                          0.0)
            p["rhs"] = p["beta_col"] * (v - p["from_state"][:c])
        for p in chunk["heads"]:
            p["top"] = _dot(p["inverse"][:half, :half], p["rhs"][:half])
        for p in chunk["heads"]:
            p["handed"] = p["rhs"][half:] + _dot(p["a"][half:, :half],
                                                 p["top"])
        for p in chunk["heads"]:
            p["v_new"] = jnp.concatenate(
                [p["top"], _dot(p["inverse"][half:, half:], p["handed"])],
                axis=0)
        for p in chunk["heads"]:
            out = p["from_state"][c:] + _dot(p["qk"], p["v_new"])
            o_ref[0, at, p["lanes"]] = out.astype(o_ref.dtype)
        for p in chunk["heads"]:
            # the chunk's last G: down a column (a state row's decay
            # over the chunk, beside its row) and along a row
            decay = jnp.exp(jnp.sum(p["gt"], axis=1, keepdims=True))
            so_ref[0, p["r"]] = decay * p["state"] + _dot(
                p["k"] * jnp.exp(p["G"][c - 1:c] - p["G"]), p["v_new"],
                (((0,), (0,)), ((), ())))


def _channel_heads(heads: int) -> int:
    """Heads a grid step takes under a per-channel gate, the most of
    `_CHANNEL_HEADS`, its half, ... that divides the model's heads:
    what reads the state is one serial chain of products a head, and
    several heads' chains written side by side interleave (module
    docstring)."""
    together = _CHANNEL_HEADS
    while heads % together:
        together //= 2
    return together


def _chunks_a_tile(n_chunks: int, rep: int) -> int:
    """The largest divisor of the window's chunks that keeps a grid
    step within `_LOCKSTEP` problems."""
    most = max(_LOCKSTEP // rep, 1)
    return max(d for d in range(1, most + 1) if n_chunks % d == 0)


def _lanes_along_a_chunk(x, keep, pad):
    """A per-token scalar `[B, S, H]` as `[B, H, n, c]` float32 rows
    (lanes along a chunk), zeroed where `keep` `[B, S]` is False and
    over the `pad` tokens that fill the last chunk."""
    batch, seq, heads = x.shape
    x = jnp.where(keep[..., None], x.astype(jnp.float32), 0.0)
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return jnp.moveaxis(x, 1, 2).reshape(batch, heads, (seq + pad) // CHUNK,
                                         CHUNK)


def _ineligible_reason(q, v, g=None) -> Optional[str]:
    """Why a window of this shape cannot take the chunk kernel, or None
    when it can. q: `[B, S, Hk, Dk]`; v: `[B, S, Hv, Dv]`; g: the gate,
    `[B, S, Hv]` or (per key channel) `[B, S, Hv, Dk]`, where the caller
    has it. Under a multi-device mesh the answer is the xla lowering
    (GSPMD cannot partition a Mosaic call; the serving window runs on
    one chip)."""
    from fengshen_tpu.parallel.mesh import get_mesh
    _, seq, key_heads, dk = q.shape
    heads, dv = v.shape[2:]
    channel = g is not None and g.ndim == 4
    if channel and heads != key_heads:
        return f"gate per channel over {heads // key_heads} value heads " \
               "a key head: the per-channel body takes one"
    mesh = get_mesh()
    if mesh is not None and mesh.size > 1:
        return f"{mesh.size}-device mesh: GSPMD cannot partition a " \
               "Mosaic call"
    if dk % 128 != 0:
        return f"Dk {dk} % 128 != 0"
    if dv % 128 != 0:
        return f"Dv {dv} % 128 != 0"
    if seq < CHUNK:
        return f"window {seq} shorter than a chunk of {CHUNK}"
    rep = _channel_heads(heads) if channel else heads // key_heads
    per_tile = _chunks_a_tile(-(-seq // CHUNK), rep)
    rows, matrix = 2 * per_tile * CHUNK, CHUNK * max(CHUNK, dk, dv) * 4
    if channel:
        # q, k, g, v and the output in two slots, the state's two
        # blocks, and a problem's arrays: q, k, g, G, three transposes,
        # a level's factor and keys, the two reads, A, T
        step = rows * rep * (2 * dk * q.dtype.itemsize +
                             dk * g.dtype.itemsize +
                             2 * dv * v.dtype.itemsize) + \
            4 * rep * dk * dv * 4 + per_tile * rep * 16 * matrix
        under = f"for {rep} heads a step under a per-channel gate"
    else:
        step = rows * (2 * dk * q.dtype.itemsize +
                       2 * rep * dv * v.dtype.itemsize) + \
            4 * rep * dk * dv * 4 + per_tile * (4 + 6 * rep) * matrix
        under = f"for {rep} value heads a key head"
    if step > _BLOCK_BYTES:
        return f"a step's blocks and matrices {under} ({step} B) " \
               "outgrow VMEM"
    return None


def pallas_gated_delta_prefill(q, k, v, g, beta, state, mask=None, *,
                               interpret: bool = False):
    """`ops.gated_delta.gated_delta_prefill` as a Mosaic kernel, the
    same arguments and results. q, k: `[B, S, Hk, Dk]` with `Hk`
    dividing v's `Hv` (value head `h` reads key head `h // (Hv // Hk)`,
    as `jnp.repeat` would lay them); v: `[B, S, Hv, Dv]`; g, beta: `[B,
    S, Hv]`; state: `[B, Hv, Dk, Dv]` float32; mask `[B, S]` or None.
    A window that is not whole chunks is padded to them (masked).
    A gate per key channel (`g` `[B, S, Hv, Dk]`, `Hv == Hk`) takes
    the kernel's other body (:func:`pallas_channel_gated_delta_prefill`).
    Named and scoped `PREFILL_SCOPE`, so a trace finds the delta rule
    by that text whichever path ran."""
    if g.ndim == 4:
        return pallas_channel_gated_delta_prefill(
            q, k, v, g, beta, state, mask, interpret=interpret)
    batch, seq, key_heads, dk = q.shape
    heads, dv = v.shape[2:]
    rep, chunk = heads // key_heads, CHUNK
    n = -(-seq // chunk)
    per_tile = _chunks_a_tile(n, rep)
    tile, pad = per_tile * chunk, n * chunk - seq
    with jax.named_scope(PREFILL_SCOPE):
        keep = jnp.ones((batch, seq), bool) if mask is None \
            else mask.astype(bool)

        g_sum = jnp.cumsum(_lanes_along_a_chunk(g, keep, pad), axis=-1)
        beta = _lanes_along_a_chunk(beta, keep, pad)
        keep = jnp.pad(keep, ((0, 0), (0, pad))).astype(
            jnp.float32).reshape(batch, n, chunk)
        if pad:
            q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for x in (q, k, v))
        q, k = (x.reshape(batch, n * chunk, key_heads * dk) for x in (q, k))
        v = v.reshape(batch, n * chunk, heads * dv)

        def rows_of(width):
            return pl.BlockSpec((1, tile, width), lambda b, h, t: (b, t, h))

        per_head = pl.BlockSpec((1, rep, n, chunk),
                                lambda b, h, t: (b, h, 0, 0))
        state_spec = pl.BlockSpec((1, rep, dk, dv),
                                  lambda b, h, t: (b, h, 0, 0))
        out, new_state = pl.pallas_call(
            functools.partial(_chunk_kernel, rep=rep, dk=dk, dv=dv),
            grid=(batch, key_heads, n // per_tile),
            in_specs=[rows_of(dk), rows_of(dk), rows_of(rep * dv),
                      per_head, per_head,
                      pl.BlockSpec((1, n, chunk), lambda b, h, t: (b, 0, 0)),
                      state_spec],
            out_specs=[rows_of(rep * dv), state_spec],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct(state.shape, jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret, name=PREFILL_SCOPE,
        )(q, k, v, g_sum, beta, keep, state.astype(jnp.float32))
        return out[:, :seq].reshape(batch, seq, heads, dv), new_state


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_channel_gated_delta_prefill(q, k, v, g, beta, state, mask=None,
                                       *, interpret: bool = False):
    """:func:`pallas_gated_delta_prefill` under a gate per key channel:
    q, k, g `[B, S, H, Dk]` (g a token's log-decays, float32), v `[B,
    S, H, Dv]`, beta `[B, S, H]`, state `[B, H, Dk, Dv]` float32, mask
    `[B, S]` or None. q, k, v and g are read where they lie, `[tile,
    D]` blocks at the head's column offset of `[B, S, H * D]`; g is
    summed along a chunk inside the kernel. Jitted, so that the layers
    of an unrolled model share one lowering a program."""
    batch, seq, heads, dk = q.shape
    dv = v.shape[-1]
    chunk = CHUNK
    n = -(-seq // chunk)
    together = _channel_heads(heads)
    per_tile = _chunks_a_tile(n, together)
    tile, pad = per_tile * chunk, n * chunk - seq
    with jax.named_scope(PREFILL_SCOPE):
        keep = jnp.ones((batch, seq), bool) if mask is None \
            else mask.astype(bool)
        beta = _lanes_along_a_chunk(beta, keep, pad)
        keep = jnp.pad(keep, ((0, 0), (0, pad))).astype(
            jnp.float32).reshape(batch, n, chunk)
        g = g.astype(jnp.float32)
        if pad:
            q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for x in (q, k, v, g))
        q, k, g = (x.reshape(batch, n * chunk, heads * dk)
                   for x in (q, k, g))
        v = v.reshape(batch, n * chunk, heads * dv)

        def rows_of(width):
            return pl.BlockSpec((1, tile, together * width),
                                lambda b, h, t: (b, t, h))

        state_spec = pl.BlockSpec((1, together, dk, dv),
                                  lambda b, h, t: (b, h, 0, 0))
        out, new_state = pl.pallas_call(
            functools.partial(_channel_chunk_kernel, heads=together, dk=dk,
                              dv=dv),
            grid=(batch, heads // together, n // per_tile),
            in_specs=[rows_of(dk), rows_of(dk), rows_of(dv), rows_of(dk),
                      pl.BlockSpec((1, together, n, chunk),
                                   lambda b, h, t: (b, h, 0, 0)),
                      pl.BlockSpec((1, n, chunk), lambda b, h, t: (b, 0, 0)),
                      state_spec],
            out_specs=[rows_of(dv), state_spec],
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                       jax.ShapeDtypeStruct(state.shape, jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret, name=PREFILL_SCOPE,
        )(q, k, v, g, beta, keep, state.astype(jnp.float32))
        return out[:, :seq].reshape(batch, seq, heads, dv), new_state
