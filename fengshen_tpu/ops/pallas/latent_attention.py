"""Multi-head latent attention's FULL form as a Mosaic kernel: a window
of queries at a traced offset onto a lane of cached latent rows, the
score tile in VMEM from the product to the probabilities
(`ops/latent_attention.py` has the mathematics and the seam;
:func:`fengshen_tpu.ops.latent_attention.latent_prefill_walk` is the
xla twin, whose float32 score tiles go out to HBM between the passes of
its online softmax).

The forward of `flash_attention.py` with three differences:

- the keys are not an operand of their own. A lane's rows `[c (rank) |
  k_shared | zeros]` stay in HBM; a grid step is one (batch, head), and
  inside it a loop walks the lane a block of `KEY_BLOCK` rows at a time
  through two VMEM slots, the next block in flight while this one is
  multiplied. The trip count is traced: `(start + S + kb - 1) // kb`
  blocks are fetched and no block past the window's last query is. The
  block is expanded into THIS head's keys and values in VMEM (`[kb,
  rank] x [rank, dn + dv]`, once a block a head: every query tile of
  the window reads that one expansion), so no `[T, H, dn + dv]` copy of
  a lane exists anywhere;
- key and value differ in width: the score contracts `dn + dr` (padded
  to whole lanes: the row's `[k_shared | zeros]` columns are taken as
  they lie beside the expanded no-position part, against a query
  padded with zeros), the value product `dv`;
- queries sit at `start + i` (`start` scalar-prefetched). The window is
  cut into tiles of `Q_TILE` queries; a (tile, block) pair wholly above
  the diagonal is skipped, one wholly below it pays for no mask, and
  only the pairs the diagonal crosses compare positions. An optional
  `[B, T]` key validity (a left-padded prompt) masks a block's columns
  like the flash kernel's segment ids; a query with no valid key comes
  out finite (the mean of what it was shown) and is read by no one.

Precision is the walk's: operands in the inputs' dtype, the expansion
rounded to it, float32 scores, max, sum and accumulator, probabilities
rounded to the value's dtype for the second product only, the scale
applied to the query before the product.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fengshen_tpu.ops.latent_attention import PREFILL_SCOPE
from fengshen_tpu.ops.pallas.flash_attention import _tile

_NEG_INF = -1e30

#: queries a tile and rows a block of the walk where the caller names
#: none (the largest multiple of 128 under it that divides the length).
#: On a v5e, a window of 2,048 queries onto 10,240 keys at the cells'
#: widths: (512, 512) 3.66 ms, (512, 1024) 3.76, (1024, 1024) 3.76; a
#: 2,048-token prompt onto its own keys 0.84 / 0.90 / 0.93: smaller
#: tiles skip more of what lies above the diagonal (PERF.md, PR 47)
Q_TILE, KEY_BLOCK = 512, 512

#: lanes a row's running max and sum are held in, the same value in
#: each: a `[tq, 1]` statistic costs a lane broadcast against every
#: score vreg of its row (the (512, 512) tile 5.49 -> 3.66 ms a window at
#: 10k keys with the statistics a whole vreg wide; PERF.md, PR 47)
_STAT_LANES = 128

#: scoped VMEM the kernel asks Mosaic for: a window's queries and
#: outputs for a head (double-buffered by the pipeline), two row blocks,
#: the float32 accumulator and statistics of the whole window and a few
#: score tiles outgrow the 16 MiB default at 2,048 queries
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _lanes(x, n: int):
    """A row statistic held in every one of `_STAT_LANES` lanes,
    `[rows, _STAT_LANES]`, as wide as `n` lanes (whole vregs side by
    side, no lane broadcast)."""
    return x if n == _STAT_LANES else pltpu.repeat(x, n // _STAT_LANES,
                                                   axis=1)


def _kernel(start_ref, q_ref, w_ref, rows_hbm, valid_ref, o_ref,
            buf, sems, slot_ref, key_ref, val_ref, acc_ref, m_ref, l_ref,
            *, rank: int, dn: int, tq: int, kb: int, has_valid: bool):
    # q_ref: [1, S, dn + drp] (scaled, zero-padded); w_ref: [rank, dn +
    # dv], this head's; rows_hbm: [B, T, width] left in HBM; valid_ref:
    # [1, T // kb, 1, kb] int32; o_ref: [1, S, dv]; buf: [2, kb, width]
    b, h = pl.program_id(0), pl.program_id(1)
    seq, dk = q_ref.shape[1:]
    dv = val_ref.shape[1]
    start = start_ref[0]
    # never past the lane's end, whatever `start` says
    n_steps = jnp.minimum((start + seq + kb - 1) // kb,
                          rows_hbm.shape[1] // kb)

    def fetch(lane, j, slot):
        return pltpu.make_async_copy(
            rows_hbm.at[lane, pl.ds(pl.multiple_of(j * kb, kb), kb)],
            buf.at[slot], sems.at[slot])

    @pl.when((b == 0) & (h == 0))
    def _first_fetch():
        slot_ref[0] = 0
        fetch(0, 0, 0).start()

    # which slot this head's first block went into: the head before
    # started that copy under its own last block (all heads walk the
    # same rows, `n_steps` of them)
    first_slot = slot_ref[0]
    slot_ref[0] = (first_slot + n_steps) % 2
    last_head = h + 1 == pl.num_programs(1)
    last_step = (b + 1 == pl.num_programs(0)) & last_head

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def walk(j, _):
        slot = (first_slot + j) % 2
        more = j + 1 < n_steps

        @pl.when(more | ~last_step)
        def _prefetch():
            fetch(jnp.where(more | ~last_head, b, b + 1),
                  jnp.where(more, j + 1, 0), 1 - slot).start()

        fetch(b, j, slot).wait()
        # this head's keys and values of the block, once for every tile
        kv = jnp.dot(buf[slot, :, pl.ds(0, rank)], w_ref[...],
                     preferred_element_type=jnp.float32).astype(key_ref.dtype)
        key_ref[:, pl.ds(0, dn)] = kv[:, :dn]
        key_ref[:, pl.ds(dn, dk - dn)] = buf[slot, :, pl.ds(rank, dk - dn)]
        val_ref[...] = kv[:, dn:]
        k0 = j * kb

        def update(t, diagonal):
            at = pl.ds(t * tq, tq)
            s = jax.lax.dot_general(
                q_ref[0, at, :], key_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [tq, kb]
            ok = None
            if diagonal:
                ahead = jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 1) - \
                    jax.lax.broadcasted_iota(jnp.int32, (tq, kb), 0)
                ok = ahead <= start + t * tq - k0
            if has_valid:
                live = valid_ref[0, j] > 0                  # [1, kb]
                ok = live if ok is None else ok & live
            if ok is not None:
                s = jnp.where(ok, s, _NEG_INF)
            m_prev = m_ref[at]                              # [tq, 128]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, kb))
            l_ref[at] = l_ref[at] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[at] = acc_ref[at] * _lanes(corr, dv) + jnp.dot(
                p.astype(val_ref.dtype), val_ref[...],
                preferred_element_type=jnp.float32)
            m_ref[at] = m_new

        for t in range(seq // tq):
            first = start + t * tq          # the tile's first query
            seen = k0 <= first + tq - 1     # its last query reaches the block
            whole = k0 + kb - 1 <= first    # its first query sees all of it
            pl.when(seen & ~whole)(functools.partial(update, t, True))
            pl.when(whole)(functools.partial(update, t, False))

    jax.lax.fori_loop(0, n_steps, walk, None)
    o_ref[0] = (acc_ref[...] / _lanes(
        jnp.maximum(l_ref[...], 1e-30), dv)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "q_tile", "key_block", "interpret"))
def pallas_latent_prefill_attention(
        q_nope, q_shared, rows, w_kvb, start, *,
        key_valid: Optional[jax.Array] = None, scale: float,
        q_tile: Optional[int] = None, key_block: Optional[int] = None,
        interpret: bool = False) -> jax.Array:
    """`ops.latent_attention.latent_prefill_attention` as a Mosaic
    kernel (:func:`_kernel`), the same arguments and result: q_nope
    `[B, S, H, dn]`, q_shared `[B, S, H, dr]` at positions `start +
    arange(S)`; rows `[B, T, width]` with `width >= rank + dr` rounded
    up to whole lanes, the columns past `rank + dr` zeros; w_kvb
    `[rank, H, dn + dv]`; `start` an int32 scalar; key_valid `[B, T]`
    or None. Rows `0 .. start + S` are read, rounded up to whole blocks
    (what lies in a block past the frontier must be finite; it weighs
    nothing). Returns `[B, S, H, dv]` in q_nope's dtype. Jitted, so
    that the layers of an unrolled model share one lowering a program.
    Named and scoped `PREFILL_SCOPE` (the name the kernel carries
    into HLO and the profiler's trace), the query's padding inside the
    scope."""
    batch, seq, heads, dn = q_nope.shape
    dr = q_shared.shape[-1]
    rank, _, dnv = w_kvb.shape
    dv = dnv - dn
    total, width = rows.shape[1:]
    drp = -(-dr // 128) * 128
    tq = _tile(seq, q_tile or Q_TILE)
    kb = _tile(total, key_block or KEY_BLOCK)
    has_valid = key_valid is not None
    with jax.named_scope(PREFILL_SCOPE):
        q = jnp.concatenate([
            (q_nope * scale).astype(q_nope.dtype),
            (q_shared * scale).astype(q_nope.dtype),
            jnp.zeros((batch, seq, heads, drp - dr), q_nope.dtype)],
            axis=-1).reshape(batch, seq, heads * (dn + drp))
        if has_valid:
            valid = key_valid.astype(jnp.int32).reshape(
                batch, total // kb, 1, kb)
        else:                 # a dummy operand keeps one kernel signature
            valid = jnp.zeros((1, 1, 1, 128), jnp.int32)
        kv_dtype = jnp.result_type(rows.dtype, w_kvb.dtype)
        kernel = functools.partial(_kernel, rank=rank, dn=dn, tq=tq, kb=kb,
                                   has_valid=has_valid)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, heads),
            in_specs=[
                pl.BlockSpec((1, seq, dn + drp), lambda b, h, *_: (b, 0, h)),
                pl.BlockSpec((rank, dnv), lambda b, h, *_: (0, h)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1,) + valid.shape[1:],
                             lambda b, h, *_: (b * has_valid, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, seq, dv), lambda b, h, *_: (b, 0, h)),
            scratch_shapes=[
                pltpu.VMEM((2, kb, width), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((kb, dn + drp), kv_dtype),      # the head's keys
                pltpu.VMEM((kb, dv), kv_dtype),            # and values
                pltpu.VMEM((seq, dv), jnp.float32),        # accumulator
                pltpu.VMEM((seq, _STAT_LANES), jnp.float32),   # running max
                pltpu.VMEM((seq, _STAT_LANES), jnp.float32),   # running sum
            ],
        )
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((batch, seq, heads * dv),
                                           q_nope.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret, name=PREFILL_SCOPE,
        )(jnp.asarray(start, jnp.int32).reshape(1), q,
          w_kvb.reshape(rank, heads * dnv), rows, valid)
        return out.reshape(batch, seq, heads, dv)


def _ineligible_reason(q_nope, q_shared, rows, w_kvb) -> Optional[str]:
    """Why a window of this shape cannot take the kernel, or None when
    it can. Under a multi-device mesh the answer is the xla lowering
    (GSPMD cannot partition a Mosaic call; the serving window runs on
    one chip)."""
    from fengshen_tpu.parallel.mesh import get_mesh
    seq, _, dn = q_nope.shape[1:]
    dr = q_shared.shape[-1]
    rank, dv = w_kvb.shape[0], w_kvb.shape[-1] - dn
    total, width = rows.shape[1:]
    mesh = get_mesh()
    if mesh is not None and mesh.size > 1:
        return f"{mesh.size}-device mesh: GSPMD cannot partition a " \
               "Mosaic call"
    for name, x in (("q", q_nope), ("rows", rows), ("w_kvb", w_kvb)):
        if x.dtype != jnp.bfloat16:
            return f"{name} is {x.dtype.name}, not bfloat16"
    for name, n in (("window", seq), ("lane", total), ("rank", rank),
                    ("dn", dn), ("dv", dv)):
        if n % 128 != 0:
            return f"{name} {n} % 128 != 0"
    if width < rank + -(-dr // 128) * 128:
        return f"row width {width} holds no whole lanes of the " \
               f"{dr}-value shared key after rank {rank}"
    return None
