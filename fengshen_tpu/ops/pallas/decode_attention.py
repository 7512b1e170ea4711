"""Paged decode attention: the block-table-aware fused kernel.

The stock paged decode path (`modeling_llama._update_paged_cache`)
pays a pure-bandwidth tax before attention ever runs: it gathers every
lane's blocks out of the shared KV pool into a contiguous
``[B, virt_len]`` virtual lane with ``jnp.take`` — a full copy of the
KV window per tick — and, on int8 pools, dequantizes the whole gathered
window to fp. Decode is memory-bound (arxiv 2311.03687), so that copy
is the phase's dominant cost.

This module is the ``decode_attention`` dispatch seam every decode
shape routes through (see fengshen_tpu/ops/pallas/__init__.py):

- :func:`pallas_decode_attention` — Mosaic kernel that reads the pool
  **through the block table directly**: the block-table row rides in as
  a scalar-prefetch operand and the kernel fetches the lane's physical
  blocks out of HBM itself, only those that hold a key (the count comes
  from ``valid``: :func:`_live_blocks`) — no gather copy, no
  virtual-lane materialization, no head-major transpose, no step for a
  table entry past the lane's cursor. A block
  arrives with all its KV heads (the only shape Mosaic can DMA out of
  a ``[.., block_size, KVH, D]`` pool) and the kernel folds tokens and
  heads into one key axis, masking the columns of other heads
  (see :func:`_decode_kernel`). int8 pools stay int8 in VMEM; the
  per-(token, head) scales (``ops/int8_matmul.quantize_kv``) multiply
  the score and probability tiles. GQA needs no ``jnp.repeat``: a query
  head's mask row selects its group's KV head. Slot-pool (contiguous
  ``[B, max_len]``) caches reuse the same kernel by reshaping into
  ``max_len // block_size`` blocks per lane with an arange block table.
  Serves both the ``[B, 1]`` decode tick and the ``[B, gamma+1]``
  speculative verify window (one grid step a lane, online softmax
  across the lane's live blocks).
- :func:`xla_decode_attention` — the stock lowering, op-for-op the
  sequence the model ran before this seam existed (take-gather →
  dequantize → GQA repeat → dense attention), so CPU tier-1 pins
  greedy decode through the dispatcher token-identical to the
  pre-kernel path.

- :func:`folded_decode_attention` — the seam's entry for pools whose
  rows hold a token's few, wide KV heads side by side (``[block, 1, G *
  D]``: 2 heads of 256), which the fold above cannot tile. Its Mosaic
  kernel (:func:`_folded_decode_kernel`) shares the walk — a lane a
  grid step, the lane's own live blocks fetched through the table
  behind the multiply — and nothing of the body: it slices each KV head
  out of the block as it lies, in the pool's dtype. Its xla lowering is
  ``ops/gated_attention.folded_decode_walk``.
- :func:`mla_decode_attention` — the seam's entry for a latent cache:
  ONE row ``[c_kv | k_rope | zeros]`` a token, key and value at once,
  shared by every query head. Over a paged pool of whole-lane rows its
  Mosaic kernel (:func:`_mla_decode_kernel`) shares the folded kernel's
  walk (:func:`_fetch_step`) with one buffer a slot; slot and lockstep
  caches and every other shape take :func:`xla_mla_decode_attention`,
  which gathers the lane.
- :func:`sparse_decode_attention`, :func:`indexed_decode_attention` —
  the chosen-block and the chosen-token entries, xla lowerings only.

Tiling (docs/kernels.md): the pallas path requires
``head_dim % 128 == 0``, ``block_size % 128 == 0`` and
``KVH % 8 == 0`` (the token×head fold is a free reshape only on f32
sublane tiles); the folded entry's wants the same of ``head_dim`` and
``block_size`` and one row a token; the latent entry's wants whole
lanes of the row's width, of ``rank`` and of ``block_size``, behind a
block table. Other shapes are the xla
lowering's; which one a traced call site took is recorded through
``ops.pallas.resolve_dispatch``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fengshen_tpu.ops.attention import dot_product_attention
from fengshen_tpu.ops.gated_attention import (CHUNK_BLOCKS, DECODE_SCOPE,
                                              fold_queries,
                                              folded_decode_walk,
                                              unfold_queries)
from fengshen_tpu.ops.int8_matmul import dequantize_kv

_NEG_INF = -1e30

#: longest query window the kernel serves — the decode tick (1) and
#: any sane speculative gamma; longer windows are prefill-shaped and
#: belong on the flash/dense paths
_MAX_QUERY_WINDOW = 8

#: scoped-VMEM ceiling the kernel asks Mosaic for: a whole K and a
#: whole V block (all heads), double-buffered, plus their f32 copies
#: outgrow the 16 MiB default at LLaMA-13B's 40 heads x 128
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: the name both lowerings carry into HLO and the profiler's trace, so
#: the operation is found by name whichever path ran (a flax scope and a
#: compiler counter named it `self_attn.7` before)
TRACE_NAME = "fstpu_decode_attention"

#: tokens per grid step when a slot cache is viewed as blocks
_SLOT_BLOCK = 128

#: largest K (or V) block, in elements, the kernel will stream: 128
#: tokens of 64 heads x 128 — its f32 copy is 4 MiB of the limit above
_MAX_BLOCK_ELEMS = 128 * 64 * 128


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     block_table: Optional[jax.Array] = None,
                     layer: Optional[jax.Array] = None,
                     dequant_dtype=None,
                     impl: Optional[str] = None,
                     interpret: bool = False) -> jax.Array:
    """The dispatch seam: every (layout, dtype, spec_mode) decode combo
    enters here and leaves as ``[B, S, H, D]`` attention output.

    q: ``[B, S, H, D]`` (S = 1 decode tick or gamma+1 verify window).
    k/v: ``[B, max_len, KVH, D]`` slot/lockstep cache, or the shared
    ``[num_blocks, block_size, KVH, D]`` pool when ``block_table``
    (``[B, max_blocks]`` int32) is given. int8 caches pass the
    per-(token, head) absmax scales (``k_scale``/``v_scale``) and the
    compute dtype ``dequant_dtype``. ``valid``: ``[B, S, L]`` bool over
    the (virtual) lane. With ``layer`` (an int32 scalar, may be traced)
    the paged pools and scales are whole ``[L, num_blocks, ...]``
    stacks, as a ``scan_layers`` model carries them, and the read is
    layer ``layer``'s: see :func:`_layer_of_stack`. ``impl`` forces
    ``"pallas"``/``"xla"``; ``None`` asks the capability probe + shape
    eligibility.
    """
    if impl is None:
        from fengshen_tpu.ops.pallas import resolve_dispatch
        impl = resolve_dispatch(
            "decode_attention",
            f"q={tuple(q.shape)} kv={tuple(k.shape[-4:])}:{k.dtype.name} "
            f"{'paged' if block_table is not None else 'slot'}",
            _ineligible_reason(q, k, block_table))
    if impl == "pallas":
        return pallas_decode_attention(
            q, k, v, valid, k_scale=k_scale, v_scale=v_scale,
            block_table=block_table, layer=layer,
            dequant_dtype=dequant_dtype, interpret=interpret)
    with jax.named_scope(TRACE_NAME):
        return xla_decode_attention(
            q, k, v, valid, k_scale=k_scale, v_scale=v_scale,
            block_table=block_table, layer=layer,
            dequant_dtype=dequant_dtype)


def _layer_of_stack(k, v, block_table, layer):
    """Layer ``layer`` of ``[L, num_blocks, block_size, KVH, D]`` pool
    stacks WITHOUT slicing it out (that slice is a copy of a layer's
    whole pool, every layer of every tick): the stack is one pool of
    ``L * num_blocks`` blocks — a free reshape of a contiguous array —
    in which the layer's block ``b`` is block ``layer * num_blocks +
    b``. Returns the flat pools and the table that reads them."""
    num_blocks = k.shape[1]
    return (k.reshape((-1,) + k.shape[2:]), v.reshape((-1,) + v.shape[2:]),
            block_table + layer * num_blocks)


def _ineligible_reason(q, k, block_table) -> Optional[str]:
    """Why this shape cannot take the Mosaic kernel, or None when it
    can (the backend capability itself is `resolve_dispatch`'s job)."""
    _, s, n_heads, head_dim = q.shape
    kv_heads = k.shape[-2]
    if s > _MAX_QUERY_WINDOW:
        return f"query window {s} > {_MAX_QUERY_WINDOW}"
    if n_heads % kv_heads != 0 or kv_heads % 8 != 0:
        return f"kv heads {kv_heads} not a multiple of 8 dividing " \
               f"{n_heads}"
    if head_dim % 128 != 0:
        return f"head_dim {head_dim} % 128 != 0"
    if k.shape[-3] % 128 != 0:
        what = "block_size" if block_table is not None else "cache length"
        return f"{what} {k.shape[-3]} % 128 != 0"
    block = k.shape[-3] if block_table is not None else _SLOT_BLOCK
    if block * kv_heads * head_dim > _MAX_BLOCK_ELEMS:
        return f"a block of {block} tokens x {kv_heads} heads x " \
               f"{head_dim} outgrows VMEM"
    return None


def pallas_decode_eligible(q, k, v=None, k_scale=None,
                           block_table=None) -> bool:
    """Shape eligibility for the Mosaic kernel: tile-aligned or stay on
    the stock lowering (mirrors `_pallas_ineligible_reason` in
    ops.flash_attention)."""
    del v, k_scale
    return _ineligible_reason(q, k, block_table) is None


def xla_decode_attention(q, k, v, valid, *, k_scale=None, v_scale=None,
                         block_table=None, layer=None, dequant_dtype=None):
    """The stock lowering, kept op-for-op identical to the pre-seam
    model path so greedy decode through the dispatcher is
    token-identical on CPU tier-1: paged pools gather into the
    contiguous virtual lane with ``jnp.take`` (then dequantize the
    gathered window), slot int8 caches dequantize in place, GQA
    repeats KV heads, and the dense fused softmax chain finishes."""
    dt = dequant_dtype if dequant_dtype is not None else jnp.float32
    if layer is not None:
        k, v, block_table = _layer_of_stack(k, v, block_table, layer)
    if block_table is not None:
        num_blocks, block_size = k.shape[:2]
        batch = q.shape[0]
        virt_len = block_table.shape[-1] * block_size
        flat_k = k.reshape(num_blocks * block_size, *k.shape[2:])
        flat_v = v.reshape(num_blocks * block_size, *v.shape[2:])
        gather_idx = ((block_table * block_size)[:, :, None] +
                      jnp.arange(block_size)[None, None, :]
                      ).reshape(batch, virt_len)
        k = jnp.take(flat_k, gather_idx, axis=0)
        v = jnp.take(flat_v, gather_idx, axis=0)
        if k_scale is not None:
            flat_ks = k_scale.reshape(num_blocks * block_size, -1)
            flat_vs = v_scale.reshape(num_blocks * block_size, -1)
            k = dequantize_kv(k, jnp.take(flat_ks, gather_idx, axis=0), dt)
            v = dequantize_kv(v, jnp.take(flat_vs, gather_idx, axis=0), dt)
    elif k_scale is not None:
        k = dequantize_kv(k, k_scale, dt)
        v = dequantize_kv(v, v_scale, dt)
    n_heads, kv_heads = q.shape[2], k.shape[2]
    if kv_heads != n_heads:
        rep = n_heads // kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return dot_product_attention(q, k, v, mask=valid[:, None])


def _live_blocks(valid, block_size: int):
    """Per lane, how many leading blocks of its table row the kernel
    has to walk: up to the block of the last valid column, over all
    query positions (the verify window's last query reaches furthest),
    and at least one, so a lane with no valid column still reads the
    block its row starts on (a released lane: the null block). Holes at
    the FRONT of ``valid`` (a left-padded prompt) are inside the walk;
    only the tail past every lane's cursor is left out. ``[B]`` int32."""
    upto = np.arange(valid.shape[-1], dtype=np.int32) // block_size + 1
    return jnp.max(jnp.where(valid, upto, 1), axis=(1, 2))


def _decode_kernel(tables_ref, live_ref, *refs, scale, n_query, rep,
                   quantized, dt):
    """One lane a grid step, over ALL heads at once; inside the step a
    loop over the lane's LIVE blocks only (``live_ref[lane]`` of them,
    :func:`_live_blocks`): a table entry past the lane's cursor costs
    no step, no DMA and no matmul.

    K and V stay in HBM (``pl.ANY``) and a block is fetched through the
    table into one of two VMEM slots while the block before it is
    multiplied; the lane's last block prefetches the NEXT lane's first,
    so the walk never waits at a lane boundary (the grid axis is
    sequential for that, and ``slot_ref`` carries across it which slot
    the lane's first block was fetched into).

    The pool block arrives whole — ``[block_size, KVH, D]`` is one
    contiguous slab of HBM, and Mosaic cannot DMA a single head out of
    it (a block's last two dims must tile by (8, 128) or span the
    array's, and one bf16 head row is half a packed sublane). So the
    kernel folds the token and head axes into one key axis of
    ``C = block_size * KVH`` columns and runs a dense
    ``[H, D] x [D, C]`` matmul per query position; columns whose KV
    head is not the row's are masked out with the invalid positions.
    The MXU work this wastes (KVH x) is idle in a bandwidth-bound
    decode; what the kernel buys is that K and V are read from HBM
    exactly once, through the block table, with no gather copy and no
    head-major transpose. The fold is a free reshape only in f32 tiles
    (hence ``KVH % 8 == 0`` in the eligibility rule), so K/V are
    widened in VMEM first. Online-softmax stats live in scratch across
    the lane's blocks."""
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, mask_ref, colhead_ref,
         o_ref, k_buf, v_buf, ks_buf, vs_buf, sems, slot_ref,
         acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, k_hbm, v_hbm, mask_ref, colhead_ref, o_ref,
         k_buf, v_buf, sems, slot_ref, acc_ref, m_ref, l_ref) = refs
    lane = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    n_live = live_ref[lane]

    def copies(lane, j, slot):
        """The DMAs that bring block ``j`` of ``lane`` into ``slot``."""
        block = tables_ref[0, lane, j]
        out = [pltpu.make_async_copy(k_hbm.at[block], k_buf.at[slot],
                                     sems.at[0, slot]),
               pltpu.make_async_copy(v_hbm.at[block], v_buf.at[slot],
                                     sems.at[1, slot])]
        if quantized:
            block = tables_ref[1, lane, j]
            out += [pltpu.make_async_copy(ks_hbm.at[block], ks_buf.at[slot],
                                          sems.at[2, slot]),
                    pltpu.make_async_copy(vs_hbm.at[block], vs_buf.at[slot],
                                          sems.at[3, slot])]
        return out

    @pl.when(lane == 0)
    def _first_fetch():
        slot_ref[0] = 0
        for dma in copies(0, 0, 0):
            dma.start()

    first_slot = slot_ref[0]
    slot_ref[0] = (first_slot + n_live) % 2

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    block_size, kv_heads, head_dim = k_buf.shape[1:]
    n_cols = block_size * kv_heads
    n_heads = q_ref.shape[2]
    row_kv = jax.lax.broadcasted_iota(jnp.int32, (n_heads, n_cols), 0)
    if rep > 1:
        row_kv = row_kv // rep
    own_head = colhead_ref[...] == row_kv                # [H, C]

    def walk(j, _):
        slot = (first_slot + j) % 2
        more = j + 1 < n_live
        next_lane = jnp.where(more, lane, lane + 1)

        @pl.when(next_lane < n_lanes)
        def _prefetch():
            for dma in copies(next_lane, jnp.where(more, j + 1, 0),
                              1 - slot):
                dma.start()

        for dma in copies(lane, j, slot):
            dma.wait()
        k = k_buf[slot].astype(jnp.float32).reshape(n_cols, head_dim)
        v = v_buf[slot].astype(jnp.float32).reshape(n_cols, head_dim)

        for s in range(n_query):
            q = q_ref[0, s].astype(jnp.float32) * scale      # [H, D]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [H, C]
            if quantized:
                # per-(token, head) dequant applied to the product: the
                # pool stays int8 in HBM and in VMEM
                scores = scores * ks_buf[slot]
            allowed = own_head & (mask_ref[0, s, pl.ds(j, 1), :] > 0)
            scores = jnp.where(allowed, scores, _NEG_INF)

            m_prev, l_prev = m_ref[s], l_ref[s]              # [H, 1]
            m_new = jnp.maximum(m_prev, scores.max(-1, keepdims=True))
            correction = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[s] = l_prev * correction + probs.sum(-1, keepdims=True)
            if quantized:
                probs = probs * vs_buf[slot]
            # round the probabilities through the compute dtype like the
            # xla lowering does before its PV matmul
            pv = jax.lax.dot_general(
                probs.astype(dt).astype(jnp.float32), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [H, D]
            acc_ref[s] = acc_ref[s] * correction + pv
            m_ref[s] = m_new

    jax.lax.fori_loop(0, n_live, walk, None)
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def pallas_decode_attention(q, k, v, valid, *, k_scale=None,
                            v_scale=None, block_table=None, layer=None,
                            dequant_dtype=None,
                            block_size: int = _SLOT_BLOCK,
                            interpret: bool = False):
    """Fused paged decode attention. Same contract as
    :func:`decode_attention`; slot caches (``block_table=None``) are
    viewed as ``max_len // block_size`` pool blocks per lane with an
    arange table, so one kernel serves both layouts. How far a lane's
    row is walked comes from ``valid`` (:func:`_live_blocks`)."""
    batch, s, n_heads, head_dim = q.shape
    kv_heads = k.shape[-2]
    rep = n_heads // kv_heads
    dt = dequant_dtype if dequant_dtype is not None else jnp.float32
    quantized = k_scale is not None

    if block_table is None:
        max_len = k.shape[1]
        if max_len % block_size != 0:
            raise ValueError(
                f"slot cache length {max_len} not divisible by "
                f"block_size {block_size}; dispatch eligibility should "
                "have routed this shape to the xla lowering")
        blocks_per_lane = max_len // block_size
        k = k.reshape(batch * blocks_per_lane, block_size,
                      kv_heads, head_dim)
        v = v.reshape(batch * blocks_per_lane, block_size,
                      kv_heads, head_dim)
        block_table = (jnp.arange(batch, dtype=jnp.int32)[:, None] *
                       blocks_per_lane +
                       jnp.arange(blocks_per_lane, dtype=jnp.int32)[None])
    else:
        block_size = k.shape[-3]
        blocks_per_lane = block_table.shape[-1]
    # the scales are re-laid out below, which copies them: of a stack
    # that copy takes the layer's own slice (small) and its own table
    scale_table = block_table
    if layer is not None:
        k, v, block_table = _layer_of_stack(k, v, block_table, layer)
        if quantized:
            k_scale, v_scale = k_scale[layer], v_scale[layer]
    tables = jnp.stack([block_table, scale_table]).astype(jnp.int32)
    n_cols = block_size * kv_heads

    # key column c of a block is (token c // KVH, kv head c % KVH): the
    # validity mask, a block a row, and the int8 scales are laid out
    # to match
    mask = jnp.repeat(valid.astype(jnp.int32), kv_heads, axis=-1).reshape(
        batch, s, blocks_per_lane, n_cols)
    col_head = jnp.tile(jnp.arange(kv_heads, dtype=jnp.int32),
                        block_size)[None]                # [1, C]

    # the whole point: K and V stay where they are and the kernel
    # fetches a lane's j-th PHYSICAL block out of the pool itself — no
    # gather into a virtual lane
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    qo_spec = pl.BlockSpec((1, s, n_heads, head_dim),
                           lambda b, *_: (b, 0, 0, 0))
    in_specs = [qo_spec, in_hbm, in_hbm]
    operands = [q, k, v]
    buffers = [pltpu.VMEM((2, block_size, kv_heads, head_dim), k.dtype),
               pltpu.VMEM((2, block_size, kv_heads, head_dim), v.dtype)]
    if quantized:
        in_specs += [in_hbm] * 2
        operands += [k_scale.reshape(-1, 1, n_cols),
                     v_scale.reshape(-1, 1, n_cols)]
        buffers += [pltpu.VMEM((2, 1, n_cols), jnp.float32)] * 2
    in_specs += [pl.BlockSpec((1, s, blocks_per_lane, n_cols),
                              lambda b, *_: (b, 0, 0, 0)),
                 pl.BlockSpec((1, n_cols), lambda b, *_: (0, 0))]
    operands += [mask, col_head]

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(head_dim), n_query=s,
        rep=rep, quantized=quantized, dt=dt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=in_specs,
        out_specs=qo_spec,
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((4 if quantized else 2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((s, n_heads, head_dim), jnp.float32),
            pltpu.VMEM((s, n_heads, 1), jnp.float32),
            pltpu.VMEM((s, n_heads, 1), jnp.float32),
        ],
    )
    with jax.named_scope(TRACE_NAME):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret, name=TRACE_NAME,
        )(tables, _live_blocks(valid, block_size), *operands)


# -- the latent (MLA) read ----------------------------------------------

#: the name the latent read carries into HLO and the trace
MLA_TRACE_NAME = "fstpu_mla_decode_attention"

#: latent blocks a step of the kernel's walk (1,024 keys at 128; the
#: step sizes measured alone on a v5e are in PERF.md, PR 43)
_MLA_BLOCKS_PER_STEP = 8


def mla_decode_attention(q_latent: jax.Array, q_rope: jax.Array,
                         kv: jax.Array, valid: jax.Array, *, scale: float,
                         block_table: Optional[jax.Array] = None,
                         layer: Optional[jax.Array] = None,
                         impl: Optional[str] = None,
                         interpret: bool = False) -> jax.Array:
    """The seam's latent entry: absorbed multi-head latent attention
    over a cache of one row `[c_kv (rank) | k_rope | zeros]` per token
    (the zeros pad the row to whole lanes; the row is as wide as the
    cache says).

    q_latent: ``[B, S, H, rank]`` (the no-position query already
    multiplied into the latent space), q_rope: ``[B, S, H, rope]``.
    kv: ``[B, max_len, 1, width]`` slot/lockstep cache, or the
    shared ``[num_blocks, block_size, 1, width]`` pool behind
    ``block_table``; with ``layer`` the ``[L, ...]`` stack of either,
    read in place as :func:`_layer_of_stack` reads K/V. ``valid``:
    ``[B, S, L]`` bool. All ``H`` query heads share the row: its first
    ``rank`` values are the key's no-position part AND the value.
    Returns ``[B, S, H, rank]``, still latent: the caller's
    up-projection turns it into heads of values. The cache's shape
    picks the path (:func:`_mla_ineligible_reason`): the Mosaic kernel
    :func:`pallas_mla_decode_attention` for a paged pool of whole-lane
    rows, else :func:`xla_mla_decode_attention`, the CPU tier-1 truth.
    ``impl`` forces either, as in :func:`decode_attention`."""
    if impl is None:
        from fengshen_tpu.ops.pallas import resolve_dispatch
        impl = resolve_dispatch(
            "mla_decode_attention",
            f"q={tuple(q_latent.shape)}+{q_rope.shape[-1]} "
            f"kv={tuple(kv.shape[-4:])}:{kv.dtype.name} "
            f"{'paged' if block_table is not None else 'slot'}",
            _mla_ineligible_reason(q_latent, kv, block_table))
    if impl == "pallas":
        return pallas_mla_decode_attention(
            q_latent, q_rope, kv, valid, scale=scale,
            block_table=block_table, layer=layer, interpret=interpret)
    with jax.named_scope(MLA_TRACE_NAME):
        return xla_mla_decode_attention(
            q_latent, q_rope, kv, valid, scale=scale,
            block_table=block_table, layer=layer)


def _mla_ineligible_reason(q_latent, kv, block_table) -> Optional[str]:
    """Why a latent cache of this shape cannot take the Mosaic kernel,
    or None when it can."""
    s, rank = q_latent.shape[1], q_latent.shape[-1]
    block_size, one, width = kv.shape[-3:]
    if block_table is None:
        return "a slot cache has no block table to walk"
    if s > _MAX_QUERY_WINDOW:
        return f"query window {s} > {_MAX_QUERY_WINDOW}"
    if one != 1:
        return f"rows {tuple(kv.shape[-2:])} are not one shared head"
    if width % 128 != 0:
        return f"row width {width} % 128 != 0"
    if rank % 128 != 0:
        return f"rank {rank} % 128 != 0"
    if block_size % 128 != 0:
        return f"block_size {block_size} % 128 != 0"
    return None


def _latent_query(q_latent, q_rope, width, dtype):
    """``[q_latent | q_rope | 0]``: the query against a whole cache row
    `[c_kv | k_rope | zeros]`, in the row's dtype."""
    pad = jnp.zeros(q_rope.shape[:-1] + (
        width - q_latent.shape[-1] - q_rope.shape[-1],), q_rope.dtype)
    return jnp.concatenate([q_latent, q_rope, pad], axis=-1).astype(dtype)


def xla_mla_decode_attention(q_latent, q_rope, kv, valid, *, scale,
                             block_table=None, layer=None):
    """The stock lowering and the CPU tier-1 truth: a paged pool
    gathers into the contiguous virtual lane (a copy of every attended
    row: what a kernel would save) a whole BLOCK at a time — one
    `block_size x width` slab per table entry; gathered row by row the
    same copy ran at a seventh of the memory's rate on the chip
    (PERF.md, PR 26) — one product of the concatenated query against
    the whole row gives the scores, and the probabilities weigh the
    row's first ``rank`` values."""
    rank = q_latent.shape[-1]
    if block_table is not None:
        if layer is not None:
            num_blocks = kv.shape[1]
            kv = kv.reshape((-1,) + kv.shape[2:])
            block_table = block_table + layer * num_blocks
        blocks = jnp.take(kv[:, :, 0, :], block_table, axis=0,
                          mode="clip")                   # [B, mb, bs, R]
        rows = blocks.reshape(blocks.shape[0], -1, blocks.shape[-1])
    else:
        if layer is not None:
            kv = kv[layer]
        rows = kv[:, :, 0, :]
    q = _latent_query(q_latent, q_rope, rows.shape[-1], rows.dtype)
    scores = jnp.einsum("bshd,btd->bhst", q, rows,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    return jnp.einsum("bhst,btc->bshc", probs,
                      rows[..., :rank]).astype(q_latent.dtype)


def _fetch_step(table_ref, pools, sems, n_live, lane, j, slot, block_size,
                wait=False):
    """Start (or wait for) the DMAs that bring step ``j`` of ``lane``
    into ``slot``: of its blocks (as many as a slot holds) those among
    the lane's ``n_live`` — a step's tail past the lane's last live
    block is not fetched. ``pools``: ``(pool in HBM, its ``[2, span,
    width]`` buffer)`` pairs that one table reads; ``sems``: ``[pool,
    slot, block of the step]``."""
    per = pools[0][1].shape[1] // block_size
    for i in range(per):
        @pl.when(j * per + i < n_live)
        def _one():
            block = table_ref[lane, j * per + i]
            at = pl.ds(i * block_size, block_size)
            for x, (hbm, buf) in enumerate(pools):
                dma = pltpu.make_async_copy(
                    hbm.at[block], buf.at[slot, at], sems.at[x, slot, i])
                if wait:
                    dma.wait()
                else:
                    dma.start()


def _mla_decode_kernel(table_ref, live_ref, q_ref, kv_hbm, mask_ref, o_ref,
                       buf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
                       scale, rank, block_size):
    """One lane a grid step; inside it a loop over the lane's LIVE
    blocks only (``live_ref[lane]`` of them, :func:`_live_blocks`),
    as many a step as a slot of ``buf`` holds, fetched through the
    table from the pool left in HBM into one of two VMEM slots while
    the step before is multiplied; the lane's last step prefetches the
    NEXT lane's first (``slot_ref`` carries across the sequential grid
    axis which slot that went into) — the walk of
    :func:`_folded_decode_kernel`, over a latent row. A step's tail
    past the lane's last live block is not fetched, a released lane
    (no valid column, its row on the null block) costs one block, an
    entry past the cursor nothing.

    ONE buffer a slot: a block ``[block_size, width]`` of rows ``[c_kv
    (rank) | k_rope | zeros]`` is key and value at once, shared by all
    ``H`` heads. Scores are ``[H, width] x [width, span]`` of the
    concatenated query ``[q_latent | q_rope | 0]`` against the block as
    it lies; the probabilities weigh the buffer's first ``rank``
    columns (a static slice at a multiple of 128 lanes). No head fold,
    no mask of other heads' columns, no float32 copy of the block.

    Precision, that of :func:`xla_mla_decode_attention`: operands in
    the pool's dtype, float32 accumulation, the scale applied to the
    float32 scores, probabilities rounded to the row's dtype before
    the second product; online-softmax statistics and the ``[H, rank]``
    accumulator in float32 scratch across the lane's steps. Every live
    row is read whole. The mask is the lane's tile of ``valid``, a row
    a step (holes at the FRONT of a lane, a left-padded prompt, are
    inside the walk); a query position of a verify window is one more
    pass of the same loop body over the fetched step."""
    lane = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    per = buf.shape[1] // block_size
    n_steps = (live_ref[lane] + per - 1) // per
    pools = ((kv_hbm, buf),)

    def fetch(lane, j, slot, wait=False):
        _fetch_step(table_ref, pools, sems, live_ref[lane], lane, j, slot,
                    block_size, wait)

    @pl.when(lane == 0)
    def _first_fetch():
        slot_ref[0] = 0
        # a step's unfetched tail is weighed by exact zeros: whatever
        # the buffer holds there must not be NaN
        buf[...] = jnp.zeros_like(buf)
        fetch(0, 0, 0)

    first_slot = slot_ref[0]
    slot_ref[0] = (first_slot + n_steps) % 2

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def walk(j, _):
        slot = (first_slot + j) % 2
        more = j + 1 < n_steps
        next_lane = jnp.where(more, lane, lane + 1)

        @pl.when(next_lane < n_lanes)
        def _prefetch():
            fetch(next_lane, jnp.where(more, j + 1, 0), 1 - slot)

        fetch(lane, j, slot, wait=True)
        for s in range(q_ref.shape[1]):
            scores = jax.lax.dot_general(
                q_ref[0, s], buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [H, span]
            scores = jnp.where(mask_ref[0, s, pl.ds(j, 1), :] > 0, scores,
                               _NEG_INF)
            m_prev = m_ref[s]
            m_new = jnp.maximum(m_prev, scores.max(-1, keepdims=True))
            correction = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[s] = l_ref[s] * correction + probs.sum(-1, keepdims=True)
            pv = jax.lax.dot_general(
                probs.astype(buf.dtype), buf[slot, :, pl.ds(0, rank)],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [H, rank]
            acc_ref[s] = acc_ref[s] * correction + pv
            m_ref[s] = m_new

    jax.lax.fori_loop(0, n_steps, walk, None)
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def pallas_mla_decode_attention(q_latent, q_rope, kv, valid, *, scale,
                                block_table, layer=None,
                                blocks_per_step: Optional[int] = None,
                                interpret: bool = False):
    """The latent read as a Mosaic kernel (:func:`_mla_decode_kernel`).
    Same contract as :func:`mla_decode_attention` over a paged pool:
    the pool stays in HBM and is read THROUGH ``block_table``, each
    live block of a lane once and no gathered copy written; a stack is
    read in place as one pool of ``L x num_blocks`` blocks. A step
    takes ``blocks_per_step`` blocks. How far a lane's row is walked
    comes from ``valid`` (:func:`_live_blocks`). Named and scoped
    ``MLA_TRACE_NAME`` with the query's concatenation and the mask's
    tiles inside the scope, so the trace finds the same work by that
    text whichever lowering ran."""
    if block_table is None:
        raise ValueError("the latent kernel walks a block table; a slot "
                         "cache is xla_mla_decode_attention's")
    batch, s, n_heads, rank = q_latent.shape
    block_size, _, width = kv.shape[-3:]
    max_blocks = block_table.shape[-1]
    per = min(blocks_per_step or _MLA_BLOCKS_PER_STEP, max_blocks)
    span, n_steps = per * block_size, -(-max_blocks // per)
    with jax.named_scope(MLA_TRACE_NAME):
        if layer is not None:
            block_table = block_table + layer * kv.shape[1]
        q = _latent_query(q_latent, q_rope, width, kv.dtype)
        # the lane's mask, a row a step of the walk
        mask = jnp.pad(valid.astype(jnp.int32), (
            (0, 0), (0, 0), (0, n_steps * span - valid.shape[-1]))
        ).reshape(batch, s, n_steps, span)
        kernel = functools.partial(_mla_decode_kernel, scale=scale,
                                   rank=rank, block_size=block_size)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch,),
            in_specs=[
                pl.BlockSpec((1, s, n_heads, width),
                             lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, s, n_steps, span),
                             lambda b, *_: (b, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, s, n_heads, rank),
                                   lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, span, width), kv.dtype),
                pltpu.SemaphoreType.DMA((1, 2, per)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((s, n_heads, rank), jnp.float32),
                pltpu.VMEM((s, n_heads, 1), jnp.float32),
                pltpu.VMEM((s, n_heads, 1), jnp.float32),
            ],
        )
        # a row's unit axis and a stack's layer axis go: a free reshape,
        # the blocks stay put
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q_latent.shape, q_latent.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret, name=MLA_TRACE_NAME,
        )(block_table.astype(jnp.int32), _live_blocks(valid, block_size), q,
          kv.reshape(-1, block_size, width), mask)


SPARSE_TRACE_NAME = "fstpu_sparse_decode_attention"

#: why every sparse read takes the xla lowering today: the kernel above
#: walks a lane's whole table in order; a chosen-block list is dynamic
#: per KV head, and this model's 2 KV heads fail `KVH % 8` besides
_NO_SPARSE_KERNEL = "no Mosaic kernel walks a dynamic block list yet"


def sparse_decode_attention(q: jax.Array, pooled: jax.Array, k: jax.Array,
                            v: jax.Array, block_table: jax.Array,
                            t: jax.Array, spec, *,
                            layer: Optional[jax.Array] = None,
                            dense: bool = False) -> jax.Array:
    """The seam's sparse entry (`ops/sparse_attention.py` has the
    mathematics): one query a lane scores the lane's POOLED keys,
    chooses `spec.topk` blocks a KV head, and attends over the chosen
    entries of the lane's block table only.

    q: ``[B, 1, H, D]``; k/v: the shared ``[num_blocks, block_size, 1,
    KVH * D]`` pools (a token's KV heads folded into one row, see
    ``sparse_attention.gather_blocks``) and pooled: ``[num_blocks,
    block_size // stride, 1, KVH * D]`` behind ``block_table`` ``[B,
    max_blocks]`` — one table reads all three, a pool block holding the
    pooled keys that START in it; with ``layer`` the ``[L, ...]``
    stacks, read in place as :func:`_layer_of_stack` reads K/V. ``t``: ``[B]`` int32, each
    query's position (its context is ``t + 1`` tokens). A pool block is
    a whole number of ``spec.block_size``-token selection blocks.
    Without ``dense`` every lane is taken to be past ``spec.dense_len``
    (a shorter one reads its ``topk`` highest blocks, which is
    everything only while it has no more); with it the read has room
    for ``dense_len`` tokens' blocks and a lane within ``dense_len``
    reads all of its own — the caller asks for that, twice the gather,
    only on a tick that holds such a lane. (The dense seam above is not
    asked: its GQA repeat of a 25,600-token lane at 16 query heads a KV
    head is 6.25 GB.) Returns ``[B, 1, H, D]``."""
    from fengshen_tpu.ops.pallas import resolve_dispatch
    # recorded, not decided: the xla lowering is the only one there is
    resolve_dispatch(
        "sparse_decode_attention",
        f"q={tuple(q.shape)} kv={tuple(k.shape[-4:])}:{k.dtype.name} "
        f"topk={spec.topk}x{spec.block_size}", _NO_SPARSE_KERNEL)
    # the whole read — the pooled keys' gather and the choice (their
    # own scope inside this one), the chosen slabs, the attention
    with jax.named_scope(SPARSE_TRACE_NAME):
        return xla_sparse_decode_attention(q, pooled, k, v, block_table, t,
                                           spec, layer=layer, dense=dense)


def xla_sparse_decode_attention(q, pooled, k, v, block_table, t, spec, *,
                                layer=None, dense=False):
    """The stock lowering and the CPU tier-1 truth: the lane's pooled
    keys gathered a block at a time (a sixteenth of the lane's keys),
    the choice, then ONE gather of the chosen blocks whole, each head
    keeping its own half of a row (`sparse_attention.gather_blocks`) —
    4,096 of up to 25,000 cached tokens — and dense attention over
    them."""
    from fengshen_tpu.ops.sparse_attention import (gather_blocks,
                                                   select_blocks)
    batch, _, heads, dim = q.shape
    if layer is not None:
        num_blocks = k.shape[1]
        k, v, pooled = (x.reshape((-1,) + x.shape[2:])
                        for x in (k, v, pooled))
        block_table = block_table + layer * num_blocks
    pool_block, groups = k.shape[1], k.shape[-1] // dim
    sel = spec.block_size
    if pool_block % sel:
        raise ValueError(f"a pool block of {pool_block} tokens is not a "
                         f"whole number of {sel}-token selection blocks")
    per = pool_block // sel
    n_sel = block_table.shape[-1] * per
    lane = jnp.take(pooled, block_table, axis=0, mode="clip")
    lane = lane.reshape(batch, -1, groups, dim)            # [B, J, G, D]
    rank = select_blocks(q, lane, t[:, None], spec, n_sel)[:, 0]
    room = max(spec.topk, -(-spec.dense_len // sel)) if dense \
        else spec.topk
    top = min(room, n_sel)
    value, chosen = jax.lax.top_k(rank, top)               # [B, G, K]
    taken = value > -jnp.inf
    if dense:
        # past dense_len a lane still reads its topk best only
        taken = taken & ((t + 1 <= spec.dense_len)[:, None, None] |
                         (jnp.arange(top) < spec.topk))
    phys = jnp.take_along_axis(
        block_table[:, None, :], chosen // per, axis=-1) * per + \
        chosen % per
    ks = gather_blocks(k.reshape((-1, sel) + k.shape[2:]), phys, dim)
    vs = gather_blocks(v.reshape((-1, sel) + v.shape[2:]), phys, dim)
    ks = ks.reshape(batch, groups, top * sel, dim)
    vs = vs.reshape(batch, groups, top * sel, dim)
    pos = (chosen[..., None] * sel + jnp.arange(sel)).reshape(
        batch, groups, top * sel)
    ok = jnp.repeat(taken, sel, axis=-1) & \
        (pos <= t[:, None, None])
    qg = q[:, 0].reshape(batch, groups, heads // groups, dim)
    scores = jnp.einsum("bgrd,bgkd->bgrk", qg, ks,
                        preferred_element_type=jnp.float32) * dim ** -0.5
    scores = jnp.where(ok[:, :, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(vs.dtype)
    out = jnp.einsum("bgrk,bgkd->bgrd", probs, vs)
    return out.reshape(batch, 1, heads, dim).astype(q.dtype)


INDEXED_TRACE_NAME = "fstpu_indexed_decode_attention"

#: why the indexed read takes the xla lowering today: the kernels above
#: walk a lane's blocks in order; a chosen-TOKEN list is 2,048 rows
#: scattered over them, a DMA a row
_NO_INDEXED_KERNEL = "no Mosaic kernel reads a dynamic token list yet"


def indexed_decode_attention(q: jax.Array, qi: jax.Array, w: jax.Array,
                             index_keys: jax.Array, k: jax.Array,
                             v: jax.Array, block_table: jax.Array,
                             t: jax.Array, *, topk: int, index_scale: float,
                             layer: Optional[jax.Array] = None
                             ) -> jax.Array:
    """The seam's entry for a learned indexer (`ops/sparse_attention.py`
    has the mathematics): one query a lane scores EVERY cached token of
    the lane by the indexer's keys, chooses `topk` single tokens, and
    attends over those rows only.

    q: ``[B, 1, H, D]``; qi: ``[B, J, Di]`` and w: ``[B, J]``, the
    indexer's queries and head weights; index_keys: ``[num_blocks,
    block_size, 1, Di]`` and k/v: ``[num_blocks, block_size, 1, KVH *
    D]`` (a token's KV heads folded into one row) behind ``block_table``
    ``[B, max_blocks]`` — one table reads all three; with ``layer`` the
    ``[L, ...]`` stacks, read in place as :func:`_layer_of_stack` reads
    K/V. ``t``: ``[B]`` int32, each query's position (its context is
    ``t + 1`` tokens, all of them read while that is within ``topk``).
    Returns ``[B, 1, H, D]``."""
    from fengshen_tpu.ops.pallas import resolve_dispatch
    # recorded, not decided: the xla lowering is the only one there is
    resolve_dispatch(
        "indexed_decode_attention",
        f"q={tuple(q.shape)} kv={tuple(k.shape[-4:])}:{k.dtype.name} "
        f"topk={topk}", _NO_INDEXED_KERNEL)
    return xla_indexed_decode_attention(
        q, qi, w, index_keys, k, v, block_table, t, topk=topk,
        index_scale=index_scale, layer=layer)


def xla_indexed_decode_attention(q, qi, w, index_keys, k, v, block_table,
                                 t, *, topk, index_scale, layer=None):
    """The stock lowering and the CPU tier-1 truth: the lane's indexer
    keys gathered a block at a time (128 B a token), the scores and the
    choice under their own scopes, then ONE gather of the chosen rows —
    a row is a token's whole K (or V), 1 KB — and dense attention over
    them. A context within ``topk`` chooses every token it holds."""
    from fengshen_tpu.ops.sparse_attention import (INDEX_SCORE_SCOPE,
                                                   INDEX_TOPK_SCOPE,
                                                   topk_tokens,
                                                   weigh_heads)
    batch, _, heads, dim = q.shape
    if layer is not None:
        # the stacks as one pool of `L * num_blocks` blocks; nothing
        # else of a pool is reshaped: merging a token axis or splitting
        # a row into heads re-lays the whole pool out on the chip
        # (2.2 GB of copies a layer at this cell's size; PERF.md, PR 36)
        num_blocks = k.shape[1]
        k, v, index_keys = (x.reshape((-1,) + x.shape[2:])
                            for x in (k, v, index_keys))
        block_table = block_table + layer * num_blocks
    block, groups = k.shape[1], k.shape[-1] // dim
    lane_len = block_table.shape[-1] * block
    top = min(topk, lane_len)
    with jax.named_scope(INDEX_SCORE_SCOPE):
        lane = jnp.take(index_keys, block_table, axis=0, mode="clip")
        products = jnp.einsum("bjd,bmtd->bjmt", qi,
                              lane[:, :, :, 0].astype(qi.dtype),
                              preferred_element_type=jnp.float32)
        scores = weigh_heads(products[:, None], w[:, None],
                             index_scale).reshape(batch, lane_len)
    with jax.named_scope(INDEX_TOPK_SCOPE):
        cached = jnp.arange(lane_len)[None, :] <= t[:, None]
        chosen, taken = topk_tokens(scores, cached, top)     # [B, K]
    with jax.named_scope(INDEXED_TRACE_NAME):
        rows = jnp.take_along_axis(block_table, chosen // block,
                                   axis=-1) * block + chosen % block
        ks, vs = (jnp.take(x.reshape(-1, x.shape[-1]), rows, axis=0,
                           mode="clip").reshape(batch, top, groups, dim)
                  for x in (k, v))
        qg = q[:, 0].reshape(batch, groups, heads // groups, dim)
        sc = jnp.einsum("bgrd,bkgd->bgrk", qg, ks,
                        preferred_element_type=jnp.float32) * dim ** -0.5
        sc = jnp.where(taken[:, None, None, :], sc, _NEG_INF)
        probs = jax.nn.softmax(sc, axis=-1).astype(vs.dtype)
        out = jnp.einsum("bgrk,bkgd->bgrd", probs, vs)
    return out.reshape(batch, 1, heads, dim).astype(q.dtype)


#: what of `_VMEM_LIMIT_BYTES` the two slots of a step's K and V blocks
#: may take in the folded kernel (4 MiB at 8 blocks of 128 x 512 bf16)
_FOLDED_BUFFER_BYTES = 16 * 1024 * 1024


def folded_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            block_table: jax.Array, t: jax.Array, *,
                            scale: float,
                            layer: Optional[jax.Array] = None,
                            impl: Optional[str] = None,
                            interpret: bool = False) -> jax.Array:
    """The seam's entry for K/V rows that fold a token's few, wide KV
    heads into one (`ops/gated_attention.py` has the mathematics): one
    query a lane, or a window of up to 8 that share ONE extent (a
    generation block: every query reads keys ``<= t[lane]``), over the
    lane's live blocks, grouped — K/V are neither
    repeated per query head (the dense seam above would: 8 copies of an
    18,432-token lane) nor is a head sliced out of a gathered row.

    q: ``[B, S, H, D]``, ``1 <= S <= 8``; k/v: the shared ``[num_blocks,
    block_size, 1, KVH * D]`` pools behind ``block_table`` ``[B,
    max_blocks]``; with ``layer`` the ``[L, ...]`` stacks, read in
    place as :func:`_layer_of_stack` reads them. ``t``: ``[B]`` int32,
    the last position the lane's queries read. Returns ``[B, S, H,
    D]``. The rows' shape picks
    the path (:func:`_folded_ineligible_reason`): the Mosaic kernel
    :func:`pallas_folded_decode_attention` where it tiles, else
    ``gated_attention.folded_decode_walk``, the xla lowering and the
    CPU tier-1 truth. ``impl`` forces either, as in
    :func:`decode_attention`."""
    if impl is None:
        from fengshen_tpu.ops.pallas import resolve_dispatch
        impl = resolve_dispatch(
            "folded_decode_attention",
            f"q={tuple(q.shape)} kv={tuple(k.shape[-4:])}:{k.dtype.name}",
            _folded_ineligible_reason(q, k))
    if layer is not None:
        k, v, block_table = _layer_of_stack(k, v, block_table, layer)
    if impl == "pallas":
        return pallas_folded_decode_attention(
            q, k, v, block_table, t, scale=scale, interpret=interpret)
    return folded_decode_walk(q, k, v, block_table, t, scale=scale)


def _folded_ineligible_reason(q, k) -> Optional[str]:
    """Why rows of this shape cannot take the folded kernel, or None
    when they can."""
    _, s, n_heads, head_dim = q.shape
    block_size, one, width = k.shape[-3:]
    if s > _MAX_QUERY_WINDOW:
        return f"query window {s} > {_MAX_QUERY_WINDOW}"
    if one != 1 or width % head_dim != 0:
        return f"rows {tuple(k.shape[-2:])} do not fold whole heads of " \
               f"{head_dim} into one"
    if n_heads % (width // head_dim) != 0:
        return f"{width // head_dim} kv heads do not divide {n_heads}"
    if head_dim % 128 != 0:
        return f"head_dim {head_dim} % 128 != 0"
    if block_size % 128 != 0:
        return f"block_size {block_size} % 128 != 0"
    if 4 * CHUNK_BLOCKS * block_size * width * k.dtype.itemsize > \
            _FOLDED_BUFFER_BYTES:
        return f"{CHUNK_BLOCKS} blocks of {block_size} tokens x {width} " \
               f"{k.dtype.name} a step outgrow VMEM"
    return None


def _folded_decode_kernel(table_ref, t_ref, q_ref, k_hbm, v_hbm, o_ref,
                          k_buf, v_buf, sems, slot_ref, acc_ref, m_ref,
                          l_ref, *, scale, groups, block_size):
    """One lane a grid step; inside it a loop over the lane's LIVE
    blocks only (``t // block_size + 1`` of them), as many a step as a
    slot holds, fetched through the table into one of two VMEM slots while
    the step before is multiplied; the lane's last step prefetches the
    NEXT lane's first (``slot_ref`` carries across the sequential grid
    axis which slot that went into) — the walk of
    :func:`_decode_kernel`, over another kind of row. A step's tail
    past the lane's last live block is not fetched: a lane's walk ends
    at ITS cursor, and a released lane (cursor 0, its row on the null
    block) costs one block.

    A block is ``[block_size, G * D]``: a token's ``G`` KV heads side
    by side. Each KV head is a static slice of the buffer at a multiple
    of 128 lanes, multiplied as it lies, in the pool's dtype with
    float32 accumulation and the probabilities rounded to V's dtype
    (the precision of ``folded_decode_walk``'s two einsums): the ``H //
    G`` query heads of KV head ``g`` (rows ``g * H // G ...`` of the
    ``[H, D]`` query) against columns ``[g * D, (g + 1) * D)``. No
    zeros in the query, no mask of other heads' columns, no float32
    copy of the block. The one mask is ``position <= t``, from an
    iota. Online-softmax statistics and the ``[H, D]`` accumulator stay
    in VMEM scratch across the lane's steps."""
    lane = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    span = k_buf.shape[1]
    t = t_ref[lane]
    n_steps = t // span + 1
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))

    def fetch(lane, j, slot, wait=False):
        _fetch_step(table_ref, pools, sems, t_ref[lane] // block_size + 1,
                    lane, j, slot, block_size, wait)

    @pl.when(lane == 0)
    def _first_fetch():
        slot_ref[0] = 0
        # a step's unfetched tail is weighed by exact zeros: whatever
        # the buffer holds there must not be NaN
        v_buf[...] = jnp.zeros_like(v_buf)
        fetch(0, 0, 0)

    first_slot = slot_ref[0]
    slot_ref[0] = (first_slot + n_steps) % 2

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    n_heads, head_dim = q_ref.shape[2:]
    rep = n_heads // groups
    q = (q_ref[0, 0] * scale).astype(q_ref.dtype)            # [H, D]
    column = jax.lax.broadcasted_iota(jnp.int32, (rep, span), 1)

    def walk(j, _):
        slot = (first_slot + j) % 2
        more = j + 1 < n_steps
        next_lane = jnp.where(more, lane, lane + 1)

        @pl.when(next_lane < n_lanes)
        def _prefetch():
            fetch(next_lane, jnp.where(more, j + 1, 0), 1 - slot)

        fetch(lane, j, slot, wait=True)
        seen = column + j * span <= t
        for g in range(groups):
            rows = pl.ds(g * rep, rep)
            cols = pl.ds(g * head_dim, head_dim)
            scores = jax.lax.dot_general(
                q[g * rep:(g + 1) * rep], k_buf[slot, :, cols],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [rep, span]
            scores = jnp.where(seen, scores, _NEG_INF)
            m_prev = m_ref[rows]
            m_new = jnp.maximum(m_prev, scores.max(-1, keepdims=True))
            correction = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[rows] = l_ref[rows] * correction + \
                probs.sum(-1, keepdims=True)
            pv = jax.lax.dot_general(
                probs.astype(v_buf.dtype), v_buf[slot, :, cols],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # [rep, D]
            acc_ref[rows] = acc_ref[rows] * correction + pv
            m_ref[rows] = m_new

    jax.lax.fori_loop(0, n_steps, walk, None)
    o_ref[0, 0] = (acc_ref[...] /
                   jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def pallas_folded_decode_attention(q, k, v, block_table, t, *, scale,
                                   blocks_per_step: Optional[int] = None,
                                   interpret: bool = False):
    """The folded read as a Mosaic kernel. q ``[B, S, H, D]`` (a window
    is folded into ``S`` times the query rows of each KV head against
    the same fetched block: ``gated_attention.fold_queries``); k/v flat
    pools ``[num_blocks, block_size, 1, G * D]`` (a stack already
    flattened by :func:`_layer_of_stack`), left in HBM; ``block_table``
    ``[B, max_blocks]``; ``t`` ``[B]``, clamped to the table row's
    reach. A step takes ``blocks_per_step`` blocks, by default the
    walk's ``gated_attention.CHUNK_BLOCKS`` (1,024 keys at 128: the
    same partition of the online softmax, and on a v5e the step at
    which the fetches hide everything else; PERF.md, PR 33). Named and
    scoped ``gated_attention.DECODE_SCOPE``, so the trace finds the
    read by that text whichever path ran."""
    window, groups = q.shape[1], k.shape[-1] // q.shape[-1]
    q = fold_queries(q, groups)
    batch, _, n_heads, head_dim = q.shape
    block_size, _, width = k.shape[-3:]
    max_blocks = block_table.shape[-1]
    per = min(blocks_per_step or CHUNK_BLOCKS, max_blocks)
    t = jnp.clip(t.astype(jnp.int32), 0, max_blocks * block_size - 1)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    qo_spec = pl.BlockSpec((1, 1, n_heads, head_dim),
                           lambda b, *_: (b, 0, 0, 0))
    kernel = functools.partial(_folded_decode_kernel, scale=scale,
                               groups=width // head_dim,
                               block_size=block_size)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch,),
        in_specs=[qo_spec, in_hbm, in_hbm],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((2, per * block_size, width), k.dtype),
            pltpu.VMEM((2, per * block_size, width), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2, per)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_heads, head_dim), jnp.float32),
            pltpu.VMEM((n_heads, 1), jnp.float32),
            pltpu.VMEM((n_heads, 1), jnp.float32),
        ],
    )
    with jax.named_scope(DECODE_SCOPE):
        # a row's unit axis goes: a free reshape, the blocks stay put
        return unfold_queries(pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            interpret=interpret, name=DECODE_SCOPE,
        )(block_table.astype(jnp.int32), t, q,
          k.reshape(-1, block_size, width), v.reshape(-1, block_size, width)),
            window, groups)
