"""Fused LM-head + cross-entropy Mosaic kernel (logits never live).

The chunked XLA lowering (`ops/fused_ce.fused_lm_head_ce`) already
bounds peak logits memory to one sequence chunk; this kernel takes the
same idea to its limit: the ``[T, V]`` logits never exist outside a
``[block_t, block_v]`` VMEM tile. The forward streams vocab tiles per
token tile, keeping online-logsumexp / gold-logit / running-argmax
stats in scratch; the backward recomputes each tile's scores (flash
style — nothing but per-token ``lse`` is saved) and accumulates
``d·Kᵀ`` / ``xᵀ·d`` without materializing ``d`` beyond one tile.

Dispatch (fengshen_tpu/ops/pallas/__init__.py): ``fused_ce_loss``
routes to :func:`pallas_fused_ce` on a Mosaic-capable backend with
tile-aligned shapes, else :func:`xla_fused_ce` — the stock chunked
scan, so CPU tier-1 pins the loss path bit-for-bit. The vocab-SHARDED
variant (tensor-parallel LM head) is
``parallel.cross_entropy.fused_vocab_parallel_ce``, which runs this
seam per shard with the mpu-style collectives outside.
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fengshen_tpu.ops.fused_ce import fused_lm_head_ce

_NEG_INF = -1e30


def fused_ce_loss(hidden: jax.Array, kernel: jax.Array,
                  labels: jax.Array, num_chunks: int = 8,
                  ignore_index: int = -100,
                  impl: Optional[str] = None,
                  interpret: bool = False):
    """Dispatch seam for the fused LM-head CE: hidden ``[B, S, H]`` @
    kernel ``[H, V]`` scored against labels ``[B, S]`` →
    (mean_loss, n_valid, n_correct), full logits never materialized.
    ``impl=None`` asks the capability probe + shape eligibility."""
    if impl is None:
        from fengshen_tpu.ops.pallas import resolve_dispatch
        impl = resolve_dispatch(
            "fused_ce",
            f"hidden={tuple(hidden.shape)} kernel={tuple(kernel.shape)}"
            f":{kernel.dtype.name}", _ineligible_reason(hidden, kernel))
    if impl == "pallas":
        return pallas_fused_ce(hidden, kernel, labels,
                               num_chunks=num_chunks,
                               ignore_index=ignore_index,
                               interpret=interpret)
    return xla_fused_ce(hidden, kernel, labels, num_chunks=num_chunks,
                        ignore_index=ignore_index)


def _ineligible_reason(hidden, kernel) -> Optional[str]:
    """Why this call cannot take the Mosaic kernel, or None when it
    can. Under a multi-device mesh the answer is always the xla
    lowering: GSPMD cannot partition a Mosaic call, and unlike
    attention the loss needs reductions across whatever the operands
    are sharded over (the vocab-sharded head has its own shard_map
    path, parallel.cross_entropy.fused_vocab_parallel_ce)."""
    from fengshen_tpu.parallel.mesh import get_mesh
    del hidden
    mesh = get_mesh()
    if mesh is not None and mesh.size > 1:
        return f"{mesh.size}-device mesh: GSPMD cannot partition a " \
               "Mosaic call"
    if kernel.shape[0] % 128 or kernel.shape[1] % 128:
        return "hidden or vocab not a multiple of 128"
    return None


def pallas_ce_eligible(hidden, kernel) -> bool:
    """Tile alignment for the Mosaic path: hidden dim and vocab must
    split into 128-multiple lanes (and no multi-device mesh)."""
    return _ineligible_reason(hidden, kernel) is None


def xla_fused_ce(hidden, kernel, labels, num_chunks: int = 8,
                 ignore_index: int = -100):
    """The stock lowering: the seq-chunked ``lax.scan`` +
    ``jax.checkpoint`` fused head (ops/fused_ce.py), unchanged — the
    trainer's pre-seam loss path, so dispatch through here is
    bit-identical on CPU tier-1."""
    return fused_lm_head_ce(hidden, kernel, labels,
                            num_chunks=num_chunks,
                            ignore_index=ignore_index)


# -- forward kernel -----------------------------------------------------
# Per-token vectors (labels, lse, gold logit, argmax, cotangents) travel
# as ``[T, 1]`` columns: a ``(block_t, 1)`` block already sits tokens-on-
# sublanes, the layout the ``[block_t, block_v]`` score tile broadcasts
# against, so the kernels never turn a lane vector into a column.

def _scores(x_ref, k_ref):
    """One ``[bt, bv]`` logits tile: operands stay in their storage
    dtype (bf16 feeds the MXU directly), accumulation is f32."""
    return jax.lax.dot_general(
        x_ref[...], k_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _ce_fwd_kernel(x_ref, k_ref, lab_ref, lse_ref, gold_ref, amax_ref,
                   m_ref, l_ref, g_ref, av_ref, ai_ref, *,
                   n_vblocks, block_v):
    """Grid (token tiles, vocab tiles), vocab innermost sequential.
    Scratch carries per-token online stats across vocab tiles: running
    max/sum (logsumexp), the gold logit (exactly one tile contributes),
    and the running argmax (value + global index, first-max tie rule
    like ``jnp.argmax``)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        g_ref[...] = jnp.zeros_like(g_ref)
        av_ref[...] = jnp.full_like(av_ref, _NEG_INF)
        ai_ref[...] = jnp.zeros_like(ai_ref)

    scores = _scores(x_ref, k_ref)                   # [bt, bv]
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    lab = lab_ref[...]                               # [bt, 1]

    m_prev = m_ref[...]                              # [bt, 1]
    tile_val = scores.max(-1, keepdims=True)
    m_new = jnp.maximum(m_prev, tile_val)
    l_ref[...] = (l_ref[...] * jnp.exp(m_prev - m_new) +
                  jnp.exp(scores - m_new).sum(-1, keepdims=True))
    m_ref[...] = m_new
    g_ref[...] += jnp.where(cols == lab, scores,
                            0.0).sum(-1, keepdims=True)
    # first column holding the tile max (jnp.argmax's tie rule), as a
    # masked min over column ids: Mosaic has no lane argmax
    tile_arg = jnp.where(scores == tile_val, cols,
                         jnp.int32(2 ** 30)).min(-1, keepdims=True)
    better = tile_val > av_ref[...]
    ai_ref[...] = jnp.where(better, tile_arg, ai_ref[...])
    av_ref[...] = jnp.maximum(av_ref[...], tile_val)

    @pl.when(j == n_vblocks - 1)
    def _finalize():
        lse_ref[...] = m_ref[...] + jnp.log(
            jnp.maximum(l_ref[...], 1e-30))
        gold_ref[...] = g_ref[...]
        amax_ref[...] = ai_ref[...]


# -- backward kernels (flash-style recompute; only lse is saved) --------

def _dlogits(x_ref, k_ref, lab_ref, lse_ref, c_lse_ref, c_gold_ref,
             col0):
    """dlogits = c_lse·softmax + c_gold·onehot for one vocab tile
    starting at column ``col0``, rounded to the operands' dtype for the
    matmul that consumes it."""
    scores = _scores(x_ref, k_ref)
    p = jnp.exp(scores - lse_ref[...])
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    onehot = (cols == lab_ref[...]).astype(jnp.float32)
    d = p * c_lse_ref[...] + onehot * c_gold_ref[...]    # [bt, bv]
    return d.astype(x_ref.dtype)


def _ce_bwd_dx_kernel(x_ref, k_ref, lab_ref, lse_ref, c_lse_ref,
                      c_gold_ref, dx_ref, acc_ref, *,
                      n_vblocks, block_v):
    """dx accumulates ``dlogits @ Kᵀ`` across the vocab tiles."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = _dlogits(x_ref, k_ref, lab_ref, lse_ref, c_lse_ref, c_gold_ref,
                 j * block_v)
    acc_ref[...] += jax.lax.dot_general(
        d, k_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bt, H]

    @pl.when(j == n_vblocks - 1)
    def _finalize():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def _ce_bwd_dk_kernel(x_ref, k_ref, lab_ref, lse_ref, c_lse_ref,
                      c_gold_ref, dk_ref, acc_ref, *,
                      n_tblocks, block_v):
    """Same tile recompute, token tiles innermost: dK accumulates
    ``xᵀ @ dlogits`` for one vocab stripe across all token tiles."""
    i = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    d = _dlogits(x_ref, k_ref, lab_ref, lse_ref, c_lse_ref, c_gold_ref,
                 i * block_v)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], d, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [H, bv]

    @pl.when(t == n_tblocks - 1)
    def _finalize():
        dk_ref[...] = acc_ref[...].astype(dk_ref.dtype)


#: scoped-VMEM ceiling asked of Mosaic (the default is 16 MiB; a v5e
#: core has 128 MiB), and the share of it the pipelined blocks plus the
#: f32 accumulator of the hungriest kernel (dK) may fill
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_VMEM_BLOCK_BUDGET = 40 * 1024 * 1024

_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _pick_block(dim: int, candidates) -> int:
    for c in candidates:
        if dim % c == 0:
            return c
    return dim


def _pick_block_v(vocab: int, hid: int, block_t: int,
                  itemsize: int) -> int:
    """Largest 128-multiple vocab tile whose dK step fits the block
    budget: x and K blocks and the dK output double-buffered, plus the
    ``[hid, block_v]`` f32 accumulator."""
    for c in (512, 256, 128):
        if vocab % c:
            continue
        need = (2 * block_t * hid * itemsize +       # x
                4 * hid * c * itemsize +             # K in, dK out
                hid * c * 4)                         # accumulator
        if need <= _VMEM_BLOCK_BUDGET:
            return c
    return _pick_block(vocab, (128,))


def _col_spec(block_t: int, token_axis: int):
    """A ``[T, 1]`` column blocked along the grid axis that walks the
    token tiles."""
    return pl.BlockSpec((block_t, 1),
                        lambda *ids: (ids[token_axis], 0))


def _token_stats_impl(x, kernel, labels, block_t, block_v, interpret):
    n_t, hid = x.shape
    vocab = kernel.shape[1]
    n_tblocks, n_vblocks = n_t // block_t, vocab // block_v
    col = _col_spec(block_t, 0)
    kernel_fn = functools.partial(_ce_fwd_kernel, n_vblocks=n_vblocks,
                                  block_v=block_v)
    lse, gold, amax = pl.pallas_call(
        kernel_fn,
        grid=(n_tblocks, n_vblocks),
        in_specs=[
            pl.BlockSpec((block_t, hid), lambda i, j: (i, 0)),
            pl.BlockSpec((hid, block_v), lambda i, j: (0, j)),
            col,
        ],
        out_specs=[col, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((n_t, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_t, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_t, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.int32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x, kernel, labels.astype(jnp.int32)[:, None])
    return lse[:, 0], gold[:, 0], amax[:, 0]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _token_stats(x, kernel, labels, block_t, block_v, interpret):
    """x [T, H], kernel [H, V], labels [T] →
    (lse [T], gold logit [T], argmax id [T] int32)."""
    return _token_stats_impl(x, kernel, labels, block_t, block_v,
                             interpret)


def _token_stats_fwd(x, kernel, labels, block_t, block_v, interpret):
    lse, gold, amax = _token_stats_impl(x, kernel, labels, block_t,
                                        block_v, interpret)
    return (lse, gold, amax), (x, kernel, labels, lse)


def _token_stats_bwd(block_t, block_v, interpret, res, cts):
    x, kernel, labels, lse = res
    c_lse, c_gold, _ = cts                           # amax: int, no grad
    n_t, hid = x.shape
    vocab = kernel.shape[1]
    n_tblocks, n_vblocks = n_t // block_t, vocab // block_v
    cols = (labels.astype(jnp.int32)[:, None], lse[:, None],
            c_lse.astype(jnp.float32)[:, None],
            c_gold.astype(jnp.float32)[:, None])

    dx = pl.pallas_call(
        functools.partial(_ce_bwd_dx_kernel, n_vblocks=n_vblocks,
                          block_v=block_v),
        grid=(n_tblocks, n_vblocks),
        in_specs=[
            pl.BlockSpec((block_t, hid), lambda i, j: (i, 0)),
            pl.BlockSpec((hid, block_v), lambda i, j: (0, j)),
            *[_col_spec(block_t, 0)] * 4,
        ],
        out_specs=pl.BlockSpec((block_t, hid), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, hid), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x, kernel, *cols)

    dk = pl.pallas_call(
        functools.partial(_ce_bwd_dk_kernel, n_tblocks=n_tblocks,
                          block_v=block_v),
        grid=(n_vblocks, n_tblocks),
        in_specs=[
            pl.BlockSpec((block_t, hid), lambda i, t: (t, 0)),
            pl.BlockSpec((hid, block_v), lambda i, t: (0, i)),
            *[_col_spec(block_t, 1)] * 4,
        ],
        out_specs=pl.BlockSpec((hid, block_v), lambda i, t: (0, i)),
        out_shape=jax.ShapeDtypeStruct(kernel.shape, kernel.dtype),
        scratch_shapes=[pltpu.VMEM((hid, block_v), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x, kernel, *cols)
    return dx, dk, None


_token_stats.defvjp(_token_stats_fwd, _token_stats_bwd)


def pallas_fused_ce(hidden: jax.Array, kernel: jax.Array,
                    labels: jax.Array, num_chunks: int = 8,
                    ignore_index: int = -100,
                    block_t: int = 256, block_v: Optional[int] = None,
                    interpret: bool = False):
    """Mosaic fused-head CE. Same contract as
    ``ops.fused_ce.fused_lm_head_ce`` (``num_chunks`` is accepted for
    signature parity and ignored — the kernel's tiling replaces it):
    returns (mean_loss, n_valid, n_correct), differentiable w.r.t.
    hidden and kernel."""
    del num_chunks
    bsz, seq, hid = hidden.shape
    n_t = bsz * seq
    x = hidden.reshape(n_t, hid)
    lab = labels.reshape(n_t)
    block_t = _pick_block(n_t, (block_t, 256, 128, 8))
    if n_t % block_t:
        pad = block_t - n_t % block_t
        x = jnp.pad(x, ((0, pad), (0, 0)))
        lab = jnp.pad(lab, (0, pad), constant_values=ignore_index)
    if block_v is None:
        block_v = _pick_block_v(kernel.shape[1], hid, block_t,
                                jnp.dtype(kernel.dtype).itemsize)
    lse, gold, amax = _token_stats(x, kernel, lab, block_t, block_v,
                                   interpret)
    valid = lab != ignore_index
    token_loss = (lse - gold) * valid
    n_valid = valid.sum()
    n_correct = ((amax == lab) & valid).sum()
    return (token_loss.sum() / jnp.maximum(n_valid, 1),
            n_valid, n_correct)
