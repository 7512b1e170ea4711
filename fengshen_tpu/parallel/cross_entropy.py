"""Vocab-parallel cross entropy.

TPU-native port of the reference's numerically-stable softmax CE over a
vocab-sharded logits tensor (reference:
fengshen/models/megatron/mpu/cross_entropy.py:27-117): global max via
allreduce(MAX), per-shard target masking, sum-exp allreduce. Here the
collectives are `jax.lax.psum`/`pmax` inside `shard_map` over the 'tensor'
mesh axis, and the backward pass comes from autodiff instead of a
hand-written autograd.Function.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from fengshen_tpu.parallel.mesh import (BATCH_AXES, SEQUENCE_AXIS,
                                        TENSOR_AXIS, get_mesh)


def stable_cross_entropy(logits: jax.Array, targets: jax.Array,
                         ignore_index: int = -100) -> tuple[jax.Array, jax.Array]:
    """Replicated-logits CE with -100 masking (HF convention used throughout
    the reference's examples, e.g. reference:
    fengshen/models/llama/modeling_llama.py:334-339).

    Returns (mean_loss, n_valid_tokens).
    """
    valid = targets != ignore_index
    safe_targets = jnp.where(valid, targets, 0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_targets[..., None], axis=-1)[..., 0]
    token_loss = (logz - gold) * valid
    n_valid = jnp.maximum(valid.sum(), 1)
    return token_loss.sum() / n_valid, valid.sum()


def _sharded_ce_block(logits: jax.Array, targets: jax.Array,
                      axis_name: str, ignore_index: int) -> jax.Array:
    """Per-shard CE body: logits [..., V/t] local shard, targets global ids."""
    vocab_shard = logits.shape[-1]
    rank = jax.lax.axis_index(axis_name)
    vocab_start = rank * vocab_shard

    logits = logits.astype(jnp.float32)
    # global max for stability (reference: mpu/cross_entropy.py:36-41);
    # gradient-neutral, and pmax has no differentiation rule, so detach
    local_max = jax.lax.stop_gradient(logits.max(axis=-1))
    global_max = jax.lax.pmax(local_max, axis_name)
    shifted = logits - global_max[..., None]
    sum_exp = jax.lax.psum(jnp.exp(shifted).sum(axis=-1), axis_name)

    # gold logit lives on exactly one shard
    # (reference: mpu/cross_entropy.py:49-67 target masking)
    local_t = targets - vocab_start
    in_shard = (local_t >= 0) & (local_t < vocab_shard)
    safe_t = jnp.clip(local_t, 0, vocab_shard - 1)
    gold_local = jnp.take_along_axis(shifted, safe_t[..., None], axis=-1)[..., 0]
    gold = jax.lax.psum(jnp.where(in_shard, gold_local, 0.0), axis_name)

    return jnp.log(sum_exp) - gold


def _leading_dims_spec(shape: tuple, mesh: Mesh) -> list:
    """Mesh axes for the leading (batch, seq, ...) dims: the batch dim over
    whichever BATCH_AXES divide it, the sequence dim over 'sequence'; an
    axis is only used when its size divides the dim (spec must fit shape)."""
    dims: list = []
    axes, div = [], 1
    for ax in BATCH_AXES:
        size = mesh.shape.get(ax, 1)
        if size > 1 and shape[0] % (div * size) == 0:
            axes.append(ax)
            div *= size
    dims.append(tuple(axes) if axes else None)
    for d in range(1, len(shape)):
        seq_size = mesh.shape.get(SEQUENCE_AXIS, 1)
        if d == 1 and seq_size > 1 and shape[1] % seq_size == 0:
            dims.append(SEQUENCE_AXIS)
        else:
            dims.append(None)
    return dims


def vocab_parallel_cross_entropy(logits: jax.Array, targets: jax.Array,
                                 mesh: Optional[Mesh] = None,
                                 ignore_index: int = -100) -> tuple[jax.Array, jax.Array]:
    """CE over logits sharded on the last (vocab) dim along the 'tensor' axis.

    Avoids materialising the all-gathered [B, S, V] logits that the
    reference's ``parallel_output=False`` eval path pays for
    (reference: fengshen/models/megatron/layers/transformer.py:800-815).
    Falls back to the replicated implementation when no mesh / no tensor
    parallelism is active.
    """
    mesh = mesh or get_mesh()
    if mesh is None or TENSOR_AXIS not in mesh.shape or mesh.shape[TENSOR_AXIS] == 1:
        return stable_cross_entropy(logits, targets, ignore_index)
    if logits.shape[-1] % mesh.shape[TENSOR_AXIS] != 0:
        return stable_cross_entropy(logits, targets, ignore_index)

    # Keep the batch/sequence dims sharded inside the shard_map (the normal
    # training layout shards them over data/fsdp/sequence); replicating them
    # here would force an all-gather of the [B, S, V/t] logits along the
    # batch axes and inflate per-device memory for no reason.
    lead = _leading_dims_spec(targets.shape, mesh)
    batch_spec = P(*lead)
    logits_spec = P(*lead, TENSOR_AXIS)

    token_loss = shard_map(
        partial(_sharded_ce_block, axis_name=TENSOR_AXIS,
                ignore_index=ignore_index),
        mesh=mesh,
        in_specs=(logits_spec, batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(logits, targets)

    valid = targets != ignore_index
    token_loss = token_loss * valid
    n_valid = jnp.maximum(valid.sum(), 1)
    return token_loss.sum() / n_valid, valid.sum()


def _fused_sharded_block(hidden: jax.Array, kernel: jax.Array,
                         targets: jax.Array, *, axis_name: str,
                         num_chunks: int, ignore_index: int):
    """Per-shard fused LM-head + CE body: hidden ``[b, s, H]`` (local
    batch/seq shard), kernel ``[H, V/t]`` (local vocab shard), targets
    global ids. Runs the head matmul per sequence chunk inside a
    ``lax.scan`` with ``jax.checkpoint`` (the ops/fused_ce.py scheme),
    so only one ``[b, chunk, V/t]`` logits slice is ever live; each
    chunk's CE reuses :func:`_sharded_ce_block` verbatim — per-token
    reductions are row-independent, which is what keeps the chunked
    loss bitwise equal to the whole-sequence one.

    Returns per-token ``(loss, predicted id)`` — the global argmax
    (pmax on the value, pmin on the candidate id) follows
    ``jnp.argmax``'s lowest-index tie rule across shards."""
    b, s, hd = hidden.shape
    vocab_shard = kernel.shape[-1]
    vocab_start = jax.lax.axis_index(axis_name) * vocab_shard
    nc = min(num_chunks, s)
    padded = s
    if s % nc:
        pad = nc - s % nc
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=ignore_index)
        padded = s + pad
    chunk = padded // nc
    hidden_c = jnp.moveaxis(hidden.reshape(b, nc, chunk, hd), 1, 0)
    targets_c = jnp.moveaxis(targets.reshape(b, nc, chunk), 1, 0)

    @jax.checkpoint
    def chunk_stats(h, t):
        # the ONLY live logits: one [b, chunk, V/t] slice
        logits = h @ kernel
        token_loss = _sharded_ce_block(logits, t, axis_name,
                                       ignore_index)
        f32 = logits.astype(jnp.float32)
        local_max = jax.lax.stop_gradient(f32.max(-1))
        local_arg = f32.argmax(-1).astype(jnp.int32) + vocab_start
        global_max = jax.lax.pmax(local_max, axis_name)
        candidate = jnp.where(local_max == global_max, local_arg,
                              jnp.int32(2**31 - 1))
        pred = jax.lax.pmin(candidate, axis_name)
        return token_loss, pred

    def body(carry, xs):
        h, t = xs
        return carry, chunk_stats(h, t)

    _, (token_loss, pred) = lax.scan(body, None, (hidden_c, targets_c))
    token_loss = jnp.moveaxis(token_loss, 0, 1).reshape(b, padded)[:, :s]
    pred = jnp.moveaxis(pred, 0, 1).reshape(b, padded)[:, :s]
    return token_loss, pred


def fused_vocab_parallel_ce(hidden: jax.Array, kernel: jax.Array,
                            targets: jax.Array,
                            mesh: Optional[Mesh] = None,
                            num_chunks: int = 8,
                            ignore_index: int = -100
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused LM-head + CE over a vocab-SHARDED head: hidden ``[B, S,
    H]`` @ kernel ``[H, V]`` (sharded on V along 'tensor') scored
    against targets ``[B, S]`` → (mean_loss, n_valid, n_correct).

    The upgrade the kernel layer brings to this module
    (docs/kernels.md): under tensor parallelism the trainer previously
    had to materialize the full sharded ``[B, S, V/t]`` logits tensor
    to feed :func:`vocab_parallel_cross_entropy`; this runs the head
    matmul chunk-by-chunk inside the shard, so peak logits memory
    drops by the chunk factor AND the vocab stays sharded — the mpu
    collectives (global max / sum-exp / gold psum) are unchanged,
    reused per chunk, which keeps the loss bitwise equal to the
    unfused path. Falls back to the replicated fused seam
    (``ops.pallas.fused_ce_loss``) when no mesh / no tensor axis /
    vocab not divisible."""
    mesh = mesh or get_mesh()
    tensor = 0 if mesh is None else mesh.shape.get(TENSOR_AXIS, 1)
    if mesh is None or tensor <= 1 or kernel.shape[-1] % tensor != 0:
        from fengshen_tpu.ops.pallas.fused_ce import fused_ce_loss
        return fused_ce_loss(hidden, kernel, targets,
                             num_chunks=num_chunks,
                             ignore_index=ignore_index)
    lead = _leading_dims_spec(targets.shape, mesh)
    batch_spec = P(*lead)

    token_loss, pred = shard_map(
        partial(_fused_sharded_block, axis_name=TENSOR_AXIS,
                num_chunks=num_chunks, ignore_index=ignore_index),
        mesh=mesh,
        in_specs=(P(*lead, None), P(None, TENSOR_AXIS), batch_spec),
        out_specs=(batch_spec, batch_spec),
        check_vma=False,
    )(hidden, kernel, targets)

    valid = targets != ignore_index
    token_loss = token_loss * valid
    n_valid = jnp.maximum(valid.sum(), 1)
    n_correct = ((pred == targets) & valid).sum()
    return token_loss.sum() / n_valid, valid.sum(), n_correct
