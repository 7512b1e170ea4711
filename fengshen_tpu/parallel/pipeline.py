"""Pipeline parallelism: a GPipe microbatch schedule over a mesh axis.

The reference's pipeline parallelism is plumbing-only: topology/groups/seeds
exist (reference: fengshen/models/megatron/mpu/initialize.py:111-134,
fengshen/strategies/megatron_deepspeed.py:347-361) but no PipelineModule is
ever wired into an example (SURVEY.md §2.4). This module provides a REAL
schedule, TPU-native: stages live on shards of a named mesh axis, stacked
per-stage parameters are sharded over that axis, and activations flow
stage-to-stage with `jax.lax.ppermute` while microbatches fill the pipe
(GPipe). Everything is a single SPMD program — no per-stage processes.

Usage sketch::

    mesh = Mesh(devices.reshape(4, 2), ("pipe", "data"))
    out = pipeline_apply(stage_fn, stacked_params, microbatches,
                         mesh=mesh, axis_name="pipe")

where ``stage_fn(stage_params, x) -> x`` is one stage's computation and
``stacked_params`` has a leading [n_stages] dim on every leaf.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _pvary(x, axis_name):
    """Mark `x` varying over `axis_name` for shard_map's vma type
    system; a value that already varies is returned as it is (pcast
    rejects re-application)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis_name, to="varying")


def _pipeline_body(stage_params: Any, microbatches: jax.Array,
                   stage_fn: Callable, axis_name: str,
                   n_microbatches: int) -> jax.Array:
    """shard_map body. stage_params: this stage's params (leading stage dim
    already split away by sharding). microbatches: [M, mb, ...] replicated.
    Returns [M, mb, ...] outputs valid on the LAST stage."""
    n_stages = jax.lax.axis_size(axis_name)
    stage_idx = jax.lax.axis_index(axis_name)
    is_first = stage_idx == 0
    is_last = stage_idx == n_stages - 1

    # strip the stage dim the sharding left as size 1
    local_params = jax.tree_util.tree_map(lambda x: x[0], stage_params)

    mb_shape = microbatches.shape[1:]
    # carries are pipe-varying (each stage holds different values); pvary
    # marks them so check_vma accepts the cond/where mixing below
    state = _pvary(jnp.zeros(mb_shape, microbatches.dtype), axis_name)
    outputs = _pvary(
        jnp.zeros((n_microbatches,) + mb_shape, microbatches.dtype),
        axis_name)

    total_ticks = n_microbatches + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(t, carry):
        state, outputs = carry
        # stage 0 ingests microbatch t while t < M; later stages use the
        # activation that arrived from the previous stage
        feed = _pvary(
            jnp.take(microbatches, jnp.clip(t, 0, n_microbatches - 1),
                     axis=0), axis_name)
        x = jnp.where(is_first, feed, state)
        y = stage_fn(local_params, x)
        # last stage emits microbatch (t - n_stages + 1) when it's valid
        out_idx = t - (n_stages - 1)
        emit = jnp.logical_and(is_last, out_idx >= 0)
        outputs = jax.lax.cond(
            emit,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(out_idx, 0), 0),
            lambda o: o, outputs)
        # rotate activations to the next stage (last→0 wraps; stage 0
        # ignores what it receives)
        state = jax.lax.ppermute(y, axis_name, perm)
        return state, outputs

    _, outputs = jax.lax.fori_loop(0, total_ticks, tick, (state, outputs))
    # broadcast the last stage's outputs to every shard so out_specs can be
    # replicated along the pipe axis
    outputs = jax.lax.psum(
        jnp.where(is_last, outputs, jnp.zeros_like(outputs)), axis_name)
    return outputs


def pipeline_apply(stage_fn: Callable, stacked_params: Any,
                   microbatches: jax.Array, mesh: Mesh,
                   axis_name: str = "pipe") -> jax.Array:
    """Run `stage_fn` as a GPipe pipeline over `axis_name`.

    stacked_params: pytree with leading [n_stages] dim on every leaf;
    microbatches: [n_microbatches, microbatch, ...] (replicated); returns
    [n_microbatches, microbatch, ...] outputs.
    """
    n_micro = microbatches.shape[0]
    params_spec = jax.tree_util.tree_map(
        lambda x: P(axis_name), stacked_params)
    # Manual ONLY over the pipe axis: every other mesh axis stays Auto, so
    # GSPMD keeps sharding the within-stage math (fsdp/tensor/sequence) —
    # PP composes with the other parallelism kinds in one SPMD program
    # (the reference's pipe-outer/model-inner topology,
    # fengshen/strategies/megatron_deepspeed.py:347-354).
    fn = shard_map(
        partial(_pipeline_body, stage_fn=stage_fn, axis_name=axis_name,
                n_microbatches=n_micro),
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
        axis_names=frozenset({axis_name}),
        check_vma=True)
    return fn(stacked_params, microbatches)


def _1f1b_body(stage_params: Any, micro_inputs: jax.Array,
               micro_targets: jax.Array, stage_fn: Callable,
               last_stage_loss: Callable, axis_name: str,
               n_microbatches: int):
    """shard_map body for the 1F1B schedule: forward activations and
    backward cotangents flow through the pipe on EVERY tick, so each stage
    alternates one-forward / one-backward in steady state, holding at most
    2·n_stages microbatch inputs (independent of the microbatch count M —
    GPipe-through-autodiff holds all M).

    fwd of microbatch m at stage s happens on tick m+s; bwd on tick
    m + 2S-1 - s. The backward recomputes the stage forward from the stored
    input (activation recompute, the standard TPU memory/flop trade).
    """
    S = jax.lax.axis_size(axis_name)
    sid = jax.lax.axis_index(axis_name)
    is_first = sid == 0
    is_last = sid == S - 1
    M = n_microbatches
    local_params = jax.tree_util.tree_map(lambda x: x[0], stage_params)

    mb_shape = micro_inputs.shape[1:]
    ring = 2 * S  # max in-flight inputs per stage is 2S-1-2s <= 2S-1
    pv = lambda x: _pvary(x, axis_name)  # noqa: E731
    in_buf = pv(jnp.zeros((ring,) + mb_shape, micro_inputs.dtype))
    fwd_state = pv(jnp.zeros(mb_shape, micro_inputs.dtype))
    bwd_state = pv(jnp.zeros(mb_shape, micro_inputs.dtype))
    dparams = jax.tree_util.tree_map(
        lambda p: pv(jnp.zeros(p.shape, jnp.float32)), local_params)
    loss_acc = pv(jnp.zeros((), jnp.float32))

    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [((i + 1) % S, i) for i in range(S)]
    total_ticks = M + 2 * S - 1

    def fwd_for(m):
        """stage forward for microbatch m; last stage also evaluates the
        per-microbatch loss so its backward can start next tick."""
        return jnp.clip(m, 0, M - 1)

    def tick(t, carry):
        in_buf, fwd_state, bwd_state, dparams, loss_acc = carry

        # ---- forward lane: microbatch m_f = t - sid ----
        m_f = t - sid
        fwd_live = jnp.logical_and(m_f >= 0, m_f < M)
        feed = pv(jnp.take(micro_inputs, fwd_for(m_f), axis=0))
        x = jnp.where(is_first, feed, fwd_state)
        in_buf = jax.lax.cond(
            fwd_live,
            lambda b: jax.lax.dynamic_update_index_in_dim(
                b, x, fwd_for(m_f) % ring, 0),
            lambda b: b, in_buf)
        y = stage_fn(local_params, x)

        # ---- backward lane: microbatch m_b = t - (2S - 1 - sid) ----
        m_b = t - (2 * S - 1 - sid)
        bwd_live = jnp.logical_and(m_b >= 0, m_b < M)
        x_saved = jnp.take(in_buf, fwd_for(m_b) % ring, axis=0)
        target = pv(jnp.take(micro_targets, fwd_for(m_b), axis=0))

        # ONE stage vjp serves both roles: the last stage seeds it with
        # the loss cotangent, others with the received cotangent
        out, s_vjp = jax.vjp(lambda p, x_in: stage_fn(p, x_in),
                             local_params, x_saved)
        l_val, l_vjp = jax.vjp(lambda o: last_stage_loss(o, target), out)
        (d_out,) = l_vjp(pv(jnp.ones((), l_val.dtype)))
        seed = jnp.where(is_last, d_out, bwd_state)
        ds_p, ds_x = s_vjp(seed)

        use_last = jnp.logical_and(bwd_live, is_last)
        dparams = jax.tree_util.tree_map(
            lambda acc, ds: acc +
            jnp.where(bwd_live, ds.astype(jnp.float32), 0.0),
            dparams, ds_p)
        dx_out = jnp.where(bwd_live, ds_x, jnp.zeros_like(ds_x))
        loss_acc = loss_acc + jnp.where(use_last, l_val, 0.0)

        # ---- rotate both lanes ----
        fwd_state = jax.lax.ppermute(y, axis_name, fwd_perm)
        bwd_state = jax.lax.ppermute(dx_out, axis_name, bwd_perm)
        return in_buf, fwd_state, bwd_state, dparams, loss_acc

    carry = (in_buf, fwd_state, bwd_state, dparams, loss_acc)
    _, _, _, dparams, loss_acc = jax.lax.fori_loop(0, total_ticks, tick,
                                                   carry)
    # every stage holds ITS OWN dparams; restore the stacked layout by
    # keeping the local slice (shard_map out_specs put the stage dim back)
    # mean over microbatches for BOTH loss and grads, so the returned
    # grads are exactly d(loss)/d(params)
    dparams = jax.tree_util.tree_map(lambda g: g[None] / M, dparams)
    loss = jax.lax.psum(loss_acc, axis_name) / M
    return loss, dparams


def pipeline_train_step_1f1b(stage_fn: Callable, last_stage_loss: Callable,
                             stacked_params: Any,
                             micro_inputs: jax.Array,
                             micro_targets: jax.Array, mesh: Mesh,
                             axis_name: str = "pipe"):
    """One 1F1B training step over the `axis_name` mesh axis.

    stage_fn(stage_params, x) -> x; last_stage_loss(final_activations,
    target) -> scalar loss (mean over the microbatch). Returns
    (mean_loss, stacked_param_grads) — grads carry the same leading
    [n_stages] dim as `stacked_params`.
    """
    n_micro = micro_inputs.shape[0]
    params_spec = jax.tree_util.tree_map(
        lambda x: P(axis_name), stacked_params)
    fn = shard_map(
        partial(_1f1b_body, stage_fn=stage_fn,
                last_stage_loss=last_stage_loss, axis_name=axis_name,
                n_microbatches=n_micro),
        mesh=mesh,
        in_specs=(params_spec, P(), P()),
        out_specs=(P(), params_spec),
        axis_names=frozenset({axis_name}),
        check_vma=True)
    return fn(stacked_params, micro_inputs, micro_targets)
