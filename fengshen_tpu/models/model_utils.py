"""Optimizer / LR-scheduler factory and shared argparse groups.

Port of the reference's shared model utilities
(reference: fengshen/models/model_utils.py:13-209):
- `add_module_args` — the canonical hyperparameter flag group (:13-28)
- no-decay parameter grouping (:39-47)
- `configure_optimizers` — optimizer + scheduler selection (:50-98)
- schedulers: polynomial / constant / cosine + custom inverse_square_root
  and Direct_LR passthrough (:101-192)
- `get_total_steps` (:194-209)

TPU-native differences: `optax.adamw` replaces FusedAdam/DeepSpeedCPUAdam
(XLA already fuses the update), and "CPU offload" of optimizer state is a
sharding/placement decision (see trainer), not a different optimizer.

Below them, what more than two decoders need of each other's cache
plumbing (ROADMAP D14: a third model takes it from here, not from a
sibling's private names): `expert_share`, `key_mask`, `token_mask`,
`LatentCache` / `write_latent`, `write_rows` / `flat_rows` (K/V rows
that fold a token's heads into one, behind one table).
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


def add_module_args(parent_parser: argparse.ArgumentParser):
    """Reference: fengshen/models/model_utils.py:13-28 (same flag names)."""
    parser = parent_parser.add_argument_group("Basic Module")
    parser.add_argument("--learning_rate", default=5e-5, type=float)
    parser.add_argument("--min_learning_rate", default=1e-7, type=float)
    parser.add_argument("--lr_decay_steps", default=0, type=int)
    parser.add_argument("--lr_decay_ratio", default=1.0, type=float)
    parser.add_argument("--warmup_steps", default=0, type=int)
    parser.add_argument("--warmup_ratio", default=0.1, type=float)
    parser.add_argument("--weight_decay", default=1e-1, type=float)
    parser.add_argument("--adam_beta1", default=0.9, type=float)
    parser.add_argument("--adam_beta2", default=0.999, type=float)
    parser.add_argument("--adam_epsilon", default=1e-8, type=float)
    parser.add_argument("--model_path", default=None, type=str)
    parser.add_argument(
        "--scheduler_type", default="polynomial", type=str,
        choices=["polynomial", "constant", "cosine", "inverse_sqrt",
                 "constant_with_warmup", "direct"])
    return parent_parser


def add_inverse_square_args(parent_parser: argparse.ArgumentParser):
    """Reference: fengshen/models/model_utils.py:31-36."""
    parser = parent_parser.add_argument_group("Inverse Square")
    parser.add_argument("--warmup_min_lr", default=1e-9, type=float)
    parser.add_argument("--warmup_max_lr", default=1e-4, type=float)
    return parent_parser


NO_DECAY_PATTERNS = ("bias", "scale", "layernorm", "layer_norm", "ln_",
                     "norm")


def decay_mask_fn(params: Any) -> Any:
    """True where weight decay applies. Port of the no-decay grouping
    (reference: fengshen/models/model_utils.py:39-47 — biases and LayerNorm
    weights are excluded)."""
    from fengshen_tpu.parallel.partition import tree_paths
    paths = tree_paths(params)

    def keep(path: str, leaf) -> bool:
        low = path.lower()
        if any(p in low for p in NO_DECAY_PATTERNS):
            return False
        return getattr(leaf, "ndim", 0) >= 2

    return jax.tree_util.tree_map(keep, paths, params)


def get_scheduler(args, total_steps: int) -> optax.Schedule:
    """LR schedule factory (reference: fengshen/models/model_utils.py:85-192).

    warmup_steps wins over warmup_ratio, as in the reference's
    `get_warmup_steps` (:194-198).
    """
    lr = args.learning_rate
    warmup = args.warmup_steps if args.warmup_steps > 0 else int(
        args.warmup_ratio * total_steps)
    decay_steps = args.lr_decay_steps if getattr(
        args, "lr_decay_steps", 0) > 0 else total_steps
    stype = getattr(args, "scheduler_type", "polynomial")

    if stype == "direct":
        # Direct_LR: constant lr, no warmup (reference custom scheduler)
        return optax.constant_schedule(lr)
    if stype in ("constant", "constant_with_warmup"):
        return optax.join_schedules(
            [optax.linear_schedule(0.0, lr, max(warmup, 1)),
             optax.constant_schedule(lr)], [warmup])
    if stype == "cosine":
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=lr, warmup_steps=warmup,
            decay_steps=decay_steps,
            end_value=getattr(args, "min_learning_rate", 0.0))
    if stype == "inverse_sqrt":
        warmup_min = getattr(args, "warmup_min_lr", 1e-9)
        warmup_max = getattr(args, "warmup_max_lr", lr)

        def inv_sqrt(step):
            w = max(warmup, 1)
            warm = warmup_min + (warmup_max - warmup_min) * (step / w)
            decay = warmup_max * (w ** 0.5) / (jax.numpy.maximum(
                step, 1) ** 0.5)
            return jax.numpy.where(step < w, warm, decay)

        return inv_sqrt
    # polynomial (HF get_polynomial_decay_schedule_with_warmup parity)
    end_lr = getattr(args, "min_learning_rate", 0.0)
    return optax.join_schedules(
        [optax.linear_schedule(0.0, lr, max(warmup, 1)),
         optax.polynomial_schedule(
             init_value=lr, end_value=end_lr, power=1.0,
             transition_steps=max(decay_steps - warmup, 1))],
        [warmup])


def configure_optimizers(args, total_steps: int,
                         params: Optional[Any] = None
                         ) -> tuple[optax.GradientTransformation,
                                    optax.Schedule]:
    """Optimizer factory (reference: fengshen/models/model_utils.py:50-98).

    Returns (tx, schedule). `params` enables the no-decay mask; without it
    decay applies everywhere (callers should pass params).
    """
    schedule = get_scheduler(args, total_steps)
    # the mask goes in as a CALLABLE so optax evaluates it on whatever
    # tree the transform actually sees — identical for plain training,
    # and under optax.masked / multi_transform (the LoRA path) it
    # adapts to the masked subtree instead of relying on optax to line
    # up an eagerly-built full-tree mask
    mask = decay_mask_fn if params is not None else None
    tx = optax.adamw(
        learning_rate=schedule,
        b1=getattr(args, "adam_beta1", 0.9),
        b2=getattr(args, "adam_beta2", 0.999),
        eps=getattr(args, "adam_epsilon", 1e-8),
        weight_decay=getattr(args, "weight_decay", 0.0),
        mask=mask,
    )
    if getattr(args, "gradient_clip_val", 0.0):
        tx = optax.chain(
            optax.clip_by_global_norm(args.gradient_clip_val), tx)
    return tx, schedule


def get_total_steps(args, dataset_len: int, world_batch: int) -> int:
    """Total optimizer steps (reference: fengshen/models/model_utils.py:194-209,
    mpu-aware world size folded into `world_batch` by the caller)."""
    if getattr(args, "max_steps", 0) and args.max_steps > 0:
        return args.max_steps
    epochs = getattr(args, "max_epochs", 1) or 1
    return max(1, epochs * dataset_len // max(world_batch, 1))


def expert_share(config, params: dict, first: int, count: int):
    """(config, params) of the share that holds experts `first ...
    first + count` of every expert layer: the `[E, ...]` tables sliced,
    everything else aliased. What one chip of an expert-parallel
    deployment is given (docs/sharding.md)."""
    def cut(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        if not name.startswith("experts_"):
            return leaf
        return leaf[first:first + count]
    return (dataclasses.replace(config, experts_held=(first, count)),
            jax.tree_util.tree_map_with_path(cut, params))


def head_rows(hidden, logits_row):
    """The rows of `hidden` `[B, T, H]` a causal LM's head projects:
    all of them when `logits_row` is None, else the ONE at `logits_row`
    (a traced int32 scalar), `[B, 1, H]`. A prefill keeps one row's
    logits a prompt, and the caller knows which; sliced after the
    product, the head ran over every row of every window (PERF.md,
    PR 46)."""
    if logits_row is None:
        return hidden
    return jax.lax.dynamic_slice_in_dim(hidden, logits_row, 1, axis=1)


def key_mask(attention_mask, max_len: int):
    """`[B, max_len]`, nonzero where a cache position may be read, from
    a mask over them (shorter than the cache: ones after it; longer:
    cut), in the mask's dtype; None stays None."""
    if attention_mask is None:
        return None
    m = attention_mask[:, :max_len]
    if m.shape[1] < max_len:
        m = jnp.concatenate(
            [m, jnp.ones((m.shape[0], max_len - m.shape[1]), m.dtype)], 1)
    return m


def token_mask(attention_mask, start, seq: int, max_len: int):
    """`[B, seq]` bool: which of the window's tokens are real, from a
    mask over cache positions (shorter than the cache: ones after it)."""
    if attention_mask is None:
        return None
    m = attention_mask.astype(bool)
    if m.shape[1] < max_len:
        m = jnp.pad(m, ((0, 0), (0, max_len - m.shape[1])),
                    constant_values=True)
    return jax.lax.dynamic_slice_in_dim(m, start, seq, axis=1)


class LatentCache(NamedTuple):
    """The cache stacks the layer loop carries (module docstring)."""

    kv: jax.Array
    index: jax.Array
    table: Optional[jax.Array]


def write_latent(cache: LatentCache, rows, layer, attention_mask):
    """Scatter this step's latent `rows` `[B, S, R]` into layer
    `layer` of the stack at each lane's cursor. Returns the cache and
    the `[B, S, T]` validity of the layer's lane positions (query `t`
    of lane `b`, at `index + t`, sees positions up to its own; a
    `attention_mask` over cache positions masks a left-padded prompt).

    The stack is addressed flat — layer `l`'s row `r` is row `l *
    rows_per_layer + r` — so the write is one scatter into the carried
    buffer, in place (PERF.md, PR 25). Paged lanes go through their
    `block_table` row; free lanes are parked on the null block."""
    batch, seq, width = rows.shape
    kv = cache.kv
    index = jnp.broadcast_to(cache.index[layer], (batch,))
    p = index[:, None] + jnp.arange(seq)[None, :]              # [B, S]
    if cache.table is not None:
        num_blocks, block_size = kv.shape[1:3]
        lane_table = cache.table[layer]
        lane_len = lane_table.shape[-1] * block_size
        if seq > lane_len:
            raise ValueError(
                f"paged cache updates take at most the virtual lane "
                f"length {lane_len} tokens per step; got seq={seq}. "
                "Prefill runs on a contiguous batch-1 cache.")
        blk = jnp.take_along_axis(lane_table, p // block_size, axis=-1)
        pos = (layer * num_blocks + blk) * block_size + p % block_size
    else:
        lane_len = kv.shape[2]
        pos = (layer * batch + jnp.arange(batch)[:, None]) * lane_len + p
    flat = kv.reshape((-1,) + kv.shape[3:])
    kv = flat.at[pos.reshape(-1)].set(
        rows.reshape(batch * seq, 1, width).astype(kv.dtype)
    ).reshape(kv.shape)
    valid = jnp.arange(lane_len)[None, None, :] <= p[:, :, None]
    if attention_mask is not None:
        valid = valid & key_mask(attention_mask,
                                 lane_len)[:, None, :].astype(bool)
    return LatentCache(kv, cache.index.at[layer].add(seq),
                       cache.table), valid


def write_rows(cache, layer: int, **rows):
    """This step's rows — each `[B, S, ...]` under the name of the stack
    it goes into (`k=`, `v=`), a token's heads folded into one row —
    into layer `layer` of those stacks (fields of `cache`, a NamedTuple
    with `table` and `start` beside them) at each lane's cursor, in place:
    one slice update a stack on a contiguous cache with a scalar cursor,
    else one scatter into the stack addressed flat (PERF.md, PR 25);
    paged lanes go through their `block_table` row, free lanes park on
    the null block."""
    batch, seq = next(iter(rows.values())).shape[:2]
    rows = {name: x.reshape(batch, seq, 1, -1) for name, x in rows.items()}
    if cache.start.ndim == 0:
        at = (layer, 0, cache.start, 0, 0)
        return cache._replace(**{
            name: jax.lax.dynamic_update_slice(
                getattr(cache, name),
                x[None].astype(getattr(cache, name).dtype), at)
            for name, x in rows.items()})
    pos = flat_rows(cache, layer,
                    cache.start[:, None] + jnp.arange(seq)[None]
                    ).reshape(-1)

    def put(pool, x):
        flat = pool.reshape((-1,) + pool.shape[3:])
        return flat.at[pos].set(
            x.reshape((batch * seq,) + x.shape[2:]).astype(pool.dtype)
        ).reshape(pool.shape)
    return cache._replace(**{name: put(getattr(cache, name), x)
                             for name, x in rows.items()})


def flat_rows(cache, layer: int, p, stride: int = 1):
    """Where positions `p` `[B, n]` (of tokens; `stride` > 1: of the
    pooled keys that start at them) of layer `layer` lie in the K/V
    (pooled) stack viewed as rows."""
    pool = cache.k
    if cache.table is not None:
        num_blocks, block_size = pool.shape[1:3]
        blk = jnp.take_along_axis(cache.table[layer], p // block_size,
                                  axis=-1)
        return ((layer * num_blocks + blk) * block_size +
                p % block_size) // stride
    batch, lane_len = pool.shape[1:3]
    lane = layer * batch + jnp.arange(batch)[:, None]
    return (lane * lane_len + p) // stride
