"""HF (`Qwen3NextForCausalLM`-shaped `state_dict`, as
Qwen/Qwen3-Next-80B-A3B-Instruct publishes it) -> flax params.

The key table: torch Linear stores [out, in] and flax Dense kernels are
[in, out], so every `.weight` of a projection is transposed; a
zero-centred norm's `weight` keeps its name (it is added to one); the
gated norm's `weight` is `norm_scale`; the per-expert modules of a layer
become three stacked `[E_held, ...]` tables (`experts_held`); the
router's `gate.weight` `[E, H]` is `router/kernel` `[H, E]` in float32.

ASSUMED (no network here to read the published checkpoint's index):

- `linear_attn.in_proj_qkvz.weight` rows are laid out per key-head
  group, `[q Dk | k Dk | v r Dv | z r Dv]` a group (`r` value heads a
  key head), and `in_proj_ba.weight` `[b r | a r]` a group, as the
  published modeling code's `fix_query_key_value_ordering` reads them;
  this program's kernels are flat `[q | k | v | z]` and `[b | a]`, so
  the columns are permuted here;
- `linear_attn.conv1d.weight` is `[channels, 1, K]` over the flat
  `[q | k | v]` channels: `conv1d` here is its `[K, channels]`;
- a vocabulary slice (`vocab_size` below the checkpoint's) takes the
  first rows of the embedding and columns of the head.

A checkpoint that lays one out otherwise needs `_ungroup` changed,
nothing else. Keys of the multi-token-prediction module (`mtp.*`) are
not read: this program does not build it.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from fengshen_tpu.models.qwen3_next.configuration_qwen3_next import (
    FULL, Qwen3NextConfig)
from fengshen_tpu.utils.convert_common import tensor as _tensor

_SWIGLU = ("gate_proj", "up_proj", "down_proj")


def _ungroup(kernel: np.ndarray, groups: int, widths: tuple) -> np.ndarray:
    """`[in, groups * sum(widths)]`, columns `[part 0 | part 1 | ...]` a
    group -> `[in, ...]` with every group's part 0 first, then every
    group's part 1, ..."""
    per = kernel.reshape(kernel.shape[0], groups, sum(widths))
    edges = np.cumsum((0,) + widths)
    return np.concatenate(
        [per[:, :, a:b].reshape(kernel.shape[0], -1)
         for a, b in zip(edges[:-1], edges[1:])], axis=1)


def torch_to_params(state_dict: Mapping[str, Any],
                    config: Qwen3NextConfig) -> dict:
    def t(name):
        return _tensor(state_dict, name)

    def proj(name):
        return {"kernel": t(name + ".weight").T}

    Hk, rep = config.linear_num_key_heads, \
        config.linear_num_value_heads // config.linear_num_key_heads
    Dk, Dv = config.linear_key_head_dim, config.linear_value_head_dim
    first, count = config.experts_held or (0, config.num_experts)

    def mixer(pre: str, kind: str) -> tuple:
        if kind == FULL:
            a = f"{pre}.self_attn"
            return "self_attn", {
                **{p: proj(f"{a}.{p}")
                   for p in ("q_proj", "k_proj", "v_proj", "o_proj")},
                "q_norm": {"weight": t(f"{a}.q_norm.weight")},
                "k_norm": {"weight": t(f"{a}.k_norm.weight")}}
        a = f"{pre}.linear_attn"
        return "linear_attn", {
            "in_proj_qkvz": {"kernel": _ungroup(
                t(f"{a}.in_proj_qkvz.weight").T, Hk,
                (Dk, Dk, rep * Dv, rep * Dv))},
            "in_proj_ba": {"kernel": _ungroup(
                t(f"{a}.in_proj_ba.weight").T, Hk, (rep, rep))},
            "conv1d": t(f"{a}.conv1d.weight")[:, 0, :].T,
            "A_log": t(f"{a}.A_log"), "dt_bias": t(f"{a}.dt_bias"),
            "norm_scale": t(f"{a}.norm.weight"),
            "out_proj": proj(f"{a}.out_proj")}

    def layer_tree(i: int, kind: str) -> dict:
        pre = f"model.layers.{i}"
        m = f"{pre}.mlp"
        mlp = {"router": {"kernel": t(f"{m}.gate.weight").T
                          .astype(np.float32)},
               "shared_experts": {p: proj(f"{m}.shared_expert.{p}")
                                  for p in _SWIGLU},
               "shared_expert_gate": proj(f"{m}.shared_expert_gate")}
        for p in _SWIGLU:
            mlp["experts_" + p[:-5]] = np.stack([
                t(f"{m}.experts.{e}.{p}.weight").T
                for e in range(first, first + count)])
        name, tree = mixer(pre, kind)
        return {name: tree, "mlp": mlp,
                "input_layernorm": {
                    "weight": t(f"{pre}.input_layernorm.weight")},
                "post_attention_layernorm": {
                    "weight": t(f"{pre}.post_attention_layernorm.weight")}}

    V = config.vocab_size
    model = {"embed_tokens": {"embedding": t("model.embed_tokens.weight")[:V]},
             "norm": {"weight": t("model.norm.weight")},
             **{f"layers_{i}": layer_tree(i, kind)
                for i, kind in enumerate(config.layer_types)}}
    return {"model": model,
            "lm_head": {"kernel": t("lm_head.weight").T[:, :V]}}
