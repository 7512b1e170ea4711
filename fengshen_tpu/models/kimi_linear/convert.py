"""HF (`KimiLinearForCausalLM`-shaped `state_dict`, as
moonshotai/Kimi-Linear-48B-A3B-Instruct publishes it) -> flax params.

ASSUMED key names (no network here; the table follows the published
modeling file as remembered and is the one place to correct): a layer's
mixer is `self_attn` in both kinds; a KDA layer has `q_proj`, `k_proj`,
`v_proj` and `q_conv1d`, `k_conv1d`, `v_conv1d` (depthwise `Conv1d`
weights `[channels, 1, K]`), `A_log` `[1, 1, H, 1]`, `f_a_proj`,
`f_b_proj`, `dt_bias`, `b_proj`, `g_a_proj`, `g_b_proj`, `o_norm.weight`
and `o_proj`; a latent layer `q_proj`, `kv_a_proj_with_mqa`,
`kv_a_layernorm`, `kv_b_proj`, `o_proj`; the experts sit under
`block_sparse_moe` as `gate.weight`, `gate.e_score_correction_bias`,
`experts.<e>.w1 / w3 / w2` (gate, up, down) and `shared_experts.
{gate,up,down}_proj`; the dense layer's SwiGLU under `mlp`.

torch Linear stores `[out, in]` and flax Dense kernels are `[in, out]`,
so every projection's `.weight` is transposed; a norm's `weight` is its
`scale`. This program runs q, k and v as ONE projection and ONE
convolution over `[q | k | v]`, so the three weights are laid side by
side (columns, channels). The 256 per-expert modules of a layer become
three stacked `[E_held, ...]` tables; a share's `vocab_size` keeps the
first rows of the embedding and columns of the head.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from fengshen_tpu.models.kimi_linear.configuration_kimi_linear import (
    KDA, KimiLinearConfig)
from fengshen_tpu.utils.convert_common import tensor as _tensor

_EXPERT = {"gate": "w1", "up": "w3", "down": "w2"}
_SWIGLU = ("gate_proj", "up_proj", "down_proj")


def torch_to_params(state_dict: Mapping[str, Any],
                    config: KimiLinearConfig) -> dict:
    def t(name):
        return _tensor(state_dict, name)

    def kernel(name):
        return {"kernel": t(name + ".weight").T}

    def swiglu(prefix):
        return {proj: kernel(f"{prefix}.{proj}") for proj in _SWIGLU}

    def kda(pre: str) -> dict:
        return {
            "qkv_proj": {"kernel": np.concatenate(
                [t(f"{pre}.{x}_proj.weight").T for x in "qkv"], axis=1)},
            # [C, 1, K] -> [K, C]
            "conv1d": np.concatenate(
                [t(f"{pre}.{x}_conv1d.weight")[:, 0].T for x in "qkv"],
                axis=1),
            "A_log": t(f"{pre}.A_log").reshape(-1),
            "dt_bias": t(f"{pre}.dt_bias").reshape(-1),
            **{p: kernel(f"{pre}.{p}") for p in (
                "f_a_proj", "f_b_proj", "b_proj", "g_a_proj", "g_b_proj",
                "o_proj")},
            "o_norm_scale": t(f"{pre}.o_norm.weight")}

    def latent(pre: str) -> dict:
        return {**{p: kernel(f"{pre}.{p}") for p in (
                    "q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")},
                "kv_a_layernorm": {
                    "scale": t(f"{pre}.kv_a_layernorm.weight")}}

    def layer_tree(i: int, kind: str) -> dict:
        pre = f"model.layers.{i}"
        mixer = (kda if kind == KDA else latent)(f"{pre}.self_attn")
        if i < config.first_k_dense_replace:
            mlp = swiglu(f"{pre}.mlp")
        else:
            moe = f"{pre}.block_sparse_moe"
            first, count = config.experts_held or (0, config.num_experts)
            mlp = {"router": {"kernel": t(f"{moe}.gate.weight").T
                              .astype(np.float32)},
                   "e_score_correction_bias": t(
                       f"{moe}.gate.e_score_correction_bias")}
            for name, w in _EXPERT.items():
                mlp["experts_" + name] = np.stack([
                    t(f"{moe}.experts.{e}.{w}.weight").T
                    for e in range(first, first + count)])
            if config.num_shared_experts and config.shared_here:
                mlp["shared_experts"] = swiglu(f"{moe}.shared_experts")
        return {
            "self_attn": mixer, "mlp": mlp,
            "input_layernorm": {"scale": t(f"{pre}.input_layernorm.weight")},
            "post_attention_layernorm": {
                "scale": t(f"{pre}.post_attention_layernorm.weight")}}

    V = config.vocab_size       # a share holds a slice of the vocabulary
    model = {"embed_tokens": {"embedding": t("model.embed_tokens.weight")[:V]},
             "norm": {"scale": t("model.norm.weight")},
             **{f"layers_{i}": layer_tree(i, kind)
                for i, kind in enumerate(config.layer_types)}}
    return {"model": model,
            "lm_head": {"kernel": t("lm_head.weight").T[:, :V]}}
