"""HF (`DeepseekV3ForCausalLM`-shaped `state_dict`, as
jdopensource/JoyAI-LLM-Flash publishes it) -> flax params.

The key table: torch Linear stores [out, in] and flax Dense kernels are
[in, out], so every `.weight` of a projection is transposed; a norm's
`weight` is its `scale`; the 256 per-expert modules of a layer become
three stacked `[E, ...]` tables; the router's `gate.weight` `[E, H]` is
`router/kernel` `[H, E]` in float32, its `e_score_correction_bias`
keeps its name. Keys under `model.layers.<num_hidden_layers>.` (the
multi-token-prediction module) are not read: this program does not
build it (ROADMAP M7).
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from fengshen_tpu.models.joyai.configuration_joyai import JoyAIConfig
from fengshen_tpu.utils.convert_common import tensor as _tensor

_ATTN_PROJS = ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj",
               "o_proj")
_ATTN_NORMS = ("q_a_layernorm", "kv_a_layernorm")
_SWIGLU = ("gate_proj", "up_proj", "down_proj")


def torch_to_params(state_dict: Mapping[str, Any],
                    config: JoyAIConfig) -> dict:
    def t(name):
        return _tensor(state_dict, name)

    def swiglu(prefix):
        return {proj: {"kernel": t(f"{prefix}.{proj}.weight").T}
                for proj in _SWIGLU}

    def layer_tree(i: int) -> dict:
        pre = f"model.layers.{i}"
        attn = {proj: {"kernel": t(f"{pre}.self_attn.{proj}.weight").T}
                for proj in _ATTN_PROJS}
        for norm in _ATTN_NORMS:
            attn[norm] = {"scale": t(f"{pre}.self_attn.{norm}.weight")}
        if i < config.first_k_dense_replace:
            mlp = swiglu(f"{pre}.mlp")
        else:
            first, count = config.experts_held or (
                0, config.n_routed_experts)
            mlp = {"router": {"kernel": t(f"{pre}.mlp.gate.weight").T
                              .astype(np.float32)},
                   "e_score_correction_bias": t(
                       f"{pre}.mlp.gate.e_score_correction_bias")}
            for proj in _SWIGLU:
                mlp["experts_" + proj[:-5]] = np.stack([
                    t(f"{pre}.mlp.experts.{e}.{proj}.weight").T
                    for e in range(first, first + count)])
            if config.n_shared_experts:
                mlp["shared_experts"] = swiglu(f"{pre}.mlp.shared_experts")
        return {
            "self_attn": attn, "mlp": mlp,
            "input_layernorm": {"scale": t(f"{pre}.input_layernorm.weight")},
            "post_attention_layernorm": {
                "scale": t(f"{pre}.post_attention_layernorm.weight")}}

    L = config.num_hidden_layers
    model = {"embed_tokens": {"embedding": t("model.embed_tokens.weight")},
             "norm": {"scale": t("model.norm.weight")},
             **{f"layers_{i}": layer_tree(i) for i in range(L)}}
    return {"model": model, "lm_head": {"kernel": t("lm_head.weight").T}}
