"""HF (`sdar_moe` `state_dict`) -> flax params.

The key table: torch Linear stores [out, in] and flax Dense kernels are
[in, out], so every `.weight` of a projection is transposed; an
RMSNorm's `weight` is its `scale`; the per-expert modules of a layer
become three stacked `[E, ...]` tables; the
router's `gate.weight` `[E, H]` is `router/kernel` `[H, E]` in float32.

ASSUMED names (no network here to read the published checkpoint's
index): the Qwen3-MoE layout the config's keys follow letter for letter
— `model.embed_tokens`, `model.layers.N.self_attn.{q,k,v,o}_proj`,
`self_attn.{q,k}_norm`, `mlp.gate`, `mlp.experts.N.{gate,up,down}_proj`,
`input_layernorm`, `post_attention_layernorm`, `model.norm`, `lm_head`.
The mask token is a row of `embed_tokens` like any other.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from fengshen_tpu.models.sdar.configuration_sdar import SdarConfig
from fengshen_tpu.utils.convert_common import tensor as _tensor

_SWIGLU = ("gate_proj", "up_proj", "down_proj")


def torch_to_params(state_dict: Mapping[str, Any],
                    config: SdarConfig) -> dict:
    def t(name):
        return _tensor(state_dict, name)

    def layer_tree(i: int) -> dict:
        pre = f"model.layers.{i}"
        a, m = f"{pre}.self_attn", f"{pre}.mlp"
        attn = {p: {"kernel": t(f"{a}.{p}.weight").T}
                for p in ("q_proj", "k_proj", "v_proj", "o_proj")}
        attn["q_norm"] = {"scale": t(f"{a}.q_norm.weight")}
        attn["k_norm"] = {"scale": t(f"{a}.k_norm.weight")}
        mlp = {"router": {"kernel": t(f"{m}.gate.weight").T
                          .astype(np.float32)}}
        for p in _SWIGLU:
            mlp["experts_" + p[:-5]] = np.stack([
                t(f"{m}.experts.{e}.{p}.weight").T
                for e in range(config.num_experts)])
        return {"self_attn": attn, "mlp": mlp,
                "input_layernorm": {
                    "scale": t(f"{pre}.input_layernorm.weight")},
                "post_attention_layernorm": {
                    "scale": t(f"{pre}.post_attention_layernorm.weight")}}

    model = {"embed_tokens": {"embedding": t("model.embed_tokens.weight")},
             "norm": {"scale": t("model.norm.weight")},
             **{f"layers_{i}": layer_tree(i)
                for i in range(config.num_hidden_layers)}}
    return {"model": model, "lm_head": {"kernel": t("lm_head.weight").T}}
