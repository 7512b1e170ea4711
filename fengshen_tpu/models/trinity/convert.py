"""HF (`afmoe` `state_dict`) -> flax params.

The key table: torch Linear stores [out, in] and flax Dense kernels are
[in, out], so every `.weight` of a projection is transposed; an
RMSNorm's `weight` is its `scale`; the per-expert modules of a layer
become three stacked `[E_held, ...]` tables (`experts_held`); the
router's `[E, H]` weight is `router/kernel` `[H, E]` in float32 and its
balancing bias `e_score_correction_bias`.

ASSUMED names (no network here to read the published checkpoint's
index), the `transformers` `afmoe` module tree: `self_attn.{q,k,v,o}_proj`,
`self_attn.gate_proj` (the output gate), `self_attn.{q,k}_norm`, the four
norms `input_layernorm`, `post_attention_layernorm`, `pre_mlp_layernorm`,
`post_mlp_layernorm`; a dense layer's `mlp.{gate,up,down}_proj`; an
expert layer's `mlp.router.gate.weight`, `mlp.expert_bias`,
`mlp.experts.N.{gate,up,down}_proj`, `mlp.shared_experts.{gate,up,down}_proj`.
A checkpoint that names one otherwise needs its row here changed,
nothing else.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from fengshen_tpu.models.trinity.configuration_trinity import TrinityConfig
from fengshen_tpu.utils.convert_common import tensor as _tensor

_SWIGLU = ("gate_proj", "up_proj", "down_proj")
_NORMS = ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
          "post_mlp_layernorm")


def torch_to_params(state_dict: Mapping[str, Any],
                    config: TrinityConfig) -> dict:
    def t(name):
        return _tensor(state_dict, name)

    first, count = config.experts_held or (0, config.num_experts)

    def proj(name):
        return {"kernel": t(name + ".weight").T}

    def layer_tree(i: int) -> dict:
        pre = f"model.layers.{i}"
        a, m = f"{pre}.self_attn", f"{pre}.mlp"
        attn = {p: proj(f"{a}.{p}") for p in
                ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj")}
        attn["q_norm"] = {"scale": t(f"{a}.q_norm.weight")}
        attn["k_norm"] = {"scale": t(f"{a}.k_norm.weight")}
        if i < config.num_dense_layers:
            mlp = {p: proj(f"{m}.{p}") for p in _SWIGLU}
        else:
            mlp = {"router": {"kernel": t(f"{m}.router.gate.weight").T
                              .astype(np.float32)},
                   "e_score_correction_bias":
                       t(f"{m}.expert_bias").astype(np.float32)}
            for p in _SWIGLU:
                mlp["experts_" + p[:-5]] = np.stack([
                    t(f"{m}.experts.{e}.{p}.weight").T
                    for e in range(first, first + count)])
            if config.num_shared_experts and config.shared_here:
                mlp["shared_experts"] = {
                    p: proj(f"{m}.shared_experts.{p}") for p in _SWIGLU}
        return {"self_attn": attn, "mlp": mlp,
                **{n: {"scale": t(f"{pre}.{n}.weight")} for n in _NORMS}}

    model = {"embed_tokens": {"embedding": t("model.embed_tokens.weight")},
             "norm": {"scale": t("model.norm.weight")},
             **{f"layers_{i}": layer_tree(i)
                for i in range(config.num_hidden_layers)}}
    return {"model": model, "lm_head": {"kernel": t("lm_head.weight").T}}
