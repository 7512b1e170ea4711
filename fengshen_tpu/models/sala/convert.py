"""HF (`minicpm_sala` `state_dict`) -> flax params.

The key table: torch Linear stores [out, in] and flax Dense kernels are
[in, out], so every `.weight` of a projection is transposed; a norm's
`weight` is its `scale`. ASSUMED names (no network here to read the
published checkpoint's index): MiniCPM4's `self_attn.{q,k,v,o}_proj`,
`q_norm` / `k_norm`, the output gate as `self_attn.z_proj`, the linear
layers' output norm as `self_attn.o_norm`. A checkpoint that names one
otherwise needs its row of `_NAMES` changed, nothing else.
"""

from __future__ import annotations

from typing import Any, Mapping

from fengshen_tpu.models.sala.configuration_sala import LINEAR, SalaConfig
from fengshen_tpu.utils.convert_common import tensor as _tensor

_PROJS = ("q_proj", "k_proj", "v_proj", "o_proj", "z_proj")
_NORMS = ("q_norm", "k_norm")
_SWIGLU = ("gate_proj", "up_proj", "down_proj")
#: flax name -> published name, where they differ
_NAMES: dict = {}


def torch_to_params(state_dict: Mapping[str, Any],
                    config: SalaConfig) -> dict:
    def t(name):
        return _tensor(state_dict, name)

    def layer_tree(i: int) -> dict:
        pre = f"model.layers.{i}"
        norms = _NORMS + (("o_norm",) if config.mixer_types[i] == LINEAR
                          else ())
        attn = {p: {"kernel": t(
            f"{pre}.self_attn.{_NAMES.get(p, p)}.weight").T} for p in _PROJS}
        for n in norms:
            attn[n] = {"scale": t(
                f"{pre}.self_attn.{_NAMES.get(n, n)}.weight")}
        return {
            "self_attn": attn,
            "mlp": {p: {"kernel": t(f"{pre}.mlp.{p}.weight").T}
                    for p in _SWIGLU},
            "input_layernorm": {"scale": t(f"{pre}.input_layernorm.weight")},
            "post_attention_layernorm": {
                "scale": t(f"{pre}.post_attention_layernorm.weight")}}

    model = {"embed_tokens": {"embedding": t("model.embed_tokens.weight")},
             "norm": {"scale": t("model.norm.weight")},
             **{f"layers_{i}": layer_tree(i)
                for i in range(config.num_hidden_layers)}}
    return {"model": model, "lm_head": {"kernel": t("lm_head.weight").T}}
