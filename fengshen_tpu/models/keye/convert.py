"""HF (`KeyeVL2` `state_dict`, the language model's keys) -> flax params.

The key table: torch Linear stores [out, in] and flax Dense kernels are
[in, out], so every `.weight` of a projection is transposed; an
RMSNorm's `weight` is its `scale`; the per-expert modules of a layer
become three stacked `[E_held, ...]` tables (`experts_held`); the
router's `gate.weight` `[E, H]` is `router/kernel` `[H, E]` in float32.

ASSUMED names (no network here to read the published checkpoint's
index): the Qwen3-MoE layout the config's keys follow —
`self_attn.{q,k,v,o}_proj`, `self_attn.{q,k}_norm`, `mlp.gate`,
`mlp.experts.N.{gate,up,down}_proj` — and, for the indexer,
DeepSeek-V3.2's names under `self_attn.indexer`: `wq` (here `q_proj`),
`wk` (`k_proj`), `k_norm` (LayerNorm `weight` and `bias`) and
`weights_proj`. The language model's keys may sit under `model.` or
under `model.language_model.`: whichever holds `embed_tokens` is read.
A checkpoint that names one otherwise needs its row of `_INDEXER`
changed, nothing else. The vision tower's keys are not read: this
program does not build it.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from fengshen_tpu.models.keye.configuration_keye import KeyeConfig
from fengshen_tpu.utils.convert_common import tensor as _tensor

_SWIGLU = ("gate_proj", "up_proj", "down_proj")
#: flax name -> assumed published name of the indexer's projections
_INDEXER = {"q_proj": "wq", "k_proj": "wk", "weights_proj": "weights_proj"}


def torch_to_params(state_dict: Mapping[str, Any],
                    config: KeyeConfig) -> dict:
    def t(name):
        return _tensor(state_dict, name)

    root = "model.language_model" if \
        "model.language_model.embed_tokens.weight" in state_dict else "model"
    first, count = config.experts_held or (0, config.num_experts)

    def proj(name):
        return {"kernel": t(name + ".weight").T}

    def layer_tree(i: int) -> dict:
        pre = f"{root}.layers.{i}"
        a, m = f"{pre}.self_attn", f"{pre}.mlp"
        attn = {p: proj(f"{a}.{p}")
                for p in ("q_proj", "k_proj", "v_proj", "o_proj")}
        attn["q_norm"] = {"scale": t(f"{a}.q_norm.weight")}
        attn["k_norm"] = {"scale": t(f"{a}.k_norm.weight")}
        attn["indexer"] = {
            **{ours: proj(f"{a}.indexer.{theirs}")
               for ours, theirs in _INDEXER.items()},
            "k_norm": {"scale": t(f"{a}.indexer.k_norm.weight"),
                       "bias": t(f"{a}.indexer.k_norm.bias")}}
        mlp = {"router": {"kernel": t(f"{m}.gate.weight").T
                          .astype(np.float32)}}
        for p in _SWIGLU:
            mlp["experts_" + p[:-5]] = np.stack([
                t(f"{m}.experts.{e}.{p}.weight").T
                for e in range(first, first + count)])
        return {"self_attn": attn, "mlp": mlp,
                "input_layernorm": {
                    "scale": t(f"{pre}.input_layernorm.weight")},
                "post_attention_layernorm": {
                    "scale": t(f"{pre}.post_attention_layernorm.weight")}}

    model = {"embed_tokens": {"embedding": t(f"{root}.embed_tokens.weight")},
             "norm": {"scale": t(f"{root}.norm.weight")},
             **{f"layers_{i}": layer_tree(i)
                for i in range(config.num_hidden_layers)}}
    return {"model": model, "lm_head": {"kernel": t("lm_head.weight").T}}
