"""JoyAI-LLM-Flash config. Field names are the keys of the published
`config.json` (`model_type` `joyai_llm_flash`, the DeepSeek-V3 keys), so
configs interoperate; the TPU knobs are additive, as in `LlamaConfig`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import warnings
from typing import Any, Optional, Tuple


@dataclasses.dataclass
class JoyAIConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168          # the leading dense layers
    moe_intermediate_size: int = 768       # one expert
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    num_nextn_predict_layers: int = 1
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rope_scaling: Optional[dict] = None
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    bos_token_id: int = 0
    eos_token_id: int = 1
    pad_token_id: int = 0
    # TPU-native knobs
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: (first, count): the routed experts this chip holds of every
    #: expert layer (docs/sharding.md); None = all of them
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)
        if self.rope_scaling is not None:
            raise ValueError("rope_scaling (yarn, mscale) is not built; "
                             "the published config has null")
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError("group-limited routing is not built; the "
                             "published config has n_group = topk_group "
                             "= 1, where it is the identity")
        if self.first_k_dense_replace != 1 or self.moe_layer_freq != 1:
            raise ValueError("one leading dense layer, then an expert "
                             "layer each: first_k_dense_replace = "
                             "moe_layer_freq = 1")
        if self.num_hidden_layers < 2:
            raise ValueError("needs the dense layer and an expert layer")
        if self.tie_word_embeddings:
            raise ValueError("the head is untied")
        if self.num_nextn_predict_layers:
            # the default filter shows it once per loading call site
            warnings.warn(
                "[fengshen-tpu] joyai: num_nextn_predict_layers="
                f"{self.num_nextn_predict_layers} is read and NOT built: "
                "the multi-token-prediction module adds nothing to the "
                "main model's logits (ROADMAP M7)", stacklevel=3)

    @property
    def latent_width(self) -> int:
        """Values of one cache row: the normed latent, the rotated
        shared key, and zeros up to a multiple of 128. The TPU's
        default layout of a row that is not whole lanes wide (512 + 64
        = 576) puts the tokens minor-most, and a program that scatters
        and gathers rows then copies the whole pool into a row-major
        layout and back, twice a tick (compiled for a described v5e:
        PERF.md, PR 26); 640 keeps rows contiguous."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @classmethod
    def from_pretrained(cls, path: str) -> "JoyAIConfig":
        cfg_file = os.path.join(path, "config.json") if os.path.isdir(path) \
            else path
        with open(cfg_file) as f:
            raw = json.load(f)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in raw.items() if k in known})

    def save_pretrained(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(dataclasses.asdict(self) |
                      {"model_type": "joyai_llm_flash"}, f, indent=2)

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "JoyAIConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    num_attention_heads=4, q_lora_rank=48,
                    kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=128,
                    num_nextn_predict_layers=0)
        base.update(overrides)
        return cls(**base)
