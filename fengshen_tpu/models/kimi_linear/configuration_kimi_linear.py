"""Kimi-Linear config. Field names are the keys of the published
`config.json` (`model_type` `kimi_linear`), `linear_attn_config` among
them as the nested group it is, so configs interoperate; the TPU knobs
are additive, as in `LlamaConfig`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional, Tuple

KDA, FULL = "kda", "full_attention"


def _published_linear_attn() -> dict:
    full = [4, 8, 12, 16, 20, 24, 27]
    return {"full_attn_layers": full, "head_dim": 128,
            "kda_layers": [i for i in range(1, 28) if i not in full],
            "num_heads": 32, "short_conv_kernel_size": 4}


@dataclasses.dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216          # the leading dense layer
    moe_intermediate_size: int = 1024      # one expert
    num_hidden_layers: int = 27
    #: which layers (counted from 1) are Kimi Delta Attention and which
    #: latent attention; the KDA heads, their size and the convolution
    linear_attn_config: dict = dataclasses.field(
        default_factory=_published_linear_attn)
    # the latent-attention layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 32          # read; the cache is latent
    head_dim: int = 72                     # read; hidden / heads
    q_lora_rank: Optional[int] = None      # null: a full-rank query
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64             # the shared key part: NOT rotated
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_theta: float = 10000.0            # read; nothing rotates
    rope_scaling: Optional[dict] = None
    # the experts, in every layer after `first_k_dense_replace`
    #: the router's outputs (the PUBLISHED count, whatever is held here)
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    num_expert_group: int = 1
    topk_group: int = 1
    use_grouped_topk: bool = True
    num_nextn_predict_layers: int = 0
    model_max_length: int = 1048576
    #: positions a cache holds (the engine sizes masks, history and a
    #: prefill's batch-1 cache by it); the model has no positions, so
    #: no mathematics reads it
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    bos_token_id: int = 163584
    eos_token_id: int = 163585
    pad_token_id: int = 0
    # TPU-native knobs
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: (first, count): the routed experts this chip holds of every
    #: expert layer (docs/sharding.md); None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    #: whether this share adds the shared expert (one share of a layer)
    shared_here: bool = True
    #: tokens a chunk of the delta rule's prefill form
    delta_chunk: int = 64

    def __post_init__(self):
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)
        lin = self.linear_attn_config
        kinds = sorted(list(lin["kda_layers"]) + list(lin["full_attn_layers"]))
        if kinds != list(range(1, self.num_hidden_layers + 1)):
            raise ValueError(
                "linear_attn_config's kda_layers and full_attn_layers, "
                "counted from 1, name every layer once: got "
                f"{kinds} for {self.num_hidden_layers} layers")
        if not lin["kda_layers"] or not lin["full_attn_layers"]:
            raise ValueError("needs a layer of each kind (the cache is the "
                             "latent rows of one beside the states of the "
                             "other)")
        if self.q_lora_rank is not None or not self.mla_use_nope:
            raise ValueError("the latent layers have a full-rank query "
                             "(q_lora_rank null) and no positions "
                             "(mla_use_nope true), as published")
        if self.rope_scaling is not None:
            raise ValueError("rope_scaling is not built; the published "
                             "config has null and nothing rotates")
        if (self.num_expert_group, self.topk_group) != (1, 1):
            raise ValueError("group-limited routing is not built; the "
                             "published config has num_expert_group = "
                             "topk_group = 1, where it is the identity")
        if self.first_k_dense_replace != 1 or self.moe_layer_freq != 1:
            raise ValueError("one leading dense layer, then an expert "
                             "layer each: first_k_dense_replace = "
                             "moe_layer_freq = 1")
        if self.moe_router_activation_func != "sigmoid":
            raise ValueError("the router scores by sigmoid")
        if self.tie_word_embeddings or self.hidden_act != "silu":
            raise ValueError("an untied head, SwiGLU")
        if self.num_nextn_predict_layers:
            raise ValueError("no multi-token-prediction module is built; "
                             "the published config has 0")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """`kda` or `full_attention` a layer, counted from 0 here."""
        kda = set(self.linear_attn_config["kda_layers"])
        return tuple(KDA if i + 1 in kda else FULL
                     for i in range(self.num_hidden_layers))

    @property
    def kda_heads(self) -> int:
        return self.linear_attn_config["num_heads"]

    @property
    def kda_head_dim(self) -> int:
        """Key and value size of a KDA head, and (ASSUMED: the config
        has no key) the rank of its two low-rank gates."""
        return self.linear_attn_config["head_dim"]

    @property
    def conv_kernel(self) -> int:
        return self.linear_attn_config["short_conv_kernel_size"]

    @property
    def kda_dim(self) -> int:
        """Channels of q, of k and of v: heads x head size."""
        return self.kda_heads * self.kda_head_dim

    @property
    def latent_width(self) -> int:
        """Values of one cached latent row: the normed latent, the
        shared key part, and zeros up to a multiple of 128
        (`JoyAIConfig.latent_width` says why: 576 -> 640)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @classmethod
    def from_pretrained(cls, path: str) -> "KimiLinearConfig":
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
                      {"model_type": "kimi_linear"}, f, indent=2)

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "KimiLinearConfig":
        """One dense layer and a period: kda, kda, kda, full, kda."""
        base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                    moe_intermediate_size=16, num_hidden_layers=5,
                    linear_attn_config={
                        "full_attn_layers": [4], "head_dim": 16,
                        "kda_layers": [1, 2, 3, 5], "num_heads": 4,
                        "short_conv_kernel_size": 4},
                    num_attention_heads=4, num_key_value_heads=4, head_dim=8,
                    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, num_experts=8, num_experts_per_token=2,
                    max_position_embeddings=128, delta_chunk=16,
                    dtype="float32")
        base.update(overrides)
        return cls(**base)
