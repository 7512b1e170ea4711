"""Qwen3-Next config. Field names are the keys of the published
`config.json` (`model_type` `qwen3_next`), so configs interoperate; the
TPU knobs are additive, as in `LlamaConfig`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional, Tuple

FULL, LINEAR = "full_attention", "linear_attention"


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120   # read; no layer is dense
    num_hidden_layers: int = 48
    # the `full_attention` layers
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: Optional[dict] = None
    full_attention_interval: int = 4
    use_sliding_window: bool = False
    # the `linear_attention` layers (Gated DeltaNet)
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # the experts, in every layer
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    #: the router's outputs (the PUBLISHED count, whatever is held here)
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    bos_token_id: int = 151643
    eos_token_id: int = 151645
    pad_token_id: int = 0
    # TPU-native knobs
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: (first, count): the routed experts this chip holds of every
    #: layer (docs/sharding.md); None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    #: whether this share adds the shared expert (one share of a layer)
    shared_here: bool = True
    #: tokens a chunk of the delta rule's prefill form
    delta_chunk: int = 64

    def __post_init__(self):
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)
        if self.rope_scaling is not None or self.use_sliding_window:
            raise ValueError("rope_scaling and a sliding window are not "
                             "built; the published config has neither")
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise ValueError("every layer has experts: decoder_sparse_step "
                             "1, mlp_only_layers []")
        if self.attention_bias or self.tie_word_embeddings or \
                self.hidden_act != "silu":
            raise ValueError("no biases, an untied head, SwiGLU")
        if self.num_hidden_layers % self.full_attention_interval:
            raise ValueError("whole periods: num_hidden_layers is a "
                             "multiple of full_attention_interval (the "
                             "cache is the rows of the full layers beside "
                             "the states of the linear ones)")
        if self.full_attention_interval < 2:
            raise ValueError("needs a layer of each kind")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("query (value) heads must divide over the KV "
                             "(key) heads")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise ValueError("the shared expert is a whole number of "
                             "expert widths")
        if self.rotary_dim % 2:
            raise ValueError("partial_rotary_factor x head_dim is even")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """`full_attention` in every `full_attention_interval`-th layer,
        `linear_attention` in the rest."""
        return tuple(FULL if (i + 1) % self.full_attention_interval == 0
                     else LINEAR for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the short convolution: `[q | k | v]`."""
        return 2 * self.key_dim + self.value_dim

    @classmethod
    def from_pretrained(cls, path: str) -> "Qwen3NextConfig":
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
                      {"model_type": "qwen3_next"}, f, indent=2)

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "Qwen3NextConfig":
        base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=8, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16,
                    linear_num_key_heads=2, linear_num_value_heads=4,
                    linear_key_head_dim=16, linear_value_head_dim=16,
                    moe_intermediate_size=16,
                    shared_expert_intermediate_size=16, num_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=128,
                    delta_chunk=16, dtype="float32")
        base.update(overrides)
        return cls(**base)
