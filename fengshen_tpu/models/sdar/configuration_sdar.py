"""SDAR config. Field names are the keys of the published `config.json`
(`model_type` `sdar_moe`: the Qwen3-MoE keys letter for letter), so
configs interoperate; `block_length` and `mask_token_id` are the
generation procedure's (the published file gives neither: the
repository's `generate.py` does) and the TPU knobs are additive, as in
`LlamaConfig`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional, Tuple


@dataclasses.dataclass
class SdarConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144   # read; no layer is dense
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None
    sliding_window: Optional[int] = None
    use_sliding_window: bool = False
    max_window_layers: int = 48     # read; no window is used
    # the experts, in every layer
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    attention_bias: bool = False
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    bos_token_id: int = 151643
    eos_token_id: int = 151645
    pad_token_id: int = 0
    #: positions a generation block holds: a query reads every earlier
    #: block and the WHOLE of its own. 1 is causal attention (Qwen3-MoE)
    block_length: int = 4
    #: the token a position not yet revealed is fed as
    mask_token_id: int = 151669
    # TPU-native knobs
    dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self):
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        scaling = self.rope_scaling or {}
        if scaling.get("rope_type", scaling.get("type", "default")) != \
                "default":
            raise ValueError("rope_scaling other than the default type is "
                             "not built; the published config has none")
        if self.use_sliding_window or self.sliding_window:
            raise ValueError("a sliding window is not built; the published "
                             "config has none")
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise ValueError("every layer has experts: decoder_sparse_step "
                             "1, mlp_only_layers []")
        if self.attention_bias or self.tie_word_embeddings or \
                self.hidden_act != "silu":
            raise ValueError("no biases, an untied head, SwiGLU")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over the KV heads")
        if self.head_dim % 2:
            raise ValueError("rotary turns pairs: the head size is even")
        if not 1 <= self.block_length <= 8:
            raise ValueError(
                f"block_length {self.block_length}: a block's queries are "
                "one read of the decode seam, which takes 1 to 8")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id is a row of the embedding")

    @classmethod
    def from_pretrained(cls, path: str) -> "SdarConfig":
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
                      {"model_type": "sdar_moe"}, f, indent=2)

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "SdarConfig":
        base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16,
                    moe_intermediate_size=16, num_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=64,
                    block_length=4, mask_token_id=63, dtype="float32")
        base.update(overrides)
        return cls(**base)
