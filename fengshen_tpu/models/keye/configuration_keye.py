"""Keye-VL-2.0 language-model config. Field names are the keys of the
published `config.json` (`model_type` `KeyeVL2`), `sa_config` as the
nested group it is there, so configs interoperate; the TPU knobs are
additive, as in `LlamaConfig`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional, Tuple


def _default_sa_config() -> dict:
    return {"indexer_head_dim": 64, "indexer_num_heads": 16,
            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
            "q_chunk_size": 512, "topk": 2048}


@dataclasses.dataclass
class KeyeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144   # read; no layer is dense
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10000000.0
    #: `mrope_section` splits a head's rotary pairs over three position
    #: axes; text gives all three the same id, which is ordinary rotary
    rope_scaling: Optional[dict] = None
    #: the indexer: `indexer_num_heads` heads of `indexer_head_dim`, one
    #: key head, `topk` tokens a query (`q_chunk_size` / `kv_chunk_size`
    #: are the published kernel's tiling: read, they change no result)
    sa_config: dict = dataclasses.field(default_factory=_default_sa_config)
    sliding_window: Optional[int] = None
    use_sliding_window: bool = False
    max_window_layers: int = 48     # read; no window is used
    # the experts, in every layer
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_local_experts: int = 128    # read: the published file repeats it
    num_experts_per_tok: int = 8
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
    #: a prefill window is compiled for cache extents a multiple of this
    #: apart (`ops/sparse_attention.index_extents`)
    index_extent_step: int = 4096
    #: queries and keys a tile of the window's selection and walk
    index_q_tile: int = 256
    index_k_tile: int = 1024

    def __post_init__(self):
        self.mlp_only_layers = tuple(self.mlp_only_layers)
        self.sa_config = dict(self.sa_config)
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)
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
        if self.sa_config.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("the indexer has one key head")
        if self.head_dim % 2 or self.index_head_dim % 2:
            raise ValueError("rotary turns pairs: head sizes are even")

    @property
    def index_heads(self) -> int:
        return int(self.sa_config["indexer_num_heads"])

    @property
    def index_head_dim(self) -> int:
        return int(self.sa_config["indexer_head_dim"])

    @property
    def index_topk(self) -> int:
        return int(self.sa_config["topk"])

    @property
    def index_scale(self) -> float:
        """`(heads * head_dim)^(-1/2)`: the head weights' `heads^(-1/2)`
        times the products' `head_dim^(-1/2)`."""
        return float(self.index_heads * self.index_head_dim) ** -0.5

    @classmethod
    def from_pretrained(cls, path: str) -> "KeyeConfig":
        cfg_file = os.path.join(path, "config.json") if os.path.isdir(path) \
            else path
        with open(cfg_file) as f:
            raw = json.load(f)
        # a multimodal file may nest the language model's keys under
        # `text_config`: read where present
        raw = {**raw, **raw.get("text_config", {})}
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in raw.items() if k in known})

    def save_pretrained(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(dataclasses.asdict(self) |
                      {"model_type": "KeyeVL2"}, f, indent=2)

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "KeyeConfig":
        base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16,
                    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
                               "indexer_num_kv_heads": 1,
                               "kv_chunk_size": 8, "q_chunk_size": 8,
                               "topk": 8},
                    moe_intermediate_size=16, num_experts=8,
                    num_local_experts=8, num_experts_per_tok=2,
                    max_position_embeddings=64, index_extent_step=16,
                    index_q_tile=4, index_k_tile=8, dtype="float32")
        base.update(overrides)
        return cls(**base)
