"""MiniCPM-SALA config. Field names are the keys of the published
`config.json` (`model_type` `minicpm_sala`), so configs interoperate;
the selection's sizes, which that file does not carry, are MiniCPM4's
`sparse_config` keys; the TPU knobs are additive, as in `LlamaConfig`.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Optional, Tuple

from fengshen_tpu.ops.sparse_attention import SparseSpec

SPARSE, LINEAR = "minicpm4", "lightning-attn"


@dataclasses.dataclass
class SalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    #: each layer's mixer, `minicpm4` or `lightning-attn`
    mixer_types: Tuple[str, ...] = ()
    # the `minicpm4` layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attn_use_rope: bool = False
    attn_use_output_gate: bool = True
    # the `lightning-attn` layers
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_scale: str = "1/sqrt(d)"
    lightning_use_rope: bool = True
    use_output_norm: bool = True
    use_output_gate: bool = True
    qk_norm: bool = True
    # MiniCPM's scalings
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    mup_denominator: int = 32       # read; enters no forward equation
    #: the depth `scale_depth / sqrt(.)` is taken over: the PUBLISHED
    #: depth where `num_hidden_layers` holds a cut of it; None = that
    residual_depth: Optional[int] = None
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    hidden_act: str = "silu"
    attention_bias: bool = False
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    bos_token_id: int = 1
    eos_token_id: int = 2
    pad_token_id: int = 0
    # the selection (MiniCPM4 `sparse_config`)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    # TPU-native knobs
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: tokens a chunk of the linear layers' prefill form
    lightning_chunk: int = 256

    def __post_init__(self):
        self.mixer_types = tuple(self.mixer_types)
        if len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError(
                f"mixer_types names {len(self.mixer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.mixer_types) - {SPARSE, LINEAR}
        if unknown:
            raise ValueError(f"unknown mixer types {sorted(unknown)}")
        if SPARSE not in self.mixer_types or LINEAR not in self.mixer_types:
            raise ValueError("needs a layer of each kind: the cache is the "
                             "rows of the one beside the state of the other")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("the linear layers have a key head a query "
                             "head (lightning_nkv = lightning_nh)")
        if self.lightning_scale != "1/sqrt(d)":
            raise ValueError("lightning_scale is '1/sqrt(d)'")
        if self.attn_use_rope or not self.lightning_use_rope:
            raise ValueError("the sparse layers take no positions and the "
                             "linear layers rotate all of a head")
        if not (self.qk_norm and self.use_output_norm and
                self.use_output_gate and self.attn_use_output_gate):
            raise ValueError("qk_norm, the output norm and both output "
                             "gates are part of the published layers")
        if self.attention_bias or self.tie_word_embeddings or \
                self.hidden_act != "silu":
            raise ValueError("no biases, an untied head, SwiGLU")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over the KV heads")
        self.sparse             # the selection's own checks

    @property
    def sparse(self) -> SparseSpec:
        return SparseSpec(self.kernel_size, self.kernel_stride,
                          self.block_size, self.topk, self.init_blocks,
                          self.window_size, self.dense_len)

    @property
    def residual_scale(self) -> float:
        """`scale_depth / sqrt(depth)`: what each branch is scaled by."""
        return self.scale_depth / math.sqrt(
            self.residual_depth or self.num_hidden_layers)

    @classmethod
    def from_pretrained(cls, path: str) -> "SalaConfig":
        cfg_file = os.path.join(path, "config.json") if os.path.isdir(path) \
            else path
        with open(cfg_file) as f:
            raw = json.load(f)
        raw.update(raw.pop("sparse_config", None) or {})
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in raw.items() if k in known})

    def save_pretrained(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(dataclasses.asdict(self) |
                      {"model_type": "minicpm_sala"}, f, indent=2)

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "SalaConfig":
        base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=4,
                    mixer_types=(SPARSE, LINEAR, LINEAR, LINEAR),
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, lightning_nh=4, lightning_nkv=4,
                    lightning_head_dim=16, dim_model_base=16,
                    max_position_embeddings=256, kernel_size=8,
                    kernel_stride=4, block_size=16, topk=6, init_blocks=1,
                    window_size=32, dense_len=96, lightning_chunk=8,
                    dtype="float32")
        base.update(overrides)
        return cls(**base)
