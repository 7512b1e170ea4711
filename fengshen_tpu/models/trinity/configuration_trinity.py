"""Trinity (arcee-ai, `model_type` `afmoe`) config. Field names are the
keys of the published `config.json`, so configs interoperate; the TPU
knobs are additive, as in `LlamaConfig`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional, Tuple

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class TrinityConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288      # the leading dense layers' SwiGLU
    num_hidden_layers: int = 60
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    #: a `sliding_attention` layer's query reads the last
    #: `sliding_window` keys, its own among them, and carries rotary
    #: positions; a `full_attention` layer reads every key and has NO
    #: positions. Every `global_attn_every_n_layers`-th layer is full
    #: unless `layer_types` says otherwise
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    # the experts: layers `num_dense_layers ...`
    num_dense_layers: int = 6
    moe_intermediate_size: int = 3072
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    n_group: int = 1
    topk_group: int = 1
    num_expert_groups: int = 1          # read: repeats n_group
    num_limited_groups: int = 1         # read: repeats topk_group
    load_balance_coeff: float = 5e-05   # read: training only
    use_grouped_mm: bool = True         # read: the published kernel's choice
    #: the embedding's output times `sqrt(hidden_size)`
    mup_enabled: bool = True
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    pad_token_id: int = 0
    # TPU-native knobs
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    #: (first, count): the routed experts this chip holds of every
    #: expert layer (docs/sharding.md); None = all of them
    experts_held: Optional[Tuple[int, int]] = None
    #: whether this share adds the shared expert (one share does)
    shared_here: bool = True

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(
                FULL if (i + 1) % n == 0 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if self.experts_held is not None:
            self.experts_held = tuple(self.experts_held)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types names {self.num_hidden_layers} layers, each "
                f"{SLIDING!r} or {FULL!r}")
        if self.rope_scaling:
            raise ValueError("rope_scaling is not built; the published "
                             "config has none")
        if self.score_func != "sigmoid":
            raise ValueError("the router is the sigmoid one")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("group-limited routing is not built; the "
                             "published config has one group")
        if self.tie_word_embeddings or self.hidden_act != "silu":
            raise ValueError("an untied head, SwiGLU")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over the KV heads")
        if self.head_dim % 2:
            raise ValueError("rotary turns pairs: the head size is even")
        if self.sliding_window < 1:
            raise ValueError("sliding_window counts the query's own key: "
                             "at least 1")

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The layers of `kind`, in order."""
        return tuple(i for i, t in enumerate(self.layer_types) if t == kind)

    @classmethod
    def from_pretrained(cls, path: str) -> "TrinityConfig":
        cfg_file = os.path.join(path, "config.json") if os.path.isdir(path) \
            else path
        with open(cfg_file) as f:
            raw = json.load(f)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in raw.items() if k in known})

    def save_pretrained(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(dataclasses.asdict(self) | {"model_type": "afmoe"}, f,
                      indent=2)

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "TrinityConfig":
        base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=5, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, sliding_window=8,
                    layer_types=(SLIDING, SLIDING, FULL, SLIDING, SLIDING),
                    num_dense_layers=1, moe_intermediate_size=16,
                    num_experts=8, num_experts_per_tok=2,
                    max_position_embeddings=64, dtype="float32")
        base.update(overrides)
        return cls(**base)
