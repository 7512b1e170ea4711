"""Trinity (arcee-ai, `model_type` `afmoe`): a decoder that mixes
`sliding_attention` layers (rotary positions, a query reads the last
`sliding_window` keys) with `full_attention` layers WITHOUT positions,
three to one; gated grouped-query attention with a per-head RMSNorm on
q and k, four norms a layer (sandwich), leading dense SwiGLU layers and
then sigmoid-routed experts with one shared expert (no reference
equivalent). The serving pool keeps a window layer's rows as a ring
(`serving/paged_cache.py`)."""

from fengshen_tpu.models.trinity.configuration_trinity import TrinityConfig
from fengshen_tpu.models.trinity.modeling_trinity import (TrinityForCausalLM,
                                                          TrinityModel)

__all__ = ["TrinityConfig", "TrinityModel", "TrinityForCausalLM"]
