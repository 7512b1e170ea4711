"""Keye-VL-2.0's language model (Kwai-Keye, `model_type` `KeyeVL2`): the
Qwen3-MoE decoder block — grouped-query attention with a per-head
RMSNorm on q and k and rotary over the whole head, softmax-routed
experts top-k renormalised with no shared expert — plus, in every
layer, a learned indexer (DeepSeek-Sparse-Attention's) that picks the
`topk` single cached tokens a query attends to (no reference
equivalent). The vision tower is not built."""

from fengshen_tpu.models.keye.configuration_keye import KeyeConfig
from fengshen_tpu.models.keye.modeling_keye import (KeyeForCausalLM,
                                                    KeyeModel)

__all__ = ["KeyeConfig", "KeyeModel", "KeyeForCausalLM"]
