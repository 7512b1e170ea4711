"""SDAR (JetLM, `model_type` `sdar_moe`): the Qwen3-MoE decoder block —
grouped-query attention with a per-head RMSNorm on q and k and rotary
over the whole head, softmax-routed experts top-k renormalised with no
shared expert — under a BLOCK-causal mask, generated by diffusion over
blocks of `block_length` positions (the serving engine's block tick:
docs/serving.md "Block generation"). No reference equivalent."""

from fengshen_tpu.models.sdar.configuration_sdar import SdarConfig
from fengshen_tpu.models.sdar.modeling_sdar import (SdarForCausalLM,
                                                    SdarModel)

__all__ = ["SdarConfig", "SdarModel", "SdarForCausalLM"]
