"""JoyAI-LLM-Flash (jdopensource, `model_type` `joyai_llm_flash`): the
DeepSeek-V3 layer — multi-head latent attention over a one-row-a-token
cache, one leading dense layer, then sigmoid-routed experts with a
shared expert (no reference equivalent)."""

from fengshen_tpu.models.joyai.configuration_joyai import JoyAIConfig
from fengshen_tpu.models.joyai.modeling_joyai import (JoyAIForCausalLM,
                                                      JoyAIModel)

__all__ = ["JoyAIConfig", "JoyAIModel", "JoyAIForCausalLM"]
