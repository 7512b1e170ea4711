"""MiniCPM-SALA (openbmb, `model_type` `minicpm_sala`): a decoder whose
layers are of two published kinds in an irregular order — InfLLM-V2
block-sparse attention (`minicpm4`) in one layer of four, Lightning
linear attention (`lightning-attn`) in the rest — with MiniCPM's
residual, embedding and logit scalings (no reference equivalent)."""

from fengshen_tpu.models.sala.configuration_sala import SalaConfig
from fengshen_tpu.models.sala.modeling_sala import (SalaForCausalLM,
                                                    SalaModel)

__all__ = ["SalaConfig", "SalaModel", "SalaForCausalLM"]
