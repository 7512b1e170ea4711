"""Kimi-Linear (moonshotai, `model_type` `kimi_linear`): Kimi Delta
Attention layers (a delta rule gated per key CHANNEL behind a short
convolution) to latent-attention layers WITHOUT positions, three to
one; one leading dense SwiGLU layer, then sigmoid-routed experts with a
shared one (no reference equivalent). The serving pool keeps a latent
row a token for the latent layers and two constant states a lane for
the others (`serving/paged_cache.py`)."""

from fengshen_tpu.models.kimi_linear.configuration_kimi_linear import (
    KimiLinearConfig)
from fengshen_tpu.models.kimi_linear.modeling_kimi_linear import (
    KimiLinearForCausalLM, KimiLinearModel)

__all__ = ["KimiLinearConfig", "KimiLinearModel", "KimiLinearForCausalLM"]
