"""Qwen3-Next (Qwen, `model_type` `qwen3_next`): a decoder whose layers
alternate three Gated DeltaNet mixers (a gated delta rule behind a
short convolution) with one gated softmax attention (2 KV heads of 256,
partial rotary, an output gate taken from the q projection), softmax-
routed experts with a sigmoid-gated shared expert in every layer, and
zero-centred RMSNorms (no reference equivalent)."""

from fengshen_tpu.models.qwen3_next.configuration_qwen3_next import (
    Qwen3NextConfig)
from fengshen_tpu.models.qwen3_next.modeling_qwen3_next import (
    Qwen3NextForCausalLM, Qwen3NextModel, expert_share)

__all__ = ["Qwen3NextConfig", "Qwen3NextModel", "Qwen3NextForCausalLM",
           "expert_share"]
