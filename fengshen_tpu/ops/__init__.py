"""Compute ops: XLA-first kernels with Pallas for the hot paths.

TPU-native replacement for the reference's native-kernel tier
(reference: fengshen/models/megatron/fused_kernels/ CUDA softmax/layernorm,
fengshen/models/megatron/layers/flash_attention.py, and the DeepSpeed sparse
attention configs in layers/utils.py:187-289). XLA already fuses
scale+mask+softmax and layernorm chains; Pallas kernels cover flash/splash
attention and block-sparse layouts.
"""

from fengshen_tpu.ops.norms import RMSNorm, LayerNorm, ScaleNorm, get_norm
from fengshen_tpu.ops.activations import get_activation
from fengshen_tpu.ops.rotary import rotary_cos_sin, apply_rotary_pos_emb
from fengshen_tpu.ops.alibi import alibi_slopes, alibi_bias
from fengshen_tpu.ops.masks import (
    causal_mask,
    sliding_window_mask,
    bigbird_mask,
    bigbird_block_layout,
    longformer_mask,
    longformer_block_layout,
    fixed_sparsity_mask,
    fixed_block_layout,
    make_attention_bias,
)
from fengshen_tpu.ops.attention import dot_product_attention
from fengshen_tpu.ops.ulysses_attention import (
    ulysses_attention_sharded, sequence_parallel_attention)
from fengshen_tpu.ops.init_functions import get_init_methods
from fengshen_tpu.ops.moe import (RoutedExperts,
                                  load_balancing_loss,
                                  MOE_PARTITION_RULES)
from fengshen_tpu.ops.gmlp import GMLPBlock, SpatialGatingUnit, TinyAttention
from fengshen_tpu.ops.soft_embedding import SoftEmbedding

__all__ = [
    "RMSNorm", "LayerNorm", "ScaleNorm", "get_norm",
    "get_activation",
    "rotary_cos_sin", "apply_rotary_pos_emb",
    "alibi_slopes", "alibi_bias",
    "causal_mask", "sliding_window_mask", "bigbird_mask", "longformer_mask",
    "fixed_sparsity_mask",
    "bigbird_block_layout", "longformer_block_layout", "fixed_block_layout",
    "make_attention_bias",
    "dot_product_attention",
    "ulysses_attention_sharded", "sequence_parallel_attention",
    "get_init_methods",
    "RoutedExperts", "load_balancing_loss", "MOE_PARTITION_RULES",
    "GMLPBlock", "SpatialGatingUnit", "TinyAttention",
    "SoftEmbedding",
]
