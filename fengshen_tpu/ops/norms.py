"""Normalisation layers.

Reference: fengshen/models/megatron/layers/norms.py:20-63 (`get_norm` →
LayerNorm / RMSNorm / ScaleNorm, optionally apex FusedLayerNorm) and the
fused layer-norm CUDA kernel (fused_kernels/layer_norm_cuda.cpp). On TPU the
XLA compiler fuses the normalisation chain into neighbouring ops, so the
"fused kernel" is the default codegen; stats are computed in fp32 regardless
of the activation dtype (matching apex semantics).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn


class RMSNorm(nn.Module):
    """Root-mean-square norm (reference: layers/norms.py:35-53)."""

    epsilon: float = 1e-8
    dtype: Any = jnp.float32
    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jnp.reciprocal(jnp.sqrt(var + self.epsilon))
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        y = y * scale
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                              jnp.float32)
            y = y + bias
        return y.astype(orig_dtype)


class ZeroCentredRMSNorm(nn.Module):
    """`x * rsqrt(mean(x^2) + eps) * (1 + weight)` in float32; `weight`
    starts at zero (Qwen3-Next, Gemma). No reference equivalent."""

    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        weight = self.param("weight", nn.initializers.zeros,
                            (x.shape[-1],), jnp.float32)
        return (y * (1.0 + weight)).astype(x.dtype)


class LayerNorm(nn.Module):
    """Standard LN with fp32 statistics (reference: layers/norms.py:20-33)."""

    epsilon: float = 1e-5
    dtype: Any = jnp.float32
    use_bias: bool = True
    use_scale: bool = True

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jnp.reciprocal(jnp.sqrt(var + self.epsilon))
        if self.use_scale:
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                               jnp.float32)
            y = y * scale
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                              jnp.float32)
            y = y + bias
        return y.astype(orig_dtype)


class ScaleNorm(nn.Module):
    """L2 scale norm (reference: layers/norms.py:55-63)."""

    epsilon: float = 1e-8
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x32 = x.astype(jnp.float32)
        norm = jnp.linalg.norm(x32, axis=-1, keepdims=True)
        g = self.param("scale", nn.initializers.ones, (1,), jnp.float32)
        y = x32 / jnp.maximum(norm, self.epsilon) * g
        return y.astype(orig_dtype)


def get_norm(norm_type: str, epsilon: Optional[float] = None,
             dtype: Any = jnp.float32) -> nn.Module:
    """Dispatch by name (reference: layers/norms.py:20-34 `get_norm(config)`)."""
    norm_type = norm_type.lower()
    if norm_type in ("layernorm", "layer_norm", "ln"):
        return LayerNorm(epsilon=epsilon or 1e-5, dtype=dtype)
    if norm_type in ("rmsnorm", "rms_norm"):
        return RMSNorm(epsilon=epsilon or 1e-8, dtype=dtype)
    if norm_type in ("scalenorm", "scale_norm"):
        return ScaleNorm(epsilon=epsilon or 1e-8, dtype=dtype)
    raise ValueError(f"unknown norm type {norm_type!r}")
