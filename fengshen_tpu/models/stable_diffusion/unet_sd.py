"""Diffusers-faithful UNet2DConditionModel (SD-1.x architecture).

The denoiser the released Taiyi-Stable-Diffusion-1B checkpoint ships
(reference workload: fengshen/examples/finetune_taiyi_stable_diffusion/
finetune.py:81-89 loads the diffusers pipeline; its UNet is the SD-1.x
`UNet2DConditionModel`). This flax module reproduces that architecture
exactly — 32-group GroupNorm, per-block transformer depth, GEGLU feed
forward, conv proj_in/proj_out, SD block layout — with a parameter tree
that mirrors the diffusers state-dict keys (``down_blocks.0.resnets.1``
→ path ``down_blocks_0/resnets_1``), so the importer in `convert.py` is
a mechanical key mangle and the released weights load directly. The
compact `unet.UNetConfig` tower remains as the small test config for
trainer plumbing.

Layout is NHWC (TPU-native; torch NCHW weights are transposed on
import). All matmuls/convs ride the MXU; attention over the flattened
spatial dim is plain dot-product attention, which XLA fuses — spatial
lengths (≤4096 at 512px) are far below the Pallas flash cutover.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn


from fengshen_tpu.sharding import (to_partition_rules,
                                   with_logical_constraint)

#: fsdp/tensor sharding for the SD towers (the reference trains SD under
#: DeepSpeed ZeRO; here the fsdp axis shards the big conv out-channels
#: and the transformer/ff matmuls ride the tensor axis). `_spec_fits`
#: drops any axis a tiny channel count cannot divide, so small test
#: configs degrade to replicated instead of failing. Dimension roles
#: are declared as logical axes (docs/sharding.md); the active rules
#: table resolves them to mesh axes.
SD_PARAM_LOGICAL_AXES: list[tuple[str, tuple]] = [
    (r"(to_q|to_k|to_v)/kernel", (None, "heads")),
    (r"to_out_0/kernel", ("heads", None)),
    (r"ff/net_0/proj/kernel", (None, "mlp")),
    (r"ff/net_2/kernel", ("mlp", None)),
    (r"time_emb_proj/kernel", (None, "conv_out")),
    (r"(linear_1|linear_2)/kernel", (None, "conv_out")),
    # `(^|/)conv` anchors the down/upsampler convs without catching
    # quant_conv/post_quant_conv (4- and 8-channel 1x1s that must stay
    # replicated)
    (r"(conv1|conv2|conv_shortcut|(^|/)conv)/kernel",
     ("conv_kernel", "conv_kernel", "conv_in", "conv_out")),
    (r"(proj_in|proj_out)/kernel",
     ("conv_kernel", "conv_kernel", "conv_in", "conv_out")),
    (".*", (None,)),
]

SD_PARTITION_RULES = to_partition_rules(SD_PARAM_LOGICAL_AXES)


@dataclasses.dataclass
class SDUNetConfig:
    """Field names follow diffusers' UNet2DConditionModel config."""

    sample_size: int = 64
    in_channels: int = 4
    out_channels: int = 4
    down_block_types: Sequence[str] = (
        "CrossAttnDownBlock2D", "CrossAttnDownBlock2D",
        "CrossAttnDownBlock2D", "DownBlock2D")
    up_block_types: Sequence[str] = (
        "UpBlock2D", "CrossAttnUpBlock2D", "CrossAttnUpBlock2D",
        "CrossAttnUpBlock2D")
    block_out_channels: Sequence[int] = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    cross_attention_dim: int = 768
    attention_head_dim: int = 8  # = number of heads (SD-1.x quirk)
    norm_num_groups: int = 32
    norm_eps: float = 1e-5
    flip_sin_to_cos: bool = True
    freq_shift: int = 0
    dtype: str = "float32"

    @classmethod
    def small_test_config(cls, **overrides: Any) -> "SDUNetConfig":
        base = dict(sample_size=8, block_out_channels=(32, 64),
                    down_block_types=("CrossAttnDownBlock2D",
                                      "DownBlock2D"),
                    up_block_types=("UpBlock2D", "CrossAttnUpBlock2D"),
                    layers_per_block=1, cross_attention_dim=32,
                    attention_head_dim=2, norm_num_groups=8)
        base.update(overrides)
        return cls(**base)


def sd_timestep_embedding(timesteps: jax.Array, dim: int,
                          flip_sin_to_cos: bool = True,
                          freq_shift: float = 0.0) -> jax.Array:
    """diffusers `Timesteps` module (get_timestep_embedding)."""
    half = dim // 2
    exponent = -math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
    exponent = exponent / (half - freq_shift)
    emb = timesteps.astype(jnp.float32)[:, None] * \
        jnp.exp(exponent)[None, :]
    emb = jnp.concatenate([jnp.sin(emb), jnp.cos(emb)], axis=-1)
    if flip_sin_to_cos:
        emb = jnp.concatenate([emb[:, half:], emb[:, :half]], axis=-1)
    # the sin|cos concat must stay replicated on its feature dim: GSPMD
    # back-propagates downstream weight shards onto it, and a
    # concatenate consumed through a sharded matmul contraction
    # mispartitions on the CPU XLA build (docs/sharding.md "Root
    # cause") — this constraint is the fix
    return with_logical_constraint(emb, ("batch", "relpos"))


class TimestepEmbedding(nn.Module):
    dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, temb):
        temb = nn.Dense(self.dim, dtype=self.dtype, name="linear_1")(temb)
        return nn.Dense(self.dim, dtype=self.dtype, name="linear_2")(
            jax.nn.silu(temb))


class ResnetBlock2D(nn.Module):
    """diffusers ResnetBlock2D: norm→silu→conv ×2 with time projection
    between, learned 1x1 shortcut on channel change."""

    out_channels: int
    groups: int = 32
    eps: float = 1e-5
    use_temb: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, temb=None):
        h = nn.GroupNorm(num_groups=self.groups, epsilon=self.eps,
                         name="norm1")(x)
        h = nn.Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="conv1")(jax.nn.silu(h))
        if self.use_temb:
            h = h + nn.Dense(self.out_channels, dtype=self.dtype,
                             name="time_emb_proj")(
                jax.nn.silu(temb))[:, None, None, :]
        h = nn.GroupNorm(num_groups=self.groups, epsilon=self.eps,
                         name="norm2")(h)
        h = nn.Conv(self.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                    dtype=self.dtype, name="conv2")(jax.nn.silu(h))
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype,
                        name="conv_shortcut")(x)
        return x + h


class Attention(nn.Module):
    """diffusers Attention: to_q/to_k/to_v (no bias) + to_out.0."""

    channels: int
    num_heads: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, context=None):
        context = x if context is None else context
        head_dim = self.channels // self.num_heads
        b = x.shape[0]
        q = nn.Dense(self.channels, use_bias=False, dtype=self.dtype,
                     name="to_q")(x)
        k = nn.Dense(self.channels, use_bias=False, dtype=self.dtype,
                     name="to_k")(context)
        v = nn.Dense(self.channels, use_bias=False, dtype=self.dtype,
                     name="to_v")(context)
        q = q.reshape(b, -1, self.num_heads, head_dim)
        k = k.reshape(b, -1, self.num_heads, head_dim)
        v = v.reshape(b, -1, self.num_heads, head_dim)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return nn.Dense(self.channels, dtype=self.dtype,
                        name="to_out_0")(
            out.reshape(b, -1, self.channels))


class FeedForward(nn.Module):
    """diffusers FeedForward with GEGLU: proj to 2×inner, a·gelu(gate).

    The GEGLU projection lives at ``ff.net.0.proj`` in diffusers (net is
    a ModuleList [GEGLU, Dropout, Linear]), hence the nested name."""

    dim: int
    dtype: Any = jnp.float32

    class _GEGLU(nn.Module):
        inner: int
        dtype: Any = jnp.float32

        @nn.compact
        def __call__(self, x):
            proj = nn.Dense(2 * self.inner, dtype=self.dtype,
                            name="proj")(x)
            a, gate = jnp.split(proj, 2, axis=-1)
            return a * jax.nn.gelu(gate, approximate=False)

    @nn.compact
    def __call__(self, x):
        h = self._GEGLU(4 * self.dim, self.dtype, name="net_0")(x)
        return nn.Dense(self.dim, dtype=self.dtype, name="net_2")(h)


class BasicTransformerBlock(nn.Module):
    channels: int
    num_heads: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, context):
        # torch LayerNorm eps is 1e-5 (flax defaults to 1e-6)
        x = x + Attention(self.channels, self.num_heads, self.dtype,
                          name="attn1")(
            nn.LayerNorm(epsilon=1e-5, name="norm1")(x))
        x = x + Attention(self.channels, self.num_heads, self.dtype,
                          name="attn2")(
            nn.LayerNorm(epsilon=1e-5, name="norm2")(x), context)
        return x + FeedForward(self.channels, self.dtype, name="ff")(
            nn.LayerNorm(epsilon=1e-5, name="norm3")(x))


class Transformer2DModel(nn.Module):
    """GroupNorm → 1x1-conv proj_in → transformer over HW → 1x1-conv
    proj_out, residual (SD-1.x: use_linear_projection=False)."""

    channels: int
    num_heads: int
    groups: int = 32
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, context):
        b, hh, ww, c = x.shape
        residual = x
        h = nn.GroupNorm(num_groups=self.groups, epsilon=1e-6,
                         name="norm")(x)
        h = nn.Conv(self.channels, (1, 1), dtype=self.dtype,
                    name="proj_in")(h)
        h = h.reshape(b, hh * ww, self.channels)
        h = BasicTransformerBlock(self.channels, self.num_heads,
                                  self.dtype,
                                  name="transformer_blocks_0")(h, context)
        h = h.reshape(b, hh, ww, self.channels)
        h = nn.Conv(self.channels, (1, 1), dtype=self.dtype,
                    name="proj_out")(h)
        return h + residual


class Downsample2D(nn.Module):
    channels: int
    # torch Conv2d(k3, s2, p1) for the UNet; the VAE pads (0,1) only
    pad: tuple = ((1, 1), (1, 1))
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        return nn.Conv(self.channels, (3, 3), strides=(2, 2),
                       padding=self.pad, dtype=self.dtype,
                       name="conv")(x)


class Upsample2D(nn.Module):
    channels: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, hh, ww, c = x.shape
        x = jax.image.resize(x, (b, hh * 2, ww * 2, c), "nearest")
        return nn.Conv(self.channels, (3, 3), padding=((1, 1), (1, 1)),
                       dtype=self.dtype, name="conv")(x)


class _DownBlock(nn.Module):
    cfg: SDUNetConfig
    channels: int
    cross_attn: bool
    is_last: bool

    @nn.compact
    def __call__(self, h, temb, context):
        cfg, dt = self.cfg, jnp.dtype(self.cfg.dtype)
        skips = []
        for j in range(cfg.layers_per_block):
            h = ResnetBlock2D(self.channels, cfg.norm_num_groups,
                              cfg.norm_eps, dtype=dt,
                              name=f"resnets_{j}")(h, temb)
            if self.cross_attn:
                h = Transformer2DModel(self.channels,
                                       cfg.attention_head_dim,
                                       cfg.norm_num_groups, dt,
                                       name=f"attentions_{j}")(h, context)
            skips.append(h)
        if not self.is_last:
            h = Downsample2D(self.channels, dtype=dt,
                             name="downsamplers_0")(h)
            skips.append(h)
        return h, skips


class _MidBlock(nn.Module):
    cfg: SDUNetConfig
    channels: int

    @nn.compact
    def __call__(self, h, temb, context):
        cfg, dt = self.cfg, jnp.dtype(self.cfg.dtype)
        h = ResnetBlock2D(self.channels, cfg.norm_num_groups,
                          cfg.norm_eps, dtype=dt,
                          name="resnets_0")(h, temb)
        h = Transformer2DModel(self.channels, cfg.attention_head_dim,
                               cfg.norm_num_groups, dt,
                               name="attentions_0")(h, context)
        return ResnetBlock2D(self.channels, cfg.norm_num_groups,
                             cfg.norm_eps, dtype=dt,
                             name="resnets_1")(h, temb)


class _UpBlock(nn.Module):
    cfg: SDUNetConfig
    channels: int
    cross_attn: bool
    is_last: bool

    @nn.compact
    def __call__(self, h, skips, temb, context):
        cfg, dt = self.cfg, jnp.dtype(self.cfg.dtype)
        for j in range(cfg.layers_per_block + 1):
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            # the skip concat's channel dim is the very next conv's
            # contraction: keep it replicated (docs/sharding.md "Root
            # cause" — same concat-contraction hazard as the timestep
            # embedding; the conv weights stay sharded on conv_out)
            h = with_logical_constraint(
                h, ("batch", None, None, "conv_in"))
            h = ResnetBlock2D(self.channels, cfg.norm_num_groups,
                              cfg.norm_eps, dtype=dt,
                              name=f"resnets_{j}")(h, temb)
            if self.cross_attn:
                h = Transformer2DModel(self.channels,
                                       cfg.attention_head_dim,
                                       cfg.norm_num_groups, dt,
                                       name=f"attentions_{j}")(h, context)
        if not self.is_last:
            h = Upsample2D(self.channels, dtype=dt,
                           name="upsamplers_0")(h)
        return h


class SDUNet2DConditionModel(nn.Module):
    """The SD-1.x denoiser; forward contract identical to the compact
    tower: (latents NHWC, timesteps [B], text states [B,T,D]) → noise."""

    config: SDUNetConfig

    @nn.compact
    def __call__(self, latents, timesteps, encoder_hidden_states):
        cfg = self.config
        dt = jnp.dtype(cfg.dtype)
        context = encoder_hidden_states

        temb = sd_timestep_embedding(timesteps, cfg.block_out_channels[0],
                                     cfg.flip_sin_to_cos, cfg.freq_shift)
        temb = TimestepEmbedding(cfg.block_out_channels[0] * 4, dt,
                                 name="time_embedding")(temb)

        h = nn.Conv(cfg.block_out_channels[0], (3, 3),
                    padding=((1, 1), (1, 1)), dtype=dt,
                    name="conv_in")(latents)
        skips = [h]
        n = len(cfg.block_out_channels)
        for i, (btype, ch) in enumerate(zip(cfg.down_block_types,
                                            cfg.block_out_channels)):
            h, block_skips = _DownBlock(
                cfg, ch, btype == "CrossAttnDownBlock2D",
                is_last=(i == n - 1), name=f"down_blocks_{i}")(
                h, temb, context)
            skips.extend(block_skips)

        h = _MidBlock(cfg, cfg.block_out_channels[-1],
                      name="mid_block")(h, temb, context)

        rev_channels = list(reversed(cfg.block_out_channels))
        for i, (btype, ch) in enumerate(zip(cfg.up_block_types,
                                            rev_channels)):
            h = _UpBlock(cfg, ch, btype == "CrossAttnUpBlock2D",
                         is_last=(i == n - 1), name=f"up_blocks_{i}")(
                h, skips, temb, context)

        h = nn.GroupNorm(num_groups=cfg.norm_num_groups,
                         epsilon=cfg.norm_eps, name="conv_norm_out")(h)
        return nn.Conv(cfg.out_channels, (3, 3), padding=((1, 1), (1, 1)),
                       dtype=dt, name="conv_out")(jax.nn.silu(h))

    def partition_rules(self):
        # resolved at call time so a `use_rules` scope takes effect
        return to_partition_rules(SD_PARAM_LOGICAL_AXES)
