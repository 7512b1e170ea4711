"""Forward parity of the diffusers-faithful SD towers (VERDICT r4
missing #1 / weak #2).

No diffusers package exists in this env, so the torch oracle below is a
compact restatement of the diffusers modules themselves — built with
torch layers named exactly like diffusers' (`down_blocks.0.resnets.0…`),
so its `state_dict()` IS a diffusers-format checkpoint. The flax towers
must import that state dict via `convert.unet_to_params` /
`vae_to_params` and reproduce the oracle's outputs.

Oracle equations follow diffusers' UNet2DConditionModel /
AutoencoderKL for the SD-1.x configuration (use_linear_projection=False,
GEGLU feed-forward, conv proj_in/out; reference workload:
fengshen/examples/finetune_taiyi_stable_diffusion/finetune.py:81-144).
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

torch = pytest.importorskip("torch")
import torch.nn as tnn  # noqa: E402
import torch.nn.functional as F  # noqa: E402


# -- torch oracle (diffusers restatement) ---------------------------------

class OResnet(tnn.Module):
    def __init__(self, cin, cout, groups, eps, temb_dim=None):
        super().__init__()
        self.norm1 = tnn.GroupNorm(groups, cin, eps=eps)
        self.conv1 = tnn.Conv2d(cin, cout, 3, padding=1)
        if temb_dim:
            self.time_emb_proj = tnn.Linear(temb_dim, cout)
        self.norm2 = tnn.GroupNorm(groups, cout, eps=eps)
        self.conv2 = tnn.Conv2d(cout, cout, 3, padding=1)
        if cin != cout:
            self.conv_shortcut = tnn.Conv2d(cin, cout, 1)

    def forward(self, x, temb=None):
        h = self.conv1(F.silu(self.norm1(x)))
        if temb is not None:
            h = h + self.time_emb_proj(F.silu(temb))[:, :, None, None]
        h = self.conv2(F.silu(self.norm2(h)))
        if hasattr(self, "conv_shortcut"):
            x = self.conv_shortcut(x)
        return x + h


class OAttention(tnn.Module):
    def __init__(self, dim, heads, ctx_dim=None, qkv_bias=False):
        super().__init__()
        ctx_dim = ctx_dim or dim
        self.heads = heads
        self.to_q = tnn.Linear(dim, dim, bias=qkv_bias)
        self.to_k = tnn.Linear(ctx_dim, dim, bias=qkv_bias)
        self.to_v = tnn.Linear(ctx_dim, dim, bias=qkv_bias)
        self.to_out = tnn.ModuleList([tnn.Linear(dim, dim)])

    def forward(self, x, ctx=None):
        ctx = x if ctx is None else ctx
        b, n, c = x.shape
        hd = c // self.heads
        q = self.to_q(x).view(b, -1, self.heads, hd).transpose(1, 2)
        k = self.to_k(ctx).view(b, -1, self.heads, hd).transpose(1, 2)
        v = self.to_v(ctx).view(b, -1, self.heads, hd).transpose(1, 2)
        att = (q @ k.transpose(-1, -2)) / math.sqrt(hd)
        out = att.softmax(-1) @ v
        return self.to_out[0](
            out.transpose(1, 2).reshape(b, n, c))


class OGEGLU(tnn.Module):
    def __init__(self, dim, inner):
        super().__init__()
        self.proj = tnn.Linear(dim, 2 * inner)

    def forward(self, x):
        h, gate = self.proj(x).chunk(2, dim=-1)
        return h * F.gelu(gate)


class OFeedForward(tnn.Module):
    def __init__(self, dim):
        super().__init__()
        self.net = tnn.ModuleList(
            [OGEGLU(dim, 4 * dim), tnn.Identity(),
             tnn.Linear(4 * dim, dim)])

    def forward(self, x):
        return self.net[2](self.net[0](x))


class OTransformerBlock(tnn.Module):
    def __init__(self, dim, heads, ctx_dim):
        super().__init__()
        self.norm1 = tnn.LayerNorm(dim)
        self.attn1 = OAttention(dim, heads)
        self.norm2 = tnn.LayerNorm(dim)
        self.attn2 = OAttention(dim, heads, ctx_dim)
        self.norm3 = tnn.LayerNorm(dim)
        self.ff = OFeedForward(dim)

    def forward(self, x, ctx):
        x = x + self.attn1(self.norm1(x))
        x = x + self.attn2(self.norm2(x), ctx)
        return x + self.ff(self.norm3(x))


class OTransformer2D(tnn.Module):
    def __init__(self, dim, heads, ctx_dim, groups):
        super().__init__()
        self.norm = tnn.GroupNorm(groups, dim, eps=1e-6)
        self.proj_in = tnn.Conv2d(dim, dim, 1)
        self.transformer_blocks = tnn.ModuleList(
            [OTransformerBlock(dim, heads, ctx_dim)])
        self.proj_out = tnn.Conv2d(dim, dim, 1)

    def forward(self, x, ctx):
        b, c, h, w = x.shape
        res = x
        y = self.proj_in(self.norm(x))
        y = y.permute(0, 2, 3, 1).reshape(b, h * w, c)
        y = self.transformer_blocks[0](y, ctx)
        y = y.reshape(b, h, w, c).permute(0, 3, 1, 2)
        return self.proj_out(y) + res


class ODownsample(tnn.Module):
    def __init__(self, ch, vae=False):
        super().__init__()
        self.vae = vae
        self.conv = tnn.Conv2d(ch, ch, 3, stride=2,
                               padding=0 if vae else 1)

    def forward(self, x):
        if self.vae:
            x = F.pad(x, (0, 1, 0, 1))
        return self.conv(x)


class OUpsample(tnn.Module):
    def __init__(self, ch):
        super().__init__()
        self.conv = tnn.Conv2d(ch, ch, 3, padding=1)

    def forward(self, x):
        return self.conv(F.interpolate(x, scale_factor=2.0,
                                       mode="nearest"))


class OUNet(tnn.Module):
    """diffusers UNet2DConditionModel restated, small config:
    blocks (32, 64), layers_per_block=1, heads 2, ctx 32, groups 8."""

    CH = (32, 64)
    GROUPS = 8
    HEADS = 2
    CTX = 32
    LAYERS = 1
    EPS = 1e-5

    def __init__(self):
        super().__init__()
        ch0, ch1 = self.CH
        tdim = ch0 * 4

        class TE(tnn.Module):
            def __init__(self):
                super().__init__()
                self.linear_1 = tnn.Linear(ch0, tdim)
                self.linear_2 = tnn.Linear(tdim, tdim)

            def forward(self, t):
                return self.linear_2(F.silu(self.linear_1(t)))

        self.time_embedding = TE()
        self.conv_in = tnn.Conv2d(4, ch0, 3, padding=1)

        db0 = tnn.Module()
        db0.resnets = tnn.ModuleList(
            [OResnet(ch0, ch0, self.GROUPS, self.EPS, tdim)])
        db0.attentions = tnn.ModuleList(
            [OTransformer2D(ch0, self.HEADS, self.CTX, self.GROUPS)])
        db0.downsamplers = tnn.ModuleList([ODownsample(ch0)])
        db1 = tnn.Module()
        db1.resnets = tnn.ModuleList(
            [OResnet(ch0, ch1, self.GROUPS, self.EPS, tdim)])
        self.down_blocks = tnn.ModuleList([db0, db1])

        mid = tnn.Module()
        mid.resnets = tnn.ModuleList(
            [OResnet(ch1, ch1, self.GROUPS, self.EPS, tdim),
             OResnet(ch1, ch1, self.GROUPS, self.EPS, tdim)])
        mid.attentions = tnn.ModuleList(
            [OTransformer2D(ch1, self.HEADS, self.CTX, self.GROUPS)])
        self.mid_block = mid

        ub0 = tnn.Module()  # UpBlock2D at ch1
        ub0.resnets = tnn.ModuleList(
            [OResnet(ch1 + ch1, ch1, self.GROUPS, self.EPS, tdim),
             OResnet(ch1 + ch0, ch1, self.GROUPS, self.EPS, tdim)])
        ub0.upsamplers = tnn.ModuleList([OUpsample(ch1)])
        ub1 = tnn.Module()  # CrossAttnUpBlock2D at ch0
        ub1.resnets = tnn.ModuleList(
            [OResnet(ch1 + ch0, ch0, self.GROUPS, self.EPS, tdim),
             OResnet(ch0 + ch0, ch0, self.GROUPS, self.EPS, tdim)])
        ub1.attentions = tnn.ModuleList(
            [OTransformer2D(ch0, self.HEADS, self.CTX, self.GROUPS),
             OTransformer2D(ch0, self.HEADS, self.CTX, self.GROUPS)])
        self.up_blocks = tnn.ModuleList([ub0, ub1])

        self.conv_norm_out = tnn.GroupNorm(self.GROUPS, ch0, eps=self.EPS)
        self.conv_out = tnn.Conv2d(ch0, 4, 3, padding=1)

    def timestep_embedding(self, t):
        half = self.CH[0] // 2
        exponent = -math.log(10000.0) * torch.arange(half).float() / half
        emb = t.float()[:, None] * exponent.exp()[None]
        emb = torch.cat([emb.sin(), emb.cos()], dim=-1)
        return torch.cat([emb[:, half:], emb[:, :half]], dim=-1)

    def forward(self, latents, t, ctx):
        temb = self.time_embedding(self.timestep_embedding(t))
        h = self.conv_in(latents)
        skips = [h]
        d0 = self.down_blocks[0]
        h = d0.resnets[0](h, temb)
        h = d0.attentions[0](h, ctx)
        skips.append(h)
        h = d0.downsamplers[0](h)
        skips.append(h)
        d1 = self.down_blocks[1]
        h = d1.resnets[0](h, temb)
        skips.append(h)

        h = self.mid_block.resnets[0](h, temb)
        h = self.mid_block.attentions[0](h, ctx)
        h = self.mid_block.resnets[1](h, temb)

        u0 = self.up_blocks[0]
        for j in range(2):
            h = torch.cat([h, skips.pop()], dim=1)
            h = u0.resnets[j](h, temb)
        h = u0.upsamplers[0](h)
        u1 = self.up_blocks[1]
        for j in range(2):
            h = torch.cat([h, skips.pop()], dim=1)
            h = u1.resnets[j](h, temb)
            h = u1.attentions[j](h, ctx)

        return self.conv_out(F.silu(self.conv_norm_out(h)))


class OVAEAttn(tnn.Module):
    def __init__(self, ch, groups):
        super().__init__()
        self.group_norm = tnn.GroupNorm(groups, ch, eps=1e-6)
        self.to_q = tnn.Linear(ch, ch)
        self.to_k = tnn.Linear(ch, ch)
        self.to_v = tnn.Linear(ch, ch)
        self.to_out = tnn.ModuleList([tnn.Linear(ch, ch)])

    def forward(self, x):
        b, c, h, w = x.shape
        y = self.group_norm(x)
        y = y.permute(0, 2, 3, 1).reshape(b, h * w, c)
        q, k, v = self.to_q(y), self.to_k(y), self.to_v(y)
        att = (q @ k.transpose(-1, -2)) / math.sqrt(c)
        y = self.to_out[0](att.softmax(-1) @ v)
        return x + y.reshape(b, h, w, c).permute(0, 3, 1, 2)


class OVAE(tnn.Module):
    """diffusers AutoencoderKL restated; blocks (16, 32),
    layers_per_block=1, groups 4."""

    CH = (16, 32)
    GROUPS = 4

    def __init__(self):
        super().__init__()
        ch0, ch1 = self.CH

        enc = tnn.Module()
        enc.conv_in = tnn.Conv2d(3, ch0, 3, padding=1)
        e0 = tnn.Module()
        e0.resnets = tnn.ModuleList(
            [OResnet(ch0, ch0, self.GROUPS, 1e-6)])
        e0.downsamplers = tnn.ModuleList([ODownsample(ch0, vae=True)])
        e1 = tnn.Module()
        e1.resnets = tnn.ModuleList(
            [OResnet(ch0, ch1, self.GROUPS, 1e-6)])
        enc.down_blocks = tnn.ModuleList([e0, e1])
        mid = tnn.Module()
        mid.resnets = tnn.ModuleList(
            [OResnet(ch1, ch1, self.GROUPS, 1e-6),
             OResnet(ch1, ch1, self.GROUPS, 1e-6)])
        mid.attentions = tnn.ModuleList([OVAEAttn(ch1, self.GROUPS)])
        enc.mid_block = mid
        enc.conv_norm_out = tnn.GroupNorm(self.GROUPS, ch1, eps=1e-6)
        enc.conv_out = tnn.Conv2d(ch1, 8, 3, padding=1)
        self.encoder = enc

        dec = tnn.Module()
        dec.conv_in = tnn.Conv2d(4, ch1, 3, padding=1)
        dmid = tnn.Module()
        dmid.resnets = tnn.ModuleList(
            [OResnet(ch1, ch1, self.GROUPS, 1e-6),
             OResnet(ch1, ch1, self.GROUPS, 1e-6)])
        dmid.attentions = tnn.ModuleList([OVAEAttn(ch1, self.GROUPS)])
        dec.mid_block = dmid
        d0 = tnn.Module()
        d0.resnets = tnn.ModuleList(
            [OResnet(ch1, ch1, self.GROUPS, 1e-6),
             OResnet(ch1, ch1, self.GROUPS, 1e-6)])
        d0.upsamplers = tnn.ModuleList([OUpsample(ch1)])
        d1 = tnn.Module()
        d1.resnets = tnn.ModuleList(
            [OResnet(ch1, ch0, self.GROUPS, 1e-6),
             OResnet(ch0, ch0, self.GROUPS, 1e-6)])
        dec.up_blocks = tnn.ModuleList([d0, d1])
        dec.conv_norm_out = tnn.GroupNorm(self.GROUPS, ch0, eps=1e-6)
        dec.conv_out = tnn.Conv2d(ch0, 3, 3, padding=1)
        self.decoder = dec

        self.quant_conv = tnn.Conv2d(8, 8, 1)
        self.post_quant_conv = tnn.Conv2d(4, 4, 1)

    def encode(self, x):
        e = self.encoder
        h = e.conv_in(x)
        h = e.down_blocks[0].resnets[0](h)
        h = e.down_blocks[0].downsamplers[0](h)
        h = e.down_blocks[1].resnets[0](h)
        h = e.mid_block.resnets[0](h)
        h = e.mid_block.attentions[0](h)
        h = e.mid_block.resnets[1](h)
        h = e.conv_out(F.silu(e.conv_norm_out(h)))
        moments = self.quant_conv(h)
        mean, logvar = moments.chunk(2, dim=1)
        return mean, logvar.clamp(-30.0, 20.0)

    def decode(self, z):
        d = self.decoder
        h = d.conv_in(self.post_quant_conv(z))
        h = d.mid_block.resnets[0](h)
        h = d.mid_block.attentions[0](h)
        h = d.mid_block.resnets[1](h)
        for i in range(2):
            blk = d.up_blocks[i]
            for r in blk.resnets:
                h = r(h)
            if i == 0:
                h = blk.upsamplers[0](h)
        return d.conv_out(F.silu(d.conv_norm_out(h)))


# -- tests ----------------------------------------------------------------

def _nhwc(x):
    return jnp.asarray(x.detach().numpy().transpose(0, 2, 3, 1))


def test_sd_unet_forward_parity():
    from fengshen_tpu.models.stable_diffusion.convert import unet_to_params
    from fengshen_tpu.models.stable_diffusion.unet_sd import (
        SDUNetConfig, SDUNet2DConditionModel)

    torch.manual_seed(0)
    oracle = OUNet().eval()
    cfg = SDUNetConfig.small_test_config()
    params = unet_to_params(oracle.state_dict())
    model = SDUNet2DConditionModel(cfg)

    rng = np.random.RandomState(1)
    lat = torch.tensor(rng.randn(2, 4, 8, 8), dtype=torch.float32)
    t = torch.tensor([7, 421])
    ctx = torch.tensor(rng.randn(2, 5, 32), dtype=torch.float32)
    with torch.no_grad():
        ref = oracle(lat, t, ctx)
    ours = model.apply({"params": params}, _nhwc(lat),
                       jnp.asarray(t.numpy()), jnp.asarray(ctx.numpy()))
    np.testing.assert_allclose(np.asarray(ours),
                               ref.numpy().transpose(0, 2, 3, 1),
                               atol=2e-4)
    # the import covered every oracle parameter (no silently-missed keys)
    n_oracle = len(oracle.state_dict())
    n_flax = len(jax.tree_util.tree_leaves(params))
    assert n_oracle == n_flax, (n_oracle, n_flax)


def test_sd_vae_forward_parity():
    from fengshen_tpu.models.stable_diffusion.convert import vae_to_params
    from fengshen_tpu.models.stable_diffusion.vae_sd import (
        SDVAEConfig, SDAutoencoderKL)

    torch.manual_seed(0)
    oracle = OVAE().eval()
    cfg = SDVAEConfig.small_test_config()
    params = vae_to_params(oracle.state_dict())
    model = SDAutoencoderKL(cfg)

    rng = np.random.RandomState(2)
    px = torch.tensor(rng.randn(1, 3, 16, 16), dtype=torch.float32)
    with torch.no_grad():
        mean_ref, logvar_ref = oracle.encode(px)
        recon_ref = oracle.decode(mean_ref)
    mean, logvar = model.apply({"params": params}, _nhwc(px),
                               method=SDAutoencoderKL.encode)
    np.testing.assert_allclose(np.asarray(mean),
                               mean_ref.numpy().transpose(0, 2, 3, 1),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(logvar),
                               logvar_ref.numpy().transpose(0, 2, 3, 1),
                               atol=2e-4)
    recon = model.apply({"params": params}, mean,
                        method=SDAutoencoderKL.decode)
    np.testing.assert_allclose(np.asarray(recon),
                               recon_ref.numpy().transpose(0, 2, 3, 1),
                               atol=5e-4)


def test_sd_vae_old_attention_naming():
    """2022-era diffusers VAE checkpoints use query/key/value/proj_attn —
    the importer must accept both namings."""
    from fengshen_tpu.models.stable_diffusion.convert import vae_to_params

    torch.manual_seed(0)
    oracle = OVAE().eval()
    state = dict(oracle.state_dict())
    renames = {"to_q": "query", "to_k": "key", "to_v": "value",
               "to_out.0": "proj_attn"}
    old_state = {}
    for k, v in state.items():
        for new, old in renames.items():
            if f"attentions.0.{new}." in k:
                k = k.replace(f"attentions.0.{new}.",
                              f"attentions.0.{old}.")
                break
        old_state[k] = v
    assert any("proj_attn" in k for k in old_state)
    a = vae_to_params(state)
    b = vae_to_params(old_state)
    for pa, pb in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                      jax.tree_util.tree_flatten_with_path(b)[0]):
        assert pa[0] == pb[0]
        np.testing.assert_array_equal(pa[1], pb[1])


def test_sd_unet_export_round_trip():
    """fs→diffusers export (derived inverse) is bit-exact."""
    from fengshen_tpu.models.stable_diffusion.convert import (
        unet_params_to_diffusers, unet_to_params)

    torch.manual_seed(0)
    oracle = OUNet()
    state = oracle.state_dict()
    params = unet_to_params(state)
    out = unet_params_to_diffusers(params, state)
    for k, v in state.items():
        np.testing.assert_array_equal(out[k], v.numpy(), err_msg=k)


def test_sd_config_from_diffusers_json():
    from fengshen_tpu.models.stable_diffusion.convert import (
        sd_unet_config_from_diffusers, sd_vae_config_from_diffusers)

    unet_cfg = sd_unet_config_from_diffusers({
        "_class_name": "UNet2DConditionModel", "sample_size": 64,
        "in_channels": 4, "out_channels": 4,
        "block_out_channels": [320, 640, 1280, 1280],
        "layers_per_block": 2, "cross_attention_dim": 768,
        "attention_head_dim": 8, "norm_num_groups": 32,
        "down_block_types": ["CrossAttnDownBlock2D"] * 3 + [
            "DownBlock2D"],
        "up_block_types": ["UpBlock2D"] + ["CrossAttnUpBlock2D"] * 3,
        "act_fn": "silu", "center_input_sample": False})
    assert unet_cfg.block_out_channels == (320, 640, 1280, 1280)
    assert unet_cfg.attention_head_dim == 8
    vae_cfg = sd_vae_config_from_diffusers({
        "_class_name": "AutoencoderKL", "latent_channels": 4,
        "block_out_channels": [128, 256, 512, 512],
        "layers_per_block": 2, "norm_num_groups": 32, "act_fn": "silu"})
    assert vae_cfg.block_out_channels == (128, 256, 512, 512)


@pytest.mark.slow
def test_finetune_over_faithful_towers_e2e(tmp_path, mesh8):
    """The Taiyi-SD finetune driver runs over the faithful towers with
    weights imported from a (synthetic) released diffusers pipeline dir
    — the full reference workload path (finetune.py:81-144)."""
    import csv
    import json as json_mod
    import os

    pytest.importorskip("PIL")
    from PIL import Image
    from transformers import BertTokenizer

    from fengshen_tpu.examples.finetune_taiyi_stable_diffusion import (
        finetune)
    from fengshen_tpu.models.bert import BertConfig

    # text tower dir
    chars = list("一张测试图片的照狗")
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + \
        sorted(set(chars))
    (tmp_path / "vocab.txt").write_text("\n".join(vocab))
    tok = BertTokenizer(str(tmp_path / "vocab.txt"))
    model_dir = tmp_path / "model"
    model_dir.mkdir()
    tok.save_pretrained(str(model_dir))
    BertConfig.small_test_config(vocab_size=len(tok)).save_pretrained(
        str(model_dir))

    # synthetic "released" diffusers pipeline dir with oracle weights
    pipe = tmp_path / "pipeline"
    torch.manual_seed(0)
    for sub, oracle, cfg in (
            ("unet", OUNet(), {
                "sample_size": 4, "in_channels": 4, "out_channels": 4,
                "block_out_channels": [32, 64], "layers_per_block": 1,
                "cross_attention_dim": 32, "attention_head_dim": 2,
                "norm_num_groups": 8,
                "down_block_types": ["CrossAttnDownBlock2D",
                                     "DownBlock2D"],
                "up_block_types": ["UpBlock2D", "CrossAttnUpBlock2D"]}),
            ("vae", OVAE(), {
                "in_channels": 3, "out_channels": 3,
                "latent_channels": 4, "block_out_channels": [16, 32],
                "layers_per_block": 1, "norm_num_groups": 4})):
        os.makedirs(pipe / sub)
        with open(pipe / sub / "config.json", "w") as f:
            json_mod.dump(cfg, f)
        torch.save(oracle.state_dict(),
                   pipe / sub / "diffusion_pytorch_model.bin")

    # tiny image/caption dataset
    img_dir = tmp_path / "imgs"
    img_dir.mkdir()
    rng = np.random.RandomState(0)
    rows = []
    for i in range(4):
        arr = (rng.rand(32, 32, 3) * 255).astype(np.uint8)
        p = img_dir / f"i{i}.png"
        Image.fromarray(arr).save(p)
        rows.append({"image": str(p), "caption": "一张测试图片"})
    csv_path = tmp_path / "data.csv"
    with open(csv_path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=["image", "caption"])
        w.writeheader()
        w.writerows(rows)

    finetune.main([
        "--model_path", str(model_dir),
        "--sd_pipeline_path", str(pipe),
        "--train_csv", str(csv_path),
        "--train_batchsize", "2", "--max_steps", "2",
        "--log_every_n_steps", "1", "--warmup_steps", "1",
        "--default_root_dir", str(tmp_path / "runs"),
        "--save_ckpt_path", str(tmp_path / "ckpt"),
        "--load_ckpt_path", str(tmp_path / "ckpt"),
        "--image_size", "32", "--max_length", "16", "--seed", "1"])
    lines = [json_mod.loads(l)
             for l in open(tmp_path / "runs" / "metrics.jsonl")]
    losses = [l["loss"] for l in lines if "loss" in l]
    assert len(losses) == 2 and all(np.isfinite(losses))


def test_sd_unet_sharded_matches_replicated(mesh8):
    """SD_PARTITION_RULES shard the faithful UNet over fsdp+tensor
    without changing the math (the 860M Taiyi-SD finetune must shard on
    a pod, not replicate).

    Formerly a non-strict xfail: the divergence
    was GSPMD back-propagating downstream weight shards onto the
    timestep sin|cos concat / up-block skip concats, whose dims then
    became sharded matmul contractions — mispartitioned on this XLA
    build. Fixed by the `with_logical_constraint` replication pins in
    unet_sd.py (docs/sharding.md "Root cause"); parity is now a hard
    tight-tolerance assertion."""
    from fengshen_tpu.models.stable_diffusion.unet_sd import (
        SDUNetConfig, SDUNet2DConditionModel)
    from fengshen_tpu.parallel import make_shardings
    from fengshen_tpu.parallel.partition import match_partition_rules

    # channels divisible by fsdp=2/tensor=2 so the rules really engage
    cfg = SDUNetConfig.small_test_config(
        block_out_channels=(32, 64), cross_attention_dim=32)
    model = SDUNet2DConditionModel(cfg)
    rng = np.random.RandomState(9)
    lat = jnp.asarray(rng.randn(2, 8, 8, 4), jnp.float32)
    t = jnp.asarray([3, 411])
    ctx = jnp.asarray(rng.randn(2, 5, 32), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), lat, t, ctx)["params"]
    ref = model.apply({"params": params}, lat, t, ctx)

    specs = match_partition_rules(model.partition_rules(), params)
    shardings = make_shardings(specs, params, mesh8)
    sharded = jax.device_put(params, shardings)
    # the cross-attention kernels must actually be partitioned
    qk = sharded["down_blocks_0"]["attentions_0"][
        "transformer_blocks_0"]["attn2"]["to_q"]["kernel"]
    assert any(e is not None for e in qk.sharding.spec)
    out = jax.jit(lambda p: model.apply({"params": p}, lat, t, ctx))(
        sharded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4)
