"""Text-to-image pipeline: the Taiyi Stable Diffusion inference surface
and the hook the batch-image serving engine plugs into.

Follows the repo's pipeline contract (`__init__(args, model=...)`,
`__call__(text)`) for the latent-diffusion pipeline
(models/stable_diffusion/modeling_taiyi_sd.py): encode the prompt with
the Chinese text tower, walk a subsampled DDPM schedule over latent
noise, decode with the VAE. `__call__` is the one-request path; the
`BatchImageEngine` (fengshen_tpu/serving/multimodal.py) instead drives
`run_batch` so co-arriving prompts ride ONE jitted denoise loop.

Released Taiyi-SD weights are three towers (text encoder + diffusers
unet/vae) — convert them with `models.stable_diffusion.convert` and
inject `module=`/`params=`. `small_test=True` builds the compact
random-init towers with a built-in byte tokenizer — the serving tests
run on it without any checkpoint or tokenizer dependency.
"""

from __future__ import annotations

import base64
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


def byte_encode(text: str, vocab_size: int, max_len: int) -> np.ndarray:
    """Dependency-free tokenizer for the small-test towers: bytes
    folded into [3, vocab), padded with 0 to `max_len`. Deterministic,
    so request→image is reproducible across processes."""
    ids = [3 + (b % (vocab_size - 3))
           for b in text.encode("utf-8")[:max_len]]
    return np.asarray(ids + [0] * (max_len - len(ids)), np.int32)


class Pipeline:
    """Taiyi Stable Diffusion text-to-image pipeline.

    Either pass `model` (an HF diffusers checkpoint directory) or
    inject `module`/`params` (+ optionally `tokenizer`) directly, or
    set `small_test=True` for the compact random-init towers. The
    tokenizer needs `encode(text) -> list[int]`; None falls back to
    the byte tokenizer above.
    """

    task = "image_generation"

    def __init__(self, args: Any = None, model: Optional[str] = None,
                 module: Any = None, params: Any = None,
                 tokenizer: Any = None, image_size: int = 32,
                 num_inference_steps: int = 4, max_text_len: int = 16,
                 seed: int = 0, small_test: bool = False):
        if args is not None:
            image_size = getattr(args, "image_size", image_size)
            num_inference_steps = getattr(args, "num_inference_steps",
                                          num_inference_steps)
        if module is None and small_test:
            module, params = self._build_small_test(seed)
        if module is None:
            if model is None:
                raise ValueError(
                    "image_generation needs an injected module/params "
                    "or small_test=True")
            # a released Taiyi-SD checkpoint is THREE towers (text
            # encoder + diffusers unet/vae); assemble the
            # TaiyiStableDiffusion params via
            # models.stable_diffusion.convert (load_diffusers_pipeline
            # + the bert converter) and inject module=/params=
            raise ValueError(
                "model= checkpoint assembly is not wired for "
                "image_generation; convert the towers with "
                "models.stable_diffusion.convert and inject "
                "module=/params= (or use small_test=True)")
        if params is None:
            raise ValueError("params are required alongside module")
        self.module = module
        self.params = params
        self.tokenizer = tokenizer
        self.image_size = int(image_size)
        self.num_inference_steps = int(num_inference_steps)
        self.max_text_len = int(max_text_len)
        self.seed = seed
        self._n_calls = 0
        self._generate_jit = jax.jit(self._generate)

    @staticmethod
    def _build_small_test(seed: int):
        from fengshen_tpu.models.bert import BertConfig
        from fengshen_tpu.models.stable_diffusion.autoencoder_kl import \
            VAEConfig
        from fengshen_tpu.models.stable_diffusion.modeling_taiyi_sd import \
            TaiyiStableDiffusion
        from fengshen_tpu.models.stable_diffusion.unet import UNetConfig
        text_cfg = BertConfig(vocab_size=128, hidden_size=32,
                              num_hidden_layers=2, num_attention_heads=2,
                              intermediate_size=64,
                              max_position_embeddings=64,
                              dtype="float32")
        module = TaiyiStableDiffusion(
            text_cfg, VAEConfig.small_test_config(),
            UNetConfig.small_test_config(cross_attention_dim=32))
        ids = jnp.zeros((1, 8), jnp.int32)
        pixels = jnp.zeros((1, 32, 32, 3), jnp.float32)
        t = jnp.zeros((1,), jnp.int32)
        noise = jnp.zeros((1, 16, 16, 4), jnp.float32)

        def init_all(m, ids, pixels, t, noise):
            # the decoder convs are inline-compact, so the init trace
            # must walk decode_image too or its params never exist
            pred, latents = m(ids, pixels, t, noise)
            m.decode_image(latents)
            return pred

        params = jax.jit(lambda r: module.init(
            r, ids, pixels, t, noise,
            method=init_all)["params"])(jax.random.PRNGKey(seed))
        return module, params

    # ---- engine integration -----------------------------------------

    def encode(self, text: str) -> np.ndarray:
        if self.tokenizer is not None:
            ids = list(self.tokenizer.encode(text))[:self.max_text_len]
            ids += [0] * (self.max_text_len - len(ids))
            return np.asarray(ids, np.int32)
        vocab = self.module.text_config.vocab_size
        return byte_encode(text, vocab, self.max_text_len)

    def warmup_input(self) -> str:
        return "warmup"

    def _generate(self, params, input_ids, rng):
        """One jitted batch: text encode → subsampled DDPM walk →
        VAE decode → [0,1] pixels. Python loop over the (static)
        inference schedule unrolls into one program."""
        from fengshen_tpu.models.stable_diffusion.scheduler import \
            DDPMScheduler
        module = self.module
        scheduler = DDPMScheduler()
        batch = input_ids.shape[0]
        text = module.apply({"params": params}, input_ids,
                            method=module.encode_text)
        factor = 2 ** (len(module.vae_config.channel_mults) - 1)
        latents = jax.random.normal(
            rng, (batch, self.image_size // factor,
                  self.image_size // factor,
                  module.vae_config.latent_channels))
        T = scheduler.num_train_timesteps
        steps = np.linspace(T - 1, 0, self.num_inference_steps,
                            dtype=np.int64)
        for i, t in enumerate(steps):
            t_b = jnp.full((batch,), int(t), jnp.int32)
            pred = module.apply({"params": params}, latents, t_b, text,
                                method=module.denoise)
            prev_t = int(steps[i + 1]) if i + 1 < len(steps) else -1
            latents = scheduler.step(pred, int(t), latents,
                                     prev_timestep=prev_t)
        pixels = module.apply({"params": params}, latents,
                              method=module.decode_image)
        return jnp.clip((pixels + 1.0) / 2.0, 0.0, 1.0)

    def run_batch(self, texts: list) -> list:
        """The BatchImageEngine hook: one jitted denoise loop for the
        whole micro-batch; per-request RNG folds in the call counter so
        repeated identical prompts differ (and the batch as a whole is
        reproducible from `seed`)."""
        from fengshen_tpu.observability import get_registry, span
        self._n_calls += 1
        ids = jnp.asarray(np.stack([self.encode(t) for t in texts]))
        rng = jax.random.fold_in(jax.random.PRNGKey(self.seed),
                                 self._n_calls)
        with span("pipeline/image_batch"):
            images = np.asarray(
                jax.block_until_ready(
                    self._generate_jit(self.params, ids, rng)))
        get_registry().counter(
            "fstpu_pipeline_images_total",
            "images generated by the batch-image pipeline"
        ).inc(len(texts))
        return [self._pack(img) for img in images]

    @staticmethod
    def _pack(img: np.ndarray) -> dict:
        """JSON-safe result: raw uint8 RGB bytes, base64. No PIL/png
        dependency — clients reshape from `shape`."""
        u8 = (img * 255.0 + 0.5).astype(np.uint8)
        return {"image_b64": base64.b64encode(u8.tobytes()).decode(),
                "shape": list(u8.shape), "dtype": "uint8"}

    # ---- legacy one-request path ------------------------------------

    def __call__(self, input_text: str) -> dict:
        return self.run_batch([input_text])[0]

    @staticmethod
    def add_pipeline_specific_args(parser):
        parser.add_argument("--image_size", default=32, type=int)
        parser.add_argument("--num_inference_steps", default=4, type=int)
        return parser
