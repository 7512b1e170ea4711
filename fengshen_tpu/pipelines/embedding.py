"""Text-embedding pipeline: the Taiyi-CLIP text tower as a serving
surface, and the hook the embedding engine plugs into.

Follows the repo's pipeline contract (`__init__(args, model=...)`,
`__call__(text)`): encode the prompt with the Chinese-BERT text tower,
project into the CLIP joint space, L2-normalize
(models/clip/modeling_taiyi_clip.py `get_text_features`). `__call__`
is the one-request path; the `EmbeddingEngine`
(fengshen_tpu/serving/multimodal.py) instead drives `run_batch` so
co-arriving requests ride ONE jitted text-tower forward.

`small_test=True` builds a compact random-init tower with a built-in
byte tokenizer — the serving tests run on it without checkpoints. Real
weights: convert the Taiyi-CLIP checkpoint with `models.clip.convert`
and inject `module=`/`params=`.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fengshen_tpu.pipelines.image_generation import byte_encode


class Pipeline:
    """Taiyi-CLIP text-embedding pipeline.

    Inject `module` (a `TaiyiCLIPModel`)/`params` (+ optionally a
    tokenizer exposing `encode(text) -> list[int]`), or set
    `small_test=True` for the compact random-init tower.
    """

    task = "embedding"

    def __init__(self, args: Any = None, model: Optional[str] = None,
                 module: Any = None, params: Any = None,
                 tokenizer: Any = None, max_text_len: int = 16,
                 seed: int = 0, small_test: bool = False):
        if args is not None:
            max_text_len = getattr(args, "max_text_len", max_text_len)
        if module is None and small_test:
            module, params = self._build_small_test(seed)
        if module is None:
            if model is None:
                raise ValueError(
                    "embedding needs an injected module/params or "
                    "small_test=True")
            raise ValueError(
                "model= checkpoint loading is not wired for embedding; "
                "convert the Taiyi-CLIP checkpoint with "
                "models.clip.convert and inject module=/params= (or "
                "use small_test=True)")
        if params is None:
            raise ValueError("params are required alongside module")
        self.module = module
        self.params = params
        self.tokenizer = tokenizer
        self.max_text_len = int(max_text_len)
        self._embed_jit = jax.jit(self._embed)

    @staticmethod
    def _build_small_test(seed: int):
        from fengshen_tpu.models.bert import BertConfig
        from fengshen_tpu.models.clip.modeling_taiyi_clip import (
            CLIPVisionConfig, TaiyiCLIPModel)
        text_cfg = BertConfig(vocab_size=128, hidden_size=32,
                              num_hidden_layers=2, num_attention_heads=2,
                              intermediate_size=64,
                              max_position_embeddings=64,
                              dtype="float32")
        module = TaiyiCLIPModel(text_cfg,
                                CLIPVisionConfig.small_test_config())
        ids = jnp.zeros((1, 8), jnp.int32)
        pixels = jnp.zeros((1, 32, 32, 3), jnp.float32)
        params = jax.jit(lambda r: module.init(
            r, ids, pixels)["params"])(jax.random.PRNGKey(seed))
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

    def _embed(self, params, input_ids):
        # through __call__ (the module's compact entry point) with
        # pixel_values=None: only the text tower runs
        text_emb, _, _ = self.module.apply({"params": params},
                                           input_ids)
        return text_emb

    def run_batch(self, texts: list) -> list:
        """The EmbeddingEngine hook: one jitted text-tower forward for
        the whole micro-batch."""
        from fengshen_tpu.observability import get_registry, span
        ids = jnp.asarray(np.stack([self.encode(t) for t in texts]))
        with span("pipeline/embed_batch"):
            emb = np.asarray(jax.block_until_ready(
                self._embed_jit(self.params, ids)))
        get_registry().counter(
            "fstpu_pipeline_embeddings_total",
            "embeddings computed by the embedding pipeline"
        ).inc(len(texts))
        return [{"embedding": row.astype(float).tolist(),
                 "dim": int(emb.shape[-1])} for row in emb]

    # ---- legacy one-request path ------------------------------------

    def __call__(self, input_text: str) -> dict:
        return self.run_batch([input_text])[0]

    @staticmethod
    def add_pipeline_specific_args(parser):
        parser.add_argument("--max_text_len", default=16, type=int)
        return parser
