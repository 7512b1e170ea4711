"""How a `family: gpt2` configuration file becomes the program's model
and which plain reference stands beside it."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head",
              "n_inner", "activation_function", "layer_norm_epsilon",
              "initializer_range", "resid_pdrop", "embd_pdrop", "attn_pdrop")
REFERENCE = "benchmarks.references.gpt2"


def build(config: dict):
    """(flax module, the program's config object)."""
    from fengshen_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(**{k: config[k] for k in MODEL_KEYS},
                     **config["program"])
    return GPT2LMHeadModel(cfg), cfg


def reference_config(config: dict) -> dict:
    return {k: config[k] for k in MODEL_KEYS}
