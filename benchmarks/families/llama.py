"""How a `family: llama` configuration file (any decoder that runs
through `models/llama`: RMSNorm, rotary, GQA, SwiGLU) becomes the
program's model and which plain reference stands beside it."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings")
REFERENCE = "benchmarks.references.mistral"


def build(config: dict):
    from fengshen_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    if config["hidden_size"] != config["num_attention_heads"] * \
            config["head_dim"]:
        raise ValueError("models/llama takes head_dim = hidden / heads")
    if config.get("sliding_window"):
        raise ValueError("models/llama has no sliding window")
    cfg = LlamaConfig(**{k: config[k] for k in MODEL_KEYS},
                      **config["program"])
    return LlamaForCausalLM(cfg), cfg


def reference_config(config: dict) -> dict:
    out = {k: config[k] for k in MODEL_KEYS}
    out["head_dim"] = config["head_dim"]
    out["param_dtype"] = config["program"]["param_dtype"]
    return out
