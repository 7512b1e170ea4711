"""How a `family: sdar` configuration file (SDAR: the Qwen3-MoE block
under a block-causal mask, generated by diffusion over blocks of
`block_length` positions) becomes the program's model through
`models/sdar`, and which plain reference stands beside it. The
generation procedure's two numbers, `block_length` and `mask_token_id`,
are the file's `assumed` (the published `config.json` has neither)."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rope_theta",
              "rope_scaling", "sliding_window", "use_sliding_window",
              "max_window_layers", "decoder_sparse_step", "mlp_only_layers",
              "moe_intermediate_size", "num_experts", "num_experts_per_tok",
              "norm_topk_prob", "max_position_embeddings", "rms_norm_eps",
              "hidden_act", "attention_bias", "tie_word_embeddings")
GENERATION_KEYS = ("block_length", "mask_token_id")
REFERENCE = "benchmarks.references.sdar"
#: the keys the reference's mathematics reads
REFERENCE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
                  "num_attention_heads", "num_key_value_heads", "head_dim",
                  "rope_theta", "moe_intermediate_size", "num_experts",
                  "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")


def build(config: dict):
    from fengshen_tpu.models.sdar import SdarConfig, SdarForCausalLM
    cfg = SdarConfig(**{k: config[k] for k in MODEL_KEYS},
                     **{k: config["assumed"][k] for k in GENERATION_KEYS},
                     **config["program"])
    return SdarForCausalLM(cfg), cfg


def reference_config(config: dict) -> dict:
    out = {k: config[k] for k in REFERENCE_KEYS}
    out.update({k: config["assumed"][k] for k in GENERATION_KEYS})
    out["param_dtype"] = config["program"]["param_dtype"]
    return out
