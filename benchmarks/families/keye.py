"""How a `family: keye` configuration file (Keye-VL-2.0's language
model: the Qwen3-MoE block with a learned indexer that picks the single
tokens a query reads, in every layer) becomes the program's model
through `models/keye`, and which plain reference stands beside it."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rope_theta",
              "rope_scaling", "sa_config", "sliding_window",
              "use_sliding_window", "max_window_layers",
              "decoder_sparse_step", "mlp_only_layers",
              "moe_intermediate_size", "num_experts", "num_local_experts",
              "num_experts_per_tok", "norm_topk_prob",
              "max_position_embeddings", "rms_norm_eps", "hidden_act",
              "attention_bias", "tie_word_embeddings")
REFERENCE = "benchmarks.references.keye"
#: the keys the reference's mathematics reads (the indexer's sizes out
#: of `sa_config` beside them)
REFERENCE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
                  "num_attention_heads", "num_key_value_heads", "head_dim",
                  "rope_theta", "moe_intermediate_size", "num_experts",
                  "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")
INDEXER_KEYS = ("indexer_num_heads", "indexer_head_dim", "topk")


def build(config: dict):
    from fengshen_tpu.models.keye import KeyeConfig, KeyeForCausalLM
    cfg = KeyeConfig(**{k: config[k] for k in MODEL_KEYS},
                     **config["program"])
    return KeyeForCausalLM(cfg), cfg


def reference_config(config: dict) -> dict:
    out = {k: config[k] for k in REFERENCE_KEYS}
    out.update({k: config["sa_config"][k] for k in INDEXER_KEYS})
    out["param_dtype"] = config["program"]["param_dtype"]
    return out
