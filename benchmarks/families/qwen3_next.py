"""How a `family: qwen3_next` configuration file (Qwen3-Next: Gated
DeltaNet in three layers of four, gated softmax attention in the
fourth, softmax-routed experts with a gated shared expert in every
layer) becomes the program's model through `models/qwen3_next`, and
which plain reference stands beside it."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "partial_rotary_factor",
              "rope_theta", "rope_scaling", "full_attention_interval",
              "use_sliding_window", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim",
              "decoder_sparse_step", "mlp_only_layers",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok", "norm_topk_prob",
              "max_position_embeddings", "rms_norm_eps", "hidden_act",
              "tie_word_embeddings")
REFERENCE = "benchmarks.references.qwen3_next"
#: the keys the reference's mathematics reads
REFERENCE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
                  "num_attention_heads", "num_key_value_heads", "head_dim",
                  "partial_rotary_factor", "rope_theta",
                  "full_attention_interval", "linear_num_key_heads",
                  "linear_num_value_heads", "linear_key_head_dim",
                  "linear_value_head_dim", "linear_conv_kernel_dim",
                  "moe_intermediate_size", "shared_expert_intermediate_size",
                  "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")


def _held(config: dict) -> tuple:
    """The file's `num_experts` counts the experts HELD here (`reduced`);
    the router keeps the published count, `router_width`."""
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise ValueError("num_experts counts the experts held: it is "
                         "experts_held's count")
    return first, count


def build(config: dict):
    from fengshen_tpu.models.qwen3_next import (Qwen3NextConfig,
                                                Qwen3NextForCausalLM)
    cfg = Qwen3NextConfig(**{k: config[k] for k in MODEL_KEYS},
                          num_experts=config["router_width"],
                          experts_held=_held(config),
                          shared_here=config["shared_here"],
                          **config["program"])
    return Qwen3NextForCausalLM(cfg), cfg


def reference_config(config: dict) -> dict:
    out = {k: config[k] for k in REFERENCE_KEYS}
    out["num_experts"] = config["router_width"]
    out["experts_held"] = list(_held(config))
    out["shared_here"] = config["shared_here"]
    out["param_dtype"] = config["program"]["param_dtype"]
    return out
