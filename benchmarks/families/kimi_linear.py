"""How a `family: kimi_linear` configuration file (moonshotai's
Kimi-Linear, `model_type` `kimi_linear`: Kimi Delta Attention layers, a
delta rule gated per key channel, beside latent-attention layers without
positions, one leading dense layer, then sigmoid-routed experts with a
shared one) becomes the program's model through `models/kimi_linear`,
and which plain reference stands beside it."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "linear_attn_config", "num_attention_heads",
              "num_key_value_heads", "head_dim", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "mla_use_nope", "rope_theta", "rope_scaling",
              "num_experts_per_token", "num_shared_experts",
              "first_k_dense_replace", "moe_layer_freq", "moe_renormalize",
              "moe_router_activation_func", "routed_scaling_factor",
              "num_expert_group", "topk_group", "use_grouped_topk",
              "num_nextn_predict_layers", "model_max_length",
              "max_position_embeddings", "rms_norm_eps", "hidden_act",
              "tie_word_embeddings")
REFERENCE = "benchmarks.references.kimi_linear"
#: the keys the reference's mathematics reads
REFERENCE_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                  "moe_intermediate_size", "num_hidden_layers",
                  "linear_attn_config", "num_attention_heads",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "num_experts_per_token",
                  "num_shared_experts", "first_k_dense_replace",
                  "moe_renormalize", "routed_scaling_factor", "rms_norm_eps")


def _held(config: dict) -> tuple:
    """The file's `num_experts` counts the experts HELD here (`reduced`);
    the router keeps the published count, `router_width`."""
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise ValueError("num_experts counts the experts held: it is "
                         "experts_held's count")
    return first, count


def build(config: dict):
    from fengshen_tpu.models.kimi_linear import (KimiLinearConfig,
                                                 KimiLinearForCausalLM)
    cfg = KimiLinearConfig(**{k: config[k] for k in MODEL_KEYS},
                           num_experts=config["router_width"],
                           experts_held=_held(config),
                           shared_here=config["shared_here"],
                           **config["program"])
    return KimiLinearForCausalLM(cfg), cfg


def reference_config(config: dict) -> dict:
    out = {k: config[k] for k in REFERENCE_KEYS}
    out["num_experts"] = config["router_width"]
    out["experts_held"] = list(_held(config))
    out["shared_here"] = config["shared_here"]
    out["param_dtype"] = config["program"]["param_dtype"]
    return out
