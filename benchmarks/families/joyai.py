"""How a `family: joyai` configuration file (JoyAI-LLM-Flash, the
DeepSeek-V3 layer: latent attention, one dense layer, then sigmoid-routed
experts with a shared one) becomes the program's model through
`models/joyai`, and which plain reference stands beside it."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
              "first_k_dense_replace", "moe_layer_freq", "n_group",
              "topk_group", "norm_topk_prob", "routed_scaling_factor",
              "scoring_func", "topk_method", "num_nextn_predict_layers",
              "max_position_embeddings", "rms_norm_eps", "rope_theta",
              "rope_interleave", "rope_scaling", "tie_word_embeddings")
REFERENCE = "benchmarks.references.joyai"
#: the keys the reference's mathematics reads
REFERENCE_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                  "moe_intermediate_size", "num_hidden_layers",
                  "num_attention_heads", "q_lora_rank", "kv_lora_rank",
                  "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                  "n_routed_experts", "n_shared_experts",
                  "num_experts_per_tok", "norm_topk_prob",
                  "routed_scaling_factor", "rms_norm_eps", "rope_theta")


def build(config: dict):
    from fengshen_tpu.models.joyai import JoyAIConfig, JoyAIForCausalLM
    if config["qk_head_dim"] != config["qk_nope_head_dim"] + \
            config["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    if config["scoring_func"] != "sigmoid" or not config["rope_interleave"]:
        raise ValueError("the reference is the sigmoid router with "
                         "interleaved rope dims")
    cfg = JoyAIConfig(**{k: config[k] for k in MODEL_KEYS},
                      experts_held=config.get("experts_held"),
                      **config["program"])
    return JoyAIForCausalLM(cfg), cfg


def reference_config(config: dict) -> dict:
    out = {k: config[k] for k in REFERENCE_KEYS}
    out["param_dtype"] = config["program"]["param_dtype"]
    if config.get("experts_held"):
        out["experts_held"] = list(config["experts_held"])
    if config.get("pick_margin"):
        # the rows the float32 reference judges (references/joyai.py)
        out["pick_margin"] = list(config["pick_margin"])
    return out
