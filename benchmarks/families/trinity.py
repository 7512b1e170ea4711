"""How a `family: trinity` configuration file (arcee-ai's Trinity,
`model_type` `afmoe`: window layers with rotary positions beside full
layers without, gated grouped-query attention, sandwich norms, leading
dense layers, then sigmoid-routed experts with a shared one) becomes the
program's model through `models/trinity`, and which plain reference
stands beside it."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rope_theta",
              "rope_scaling", "sliding_window",
              "global_attn_every_n_layers", "layer_types",
              "num_dense_layers", "moe_intermediate_size",
              "num_experts_per_tok", "num_shared_experts", "score_func",
              "route_norm", "route_scale", "n_group", "topk_group",
              "num_expert_groups", "num_limited_groups",
              "load_balance_coeff", "use_grouped_mm", "mup_enabled",
              "max_position_embeddings", "rms_norm_eps", "hidden_act",
              "tie_word_embeddings")
REFERENCE = "benchmarks.references.trinity"
#: the keys the reference's mathematics reads
REFERENCE_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                  "num_hidden_layers", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "rope_theta",
                  "sliding_window", "layer_types", "num_dense_layers",
                  "moe_intermediate_size", "num_experts_per_tok",
                  "num_shared_experts", "route_norm", "route_scale",
                  "rms_norm_eps")


def _held(config: dict) -> tuple:
    """The file's `num_experts` counts the experts HELD here (`reduced`);
    the router keeps the published count, `router_width`."""
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise ValueError("num_experts counts the experts held: it is "
                         "experts_held's count")
    return first, count


def build(config: dict):
    from fengshen_tpu.models.trinity import (TrinityConfig,
                                             TrinityForCausalLM)
    if not config["mup_enabled"] or config["score_func"] != "sigmoid":
        raise ValueError("the reference multiplies the embedding by "
                         "sqrt(hidden_size) and routes by sigmoid scores")
    cfg = TrinityConfig(**{k: config[k] for k in MODEL_KEYS},
                        num_experts=config["router_width"],
                        experts_held=_held(config),
                        shared_here=config["shared_here"],
                        **config["program"])
    return TrinityForCausalLM(cfg), cfg


def reference_config(config: dict) -> dict:
    out = {k: config[k] for k in REFERENCE_KEYS}
    out["num_experts"] = config["router_width"]
    out["experts_held"] = list(_held(config))
    out["shared_here"] = config["shared_here"]
    out["param_dtype"] = config["program"]["param_dtype"]
    return out
