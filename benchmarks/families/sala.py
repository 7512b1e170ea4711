"""How a `family: sala` configuration file (MiniCPM-SALA: InfLLM-V2
block-sparse attention in one layer of four, Lightning linear attention
in the rest, MiniCPM's scalings) becomes the program's model through
`models/sala`, and which plain reference stands beside it."""

from __future__ import annotations

MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "mixer_types", "num_attention_heads",
              "num_key_value_heads", "head_dim", "attn_use_rope",
              "attn_use_output_gate", "lightning_nh", "lightning_nkv",
              "lightning_head_dim", "lightning_scale", "lightning_use_rope",
              "use_output_norm", "use_output_gate", "qk_norm", "scale_emb",
              "scale_depth", "dim_model_base", "mup_denominator",
              "residual_depth", "max_position_embeddings", "rms_norm_eps",
              "rope_theta", "hidden_act", "attention_bias",
              "tie_word_embeddings")
#: the selection's sizes, which the published file does not carry
ASSUMED_KEYS = ("kernel_size", "kernel_stride", "block_size", "topk",
                "init_blocks", "window_size", "dense_len")
REFERENCE = "benchmarks.references.sala"
#: the keys the reference's mathematics reads
REFERENCE_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
                  "mixer_types", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "lightning_nh",
                  "lightning_head_dim", "rms_norm_eps", "rope_theta",
                  "scale_emb", "scale_depth", "dim_model_base",
                  "residual_depth")


def build(config: dict):
    from fengshen_tpu.models.sala import SalaConfig, SalaForCausalLM
    cfg = SalaConfig(**{k: config[k] for k in MODEL_KEYS},
                     **{k: config["assumed"][k] for k in ASSUMED_KEYS},
                     **config["program"])
    return SalaForCausalLM(cfg), cfg


def reference_config(config: dict) -> dict:
    out = {k: config[k] for k in REFERENCE_KEYS}
    out.update({k: config["assumed"][k] for k in ASSUMED_KEYS})
    out["mixer_types"] = list(out["mixer_types"])
    out["param_dtype"] = config["program"]["param_dtype"]
    return out
