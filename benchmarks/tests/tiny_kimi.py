"""Tiny stand-ins for the files of `kimi_longreason_saturated`, for the
CPU rehearsal: the same keys as the real files, sizes a CPU holds
(beside `tiny.py`, which a PR that adds a cell may not edit)."""

from __future__ import annotations

import copy

from benchmarks.tests.tiny import _load


def kimi() -> dict:
    """The real file's five layers (kda, kda, kda, latent, kda), 8
    router outputs of which the first 4 are held."""
    c = _load("configs", "kimi-linear-48b-a3b")
    c.update(vocab_size=64, hidden_size=32, intermediate_size=64,
             moe_intermediate_size=16, num_attention_heads=4,
             num_key_value_heads=4, head_dim=8, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             linear_attn_config=dict(c["linear_attn_config"], head_dim=16,
                                     num_heads=4),
             num_experts=4, router_width=8, experts_held=[0, 4],
             num_experts_per_token=2, num_experts_per_tok=2,
             max_position_embeddings=256)
    c["engine_args"] = dict(c["engine_args"], num_slots=3, kv_block_size=32,
                            kv_num_blocks=25)
    return c


def longreason() -> dict:
    """Every prompt past the one bucket, as the real mix's are at or
    past 2048; the last window of each padded on the right."""
    m = copy.deepcopy(_load("traffic", "longreason_closed_96"))
    m.update(clients=4, table_size=8,
             prompt_len={"dist": "log_uniform", "min": 40, "max": 200},
             output_len={"dist": "log_uniform", "min": 3, "max": 8},
             pairing={"stride": 3, "offset": 1},
             ramp={"stagger_s": 0.01, "open_after_completed": 8,
                   "every_lane_occupied": True},
             check=dict(m["check"], sample=3, pad_to=224))
    m["engine_args"] = {"buckets": [32], "max_new_tokens": 8,
                        "kv_max_blocks_per_slot": 8, "max_queue": 64}
    return m


# bf16 program against the float32 reference at this size: the cell's
# statistic over every served token (`limits/kimi_longreason_saturated`
# says which) reads under a hundredth over the seeds the tests use; a
# token altered where it is produced reads ~0.1
SERVE_LIMITS = {"served_logit_gap": 0.05}
