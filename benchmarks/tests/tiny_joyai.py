"""Tiny stand-ins for the files of `joyai_reason_saturated`, for the CPU
rehearsal: the same keys as the real files, sizes a CPU holds (beside
`tiny.py`, which a PR that adds a cell may not edit)."""

from __future__ import annotations

import copy

from benchmarks.tests.tiny import _load


def joyai() -> dict:
    c = _load("configs", "joyai-llm-flash")
    c.update(vocab_size=256, hidden_size=64, intermediate_size=128,
             moe_intermediate_size=32, num_hidden_layers=3,
             num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
             v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
             max_position_embeddings=256,
             # two expert layers; about half the rows judged at this size
             pick_margin=[0.004, 0.004])
    c["engine_args"] = dict(c["engine_args"], num_slots=4, kv_block_size=16,
                            kv_num_blocks=33)
    return c


def reason() -> dict:
    m = copy.deepcopy(_load("traffic", "reason_closed_96"))
    m.update(clients=6, table_size=8,
             prompt_len={"dist": "log_uniform", "min": 8, "max": 32},
             output_len={"dist": "log_uniform", "min": 3, "max": 8},
             pairing={"stride": 3, "offset": 1},
             ramp={"stagger_s": 0.01, "open_after_completed": 8,
                   "every_lane_occupied": True},
             check={"sample": 3, "pad_to": 48})
    m["engine_args"] = {"buckets": [8, 16, 32], "max_new_tokens": 8,
                        "kv_max_blocks_per_slot": 3, "max_queue": 64}
    return m


# bf16 program against the float32 reference at this size: sound runs
# read 0.00-0.02 over the seeds the tests use, the int8 control 0.2-0.5
SERVE_LIMITS = {"served_logit_gap": 0.05}
