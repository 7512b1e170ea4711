"""Tiny stand-ins for the files of `sdar_blockgen_saturated`, for the CPU
rehearsal: the same keys as the real files, sizes a CPU holds (beside
`tiny.py`, which a PR that adds a cell may not edit)."""

from __future__ import annotations

import copy

from benchmarks.tests.tiny import _load


def sdar() -> dict:
    c = _load("configs", "sdar-30b-a3b-chat")
    c.update(vocab_size=128, hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
             num_experts=8, num_experts_per_tok=2,
             max_position_embeddings=96)
    c["assumed"] = dict(c["assumed"], mask_token_id=127)
    c["engine_args"] = dict(c["engine_args"], num_slots=3, kv_block_size=16,
                            kv_num_blocks=19)
    return c


def blockgen() -> dict:
    """Prompts with every tail a block of 4 leaves, in one window; two
    tokens a forward, as the real mix."""
    m = copy.deepcopy(_load("traffic", "blockgen_closed_96"))
    m.update(clients=4, table_size=8,
             prompt_len={"dist": "log_uniform", "min": 9, "max": 30},
             output_len={"dist": "log_uniform", "min": 5, "max": 18},
             pairing={"stride": 3, "offset": 1},
             ramp={"stagger_s": 0.01, "open_after_completed": 8,
                   "every_lane_occupied": True},
             check={"sample": 3, "pad_to": 48, "own_matmul": "bf16"})
    m["engine_args"] = dict(m["engine_args"], buckets=[32],
                            max_new_tokens=18, kv_max_blocks_per_slot=6,
                            max_queue=64)
    return m


# bf16 program against the float32 reference at this size, the mean gap
# over every served token at its reveal step less the reference's own
# with bf16 operands (`lib/check_blocks.py`): sound runs read
# -0.001-0.002 over the seeds the tests use
SERVE_LIMITS = {"served_logit_gap": 0.02}
