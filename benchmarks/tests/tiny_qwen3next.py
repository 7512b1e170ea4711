"""Tiny stand-ins for the files of `qwen3next_longchat_saturated`, for
the CPU rehearsal: the same keys as the real files, sizes a CPU holds
(beside `tiny.py`, which a PR that adds a cell may not edit)."""

from __future__ import annotations

import copy

from benchmarks.tests.tiny import _load


def qwen3next() -> dict:
    """One period, 8 router outputs of which the first 4 are held."""
    c = _load("configs", "qwen3-next-80b-a3b")
    c.update(vocab_size=64, hidden_size=32, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=16, linear_value_head_dim=16,
             moe_intermediate_size=16, shared_expert_intermediate_size=16,
             num_experts=4, router_width=8, experts_held=[0, 4],
             num_experts_per_tok=2, max_position_embeddings=256)
    c["engine_args"] = dict(c["engine_args"], num_slots=3, kv_block_size=32,
                            kv_num_blocks=25)
    return c


def longchat() -> dict:
    """Every prompt past the one bucket, as most of the real mix's are
    past 2048; the last window of each padded on the right."""
    m = copy.deepcopy(_load("traffic", "longchat_closed_96"))
    m.update(clients=4, table_size=8,
             prompt_len={"dist": "log_uniform", "min": 40, "max": 200},
             output_len={"dist": "log_uniform", "min": 3, "max": 8},
             pairing={"stride": 3, "offset": 1},
             ramp={"stagger_s": 0.01, "open_after_completed": 8,
                   "every_lane_occupied": True},
             check={"sample": 3, "pad_to": 224})
    m["engine_args"] = {"buckets": [32], "max_new_tokens": 8,
                        "kv_max_blocks_per_slot": 8, "max_queue": 64}
    return m


# the MEAN gap (lib/check_mean.py) of the bf16 program under the float32
# reference at this size: sound runs read 0.0000-0.0005 over the seeds
# the tests use; a token altered where it is produced reads ~0.1
SERVE_LIMITS = {"served_logit_gap": 0.005}
