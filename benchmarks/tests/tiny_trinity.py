"""Tiny stand-ins for the files of `trinity_mixedlen_saturated`, for the
CPU rehearsal: the same keys as the real files, sizes a CPU holds
(beside `tiny.py`, which a PR that adds a cell may not edit)."""

from __future__ import annotations

import copy

from benchmarks.tests.tiny import _load


def trinity() -> dict:
    c = _load("configs", "trinity-large-preview")
    c.update(vocab_size=128, hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             sliding_window=64, moe_intermediate_size=32, num_experts=4,
             router_width=8, experts_held=[0, 4], num_experts_per_tok=2,
             max_position_embeddings=256)
    c["engine_args"] = dict(c["engine_args"], num_slots=3, kv_block_size=32,
                            kv_num_blocks=25, kv_ring_num_blocks=10)
    return c


def mixedlen() -> dict:
    """Prompts under the tiny window 64 and past it in one queue, as the
    real mix's lie on both sides of 4,096; every one past the one
    bucket."""
    m = copy.deepcopy(_load("traffic", "mixedlen_closed_24"))
    m.update(clients=4, table_size=8,
             prompt_len={"dist": "log_uniform", "min": 40, "max": 200},
             output_len={"dist": "log_uniform", "min": 3, "max": 8},
             pairing={"stride": 3, "offset": 1},
             ramp={"stagger_s": 0.01, "open_after_completed": 8,
                   "every_lane_occupied": True},
             check=dict(m["check"], sample=3, pad_to=224))
    m["engine_args"] = {"buckets": [32], "max_new_tokens": 8,
                        "kv_max_blocks_per_slot": 8,
                        "kv_ring_blocks_per_slot": 3, "max_queue": 64}
    return m


# bf16 program against the float32 reference at this size: the cell's
# statistic over every served token (`limits/trinity_mixedlen_saturated`
# says which) reads under a hundredth over the seeds the tests use
SERVE_LIMITS = {"served_logit_gap": 0.05}
