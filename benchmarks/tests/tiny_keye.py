"""Tiny stand-ins for the files of `keye_longctx_saturated`, for the CPU
rehearsal: the same keys as the real files, sizes a CPU holds (beside
`tiny.py`, which a PR that adds a cell may not edit)."""

from __future__ import annotations

import copy

from benchmarks.tests.tiny import _load


def keye() -> dict:
    c = _load("configs", "keye-vl-2.0-30b-a3b")
    c.update(vocab_size=128, hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
             num_experts=8, num_local_experts=8, num_experts_per_tok=2,
             max_position_embeddings=256)
    c["sa_config"] = dict(c["sa_config"], indexer_head_dim=8,
                          indexer_num_heads=4, topk=16)
    c["program"] = dict(c["program"], index_extent_step=64,
                        index_q_tile=16, index_k_tile=32)
    c["engine_args"] = dict(c["engine_args"], num_slots=3, kv_block_size=32,
                            kv_num_blocks=25)
    return c


def longctx() -> dict:
    """Every prompt past the tiny `topk` 16 and past the one bucket, as
    the real mix's are past 2,048 (both of them there)."""
    m = copy.deepcopy(_load("traffic", "longctx_closed_24"))
    m.update(clients=4, table_size=8,
             prompt_len={"dist": "log_uniform", "min": 100, "max": 200},
             output_len={"dist": "log_uniform", "min": 3, "max": 8},
             pairing={"stride": 3, "offset": 1},
             ramp={"stagger_s": 0.01, "open_after_completed": 8,
                   "every_lane_occupied": True},
             check={"sample": 3, "pad_to": 224, "own_matmul": "bf16"})
    m["engine_args"] = {"buckets": [32], "max_new_tokens": 8,
                        "kv_max_blocks_per_slot": 8, "max_queue": 64}
    return m


# bf16 program against the float32 reference at this size, the mean gap
# over every served token less the reference's own with bf16 operands
# (`lib/check_paired.py`): sound runs read -0.001-0.003 over the seeds
# the tests use
SERVE_LIMITS = {"served_logit_gap": 0.02}
