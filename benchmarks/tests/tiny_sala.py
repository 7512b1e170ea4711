"""Tiny stand-ins for the files of `sala_longdoc_saturated`, for the CPU
rehearsal: the same keys as the real files, sizes a CPU holds (beside
`tiny.py`, which a PR that adds a cell may not edit)."""

from __future__ import annotations

import copy

from benchmarks.tests.tiny import _load


def sala() -> dict:
    c = _load("configs", "minicpm-sala")
    c.update(vocab_size=128, hidden_size=64, intermediate_size=128,
             num_hidden_layers=4,
             mixer_types=["minicpm4"] + ["lightning-attn"] * 3,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
             dim_model_base=16, residual_depth=4,
             max_position_embeddings=256)
    c["assumed"] = dict(c["assumed"], kernel_size=8, kernel_stride=4,
                        block_size=16, topk=6, init_blocks=1,
                        window_size=32, dense_len=96)
    c["engine_args"] = dict(c["engine_args"], num_slots=3, kv_block_size=32,
                            kv_num_blocks=25)
    return c


def longdoc() -> dict:
    """Every prompt past the tiny `dense_len` 96 and past the one
    bucket, as the real mix's are past 8192 and 2048."""
    m = copy.deepcopy(_load("traffic", "longdoc_closed_24"))
    m.update(clients=4, table_size=8,
             prompt_len={"dist": "log_uniform", "min": 100, "max": 200},
             output_len={"dist": "log_uniform", "min": 3, "max": 8},
             pairing={"stride": 3, "offset": 1},
             ramp={"stagger_s": 0.01, "open_after_completed": 8,
                   "every_lane_occupied": True},
             check={"sample": 3, "pad_to": 224})
    m["engine_args"] = {"buckets": [32], "max_new_tokens": 8,
                        "kv_max_blocks_per_slot": 8, "max_queue": 64}
    return m


# bf16 program against the float32 reference at this size: sound runs
# read 0.00-0.01 over the seeds the tests use
SERVE_LIMITS = {"served_logit_gap": 0.05}
