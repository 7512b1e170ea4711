"""Tiny stand-ins for the cells' files, for the CPU rehearsals: the
same keys as the real files, sizes a CPU holds. The tests lift the
harness's chip gate themselves; no option of the harness does."""

from __future__ import annotations

import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def gpt2(fsdp: int = 1) -> dict:
    c = _load("configs", "wenzhong-gpt2-3.5b")
    c.update(vocab_size=128, n_positions=32, n_embd=32, n_head=4,
             n_inner=64, n_layer=2, mesh={"fsdp": fsdp},
             reference={"rows_per_block": 2, "steps": 3})
    return c


def pretrain() -> dict:
    m = _load("traffic", "pretrain_packed_1k")
    m.update(seq=32, rows_per_chip=2, warm_steps=4)
    return m


def mistral() -> dict:
    c = _load("configs", "mistral-7b-v0.3")
    c.update(vocab_size=256, hidden_size=64, intermediate_size=128,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16,
             max_position_embeddings=256)
    c["engine_args"] = dict(c["engine_args"], num_slots=4, kv_block_size=16,
                            kv_num_blocks=33)
    return c


def doc() -> dict:
    m = copy.deepcopy(_load("traffic", "doc_closed_64"))
    m.update(clients=8, table_size=8,
             prompt_len={"dist": "log_uniform", "min": 8, "max": 32},
             output_len={"dist": "log_uniform", "min": 3, "max": 8},
             pairing={"stride": 3, "offset": 1},
             ramp={"stagger_s": 0.01, "open_after_completed": 8,
                   "every_lane_occupied": True},
             check={"sample": 3, "pad_to": 48})
    m["engine_args"] = {"buckets": [8, 16, 32], "max_new_tokens": 8,
                        "kv_max_blocks_per_slot": 3, "max_queue": 64}
    return m


def chat() -> dict:
    m = copy.deepcopy(_load("traffic", "chat_open_steady"))
    m.update(table_size=8,
             arrivals={"dist": "exponential", "rate_per_s": 20.0},
             prompt_len={"dist": "log_normal", "median": 12, "sigma": 0.6,
                         "min": 4, "max": 32},
             output_len={"dist": "log_normal", "median": 5, "sigma": 0.5,
                         "min": 2, "max": 8},
             pairing={"stride": 3, "offset": 1},
             ramp={"open_after_due": 8}, check={"sample": 3, "pad_to": 48})
    m["engine_args"] = {"buckets": [8, 16, 32], "max_new_tokens": 8,
                        "kv_max_blocks_per_slot": 3, "max_queue": 64}
    return m


SERVE_LIMITS = {"served_logit_gap": 0.05}
# the change of the parameters is held against a step that returns its
# state unchanged (gap 1.0): Adam's first updates are all but sign(g), so
# the leaves whose gradient is rounding noise (the key bias) move the
# sound runs' worst leaf to 0.13-0.15 at this size; three times that
# The first gradient's norm at this size: the program's worst leaf reads
# 0.0015-0.0039 over the seeds the tests use, the fp8 control
# 0.0083-0.0132; the limit lies between.
TRAIN_LIMITS = {"loss_gap": 0.01, "grad_norm_gap": 0.006,
                "change_norm_gap": 0.45}
