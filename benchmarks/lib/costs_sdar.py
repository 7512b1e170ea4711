"""Bytes and operations the ALGORITHM needs in a decoder generated by
diffusion over blocks (`models/sdar`: grouped-query attention under a
block-causal mask, routed experts in every layer), from shapes and
counters. The yardstick of the roofline shares of
`sdar_blockgen_saturated`: padding is not counted, nor what a lowering
happens to move, nor the forwards the procedure spends (a tick's bytes
are what THAT tick's queries read). The experts are `ops/moe.py`'s as
in every other cell, every one held: the accepted `moe_*.longchat`
readers and `costs_qwen3next.moe_decode_bytes` read them here too.
(Beside `costs.py`, which a PR that adds a cell may not edit.)
"""

from __future__ import annotations

from benchmarks.lib.costs_sala import DTYPE_BYTES

#: the device scopes of the block's two reads and of the reveal
#: (fengshen_tpu/models/sdar, fengshen_tpu/serving/engine.py)
DECODE_SCOPE = "fstpu_block_decode_attention"
PREFILL_SCOPE = "fstpu_block_prefill_attention"
#: the reveal, and the head every tick runs over its `lanes x L` rows
REVEAL_SCOPES = ("fstpu_block_reveal", "/lm_head/")


def block_length(cfg: dict) -> int:
    return cfg["assumed"]["block_length"]


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def block_decode_bytes(attended: float, cfg: dict) -> float:
    """Bytes a tick's attention has to read: K and V of the `attended`
    tokens (summed over the live lanes, `cursor + L` each: the block's
    queries share one read of the lane), every layer."""
    return attended * kv_row_bytes(cfg) * cfg["num_hidden_layers"]


def visible_keys(position: int, cfg: dict) -> int:
    """Keys the query at `position` reads: every earlier block and the
    whole of its own."""
    L = block_length(cfg)
    return (position // L + 1) * L


def window_visible_pairs(start: int, tokens: int, cfg: dict) -> int:
    """Sum over the `tokens` real queries of a window that begins at
    position `start` of the keys each reads."""
    return sum(visible_keys(start + i, cfg) for i in range(tokens))


def block_prefill_flops(pairs: float, cfg: dict) -> float:
    """Operations the attention needs for `pairs` visible (query, key)
    pairs a layer: a score and a weighted value a head, 2 x 2 x
    head_dim."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs * \
        cfg["num_hidden_layers"]
