"""Bytes and operations the ALGORITHM needs in a decoder that mixes
WINDOW layers (a query reads the last `sliding_window` keys, its own
among them) with FULL layers, with routed experts of which this chip
holds a share, from shapes and counters. The yardstick of the roofline
shares of `trinity_mixedlen_saturated`: padding is not counted, nor
what a lowering happens to move (a band wider than the window, whole
blocks where the window ends inside one, a gather's copies). An
expert's bytes are `costs_qwen3next.expert_bytes`.
(Beside `costs.py`, which a PR that adds a cell may not edit.)
"""

from __future__ import annotations

from benchmarks.lib.costs_qwen3next import expert_bytes
from benchmarks.lib.costs_sala import DTYPE_BYTES

SLIDING, FULL = "sliding_attention", "full_attention"

#: the device scopes of the four reads (fengshen_tpu/ops/window_attention)
WINDOW_DECODE_SCOPE = "fstpu_window_decode_attention"
FULL_DECODE_SCOPE = "fstpu_full_decode_attention"
WINDOW_PREFILL_SCOPE = "fstpu_window_prefill_attention"
FULL_PREFILL_SCOPE = "fstpu_full_prefill_attention"
MIXER_SCOPES = (WINDOW_DECODE_SCOPE, FULL_DECODE_SCOPE,
                WINDOW_PREFILL_SCOPE, FULL_PREFILL_SCOPE)


def layers(cfg: dict, kind: str) -> int:
    return sum(t == kind for t in cfg["layer_types"])


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def window_tokens(context: int, cfg: dict) -> int:
    """Keys a window layer's query with `context` cached tokens (its
    own among them) reads."""
    return min(context, cfg["sliding_window"])


def window_decode_bytes(attended: float, cfg: dict) -> float:
    """Bytes a tick's window layers have to read: K and V of the
    `attended` keys (a layer's, summed over the live lanes, as
    `fstpu_serving_kv_window_tokens_attended_total` counts them), every
    window layer."""
    return attended * kv_row_bytes(cfg) * layers(cfg, SLIDING)


def full_decode_bytes(cached: float, cfg: dict) -> float:
    """The same for the full layers: K and V of every real cached token
    of every live lane (`fstpu_serving_kv_tokens_attended_total`)."""
    return cached * kv_row_bytes(cfg) * layers(cfg, FULL)


def window_prefill_pairs(start: int, tokens: int, cfg: dict) -> int:
    """Visible (query, key) pairs of a window layer over the `tokens`
    real queries of a prefill window that begins at position `start`."""
    return sum(window_tokens(start + i + 1, cfg) for i in range(tokens))


def full_prefill_pairs(start: int, tokens: int) -> int:
    """The same in a full layer: query `i` reads `start + i + 1` keys."""
    return tokens * start + tokens * (tokens + 1) // 2


def attn_flops(pairs: float, n_layers: int, cfg: dict) -> float:
    """Operations attention needs for `pairs` visible pairs a layer: a
    score and a weighted value a head a pair, 2 x 2 x head_dim."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs * \
        n_layers


def held_share(cfg: dict) -> float:
    """The share of a token's picks that lands on an expert held here
    when the router spreads them evenly: held over the router's width."""
    return cfg["num_experts"] / cfg["router_width"]


def moe_prefill_floor_s(tokens: float, windows: int, cfg: dict,
                        peaks: dict) -> tuple:
    """(the least seconds the routed experts of `windows` prefill
    windows holding `tokens` real tokens between them could take, which
    bound it is): the larger of the held tables' bytes, each read once a
    window a layer (a window of hundreds of tokens touches every held
    expert), over the memory's rate, and of the three products of the
    assignments that land here, `6 x hidden x width` operations each at
    an even spread of the picks, over the peak. The same whichever
    lowering runs."""
    n = expert_layers(cfg)
    by_bytes = windows * n * cfg["num_experts"] * expert_bytes(cfg) / \
        peaks["hbm_bytes_per_s"]
    held = tokens * cfg["num_experts_per_tok"] * held_share(cfg)
    by_ops = n * held * 6.0 * cfg["hidden_size"] * \
        cfg["moe_intermediate_size"] / peaks["bf16_flops_per_s"]
    return max(by_bytes, by_ops), \
        "bytes" if by_bytes >= by_ops else "operations"


def ring_bytes_share(blocks: float, ring_blocks: float, cfg: dict) -> float:
    """K/V bytes the two pools hold in use over what ONE table for all
    layers would: `blocks` of the lane-long kind (the full layers') and
    `ring_blocks` of the ring kind (the window layers'), against every
    layer behind the lane-long table."""
    f, w = layers(cfg, FULL), layers(cfg, SLIDING)
    return (blocks * f + ring_blocks * w) / (blocks * (f + w))
