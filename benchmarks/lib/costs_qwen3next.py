"""Bytes and operations the ALGORITHM needs in a decoder whose layers
are Gated DeltaNet (two constant states a lane) or gated softmax
attention (rows a token), with routed experts of which this chip holds
a share, from shapes and counters. The yardstick of the roofline shares
of `qwen3next_longchat_saturated`: padding is not counted, nor what a
lowering happens to move. (Beside `costs.py`, which a PR that adds a
cell may not edit.)
"""

from __future__ import annotations

# bytes of a `program` dtype's value; the delta state is float32
# whatever the program's dtype, as the linear layers' of `costs_sala`
from benchmarks.lib.costs_sala import DTYPE_BYTES, STATE_BYTES

#: the device scopes of the two mixers (fengshen_tpu/ops)
MIXER_SCOPES = ("fstpu_gated_delta_prefill", "fstpu_gated_delta_decode",
                "fstpu_short_conv", "fstpu_gated_attention_decode",
                "fstpu_gated_attention_prefill")
#: the experts' scopes, and the grouped matmuls by their own name
#: (XLA:TPU drops the `op_name` of a `ragged-dot` call)
MOE_SCOPES = ("fstpu_moe_route", "fstpu_moe_experts", "fstpu_moe_shared",
              "%ragged-dot")
#: what of them is the routed experts' own work
EXPERT_SCOPES = ("fstpu_moe_experts", "%ragged-dot")


def layer_counts(cfg: dict) -> tuple:
    """(full layers, linear layers) of a configuration."""
    full = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return full, cfg["num_hidden_layers"] - full


def conv_channels(cfg: dict) -> int:
    return 2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"] + \
        cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]


def delta_state_bytes(cfg: dict) -> int:
    """One lane's delta state in one linear layer: `[Hv, Dk, Dv]`
    float32."""
    return cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] * \
        cfg["linear_value_head_dim"] * STATE_BYTES


def conv_state_bytes(cfg: dict) -> int:
    """One lane's convolution state in one linear layer: the last
    `K - 1` inputs over the channels, in the program's dtype."""
    return (cfg["linear_conv_kernel_dim"] - 1) * conv_channels(cfg) * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def gdn_decode_bytes(live_lanes: float, cfg: dict) -> float:
    """Bytes one tick's linear layers have to move: every live lane's
    two states read once and written once, a linear layer. q, k, v are
    hundreds of times smaller and left out."""
    _, linear = layer_counts(cfg)
    return live_lanes * linear * 2.0 * (delta_state_bytes(cfg) +
                                        conv_state_bytes(cfg))


def gdn_prefill_floor_s(tokens: float, cfg: dict, peaks: dict) -> tuple:
    """(the least seconds the linear layers' mixer could take over
    `tokens` prompt tokens, which bound it is): the larger of its bytes
    (q, k, v in, the gate z in and o out, a token a layer) over the
    memory's rate and of the recurrence's operations (three products
    with the `[Dk, Dv]` state a token a value head: `k S`, `k^T d`, `q
    S`, 6 x Dk x Dv) over the peak. It does not depend on the chunk a
    program picks."""
    _, linear = layer_counts(cfg)
    value = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    moved = (conv_channels(cfg) + 2 * value) * \
        DTYPE_BYTES[cfg["program"]["dtype"]]
    ops = 6 * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] * \
        cfg["linear_num_value_heads"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    by_ops = ops / peaks["bf16_flops_per_s"]
    return tokens * linear * max(by_bytes, by_ops), \
        "bytes" if by_bytes >= by_ops else "operations"


def cached_token_bytes(cfg: dict) -> int:
    """K and V of one token over the full layers."""
    full, _ = layer_counts(cfg)
    return 2 * full * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def attn_decode_bytes(kv_tokens: float, cfg: dict) -> float:
    """Bytes a tick's full layers have to read: K and V of every real
    cached token of every live lane (`kv_tokens` is that sum)."""
    return kv_tokens * cached_token_bytes(cfg)


def attn_prefill_flops(start: int, tokens: int, cfg: dict) -> float:
    """Operations the full layers' attention needs for the `tokens`
    real queries of a window that begins at position `start`: query `i`
    reads `start + i + 1` keys, a score and a weighted value a head a
    key, 2 x 2 x head_dim."""
    full, _ = layer_counts(cfg)
    keys = tokens * start + tokens * (tokens + 1) // 2
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * keys * full


def expert_bytes(cfg: dict) -> int:
    """One SwiGLU expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * \
        DTYPE_BYTES[cfg["program"]["param_dtype"]]


def moe_decode_bytes(experts_touched: float, cfg: dict) -> float:
    """Bytes one tick's routed experts have to read: the weights of
    every HELD expert at least one live token picked, once, summed over
    the layers (`experts_touched` is that sum)."""
    return experts_touched * expert_bytes(cfg)
