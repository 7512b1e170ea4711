"""Bytes and operations the ALGORITHM needs in a decoder whose layers
are block-sparse attention (rows a token, a chosen subset read) or
linear attention (a constant state a lane), from shapes and counters.
The yardstick of the four roofline shares of `sala_longdoc_saturated`:
padding is not counted, nor what a lowering happens to move. (Beside
`costs.py`, which a PR that adds a cell may not edit.)
"""

from __future__ import annotations

#: bytes a value of a configuration's `program` dtype takes
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
#: the linear layers' state is float32 whatever the program's dtype
STATE_BYTES = 4
#: the device scopes of the two mixers (fengshen_tpu/ops)
MIXER_SCOPES = ("fstpu_lightning_prefill", "fstpu_lightning_decode",
                "fstpu_sparse_pool", "fstpu_sparse_select",
                "fstpu_sparse_decode_attention",
                "fstpu_sparse_prefill_attention")


def layer_counts(cfg: dict) -> tuple:
    """(sparse layers, linear layers) of a configuration."""
    kinds = cfg["mixer_types"]
    return kinds.count("minicpm4"), kinds.count("lightning-attn")


def attended_tokens(context: int, cfg: dict) -> int:
    """Tokens a sparse layer's query with `context` cached tokens (its
    own among them) reads: everything up to `dense_len`, past it `topk`
    blocks, all full but its own."""
    a = cfg["assumed"]
    if context <= a["dense_len"]:
        return context
    own = (context - 1) % a["block_size"] + 1
    return (a["topk"] - 1) * a["block_size"] + own


def state_bytes_a_lane(cfg: dict) -> int:
    """One lane's recurrent state: `[heads, dim, dim]` float32 a linear
    layer."""
    _, linear = layer_counts(cfg)
    d = cfg["lightning_head_dim"]
    return linear * cfg["lightning_nh"] * d * d * STATE_BYTES


def linear_decode_bytes(live_lanes: float, cfg: dict) -> float:
    """Bytes one tick's linear layers have to move: every live lane's
    state read once and written once. q, k, v are thousands of times
    smaller and left out."""
    return 2.0 * live_lanes * state_bytes_a_lane(cfg)


def cached_token_bytes(cfg: dict) -> int:
    """K and V of one token over the sparse layers."""
    sparse, _ = layer_counts(cfg)
    return 2 * sparse * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def pooled_token_bytes(cfg: dict) -> float:
    """One token's share of the pooled keys over the sparse layers: a
    key row every `kernel_stride` tokens."""
    return cached_token_bytes(cfg) / 2 / cfg["assumed"]["kernel_stride"]


def sparse_decode_bytes(attended: float, cached: float, cfg: dict) -> float:
    """Bytes a tick's sparse layers have to read: K and V of the
    `attended` tokens of the chosen blocks, and the pooled keys of the
    `cached` tokens the choice is made over (both summed over the live
    lanes)."""
    return attended * cached_token_bytes(cfg) + \
        cached * pooled_token_bytes(cfg)


def sparse_prefill_flops(chosen_tokens: float, cfg: dict) -> float:
    """Operations the sparse layers' attention needs for queries that
    read `chosen_tokens` tokens between them: a score and a weighted
    value a head a token, 2 x 2 x head_dim."""
    sparse, _ = layer_counts(cfg)
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * \
        chosen_tokens * sparse


def window_chosen_tokens(start: int, tokens: int, cfg: dict) -> int:
    """Sum over the `tokens` real queries of a window that begins at
    position `start` of the tokens each reads."""
    return sum(attended_tokens(start + i + 1, cfg) for i in range(tokens))


def linear_prefill_floor_s(tokens: float, cfg: dict, peaks: dict) -> float:
    """The least seconds the linear layers' attention could take over
    `tokens` prompt tokens: the larger of its bytes (q, k, v in and o
    out, a token a layer) over the memory's rate and of the
    recurrence's operations (a decayed outer product into the state and
    a product out of it, 4 x dim x dim a token a head) over the peak.
    It does not depend on the chunk a program picks."""
    _, linear = layer_counts(cfg)
    heads, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    moved = 4 * heads * d * DTYPE_BYTES[cfg["program"]["dtype"]]
    ops = 4 * d * d * heads
    return tokens * linear * max(moved / peaks["hbm_bytes_per_s"],
                                 ops / peaks["bf16_flops_per_s"])
