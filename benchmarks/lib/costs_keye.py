"""Bytes and operations the ALGORITHM needs in a decoder whose every
layer's attention reads the single tokens a learned indexer chose, from
shapes and counters. The yardstick of the roofline shares of
`keye_longctx_saturated`: padding is not counted, nor what a lowering
happens to move (a masked walk over every cached key, a gather's
copies), nor scores a lowering takes for a query that has nothing to
choose. The experts' bytes are `costs_qwen3next.moe_decode_bytes`.
(Beside `costs.py`, which a PR that adds a cell may not edit.)
"""

from __future__ import annotations

from benchmarks.lib.costs_sala import DTYPE_BYTES


def selected_tokens(context: int, cfg: dict) -> int:
    """Tokens a query with `context` cached tokens (its own among them)
    reads in a layer: all of them within `topk`, else `topk`."""
    return min(context, cfg["sa_config"]["topk"])


def scored_tokens(context: int, cfg: dict) -> int:
    """Cached tokens a layer's indexer has to score for that query:
    every one, once there are more than `topk` to choose from."""
    return context if context > cfg["sa_config"]["topk"] else 0


def window_selected_tokens(start: int, tokens: int, cfg: dict) -> int:
    """Sum over the `tokens` real queries of a window that begins at
    position `start` of the tokens each reads."""
    return sum(selected_tokens(start + i + 1, cfg) for i in range(tokens))


def window_scored_pairs(start: int, tokens: int, cfg: dict) -> int:
    """Sum over the same queries of the keys each has to score."""
    return sum(scored_tokens(start + i + 1, cfg) for i in range(tokens))


def index_score_flops(pairs: float, cfg: dict) -> float:
    """Operations the indexer's scores need for `pairs` (query, key)
    pairs a layer: a product of `indexer_head_dim` a head, 2 x heads x
    head_dim (the ReLU, the weights and the sum over heads are
    `1 / head_dim` of that and left out)."""
    sa = cfg["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * \
        pairs * cfg["num_hidden_layers"]


def indexed_prefill_flops(selected: float, cfg: dict) -> float:
    """Operations the attention needs for queries that read `selected`
    chosen tokens between them, a layer: a score and a weighted value a
    head a token, 2 x 2 x head_dim."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * \
        selected * cfg["num_hidden_layers"]


def kv_row_bytes(cfg: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def index_row_bytes(cfg: dict) -> int:
    """The indexer's key of one token in one layer."""
    return cfg["sa_config"]["indexer_head_dim"] * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def indexed_decode_bytes(selected: float, scored: float, cfg: dict) -> float:
    """Bytes a tick's layers have to read: K and V of the `selected`
    tokens and the indexer's keys of the `scored` ones (both a layer's,
    summed over the live lanes, as the engine's two counters count
    them), every layer."""
    return cfg["num_hidden_layers"] * (
        selected * kv_row_bytes(cfg) + scored * index_row_bytes(cfg))
