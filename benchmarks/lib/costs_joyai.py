"""Bytes the ALGORITHM needs in a decode tick of a latent-attention,
routed-expert decoder, from shapes and counters. The yardstick of the
two roofline shares of `joyai_reason_saturated`: padding is not
counted. (Beside `costs.py`, which a PR that adds a cell may not edit.)
"""

from __future__ import annotations

#: bytes a value of a configuration's `program` dtype takes
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}
#: the routed experts' own work on the trace: the scope (the sort, the
#: rows' gather, the unsort and weighted sum) and the grouped matmuls by
#: their own name: XLA:TPU lowers `jax.lax.ragged_dot` to a custom call
#: `ragged-dot-none*` whose `op_name` it drops (my chip run, PR 26)
EXPERT_SCOPES = ("fstpu_moe_experts", "%ragged-dot-none")
#: the latent read's scope (xla lowering; no Mosaic kernel yet)
MLA_DECODE_SCOPE = "fstpu_mla_decode_attention"


def moe_expert_bytes(hidden: int, width: int, weight_bytes: int) -> int:
    """One SwiGLU expert's three matrices."""
    return 3 * hidden * width * weight_bytes


def moe_decode_bytes(experts_touched: float, hidden: int, width: int,
                     weight_bytes: int) -> float:
    """Bytes one tick's routed experts have to read: the weights of
    every expert at least one live token picked, once, summed over the
    expert layers (`experts_touched` is that sum). The tokens' own rows
    are thousands of times smaller and left out."""
    return experts_touched * moe_expert_bytes(hidden, width, weight_bytes)


def mla_decode_attention_bytes(kv_tokens: float, latent: int, rope: int,
                               kv_bytes: int, layers: int) -> float:
    """Bytes one tick's latent attention has to read: one row of
    `latent + rope` values for every cached token of every live lane,
    once per layer. The row is key and value at once, and all heads
    share it."""
    return kv_tokens * (latent + rope) * kv_bytes * layers
