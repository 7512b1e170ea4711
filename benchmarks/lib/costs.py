"""Operations and bytes the ALGORITHM needs, from shapes. The yardstick
for utilization and roofline shares: recomputation is not counted, and
neither is padding.
"""

from __future__ import annotations


def gpt2_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward of a GPT-2 decoder with a tied head, per
    token of a full `seq`-token causal row: 6 per matmul weight, plus
    causal attention's two [S, S/2 on average] products per layer
    (QK^T and PV: 2 * 2 * E * S/2 forward, three times that with the
    backward pass)."""
    E, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    inner = cfg.get("n_inner") or 4 * E
    weights = L * (3 * E * E + E * E + 2 * E * inner) + V * E
    attention = L * 2 * E * seq           # 2 products x 2 flops x E x S/2
    return 6.0 * weights + 3.0 * attention


def decode_attention_bytes(kv_tokens: int, kv_heads: int, head_dim: int,
                           kv_bytes: int, layers: int) -> int:
    """Bytes one decode tick's attention has to read: K and V of every
    cached token of every live lane, once per layer."""
    return 2 * kv_tokens * kv_heads * head_dim * kv_bytes * layers
