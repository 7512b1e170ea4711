"""Model-FLOPs estimator + per-chip peak table: the MFU denominator.

MFU (model-FLOPs-utilization, the PaLM accounting) = achieved model
FLOP/s over peak hardware FLOP/s:

    mfu = tokens_per_sec * flops_per_token / (peak_per_chip * n_chips)

``estimate_flops_per_token`` counts the PARAMETER matmul FLOPs of one
token through a dense decoder (2 FLOPs per multiply-add, x3 for
forward+backward), from the model config alone:

    per_layer = 2*h*h (q+o) + 2*h*(kv_heads*head_dim) (k+v, GQA-aware)
              + 3*h*inter (gate/up/down)
    per_token = mult * (layers * per_layer + h * vocab)   # mult: 6 train, 2 infer

Assumptions (documented in docs/observability.md): attention
score/value FLOPs (the O(seq) term) are excluded, as are norms,
embeddings-as-lookup, and activation functions — the standard "6N"
family of approximations, exact enough that MFU deltas track real
optimization work. For a non-GQA model this reduces to the familiar
``6*(l*(4h^2 + 3*h*inter) + h*v)``.

Peak FLOP/s comes from the table below (bf16), keyed by the
``device_kind`` jax reports. An accelerator that is not in the table
is an error, not a default: a made-up peak makes a made-up MFU. The
CPU backend alone gets a nominal figure, so that MFU stays FINITE and
monotonic in CI runs; a CPU MFU is never a hardware claim.
"""

from __future__ import annotations

from typing import Any, Optional

#: peak bf16 FLOP/s per chip (the table that lived in trainer.py;
#: trainer re-exports it for compatibility). Source: Google Cloud TPU
#: documentation, per-generation system architecture pages. The v5e
#: reports itself as "TPU v5 lite" (libtpu 0.0.34, PR 21 chip run).
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

#: nominal figure for the CPU backend (CI runs): a round 1 TFLOP/s so
#: mfu is finite and comparable run-to-run on the same host
CPU_NOMINAL_FLOPS = 1e12


def peak_flops_per_chip(device_kind: Optional[str] = None) -> float:
    """Peak FLOP/s for one chip of ``device_kind`` (default: the first
    visible jax device). Raises for an accelerator the table does not
    know."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind in PEAK_FLOPS:
        return PEAK_FLOPS[device_kind]
    if device_kind.lower() == "cpu":
        return CPU_NOMINAL_FLOPS
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}; add "
        f"it to PEAK_FLOPS with its source (known: {sorted(PEAK_FLOPS)})")


def estimate_flops_per_token(config: Any,
                             include_backward: bool = True
                             ) -> Optional[float]:
    """FLOPs one token costs through the model described by ``config``
    (6x params-touched for training, 2x for inference). Returns None
    when the config doesn't describe a dense decoder LM (no
    hidden_size/num_hidden_layers) — callers treat None as "estimator
    doesn't support this model" and omit mfu."""
    h = getattr(config, "hidden_size", None)
    layers = getattr(config, "num_hidden_layers", None)
    if not h or not layers:
        return None
    inter = getattr(config, "intermediate_size", None) or 4 * h
    vocab = getattr(config, "vocab_size", 0) or 0
    heads = getattr(config, "num_attention_heads", None) or 1
    kv_heads = getattr(config, "num_key_value_heads", None) or heads
    head_dim = h // heads
    per_layer = (2 * h * h                       # q_proj + o_proj
                 + 2 * h * (kv_heads * head_dim)  # k_proj + v_proj (GQA)
                 + 3 * h * inter)                # gate + up + down
    mult = 6.0 if include_backward else 2.0
    return mult * (layers * per_layer + h * vocab)
