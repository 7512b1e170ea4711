"""Bytes and operations the ALGORITHM needs in a decoder whose layers
are Kimi Delta Attention (a delta rule gated per key channel: two
constant states a lane) or latent attention without positions (one
latent row a token), with routed experts of which this chip holds a
share, from shapes and counters. The yardstick of the roofline shares
of `kimi_longreason_saturated`: padding is not counted (the row's 576
values, not the 640 the pool pads them to), nor what a lowering happens
to move. An expert's bytes are `costs_qwen3next.expert_bytes`, a full
layer's visible pairs `costs_trinity.full_prefill_pairs`.
(Beside `costs.py`, which a PR that adds a cell may not edit.)
"""

from __future__ import annotations

from benchmarks.lib import costs_trinity
from benchmarks.lib.costs_sala import DTYPE_BYTES, STATE_BYTES
from benchmarks.lib.costs_trinity import full_prefill_pairs  # noqa: F401

#: the device scopes of the two mixers (fengshen_tpu/ops): both gate
#: shapes of the delta rule run under the same three
KDA_PREFILL_SCOPE = "fstpu_gated_delta_prefill"
KDA_DECODE_SCOPES = ("fstpu_gated_delta_decode", "fstpu_short_conv")
MLA_DECODE_SCOPE = "fstpu_mla_decode_attention"
MLA_PREFILL_SCOPE = "fstpu_mla_prefill_attention"
MIXER_SCOPES = (KDA_PREFILL_SCOPE,) + KDA_DECODE_SCOPES + (
    MLA_DECODE_SCOPE, MLA_PREFILL_SCOPE)


def layer_counts(cfg: dict) -> tuple:
    """(latent layers, KDA layers, expert layers) of a configuration."""
    lin = cfg["linear_attn_config"]
    return (len(lin["full_attn_layers"]), len(lin["kda_layers"]),
            cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])


def kda_channels(cfg: dict) -> int:
    """Channels of q, of k, of v and of each gate: heads x head size."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def delta_state_bytes(cfg: dict) -> int:
    """One lane's delta state in one KDA layer: `[H, D, D]` float32."""
    return kda_channels(cfg) * cfg["linear_attn_config"]["head_dim"] * \
        STATE_BYTES


def conv_state_bytes(cfg: dict) -> int:
    """One lane's convolution state in one KDA layer: the last `K - 1`
    inputs over the `[q | k | v]` channels, in the program's dtype."""
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    return (taps - 1) * 3 * kda_channels(cfg) * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def kda_decode_bytes(live_lanes: float, cfg: dict) -> float:
    """Bytes one tick's KDA layers have to move: every live lane's two
    states read once and written once, a KDA layer. q, k, v and the
    gates are hundreds of times smaller and left out."""
    _, kda, _ = layer_counts(cfg)
    return live_lanes * kda * 2.0 * (delta_state_bytes(cfg) +
                                     conv_state_bytes(cfg))


def kda_prefill_floor_s(tokens: float, cfg: dict, peaks: dict) -> tuple:
    """(the least seconds the KDA layers' delta rule could take over
    `tokens` prompt tokens, which bound it is): the larger of its bytes
    (q, k, v, the gate's `H x D` values and the output gate in, o out:
    six rows of `H x D` a token a layer) over the memory's rate and of
    the recurrence's operations a token a head (the decay of the state's
    rows, `Dk x Dv`, and three products with it, `k S`, `k^T d`, `q S`:
    `6 x Dk x Dv`) over the peak. It does not depend on the chunk, the
    anchoring or the kernel a program picks."""
    _, kda, _ = layer_counts(cfg)
    lin = cfg["linear_attn_config"]
    moved = 6 * kda_channels(cfg) * DTYPE_BYTES[cfg["program"]["dtype"]]
    ops = 7 * lin["head_dim"] * lin["head_dim"] * lin["num_heads"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    by_ops = ops / peaks["bf16_flops_per_s"]
    return tokens * kda * max(by_bytes, by_ops), \
        "bytes" if by_bytes >= by_ops else "operations"


def latent_row_bytes(cfg: dict) -> int:
    """The values of a token's latent row the algorithm reads: the
    latent and the shared key part, key and value of every head at
    once."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * \
        DTYPE_BYTES[cfg["program"]["dtype"]]


def mla_decode_bytes(attended: float, cfg: dict) -> float:
    """Bytes a tick's latent layers have to read: one row for every real
    cached token of every live lane (`attended`: the engine's
    `fstpu_serving_kv_tokens_attended_total`, which counts a lane's
    tokens ONCE, one layer's worth), a latent layer."""
    latent, _, _ = layer_counts(cfg)
    return attended * latent_row_bytes(cfg) * latent


def mla_prefill_flops(pairs: float, cfg: dict) -> float:
    """Operations the latent layers' full form needs for `pairs`
    visible (query, key) pairs a layer: a score over `dn + dr` and a
    weighted value over `dv` a head a pair. The rows' expansion into
    heads (`rank x H x (dn + dv)` a key a window) is the lowering's, and
    left out."""
    latent, _, _ = layer_counts(cfg)
    per_pair = 2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) + \
        2 * cfg["v_head_dim"]
    return float(cfg["num_attention_heads"] * per_pair) * pairs * latent


def moe_prefill_floor_s(tokens: float, windows: int, cfg: dict,
                        peaks: dict) -> tuple:
    """`costs_trinity.moe_prefill_floor_s` under this family's keys: the
    larger of the held tables' bytes, each read once a window a layer,
    and of `6 x hidden x width` operations a held assignment."""
    return costs_trinity.moe_prefill_floor_s(tokens, windows, dict(
        cfg, num_dense_layers=cfg["first_k_dense_replace"],
        num_experts_per_tok=cfg["num_experts_per_token"]), peaks)


def cached_row_bytes(cfg: dict) -> int:
    """A token's row as the pool holds it: padded to whole 128-value
    lanes (576 -> 640), in the program's dtype."""
    width = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128
    return width * DTYPE_BYTES[cfg["program"]["dtype"]]


def cache_bytes_share(blocks: float, lanes: float, block_size: int,
                      cfg: dict) -> float:
    """Bytes this cache holds over what a latent row in EVERY layer
    would hold for the same cached tokens: `blocks` latent blocks in use
    (one latent layer's) and `lanes` live lanes' two states a KDA layer,
    against a row a token in all `num_hidden_layers` layers."""
    latent, kda, _ = layer_counts(cfg)
    rows = blocks * block_size * cached_row_bytes(cfg)
    states = lanes * kda * (delta_state_bytes(cfg) + conv_state_bytes(cfg))
    return (rows * latent + states) / (rows * cfg["num_hidden_layers"])
