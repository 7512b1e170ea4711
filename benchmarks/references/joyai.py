"""Plain JoyAI-LLM-Flash block stack (jdopensource/JoyAI-LLM-Flash,
`model_type` `joyai_llm_flash`; HF `deepseek_v3` modeling semantics as
the config states them): pre-norm residual blocks, eps 1e-6, of

- multi-head latent attention in its FULL form: `c_q = RMSNorm(h
  W_qa)`, `q = c_q W_qb` -> 32 heads of `[q_nope 128 | q_rope 64]`;
  `[c_kv 512 | k_r 64] = h W_kva`, `c_kv = RMSNorm(c_kv)`, `k_rope =
  RoPE(k_r)` one head shared by all; `[k_nope_i | v_i] = c_kv W_kvb`
  per head; RoPE (theta 32e6, `rope_interleave`: the 64 dims viewed as
  32 adjacent pairs, moved to `[2, 32]`, then rotate-half; no scaling)
  on the rope dims; scores `(q_nope.k_nope + q_rope.k_rope) / sqrt(192)`,
  causal softmax, `concat_i(sum p v_i) W_o`;
- a feed-forward that is a SwiGLU of width 7168 in layer 0 and, after
  it, `s = sigmoid(h W_g)` over 256 experts, the 8 largest of `s + b`
  picked, their weights the picked `s` (no bias) over `(sum + 1e-20)`
  times 2.5, `y = sum_k w_k E_k(h) + E_shared(h)`, experts SwiGLU of
  width 768;

a final RMSNorm and an untied head: one full forward pass over a whole
sequence in float32 `jax.numpy`. No cache, no kernels, no sort, no
absorbed form: every token goes through every expert of a block of
experts, and a dense `[tokens, experts]` matrix that is 0 off the picks
weighs the sum. Imports nothing of the program; its parameters come
from `lib.weights` under the names below, a tree of its own a layer
(the program's unrolled layout), in the type they are served in and
raised to float32 where they are used.

Departures from the published description: none in the mathematics.
The multi-token-prediction module (`num_nextn_predict_layers` 1) is
absent: the published serving code does not load it and it feeds no
logit. Layers are walked one at a time and experts in blocks of
`expert_block`, so no layer's float32 copy is ever whole (one expert
layer in float32 is 4.96 GB beside 11.1 GB of bf16 parameters).
`experts_held = [first, count]` gives the reference the same share of
an expert-parallel deployment as the program: what the absent experts
would have added is left out.

Which rows it judges. A token's 8 experts are a discontinuous function
of its hidden state: where the 8th and 9th largest of `s + b` lie
closer than the configuration's own rounding moves them, bf16 and
float32 pick different experts, both are right, and the token's logits
differ by a third of a layer's output whatever the precision. With
`pick_margin` (one threshold an expert layer, in units of `s + b`) the
float32 pass answers only for rows whose pick is clear of a tie by
that much in EVERY expert layer; for the other rows it abstains: the
row comes back all zeros, so every token is as good as the best there
and the comparison reads a gap of 0. The thresholds are a reading
(limits/<cell>.json), not a choice by eye: twice the largest margin at
which the bf16 program was ever seen to pick differently. A pass in
another precision (a control standing where the program would) never
abstains. Without the key every row is judged (the tier-1 tests).
"""

from __future__ import annotations

import functools
import json
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.lib.runlog import say
from benchmarks.references.common import HIGHEST, MATMULS



def layer_prefix(i: int) -> str:
    return f"model/layers_{i}/"


#: experts whose float32 copies are alive at once
EXPERT_BLOCK = 16
#: a gap this wide is a different pick's, not rounding's (the log line
#: of `_judged`; decides nothing)
FAR_OFF = 0.05


def _attention_shapes(cfg: dict, lead: tuple) -> dict:
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    w, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    return {
        "input_layernorm/scale": (lead + (E,), f32),
        "post_attention_layernorm/scale": (lead + (E,), f32),
        "self_attn/q_a_proj/kernel": (lead + (E, qr), w),
        "self_attn/q_a_layernorm/scale": (lead + (qr,), f32),
        "self_attn/q_b_proj/kernel": (lead + (qr, H * (dn + dr)), w),
        "self_attn/kv_a_proj_with_mqa/kernel": (lead + (E, rank + dr), w),
        "self_attn/kv_a_layernorm/scale": (lead + (rank,), f32),
        "self_attn/kv_b_proj/kernel": (lead + (rank, H * (dn + dv)), w),
        "self_attn/o_proj/kernel": (lead + (H * dv, E), w),
    }


def _swiglu_shapes(prefix: str, E: int, inner: int, lead: tuple, w) -> dict:
    return {prefix + "gate_proj/kernel": (lead + (E, inner), w),
            prefix + "up_proj/kernel": (lead + (E, inner), w),
            prefix + "down_proj/kernel": (lead + (inner, E), w)}


def held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held") or (0, cfg["n_routed_experts"]))


def param_shapes(cfg: dict) -> dict:
    L, E, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    F, n = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    count = held(cfg)[1]
    w, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    first = {**_attention_shapes(cfg, ()),
             **_swiglu_shapes("mlp/", E, cfg["intermediate_size"], (), w)}
    rest = {**_attention_shapes(cfg, ()),
            "mlp/router/kernel": ((E, n), f32),
            "mlp/e_score_correction_bias": ((n,), f32),
            "mlp/experts_gate": ((count, E, F), w),
            "mlp/experts_up": ((count, E, F), w),
            "mlp/experts_down": ((count, F, E), w),
            **_swiglu_shapes("mlp/shared_experts/", E,
                             F * cfg["n_shared_experts"], (), w)}
    out = {"lm_head/kernel": ((E, V), w),
           "model/embed_tokens/embedding": ((V, E), w),
           "model/norm/scale": ((E,), f32)}
    for i in range(L):
        out.update({layer_prefix(i) + k: v
                    for k, v in (rest if i else first).items()})
    return out


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_interleaved(x, theta):
    # x: [B, S, H, D], positions 0..S-1. The published code first moves
    # the D/2 adjacent pairs to the rotate-half layout, then rotates.
    B, S, H, D = x.shape
    x = x.reshape(B, S, H, D // 2, 2).swapaxes(-1, -2).reshape(B, S, H, D)
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(cfg, mm, x, lp):
    """`x + Attn(RMSNorm(x))`, full form."""
    B, S, _ = x.shape
    H, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms(x, lp["input_layernorm/scale"], eps)
    c_q = _rms(mm(h, lp["self_attn/q_a_proj/kernel"]),
               lp["self_attn/q_a_layernorm/scale"], eps)
    q = mm(c_q, lp["self_attn/q_b_proj/kernel"]).reshape(B, S, H, dn + dr)
    ckv = mm(h, lp["self_attn/kv_a_proj_with_mqa/kernel"])
    c_kv = _rms(ckv[..., :rank], lp["self_attn/kv_a_layernorm/scale"], eps)
    k_rope = _rope_interleaved(ckv[:, :, None, rank:], theta)   # [B,S,1,dr]
    q_rope = _rope_interleaved(q[..., dn:], theta)
    kv = mm(c_kv, lp["self_attn/kv_b_proj/kernel"]).reshape(
        B, S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))], -1)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(dn + dr))
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, kv[..., dn:],
                   precision=HIGHEST)
    return x + mm(a.reshape(B, S, H * dv), lp["self_attn/o_proj/kernel"])


def _swiglu(mm, h, lp, prefix):
    gate = jax.nn.silu(mm(h, lp[prefix + "gate_proj/kernel"]))
    return mm(gate * mm(h, lp[prefix + "up_proj/kernel"]),
              lp[prefix + "down_proj/kernel"])


def pick_weights(cfg, scores, bias):
    """`[T, n]`: token t's weight on expert e, 0 where e is not one of
    its picks. The picks are the `num_experts_per_tok` largest of
    `scores + bias`; the weights come from `scores` alone."""
    k = cfg["num_experts_per_tok"]
    _, index = jax.lax.top_k(scores + bias, k)
    picked = jax.nn.one_hot(index, scores.shape[-1], dtype=jnp.float32) \
        .sum(axis=-2)                                    # [T, n] 0/1
    weights = scores * picked
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * cfg["routed_scaling_factor"]


def pick_margin(cfg, scores, bias):
    """`[T]`: how far the last expert picked lies above the first one
    left out, in units of `scores + bias`."""
    top, _ = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"] + 1)
    return top[:, -2] - top[:, -1]


def routed(cfg, mm, h, lp, shared: bool = True):
    """The expert feed-forward of `h` `[T, E]`: every token through
    every expert held, a block of experts at a time, weighed by
    `pick_weights`; the shared expert added once."""
    first, count = held(cfg)
    block = min(cfg.get("expert_block", EXPERT_BLOCK), count)
    if count % block:
        raise ValueError(f"{count} experts held, blocks of {block}")
    scores = jax.nn.sigmoid(mm(h, lp["mlp/router/kernel"]))
    weights = pick_weights(cfg, scores, lp["mlp/e_score_correction_bias"])
    weights = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    def one_block(total, b):
        def cut(name):
            return jax.lax.dynamic_slice_in_dim(lp[name], b * block, block)
        gate = jax.nn.silu(mm(h, cut("mlp/experts_gate")))   # [blk, T, F]
        out = mm(gate * mm(h, cut("mlp/experts_up")),
                 cut("mlp/experts_down"))                    # [blk, T, E]
        w = jax.lax.dynamic_slice_in_dim(weights, b * block, block, axis=1)
        return total + jnp.einsum("tb,bte->te", w, out,
                                  precision=HIGHEST), None
    total, _ = jax.lax.scan(one_block, jnp.zeros_like(h),
                            jnp.arange(count // block))
    if shared:
        total = total + _swiglu(mm, h, lp, "mlp/shared_experts/")
    return total


def _first_layer(cfg, mm, x, lp):
    x = attention(cfg, mm, x, lp)
    h = _rms(x, lp["post_attention_layernorm/scale"], cfg["rms_norm_eps"])
    return x + _swiglu(mm, h, lp, "mlp/")


def _expert_layer(cfg, mm, x, lp):
    """(the layer's output, each token's `pick_margin`)."""
    x = attention(cfg, mm, x, lp)
    B, S, E = x.shape
    h = _rms(x, lp["post_attention_layernorm/scale"],
             cfg["rms_norm_eps"]).reshape(B * S, E)
    margin = pick_margin(
        cfg, jax.nn.sigmoid(mm(h, lp["mlp/router/kernel"])),
        lp["mlp/e_score_correction_bias"])
    return x + routed(cfg, mm, h, lp).reshape(B, S, E), margin


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, matmul: str):
    cfg, mm = json.loads(cfg_json), MATMULS[matmul]
    first = jax.jit(partial(_first_layer, cfg, mm))
    expert = jax.jit(partial(_expert_layer, cfg, mm))

    @jax.jit
    def head(x, scale, kernel, rows):
        return mm(_rms(x[0, rows], scale, cfg["rms_norm_eps"]), kernel)
    return first, expert, head


def forward_logits(cfg: dict, matmul: str, params: dict, ids, rows):
    """Float32 logits [len(rows), V] at the positions `rows` of one
    sequence `ids` [S] (the whole sequence attends; only the rows asked
    for reach the head). A caller that pads `ids` on the right to one
    length compiles once: attention is causal and a token's experts are
    its own, so the padding changes no row before it.

    With `cfg["pick_margin"]` the float32 pass (`matmul` "highest")
    abstains on the rows whose top-8 pick is within that margin of a
    tie in some expert layer: they come back all zeros (module
    docstring)."""
    first, expert, head = _programs(json.dumps(cfg, sort_keys=True), matmul)
    ids, rows = jnp.asarray(ids), jnp.asarray(rows)
    x = params["model/embed_tokens/embedding"][ids][None] \
        .astype(jnp.float32)
    margins = []
    for i in range(cfg["num_hidden_layers"]):
        prefix = layer_prefix(i)
        lp = {p[len(prefix):]: v for p, v in params.items()
              if p.startswith(prefix)}
        if i:
            x, margin = expert(x, lp)
            margins.append(margin)
        else:
            x = first(x, lp)
    logits = head(x, params["model/norm/scale"], params["lm_head/kernel"],
                  rows)
    if matmul != "highest" or not cfg.get("pick_margin"):
        return logits
    return _judged(cfg, logits, jnp.stack(margins)[:, rows], ids, rows)


def _judged(cfg, logits, margins, ids, rows):
    """`logits` with the rows the reference abstains on zeroed, and one
    line of what that left out: row r scores the token after it,
    `ids[r + 1]`, so the gaps the caller will read are known here."""
    clear = margins / jnp.asarray(cfg["pick_margin"],
                                  jnp.float32)[:, None]     # [layers, rows]
    clear = clear.min(axis=0)
    judged = clear >= 1.0
    served = ids[jnp.minimum(rows + 1, ids.shape[0] - 1)]
    gap = logits.max(-1) - jnp.take_along_axis(
        logits, served[:, None], axis=-1)[:, 0]
    # the caller pads `rows` with its first row: count each row once
    once = jnp.concatenate([jnp.ones((1,), bool), rows[1:] > rows[:-1]])
    # the rows a different pick would explain: left out, and far off
    off = once & ~judged & (gap > FAR_OFF)
    say(f"joyai reference: judges {int((judged & once).sum())} of "
        f"{int(once.sum())} rows (top-{cfg['num_experts_per_tok']} pick "
        f"clear of a tie by pick_margin in every expert layer); widest "
        f"gap of the next token among the rows judged "
        f"{float(jnp.where(judged & once, gap, 0).max()):.4f}, among "
        f"those not judged "
        f"{float(jnp.where(~judged & once, gap, 0).max()):.4f}; of the "
        f"{int(off.sum())} rows not judged whose next token lies more "
        f"than {FAR_OFF} below the best, the clearest pick stands at "
        f"{float(jnp.where(off, clear, 0).max()):.3f} of its margin")
    return jnp.where(judged[:, None], logits, 0.0)
