"""Plain Qwen3-Next layer stack (Qwen/Qwen3-Next-80B-A3B-Instruct,
`model_type` `qwen3_next`; Gated DeltaNet, Yang et al. arXiv:2412.06464):
one full forward pass over a whole sequence in float32 `jax.numpy`. No
kernels, no cache, no chunked form, no batching. Imports nothing of the
program; its parameters come from `lib.weights` under the program's
leaf names, in the type they are served in and raised to float32 where
they are used.

`layer_types[i]` = `full_attention` if `(i + 1) % full_attention_interval
== 0` else `linear_attention`. `norm(x) = x * rsqrt(mean(x^2) + eps) *
(1 + w)` (zero-centred: `w` starts at zero). Every layer: `h = h +
mixer(norm_1(h))`, `h = h + moe(norm_2(h))`. Final `norm`, then an
untied head.

- Gated DeltaNet mixer (`Hk` key heads, `Hv` value heads, head dims
  `Dk`, `Dv`, kernel `K`): `[q | k | v | z] = x W_qkvz`, `[b | a] = x
  W_ba`, no bias. `[q | k | v] <- silu(conv(u))`, a causal depthwise
  convolution `y_t = sum_{j < K} c_j u_{t-K+1+j}`, zeros before the
  sequence. `beta_t = sigmoid(b_t)`; `g_t = -exp(A_log) * softplus(a_t +
  dt_bias)` a value head. q and k heads repeated `Hk -> Hv` (each key
  head serves `Hv / Hk` consecutive value heads); `q <- q / sqrt(sum q^2
  + 1e-6) / sqrt(Dk)`, `k <- k / sqrt(sum k^2 + 1e-6)`. Per value head,
  state `S` `[Dk, Dv]`, `S_0 = 0`, TOKEN BY TOKEN (a `lax.scan` over
  positions): `S' = exp(g_t) S_{t-1}`; `d_t = beta_t (v_t - k_t S')`;
  `S_t = S' + k_t^T d_t`; `o_t = q_t S_t`. `out = W_o (rmsnorm_Dv(o_t;
  weight w, NOT 1 + w) * silu(z_t))` per head.
- Gated attention (`H` query heads, `G` KV heads, head dim `D`):
  `q_proj`: each head's `2 D` outputs split `[query D | gate D]`; `q <-
  norm(q)`, `k <- norm(k)` over `D` with `(1 + w)`; rotary on the first
  `partial_rotary_factor * D` dims only (rotate-half, theta `rope_theta`,
  position = token index, no scaling); causal softmax, scale `D^-0.5`;
  `out = o_proj(attn * sigmoid(gate))`. Query rows are taken in blocks,
  so no `[H, S, S]` array exists.
- Experts, every layer: `p = softmax(x W_r)` over ALL `num_experts`
  router outputs; the `num_experts_per_tok` largest; their weights
  renormalised to sum 1 (`norm_topk_prob`); expert `e`: `W_d(silu(W_g x)
  * W_u x)`; `+ sigmoid(x w_sg) * shared(x)`, one shared expert. Every
  token goes through every expert of a block of experts, and a dense
  `[tokens, experts]` matrix that is 0 off the picks weighs the sum.

Departures from the published description: none in the mathematics.
The multi-token-prediction module is absent: it feeds no logit of the
main model. The published checkpoint lays `W_qkvz` / `W_ba` out per
key-head group; here (and in the program) they are flat `[q | k | v |
z]`, `[b | a]`, which is a permutation of columns
(`models/qwen3_next/convert.py`, ASSUMED). `experts_held = [first,
count]` gives the reference the same share of an expert-parallel
deployment as the program: what the absent experts would have added is
left out; `vocab_size` is whatever slice of the vocabulary the
configuration states.

Every row is judged. A token's top-k experts are a discontinuous
function of its hidden state, and at these widths no row's pick is
clear of a tie by what bf16 rounding moves in all four layers (PERF.md,
PR 32), so an abstention near ties as `references/joyai.py` has would
judge nothing; the cell's comparison is the mean gap over all rows
instead (`lib/check_mean.py`).
"""

from __future__ import annotations

import functools
import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.common import HIGHEST, MATMULS

FULL, LINEAR = "full_attention", "linear_attention"

#: query rows (full layer), rows (experts) and experts taken at once
Q_ROWS, MLP_ROWS, EXPERT_BLOCK = 256, 2048, 16


def layer_types(cfg: dict) -> list:
    n = cfg["full_attention_interval"]
    return [FULL if (i + 1) % n == 0 else LINEAR
            for i in range(cfg["num_hidden_layers"])]


def held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held") or (0, cfg["num_experts"]))


def _layer_shapes(cfg: dict, kind: str) -> dict:
    E, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    Fs = cfg["shared_expert_intermediate_size"]
    w, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    count = held(cfg)[1]
    out = {
        "input_layernorm/weight": ((E,), f32),
        "post_attention_layernorm/weight": ((E,), f32),
        "mlp/router/kernel": ((E, cfg["num_experts"]), f32),
        "mlp/experts_gate": ((count, E, F), w),
        "mlp/experts_up": ((count, E, F), w),
        "mlp/experts_down": ((count, F, E), w),
        "mlp/shared_experts/gate_proj/kernel": ((E, Fs), w),
        "mlp/shared_experts/up_proj/kernel": ((E, Fs), w),
        "mlp/shared_experts/down_proj/kernel": ((Fs, E), w),
        "mlp/shared_expert_gate/kernel": ((E, 1), w),
    }
    if kind == FULL:
        H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
        out.update({
            "self_attn/q_proj/kernel": ((E, H * 2 * D), w),
            "self_attn/k_proj/kernel": ((E, G * D), w),
            "self_attn/v_proj/kernel": ((E, G * D), w),
            "self_attn/o_proj/kernel": ((H * D, E), w),
            "self_attn/q_norm/weight": ((D,), f32),
            "self_attn/k_norm/weight": ((D,), f32)})
    else:
        Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
        Dk, Dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
        conv = 2 * Hk * Dk + Hv * Dv
        out.update({
            "linear_attn/in_proj_qkvz/kernel": ((E, conv + Hv * Dv), w),
            "linear_attn/in_proj_ba/kernel": ((E, 2 * Hv), w),
            "linear_attn/conv1d": ((cfg["linear_conv_kernel_dim"], conv), w),
            "linear_attn/A_log": ((Hv,), f32),
            "linear_attn/dt_bias": ((Hv,), f32),
            "linear_attn/norm_scale": ((Dv,), f32),
            "linear_attn/out_proj/kernel": ((Hv * Dv, E), w)})
    return out


def param_shapes(cfg: dict) -> dict:
    E, V = cfg["hidden_size"], cfg["vocab_size"]
    w = jnp.dtype(cfg["param_dtype"])
    shapes = {"lm_head/kernel": ((E, V), w),
              "model/embed_tokens/embedding": ((V, E), w),
              "model/norm/weight": ((E,), jnp.float32)}
    for i, kind in enumerate(layer_types(cfg)):
        for name, spec in _layer_shapes(cfg, kind).items():
            shapes[f"model/layers_{i}/{name}"] = spec
    return shapes


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        (1.0 + w)


def rope_partial(x, theta, rotary_dim):
    """x: [S, H, D]; rotate-half on the first `rotary_dim` dims,
    positions 0..S-1; the rest passes."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = jnp.split(rot, 2, axis=-1)
    rot = rot * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)
    return jnp.concatenate([rot, rest], -1)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _linear_mixer(cfg, mm, h, lp):
    S = h.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K = cfg["linear_conv_kernel_dim"]
    key_dim, conv = Hk * Dk, 2 * Hk * Dk + Hv * Dv
    qkvz = mm(h, lp["linear_attn/in_proj_qkvz/kernel"])
    u, z = qkvz[:, :conv], qkvz[:, conv:]
    ba = mm(h, lp["linear_attn/in_proj_ba/kernel"])
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(lp["linear_attn/A_log"]) * jax.nn.softplus(
        ba[:, Hv:] + lp["linear_attn/dt_bias"])
    c = lp["linear_attn/conv1d"].astype(jnp.float32)            # [K, C]
    padded = jnp.concatenate([jnp.zeros((K - 1, conv), u.dtype), u])
    y = jax.nn.silu(sum(c[j] * padded[j:j + S] for j in range(K)))
    q = y[:, :key_dim].reshape(S, Hk, Dk)
    k = y[:, key_dim:2 * key_dim].reshape(S, Hk, Dk)
    v = y[:, 2 * key_dim:].reshape(S, Hv, Dv)
    q = jnp.repeat(_l2(q) / math.sqrt(Dk), Hv // Hk, axis=1)
    k = jnp.repeat(_l2(k), Hv // Hk, axis=1)

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[:, None, None] * state
        pred = jnp.einsum("hk,hkv->hv", k_t, state, precision=HIGHEST)
        d_t = b_t[:, None] * (v_t - pred)
        state = state + k_t[:, :, None] * d_t[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                 precision=HIGHEST)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, Dk, Dv), jnp.float32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) +
                          cfg["rms_norm_eps"]) * lp["linear_attn/norm_scale"]
    o = o * jax.nn.silu(z.reshape(S, Hv, Dv))
    return mm(o.reshape(S, Hv * Dv), lp["linear_attn/out_proj/kernel"])


def _full_mixer(cfg, mm, h, lp):
    S = h.shape[0]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    rot = int(D * cfg["partial_rotary_factor"])
    qg = mm(h, lp["self_attn/q_proj/kernel"]).reshape(S, H, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = mm(h, lp["self_attn/k_proj/kernel"]).reshape(S, G, D)
    v = mm(h, lp["self_attn/v_proj/kernel"]).reshape(S, G, D)
    q = rope_partial(_norm(q, lp["self_attn/q_norm/weight"], eps), theta, rot)
    k = rope_partial(_norm(k, lp["self_attn/k_norm/weight"], eps), theta, rot)

    def rows(args):
        q_rows, t = args                                     # [R, H, D], [R]
        sc = jnp.einsum("rghd,sgd->rghs", q_rows.reshape(-1, G, H // G, D),
                        k, precision=HIGHEST) / math.sqrt(D)
        ok = jnp.arange(S)[None, :] <= t[:, None]
        sc = jnp.where(ok[:, None, None, :], sc, -jnp.inf)
        return jnp.einsum("rghs,sgd->rghd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST).reshape(-1, H, D)

    R = math.gcd(S, Q_ROWS)
    o = jax.lax.map(rows, (q.reshape(S // R, R, H, D),
                           jnp.arange(S).reshape(S // R, R)))
    o = o.reshape(S, H, D) * jax.nn.sigmoid(gate)
    return mm(o.reshape(S, H * D), lp["self_attn/o_proj/kernel"])


def pick_weights(cfg, p):
    """`[T, n]`: token t's weight on expert e, 0 where e is not one of
    its picks (the `num_experts_per_tok` largest of `p`)."""
    _, index = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    picked = jax.nn.one_hot(index, p.shape[-1], dtype=jnp.float32) \
        .sum(axis=-2)
    weights = p * picked
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights


def routed(cfg, mm, h, lp, shared: bool = True):
    """The expert feed-forward of `h` `[T, E]`: every token through
    every expert held, a block of experts at a time, weighed by
    `pick_weights`; the gated shared expert added once."""
    first, count = held(cfg)
    block = min(cfg.get("expert_block", EXPERT_BLOCK), count)
    if count % block:
        raise ValueError(f"{count} experts held, blocks of {block}")
    p = jax.nn.softmax(mm(h, lp["mlp/router/kernel"]), axis=-1)
    weights = jax.lax.dynamic_slice_in_dim(pick_weights(cfg, p), first,
                                           count, axis=1)

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
        pre = "mlp/shared_experts/"
        gate = jax.nn.silu(mm(h, lp[pre + "gate_proj/kernel"]))
        out = mm(gate * mm(h, lp[pre + "up_proj/kernel"]),
                 lp[pre + "down_proj/kernel"])
        total = total + jax.nn.sigmoid(
            mm(h, lp["mlp/shared_expert_gate/kernel"])) * out
    return total


def _layer(cfg, mm, kind, x, lp):
    eps = cfg["rms_norm_eps"]
    mixer = _full_mixer if kind == FULL else _linear_mixer
    x = x + mixer(cfg, mm, _norm(x, lp["input_layernorm/weight"], eps), lp)
    h = _norm(x, lp["post_attention_layernorm/weight"], eps)
    R = math.gcd(x.shape[0], MLP_ROWS)
    out = jax.lax.map(
        lambda rows: routed(cfg, mm, rows, lp,
                            shared=cfg.get("shared_here", True)),
        h.reshape(-1, R, h.shape[-1]))
    return x + out.reshape(h.shape)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, matmul: str):
    cfg, mm = json.loads(cfg_json), MATMULS[matmul]
    layers = {kind: jax.jit(partial(_layer, cfg, mm, kind))
              for kind in (FULL, LINEAR)}

    @jax.jit
    def head(x, w, kernel, rows):
        return mm(_norm(x[rows], w, cfg["rms_norm_eps"]), kernel)
    return layers, head


def forward_logits(cfg: dict, matmul: str, params: dict, ids, rows):
    """Float32 logits [len(rows), V] at the positions `rows` of one
    sequence `ids` [S] (the whole sequence runs; only the rows asked
    for reach the head). A caller that pads `ids` on the right to one
    length compiles once: both mixers are causal and a token's experts
    are its own, so the padding changes no row before it."""
    layers, head = _programs(json.dumps(cfg, sort_keys=True), matmul)
    x = params["model/embed_tokens/embedding"][
        jnp.asarray(ids)].astype(jnp.float32)
    for i, kind in enumerate(layer_types(cfg)):
        pre = f"model/layers_{i}/"
        x = layers[kind](x, {p[len(pre):]: w for p, w in params.items()
                             if p.startswith(pre)})
    return head(x, params["model/norm/weight"], params["lm_head/kernel"],
                jnp.asarray(rows))
