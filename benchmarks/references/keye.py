"""Plain Keye-VL-2.0 language-model layer stack (Kwai-Keye/
Keye-VL-2.0-30B-A3B, `model_type` `KeyeVL2`; the indexer as
DeepSeek-Sparse-Attention publishes it, arXiv:2512.02556): one full
forward pass over a whole sequence in float32 `jax.numpy`. No kernels,
no cache, no windows, no batching. Imports nothing of the program; its
parameters come from `lib.weights` under the program's leaf names, in
the type they are served in and raised to float32 where they are used.

Every layer, with `x = RMSNorm(h)` (eps `rms_norm_eps`, weight `w`):

- attention, query heads `i` of `num_attention_heads`, KV heads `g` of
  `num_key_value_heads`, head dim `D`, no bias: `q_{t,i} = R_t(RMSNorm_D(
  W_q x_t)_i)`, `k_{t,g} = R_t(RMSNorm_D(W_k x_t)_g)`, `v_{t,g} = (W_v
  x_t)_g`; `R_t` rotary over all `D` dims, rotate-half, theta
  `rope_theta`, position = token index, no scaling.
- indexer, heads `j` of `indexer_num_heads`, head dim `Di`, ONE key
  head: `qI_{t,j} = R_t((W_Iq x_t)_j)`, `kI_s = R_s(LayerNorm_Di(W_Ik
  x_s))` (weight and bias, eps `rms_norm_eps`), `w_t = W_w x_t`; `I_{t,s}
  = (J Di)^(-1/2) sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)` for `s <= t`.
- selection, PER QUERY: `S_t` = the `topk` positions `s <= t` of largest
  `I_{t,s}`, all of them while `t < topk`; of equal scores the lower
  position (`lax.top_k`'s order).
- `o_{t,i} = sum_{s in S_t} softmax_{s in S_t}(q_{t,i} . k_{s,g(i)} /
  sqrt(D)) v_{s,g(i)}`, one choice a layer for all heads; `h' = h + W_o
  o_t`. Query rows are taken in blocks against a dense mask, so no `[H,
  S, S]` array exists.
- experts: `p = softmax(W_r x')` over ALL `num_experts`; the
  `num_experts_per_tok` largest, renormalised to sum 1; `h'' = h' +
  sum_e p_e W_down,e(silu(W_gate,e x') * W_up,e x')`; no shared expert
  (`references/qwen3_next.routed`, the same mathematics without its
  shared term).

Final RMSNorm, then an untied head.

ASSUMED (the published `config.json` names the indexer's sizes and
nothing of its wiring; the configuration file lists each with its
reason): the indexer reads the layer's normed input; the LayerNorm on
its key; rotary over all `Di` dims of its queries and its key, with the
model's theta; the score's scale; the per-head q/k RMSNorm.
`q_chunk_size` / `kv_chunk_size` are the published kernel's tiling and
enter no equation. Departures from the description: none in the
mathematics. The vision tower and the three-axis positions of image
tokens (`mrope_section`; equal ids for text are ordinary rotary) are
absent.
"""

from __future__ import annotations

import functools
import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.common import HIGHEST, MATMULS
from benchmarks.references.qwen3_next import routed

#: query rows (attention) and rows (experts) taken at once
Q_ROWS, MLP_ROWS = 128, 2048


def _layer_shapes(cfg: dict) -> dict:
    E, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    J, Di = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
    w, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    n = cfg["num_experts"]
    return {
        "input_layernorm/scale": ((E,), f32),
        "post_attention_layernorm/scale": ((E,), f32),
        "self_attn/q_proj/kernel": ((E, H * D), w),
        "self_attn/k_proj/kernel": ((E, G * D), w),
        "self_attn/v_proj/kernel": ((E, G * D), w),
        "self_attn/o_proj/kernel": ((H * D, E), w),
        "self_attn/q_norm/scale": ((D,), f32),
        "self_attn/k_norm/scale": ((D,), f32),
        "self_attn/indexer/q_proj/kernel": ((E, J * Di), w),
        "self_attn/indexer/k_proj/kernel": ((E, Di), w),
        "self_attn/indexer/weights_proj/kernel": ((E, J), w),
        "self_attn/indexer/k_norm/scale": ((Di,), f32),
        "self_attn/indexer/k_norm/bias": ((Di,), f32),
        "mlp/router/kernel": ((E, n), f32),
        "mlp/experts_gate": ((n, E, F), w),
        "mlp/experts_up": ((n, E, F), w),
        "mlp/experts_down": ((n, F, E), w),
    }


def param_shapes(cfg: dict) -> dict:
    E, V = cfg["hidden_size"], cfg["vocab_size"]
    w = jnp.dtype(cfg["param_dtype"])
    shapes = {"lm_head/kernel": ((E, V), w),
              "model/embed_tokens/embedding": ((V, E), w),
              "model/norm/scale": ((E,), jnp.float32)}
    for i in range(cfg["num_hidden_layers"]):
        for name, spec in _layer_shapes(cfg).items():
            shapes[f"model/layers_{i}/{name}"] = spec
    return shapes


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    c = x - jnp.mean(x, -1, keepdims=True)
    return c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps) * \
        scale + bias


def _rope(x, theta):
    # x: [S, H, D]; rotate-half layout over all of D, positions 0..S-1
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def index_scores(cfg, qi_rows, w_rows, ki):
    """`I` `[R, S]` of query rows (`qi_rows` `[R, J, Di]`, `w_rows` `[R,
    J]`) against every key `ki` `[S, Di]`, causality not yet applied."""
    J, Di = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
    s = jnp.einsum("rjd,sd->rjs", qi_rows, ki, precision=HIGHEST)
    return (jnp.maximum(s, 0.0) * w_rows[:, :, None]).sum(1) / \
        math.sqrt(J * Di)


def choose(cfg, scores, t):
    """`[R, S]` bool: the positions each query row (at position `t[r]`,
    scores `[R, S]`) reads."""
    S = scores.shape[-1]
    causal = jnp.arange(S)[None, :] <= t[:, None]
    masked = jnp.where(causal, scores, -jnp.inf)
    _, index = jax.lax.top_k(masked, min(cfg["topk"], S))
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], index].set(True)
    return picked & causal


def _attention(cfg, mm, h, lp):
    S = h.shape[0]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    J, Di = cfg["indexer_num_heads"], cfg["indexer_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = mm(h, lp["self_attn/q_proj/kernel"]).reshape(S, H, D)
    k = mm(h, lp["self_attn/k_proj/kernel"]).reshape(S, G, D)
    v = mm(h, lp["self_attn/v_proj/kernel"]).reshape(S, G, D)
    q = _rope(_rms(q, lp["self_attn/q_norm/scale"], eps), theta)
    k = _rope(_rms(k, lp["self_attn/k_norm/scale"], eps), theta)
    pre = "self_attn/indexer/"
    qi = _rope(mm(h, lp[pre + "q_proj/kernel"]).reshape(S, J, Di), theta)
    ki = _layer_norm(mm(h, lp[pre + "k_proj/kernel"]),
                     lp[pre + "k_norm/scale"], lp[pre + "k_norm/bias"], eps)
    ki = _rope(ki[:, None, :], theta)[:, 0]
    w = mm(h, lp[pre + "weights_proj/kernel"])

    def rows(args):
        q_rows, qi_rows, w_rows, t = args
        ok = choose(cfg, index_scores(cfg, qi_rows, w_rows, ki), t)
        sc = jnp.einsum("rghd,sgd->rghs", q_rows.reshape(-1, G, H // G, D),
                        k, precision=HIGHEST) / math.sqrt(D)
        sc = jnp.where(ok[:, None, None, :], sc, -jnp.inf)
        return jnp.einsum("rghs,sgd->rghd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST).reshape(-1, H * D)

    R = math.gcd(S, Q_ROWS)
    o = jax.lax.map(rows, (q.reshape(S // R, R, H, D),
                           qi.reshape(S // R, R, J, Di),
                           w.reshape(S // R, R, J),
                           jnp.arange(S).reshape(S // R, R)))
    return mm(o.reshape(S, H * D), lp["self_attn/o_proj/kernel"])


def _layer(cfg, mm, x, lp):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, mm, _rms(x, lp["input_layernorm/scale"], eps),
                       lp)
    h = _rms(x, lp["post_attention_layernorm/scale"], eps)
    R = math.gcd(x.shape[0], MLP_ROWS)
    out = jax.lax.map(lambda rows: routed(cfg, mm, rows, lp, shared=False),
                      h.reshape(-1, R, h.shape[-1]))
    return x + out.reshape(h.shape)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, matmul: str):
    cfg, mm = json.loads(cfg_json), MATMULS[matmul]
    layer = jax.jit(partial(_layer, cfg, mm))

    @jax.jit
    def head(x, scale, kernel, rows):
        return mm(_rms(x[rows], scale, cfg["rms_norm_eps"]), kernel)
    return layer, head


def forward_logits(cfg: dict, matmul: str, params: dict, ids, rows):
    """Float32 logits [len(rows), V] at the positions `rows` of one
    sequence `ids` [S] (the whole sequence runs; only the rows asked
    for reach the head). A caller that pads `ids` on the right to one
    length compiles once: attention and the selection are causal and a
    token's experts are its own, so the padding changes no row before
    it."""
    layer, head = _programs(json.dumps(cfg, sort_keys=True), matmul)
    x = params["model/embed_tokens/embedding"][
        jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model/layers_{i}/"
        x = layer(x, {p[len(pre):]: w for p, w in params.items()
                      if p.startswith(pre)})
    return head(x, params["model/norm/scale"], params["lm_head/kernel"],
                jnp.asarray(rows))
