"""Plain Trinity layer stack (arcee-ai/Trinity-Large-Preview, `model_type`
`afmoe`): one full forward pass over a whole sequence in float32
`jax.numpy`. No kernels, no cache, no ring, no windows, no batching.
Imports nothing of the program; its parameters come from `lib.weights`
under the program's leaf names, in the type they are served in and
raised to float32 where they are used.

`x_0 = Embed(ids) * sqrt(hidden_size)`. Every layer `l` with input `h`,
`N(x) = x * rsqrt(mean(x^2) + eps) * w` (four learned `w` a layer):
`a = h + N2(Attn(N1(h)))`, `out = a + N4(MLP(N3(a)))` (sandwich norms).

- `Attn(x)`, query heads `i` of `num_attention_heads`, KV heads `g` of
  `num_key_value_heads`, head dim `D`, no bias: `q_{t,i} = N_D((W_q
  x_t)_i)`, `k_{t,g} = N_D((W_k x_t)_g)` (one learned `[D]` each a
  layer), `v_{t,g} = (W_v x_t)_g`, `gate_t = W_g x_t` as wide as q. In a
  `sliding_attention` layer q and k are rotated (`R_t`, all `D` dims,
  rotate-half, theta `rope_theta`, position = token index, no scaling)
  and query `t` reads keys `s` with `t - sliding_window < s <= t`; in a
  `full_attention` layer NOTHING is rotated and it reads every `s <= t`.
  `o_t = W_o(softmax_s(q_t . k_s / sqrt(D)) v_s * sigmoid(gate_t))`, the
  gate elementwise a head value. Query rows are taken in blocks against
  a dense mask, so no `[H, S, S]` array exists.
- `MLP`, layers `0 .. num_dense_layers - 1`: `W_d(silu(W_g x) * W_u x)`
  of width `intermediate_size`. The others: `s = sigmoid(x W_r)` over ALL
  `num_experts` router outputs; the `num_experts_per_tok` largest of `s +
  b` are picked (`b`: the balancing bias, which changes the pick and
  never the weight); weights `s_picked / (sum s_picked + 1e-20) *
  route_scale`; `sum_k w_k E_k(x) + Shared(x)`, every expert and the one
  shared expert a SwiGLU of width `moe_intermediate_size`
  (`references/joyai.routed`: the same router, DeepSeek-V3's).

Final `N`, then an untied head.

`experts_held = [first, count]` gives the reference the same share of
an expert-parallel deployment as the program: what the absent experts
would have added is left out, the shared expert is added where
`shared_here`; `vocab_size` is whatever slice of the vocabulary the
configuration states.

ASSUMED (the configuration file lists each with its reason): positions
in the sliding layers only; the per-head q/k norm; the gate reads the
layer's normed input, is elementwise and applied before `W_o`; the four
norms' placement; the `sqrt(hidden_size)` embedding multiplier; a window
counts the query's own position. Departures from the description: none
in the mathematics.

Every row is judged (`lib/check_mean.py`, `lib/check_paired.py`: which
statistic, the cell's limit file says).
"""

from __future__ import annotations

import functools
import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.common import HIGHEST, MATMULS
from benchmarks.references.joyai import routed

SLIDING, FULL = "sliding_attention", "full_attention"

#: query rows (attention) and rows (MLP) taken at once
Q_ROWS, MLP_ROWS = 128, 2048
#: experts whose float32 products are alive at once
EXPERT_BLOCK = 8


def held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held") or (0, cfg["num_experts"]))


def _swiglu_shapes(prefix: str, E: int, inner: int, w) -> dict:
    return {prefix + "gate_proj/kernel": ((E, inner), w),
            prefix + "up_proj/kernel": ((E, inner), w),
            prefix + "down_proj/kernel": ((inner, E), w)}


def _layer_shapes(cfg: dict, dense: bool) -> dict:
    E, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    w, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    out = {
        "input_layernorm/scale": ((E,), f32),
        "post_attention_layernorm/scale": ((E,), f32),
        "pre_mlp_layernorm/scale": ((E,), f32),
        "post_mlp_layernorm/scale": ((E,), f32),
        "self_attn/q_proj/kernel": ((E, H * D), w),
        "self_attn/k_proj/kernel": ((E, G * D), w),
        "self_attn/v_proj/kernel": ((E, G * D), w),
        "self_attn/gate_proj/kernel": ((E, H * D), w),
        "self_attn/o_proj/kernel": ((H * D, E), w),
        "self_attn/q_norm/scale": ((D,), f32),
        "self_attn/k_norm/scale": ((D,), f32),
    }
    if dense:
        out.update(_swiglu_shapes("mlp/", E, cfg["intermediate_size"], w))
        return out
    n, count = cfg["num_experts"], held(cfg)[1]
    out.update({
        "mlp/router/kernel": ((E, n), f32),
        "mlp/e_score_correction_bias": ((n,), f32),
        "mlp/experts_gate": ((count, E, F), w),
        "mlp/experts_up": ((count, E, F), w),
        "mlp/experts_down": ((count, F, E), w)})
    if cfg["num_shared_experts"] and cfg.get("shared_here", True):
        out.update(_swiglu_shapes("mlp/shared_experts/", E,
                                  F * cfg["num_shared_experts"], w))
    return out


def param_shapes(cfg: dict) -> dict:
    E, V = cfg["hidden_size"], cfg["vocab_size"]
    w = jnp.dtype(cfg["param_dtype"])
    shapes = {"lm_head/kernel": ((E, V), w),
              "model/embed_tokens/embedding": ((V, E), w),
              "model/norm/scale": ((E,), jnp.float32)}
    for i in range(cfg["num_hidden_layers"]):
        for name, spec in _layer_shapes(
                cfg, i < cfg["num_dense_layers"]).items():
            shapes[f"model/layers_{i}/{name}"] = spec
    return shapes


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    # x: [S, H, D]; rotate-half layout over all of D, positions 0..S-1
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _attention(cfg, mm, kind, h, lp):
    S = h.shape[0]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = mm(h, lp["self_attn/q_proj/kernel"]).reshape(S, H, D)
    k = mm(h, lp["self_attn/k_proj/kernel"]).reshape(S, G, D)
    v = mm(h, lp["self_attn/v_proj/kernel"]).reshape(S, G, D)
    gate = mm(h, lp["self_attn/gate_proj/kernel"])
    q = _rms(q, lp["self_attn/q_norm/scale"], eps)
    k = _rms(k, lp["self_attn/k_norm/scale"], eps)
    if kind == SLIDING:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])

    def rows(args):
        q_rows, t = args                                     # [R, H, D], [R]
        sc = jnp.einsum("rghd,sgd->rghs", q_rows.reshape(-1, G, H // G, D),
                        k, precision=HIGHEST) / math.sqrt(D)
        s = jnp.arange(S)[None, :]
        ok = s <= t[:, None]
        if kind == SLIDING:
            ok &= s > t[:, None] - cfg["sliding_window"]
        sc = jnp.where(ok[:, None, None, :], sc, -jnp.inf)
        return jnp.einsum("rghs,sgd->rghd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST).reshape(-1, H * D)

    R = math.gcd(S, Q_ROWS)
    o = jax.lax.map(rows, (q.reshape(S // R, R, H, D),
                           jnp.arange(S).reshape(S // R, R)))
    return mm(o.reshape(S, H * D) * jax.nn.sigmoid(gate),
              lp["self_attn/o_proj/kernel"])


def _route_cfg(cfg: dict) -> dict:
    """The keys `references/joyai.routed` reads, from this family's."""
    return {"n_routed_experts": cfg["num_experts"],
            "experts_held": list(held(cfg)),
            "num_experts_per_tok": cfg["num_experts_per_tok"],
            "norm_topk_prob": cfg["route_norm"],
            "routed_scaling_factor": cfg["route_scale"],
            "expert_block": cfg.get("expert_block", EXPERT_BLOCK)}


def _mlp(cfg, mm, dense, h, lp):
    if dense:
        gate = jax.nn.silu(mm(h, lp["mlp/gate_proj/kernel"]))
        return mm(gate * mm(h, lp["mlp/up_proj/kernel"]),
                  lp["mlp/down_proj/kernel"])
    shared = bool(cfg["num_shared_experts"]) and cfg.get("shared_here", True)
    return routed(_route_cfg(cfg), mm, h, lp, shared=shared)


def _layer(cfg, mm, kind, dense, x, lp):
    eps = cfg["rms_norm_eps"]
    a = x + _rms(_attention(
        cfg, mm, kind, _rms(x, lp["input_layernorm/scale"], eps), lp),
        lp["post_attention_layernorm/scale"], eps)
    h = _rms(a, lp["pre_mlp_layernorm/scale"], eps)
    R = math.gcd(x.shape[0], MLP_ROWS)
    out = jax.lax.map(lambda rows: _mlp(cfg, mm, dense, rows, lp),
                      h.reshape(-1, R, h.shape[-1]))
    return a + _rms(out.reshape(h.shape), lp["post_mlp_layernorm/scale"],
                    eps)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, matmul: str):
    cfg, mm = json.loads(cfg_json), MATMULS[matmul]
    layers = {(kind, dense): jax.jit(partial(_layer, cfg, mm, kind, dense))
              for kind in (SLIDING, FULL) for dense in (False, True)}

    @jax.jit
    def head(x, scale, kernel, rows):
        return mm(_rms(x[rows], scale, cfg["rms_norm_eps"]), kernel)
    return layers, head


def forward_logits(cfg: dict, matmul: str, params: dict, ids, rows):
    """Float32 logits [len(rows), V] at the positions `rows` of one
    sequence `ids` [S] (the whole sequence runs; only the rows asked
    for reach the head). A caller that pads `ids` on the right to one
    length compiles once: attention is causal and a token's experts are
    its own, so the padding changes no row before it."""
    layers, head = _programs(json.dumps(cfg, sort_keys=True), matmul)
    x = params["model/embed_tokens/embedding"][
        jnp.asarray(ids)].astype(jnp.float32) * math.sqrt(cfg["hidden_size"])
    for i, kind in enumerate(cfg["layer_types"]):
        pre = f"model/layers_{i}/"
        x = layers[kind, i < cfg["num_dense_layers"]](
            x, {p[len(pre):]: w for p, w in params.items()
                if p.startswith(pre)})
    return head(x, params["model/norm/scale"], params["lm_head/kernel"],
                jnp.asarray(rows))
