"""Plain Kimi-Linear layer stack (moonshotai/Kimi-Linear-48B-A3B-Instruct,
`model_type` `kimi_linear`; Kimi Delta Attention, arXiv:2510.26692): one
full forward pass over a whole sequence in float32 `jax.numpy`. No
kernels, no cache, no chunked form, no absorbed form, no batching.
Imports nothing of the program; its parameters come from `lib.weights`
under the program's leaf names, in the type they are served in and
raised to float32 where they are used.

`linear_attn_config` names the layers of each kind, counted from 1:
`kda_layers` and `full_attn_layers`. `norm(x) = x * rsqrt(mean(x^2) +
eps) * w`. Every layer: `h = h + mixer(norm_1(h))`, `h = h + mlp(norm_2
(h))`. Final `norm`, then an untied head. There are no positions
anywhere.

- KDA mixer (`H` heads of `D`, kernel `K`): `[q | k | v] = x W_qkv`, no
  bias; `[q | k | v] <- silu(conv(.))`, a causal depthwise convolution
  `y_t = sum_{j < K} c_j u_{t-K+1+j}`, zeros before the sequence. Per
  head `q <- q / sqrt(sum q^2 + 1e-6) / sqrt(D)`, `k <- k / sqrt(sum
  k^2 + 1e-6)`. `g_t = -exp(A_log[head]) * softplus((x W_fa) W_fb +
  dt_bias)` in `R^{H x D}`: one log-decay a KEY CHANNEL. `beta_t =
  sigmoid(x W_b)` a head. Per head, state `S` `[D, D]`, `S_0 = 0`,
  TOKEN BY TOKEN (a `lax.scan` over positions): `S' = Diag(exp(g_t))
  S_{t-1}` (row `d` times `exp(g_t[d])`); `d_t = beta_t (v_t - k_t
  S')`; `S_t = S' + k_t^T d_t`; `o_t = q_t S_t`. `out = W_o
  (rmsnorm_D(o_t; weight w) * sigmoid((x W_ga) W_gb))` per head. The
  heads are taken a group at a time so that 36,864 positions fit.
- Latent mixer (`H` heads; rank `r`, `dn`, `dr`, `dv`): `q = x W_q` ->
  heads of `[q_nope dn | q_shared dr]`; `[c r | k_shared dr] = x W_kva`,
  `c <- norm(c)`; NOTHING is rotated (`mla_use_nope`); per head `[k_nope
  | v] = c W_kvb`, `k = [k_nope | k_shared]`; causal softmax of `q.k /
  sqrt(dn + dr)`; `W_o`. Query rows are taken in blocks, so no `[H, S,
  S]` array exists.
- MLP: layer 1 a dense SwiGLU; after it `references/joyai.routed` with
  this family's keys: `s = sigmoid(x W_r)` over ALL `num_experts`
  router outputs; the `num_experts_per_token` largest of `s + b`; their
  weights `s / (sum + 1e-20) * routed_scaling_factor`; expert `e`:
  `W_d(silu(W_g x) * W_u x)`; plus one shared expert.

Departures from the published description: none in the mathematics
(what the configuration file lists under `assumed` is assumed alike in
the program and here). `W_qkv` and the convolution's weight are `[q | k
| v]` side by side, as the program holds them
(`models/kimi_linear/convert.py` lays the three published matrices
out). `experts_held = [first, count]` gives the reference the same
share of an expert-parallel deployment as the program: what the absent
experts would have added is left out; `vocab_size` is whatever slice of
the vocabulary the configuration states.

Every row is judged, as in `references/qwen3_next.py`.
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

KDA, FULL = "kda", "full_attention"

#: query rows (latent layer), rows (MLP), KDA heads taken at once
Q_ROWS, MLP_ROWS, HEAD_GROUP = 64, 2048, 8
#: experts whose float32 products are alive at once
EXPERT_BLOCK = 16


def layer_types(cfg: dict) -> list:
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    return [KDA if i + 1 in kda else FULL
            for i in range(cfg["num_hidden_layers"])]


def held(cfg: dict) -> tuple:
    return tuple(cfg.get("experts_held") or (0, cfg["num_experts"]))


def _swiglu_shapes(prefix: str, E: int, inner: int, w) -> dict:
    return {prefix + "gate_proj/kernel": ((E, inner), w),
            prefix + "up_proj/kernel": ((E, inner), w),
            prefix + "down_proj/kernel": ((inner, E), w)}


def _layer_shapes(cfg: dict, kind: str, dense: bool) -> dict:
    E, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    w, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    out = {"input_layernorm/scale": ((E,), f32),
           "post_attention_layernorm/scale": ((E,), f32)}
    if kind == KDA:
        lin = cfg["linear_attn_config"]
        H, D, K = lin["num_heads"], lin["head_dim"], \
            lin["short_conv_kernel_size"]
        out.update({
            "self_attn/qkv_proj/kernel": ((E, 3 * H * D), w),
            "self_attn/conv1d": ((K, 3 * H * D), w),
            "self_attn/A_log": ((H,), f32),
            "self_attn/dt_bias": ((H * D,), f32),
            "self_attn/f_a_proj/kernel": ((E, D), w),
            "self_attn/f_b_proj/kernel": ((D, H * D), w),
            "self_attn/b_proj/kernel": ((E, H), w),
            "self_attn/g_a_proj/kernel": ((E, D), w),
            "self_attn/g_b_proj/kernel": ((D, H * D), w),
            "self_attn/o_norm_scale": ((D,), f32),
            "self_attn/o_proj/kernel": ((H * D, E), w)})
    else:
        H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
        dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        out.update({
            "self_attn/q_proj/kernel": ((E, H * (dn + dr)), w),
            "self_attn/kv_a_proj_with_mqa/kernel": ((E, r + dr), w),
            "self_attn/kv_a_layernorm/scale": ((r,), f32),
            "self_attn/kv_b_proj/kernel": ((r, H * (dn + dv)), w),
            "self_attn/o_proj/kernel": ((H * dv, E), w)})
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
    for i, kind in enumerate(layer_types(cfg)):
        for name, spec in _layer_shapes(
                cfg, kind, i < cfg["first_k_dense_replace"]).items():
            shapes[f"model/layers_{i}/{name}"] = spec
    return shapes


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda_mixer(cfg, mm, h, lp):
    S = h.shape[0]
    lin = cfg["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    W = H * D
    n = math.gcd(H, HEAD_GROUP)              # heads a group
    w_qkv = lp["self_attn/qkv_proj/kernel"]
    conv = lp["self_attn/conv1d"].astype(jnp.float32)           # [K, 3W]
    f_low = mm(h, lp["self_attn/f_a_proj/kernel"])              # [S, D]
    g_low = mm(h, lp["self_attn/g_a_proj/kernel"])
    beta = jax.nn.sigmoid(mm(h, lp["self_attn/b_proj/kernel"]))  # [S, H]
    a = jnp.exp(lp["self_attn/A_log"])                          # [H]

    def cols(x, first, count):
        return jax.lax.dynamic_slice_in_dim(x, first, count, axis=-1)

    def group(i):
        at = i * n * D                       # the group's first channel

        def part(which):
            u = mm(h, cols(w_qkv, which * W + at, n * D))
            c = cols(conv, which * W + at, n * D)
            padded = jnp.concatenate([jnp.zeros((K - 1, n * D), u.dtype), u])
            y = jax.nn.silu(sum(c[j] * padded[j:j + S] for j in range(K)))
            return y.reshape(S, n, D)
        q, k, v = _l2(part(0)) / math.sqrt(D), _l2(part(1)), part(2)
        f = mm(f_low, cols(lp["self_attn/f_b_proj/kernel"], at, n * D)) + \
            cols(lp["self_attn/dt_bias"], at, n * D)
        g = -jax.lax.dynamic_slice_in_dim(a, i * n, n)[:, None] * \
            jax.nn.softplus(f.reshape(S, n, D))
        b = cols(beta, i * n, n)

        def token(state, x):
            q_t, k_t, v_t, g_t, b_t = x
            state = jnp.exp(g_t)[:, :, None] * state       # a key channel
            pred = jnp.einsum("hk,hkv->hv", k_t, state, precision=HIGHEST)
            d_t = b_t[:, None] * (v_t - pred)
            state = state + k_t[:, :, None] * d_t[:, None, :]
            return state, jnp.einsum("hk,hkv->hv", q_t, state,
                                     precision=HIGHEST)

        _, o = jax.lax.scan(token, jnp.zeros((n, D, D), jnp.float32),
                            (q, k, v, g, b))
        o = _rms(o, lp["self_attn/o_norm_scale"], cfg["rms_norm_eps"])
        gate = jax.nn.sigmoid(
            mm(g_low, cols(lp["self_attn/g_b_proj/kernel"], at, n * D)))
        return o * gate.reshape(S, n, D)

    o = jax.lax.map(group, jnp.arange(H // n))                  # [H/n,S,n,D]
    return mm(jnp.moveaxis(o, 0, 1).reshape(S, W),
              lp["self_attn/o_proj/kernel"])


def _latent_mixer(cfg, mm, h, lp):
    S = h.shape[0]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    q = mm(h, lp["self_attn/q_proj/kernel"]).reshape(S, H, dn + dr)
    ckv = mm(h, lp["self_attn/kv_a_proj_with_mqa/kernel"])
    c = _rms(ckv[:, :r], lp["self_attn/kv_a_layernorm/scale"],
             cfg["rms_norm_eps"])
    kv = mm(c, lp["self_attn/kv_b_proj/kernel"]).reshape(S, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(ckv[:, None, r:], (S, H, dr))], -1)
    v = kv[..., dn:]

    def rows(args):
        q_rows, t = args                                 # [R, H, dn+dr], [R]
        sc = jnp.einsum("rhd,shd->rhs", q_rows, k, precision=HIGHEST) \
            / math.sqrt(dn + dr)
        ok = jnp.arange(S)[None, :] <= t[:, None]
        sc = jnp.where(ok[:, None, :], sc, -jnp.inf)
        return jnp.einsum("rhs,shd->rhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST).reshape(-1, H * dv)

    R = math.gcd(S, Q_ROWS)
    o = jax.lax.map(rows, (q.reshape(S // R, R, H, dn + dr),
                           jnp.arange(S).reshape(S // R, R)))
    return mm(o.reshape(S, H * dv), lp["self_attn/o_proj/kernel"])


def _route_cfg(cfg: dict) -> dict:
    """The keys `references/joyai.routed` reads, from this family's."""
    return {"n_routed_experts": cfg["num_experts"],
            "experts_held": list(held(cfg)),
            "num_experts_per_tok": cfg["num_experts_per_token"],
            "norm_topk_prob": cfg["moe_renormalize"],
            "routed_scaling_factor": cfg["routed_scaling_factor"],
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
    mixer = _kda_mixer if kind == KDA else _latent_mixer
    x = x + mixer(cfg, mm, _rms(x, lp["input_layernorm/scale"], eps), lp)
    h = _rms(x, lp["post_attention_layernorm/scale"], eps)
    R = math.gcd(x.shape[0], MLP_ROWS)
    out = jax.lax.map(lambda rows: _mlp(cfg, mm, dense, rows, lp),
                      h.reshape(-1, R, h.shape[-1]))
    return x + out.reshape(h.shape)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, matmul: str):
    cfg, mm = json.loads(cfg_json), MATMULS[matmul]
    layers = {(kind, dense): jax.jit(partial(_layer, cfg, mm, kind, dense))
              for kind in (KDA, FULL) for dense in (False, True)}

    @jax.jit
    def head(x, scale, kernel, rows):
        return mm(_rms(x[rows], scale, cfg["rms_norm_eps"]), kernel)
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
        x = layers[kind, i < cfg["first_k_dense_replace"]](
            x, {p[len(pre):]: w for p, w in params.items()
                if p.startswith(pre)})
    return head(x, params["model/norm/scale"], params["lm_head/kernel"],
                jnp.asarray(rows))
