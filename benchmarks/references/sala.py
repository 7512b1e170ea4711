"""Plain MiniCPM-SALA layer stack (openbmb `minicpm_sala`; Lightning
Attention, Qin et al. arXiv:2401.04658; InfLLM-V2 as in MiniCPM4,
arXiv:2506.07900): one full forward pass over a whole sequence in
float32 `jax.numpy`. No kernels, no cache, no chunks, no batching
tricks. Imports nothing of the program; its parameters come from
`lib.weights` under the program's leaf names, in the type they are
served in and raised to float32 where they are used.

Stream: `x0 = scale_emb * Embed(ids)`; each layer, pre-norm (eps
`rms_norm_eps`), `x += a * Mixer(RMSNorm(x))`, `x += a * MLP(RMSNorm(x))`,
`a = scale_depth / sqrt(residual_depth)` (the PUBLISHED depth);
`MLP(h) = W_d(silu(W_g h) * W_u h)`; `logits = W_head (RMSNorm(x_L) /
(hidden_size / dim_model_base))`.

- `lightning-attn`: q, k, v of `lightning_nh` heads; per-head RMSNorm on
  q and k (one learned `[D]`); RoPE on all of a head, rotate-half,
  position = token index; per head `i` the TOKEN-BY-TOKEN recurrence
  `S_t = lambda_i S_{t-1} + k_t^T v_t`, `o_t = q_t S_t / sqrt(D)`
  (a `lax.scan` over positions, not a chunk form); `out =
  (RMSNorm(concat o_t) * sigmoid(h W_z)) W_o`.
- `minicpm4`: q of `num_attention_heads`, k, v of `num_key_value_heads`
  heads; per-head RMSNorm on q and k; no positions. PER QUERY `t`: while
  `t + 1 <= dense_len` causal softmax attention over everything; past
  it pooled keys `k~_j = mean(k_{s j} .. k_{s j + K - 1})` for every
  window that ends at or before `t`, `p^h_j = softmax_j(q^h . k~_j /
  sqrt(D))`, `a_j = sum_{h in g} p^h_j`, block score `b_m` = the max of
  `a_j` over the pooled windows that overlap block `m`; chosen: the
  first `init_blocks` blocks, every block that overlaps the last
  `window_size` tokens, and the highest `b_m` among the rest until
  `topk` blocks IN ALL (of equal scores — neighbouring blocks share a
  pooled window — the lower block first); causal softmax attention over the tokens of the
  chosen blocks, one choice a KV head. `out = (concat o * sigmoid(h
  W_z)) W_o`. Query rows are taken in blocks against a dense mask, so
  no `[H, S, S]` array exists; so is the MLP.

ASSUMED sizes (the published `config.json` does not carry them; the
configuration file lists each with its reason): `kernel_size` 32,
`kernel_stride` 16, `block_size` 64, `topk` 64, `init_blocks` 1,
`window_size` 2048, `dense_len` 8192 (MiniCPM4's `sparse_config`); that
the 64 count the forced blocks; `lambda_i = exp(-2^(-8 (i + 1) / H))`
(Lightning Attention's slopes), the same in every layer; the output
norm over the concatenated heads; square gate matrices; `qk_norm` on
both mixers.

Departure from the description: none in the mathematics.
"""

from __future__ import annotations

import functools
import json
import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.common import HIGHEST, MATMULS

SPARSE, LINEAR = "minicpm4", "lightning-attn"

#: query rows (sparse layer) and rows (MLP) taken at once
Q_ROWS, MLP_ROWS = 128, 2048


def _layer_shapes(cfg: dict, kind: str) -> dict:
    E, inter = cfg["hidden_size"], cfg["intermediate_size"]
    w, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    if kind == SPARSE:
        D = cfg["head_dim"]
        q, kv = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    else:
        D = cfg["lightning_head_dim"]
        q = kv = cfg["lightning_nh"] * D
    out = {
        "input_layernorm/scale": ((E,), f32),
        "post_attention_layernorm/scale": ((E,), f32),
        "mlp/gate_proj/kernel": ((E, inter), w),
        "mlp/up_proj/kernel": ((E, inter), w),
        "mlp/down_proj/kernel": ((inter, E), w),
        "self_attn/q_proj/kernel": ((E, q), w),
        "self_attn/k_proj/kernel": ((E, kv), w),
        "self_attn/v_proj/kernel": ((E, kv), w),
        "self_attn/z_proj/kernel": ((E, q), w),
        "self_attn/o_proj/kernel": ((q, E), w),
        "self_attn/q_norm/scale": ((D,), f32),
        "self_attn/k_norm/scale": ((D,), f32),
    }
    if kind == LINEAR:
        out["self_attn/o_norm/scale"] = ((q,), f32)
    return out


def param_shapes(cfg: dict) -> dict:
    E, V = cfg["hidden_size"], cfg["vocab_size"]
    w = jnp.dtype(cfg["param_dtype"])
    shapes = {"lm_head/kernel": ((E, V), w),
              "model/embed_tokens/embedding": ((V, E), w),
              "model/norm/scale": ((E,), jnp.float32)}
    for i, kind in enumerate(cfg["mixer_types"]):
        for name, spec in _layer_shapes(cfg, kind).items():
            shapes[f"model/layers_{i}/{name}"] = spec
    return shapes


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    # x: [S, H, D]; rotate-half layout, positions 0..S-1
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _qkv(cfg, mm, h, lp, heads, kv_heads, D):
    S, eps = h.shape[0], cfg["rms_norm_eps"]
    q = mm(h, lp["self_attn/q_proj/kernel"]).reshape(S, heads, D)
    k = mm(h, lp["self_attn/k_proj/kernel"]).reshape(S, kv_heads, D)
    v = mm(h, lp["self_attn/v_proj/kernel"]).reshape(S, kv_heads, D)
    return (_rms(q, lp["self_attn/q_norm/scale"], eps),
            _rms(k, lp["self_attn/k_norm/scale"], eps), v)


def _linear_mixer(cfg, mm, h, lp):
    S = h.shape[0]
    H, D = cfg["lightning_nh"], cfg["lightning_head_dim"]
    q, k, v = _qkv(cfg, mm, h, lp, H, H, D)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    lam = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(H) + 1.0) / H)))

    def token(state, qkv):
        q_t, k_t, v_t = qkv                                  # [H, D]
        state = lam[:, None, None] * state + \
            k_t[:, :, None] * v_t[:, None, :]
        o_t = jnp.einsum("hd,hde->he", q_t, state, precision=HIGHEST)
        return state, o_t / math.sqrt(D)

    _, o = jax.lax.scan(token, jnp.zeros((H, D, D), jnp.float32), (q, k, v))
    o = _rms(o.reshape(S, H * D), lp["self_attn/o_norm/scale"],
             cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(mm(h, lp["self_attn/z_proj/kernel"]))
    return mm(o * gate, lp["self_attn/o_proj/kernel"])


def _choose(cfg, q_rows, pooled, t, M):
    """`[R, G, M]` bool: the blocks each of the query rows `q_rows`
    `[R, H, D]` at positions `t` `[R]` reads of `M`, by the published
    rule."""
    K, s, B = cfg["kernel_size"], cfg["kernel_stride"], cfg["block_size"]
    R, H, D = q_rows.shape
    J, G, _ = pooled.shape
    ends = jnp.arange(J) * s + K - 1
    ended = ends[None, :] <= t[:, None]                      # [R, J]
    # query head h scores its own group's pooled keys
    sc = jnp.einsum("rhd,jhd->rhj", q_rows,
                    jnp.repeat(pooled, H // G, axis=1),
                    precision=HIGHEST) / math.sqrt(D)
    sc = jnp.where(ended[:, None, :], sc, -jnp.inf)
    p = jnp.nan_to_num(jax.nn.softmax(sc, axis=-1))          # no window: 0
    a = p.reshape(R, G, H // G, J).sum(axis=2)               # [R, G, J]
    a = jnp.where(ended[:, None, :], a, -1.0)
    # block m's score: the pooled windows that overlap its tokens
    starts = jnp.arange(J) * s
    over = (starts[None, :] <= (jnp.arange(M) * B + B - 1)[:, None]) & \
        (ends[None, :] >= (jnp.arange(M) * B)[:, None])      # [M, J]
    b = jnp.where(over[None, None], a[:, :, None, :], -1.0).max(-1)
    first, last = jnp.arange(M) * B, jnp.arange(M) * B + B - 1
    begun = first[None, :] <= t[:, None]                     # [R, M]
    forced = begun & ((jnp.arange(M)[None, :] < cfg["init_blocks"]) |
                      (last[None, :] >= (t - cfg["window_size"] + 1)[:, None]))
    free = begun & ~forced
    room = cfg["topk"] - forced.sum(-1)                      # [R]
    # a free block's place among the free ones, best first
    bf = jnp.where(free[:, None, :], b, -jnp.inf)            # [R, G, M]
    ahead = (bf[..., None, :] > bf[..., :, None]) | (
        (bf[..., None, :] == bf[..., :, None]) &
        (jnp.arange(M)[None, :] < jnp.arange(M)[:, None]))
    place = (ahead & free[:, None, None, :]).sum(-1)         # [R, G, M]
    chosen = forced[:, None, :] | (free[:, None, :] &
                                   (place < room[:, None, None]))
    dense = (t + 1 <= cfg["dense_len"])[:, None, None]
    return jnp.where(dense, begun[:, None, :], chosen)


def _sparse_mixer(cfg, mm, h, lp):
    S = h.shape[0]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    K, s, B = cfg["kernel_size"], cfg["kernel_stride"], cfg["block_size"]
    q, k, v = _qkv(cfg, mm, h, lp, H, G, D)
    J = max((S - K) // s + 1, 1)
    idx = (jnp.arange(J) * s)[:, None] + jnp.arange(K)[None, :]
    pooled = k[jnp.minimum(idx, S - 1)].mean(axis=1)         # [J, G, D]
    block_of = jnp.arange(S) // B

    def rows(args):
        q_rows, t = args                                     # [R, H, D], [R]
        chosen = _choose(cfg, q_rows, pooled, t, -(-S // B))  # [R, G, M]
        ok = chosen[:, :, block_of] & \
            (jnp.arange(S)[None, None, :] <= t[:, None, None])
        sc = jnp.einsum("rghd,sgd->rghs", q_rows.reshape(-1, G, H // G, D),
                        k, precision=HIGHEST) / math.sqrt(D)
        sc = jnp.where(ok[:, :, None, :], sc, -jnp.inf)
        return jnp.einsum("rghs,sgd->rghd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST).reshape(-1, H * D)

    R = math.gcd(S, Q_ROWS)
    o = jax.lax.map(rows, (q.reshape(S // R, R, H, D),
                           jnp.arange(S).reshape(S // R, R)))
    gate = jax.nn.sigmoid(mm(h, lp["self_attn/z_proj/kernel"]))
    return mm(o.reshape(S, H * D) * gate, lp["self_attn/o_proj/kernel"])


def _layer(cfg, mm, kind, x, lp):
    eps = cfg["rms_norm_eps"]
    a = cfg["scale_depth"] / math.sqrt(cfg["residual_depth"])
    mixer = _sparse_mixer if kind == SPARSE else _linear_mixer
    x = x + a * mixer(cfg, mm, _rms(x, lp["input_layernorm/scale"], eps), lp)
    h = _rms(x, lp["post_attention_layernorm/scale"], eps)

    def mlp(rows):
        gate = jax.nn.silu(mm(rows, lp["mlp/gate_proj/kernel"]))
        return mm(gate * mm(rows, lp["mlp/up_proj/kernel"]),
                  lp["mlp/down_proj/kernel"])

    R = math.gcd(x.shape[0], MLP_ROWS)
    return x + a * jax.lax.map(mlp, h.reshape(-1, R, h.shape[-1])) \
        .reshape(h.shape)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, matmul: str):
    cfg, mm = json.loads(cfg_json), MATMULS[matmul]
    layers = {kind: jax.jit(partial(_layer, cfg, mm, kind))
              for kind in (SPARSE, LINEAR)}

    @jax.jit
    def head(x, scale, kernel, rows):
        h = _rms(x[rows], scale, cfg["rms_norm_eps"])
        return mm(h / (cfg["hidden_size"] / cfg["dim_model_base"]), kernel)
    return layers, head


def forward_logits(cfg: dict, matmul: str, params: dict, ids, rows):
    """Float32 logits [len(rows), V] at the positions `rows` of one
    sequence `ids` [S] (the whole sequence runs; only the rows asked
    for reach the head). A caller that pads `ids` on the right to one
    length compiles once: both mixers are causal, so the padding changes
    no row before it."""
    layers, head = _programs(json.dumps(cfg, sort_keys=True), matmul)
    x = cfg["scale_emb"] * params["model/embed_tokens/embedding"][
        jnp.asarray(ids)].astype(jnp.float32)
    for i, kind in enumerate(cfg["mixer_types"]):
        pre = f"model/layers_{i}/"
        x = layers[kind](x, {p[len(pre):]: w for p, w in params.items()
                             if p.startswith(pre)})
    return head(x, params["model/norm/scale"], params["lm_head/kernel"],
                jnp.asarray(rows))
