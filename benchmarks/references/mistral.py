"""Plain Mistral-7B block stack (Jiang et al. 2023; HF
`MistralForCausalLM` semantics as the v0.3 config states them: RMSNorm
pre-norm, rotary positions in the rotate-half layout with theta 1e6,
grouped-query attention with 32 query and 8 key-value heads, SwiGLU,
untied head, no sliding window): one full forward pass over a whole
sequence in float32 `jax.numpy`. No kernels, no cache, no batching
tricks. Imports nothing of the program; its parameters come from
`lib.weights` under the names below, stacked over layers, in the type
they are served in and raised to float32 where they are used.

Departure from the description: none in the mathematics. Layers are
walked one at a time so that one layer's float32 copy is alive at once.
"""

from __future__ import annotations

import functools
import json
from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.common import HIGHEST, MATMULS

LAYER = "model/layers/layer/"


def param_shapes(cfg: dict) -> dict:
    L, E, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    inter, hd = cfg["intermediate_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    w, f32 = jnp.dtype(cfg["param_dtype"]), jnp.float32
    return {
        "lm_head/kernel": ((E, V), w),
        "model/embed_tokens/embedding": ((V, E), w),
        LAYER + "input_layernorm/scale": ((L, E), f32),
        LAYER + "mlp/down_proj/kernel": ((L, inter, E), w),
        LAYER + "mlp/gate_proj/kernel": ((L, E, inter), w),
        LAYER + "mlp/up_proj/kernel": ((L, E, inter), w),
        LAYER + "post_attention_layernorm/scale": ((L, E), f32),
        LAYER + "self_attn/k_proj/kernel": ((L, E, kv), w),
        LAYER + "self_attn/o_proj/kernel": ((L, q, E), w),
        LAYER + "self_attn/q_proj/kernel": ((L, E, q), w),
        LAYER + "self_attn/v_proj/kernel": ((L, E, kv), w),
        "model/norm/scale": ((E,), f32),
    }


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    # x: [B, S, H, D]; rotate-half layout, positions 0..S-1
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _layer(cfg, mm, x, lp):
    B, S, E = x.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _rms(x, lp["input_layernorm/scale"], eps)
    q = mm(h, lp["self_attn/q_proj/kernel"]).reshape(B, S, nh, hd)
    k = mm(h, lp["self_attn/k_proj/kernel"]).reshape(B, S, nkv, hd)
    v = mm(h, lp["self_attn/v_proj/kernel"]).reshape(B, S, nkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    x = x + mm(a.reshape(B, S, nh * hd), lp["self_attn/o_proj/kernel"])
    h = _rms(x, lp["post_attention_layernorm/scale"], eps)
    gate = jax.nn.silu(mm(h, lp["mlp/gate_proj/kernel"]))
    return x + mm(gate * mm(h, lp["mlp/up_proj/kernel"]),
                  lp["mlp/down_proj/kernel"])


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, matmul: str):
    cfg, mm = json.loads(cfg_json), MATMULS[matmul]
    layer = jax.jit(partial(_layer, cfg, mm))
    take = jax.jit(lambda t, i: jax.tree_util.tree_map(lambda a: a[i], t))

    @jax.jit
    def head(x, scale, kernel, rows):
        return mm(_rms(x[0, rows], scale, cfg["rms_norm_eps"]), kernel)
    return layer, take, head


def forward_logits(cfg: dict, matmul: str, params: dict, ids, rows):
    """Float32 logits [len(rows), V] at the positions `rows` of one
    sequence `ids` [S] (the whole sequence attends; only the rows asked
    for reach the head, so the [S, V] float32 logits never exist). A
    caller that pads `ids` on the right to one length compiles once:
    attention is causal, so the padding changes no row before it."""
    layer, take, head = _programs(json.dumps(cfg, sort_keys=True), matmul)
    stacked = {p[len(LAYER):]: v for p, v in params.items()
               if p.startswith(LAYER)}
    x = params["model/embed_tokens/embedding"][jnp.asarray(ids)][None] \
        .astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, take(stacked, jnp.int32(i)))
    return head(x, params["model/norm/scale"], params["lm_head/kernel"],
                jnp.asarray(rows))
