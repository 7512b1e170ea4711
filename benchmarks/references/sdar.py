"""Plain SDAR (JetLM/SDAR-30B-A3B-Chat, `model_type` `sdar_moe`; block
diffusion): the Qwen3-MoE layer stack under a BLOCK mask and the
generation procedure over it, in float32 `jax.numpy`. No kernels, no
cache, no batching. Imports nothing of the program; its parameters come
from `lib.weights` under the program's leaf names, in the type they are
served in and raised to float32 where they are used.

Every layer, with `x = RMSNorm(h)` (eps `rms_norm_eps`, weight `w`):

- attention, query heads `i` of `num_attention_heads`, KV heads `g` of
  `num_key_value_heads`, head dim `D`, no bias: `q_{t,i} = R_t(RMSNorm_D(
  W_q x_t)_i)`, `k_{t,g} = R_t(RMSNorm_D(W_k x_t)_g)`, `v_{t,g} = (W_v
  x_t)_g`; `R_t` rotary over all `D` dims, rotate-half, theta
  `rope_theta`, at the token's position, no scaling. With `L =
  block_length` the query at position `p` reads every key at a position
  `< (p // L + 1) * L`: all earlier blocks and the whole of its own.
  `o_{t,i} = sum_s softmax_s(q_{t,i} . k_{s,g(i)} / sqrt(D)) v_{s,g(i)}`
  over the visible `s`; `h' = h + W_o o_t`. Query rows are taken in
  blocks, so no `[H, S, S]` array exists.
- experts: `p = softmax(W_r x')` over ALL `num_experts`; the
  `num_experts_per_tok` largest, renormalised to sum 1; `h'' = h' +
  sum_e p_e W_down,e(silu(W_gate,e x') * W_up,e x')`; no shared expert
  (`references/qwen3_next.routed` without its shared term).

Final RMSNorm, then an untied head; the logits at position `i` predict
the token AT position `i`.

Generation (`generate`): the sequence is cut into blocks of `L`
positions from position 0. A block that holds positions to generate
starts as `[prompt tail | MASK ...]`; each forward of the whole
sequence so far (the block's positions fed the mask token's embedding
where still masked) reveals `n = L / steps` of the masked positions —
`sequential`: the leftmost; `low_confidence`: those whose largest
softmax probability is highest, ties to the left — each as the argmax
of its own logits, never to change again; the next block begins when
none is masked. (The program spends one more forward a block writing
the final block's K/V; with no cache there is nothing to write.)

`block_logits` is the teacher-forced form for a check after the fact:
the logits of denoising step `step` of every output position's block
under the `sequential` rule, from ONE forward over the clean sequence
followed by its noised copy (the block-diffusion training mask).

ASSUMED (the published `config.json` gives none of it; the
configuration file lists each with its reason): `block_length`,
`mask_token_id`, no shift between a position's logits and its token,
the block grid counted from position 0 with the prompt's tail joining
the first generated block, the per-head q/k RMSNorm before rotary.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references.common import HIGHEST, MATMULS
from benchmarks.references.qwen3_next import routed

#: query rows (attention) and rows (experts) taken at once
Q_ROWS, MLP_ROWS = 128, 2048


def _layer_shapes(cfg: dict) -> dict:
    E, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
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


def _rope(x, positions, theta):
    # x: [S, H, D]; rotate-half layout over all of D
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def visible(block: int, n_clean: int, qi, kj):
    """`[R, S]` bool: which entries `kj` of a sequence the entries `qi`
    read. Entries `< n_clean` are a sequence at positions `0 ..` under
    the block mask; entries from `n_clean` on are its NOISED copy (entry
    `n_clean + p` at position `p`), whose block `b` reads the clean
    blocks `< b` and itself."""
    q_noised, k_noised = qi[:, None] >= n_clean, kj[None, :] >= n_clean
    qb = (qi % n_clean)[:, None] // block
    kb = (kj % n_clean)[None, :] // block
    return jnp.where(q_noised,
                     jnp.where(k_noised, kb == qb, kb < qb),
                     ~k_noised & (kb <= qb))


def _attention(cfg, mm, n_clean, h, lp):
    S = h.shape[0]
    H, G, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    at = jnp.arange(S)
    positions = at % n_clean
    q = mm(h, lp["self_attn/q_proj/kernel"]).reshape(S, H, D)
    k = mm(h, lp["self_attn/k_proj/kernel"]).reshape(S, G, D)
    v = mm(h, lp["self_attn/v_proj/kernel"]).reshape(S, G, D)
    q = _rope(_rms(q, lp["self_attn/q_norm/scale"], eps), positions, theta)
    k = _rope(_rms(k, lp["self_attn/k_norm/scale"], eps), positions, theta)

    def rows(args):
        q_rows, qi = args
        ok = visible(cfg["block_length"], n_clean, qi, at)
        sc = jnp.einsum("rghd,sgd->rghs", q_rows.reshape(-1, G, H // G, D),
                        k, precision=HIGHEST) / math.sqrt(D)
        sc = jnp.where(ok[:, None, None, :], sc, -jnp.inf)
        return jnp.einsum("rghs,sgd->rghd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST).reshape(-1, H * D)

    R = math.gcd(S, Q_ROWS)
    o = jax.lax.map(rows, (q.reshape(S // R, R, H, D),
                           at.reshape(S // R, R)))
    return mm(o.reshape(S, H * D), lp["self_attn/o_proj/kernel"])


def _layer(cfg, mm, n_clean, x, lp):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, mm, n_clean,
                       _rms(x, lp["input_layernorm/scale"], eps), lp)
    h = _rms(x, lp["post_attention_layernorm/scale"], eps)
    R = math.gcd(x.shape[0], MLP_ROWS)
    out = jax.lax.map(lambda rows: routed(cfg, mm, rows, lp, shared=False),
                      h.reshape(-1, R, h.shape[-1]))
    return x + out.reshape(h.shape)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, matmul: str, doubled: bool):
    cfg, mm = json.loads(cfg_json), MATMULS[matmul]

    @jax.jit
    def layer(x, lp):
        n_clean = x.shape[0] // 2 if doubled else x.shape[0]
        return _layer(cfg, mm, n_clean, x, lp)

    @jax.jit
    def head(x, scale, kernel, rows):
        return mm(_rms(x[rows], scale, cfg["rms_norm_eps"]), kernel)
    return layer, head


def _logits(cfg: dict, matmul: str, params: dict, ids, rows,
            doubled: bool = False):
    """Float32 logits `[len(rows), V]` at the entries `rows` of one
    sequence `ids` (`doubled`: a clean sequence and its noised copy,
    `visible`). The whole sequence runs; only the rows asked for reach
    the head."""
    layer, head = _programs(json.dumps(cfg, sort_keys=True), matmul, doubled)
    with jax.default_matmul_precision("highest"):
        x = params["model/embed_tokens/embedding"][
            jnp.asarray(ids)].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model/layers_{i}/"
            x = layer(x, {p[len(pre):]: w for p, w in params.items()
                          if p.startswith(pre)})
        return head(x, params["model/norm/scale"], params["lm_head/kernel"],
                    jnp.asarray(rows))


def forward_logits(cfg: dict, matmul: str, params: dict, ids, rows):
    """Float32 logits at the positions `rows` of one sequence `ids`
    under the block mask. A caller that pads `ids` on the right to one
    length compiles once: a padded token lies in a later block than any
    real query's unless it shares the last real block, which the caller
    fills."""
    return _logits(cfg, matmul, params, ids, rows)


def reveal_order(n_masked: int, per_step: int) -> list:
    """How many positions each reveal forward of a block with
    `n_masked` positions to generate sets: `per_step`, the last what is
    left."""
    return [min(per_step, n_masked - done)
            for done in range(0, n_masked, per_step)]


def pick(masked, logits, n: int, remasking: str):
    """The `n` of the `masked` positions (`[L]` bool) a forward with
    `logits` `[L, V]` reveals."""
    if remasking == "sequential":
        order = np.nonzero(masked)[0]
    elif remasking == "low_confidence":
        z = logits.astype(np.float64)
        p = np.exp(z - z.max(-1, keepdims=True))
        confidence = (p / p.sum(-1, keepdims=True)).max(-1)
        order = sorted(np.nonzero(masked)[0],
                       key=lambda i: (-confidence[i], i))
    else:
        raise ValueError(f"unknown remasking {remasking!r}")
    return np.asarray(order[:n], np.int64)


def generate(cfg: dict, matmul: str, params: dict, prompt, n_new: int,
             steps: int, remasking: str = "low_confidence"):
    """The generation procedure as a plain loop (module docstring): a
    whole-sequence forward under the block mask every step. Returns the
    `n_new` generated tokens and one record a forward: `{"block",
    "step", "logits" [L, V], "masked" [L] (before it), "revealed"
    (positions in the block)}`. The last block is generated whole and
    cut."""
    L, mask_id = cfg["block_length"], cfg["mask_token_id"]
    if L % steps:
        raise ValueError(f"{steps} steps do not divide a block of {L}")
    prompt = np.asarray(prompt, np.int64)
    P = len(prompt)
    total = -(-(P + n_new) // L) * L
    seq = np.zeros((total,), np.int64)
    seq[:P] = prompt
    records = []
    for b in range(P // L, total // L):
        at = np.arange(b * L, (b + 1) * L)
        masked = at >= P
        for step, n in enumerate(reveal_order(int(masked.sum()),
                                              L // steps)):
            fed = seq.copy()
            fed[at[masked]] = mask_id
            logits = np.asarray(_logits(cfg, matmul, params, fed, at))
            chosen = pick(masked, logits, n, remasking)
            records.append({"block": b, "step": step, "logits": logits,
                            "masked": masked.copy(), "revealed": chosen})
            seq[at[chosen]] = logits[chosen].argmax(-1)
            masked[chosen] = False
    return seq[P:P + n_new], records


def block_logits(cfg: dict, matmul: str, params: dict, ids,
                 prompt_len: int, n_out: int, step: int, steps: int,
                 rows=None):
    """For every output position `prompt_len .. prompt_len + n_out - 1`
    of the sequence `ids` (prompt and served tokens, padded on the right
    to any length), the float32 logits `[n_out, V]` of denoising step
    `step` of ITS block, teacher-forced from the served tokens under the
    `sequential` rule: step `s` of a block sees the first `s * L /
    steps` of its positions to generate revealed and the rest masked.
    ONE forward over the clean sequence followed by the step's noised
    copy (`visible`). `rows`: a padded count of rows to compile once
    for (the rows past `n_out` repeat the first)."""
    L, mask_id = cfg["block_length"], cfg["mask_token_id"]
    ids = np.asarray(ids, np.int64)
    n = len(ids)
    at = np.arange(n)
    # a position's index among its block's positions to generate
    new_index = at - np.maximum(prompt_len, at // L * L)
    noised = np.where((at < prompt_len) | (new_index < step * (L // steps)),
                      ids, mask_id)
    out = np.full((rows or n_out,), n + prompt_len, np.int64)
    out[:n_out] = n + prompt_len + np.arange(n_out)
    return _logits(cfg, matmul, params, np.concatenate([ids, noised]), out,
                   doubled=True)[:n_out]


def step_of(prompt_len: int, n_out: int, block: int, steps: int):
    """`[n_out]`: the denoising step of its block that reveals each
    output position under the `sequential` rule."""
    at = prompt_len + np.arange(n_out)
    return (at - np.maximum(prompt_len, at // block * block)) // \
        (block // steps)
