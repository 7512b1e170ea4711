"""Plain GPT-2 (Radford et al. 2019, HF `GPT2LMHeadModel` semantics):
learned positions, pre-LN blocks, tanh-GELU, tied head, mean shifted
cross-entropy; forward, loss, gradients and Adam in float32
`jax.numpy`. No kernels, no cache, no dropout (the configuration
states dropout 0). Imports nothing of the program; its parameters come
from `lib.weights` under the names below, stacked over layers.

Departures from the published description: none in the mathematics.
Rows are taken in blocks and the layer body is rematerialised so the
float32 activations fit beside the state; both leave the sums equal up
to float32 addition order.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.references.common import HIGHEST, MATMULS

H = "transformer/h/block/"


def param_shapes(cfg: dict) -> dict:
    L, E, V = cfg["n_layer"], cfg["n_embd"], cfg["vocab_size"]
    inner, f32 = cfg.get("n_inner") or 4 * E, jnp.float32
    return {
        H + "attn/c_attn/bias": ((L, 3 * E), f32),
        H + "attn/c_attn/kernel": ((L, E, 3 * E), f32),
        H + "attn/c_proj/bias": ((L, E), f32),
        H + "attn/c_proj/kernel": ((L, E, E), f32),
        H + "c_fc/bias": ((L, inner), f32),
        H + "c_fc/kernel": ((L, E, inner), f32),
        H + "c_proj/bias": ((L, E), f32),
        H + "c_proj/kernel": ((L, inner, E), f32),
        H + "ln_1/bias": ((L, E), f32), H + "ln_1/scale": ((L, E), f32),
        H + "ln_2/bias": ((L, E), f32), H + "ln_2/scale": ((L, E), f32),
        "transformer/ln_f/bias": ((E,), f32),
        "transformer/ln_f/scale": ((E,), f32),
        "transformer/wpe/embedding": ((cfg["n_positions"], E), f32),
        "transformer/wte/embedding": ((V, E), f32),
    }


def _ln(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _block(cfg, mm, x, lp):
    B, S, E = x.shape
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    h = _ln(x, lp["ln_1/scale"], lp["ln_1/bias"], eps)
    qkv = mm(h, lp["attn/c_attn/kernel"]) + lp["attn/c_attn/bias"]
    q, k, v = (t.reshape(B, S, heads, E // heads)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(E // heads).astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    x = x + mm(a.reshape(B, S, E), lp["attn/c_proj/kernel"]) \
        + lp["attn/c_proj/bias"]
    h = _ln(x, lp["ln_2/scale"], lp["ln_2/bias"], eps)
    h = jax.nn.gelu(mm(h, lp["c_fc/kernel"]) + lp["c_fc/bias"],
                    approximate=True)
    return x + mm(h, lp["c_proj/kernel"]) + lp["c_proj/bias"]


def summed_loss(cfg: dict, matmul: str, params: dict, ids):
    """Sum over the rows' shifted positions of -log p(next token)."""
    mm = MATMULS[matmul]
    S = ids.shape[1]
    wte = params["transformer/wte/embedding"]
    x = wte[ids] + params["transformer/wpe/embedding"][:S]
    stacked = {p[len(H):]: v for p, v in params.items() if p.startswith(H)}
    body = jax.checkpoint(partial(_block, cfg, mm))
    x, _ = jax.lax.scan(lambda x, lp: (body(x, lp), None), x, stacked)
    x = _ln(x, params["transformer/ln_f/scale"],
            params["transformer/ln_f/bias"], cfg["layer_norm_epsilon"])
    logits = mm(x[:, :-1], wte.T)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
    return -picked.sum()


def follow_steps(cfg: dict, matmul: str, params: dict, batches, opt: dict,
                 rows_per_block: int, place=None) -> dict:
    """Adam (Kingma & Ba, bias-corrected, no weight decay) over
    `batches`, a list of [B, S] int arrays. Returns each step's loss,
    the per-leaf norm of the first gradient and the per-leaf norm of
    the parameters' change over all steps. `place(tree_or_array,
    kind)` puts arrays on a mesh (kind "params" or "rows"); None
    leaves them on the default device."""
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    n_tok = batches[0].shape[0] * (batches[0].shape[1] - 1)
    # everything parameter-shaped is laid out as the parameters are (on
    # one chip: as is); left to the compiler, a zeros tree came back
    # whole on every chip
    like_params = None if place is None else jax.tree_util.tree_map(
        lambda a: a.sharding, params)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, ids: summed_loss(cfg, matmul, p, ids) / n_tok),
        out_shardings=(None, like_params))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0, out_shardings=like_params)

    @partial(jax.jit, donate_argnums=(0, 1, 2),
             out_shardings=(like_params,) * 3)
    def adam(p, m, v, g, t):
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        p = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t)) /
            (jnp.sqrt(v / (1 - b2 ** t)) + eps), p, m, v)
        return p, m, v

    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(x)))
                               for k, x in t.items()})
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    out_shardings=like_params)
    m = v = None
    losses, first = [], None
    for t, ids in enumerate(batches, start=1):
        loss, grads = 0.0, None
        for lo in range(0, ids.shape[0], rows_per_block):
            block = ids[lo:lo + rows_per_block]
            part, g = grad_fn(params, block if place is None
                              else place(block, "rows"))
            loss += float(part)
            grads = g if grads is None else add(grads, g)
            del g
        losses.append(loss)
        if first is None:
            first = {k: float(x) for k, x in norms(grads).items()}
        # the moments wait on the host while gradients are gathered:
        # parameters, the running sum and one block's gradient are 12
        # bytes a parameter, under the program's 16 and its activations
        if m is None:
            m, v = zeros(grads), zeros(grads)
        elif place is None:
            m, v = jax.device_put((m, v))
        else:
            m, v = place(m, "params"), place(v, "params")
        params, m, v = adam(params, m, v, grads, jnp.float32(t))
        del grads
        if t < len(batches):
            m, v = jax.device_get((m, v))
    return {"losses": losses, "grad_norm": first, "params": params}
