"""The gated delta rule (Gated DeltaNet, Yang et al. arXiv:2412.06464)
and the short causal convolution that feeds it: a `[Dk, Dv]` state a
head that forgets by a data-dependent gate and, before it writes a
key's value, subtracts what it already predicts for that key.

Per head, with `g_t <= 0` the token's log-decay and `beta_t` in (0, 1)
its write strength (`S_0 = 0`):

    S' = exp(g_t) S_{t-1}
    d_t = beta_t (v_t - k_t S')
    S_t = S' + k_t^T d_t
    o_t = q_t S_t

One recurrence, two gate shapes, told apart by `g`'s rank: ONE scalar a
head a token (Gated DeltaNet: `g` `[..., H]`), or one value a KEY
CHANNEL (Kimi Delta Attention, arXiv:2510.26692: `g` `[..., H, Dk]`,
`S' = Diag(exp(g_t)) S_{t-1}`, row `d` of the state decays by its own
`exp(g_t[d])`). A per-channel gate that is constant over a head's
channels is the scalar one.

Two forms of that one recurrence, in plain `jax.numpy` (the CPU
tier-1 truth; each under its own device scope so a trace finds it):

- :func:`gated_delta_decode` — the one-token step over a pool of lanes,
  gated by the tick's live mask: a state has no null block to park a
  dead lane's write on, so a lane that is not live keeps its state bit
  for bit. The two products with the state (`k S'`, `q S_t`) are a
  multiply and a sum over the key axis in float32: exact, and fused
  into the passes over the state that the update makes anyway;
- :func:`gated_delta_prefill` — the same mathematics over chunks of `c`
  tokens (the WY / UT transform). Inside a chunk with cumulative
  log-decays `G` of a scalar gate: `A = -strict_tril((beta K) K^T *
  exp(G_i - G_j))`,
  `T = (I - A)^-1` (a unit lower triangular solve: forward
  substitution), `W = T (beta K exp(G))`, `U = T (beta V)`; across
  chunks, in order, `V_new = U - W S`, `O = (Q exp(G)) S + tril(Q K^T
  exp(G_i - G_j)) V_new`, `S <- exp(G_last) S + (K exp(G_last - G))^T
  V_new`. The result does not depend on `c`. It is a seam of
  `ops/pallas`: a window whose shape the Mosaic chunk kernel tiles
  (`ops/pallas/gated_delta.py`: the state in VMEM over a head's chunks,
  the inverse by products) takes it on a TPU, chosen at trace time from
  the operands' shape with the reason on record; any other shape, and
  every backend that is not a TPU, takes
  :func:`xla_gated_delta_prefill`, the `jax.numpy` form: everything
  that does not read the state batched over the chunks, a scan that
  carries the `[H, Dk, Dv]` state and four products a chunk.

  Under a per-channel gate the decay between tokens `i >= j` of a chunk
  is `exp(G_i - G_j)` a channel, no longer a `[c, c]` mask on `K K^T`:
  `A = -strict_tril((beta K exp(G)) (K exp(-G))^T)`, `W = T (beta K
  exp(G))`, `U = T (beta V)`, `O = (Q exp(G)) S + tril((Q exp(G)) (K
  exp(-G))^T) V_new`, `S <- Diag(exp(G_last)) S + (K exp(G_last -
  G))^T V_new`. `exp(-G_j)` overflows float32 once a channel has
  decayed by e^88 inside a chunk, so the two `[c, c]` products are
  never formed that way (:func:`_channel_decayed_products`): the chunk
  is cut into sub-blocks of `SUB_BLOCK` rows; a sub-block below the
  diagonal refers both factors to ITS first row `r` (`exp(G_i - G_r)`
  and `exp(G_r - G_j)`, `j < r <= i`: both exponents <= 0), a diagonal
  sub-block takes the explicit `[16, 16, Dk]` differences (`i >= j`:
  <= 0 again). No exponent is ever positive; a factor that underflows
  bounds a product that is itself under 1e-38. The Mosaic chunk kernel
  has a second body for this gate, taken by the gate's rank where the
  window tiles (one value head a key head): the same anchoring along
  its merge tree, the diagonal sub-blocks along their diagonals, `T` by
  the same block products, no triangular solve and no scan left in the
  window program.

A masked token (padding) has `beta = 0`, `g = 0`, `k = v = 0`: it
neither writes nor decays, so padding on either side leaves the state
as if the token were not there.

The state is float32 and every matmul that touches it, or the
triangular solve, runs at `HIGHEST` (on a TPU a float32 matmul is one
bf16 pass unless asked; `ops/lightning_attention.py` argues the same):
a delta rule feeds its own prediction error back into the state, so a
rounded `k S` is written into every later token. They are ~13 MFLOP a
chunk a head, a few percent of the layer's projections.

:func:`short_conv_prefill` / :func:`short_conv_decode`: the depthwise
causal convolution of kernel `K` over the channels of `[q | k | v]`,
`y_t = sum_j c_j u_{t-K+1+j}` with zeros before the sequence, then
SiLU. Its state is the last `K - 1` INPUTS `u` of a lane, `[B, K - 1,
C]` (time before channels: the channels are the lanes of a TPU tile; a
`[C, K - 1]` state, as the published code keeps it, pads 3 to 128).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

PREFILL_SCOPE = "fstpu_gated_delta_prefill"
DECODE_SCOPE = "fstpu_gated_delta_decode"
CONV_SCOPE = "fstpu_short_conv"

_HIGHEST = jax.lax.Precision.HIGHEST

#: tokens a chunk of the prefill form (the result does not depend on it)
DEFAULT_CHUNK = 64
#: rows a sub-block of a chunk under a per-channel gate (module docstring)
SUB_BLOCK = 16


def a_log_init(key, shape, dtype):
    """`A_log`'s published initialisation: `log(A)`, A uniform in (0, 16)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def l2norm(x, eps: float = 1e-6):
    """`x / sqrt(sum x^2 + eps)` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_decode(q, k, v, g, beta, state,
                       live: Optional[jax.Array] = None):
    """One token a lane. q, k: `[B, H, Dk]`; v: `[B, H, Dv]`; beta:
    `[B, H]` float32; g: `[B, H]` (one scalar a head) or `[B, H, Dk]`
    (one value a key channel: a broadcast along the state's rows);
    state: `[B, H, Dk, Dv]` float32; `live`: `[B]` bool or None. Returns
    (`[B, H, Dv]` in v's dtype, the new state); where `live` is False
    the state is the one passed in, unchanged."""
    with jax.named_scope(DECODE_SCOPE):
        qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
        decay = jnp.exp(g.astype(jnp.float32))
        decayed = (decay[..., None, None] if g.ndim == 2
                   else decay[..., None]) * state
        predicted = (kf[..., :, None] * decayed).sum(axis=-2)   # [B,H,Dv]
        delta = beta.astype(jnp.float32)[..., None] * (vf - predicted)
        new = decayed + kf[..., :, None] * delta[..., None, :]
        if live is not None:
            new = jnp.where(live[:, None, None, None], new, state)
        out = (qf[..., :, None] * new).sum(axis=-2)
        return out.astype(v.dtype), new


def gated_delta_prefill(q, k, v, g, beta, state,
                        mask: Optional[jax.Array] = None,
                        chunk: int = DEFAULT_CHUNK):
    """A window of tokens onto a state. q, k: `[B, S, Hk, Dk]`; v: `[B,
    S, H, Dv]`, `Hk` dividing `H` (value head `h` reads key head `h //
    (H // Hk)`, as `jnp.repeat` lays them); beta: `[B, S, H]`; g: `[B, S,
    H]` or, gated per key channel, `[B, S, H, Dk]`; state: `[B, H, Dk,
    Dv]` float32; `mask`: `[B, S]`, 0 on padding (either side), or None.
    Returns (`[B, S, H, Dv]` in v's dtype, the state
    after the window's valid tokens). A padded query's output is
    unspecified. The window's shape picks the path
    (`ops.pallas.gated_delta._ineligible_reason`): the Mosaic chunk
    kernel where it tiles (the scalar gate's body or the per-channel
    gate's, by `g`'s rank), else :func:`xla_gated_delta_prefill` in
    chunks of `chunk` (the kernel's chunk is its module's constant; the
    result depends on neither)."""
    # here, not at the top: the registry imports this module's xla form
    from fengshen_tpu.ops.pallas import gated_delta as kernel
    from fengshen_tpu.ops.pallas import resolve_dispatch
    impl = resolve_dispatch(
        "gated_delta_prefill",
        f"q={tuple(q.shape)}:{q.dtype.name} v={tuple(v.shape)}:"
        f"{v.dtype.name}" + (f" g={tuple(g.shape)}" if g.ndim == 4 else ""),
        kernel._ineligible_reason(q, v, g))
    if impl == "pallas":
        return kernel.pallas_gated_delta_prefill(q, k, v, g, beta, state,
                                                 mask)
    return xla_gated_delta_prefill(q, k, v, g, beta, state, mask, chunk)


def xla_gated_delta_prefill(q, k, v, g, beta, state,
                            mask: Optional[jax.Array] = None,
                            chunk: int = DEFAULT_CHUNK):
    """:func:`gated_delta_prefill` in `jax.numpy`: the CPU tier-1 truth
    and the kernel's xla twin, the same arguments and results."""
    batch, seq, heads, dv = v.shape
    dk = q.shape[-1]
    if q.shape[2] != heads:
        q, k = (jnp.repeat(x, heads // x.shape[2], axis=2) for x in (q, k))
    c = min(chunk, seq)
    pad = -seq % c
    if mask is None:
        mask = jnp.ones((batch, seq), bool)
    mask = mask.astype(bool)
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                   for x in (g, beta))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (seq + pad) // c
    with jax.named_scope(PREFILL_SCOPE):
        keep = mask[..., None]

        def chunks(x):
            # [B, S, H, ...] -> [B, n, H, c, ...], float32, padding zeroed
            x = jnp.where(keep.reshape(keep.shape + (1,) * (x.ndim - 3)),
                          x.astype(jnp.float32), 0.0)
            x = x.reshape((batch, n, c) + x.shape[2:])
            return jnp.moveaxis(x, 2, 3)

        qc, kc, vc = chunks(q), chunks(k), chunks(v)       # [B,n,H,c,D]
        bc, gc = chunks(beta), chunks(g)       # [B,n,H,c]; gc [.., c(, Dk)]
        if g.ndim == 4:
            W, U, q_dec, qk, k_dec, chunk_decay = _channel_gated_chunks(
                qc, kc, vc, bc, gc)
        else:
            G = jnp.cumsum(gc, axis=-1)
            # exp(G_i - G_j) for i >= j: every exponent <= 0
            lower = jnp.tril(jnp.ones((c, c), bool))
            decay = jnp.where(lower, jnp.exp(jnp.where(
                lower, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
            k_beta = kc * bc[..., None]
            eye = jnp.eye(c, dtype=jnp.float32)
            A = -jnp.einsum("bnhid,bnhjd->bnhij", k_beta, kc,
                            precision=_HIGHEST) * (decay * (1.0 - eye))
            # T (I - A) = I, forward substitution on a unit lower triangle
            rhs = jnp.concatenate(
                [k_beta * jnp.exp(G)[..., None], vc * bc[..., None]],
                axis=-1)
            solved = solve_triangular(eye - A, rhs, lower=True,
                                      unit_diagonal=True)
            W, U = solved[..., :dk], solved[..., dk:]      # [B,n,H,c,D]
            qk = jnp.einsum("bnhid,bnhjd->bnhij", qc, kc,
                            precision=_HIGHEST) * decay
            q_dec = qc * jnp.exp(G)[..., None]
            G_last = G[..., -1:]                           # [B,n,H,1]
            k_dec = kc * jnp.exp(G_last - G)[..., None]
            chunk_decay = jnp.exp(G_last)[..., None]       # [B,n,H,1,1]

        def carry(s, xs):
            w, u, qd, a, kd, dec = xs
            v_new = u - jnp.einsum("bhck,bhkv->bhcv", w, s,
                                   precision=_HIGHEST)
            out = jnp.einsum("bhck,bhkv->bhcv", qd, s, precision=_HIGHEST) \
                + jnp.einsum("bhij,bhjv->bhiv", a, v_new,
                             precision=_HIGHEST)
            s = dec * s + jnp.einsum("bhck,bhcv->bhkv", kd, v_new,
                                     precision=_HIGHEST)
            return s, out

        state, out = jax.lax.scan(
            carry, state.astype(jnp.float32),
            tuple(jnp.moveaxis(x, 1, 0)
                  for x in (W, U, q_dec, qk, k_dec, chunk_decay)))
        # [n, B, H, c, Dv] -> [B, S, H, Dv]
        out = jnp.moveaxis(out, (0, 3), (1, 2)).reshape(
            batch, n * c, heads, dv)[:, :seq]
        return out.astype(v.dtype), state


def _channel_decayed_products(rows, keys, G, sub: int = SUB_BLOCK):
    """`M[i, j] = sum_d r_i[d] k_j[d] exp(G_i[d] - G_j[d])` for `i >= j`
    (0 above the diagonal), for each `r` of `rows`: the two `[c, c]`
    products of a chunk under a per-channel gate, with no exponent ever
    positive (module docstring). rows: arrays `[..., c, Dk]`; keys, G:
    `[..., c, Dk]`, `G` the chunk's cumulative log-decays (never
    rising along `c`). Returns a tuple of `[..., c, c]` float32."""
    c, dk = G.shape[-2:]
    sub = math.gcd(c, sub)
    nb = c // sub
    lead = G.shape[:-2]
    Gs = G.reshape(lead + (nb, sub, dk))
    anchor = Gs[..., 0, :]                                  # [..., nb, Dk]
    # below the diagonal: sub-block I's rows and every earlier key, both
    # referred to I's first row (the clamp only meets keys the mask drops)
    to_anchor = jnp.exp(Gs - anchor[..., None, :])          # [..,nb,sub,Dk]
    keys_at = keys[..., None, :, :] * jnp.exp(jnp.minimum(
        anchor[..., :, None, :] - G[..., None, :, :], 0.0))  # [..,nb,c,Dk]
    # the diagonal sub-blocks: explicit differences, i >= j
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    within = jnp.exp(jnp.where(
        tri[..., None], Gs[..., :, None, :] - Gs[..., None, :, :], 0.0))
    ks = keys.reshape(lead + (nb, sub, dk))
    blocks = jnp.arange(nb)
    below = (blocks[:, None] > blocks[None, :])[:, None, :, None]
    on = (blocks[:, None] == blocks[None, :])[:, None, :, None]
    out = []
    for r in rows:
        rs = r.reshape(lead + (nb, sub, dk))
        off = jnp.einsum("...id,...jd->...ij", rs * to_anchor, keys_at,
                         precision=_HIGHEST)                # [..,nb,sub,c]
        off = off.reshape(lead + (nb, sub, nb, sub))
        diag = jnp.where(tri, (rs[..., :, None, :] * ks[..., None, :, :] *
                               within).sum(-1), 0.0)        # [..,nb,sub,sub]
        full = jnp.where(on, diag[..., :, :, None, :],
                         jnp.where(below, off, 0.0))
        out.append(full.reshape(lead + (c, c)))
    return tuple(out)


def _channel_gated_chunks(qc, kc, vc, bc, gc):
    """What the scan over chunks reads, under a per-channel gate: (W, U,
    Q exp(G), the `[c, c]` read of a chunk's own tokens, K exp(G_last -
    G), a chunk's decay a state row `[.., Dk, 1]`). qc, kc, gc: `[B, n,
    H, c, Dk]`; vc: `[B, n, H, c, Dv]`; bc: `[B, n, H, c]`."""
    c, dk = gc.shape[-2:]
    G = jnp.cumsum(gc, axis=-2)
    k_beta = kc * bc[..., None]
    eye = jnp.eye(c, dtype=jnp.float32)
    A, qk = _channel_decayed_products((k_beta, qc), kc, G)
    rhs = jnp.concatenate([k_beta * jnp.exp(G), vc * bc[..., None]],
                          axis=-1)
    solved = solve_triangular(eye + A * (1.0 - eye), rhs, lower=True,
                              unit_diagonal=True)
    G_last = G[..., -1:, :]                                # [B,n,H,1,Dk]
    return (solved[..., :dk], solved[..., dk:], qc * jnp.exp(G), qk,
            kc * jnp.exp(G_last - G),
            jnp.swapaxes(jnp.exp(G_last), -1, -2))


def _conv(window, weight):
    """`y_t = sum_j c_j u_{t+j}` over a `[B, S + K - 1, C]` window of
    inputs; weight `[K, C]`. Float32, then SiLU."""
    taps = weight.shape[0]
    seq = window.shape[1] - taps + 1
    w = weight.astype(jnp.float32)
    y = sum(w[j] * window[:, j:j + seq].astype(jnp.float32)
            for j in range(taps))
    return jax.nn.silu(y)


def short_conv_prefill(u, weight, state, n_valid=None):
    """A window of inputs `u` `[B, S, C]` after a lane's last `K - 1`
    inputs `state` `[B, K - 1, C]` (zeros at the start of a sequence);
    weight `[K, C]`. `n_valid` (`[B]` int, or None: all of them) counts
    the real tokens of a window padded on the RIGHT. Returns (`[B, S,
    C]` in u's dtype, the last `K - 1` real inputs)."""
    with jax.named_scope(CONV_SCOPE):
        taps = weight.shape[0]
        window = jnp.concatenate([state.astype(u.dtype), u], axis=1)
        y = _conv(window, weight).astype(u.dtype)
        if n_valid is None:
            new = window[:, window.shape[1] - (taps - 1):]
        else:
            # input t of the window is row t + K - 1: the last K - 1
            # real ones start at row n_valid, earlier state included
            new = jax.vmap(lambda w, at: jax.lax.dynamic_slice_in_dim(
                w, at, taps - 1, axis=0))(window, n_valid)
        return y, new.astype(state.dtype)


def short_conv_decode(u, weight, state, live: Optional[jax.Array] = None):
    """One input a lane. u: `[B, C]`; state: `[B, K - 1, C]`. Returns
    (`[B, C]` in u's dtype, the state shifted by the input); where
    `live` is False the state is the one passed in."""
    with jax.named_scope(CONV_SCOPE):
        window = jnp.concatenate([state.astype(u.dtype), u[:, None]], axis=1)
        y = _conv(window, weight)[:, 0].astype(u.dtype)
        new = window[:, 1:].astype(state.dtype)
        if live is not None:
            new = jnp.where(live[:, None, None], new, state)
        return y, new
