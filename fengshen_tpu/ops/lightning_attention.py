"""Lightning linear attention (Qin et al., arXiv:2401.04658): a decayed
`[D, D]` state a head instead of rows a token.

Per head `i` with decay `lambda_i = exp(-slope_i)`:

    S_t = lambda_i * S_{t-1} + k_t^T v_t        (S_0 = 0)
    o_t = q_t S_t / sqrt(D)

Two forms of that one recurrence, both plain `jax.numpy` (the CPU
tier-1 truth; each under its own device scope so a trace finds it):

- :func:`lightning_decode` — the one-token step over a pool of lanes,
  gated by the tick's live mask: a recurrent state has no null block
  to park a dead lane's write on, so a lane that is not live keeps its
  state bit for bit;
- :func:`lightning_prefill` — the same mathematics over chunks of `c`
  tokens: inside a chunk `((Q K^T) * D) V` with `D_ab = lambda^(a-b)`
  for `a >= b`, across chunks the state, `O += Lambda (Q S_prev)` and
  `S_next = lambda^c S_prev + sum_b lambda^(c-1-b) k_b^T v_b`. Every
  matmul is batched over the chunks; only the `[H, D, D]` state update
  is sequential.

A masked token (padding) contributes `k = v = 0` AND no decay step:
the exponents count valid tokens, not positions, so padding on either
side leaves the state as if the token were not there.

The state is float32 and the matmuls that touch it run at `HIGHEST`
(on a TPU a float32 matmul is one bf16 pass unless asked): 25,000
decay-and-add steps carried in bf16 drift past any limit a reference
comparison can hold. They are 4 * D * D FLOP a token a head, a few
percent of the layer's projections.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

PREFILL_SCOPE = "fstpu_lightning_prefill"
DECODE_SCOPE = "fstpu_lightning_decode"

_HIGHEST = jax.lax.Precision.HIGHEST

#: tokens a chunk of the prefill form (the result does not depend on it)
DEFAULT_CHUNK = 256


def lightning_slopes(num_heads: int) -> np.ndarray:
    """`slope_i = 2^(-8 (i + 1) / H)`, Lightning Attention's per-head
    decay rates: `lambda_i = exp(-slope_i)`."""
    return (2.0 ** (-8.0 * (np.arange(num_heads) + 1) / num_heads)
            ).astype(np.float32)


def lightning_decode(q, k, v, state, slopes, live: Optional[jax.Array] = None):
    """One token a lane. q, k, v: `[B, H, D]`; state: `[B, H, D, D]`
    float32; `live`: `[B]` bool or None. Returns (`[B, H, D]` in q's
    dtype, the new state); where `live` is False the state is the one
    passed in, unchanged."""
    with jax.named_scope(DECODE_SCOPE):
        decay = jnp.exp(-jnp.asarray(slopes, jnp.float32))[None, :, None,
                                                            None]
        kv = k.astype(jnp.float32)[..., :, None] * \
            v.astype(jnp.float32)[..., None, :]
        new = decay * state + kv
        if live is not None:
            new = jnp.where(live[:, None, None, None], new, state)
        out = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), new,
                         precision=_HIGHEST) * (q.shape[-1] ** -0.5)
        return out.astype(q.dtype), new


def lightning_prefill(q, k, v, state, slopes,
                      mask: Optional[jax.Array] = None,
                      chunk: int = DEFAULT_CHUNK):
    """A window of tokens onto a state. q, k, v: `[B, S, H, D]`; state:
    `[B, H, D, D]` float32; `mask`: `[B, S]`, 0 on padding (either
    side), or None. Returns (`[B, S, H, D]` in q's dtype, the state
    after the window's valid tokens). A padded query's output is
    unspecified."""
    batch, seq, heads, dim = q.shape
    c = min(chunk, seq)
    pad = -seq % c
    if mask is None:
        mask = jnp.ones((batch, seq), bool)
    mask = mask.astype(bool)
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (seq + pad) // c
    with jax.named_scope(PREFILL_SCOPE):
        slope = jnp.asarray(slopes, jnp.float32)
        m = mask.reshape(batch, n, c)
        # valid tokens up to and including each position of its chunk
        cnt = jnp.cumsum(m, axis=-1).astype(jnp.float32)       # [B, n, c]
        tot = cnt[..., -1]                                     # [B, n]
        keep = m[..., None, None]
        qc = q.reshape(batch, n, c, heads, dim)
        kc = jnp.where(keep, k.reshape(batch, n, c, heads, dim), 0)
        vc = jnp.where(keep, v.reshape(batch, n, c, heads, dim), 0)

        # inside a chunk: ((Q K^T) * D) V, D_ab = lambda^(cnt_a - cnt_b)
        scores = jnp.einsum("bnahd,bnchd->bnhac", qc, kc,
                            preferred_element_type=jnp.float32)
        gap = cnt[:, :, None, :, None] - cnt[:, :, None, None, :]
        causal = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.where(causal,
                          jnp.exp(-slope[None, None, :, None, None] * gap),
                          0.0)                                 # [B,n,H,c,c]
        intra = jnp.einsum("bnhac,bnchd->bnahd",
                           (scores * decay).astype(v.dtype), vc,
                           preferred_element_type=jnp.float32)

        # each chunk's own contribution to the state, all at once
        k_dec = kc.astype(jnp.float32) * jnp.exp(
            -slope[None, None, None, :] *
            (tot[:, :, None] - cnt)[..., None])[..., None]     # [B,n,c,H,D]
        update = jnp.einsum("bnchd,bnche->bnhde", k_dec,
                            vc.astype(jnp.float32), precision=_HIGHEST)
        chunk_decay = jnp.exp(-slope[None, None, :] * tot[..., None])

        def carry(s, xs):
            u, d = xs
            return d[..., None, None] * s + u, s

        state, before = jax.lax.scan(
            carry, state.astype(jnp.float32),
            (jnp.moveaxis(update, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
        before = jnp.moveaxis(before, 0, 1)                    # [B,n,H,D,D]

        # across chunks: Lambda (Q S_prev), Lambda_a = lambda^cnt_a
        q_dec = qc.astype(jnp.float32) * jnp.exp(
            -slope[None, None, None, :] * cnt[..., None])[..., None]
        inter = jnp.einsum("bnahd,bnhde->bnahe", q_dec, before,
                           precision=_HIGHEST)
        out = (intra + inter) * (dim ** -0.5)
        out = out.reshape(batch, n * c, heads, dim)[:, :seq]
        return out.astype(q.dtype), state
