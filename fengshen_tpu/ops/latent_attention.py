"""Multi-head latent attention's FULL form over a window of queries
onto a lane of cached latent rows: the prefill-side twin of the
`decode_attention` seam's absorbed latent entry
(`ops/pallas/decode_attention.mla_decode_attention`).

A cached row is `[c (rank, after its norm) | k_shared (the key part all
heads share) | zeros]`; head `h`'s key is `[c W_k[h] | k_shared]` and
its value `c W_v[h]` (`w_kvb` `[rank, H, dn + dv]` holds both). The
absorbed form multiplies the query into the latent space instead and is
right for one query a lane; over a window of thousands of queries the
expansion is cheaper (a key costs `dn + dr` operations a query a head,
not `rank + dr`), and it is done a block of keys at a time, inside the
walk, so no `[T, H, dn + dv]` copy of a long lane is ever whole.

:func:`latent_prefill_attention` is the seam both models with a latent
layer call (`models/kimi_linear`: a window onto the carried cache;
`models/joyai`: the whole left-padded prompt): the call's shapes pick
the Mosaic kernel `ops/pallas/latent_attention.py`, which keeps the
score tile in VMEM, or :func:`latent_prefill_walk`, the same algorithm
in `jax.numpy` (the CPU tier-1 truth, a mesh's path).

:func:`latent_prefill_walk` takes the window's queries in tiles and
walks the lane's rows from 0 to a tile's last position in blocks of
`key_block` keys with an online softmax (a dynamic trip count: a window
early in a prompt reads little), so the largest score tensor is `[H,
q_tile, key_block]` float32 whatever the lane's length — never `[H, S,
T]` (9.7 GB for 32 heads x 2,048 queries x 36,864 rows). A key block is
expanded once a query tile, so the default tile is the whole window of
the serving path (2,048): more tiles repeat the expansion, `rank x H x
(dn + dv)` operations a key, for nothing.

Positions are physical: query `i` of a window that starts at `start`
sits at `start + i` and reads rows `0 .. start + i`, less those an
optional `[B, T]` key validity masks (a left-padded prompt's padding).
Nothing here rotates anything: a model with rotary parts hands them in
rotated.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from fengshen_tpu.ops.gated_attention import _online

#: the full form's device scope (the absorbed one is the seam's)
PREFILL_SCOPE = "fstpu_mla_prefill_attention"

#: queries a tile, keys a step of the walk
Q_TILE, KEY_BLOCK = 2048, 512

_NEG_INF = -1e30


class RawKernel(nn.Module):
    """A bias-free projection's `kernel`, handed out raw in `dtype`:
    both forms multiply by slices of the up-projection `w_kvb` instead
    of applying it."""

    shape: tuple
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    initializer_range: float = 0.02

    @nn.compact
    def __call__(self):
        return self.param(
            "kernel", nn.initializers.normal(self.initializer_range),
            self.shape, self.param_dtype).astype(self.dtype)


def latent_prefill_attention(q_nope, q_shared, rows, w_kvb, start, *,
                             key_valid: Optional[jax.Array] = None,
                             scale: float) -> jax.Array:
    """The full form's seam. q_nope: `[B, S, H, dn]`, q_shared: `[B, S,
    H, dr]` at positions `start + arange(S)`; rows: `[B, T, width]`, a
    lane's latent rows with the window's own at `start ..` (`width >=
    rank + dr`, the rest zeros); w_kvb: `[rank, H, dn + dv]`; `start`:
    int32 scalar, traced or not; `key_valid`: `[B, T]`, 0 on the rows no
    query may read (a left-padded prompt's padding), or None. Query `i`
    reads the valid rows among `0 .. start + i`; one with no valid row
    returns something finite that no one reads. Returns `[B, S, H, dv]`
    in q_nope's dtype. The call's shapes pick the path
    (`ops.pallas.latent_attention._ineligible_reason`): the Mosaic
    kernel where they tile on one chip, else
    :func:`latent_prefill_walk`, the CPU tier-1 truth and the mesh's
    path. Both run under `PREFILL_SCOPE`."""
    # here, not at the top: the registry imports this module's xla form
    from fengshen_tpu.ops.pallas import latent_attention as kernel
    from fengshen_tpu.ops.pallas import resolve_dispatch
    impl = resolve_dispatch(
        "mla_prefill_attention",
        f"q={tuple(q_nope.shape)}+{q_shared.shape[-1]}:{q_nope.dtype.name} "
        f"rows={tuple(rows.shape)}:{rows.dtype.name}" +
        ("" if key_valid is None else " key_valid"),
        kernel._ineligible_reason(q_nope, q_shared, rows, w_kvb))
    if impl == "pallas":
        return kernel.pallas_latent_prefill_attention(
            q_nope, q_shared, rows, w_kvb, start, key_valid=key_valid,
            scale=scale)
    return latent_prefill_walk(q_nope, q_shared, rows, w_kvb, start,
                               key_valid=key_valid, scale=scale)


def latent_prefill_walk(q_nope, q_shared, rows, w_kvb, start, *,
                        key_valid: Optional[jax.Array] = None,
                        scale: float, q_tile: int = Q_TILE,
                        key_block: int = KEY_BLOCK) -> jax.Array:
    """:func:`latent_prefill_attention` in `jax.numpy`, the same
    arguments and result: the kernel's xla twin."""
    batch, seq, heads, dn = q_nope.shape
    dr = q_shared.shape[-1]
    rank = w_kvb.shape[0]
    total = rows.shape[1]
    tq = math.gcd(seq, q_tile)
    kb = math.gcd(total, key_block)
    with jax.named_scope(PREFILL_SCOPE):
        tiles = lambda x: jnp.moveaxis(  # noqa: E731
            (x * scale).astype(x.dtype).reshape(
                batch, seq // tq, tq, heads, x.shape[-1]), 1, 0)

        def tile(args):
            qn, qs, first = args           # [B, tq, H, dn], [.., dr], []
            at = first + jnp.arange(tq)

            def step(j, carry):
                block = jax.lax.dynamic_slice_in_dim(rows, j * kb, kb,
                                                     axis=1)
                kv = jnp.einsum("btc,chd->bthd", block[..., :rank], w_kvb)
                s = jnp.einsum("bshd,bthd->bhst", qn, kv[..., :dn],
                               preferred_element_type=jnp.float32) + \
                    jnp.einsum("bshr,btr->bhst", qs,
                               block[..., rank:rank + dr],
                               preferred_element_type=jnp.float32)
                ok = ((j * kb + jnp.arange(kb))[None, :] <=
                      at[:, None])[None]                     # [1, tq, kb]
                if key_valid is not None:
                    ok = ok & jax.lax.dynamic_slice_in_dim(
                        key_valid.astype(bool), j * kb, kb, axis=1)[:, None]
                s = jnp.where(ok[:, None], s, _NEG_INF)
                return _online(carry, s, kv[..., dn:], "bhst,bthd->bhsd")

            steps = (first + tq + kb - 1) // kb
            _, l, acc = jax.lax.fori_loop(0, steps, step, (
                jnp.full((batch, heads, tq), _NEG_INF, jnp.float32),
                jnp.zeros((batch, heads, tq), jnp.float32),
                jnp.zeros((batch, heads, tq, w_kvb.shape[-1] - dn),
                          jnp.float32)))
            out = acc / jnp.maximum(l, 1e-30)[..., None]     # [B,H,tq,dv]
            return jnp.moveaxis(out, 1, 2).astype(q_nope.dtype)

        out = jax.lax.map(tile, (tiles(q_nope), tiles(q_shared),
                                 start + jnp.arange(seq // tq) * tq))
        return jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads, -1)
