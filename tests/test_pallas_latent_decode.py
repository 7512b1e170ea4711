"""The latent entry of the decode-attention seam: one shared row a
token, key and value at once (docs/kernels.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fengshen_tpu.ops.pallas.decode_attention import (
    _mla_ineligible_reason, mla_decode_attention,
    pallas_mla_decode_attention, xla_mla_decode_attention)


#: per lane of a `_mla_case`, the (first, last) valid key of the FIRST
#: query on a table row of four 128-token blocks (None: a released
#: lane, no valid key, its row parked on the null block): one key, a
#: cursor on a block's last key and on the next block's first, a
#: left-padded lane, one whose whole first block is padding, the row's
#: last key
_MLA_LANES = ((0, 0), (0, 127), (0, 128), (5, 129), (130, 300), None,
              (0, 510), (256, 383))


def _mla_case(rng, stacked, dtype, window=1, n_heads=8, rank=128, rope=64,
              width=256):
    """q_latent, q_rope, pool, valid, table, layer for the seam's
    latent entry, and the lanes' rows in order for a plain reference.
    Every lane's four blocks lie scattered in a pool whose block 0 is
    the null block; `stacked` hands the pool as a `[3, ...]` stack read
    at layer 1; query `s` of a `window` sees one key more than query
    `s - 1`."""
    lanes, per, block = len(_MLA_LANES), 4, 128
    rows = rng.randn(lanes, per * block, width).astype(np.float32)
    rows[..., rank + rope:] = 0.
    order = rng.permutation(lanes * per) + 1
    table = order.reshape(lanes, per).astype(np.int32)
    layers = 3 if stacked else 1
    pool = rng.randn(layers, lanes * per + 1, block, 1,
                     width).astype(np.float32)
    pool[layers // 2, order] = rows.reshape(-1, block, 1, width)
    valid = np.zeros((lanes, window, per * block), bool)
    for b, span in enumerate(_MLA_LANES):
        if span is None:
            table[b] = 0
            continue
        for s in range(window):
            valid[b, s, span[0]:span[1] + 1 + s] = True
    q_latent = jnp.asarray(rng.randn(lanes, window, n_heads, rank), dtype)
    q_rope = jnp.asarray(rng.randn(lanes, window, n_heads, rope), dtype)
    return (q_latent, q_rope,
            jnp.asarray(pool if stacked else pool[0], dtype),
            jnp.asarray(valid), jnp.asarray(table),
            jnp.int32(1) if stacked else None, rows)


def _latent_softmax(q_latent, q_rope, rows, valid, scale):
    """Plain absorbed latent attention over each lane's rows, float32:
    every head scores `[c_kv | k_rope]` and weighs `c_kv`."""
    rank = q_latent.shape[-1]
    q = np.concatenate([np.asarray(q_latent, np.float32),
                        np.asarray(q_rope, np.float32)], -1)
    s = np.einsum("bshd,btd->bsht", q, rows[..., :q.shape[-1]]) * scale
    s = np.where(valid[:, :, None], s, -np.inf)
    with np.errstate(invalid="ignore"):     # a released lane: no key
        p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bsht,btc->bshc", p, rows[..., :rank])


def _mla_kernel(q_latent, q_rope, pool, valid, table, layer, per):
    """The kernel in interpret mode: through the seam, or (`per`) with
    that many blocks a step."""
    if per is None:
        return mla_decode_attention(q_latent, q_rope, pool, valid,
                                    scale=0.1, block_table=table,
                                    layer=layer, impl="pallas",
                                    interpret=True)
    return pallas_mla_decode_attention(q_latent, q_rope, pool, valid,
                                       scale=0.1, block_table=table,
                                       layer=layer, blocks_per_step=per,
                                       interpret=True)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("stacked", [False, True], ids=["pool", "stack"])
@pytest.mark.parametrize("per, window", [
    (None, 1), (1, 1), (2, 1), (3, 1), (None, 3), (3, 3)],
    ids=["row", "1", "2", "3", "row_window", "3_window"])
def test_mla_decode_kernel_interpret_parity(per, window, stacked, dtype):
    """The latent kernel (interpret mode) against
    `xla_mla_decode_attention` and against plain latent attention:
    ragged cursors in one call (`_MLA_LANES`), a cursor on a block's
    edge, left-padded lanes (holes at the front of `valid`, a whole
    block of them), a released lane on the null block, the pool as it
    is and as a stack read in place through a TRACED `layer`, blocks
    scattered; the whole 4-block row a step (the seam's 8 blocks, cut
    to the row), a block a step, two, and 3, which leaves a lane's last
    step one live block of three; one query a lane and a verify window
    of three. In float32 the two differ by the online softmax's
    partition; in bfloat16 they round the same operands the same
    way."""
    rng = np.random.RandomState(430 + 2 * stacked + (dtype == "float32"))
    q_latent, q_rope, pool, valid, table, layer, rows = _mla_case(
        rng, stacked, jnp.dtype(dtype), window)
    assert _mla_ineligible_reason(q_latent, pool, table) is None
    got = jax.jit(_mla_kernel, static_argnums=6)(
        q_latent, q_rope, pool, valid, table, layer, per)
    want = xla_mla_decode_attention(q_latent, q_rope, pool, valid,
                                    scale=0.1, block_table=table,
                                    layer=layer)
    assert got.shape == q_latent.shape and got.dtype == q_latent.dtype
    tol = 2e-5 if dtype == "float32" else 2e-2
    held = np.array([span is not None for span in _MLA_LANES])
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[held], np.asarray(want, np.float32)[held],
                               rtol=tol, atol=tol)
    if dtype == "bfloat16":
        rows = np.asarray(jnp.asarray(rows, jnp.bfloat16), np.float32)
    plain = _latent_softmax(q_latent, q_rope, rows, np.asarray(valid), 0.1)
    np.testing.assert_allclose(got[held], plain[held], rtol=tol, atol=tol)


@pytest.mark.parametrize("per", [None, 1, 3], ids=["row", "1", "3"])
def test_mla_decode_kernel_never_reads_past_a_lanes_cursor(per):
    """That the walk ends at each lane's OWN last block, inside a step
    too: with NaN in the null block and in every block past a lane's
    cursor, the lanes that hold a request return what they return over
    a clean pool, bit for bit (the xla lowering gathers every entry of
    every row and multiplies a zero probability by that NaN)."""
    rng = np.random.RandomState(440)
    q_latent, q_rope, pool, valid, table, _, _ = _mla_case(
        rng, False, jnp.float32)
    clean = _mla_kernel(q_latent, q_rope, pool, valid, table, None, per)
    held = np.array([span is not None for span in _MLA_LANES])
    reached = {int(block) for row, span in zip(np.asarray(table), _MLA_LANES)
               if span is not None for block in row[:span[1] // 128 + 1]}
    dead = [i for i in range(pool.shape[0]) if i not in reached]
    assert 0 in dead and len(dead) > len(_MLA_LANES)
    pool = pool.at[jnp.asarray(dead)].set(jnp.nan)
    out = np.asarray(_mla_kernel(q_latent, q_rope, pool, valid, table,
                                 None, per))
    assert np.isfinite(out[held]).all()
    np.testing.assert_array_equal(out[held], np.asarray(clean)[held])
    lost = np.asarray(mla_decode_attention(
        q_latent, q_rope, pool, valid, scale=0.1, block_table=table,
        impl="xla"))
    assert np.isnan(lost[held]).any()


@pytest.mark.parametrize("q_shape, kv_shape, paged, why", [
    ((2, 1, 32, 512), (2, 512, 1, 640), False, "slot cache"),
    ((2, 1, 32, 512), (3, 2, 512, 1, 640), False, "slot cache"),
    ((2, 1, 32, 512), (9, 128, 1, 576), True, "row width 576 % 128"),
    ((2, 9, 32, 512), (9, 128, 1, 640), True, "query window 9 > 8"),
    ((2, 1, 4, 32), (9, 128, 1, 128), True, "rank 32 % 128"),
    ((2, 1, 32, 512), (9, 16, 1, 640), True, "block_size 16 % 128"),
    ((2, 1, 4, 128), (9, 128, 1, 256), True, None),
    ((64, 1, 32, 512), (5, 1537, 128, 1, 640), True, None),
    ((2, 8, 32, 512), (9, 128, 1, 640), True, None),
], ids=["slot", "slot_stack", "published_row", "long_window", "tiny_rank",
        "tiny_block", "four_heads", "cell_stack", "verify_window"])
def test_mla_dispatch_follows_the_caches_shape(fresh_probe, monkeypatch,
                                               q_shape, kv_shape, paged,
                                               why):
    """The latent entry chooses its path from the cache's shape through
    `resolve_dispatch`: on a backend that runs Mosaic the cell's paged
    stack of 640-wide rows takes the kernel (a verify window up to 8
    too); a slot cache, the published 576-wide row, a window of 9 and
    the tiny shapes of the CPU tests take the xla lowering, each with
    its reason on record by name."""
    import fengshen_tpu.ops.pallas as kernels
    monkeypatch.setitem(kernels._PROBE_CACHE, ("cpu", None),
                        kernels.KernelProbe("tpu", True, None, "test"))
    monkeypatch.setattr(kernels, "_TRACED", {})
    lanes, window = q_shape[:2]
    stacked = len(kv_shape) == 5
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    q_rope = jax.ShapeDtypeStruct(q_shape[:3] + (64,), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16)
    table = jax.ShapeDtypeStruct((lanes, 4), jnp.int32) if paged else None
    lane_len = 4 * kv_shape[-3] if paged else kv_shape[-3]
    reason = _mla_ineligible_reason(q, kv, table)
    assert (reason is None) if why is None else (why in reason), reason
    out = jax.eval_shape(
        lambda q, q_rope, kv, valid, table: mla_decode_attention(
            q, q_rope, kv, valid, scale=1.0, block_table=table,
            layer=jnp.int32(1) if stacked else None),
        q, q_rope, kv,
        jax.ShapeDtypeStruct((lanes, window, lane_len), jnp.bool_), table)
    assert out.shape == q_shape and out.dtype == jnp.bfloat16
    took, = kernels.traced_dispatch()
    assert took["op"] == "mla_decode_attention"
    assert took["impl"] == ("pallas" if why is None else "xla")
    assert ("paged" if paged else "slot") in took["detail"]
    assert (why is None) or (why in took["detail"])


def test_the_latent_kernel_refuses_a_slot_cache_by_name():
    q = jnp.zeros((2, 1, 8, 128))
    with pytest.raises(ValueError, match="walks a block table"):
        mla_decode_attention(q, q[..., :64], jnp.zeros((2, 128, 1, 256)),
                             jnp.ones((2, 1, 128), bool), scale=1.0,
                             impl="pallas", interpret=True)
